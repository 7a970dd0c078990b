#!/usr/bin/env bash
# CI entry point — the analogue of the reference's .travis.yml script
# section: run the full test suite, then smoke-run two examples under
# the launcher at np=2 (the reference runs tensorflow_mnist.py and a
# shrunk keras_mnist_advanced.py under `mpirun -np 2`).
set -euxo pipefail
cd "$(dirname "$0")"

JAX_PLATFORMS=cpu python -m pytest tests/ -q

# hvdlint gate (docs/analysis.md): the JAX-aware static analyzer must
# be clean against the committed baseline — which this repo ships
# EMPTY, so ANY finding (a host sync sneaking into the @hot_path tick
# ring, trace-unsafe control flow, an unregistered env knob, ...)
# fails CI here. The gate's failure mode is proven by
# tests/test_analysis.py::TestCIGate with a deliberately-violating
# temp file, so CI itself stays green-on-clean.
JAX_PLATFORMS=cpu python -m horovod_tpu.analysis \
    --baseline .hvdlint-baseline.json

# Runtime lock witness (docs/analysis.md "The runtime witness"): the
# dynamic half of HVD007. Re-run the lock-heaviest suites (serving
# engine/router, resilience, elastic membership) with every registered
# lock ARMED (HVD_LOCK_CHECK=1) — each acquisition feeds the witness's
# order graph. The dump must show ZERO observed order inversions (an
# inversion here is a deadlock the suite actually walked), and
# tests/test_lockcheck.py separately pins that observed edges are a
# subset of the static lock_order_graph.
rm -f /tmp/hvd_lock_witness.json
HVD_LOCK_CHECK=1 HVD_LOCK_CHECK_OUT=/tmp/hvd_lock_witness.json \
    JAX_PLATFORMS=cpu python -m pytest -q \
    tests/test_serving.py tests/test_router.py \
    tests/test_resilience.py tests/test_membership.py
python - <<'EOF'
import json
snap = json.load(open("/tmp/hvd_lock_witness.json"))
assert snap["inversions"] == [], (
    "lock witness observed order inversions:\n"
    + json.dumps(snap["inversions"], indent=2))
print(f"lock witness: {sum(len(v) for v in snap['edges'].values())} "
      f"edge(s), 0 inversions")
EOF

# Compat matrix (the reference sweeps {py27/34/36} x {TF 1.1/1.4/
# nightly} x {OpenMPI,MPICH} in .travis.yml; this image pins ONE real
# generation — TF 2.21 / Keras 3 — so the other Keras generations'
# optimizer surfaces are driven explicitly by stub optimizers of each
# generation's API). One leg per interception path
# (horovod/keras/__init__.py): Keras-3 apply_gradients via the real
# optimizer (test_fit_decreases_loss), Keras-2 get_gradients and
# TF2-legacy _compute_gradients via the generation stubs. The tests
# run in the full suite above; this collect-only step is the named
# guard that each generation leg still exists (a rename/removal fails
# CI here even if the suite still passes).
JAX_PLATFORMS=cpu python -m pytest -q --collect-only \
    "tests/test_tf_compat.py::TestKeras::test_fit_decreases_loss" \
    "tests/test_tf_compat.py::TestCompatRegressions::test_keras2_get_gradients_path_averages" \
    "tests/test_tf_compat.py::TestCompatRegressions::test_tf2_legacy_compute_gradients_path_averages" \
    > /dev/null

# Serving-engine smoke: 4 concurrent requests through the continuous-
# batching engine on CPU; asserts completion AND token-exactness vs
# sequential generate (the engine's oracle contract), PLUS the PR-3
# hot-path guarantees: --warmup pins that program warmup happened (no
# XLA compile inside the timed serving window, compiles == 0) and
# --interleave-check pins that TPOT under a concurrent long-prompt
# admission stays within 2x the idle-pool TPOT (interleaved chunked
# prefill; bound loose enough for CPU CI). --obs-check is the
# observability smoke (docs/observability.md): the metrics exporter
# comes up on an EPHEMERAL port, /metrics is fetched over real HTTP
# and must expose the serving + resilience + training metric families
# from the shared registry in ONE scrape, and /healthz must show the
# live engine's dispatch generation. --prefix-check is the paged-KV
# smoke (PR 7, docs/serving.md "Paged KV cache"): two requests sharing
# a 48-token system prompt through a PAGED engine — the second must
# report prefill-tokens-skipped > 0 (prefix served from resident
# blocks) and TTFT strictly below the cold request's, both token-exact
# vs sequential generate. --spec-check is the decode-fast-path smoke
# (PR 13, docs/serving.md "Decode fast path"): a speculative
# (self-draft) engine's greedy streams must be BITWISE the plain
# engine's with >= 1 multi-token round observed.
JAX_PLATFORMS=cpu python examples/transformer_serving.py --requests 4 \
    --warmup --interleave-check --obs-check --prefix-check --spec-check

# Overload-control smoke (PR 17, docs/serving.md "Overload control"):
# two tenants (HVD_TENANT_WEIGHTS-style weighted lanes) against a TINY
# paged pool — a low-priority "free" flood saturates it, then a
# priority-5 "paid" request must be admitted by token-exact PREEMPTION
# (bounded TTFT, not parked behind the flood). Two phases pin both
# resume modes: >= 1 swap preemption (KV blocks shelved in host RAM
# and re-grafted on resume) and >= 1 recompute preemption
# (swap_bytes=0: forced-prefix re-prefill). Every stream must be
# bitwise the unpressured run's and no flood request may starve (the
# WFQ aging guarantee). Knobs: HVD_PREEMPT, HVD_SWAP_BYTES,
# HVD_TENANT_WEIGHTS, HVD_BROWNOUT (runtime/config.py registry).
JAX_PLATFORMS=cpu python examples/transformer_serving.py --requests 2 \
    --preempt-check

# Fleet-observability smoke (docs/observability.md "Fleet view" /
# "Flight recorder"): on a 2-engine host, one /fleet scrape must show
# the fleet-merged hvd_fleet_* histograms (both engines' requests
# pooled) and hvd_rank_skew_* gauges; then the env-armed chaos fault
# (serving_dispatch_crash, deferred by the example until a request is
# in flight) must be healed by the watchdog AND leave a
# flight-recorder bundle in HVD_FLIGHT_DIR whose pretty-printer
# output names the ring's newest event and the crashed request's
# trace_id — the end-to-end post-mortem proof. The module CLI is then
# exercised on the bundle directly. hvdlint above already proves the
# new obs modules (aggregate/straggler/flightrec/slo) sit on the
# EMPTY baseline.
rm -rf /tmp/hvd_fleet_smoke
HVD_CHAOS=serving_dispatch_crash:1 HVD_FLIGHT_DIR=/tmp/hvd_fleet_smoke \
    JAX_PLATFORMS=cpu python examples/transformer_serving.py \
    --requests 2 --fleet-check
JAX_PLATFORMS=cpu python -m horovod_tpu.obs.flightrec \
    "$(ls /tmp/hvd_fleet_smoke/flight_*.json | tail -1)" \
    | grep -q "trace_id="

# Request-tracing smoke (PR 20, docs/observability.md "Request
# tracing" / "Record/replay"): under a scoped SpanRecorder one
# request's causal span tree must decompose into the FULL serving
# anatomy — the printed waterfall shows the queue_wait/admission/
# prefill/decode phase tags and the phase anatomy sums to within 5%
# of the client-observed latency (no unattributed wall-clock).
# Then 8 client arrivals are recorded to an obs.reqlog JSONL,
# prompt-synthesized back from their digests, and re-served on a
# fresh engine: request count and every per-request token count must
# round-trip exactly — the record->replay guarantee. Knobs: HVD_TRACE_LOG,
# HVD_TRACE_SAMPLE, HVD_REQLOG (runtime/config.py registry).
JAX_PLATFORMS=cpu python examples/transformer_serving.py --requests 2 \
    --trace-check

# Serving-fleet failover smoke (docs/serving.md "Fleet failover"):
# three in-process ServingEngine replicas behind a ServingRouter; the
# router.replica_kill chaos site hard-kills the busiest replica while
# streams are mid-decode. All requests must complete, migrated
# streams must be BITWISE a no-chaos run's (token-exact migration:
# already-generated tokens resubmitted as a forced prefix, sample
# stream resumed at the right ordinal), and the fleet must be back at
# full strength via a cold replacement.
JAX_PLATFORMS=cpu python examples/transformer_serving.py --requests 4 \
    --failover-check

# Sharded-serving smoke (docs/serving.md "Sharded serving"): the
# example bootstraps a 4-device virtual CPU mesh
# (--xla_force_host_platform_device_count) and asserts (1) fixed AND
# paged engines sharded over a model=4 mesh produce BITWISE the
# unsharded engine's token streams, greedy and seeded — the mesh
# changes where the hot path runs, never what it produces — and (2) a
# MIXED sharded/unsharded fleet under ServingRouter survives a
# router.replica_kill mid-decode with every stream token-exact vs the
# no-chaos run (forced-prefix migration is layout-agnostic).
JAX_PLATFORMS=cpu python examples/transformer_serving.py --requests 3 \
    --sharded-check

# Disaggregated-serving smoke (docs/serving.md "Disaggregated
# serving"): a prefill pool and a decode pool behind a DisaggRouter —
# every stream prefills on one engine, hands its KV blocks (digest-
# verified manifest) to the other, and resumes mid-flight BITWISE the
# shared-program engine's stream, with the full prompt blocks grafted
# into the decode pool's prefix cache (only the sub-block tail
# re-prefills). A chaos-corrupted transfer (disagg.block_corrupt)
# must be rejected by byte-digest verification and the stream
# recovered via token-level recompute — still bitwise.
JAX_PLATFORMS=cpu python examples/transformer_serving.py --requests 4 \
    --disagg-check

# Resume smoke (docs/resilience.md "Exact resume"): a short training
# run over a sharded shuffled dataset is killed mid-epoch AND
# mid-checkpoint-save via HVD_CHAOS, restarted with full TrainSnapshot
# resume (model + data cursor + guard), and equivalence-checked
# against an uninterrupted control — the batch streams must be
# bitwise identical, final params must match, and the resume gap must
# be 0 (the module exits nonzero otherwise, and also if no kill
# actually fired — an inert smoke proves nothing).
rm -rf /tmp/hvd_resume_smoke
HVD_CHAOS=train_crash:2,ckpt_kill:1 JAX_PLATFORMS=cpu \
    python -m horovod_tpu.resilience.equivalence \
    --workdir /tmp/hvd_resume_smoke --epochs 2 --save-every 2 \
    2>&1 | tee /tmp/hvd_resume_smoke.log
grep -q "equivalence OK" /tmp/hvd_resume_smoke.log

# Elastic-membership smoke (docs/resilience.md "Elastic membership"):
# a 4-member in-process simulated world trains under an env-armed
# rank_death — one member stops heartbeating mid-epoch, the survivors
# must detect the lapsed lease, commit generation 1, shrink to 3,
# roll back to the last committed TrainSnapshot, rebalance shards,
# and finish every epoch with the union of all members' effective
# per-record streams bitwise-equal (as a multiset) to an
# uninterrupted control run's — no record trained twice, none
# silently dropped (the module exits nonzero otherwise, and also if
# the death or the resize never actually happened).
rm -rf /tmp/hvd_elastic_smoke
HVD_CHAOS=rank_death:1 JAX_PLATFORMS=cpu \
    python -m horovod_tpu.resilience.equivalence --resize \
    --workdir /tmp/hvd_elastic_smoke \
    2>&1 | tee /tmp/hvd_elastic_smoke.log
grep -q "resize equivalence OK" /tmp/hvd_elastic_smoke.log

# Multi-controller elastic smoke (docs/resilience.md "The
# multi-process drill"): the REAL thing — hvdrun launches 3 worker
# processes over the native rendezvous KV server (--elastic: a signal
# death is a membership event, not a job failure), each worker
# installs BootstrapKV as its membership transport and trains in
# KV-coordinated lockstep (no cross-process jax collectives), worker
# 2 SIGKILLs itself mid-epoch, the survivors' shared FailureDetector
# sees the lease lapse, the resize protocol commits generation 1,
# bootstrap.apply_resize re-keys the runtime, and training resumes
# from the committed TrainSnapshot with the shard remainder
# rebalanced — the driver verifies the surviving world's final states
# agree bitwise and the effective per-record union equals every
# dataset record exactly once per epoch, then prints the OK line.
rm -rf /tmp/hvd_elastic_mc
JAX_PLATFORMS=cpu python -m horovod_tpu.resilience.drill \
    --workdir /tmp/hvd_elastic_mc --world 3 --kill-rank 2 \
    2>&1 | tee /tmp/hvd_elastic_mc.log
grep -q "resize equivalence OK (multi-process)" /tmp/hvd_elastic_mc.log

# Chaos smoke (docs/resilience.md): one injected checkpoint-write
# failure mid-run — the shared RetryPolicy must retry with backoff and
# the run must still complete and leave a restorable checkpoint.
rm -rf /tmp/hvd_chaos_smoke
HVD_CHAOS=ckpt_write_fail:1 JAX_PLATFORMS=cpu \
    python examples/jax_checkpoint_resume.py --steps 10 --save-every 5 \
    --ckpt-dir /tmp/hvd_chaos_smoke 2>&1 | tee /tmp/hvd_chaos_smoke.log
grep -q "retry 1/" /tmp/hvd_chaos_smoke.log       # the retry happened
grep -q "final loss" /tmp/hvd_chaos_smoke.log     # ...and run finished
test -d /tmp/hvd_chaos_smoke/step_00000010        # ...with the save

python -m horovod_tpu.runner -np 2 --platform cpu -- \
    python examples/jax_mnist.py --steps 20

# Compressed-allreduce leg: DistributedOptimizer(compression=powersgd)
# composed with the CNN step factory (single reduce), multi-process.
python -m horovod_tpu.runner -np 2 --platform cpu -- \
    python examples/jax_mnist.py --steps 20 --compression powersgd

python -m horovod_tpu.runner -np 2 --platform cpu -- \
    python examples/jax_mnist_advanced.py --epochs 1

python -m horovod_tpu.runner -np 2 --platform cpu -- \
    python examples/torch_mnist.py --steps 20

echo "CI OK"
