"""Benchmark harness — prints ONE JSON line (the last stdout line).

Flagship benchmark: ResNet-101 data-parallel training throughput in
images/sec/chip, the metric family of BASELINE.md (the reference's
headline chart is ResNet-101/Inception-V3/VGG-16 scaling on 128×P100,
`README.md:27-32`). Runs on whatever devices are visible (the driver
provides one real TPU chip); the full framework path is exercised —
mesh init, shard_map train step, fused gradient allreduce, optimizer.

vs_baseline: ratio against the Horovod-paper-era single-P100 fp32
ResNet-101 throughput (~138 img/s, tf_cnn_benchmarks as used in
arXiv:1802.05799's setup) — i.e. per-chip speed relative to the
hardware the reference published on.

The bench measures an accelerator: it runs in ONE process on the
devices JAX reports, and when those are CPUs it exits non-zero with
one message and no number — unless `--platform cpu` asks for the CPU
explicitly (functional smoke runs of the harness; every line names its
platform). For a CNN primary a WARM-START fast pass (same model,
batch 32, 2 steps) is emitted first, then the full-size pass
overwrites it. The Pallas flash fwd+bwd proof is emitted EARLY as its
own JSON line so it survives a later model-bench timeout; the driver
parses the final (model) line.

Extras:
  --sweep-fusion 0,1048576,8388608,67108864   per-threshold img/s in
      one JSON (`sweep` key) — the reference's VGG-16 fusion-buffer
      experiment (docs/tensor-fusion.md:18-28, BASELINE.md configs).
  flash-attention proof: on TPU, one non-interpret Pallas flash
      forward+backward is compiled and timed (`flash_attn_ms` key)
      so the hot kernel is exercised on real hardware every bench run.

Usage: python bench.py [--model resnet101] [--batch 128] [--steps 10]
"""

import argparse
import json
import os
import sys
import threading
import time

P100_RESNET101_IMG_S = 138.0  # per-GPU fp32 baseline (paper-era setup)

# Analytic training FLOPs per image at 224²/299² (3× forward pass);
# used for the MFU estimate when XLA cost analysis is unavailable.
TRAIN_GFLOPS_PER_IMG = {
    "resnet50": 3 * 4.1, "resnet101": 3 * 7.8, "vgg16": 3 * 15.5,
    "inception3": 3 * 5.7, "mnist": 3 * 0.01,
    "vit": 3 * 17.6,  # ViT-B/16 @224 (Dosovitskiy et al. Table 6)
}
# Peak bf16 TFLOP/s by device kind — canonical table lives in
# utils/profile_analysis.py (shared with the obs-plane MFU gauge);
# mirrored lazily here so `--help` and argument errors never pay the
# package import.


def _peak_bf16():
    from horovod_tpu.utils.profile_analysis import PEAK_BF16_FLOPS
    return PEAK_BF16_FLOPS
# HBM bandwidth GB/s by device kind (public TPU specs) — the decode
# roofline's denominator (docs/inference.md).
HBM_GBPS = {
    "TPU v4": 1228, "TPU v5 lite": 819, "TPU v5e": 819,
    "TPU v5p": 2765, "TPU v6 lite": 1640, "TPU v6e": 1640,
}


def log(msg):
    print(msg, file=sys.stderr, flush=True)


_EMIT_LOCK = threading.Lock()

def emit(result):
    # Serialized against the watchdog's re-emit so the driver-parsed
    # final line can never be interleaved/corrupted JSON.
    with _EMIT_LOCK:
        print(json.dumps(result), flush=True)


# Best primary result so far — what the deadline watchdog re-emits as
# the FINAL line if a later pass hangs (see start_deadline_watchdog).
# Written via _set_best / read by the watchdog, both under _EMIT_LOCK.
_BEST_RESULT = {}


def _set_best(result):
    with _EMIT_LOCK:
        _BEST_RESULT.clear()
        _BEST_RESULT.update(result)


def start_deadline_watchdog(metric, unit, deadline_s):
    """Arm a global wall-clock deadline for the whole bench.

    Every per-model line is emitted immediately, so completed numbers
    survive; but the driver parses the LAST stdout line, and a pass
    that overruns means the canonical final line never prints and the
    driver's own timeout records nothing useful. This daemon thread
    guarantees a meaningful final line: at the deadline it re-emits
    the best primary result (tagged `watchdog`) — or a diagnostic
    error line if no pass completed — and exits the process
    (os._exit: a thread blocked in a device call cannot be joined)."""

    def fire():
        with _EMIT_LOCK:   # atomic snapshot + final print
            if _BEST_RESULT:
                r = dict(_BEST_RESULT)
                r["watchdog"] = (f"deadline {deadline_s:.0f}s reached; "
                                 "remaining passes skipped")
                print(json.dumps(r), flush=True)
                os._exit(0)
            print(json.dumps(
                {"metric": metric, "value": 0.0, "unit": unit,
                 "vs_baseline": None,
                 "error": f"watchdog: no pass completed within "
                          f"{deadline_s:.0f}s"}),
                flush=True)
            os._exit(1)

    t = threading.Timer(deadline_s, fire)
    t.daemon = True
    t.start()
    return t


def write_out(args):
    """--out: persist the current best (final) result JSON to a file
    — every mode's final emit calls this, so the artifact exists
    whether the bench measured serving, decode, training, or CNNs."""
    if not getattr(args, "out", None):
        return
    with _EMIT_LOCK:
        data = dict(_BEST_RESULT)
    with open(args.out, "w") as f:
        json.dump(data, f, indent=1)
        f.write("\n")
    log(f"result written to {args.out}")


def fail(metric, unit, kind, detail, rc=1):
    """Diagnostic JSON: `error` distinguishes backend-unavailable from
    benchmark-failed (the bench must not die silently).

    Exits with os._exit so the armed deadline-watchdog timer and any
    engine threads cannot hold interpreter shutdown open behind the
    diagnostic."""
    emit({"metric": metric, "value": 0.0, "unit": unit,
          "vs_baseline": None, "error": f"{kind}: {detail}"})
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)


def _force_platform(platform):
    """Select the JAX platform by name before the backend starts."""
    if platform:
        import jax
        jax.config.update("jax_platforms", platform)


def _profile_ctx(profile_dir):
    """jax.profiler trace context (nullcontext when disabled); the
    caller must time strictly inside it so profiler start/serialize
    stay untimed."""
    import contextlib

    import jax
    if not profile_dir:
        return contextlib.nullcontext()
    return jax.profiler.trace(profile_dir)


def _lm_arch_kwargs(args):
    """The --arch preset's TransformerLM kwargs — one shared source
    (`models.transformer.LLAMA_ARCH_KW`), consumed by BOTH the train
    and decode LM benches (pos_emb is resolved separately in main)."""
    if args.arch == "llama":
        from horovod_tpu.models.transformer import LLAMA_ARCH_KW
        return dict(LLAMA_ARCH_KW)
    return {}


def time_steps(step, state, batch, rng, steps, warmup,
               profile_dir=None):
    t0 = time.time()
    for _ in range(max(1, warmup)):  # >=1 so compile stays untimed
        state, loss = step(state, batch, rng)
    # The scalar read-back is the fence (and the logged loss):
    # float() blocks until the value exists, as block_until_ready
    # does (chip_smoke.py's env phase checks that on the chip).
    warm_loss = float(loss)
    compile_s = time.time() - t0
    log(f"warmup done in {compile_s:.1f}s (loss={warm_loss:.3f})")
    with _profile_ctx(profile_dir):
        t0 = time.time()
        for _ in range(steps):
            state, loss = step(state, batch, rng)
        final = float(loss)  # same fence closes the timed window
        dt = time.time() - t0
    if profile_dir:
        log(f"profiler trace written to {profile_dir}")
    return state, final, dt, compile_s


def flash_attention_proof(platform):
    """Compile + time one NON-interpret Pallas flash fwd+bwd on the
    chip — the driver-visible proof the hot kernel works on hardware
    Tries the fused Pallas backward first and
    falls back to the blockwise recompute VJP if the fused kernels
    fail to compile on this toolchain. Returns (step-ms, bwd_impl) or
    (None, None) off-TPU."""
    if platform != "tpu":
        return None, None
    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops.flash_attention import flash_attention

    B, S, H, D = 4, 2048, 8, 128
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(key_i, (B, S, H, D), jnp.bfloat16)
               for key_i in jax.random.split(key, 3))

    def timed(bwd_impl):
        def loss_fn(q, k, v):
            out = flash_attention(q, k, v, causal=True,
                                  interpret=False, bwd_impl=bwd_impl)
            return out.astype(jnp.float32).mean()

        grad_fn = jax.jit(
            jax.value_and_grad(loss_fn, argnums=(0, 1, 2)))
        t0 = time.time()
        loss, grads = grad_fn(q, k, v)
        log(f"flash-attn fwd+bwd({bwd_impl}) compiled in "
            f"{time.time() - t0:.1f}s (loss={float(loss):.4f})")
        n = 10
        t0 = time.time()
        for _ in range(n):
            loss, grads = grad_fn(q, k, v)
        float(loss)
        return (time.time() - t0) / n * 1e3

    try:
        ms, impl = timed("pallas"), "pallas"
    except Exception as e:  # noqa: BLE001 — fall back, then report
        log(f"fused pallas backward failed ({e!r}); "
            f"falling back to recompute VJP")
        ms, impl = timed("recompute"), "recompute"
    log(f"flash-attn [B{B} S{S} H{H} D{D}] fwd+bwd({impl}): "
        f"{ms:.2f} ms/step")
    return round(ms, 2), impl


def run_decode(args, devices, n_chips, log):
    """Autoregressive inference throughput (tokens/sec/chip): the
    KV-cache `generate` loop on the flagship LM — the serving-side
    number the training tokens/sec pairs with. Runs on the default
    device only (serving is per-replica), so the result is per-chip by
    construction regardless of world size."""
    import jax
    import numpy as np

    from horovod_tpu.models.transformer import generate

    model, params = _build_decode_lm(args)
    B, P, steps = args.batch, 32, args.decode_steps
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(params))
    # Analytic per-tick HBM roofline (docs/inference.md): every
    # parameter byte is re-read each tick, plus the FILLED cache
    # prefix (rounded up to the read-block granularity; all max_len
    # slots when the prefix path is off), at the final tick's fill.
    weight_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                       for p in jax.tree.leaves(params))
    Hkv = args.kv_heads or args.heads
    fill = P + steps
    blk = args.decode_prefix_block
    if args.window is not None:
        # The rolling-window cache allocates exactly `window` slots
        # and the decode path reads ALL of them every tick (slot
        # validity is a mask, not a bound) — charge the full buffer.
        slots = args.window
    elif blk and args.seq % min(blk, args.seq) == 0:
        slots = min(args.seq, -(-fill // blk) * blk)
    else:
        slots = args.seq
    kv_itemsize = 1 if args.kv_quant == "int8" else 2
    cache_bytes = (2 * B * slots * Hkv * args.head_dim * kv_itemsize
                   * args.layers)
    # EFFECTIVE attention path — mirror _decode_attention's dispatch
    # so the artifact never labels a silent fallback as the requested
    # engine (a pallas-vs-lax A/B must not compare lax to itself).
    if args.window is not None:
        eff_impl = "rolling_window"
    elif not (blk and args.seq % min(blk, args.seq) == 0):
        eff_impl = "cache_wide"
    else:
        # the rule the model itself follows (kernel on a TPU at S = 1,
        # un-quantized; the lax walk otherwise)
        from horovod_tpu.ops.flash_attention import decode_attention_plan
        eff_impl = {"kernel": "pallas", "lax": "lax"}[
            decode_attention_plan(
                B, args.seq, args.heads, args.kv_heads or args.heads,
                args.head_dim, impl=args.decode_prefix_impl,
                quantized=bool(args.kv_quant)).path]
    prompt = np.random.RandomState(0).randint(0, 32768, (B, P))
    log(f"decode: {n_params / 1e6:.1f}M params, B={B}, prompt={P}, "
        f"steps={steps}, quant={args.weight_quant or 'none'}, "
        f"hbm/tick={{weights {weight_bytes / 1e6:.0f}MB, "
        f"cache {cache_bytes / 1e6:.0f}MB}}")
    t0 = time.time()
    out = generate(model, params, prompt, steps=steps)
    np.asarray(out)  # device->host read-back fences (see time_steps)
    log(f"decode compiled+first run in {time.time() - t0:.1f}s")
    with _profile_ctx(args.profile):
        t0 = time.time()
        out = generate(model, params, prompt, steps=steps)
        np.asarray(out)
        dt = time.time() - t0
    if args.profile:
        log(f"profiler trace written to {args.profile}")
    tok_s = B * steps / dt
    log(f"decode: {tok_s:.1f} tokens/s "
        f"({dt / steps * 1e3:.2f} ms/tick at B={B})")
    return {"tok_s_chip": tok_s, "n_params": n_params,
            "ms_per_tick": dt / steps * 1e3,
            "hbm_bytes_per_tick": weight_bytes + cache_bytes,
            "decode_prefix_block": blk or None,
            "decode_prefix_impl": eff_impl,
            "serve_cast": args.serve_cast,
            "weight_quant": args.weight_quant}


def _build_decode_lm(args):
    """(model, params) for the inference benches — ONE construction
    site so `--decode` and `--serving` cannot drift: arch preset,
    prefix-block knobs, `--no-serve-cast`, weight-only int8, and the
    int8 KV cache all compose here."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.parallel.tensor import unbox

    model = TransformerLM(
        vocab_size=32768, num_layers=args.layers,
        num_heads=args.heads, num_kv_heads=args.kv_heads,
        pos_emb=args.pos_emb, window=args.window,
        head_dim=args.head_dim,
        max_len=args.seq, dtype=jnp.bfloat16,
        decode_prefix_block=args.decode_prefix_block or None,
        decode_prefix_impl=args.decode_prefix_impl,
        attn_impl=args.attn_impl, **_lm_arch_kwargs(args))
    params = unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))["params"])
    if args.serve_cast:
        # Serve at the compute dtype: the stored-f32 master weights
        # would otherwise be re-read (or re-converted) inside every
        # decode tick — docs/inference.md roofline term #1.
        from horovod_tpu.models.transformer import serving_params
        params = serving_params(params, jnp.bfloat16)
    if args.weight_quant:
        # Weight-only int8 serving path: block kernels stored int8,
        # dequantized in VMEM inside the decode scan (half the weight
        # HBM traffic per tick).
        from horovod_tpu.ops.quantization import quantize_lm_params
        model = model.clone(weight_quant=args.weight_quant)
        params = quantize_lm_params(params)
    if args.kv_quant:
        # int8 KV cache: 2x context per byte of cache HBM, half the
        # per-tick cache read traffic.
        model = model.clone(kv_quant=args.kv_quant)
    return model, params


def _tpot_histogram(results):
    """Inter-token latency distribution over one rate point's
    completed requests: percentiles + an 8-bin histogram (ms) — the
    before/after evidence artifact for the hot-path pipelining PR."""
    import numpy as np
    xs = np.asarray([r.tpot_s for r in results
                     if r.tpot_s is not None]) * 1e3
    if xs.size == 0:
        return None
    counts, edges = np.histogram(xs, bins=8)
    out = {f"p{q}": round(float(np.percentile(xs, q)), 3)
           for q in (10, 25, 50, 75, 90, 95, 99)}
    out.update({"mean": round(float(xs.mean()), 3), "n": int(xs.size),
                "hist_edges_ms": [round(float(e), 3) for e in edges],
                "hist_counts": [int(c) for c in counts]})
    return out


def _serve_rate(model, params, args, prompts, rate, *,
                pipeline_depth, prefill_chunk_budget, chaos_mode,
                log, paged_cfg=None, slo_spec=None, engine_kw=None,
                label=""):
    """One open-loop Poisson rate point through a fresh (pre-warmed)
    engine; returns the per-rate record. ``pipeline_depth`` /
    ``prefill_chunk_budget`` parameterize the hot path so the same
    harness measures the PR-3 pipeline and its PR-1-shaped control;
    ``paged_cfg`` (num_slots/kv_blocks/kv_block_size) switches the
    engine to the paged KV cache for the PR-7 paged-vs-fixed A/B;
    ``slo_spec`` attaches a burn-rate SLO monitor (obs/slo.py) whose
    summary lands in the record's ``slo`` block."""
    import numpy as np

    from horovod_tpu.serving import ServingEngine

    steps, n_req = args.decode_steps, args.serving_requests
    S = (paged_cfg["num_slots"] if paged_cfg
         else args.serving_slots)
    kw = {}
    if paged_cfg:
        kw = dict(paged=True, kv_blocks=paged_cfg["kv_blocks"],
                  kv_block_size=paged_cfg["kv_block_size"],
                  paged_kernel=paged_cfg.get(
                      "kernel", getattr(args, "serving_paged_kernel",
                                        None)))
    if engine_kw:
        # Decode-fast-path matrix knobs (weight_quant / spec_draft /
        # spec_k / paged_kernel) ride straight into the engine.
        kw.update(engine_kw)
    slo_mon = None
    if slo_spec:
        from horovod_tpu.obs.slo import SLOMonitor
        slo_mon = SLOMonitor.from_spec(slo_spec)
        kw["slo"] = slo_mon
    if chaos_mode:
        from horovod_tpu.resilience import chaos as chaos_mod
    gaps = np.random.RandomState(7).exponential(1.0 / rate, size=n_req)
    eng = ServingEngine(model, params, num_slots=S,
                        max_queue=2 * n_req, warmup=True,
                        pipeline_depth=pipeline_depth,
                        prefill_chunk_budget=prefill_chunk_budget,
                        auto_restart=chaos_mode, max_restarts=8,
                        **kw)
    t0 = time.time()
    handles = []
    for i, p in enumerate(prompts):
        handles.append(eng.submit(p, steps))
        if chaos_mode and i == n_req // 3:
            # Mid-load crash: deterministic site, armed once the
            # engine is demonstrably busy.
            chaos_mod.arm("serving_dispatch_crash", 1)
        if i < n_req - 1:
            time.sleep(float(gaps[i]))
    results = [h.result() for h in handles]
    eng.shutdown()
    if chaos_mode:
        chaos_mod.install(None)
    dt = time.time() - t0
    snap = eng.metrics_snapshot()
    tok_s = sum(len(r.tokens) for r in results) / dt
    rec = {
        "tok_s": round(tok_s, 2),
        "ttft_ms_p50": snap["ttft_ms"]["p50"],
        "ttft_ms_p95": snap["ttft_ms"]["p95"],
        "tpot_ms_p50": snap["tpot_ms"]["p50"],
        "tpot_ms_p95": snap["tpot_ms"]["p95"],
        "tpot_hist_ms": _tpot_histogram(results),
        "queue_wait_ms_p95": snap["queue_wait_ms"]["p95"],
        "completed": snap["completed"],
        # Hot-path serialization evidence (the tentpole's metric):
        # exposed host syncs per generated token, and how many tick
        # reads hid behind the next tick's device compute.
        "host_syncs": snap["host_syncs"],
        "host_syncs_per_token": snap["host_syncs_per_token"],
        "ticks": snap["ticks"],
        "ticks_overlapped": snap["ticks_overlapped"],
        "compiles": snap["compiles"],
        "pipeline_depth": pipeline_depth,
        "prefill_chunk_budget": prefill_chunk_budget,
        # Decode-fast-path evidence: tokens retired per decode tick
        # across all lanes (~busy lanes without spec decode; x
        # (1 + acceptance x k) per lane with it — compare legs at
        # the same occupancy).
        "tokens_per_tick": snap["tokens_per_tick"],
        # Effective concurrency high-water mark (decoding +
        # mid-prefill): bounded by num_slots on the fixed pool, by
        # BLOCK availability on the paged one — the capacity half of
        # the paged A/B.
        "peak_active": snap["peak_active"],
        "num_slots": S,
        # 1 = unsharded; > 1 = the serving mesh width the engine
        # partitioned the hot path over (docs/serving.md "Sharded
        # serving").
        "mesh_devices": snap.get("mesh_devices", 1),
    }
    if snap["spec_rounds"]:
        rec.update({
            "spec_rounds": snap["spec_rounds"],
            "spec_proposed": snap["spec_proposed"],
            "spec_accepted": snap["spec_accepted"],
            "spec_acceptance_rate": snap["spec_acceptance_rate"],
            "spec_multi_token_ticks": snap["spec_multi_token_ticks"],
        })
    if label:
        rec["config"] = label
    if slo_mon is not None:
        # Burn-rate view of the same window (obs/slo.py): objectives,
        # fast/slow burn per objective, and whether anything breached.
        rec["slo"] = slo_mon.summary()
        burns = {n: b["fast"]
                 for n, b in rec["slo"]["burn_rates"].items()}
        log(f"serving rate={rate}/s slo: fast burns {burns}, "
            f"breaches={rec['slo']['breach_count']}")
    if paged_cfg:
        cold = [r.ttft_s for r in results
                if r.prefix_tokens_cached == 0]
        hit = [r.ttft_s for r in results if r.prefix_tokens_cached > 0]
        rec.update({
            "paged": True,
            "kv_blocks": paged_cfg["kv_blocks"],
            "kv_block_size": paged_cfg["kv_block_size"],
            "prefix_hits": snap["prefix_hits"],
            "prefix_misses": snap["prefix_misses"],
            "prefix_hit_rate": snap["prefix_hit_rate"],
            "prefix_evictions": snap["prefix_evictions"],
            "prefill_tokens_skipped": snap["prefill_tokens_skipped"],
            "requests_prefix_hit": len(hit),
            # The TTFT the cache deletes: requests whose prefix was
            # resident vs requests that prefilled everything.
            "ttft_cold_ms_p50": (round(float(
                np.percentile(cold, 50)) * 1e3, 3) if cold else None),
            "ttft_hit_ms_p50": (round(float(
                np.percentile(hit, 50)) * 1e3, 3) if hit else None),
        })
    if chaos_mode:
        # The robustness cost on the perf trajectory: how long a
        # crash-to-requeued recovery takes under this load.
        rec.update({
            "restarts": snap["restarts"],
            "requeued": snap["requeued"],
            "faults_injected": snap["faults_injected"],
            "recovery_ms_p50": snap["recovery_ms"]["p50"],
            "recovery_ms_p95": snap["recovery_ms"]["p95"],
        })
        log(f"serving rate={rate}/s chaos: "
            f"{snap['restarts']} restart(s), "
            f"{snap['requeued']} requeued, recovery p95 = "
            f"{snap['recovery_ms']['p95']} ms")
    log(f"serving rate={rate}/s depth={pipeline_depth} "
        f"budget={prefill_chunk_budget}: {tok_s:.1f} tok/s, "
        f"ttft p50/p95 = {snap['ttft_ms']['p50']}/"
        f"{snap['ttft_ms']['p95']} ms, tpot p50/p95 = "
        f"{snap['tpot_ms']['p50']}/{snap['tpot_ms']['p95']} ms, "
        f"host-syncs/token = {snap['host_syncs_per_token']}")
    return rec


def _router_leg(model, params, args, prompts, rate, *, replicas,
                kill, log, refs=None):
    """One serving-fleet leg for the --router A/B: Poisson arrivals
    through a `ServingRouter` over ``replicas`` engine replicas;
    ``kill=True`` arms the ``router.replica_kill`` chaos site a third
    of the way into the arrival stream (abrupt replica death with
    streams mid-decode). Returns (record, streams) — ``refs`` (the
    matching no-chaos leg's streams) pins the token-exact-failover
    bit recorded in the artifact."""
    import numpy as np

    from horovod_tpu.resilience import chaos as chaos_mod
    from horovod_tpu.serving import ServingEngine, ServingRouter

    steps, n_req = args.decode_steps, len(prompts)
    S = args.serving_slots

    def factory():
        return ServingEngine(
            model, params, num_slots=S, max_queue=2 * n_req,
            warmup=True, pipeline_depth=args.serving_pipeline_depth,
            prefill_chunk_budget=args.prefill_chunk_budget)

    gaps = np.random.RandomState(7).exponential(1.0 / rate,
                                                size=n_req)
    router = ServingRouter(factory, num_replicas=replicas,
                           health_poll_s=0.01)
    monkey = None
    # A previously armed monkey (e.g. env HVD_CHAOS) must survive
    # this leg: install() returns the NEW value, so the previous one
    # comes from active() (the PR-6 equivalence-harness lesson).
    prev_monkey = chaos_mod.active()
    t0 = time.time()
    handles = []
    try:
        for i, p in enumerate(prompts):
            handles.append(router.submit(p, steps, temperature=0.7,
                                         seed=i))
            if kill and i == n_req // 3:
                # Seeded chaos once the fleet is demonstrably busy.
                monkey = chaos_mod.ChaosMonkey("router.replica_kill:1")
                chaos_mod.install(monkey)
            if i < n_req - 1:
                time.sleep(float(gaps[i]))
        results = [h.result() for h in handles]
        if kill:
            # The cold replacement lands >= one monitor sweep after
            # the migrations; wait for it so the artifact records the
            # restored fleet, not the race.
            t_end = time.time() + 10
            while (router.metrics_snapshot()["replacements"] < 1
                   and time.time() < t_end):
                time.sleep(0.02)
    finally:
        if monkey is not None:
            chaos_mod.install(prev_monkey)
        snap = router.metrics_snapshot()
        router.shutdown()
    dt = time.time() - t0
    streams = [list(r.tokens) for r in results]
    ttfts = sorted(r.ttft_s for r in results)
    e2es = sorted(r.e2e_s for r in results)

    def pct(xs, q):
        return round(float(np.percentile(xs, q)) * 1e3, 3)

    rec = {
        "replicas": replicas,
        "chaos": bool(kill),
        "tok_s": round(sum(len(s) for s in streams) / dt, 2),
        "completed": snap["completed"],
        "failed": snap["failed"],
        "ttft_ms_p50": pct(ttfts, 50), "ttft_ms_p95": pct(ttfts, 95),
        "e2e_ms_p50": pct(e2es, 50), "e2e_ms_p95": pct(e2es, 95),
        "migrations": snap["migrations"],
        "migrated_tokens": snap["migrated_tokens"],
        "replica_deaths": snap["replica_deaths"],
        "replacements": snap["replacements"],
        "retries": snap["retries"], "hedges": snap["hedges"],
    }
    if kill:
        rec["kills_fired"] = (monkey.fired("router.replica_kill")
                              if monkey else 0)
    if refs is not None:
        # THE failover acceptance bit: chaos-leg streams bitwise equal
        # the no-chaos leg's (same prompts + seeds => deterministic).
        rec["token_exact_vs_no_chaos"] = streams == refs
    log(f"router leg replicas={replicas} chaos={kill}: "
        f"{rec['tok_s']} tok/s, ttft p50/p95 {rec['ttft_ms_p50']}/"
        f"{rec['ttft_ms_p95']} ms, {rec['migrations']} migration(s), "
        f"{rec['replica_deaths']} death(s)"
        + (f", token-exact={rec['token_exact_vs_no_chaos']}"
           if refs is not None else ""))
    return rec, streams


def _router_ab(model, params, args, prompts, rate, log):
    """--serving --router: the fleet-failover A/B (docs/serving.md
    "Fleet failover") — 1 vs N replicas, each with and without the
    seeded router.replica_kill chaos. The single-replica chaos leg
    exercises recovery-by-cold-replacement (the kill leaves no
    sibling, so migrated streams wait for the factory replacement);
    the fleet chaos leg is the headline: replica death invisible and
    token-exact."""
    n = args.router_replicas
    single, s_streams = _router_leg(
        model, params, args, prompts, rate, replicas=1, kill=False,
        log=log)
    single_chaos, _ = _router_leg(
        model, params, args, prompts, rate, replicas=1, kill=True,
        log=log, refs=s_streams)
    fleet, f_streams = _router_leg(
        model, params, args, prompts, rate, replicas=n, kill=False,
        log=log)
    fleet_chaos, _ = _router_leg(
        model, params, args, prompts, rate, replicas=n, kill=True,
        log=log, refs=f_streams)
    return {"rate": rate, "single": single,
            "single_chaos": single_chaos, "fleet": fleet,
            "fleet_chaos": fleet_chaos}


def _disagg_leg(model, params, args, prompts, rate, *, disagg, log,
                refs=None):
    """One leg of the --disagg A/B: Poisson arrivals through a router
    over TWO paged engines — as a plain 2-replica fleet (``disagg=
    False``, the shared-program baseline) or as a prefill pool +
    decode pool with KV-block handoffs (``disagg=True``). Equal
    engine count and equal per-engine KV geometry on both sides, so
    the columns isolate the PLACEMENT lever. ``refs`` (the baseline
    leg's streams) pins the bitwise-handoff bit in the artifact."""
    import numpy as np

    from horovod_tpu.serving import ServingEngine, ServingRouter

    steps, n_req = args.decode_steps, len(prompts)
    S = args.serving_slots
    bs = args.serving_kv_block_size

    def factory():
        return ServingEngine(
            model, params, num_slots=S, max_queue=2 * n_req,
            warmup=True, paged=True,
            kv_blocks=S * args.seq // bs + 1, kv_block_size=bs,
            pipeline_depth=args.serving_pipeline_depth,
            prefill_chunk_budget=args.prefill_chunk_budget)

    gaps = np.random.RandomState(7).exponential(1.0 / rate,
                                                size=n_req)
    if disagg:
        router = ServingRouter(factory,
                               disagg={"prefill": 1, "decode": 1})
    else:
        router = ServingRouter(factory, num_replicas=2,
                               health_poll_s=0.01)
    t0 = time.time()
    handles = []
    try:
        for i, p in enumerate(prompts):
            handles.append(router.submit(p, steps, temperature=0.7,
                                         seed=i))
            if i < n_req - 1:
                time.sleep(float(gaps[i]))
        results = [h.result() for h in handles]
    finally:
        snap = router.metrics_snapshot()
        router.shutdown()
    dt = time.time() - t0
    streams = [list(r.tokens) for r in results]
    ttfts = sorted(r.ttft_s for r in results)
    tpots = sorted(r.tpot_s for r in results
                   if r.tpot_s is not None)
    e2es = sorted(r.e2e_s for r in results)

    def pct(xs, q):
        return round(float(np.percentile(xs, q)) * 1e3, 3)

    rec = {
        "disagg": bool(disagg),
        "engines": 2,
        "tok_s": round(sum(len(s) for s in streams) / dt, 2),
        "completed": snap["completed"],
        "failed": snap["failed"],
        "ttft_ms_p50": pct(ttfts, 50), "ttft_ms_p95": pct(ttfts, 95),
        "tpot_ms_p50": pct(tpots, 50), "tpot_ms_p95": pct(tpots, 95),
        "e2e_ms_p50": pct(e2es, 50), "e2e_ms_p95": pct(e2es, 95),
        "prefix_tokens_cached": int(sum(r.prefix_tokens_cached
                                        for r in results)),
    }
    if disagg:
        rec["handoffs"] = snap["disagg"]["handoffs"]
        rec["fallbacks"] = snap["disagg"]["fallbacks"]
    if refs is not None:
        # THE handoff acceptance bit: disagg streams bitwise equal the
        # shared-program baseline's (same prompts + seeds =>
        # deterministic decode; the handoff moves WHERE, never WHAT).
        rec["token_exact_vs_baseline"] = streams == refs
    label = "disagg" if disagg else "baseline"
    log(f"disagg leg {label}: {rec['tok_s']} tok/s, ttft p50/p95 "
        f"{rec['ttft_ms_p50']}/{rec['ttft_ms_p95']} ms, tpot p50 "
        f"{rec['tpot_ms_p50']} ms"
        + (f", {rec['handoffs']} handoff(s), {rec['fallbacks']} "
           f"fallback(s), token-exact="
           f"{rec.get('token_exact_vs_baseline')}" if disagg else ""))
    return rec, streams


def _disagg_ab(model, params, args, prompts, rate, log):
    """--serving --disagg: the disaggregated prefill/decode A/B
    (docs/serving.md "Disaggregated serving") at the highest rate —
    2 shared-program replicas vs prefill-pool(1) + decode-pool(1)
    with KV-block handoffs, equal engine count. The headline is TTFT
    under admission pressure: decode ticks no longer queue behind
    other requests' prompt chunks."""
    baseline, b_streams = _disagg_leg(
        model, params, args, prompts, rate, disagg=False, log=log)
    disagg, _ = _disagg_leg(
        model, params, args, prompts, rate, disagg=True, log=log,
        refs=b_streams)
    return {"rate": rate, "baseline": baseline, "disagg": disagg}


def _overload_leg(model, params, args, prompts, rate, *, preempt,
                  log, refs=None):
    """One leg of the --overload A/B: Poisson arrivals into ONE paged
    engine whose pool is deliberately undersized (fits ~1.5 worst-case
    streams), with every 4th request a priority-5 "paid" submit and
    the rest priority-0 "free" flood. ``preempt=False`` is shed-only:
    the paid head waits in its WFQ lane until a lane drains.
    ``preempt=True`` is the overload control plane (docs/serving.md
    "Overload control"): watermark admission + token-exact preemption
    — the paid head evicts the cheapest free victims (swap when the
    host budget allows, else recompute) and the victims resume
    bitwise. Equal pool geometry on both legs, so the columns isolate
    the PREEMPTION lever; the headline is paid-tenant TTFT under
    saturation. ``refs`` (the shed leg's streams) pins the
    preempt-resume-bitwise bit in the artifact."""
    import numpy as np

    from horovod_tpu.serving import ServingEngine

    steps, n_req = args.decode_steps, len(prompts)
    S = args.serving_slots
    bs = args.serving_kv_block_size
    # Undersized on purpose: ~1.5 worst-case streams (prompt + steps,
    # +1 for the partial-block tail). The shed leg still always makes
    # progress (one stream fits), the preempt leg has victims to take.
    per_req = (max(len(p) for p in prompts) + steps + bs - 1) // bs + 1
    kv_blocks = 1 + per_req + max(2, per_req // 2)
    hi = set(range(3, n_req, 4))
    gaps = np.random.RandomState(7).exponential(1.0 / rate,
                                                size=n_req)
    eng = ServingEngine(
        model, params, num_slots=S, max_queue=4 * n_req + 8,
        warmup=True, paged=True,
        kv_blocks=kv_blocks, kv_block_size=bs,
        pipeline_depth=args.serving_pipeline_depth,
        prefill_chunk_budget=args.prefill_chunk_budget,
        preempt=preempt, swap_bytes=(256 << 20) if preempt else 0,
        tenant_weights="paid=3,free=1")
    t0 = time.time()
    handles = []
    try:
        for i, p in enumerate(prompts):
            if i in hi:
                handles.append(eng.submit(p, steps, temperature=0.7,
                                          seed=i, priority=5,
                                          tenant="paid"))
            else:
                handles.append(eng.submit(p, steps, temperature=0.7,
                                          seed=i, tenant="free"))
            if i < n_req - 1:
                time.sleep(float(gaps[i]))
        results = [h.result() for h in handles]
    finally:
        snap = eng.metrics_snapshot()
        eng.shutdown()
    dt = time.time() - t0
    streams = [list(r.tokens) for r in results]
    hi_ttfts = sorted(results[i].ttft_s for i in sorted(hi))
    ttfts = sorted(r.ttft_s for r in results)

    def pct(xs, q):
        return round(float(np.percentile(xs, q)) * 1e3, 3)

    rec = {
        "preempt": bool(preempt),
        "kv_blocks": kv_blocks,
        "tok_s": round(sum(len(s) for s in streams) / dt, 2),
        "completed": snap["completed"],
        "rejected": snap["rejected"],
        "hi_ttft_ms_p50": pct(hi_ttfts, 50),
        "hi_ttft_ms_p95": pct(hi_ttfts, 95),
        "ttft_ms_p50": pct(ttfts, 50), "ttft_ms_p95": pct(ttfts, 95),
        "preemptions_swap": snap.get("preemptions_swap", 0),
        "preemptions_recompute": snap.get("preemptions_recompute", 0),
        "preempt_tokens_recomputed": snap.get(
            "preempt_tokens_recomputed", 0),
        "preempt_tokens_swapped_in": snap.get(
            "preempt_tokens_swapped_in", 0),
        # THE anti-starvation bit: every request (flood victims
        # included) finished — shedding/preempting the low band never
        # stranded anyone.
        "starvation_free": (len(results) == n_req
                            and snap["rejected"] == 0
                            and snap["timed_out"] == 0),
    }
    if refs is not None:
        # THE preempt-resume acceptance bit: streams with preemption
        # bitwise equal the shed leg's (same prompts + seeds =>
        # deterministic decode; preemption moves WHEN, never WHAT).
        rec["token_exact_vs_baseline"] = streams == refs
    label = "preempt" if preempt else "shed-only"
    log(f"overload leg {label}: {rec['tok_s']} tok/s, hi ttft "
        f"p50/p95 {rec['hi_ttft_ms_p50']}/{rec['hi_ttft_ms_p95']} "
        f"ms, starvation-free={rec['starvation_free']}"
        + (f", {rec['preemptions_swap']} swap / "
           f"{rec['preemptions_recompute']} recompute preemption(s), "
           f"token-exact={rec.get('token_exact_vs_baseline')}"
           if preempt else ""))
    return rec, streams


def _overload_ab(model, params, args, prompts, rate, log):
    """--serving --overload: the overload-control A/B (docs/serving.md
    "Overload control") at the highest rate — shed-only vs token-exact
    preemption on an EQUAL undersized paged pool, priority-5 "paid"
    trickle against a priority-0 "free" flood. The headline is paid
    TTFT under saturation: shed-only parks the paid head behind the
    flood's KV residency; preemption evicts the cheapest victims and
    resumes them bitwise."""
    shed, s_streams = _overload_leg(
        model, params, args, prompts, rate, preempt=False, log=log)
    pre, _ = _overload_leg(
        model, params, args, prompts, rate, preempt=True, log=log,
        refs=s_streams)
    return {"rate": rate, "shed_only": shed, "preempt": pre}


def _serving_trace_check(model, params, args, prompts, log):
    """Observability acceptance evidence: run a few requests with the
    event log, the span recorder and the shared metric
    registry all live, then recover ONE request's ``trace_id`` from
    each subsystem — the proof that a request can be followed across
    the whole plane (docs/observability.md). Recorded in the bench
    artifact as ``trace_check``."""
    import json as _json
    import tempfile

    from horovod_tpu.obs import events as obs_events
    from horovod_tpu.obs import spans as obs_spans
    from horovod_tpu.obs.registry import registry as obs_registry
    from horovod_tpu.serving import ServingEngine

    tmp = tempfile.mkdtemp(prefix="hvd_obs_trace_")
    ev_path = os.path.join(tmp, "events.jsonl")
    # A scoped swap, restored: a user-configured HVD_EVENTS_LOG must
    # keep receiving events after the check.
    prev_ev = obs_events.install(obs_events.EventLog(ev_path))
    try:
        with ServingEngine(model, params,
                           num_slots=min(2, args.serving_slots),
                           max_queue=16, warmup=True) as eng:
            handles = [eng.submit(p, 8) for p in prompts[:3]]
            for h in handles:
                h.result(timeout=600)
    finally:
        obs_events.install(prev_ev)
    # Subsystem 1: the shared registry's exemplar (the last retired
    # request's trace_id rides the e2e histogram).
    hist = obs_registry().get("hvd_serving_e2e_seconds")
    ex = hist.samples()[0][1].exemplar if hist else None
    tid = (ex or {}).get("trace_id")
    in_exemplar = tid is not None
    # Subsystems 2+3: the SAME id in the event log and the span tree.
    in_events = in_spans = False
    if tid:
        with open(ev_path) as f:
            in_events = any(
                _json.loads(line).get("trace_id") == tid
                for line in f)
        in_spans = bool(obs_spans.trace(tid))
    n = sum((in_exemplar, in_events, in_spans))
    log(f"serving trace check: trace_id={tid} found in {n}/3 "
        f"subsystems (metrics exemplar={in_exemplar}, "
        f"event log={in_events}, span tree={in_spans})")
    return {"trace_id": tid, "in_metrics_exemplar": in_exemplar,
            "in_event_log": in_events, "in_span_tree": in_spans,
            "subsystems": n}


def _prefix_ttft_check(model, params, args, paged_cfg, log,
                       rounds=5):
    """The controlled cold-vs-cache-hit TTFT measurement (PR-7
    acceptance): on one warmed, otherwise-idle paged engine, each
    round submits a request with a FRESH block-aligned prefix (cold —
    full prefill) and then a second sharing that prefix (hit —
    prefill covers only the tail), sequentially. Same engine, same
    conditions, the only variable is prefix residency — unlike the
    open-loop rate point, where cold/hit correlates with arrival-time
    LOAD (early arrivals are cold AND unloaded), this isolates the
    prefill the cache deletes. Reported as p50 over rounds."""
    import numpy as np

    from horovod_tpu.serving import ServingEngine

    bs = paged_cfg["kv_block_size"]
    steps = args.decode_steps
    # Largest block-aligned prefix that (with its 2-token tail) still
    # satisfies the engine's P + steps - 1 <= max_len contract; a
    # geometry with no room for even one block skips the check
    # instead of crashing the run after the expensive rate sweep.
    plen = min(args.serving_prefix_len, args.seq - steps + 1 - 2)
    plen -= plen % bs
    if plen < bs:
        log(f"prefix TTFT check skipped: no room for a {bs}-token "
            f"block in prompts at --seq {args.seq} / --decode-steps "
            f"{steps}")
        return None
    rs = np.random.RandomState(13)
    cold_ts, hit_ts, skipped = [], [], 0
    eng = ServingEngine(model, params, num_slots=2,
                        max_queue=8, warmup=True, paged=True,
                        kv_blocks=paged_cfg["kv_blocks"],
                        kv_block_size=bs)
    try:
        for _ in range(rounds):
            prefix = rs.randint(0, 32768, (plen,))
            a = eng.submit(np.concatenate(
                [prefix, rs.randint(0, 32768, (2,))]), steps).result()
            b = eng.submit(np.concatenate(
                [prefix, rs.randint(0, 32768, (2,))]), steps).result()
            assert a.prefix_tokens_cached == 0
            cold_ts.append(a.ttft_s)
            hit_ts.append(b.ttft_s)
            skipped += b.prefix_tokens_cached
    finally:
        eng.shutdown()
    cold = round(float(np.percentile(cold_ts, 50)) * 1e3, 3)
    hit = round(float(np.percentile(hit_ts, 50)) * 1e3, 3)
    log(f"prefix TTFT check ({rounds} rounds, {plen}-token prefix): "
        f"cold p50 {cold} ms -> cache-hit p50 {hit} ms "
        f"({skipped // max(1, rounds)} tokens skipped per hit)")
    return {"rounds": rounds, "prefix_tokens": plen,
            "ttft_cold_ms_p50": cold, "ttft_hit_ms_p50": hit,
            "tokens_skipped_per_hit": skipped // max(1, rounds)}


def _serve_replay(model, params, args, path, log):
    """--serving --replay: re-serve a recorded request log open-loop.

    Arrivals fire at the RECORDED offsets divided by --replay-speed;
    prompts are synthesized from the log's prefix-chain digests
    (obs/reqlog.py), so the prefix-cache hit pattern the record run
    saw is the hit pattern this run exercises; per-request token
    budgets, tenant lanes and priorities are the recorded ones. The
    round-trip acceptance bits land in the record: request count ==
    the log's arrival count, per-request produced tokens == the
    recorded budgets (no-EOS serving: budget IS the output length),
    and the re-chained synthesized prompts reproduce the recorded
    prefix-group structure exactly."""
    import numpy as np

    from horovod_tpu.obs import reqlog as _reqlog
    from horovod_tpu.serving import ServingEngine

    header, records = _reqlog.load(path)
    if not records:
        raise ValueError(f"--replay {path!r} has no arrivals")
    speed = max(1e-6, args.replay_speed)
    block = int(header.get("block", _reqlog.DEFAULT_BLOCK))
    prompts = [_reqlog.synthesize_prompt(r, model.vocab_size, block)
               for r in records]
    # The engine enforces P + max_new - 1 <= max_len: a log recorded
    # on a longer-context engine still replays, with oversized
    # prompts tail-clamped and the clamp COUNTED in the artifact
    # (silent truncation would fake the round-trip bits below).
    clamped = 0
    for i, (r, p) in enumerate(zip(records, prompts)):
        limit = args.seq - int(r["max_new"]) + 1
        if len(p) > limit:
            prompts[i] = p[:max(1, limit)]
            clamped += 1
    if clamped:
        log(f"replay: {clamped}/{len(records)} prompts clamped to "
            f"--seq {args.seq} minus the recorded budget")
    # Replay legs are synthetic re-serves, not client arrivals: mute
    # any configured request log for the duration so replaying a log
    # never appends to (or re-records) one.
    prev_log = _reqlog.install(None)
    eng = ServingEngine(model, params, num_slots=args.serving_slots,
                        max_queue=2 * len(records) + 2, warmup=True,
                        pipeline_depth=args.serving_pipeline_depth,
                        prefill_chunk_budget=args.prefill_chunk_budget)
    try:
        t0 = time.time()
        handles = []
        for r, p in zip(records, prompts):
            delay = t0 + float(r["t"]) / speed - time.time()
            if delay > 0:
                time.sleep(delay)
            handles.append(eng.submit(
                p, int(r["max_new"]), tenant=r.get("tenant", ""),
                priority=int(r.get("priority", 0))))
        results = [h.result() for h in handles]
        dt = time.time() - t0
        eng.shutdown()
    finally:
        _reqlog.install(prev_log)
    snap = eng.metrics_snapshot()
    tokens = [len(res.tokens) for res in results]
    resynth = [{"prefix": _reqlog.prefix_chain(p, block)}
               for p in prompts]
    rec = {
        "source": path,
        "speed": speed,
        "recorded_requests": len(records),
        "requests": len(results),
        "tokens_total": sum(tokens),
        "tokens_per_request": tokens,
        "prompts_clamped": clamped,
        # The round-trip bits (tests/test_spans.py pins the library
        # halves; these pin the bench path end to end).
        "token_counts_match": tokens == [int(r["max_new"])
                                         for r in records],
        "prefix_pattern_preserved": (
            _reqlog.prefix_pattern(resynth)
            == _reqlog.prefix_pattern(records)),
        "tok_s": round(sum(tokens) / dt, 2),
        "ttft_ms_p50": snap["ttft_ms"]["p50"],
        "ttft_ms_p95": snap["ttft_ms"]["p95"],
        "tpot_ms_p50": snap["tpot_ms"]["p50"],
        "tpot_ms_p95": snap["tpot_ms"]["p95"],
        "queue_wait_ms_p95": snap["queue_wait_ms"]["p95"],
        "completed": snap["completed"],
        "compiles": snap["compiles"],
        "num_slots": args.serving_slots,
    }
    log(f"serving replay of {path} at x{speed}: "
        f"{rec['requests']}/{rec['recorded_requests']} requests, "
        f"{rec['tokens_total']} tokens "
        f"(counts match: {rec['token_counts_match']}, prefix groups "
        f"preserved: {rec['prefix_pattern_preserved']}), "
        f"{rec['tok_s']} tok/s")
    return rec


def run_serving(args, devices, n_chips, log):
    """Serving-engine throughput/latency under open-loop load: Poisson
    arrivals against `horovod_tpu.serving.ServingEngine` at each
    --arrival-rates point, reporting tokens/s plus TTFT/TPOT p50/p95,
    the inter-token (TPOT) histogram, and host-syncs-per-token — the
    continuous-batching counterpart of the closed-loop `--decode`
    number (which measures the decode kernel with the batch always
    full; this measures how close admission + scheduling get to that
    ceiling when requests arrive asynchronously). Unless --no-serving-
    ab, the highest rate is additionally measured in the PR-1-shaped
    control configuration (pipeline_depth=0, no prefill interleaving)
    so the pipelining win is an in-artifact A/B, not a cross-run
    diff."""
    import jax
    import numpy as np

    from horovod_tpu.serving import ServingEngine

    model, params = _build_decode_lm(args)
    # The spec matrix's fp legs use the model AS BUILT — captured
    # before the main-leg quantization below, so
    # --serving-weight-quant can't contaminate the fp column of the
    # fp-vs-int8 A/B.
    fp_model, fp_params = model, params
    if (args.serving_weight_quant
            and model.weight_quant != args.serving_weight_quant):
        # Weight-only int8 for the MAIN serving legs (the spec matrix
        # below always measures fp AND int8 regardless).
        from horovod_tpu.ops.quantization import quantize_lm_params
        model = model.clone(weight_quant=args.serving_weight_quant)
        params = quantize_lm_params(params)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(params))
    S = args.serving_slots
    steps = args.decode_steps
    n_req = args.serving_requests
    # Prompt lengths sample [4, max_prompt); the engine enforces
    # P + steps - 1 <= max_len, so max_prompt may never exceed
    # seq - steps + 1 (a floor here would reintroduce mid-run submit
    # ValueErrors after a passing warmup).
    max_prompt = min(args.serving_max_prompt, args.seq - steps + 1)
    if max_prompt < 5:
        raise ValueError(
            f"--seq {args.seq} leaves no prompt room at "
            f"--decode-steps {steps} (need seq >= steps + 4); raise "
            f"--seq or lower --decode-steps")
    rates = [float(r) for r in args.arrival_rates.split(",")]
    log(f"serving: {n_params / 1e6:.1f}M params, slots={S}, "
        f"max_new={steps}, {n_req} req/rate at rates={rates} req/s")

    rs = np.random.RandomState(0)
    frac = max(0.0, min(1.0, args.serving_shared_prefix))
    if frac > 0 and args.seq % args.serving_kv_block_size:
        # Fail BEFORE the expensive rate sweep: the paged A/B leg
        # needs the block size to divide max_len (paged_cache_spec
        # enforces it at engine construction, which would otherwise
        # only fire after the sweep completed).
        raise ValueError(
            f"--serving-kv-block-size {args.serving_kv_block_size} "
            f"must divide --seq {args.seq} for the paged A/B "
            f"(--serving-shared-prefix)")
    sys_prompt = None
    if frac > 0:
        # The millions-of-users traffic shape: `frac` of requests
        # share ONE system prompt (block-aligned so the paged leg's
        # prefix match covers it fully), each with a short unique
        # tail; the rest stay fully random. The prefix must leave
        # prompt room: clamp to half the usable span.
        plen = min(args.serving_prefix_len, max(0, max_prompt // 2))
        plen -= plen % args.serving_kv_block_size
        if plen <= 0:
            raise ValueError(
                f"--serving-shared-prefix needs room for at least one "
                f"{args.serving_kv_block_size}-token block in prompts "
                f"(max_prompt={max_prompt}); raise --seq or lower "
                f"--serving-prefix-len / --serving-kv-block-size")
        sys_prompt = rs.randint(0, 32768, (plen,))
        log(f"serving workload: {frac:.0%} of requests share a "
            f"{plen}-token system prompt")
    prompts = []
    for _ in range(n_req):
        if sys_prompt is not None and rs.rand() < frac:
            tail = rs.randint(
                0, 32768,
                (int(rs.randint(1, max(2, max_prompt
                                       - len(sys_prompt)))),))
            prompts.append(np.concatenate([sys_prompt, tail]))
        else:
            prompts.append(
                rs.randint(0, 32768, (int(rs.randint(4, max_prompt)),)))

    # Program warmup: the first engine construction precompiles the
    # tick + pinned prefill-chunk set (ServingEngine(warmup=True));
    # the jit cache is process-global, so every later per-rate engine
    # warms in milliseconds and no timed window ever contains an XLA
    # compile (each rate point's `compiles` field pins that at 0).
    t0 = time.time()
    ServingEngine(model, params, num_slots=S, warmup=True).shutdown()
    log(f"serving warmup (compiles) in {time.time() - t0:.1f}s")

    chaos_mode = getattr(args, "chaos", False)
    if chaos_mode:
        log("serving chaos mode: one dispatch-thread crash injected "
            "per rate point; recovery latency (time-to-requeue) "
            "recorded")

    depth = args.serving_pipeline_depth
    budget = args.prefill_chunk_budget
    slo_spec = getattr(args, "serving_slo", "") or None
    reqlog_path = getattr(args, "record_reqlog", None)
    replay_path = getattr(args, "replay", None)
    if replay_path == "self" and not reqlog_path:
        raise ValueError("--replay self needs --record-reqlog PATH "
                         "(the log the sweep records is what gets "
                         "replayed)")
    if reqlog_path:
        from horovod_tpu.obs import reqlog as _reqlog
        _reqlog.configure(reqlog_path)
        log(f"serving: recording client arrivals to {reqlog_path}")
    if replay_path and replay_path != "self":
        # Replay-only mode: the recorded workload replaces the
        # Poisson sweep; the artifact keeps the serving schema with
        # the replay leg as its single rate point.
        rep = _serve_replay(model, params, args, replay_path, log)
        return {"tok_s_chip": rep["tok_s"], "n_params": n_params,
                "num_slots": rep["num_slots"], "max_new_tokens": steps,
                "requests_per_rate": rep["requests"],
                "chaos": False, "pipeline_depth": depth,
                "prefill_chunk_budget": budget,
                "rates": {"replay": rep}, "replay": rep,
                "trace_check": _serving_trace_check(
                    model, params, args, prompts, log)}
    per_rate = {}
    best_tok_s = 0.0
    for rate in rates:
        rec = _serve_rate(model, params, args, prompts, rate,
                          pipeline_depth=depth,
                          prefill_chunk_budget=budget,
                          chaos_mode=chaos_mode, log=log,
                          slo_spec=slo_spec)
        best_tok_s = max(best_tok_s, rec["tok_s"])
        per_rate[str(rate)] = rec
    out = {"tok_s_chip": best_tok_s, "n_params": n_params,
           "num_slots": S, "max_new_tokens": steps,
           "requests_per_rate": n_req, "chaos": chaos_mode,
           "pipeline_depth": depth, "prefill_chunk_budget": budget,
           "rates": per_rate,
           # One request followed across the observability plane
           # (event log + span tree + metric exemplar).
           "trace_check": _serving_trace_check(
               model, params, args, prompts, log)}
    if slo_spec:
        # The artifact's headline SLO block: the highest rate point's
        # objectives / burn rates / breach count — the load level
        # where the burn rates are most informative.
        out["slo"] = per_rate[str(max(rates))].get("slo")
    if args.serving_ab and not chaos_mode:
        # In-artifact A/B at the highest rate: the PR-1-shaped hot
        # path (synchronous ticks, whole-prompt prefill) vs the PR-3
        # pipeline — TPOT p50 and host-syncs-per-token side by side.
        rate = max(rates)
        out["pipeline_ab"] = {
            "rate": rate,
            "pre_pipelining": _serve_rate(
                model, params, args, prompts, rate,
                pipeline_depth=0, prefill_chunk_budget=0,
                chaos_mode=False, log=log),
            "pipelined": _serve_rate(
                model, params, args, prompts, rate,
                pipeline_depth=depth, prefill_chunk_budget=budget,
                chaos_mode=False, log=log),
        }
        a = out["pipeline_ab"]["pre_pipelining"]
        b = out["pipeline_ab"]["pipelined"]
        log(f"pipeline A/B at rate={rate}/s: tpot p50 "
            f"{a['tpot_ms_p50']} -> {b['tpot_ms_p50']} ms, "
            f"host-syncs/token {a['host_syncs_per_token']} -> "
            f"{b['host_syncs_per_token']}")
    if args.serving_shared_prefix > 0 and not chaos_mode:
        # Paged-vs-fixed A/B at the highest rate (PR 7): SAME device
        # KV bytes on both sides — the fixed leg is S slots x max_len
        # rows, the paged leg carves those exact bytes into blocks
        # (kv_blocks = S x max_len / block_size, +1 null) but runs 4S
        # decode lanes, since lanes are now cheap program width and
        # admission gates on BLOCKS. The artifact's acceptance
        # numbers: prefix_hit_rate > 0, ttft_hit_ms_p50 strictly
        # below ttft_cold_ms_p50, and the paged leg's peak_active
        # exceeding the fixed leg's num_slots bound.
        rate = max(rates)
        bs = args.serving_kv_block_size
        paged_cfg = {"num_slots": 4 * S,
                     "kv_blocks": S * args.seq // bs + 1,
                     "kv_block_size": bs}
        out["paged_ab"] = {
            "rate": rate,
            "equal_kv_token_rows": S * args.seq,
            "fixed": _serve_rate(
                model, params, args, prompts, rate,
                pipeline_depth=depth, prefill_chunk_budget=budget,
                chaos_mode=False, log=log),
            "paged": _serve_rate(
                model, params, args, prompts, rate,
                pipeline_depth=depth, prefill_chunk_budget=budget,
                chaos_mode=False, log=log, paged_cfg=paged_cfg),
            # Controlled cold-vs-hit TTFT (the acceptance pair): the
            # open-loop leg's per-request split above is confounded by
            # arrival-time load (early arrivals are cold AND
            # unloaded), so the isolated measurement runs idle.
            "prefix_ttft": _prefix_ttft_check(
                model, params, args, paged_cfg, log),
        }
        f, p = out["paged_ab"]["fixed"], out["paged_ab"]["paged"]
        pt = out["paged_ab"]["prefix_ttft"]
        ttft = (f"; controlled TTFT cold {pt['ttft_cold_ms_p50']} -> "
                f"hit {pt['ttft_hit_ms_p50']} ms" if pt else "")
        log(f"paged A/B at rate={rate}/s (equal KV bytes): "
            f"ttft p50 {f['ttft_ms_p50']} -> {p['ttft_ms_p50']} ms, "
            f"prefix hit rate {p['prefix_hit_rate']}, prefill tokens "
            f"skipped {p['prefill_tokens_skipped']}, peak concurrency "
            f"{f['peak_active']} (cap {f['num_slots']}) -> "
            f"{p['peak_active']}{ttft}")
    if args.serving_spec_k > 0 and not chaos_mode:
        # Decode-fast-path A/B matrix (docs/serving.md "Decode fast
        # path"): paged x {fp, int8 weights} x {spec off, spec on} at
        # the highest rate — every leg the same paged geometry and
        # kernel mode, so the columns isolate the weight-quant and
        # the spec-decode levers. Self-draft (default) measures the
        # acceptance CEILING (rate 1.0 — the round mechanics with
        # every proposal accepted); --serving-spec-draft-layers swaps
        # in a random small draft for realistic plumbing.
        k = args.serving_spec_k
        rate = max(rates)
        bs = args.serving_kv_block_size
        if args.seq % bs:
            raise ValueError(
                f"--serving-kv-block-size {bs} must divide --seq "
                f"{args.seq} for the spec matrix's paged legs")
        paged_cfg = {"num_slots": S,
                     "kv_blocks": S * args.seq // bs + 1,
                     "kv_block_size": bs,
                     "kernel": args.serving_paged_kernel}
        # Spec-mode verify needs k tokens of cache headroom; trim the
        # workload's prompts so every submit passes the bound.
        limit = max(1, args.seq - steps - k + 1)
        mprompts = [p if len(p) <= limit else p[:limit]
                    for p in prompts]
        import jax.numpy as jnp

        from horovod_tpu.models.transformer import TransformerLM
        from horovod_tpu.ops.quantization import quantize_lm_params
        from horovod_tpu.parallel.tensor import unbox
        if args.serving_spec_draft_layers > 0:
            dm = TransformerLM(
                vocab_size=32768,
                num_layers=args.serving_spec_draft_layers,
                num_heads=args.heads, num_kv_heads=args.kv_heads,
                pos_emb=args.pos_emb, head_dim=args.head_dim,
                max_len=args.seq, dtype=jnp.bfloat16,
                attn_impl=args.attn_impl, **_lm_arch_kwargs(args))
            dp = unbox(dm.init(jax.random.PRNGKey(2),
                               jnp.zeros((1, 64), jnp.int32))["params"])
            draft_fp = draft_q = (dm, dp)
        else:
            qm = (fp_model if fp_model.weight_quant == "int8"
                  else fp_model.clone(weight_quant="int8"))
            qp = (fp_params if fp_model.weight_quant == "int8"
                  else quantize_lm_params(fp_params))
            draft_fp = (fp_model, fp_params)
            draft_q = (qm, qp)   # int8 legs self-draft at int8 too
        legs = {
            "paged_fp": {},
            "paged_int8": {"weight_quant": "int8"},
            "paged_fp_spec": {"spec_draft": draft_fp, "spec_k": k},
            "paged_int8_spec": {"weight_quant": "int8",
                                "spec_draft": draft_q, "spec_k": k},
        }
        matrix = {"rate": rate, "spec_k": k,
                  "paged_kernel": args.serving_paged_kernel,
                  "self_draft": args.serving_spec_draft_layers == 0}
        for name, ekw in legs.items():
            matrix[name] = _serve_rate(
                fp_model, fp_params, args, mprompts, rate,
                pipeline_depth=depth, prefill_chunk_budget=budget,
                chaos_mode=False, log=log, paged_cfg=paged_cfg,
                engine_kw=dict(ekw), label=name)
        out["spec_matrix"] = matrix
        log(f"spec matrix at rate={rate}/s: tokens/tick "
            + ", ".join(f"{n}={matrix[n]['tokens_per_tick']}"
                        for n in legs)
            + "; tpot p50 "
            + ", ".join(f"{n}={matrix[n]['tpot_ms_p50']}ms"
                        for n in legs))
    if args.serving_mesh > 1 and not chaos_mode:
        # Sharded-serving A/B (docs/serving.md "Sharded serving"): the
        # paged engine on 1 vs N mesh devices at EQUAL per-device KV
        # bytes — heads-sharded KV puts 1/N of every block on each
        # device, so the N-device pool carries N x the blocks (and N x
        # the lanes) at the unsharded leg's per-device footprint. The
        # capacity claim is the per-device-memory -> concurrency
        # trade; the token streams stay bitwise by construction
        # (pinned by tests/test_sharded_serving.py, not re-proven
        # here).
        N = args.serving_mesh
        if jax.device_count() < N:
            log(f"serving mesh A/B skipped: need {N} devices, "
                f"{jax.device_count()} visible (use --platform cpu "
                f"to force a virtual mesh)")
        else:
            rate = max(rates)
            bs = args.serving_kv_block_size
            if args.seq % bs:
                raise ValueError(
                    f"--serving-kv-block-size {bs} must divide --seq "
                    f"{args.seq} for the mesh A/B's paged legs")
            base_cfg = {"num_slots": S,
                        "kv_blocks": S * args.seq // bs + 1,
                        "kv_block_size": bs}
            sharded_cfg = {"num_slots": N * S,
                           "kv_blocks": N * S * args.seq // bs + 1,
                           "kv_block_size": bs}
            out["mesh_ab"] = {
                "rate": rate, "mesh_devices": N,
                "equal_per_device_kv_token_rows": S * args.seq,
                "unsharded": _serve_rate(
                    model, params, args, prompts, rate,
                    pipeline_depth=depth, prefill_chunk_budget=budget,
                    chaos_mode=False, log=log, paged_cfg=base_cfg,
                    label="mesh1"),
                "sharded": _serve_rate(
                    model, params, args, prompts, rate,
                    pipeline_depth=depth, prefill_chunk_budget=budget,
                    chaos_mode=False, log=log, paged_cfg=sharded_cfg,
                    engine_kw={"mesh": N}, label=f"mesh{N}"),
            }
            u = out["mesh_ab"]["unsharded"]
            s = out["mesh_ab"]["sharded"]
            log(f"mesh A/B at rate={rate}/s (equal per-device KV "
                f"bytes): 1 -> {N} devices, {u['tok_s']} -> "
                f"{s['tok_s']} tok/s, ttft p50 {u['ttft_ms_p50']} -> "
                f"{s['ttft_ms_p50']} ms, tpot p50 {u['tpot_ms_p50']} "
                f"-> {s['tpot_ms_p50']} ms, peak concurrency "
                f"{u['peak_active']} (cap {u['num_slots']}) -> "
                f"{s['peak_active']} (cap {s['num_slots']})")
    if getattr(args, "router", False):
        # Fleet-failover A/B (1 vs N replicas, with and without the
        # seeded router.replica_kill chaos) at the highest rate.
        out["router_ab"] = _router_ab(model, params, args, prompts,
                                      max(rates), log)
    if getattr(args, "disagg", False) and not chaos_mode:
        if args.seq % args.serving_kv_block_size:
            raise ValueError(
                f"--serving-kv-block-size "
                f"{args.serving_kv_block_size} must divide --seq "
                f"{args.seq} for the disagg A/B's paged pools")
        out["disagg_ab"] = _disagg_ab(model, params, args, prompts,
                                      max(rates), log)
    if getattr(args, "overload", False) and not chaos_mode:
        if args.seq % args.serving_kv_block_size:
            raise ValueError(
                f"--serving-kv-block-size "
                f"{args.serving_kv_block_size} must divide --seq "
                f"{args.seq} for the overload A/B's paged pools")
        out["overload_ab"] = _overload_ab(model, params, args,
                                          prompts, max(rates), log)
    if reqlog_path:
        from horovod_tpu.obs import reqlog as _reqlog
        rl = _reqlog.get()
        n_rec = rl.count if rl is not None else 0
        _reqlog.configure(None)   # flushes by closing below
        if rl is not None:
            rl.close()
        out["reqlog"] = {"path": reqlog_path, "requests": n_rec}
        log(f"serving: request log closed with {n_rec} arrival(s)")
        if replay_path == "self":
            # The in-artifact record -> replay round-trip: re-serve
            # the log this very run recorded.
            out["replay"] = _serve_replay(model, params, args,
                                          reqlog_path, log)
    return out


def run_resume_check(args):
    """--resume-check: the exactly-once resumable-training acceptance
    artifact (docs/resilience.md "Exact resume"). Runs the
    crash-restart equivalence harness — train a small sharded-dataset
    workload uninterrupted, then again under chaos-injected kills
    (kill-mid-epoch + kill-during-save) with restarts — and records
    the proof: bitwise-identical batch streams, params match,
    resume_gap_batches == 0, plus recovery_ms per restart. Host-side
    (numpy + checkpoint I/O), so it runs identically on any backend;
    cpu is forced unless --platform says otherwise."""
    import tempfile

    _force_platform(args.platform or "cpu")
    from horovod_tpu.resilience.equivalence import (
        run_crash_restart_equivalence)

    import shutil

    workdir = tempfile.mkdtemp(prefix="hvd_resume_check_")
    try:
        report = run_crash_restart_equivalence(workdir, log=log)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    s = report.summary()
    # Same acceptance as the module CLI: equivalent, zero gap, AND at
    # least one kill actually fired — an externally-armed monkey with
    # unrelated sites would otherwise make this a vacuous pass.
    result = {
        "metric": "crash_restart_equivalence",
        "value": 1.0 if (report.ok and report.resume_gap_batches == 0
                         and report.kills > 0) else 0.0,
        "unit": "bool",
        "vs_baseline": None,  # reference has no exact-resume story
        **s,
    }
    _set_best(result)
    emit(_BEST_RESULT)
    write_out(args)
    return 0 if result["value"] else 1


def run_elastic_check(args):
    """--elastic-check: the elastic-membership acceptance artifact
    (docs/resilience.md "Elastic membership"). Runs the resize
    equivalence harness — a 4-member in-process simulated world under
    a seeded rank_death (one member's heartbeat lease lapses
    mid-epoch; the survivors commit a new generation, roll back to
    the committed TrainSnapshot, and rebalance shards) against an
    uninterrupted control — and records the proof: the union of all
    members' effective per-record streams bitwise-equal as multisets,
    plus resize count, detection and time-to-resume p50/max, and
    records reassigned. Host-side (numpy + threads + checkpoint I/O),
    daemon-runnable like --resume-check; cpu is forced unless
    --platform says otherwise."""
    import shutil
    import tempfile

    _force_platform(args.platform or "cpu")
    from horovod_tpu.resilience.equivalence import (
        run_resize_equivalence)

    if getattr(args, "real_procs", False):
        # The REAL multi-controller drill (resilience/drill.py):
        # hvdrun worker processes over the rendezvous KV, an actual
        # SIGKILL, lease detection through the shared FailureDetector,
        # commit'd resize, union-bitwise-exact resume. detect_s /
        # time_to_resume_s here are the multi-PROCESS numbers the
        # simulated world cannot honestly produce.
        from horovod_tpu.resilience.drill import run_drill
        workdir = tempfile.mkdtemp(prefix="hvd_elastic_mc_")
        try:
            dreport = run_drill(workdir, log=log)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        result = {
            "metric": "elastic_mc_drill",
            "value": 1.0 if dreport.ok else 0.0,
            "unit": "bool",
            "vs_baseline": None,  # reference: mpirun kills the job
            **dreport.summary(),
        }
        _set_best(result)
        emit(_BEST_RESULT)
        write_out(args)
        return 0 if result["value"] else 1
    workdir = tempfile.mkdtemp(prefix="hvd_elastic_check_")
    try:
        report = run_resize_equivalence(workdir, log=log)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    s = report.summary()
    # Same acceptance shape as the module CLI: union-equivalent AND a
    # death actually fired AND a resize actually committed — an
    # externally-armed monkey with unrelated sites would otherwise
    # make this a vacuous pass.
    result = {
        "metric": "elastic_resize_equivalence",
        "value": 1.0 if report.ok else 0.0,
        "unit": "bool",
        "vs_baseline": None,  # reference kills the job on rank death
        **s,
    }
    _set_best(result)
    emit(_BEST_RESULT)
    write_out(args)
    return 0 if result["value"] else 1


def run_bert(args, devices, n_chips, log):
    """BERT-MLM pretraining throughput (tokens/sec/chip): the masked-
    LM objective on the shared encoder blocks (`models/bert.py`) —
    corrupt + forward + masked CE + grads per step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.models.bert import BertMLM, make_mlm_train_step
    from horovod_tpu.models.transformer import init_lm_state
    from horovod_tpu.parallel.mesh import make_mesh, shard_batch

    mesh = make_mesh(devices=devices, data=n_chips)
    model = BertMLM(
        vocab_size=32768, num_layers=args.layers,
        num_heads=args.heads, head_dim=args.head_dim,
        max_len=args.seq, dtype=jnp.bfloat16,
        attn_impl=args.attn_impl)
    toks = np.random.RandomState(0).randint(
        0, 32768, (args.batch * n_chips, args.seq)).astype(np.int32)
    tx = optax.adamw(3e-4)
    # Same (rng, tokens) init signature as the LM, so the LM's state
    # factory applies: params AND optimizer slots land sharded.
    params, opt_state = init_lm_state(
        model, tx, jax.random.PRNGKey(0), mesh, toks)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(params))
    log(f"bert: {n_params / 1e6:.1f}M params, seq={args.seq}, "
        f"global batch={args.batch * n_chips}")
    step = make_mlm_train_step(model, tx, mesh)
    toks_sh = shard_batch(mesh, toks)
    rng = jax.random.PRNGKey(1)

    def b_step(state, batch, _):
        params, opt_state = state
        params, opt_state, loss = step(params, opt_state, batch, rng)
        return (params, opt_state), loss

    _, _, dt, _ = time_steps(b_step, (params, opt_state), toks_sh,
                             None, args.steps, args.warmup,
                             profile_dir=args.profile)
    tokens = args.steps * args.batch * n_chips * args.seq
    d_model = args.heads * args.head_dim
    # 6N matmul + full (bidirectional) attention term 12·L·S·D.
    flops_per_tok = (6 * n_params
                     + 12 * args.layers * args.seq * d_model)
    return {"tok_s_chip": tokens / dt / n_chips,
            "flops_per_tok": flops_per_tok, "n_params": n_params,
            "step_ms": dt / args.steps * 1e3}


def run_transformer(args, devices, n_chips, log):
    """Flagship transformer-LM throughput: tokens/sec/chip with the
    Pallas flash-attention kernel in the hot path (no reference
    analogue — the long-context extension's headline number)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.models.transformer import (init_lm_state,
                                                make_lm_train_step,
                                                TransformerLM)
    from horovod_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(devices=devices, data=n_chips)
    model = TransformerLM(
        vocab_size=32768, num_layers=args.layers,
        num_heads=args.heads, num_kv_heads=args.kv_heads,
        pos_emb=args.pos_emb, window=args.window,
        head_dim=args.head_dim,
        max_len=args.seq, dtype=jnp.bfloat16,
        attn_impl=args.attn_impl, remat=args.remat,
        flash_block_q=args.flash_block_q,
        flash_block_k=args.flash_block_k, **_lm_arch_kwargs(args))
    toks = np.random.RandomState(0).randint(
        0, 32768, (args.batch * n_chips, args.seq))
    params, opt_state = init_lm_state(
        model, tx := optax.adamw(3e-4), jax.random.PRNGKey(0), mesh,
        toks)
    step_kwargs = ({"loss_chunk": args.loss_chunk}
                   if args.loss_chunk else {})
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(params))
    log(f"transformer: {n_params / 1e6:.1f}M params, seq={args.seq}, "
        f"global batch={args.batch * n_chips}")
    step = make_lm_train_step(model, tx, mesh, **step_kwargs)

    def lm_step(state, batch, rng):
        params, opt_state = state
        params, opt_state, loss = step(params, opt_state, batch)
        return (params, opt_state), loss

    _, _, dt, _ = time_steps(lm_step, (params, opt_state), toks, None,
                             args.steps, args.warmup,
                             profile_dir=args.profile)

    tokens = args.steps * args.batch * n_chips * args.seq
    tok_s_chip = tokens / dt / n_chips
    # 6·N·T (fwd+bwd matmul flops) + causal attention term
    # 12·L·S·D·T/2; coarse analytic, stated as an estimate.
    d_model = args.heads * args.head_dim
    flops_per_tok = 6 * n_params + 6 * args.layers * args.seq * d_model
    return {"tok_s_chip": tok_s_chip, "flops_per_tok": flops_per_tok,
            "n_params": n_params,
            "step_ms": dt / args.steps * 1e3}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None,
                    choices=["resnet50", "resnet101", "vgg16",
                             "inception3", "vit", "mnist",
                             "transformer", "bert"],
                    help="single model to bench; omitted (the driver "
                         "default) = resnet101 plus an --all-models "
                         "pass over the other BASELINE.md models")
    ap.add_argument("--all-models", action="store_true",
                    help="after the primary model, also time "
                         "resnet101+s2d, inception3, vgg16 (each "
                         "failure-isolated; one JSON line per model)")
    ap.add_argument("--bn-sample", type=int, default=1,
                    help="BN statistics from batch[:B/N] "
                         "(SampledBatchNorm) — the measured-37.8%%-of-"
                         "step BN stat traffic lever (docs/mfu.md); "
                         "resnet/inception only")
    ap.add_argument("--stem", default="plain", choices=["plain", "s2d"],
                    help="resnet/inception stem: plain conv or the "
                         "numerically-identical space-to-depth re-pack "
                         "(MXU-friendly; docs/mfu.md culprit #1)")
    ap.add_argument("--batch", type=int, default=None,
                    help="per-chip batch size (default: 128 for CNNs, "
                         "8 for the transformer)")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--fusion-threshold", type=int, default=None)
    ap.add_argument("--sweep-fusion", default=None, metavar="B0,B1,...",
                    help="comma list of fusion thresholds (bytes); "
                         "times each and reports all in one JSON")
    ap.add_argument("--sweep-batch", default=None, metavar="B0,B1,...",
                    help="comma list of per-chip batch sizes; times "
                         "each (OOM tolerated), reports all + picks "
                         "the best (the first knob of the MFU hunt)")
    ap.add_argument("--no-flash", action="store_true",
                    help="skip the Pallas flash-attention hardware "
                         "proof")
    ap.add_argument("--platform", default=None,
                    help="select a jax platform by name; `cpu` is the "
                         "explicit opt-in for functional smoke runs "
                         "of the harness — without it the bench "
                         "refuses to run on CPU devices")
    ap.add_argument("--remat", action="store_true",
                    help="jax.checkpoint the forward (fit larger batch)")
    ap.add_argument("--seq", type=int, default=2048,
                    help="transformer sequence length")
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="GQA: fewer K/V heads (shrinks the KV cache)")
    ap.add_argument("--pos-emb", default=None,
                    choices=["learned", "rope"],
                    help="default: learned (gpt arch) / rope (llama)")
    ap.add_argument("--window", type=int, default=None,
                    help="sliding-window attention span")
    # head_dim 128 fills the MXU lanes — measured 1.56x over 64.
    ap.add_argument("--head-dim", type=int, default=128)
    # Mirrors models.transformer.ATTN_IMPLS by hand (argument parsing
    # stays free of the jax import). On the bench's data-only mesh the
    # SP impls run their real shard_map path at seq degree 1 — e.g.
    # ring_flash times the Pallas kernel, it is NOT a blockwise
    # fallback (that branch only triggers with no ambient mesh).
    ap.add_argument("--attn-impl", default="flash",
                    choices=["dot", "blockwise", "flash", "ring",
                             "ring_flash", "ulysses", "ulysses_flash"])
    ap.add_argument("--loss-chunk", type=int, default=512,
                    help="transformer: fused head+loss scanned over "
                         "seq chunks — avoids materializing the "
                         "[B,S,V] logits (2.1 GB bf16 at B16/S2048/"
                         "V32k, the LM's largest activation); 0 = "
                         "plain full-logits loss (A/B control)")
    ap.add_argument("--decode", action="store_true",
                    help="transformer: benchmark KV-cache inference "
                         "(generate) instead of training")
    ap.add_argument("--serving", action="store_true",
                    help="transformer: benchmark the continuous-"
                         "batching ServingEngine under open-loop "
                         "Poisson arrivals (tokens/s + TTFT/TPOT "
                         "p50/p95 per --arrival-rates point)")
    ap.add_argument("--serving-slots", type=int, default=8,
                    help="serving: decode-slot pool width S")
    ap.add_argument("--serving-requests", type=int, default=24,
                    help="serving: requests submitted per rate point")
    ap.add_argument("--serving-max-prompt", type=int, default=64,
                    help="serving: prompt lengths sample [4, this) "
                         "(clamped to seq - decode_steps + 1); raise "
                         "it to make long-prompt admission churn — "
                         "what interleaved chunked prefill exists "
                         "for — visible in the TPOT histogram")
    ap.add_argument("--serving-pipeline-depth", type=int, default=1,
                    choices=[0, 1],
                    help="serving: decode-tick pipeline depth (1 = "
                         "one-deep async in-flight ring, 0 = sync "
                         "every tick — the PR-1-shaped control)")
    ap.add_argument("--prefill-chunk-budget", type=int, default=128,
                    help="serving: max prompt tokens streamed per "
                         "scheduler step (interleaved chunked "
                         "prefill; 0 = whole prompt at once). Env "
                         "parity: HVD_PREFILL_CHUNK_BUDGET")
    ap.add_argument("--no-serving-ab", dest="serving_ab",
                    action="store_false", default=True,
                    help="serving: skip the in-artifact pipelined-vs-"
                         "control A/B at the highest rate")
    ap.add_argument("--serving-shared-prefix", type=float, default=0.0,
                    metavar="FRAC",
                    help="serving: fraction of requests sharing one "
                         "system prompt (paged-KV workload mix); > 0 "
                         "adds a paged-vs-fixed A/B at the highest "
                         "rate (prefix hit rate, cache-hit vs cold "
                         "TTFT, effective concurrency at equal KV "
                         "bytes) to the artifact (docs/serving.md "
                         "'Paged KV cache')")
    ap.add_argument("--serving-prefix-len", type=int, default=32,
                    metavar="TOKENS",
                    help="serving: shared system-prompt length for "
                         "--serving-shared-prefix (block-aligned "
                         "skips want a multiple of the KV block "
                         "size)")
    ap.add_argument("--serving-kv-block-size", type=int, default=16,
                    help="serving: paged-KV block size in tokens for "
                         "the paged A/B leg (HVD_KV_BLOCK_SIZE "
                         "parity)")
    ap.add_argument("--serving-spec-k", type=int, default=0,
                    metavar="K",
                    help="serving: > 0 adds the decode-fast-path A/B "
                         "matrix at the highest rate — paged x "
                         "{fp,int8 weights} x {spec off, spec on at "
                         "K proposals/round} — recording "
                         "accepted-tokens-per-tick, acceptance rate "
                         "and TPOT per config (HVD_SPEC_K parity; "
                         "docs/serving.md 'Decode fast path')")
    ap.add_argument("--serving-spec-draft-layers", type=int, default=0,
                    metavar="N",
                    help="serving: draft depth for the spec legs — 0 "
                         "(default) self-drafts with the target "
                         "itself (the acceptance CEILING: measures "
                         "round mechanics at acceptance 1.0), N >= 1 "
                         "builds a random N-layer draft (realistic "
                         "plumbing, chance-level acceptance on "
                         "random weights)")
    ap.add_argument("--serving-weight-quant", default="",
                    choices=["", "int8"],
                    help="serving: weight-only quantization for the "
                         "MAIN serving legs (the spec matrix always "
                         "runs both fp and int8; HVD_WEIGHT_QUANT "
                         "parity)")
    ap.add_argument("--serving-paged-kernel", default="auto",
                    choices=["auto", "off", "lax", "pallas"],
                    help="serving: paged-attention dispatch for every "
                         "paged leg (HVD_PAGED_KERNEL parity; 'off' "
                         "= the legacy full-span gather)")
    ap.add_argument("--serving-mesh", type=int, default=0,
                    metavar="N",
                    help="serving: > 1 adds the sharded-serving A/B "
                         "at the highest rate — the paged engine on "
                         "1 vs N mesh devices at EQUAL per-device KV "
                         "bytes (the N-device pool carries N x the "
                         "blocks and lanes, each shard holding the "
                         "same bytes as the unsharded pool) — "
                         "recording TTFT/TPOT, tokens/s and peak "
                         "concurrency per leg. With --platform cpu "
                         "the virtual device count is forced to N "
                         "(HVD_SERVE_MESH parity; docs/serving.md "
                         "'Sharded serving')")
    ap.add_argument("--router", action="store_true",
                    help="serving: add the fleet-failover A/B — "
                         "ServingRouter over 1 vs --router-replicas "
                         "engine replicas, each with and without the "
                         "seeded router.replica_kill chaos; records "
                         "migrations, failover counts and the "
                         "token-exact-vs-no-chaos bit "
                         "(docs/serving.md 'Fleet failover')")
    ap.add_argument("--router-replicas", type=int, default=3,
                    help="serving: fleet width for the --router A/B "
                         "(HVD_ROUTER_REPLICAS parity)")
    ap.add_argument("--disagg", action="store_true",
                    help="serving: add the disaggregated prefill/"
                         "decode A/B at the highest rate — 2 shared-"
                         "program replicas vs a prefill pool + decode "
                         "pool with KV-block handoffs (equal engine "
                         "count, equal paged KV geometry); records "
                         "TTFT/TPOT per leg, handoff/fallback counts "
                         "and the bitwise-vs-baseline bit "
                         "(HVD_DISAGG parity; docs/serving.md "
                         "'Disaggregated serving')")
    ap.add_argument("--overload", action="store_true",
                    help="serving: add the overload-control A/B at "
                         "the highest rate — shed-only vs token-exact "
                         "KV preemption on an EQUAL undersized paged "
                         "pool, a priority-5 'paid' trickle against a "
                         "priority-0 'free' flood; records paid-"
                         "tenant TTFT, swap/recompute preemption "
                         "counts, the starvation-free bit and the "
                         "preempt-resume-bitwise bit (HVD_PREEMPT "
                         "parity; docs/serving.md 'Overload "
                         "control')")
    ap.add_argument("--serving-slo",
                    default="ttft=30,tpot=5,shed=0.1,target=0.9,"
                            "fast=5,slow=60,burn=5",
                    metavar="SPEC",
                    help="serving: SLO objective spec (HVD_SLO "
                         "grammar) evaluated per rate point; the "
                         "artifact's `slo` block records objectives, "
                         "burn rates and the breach count (default "
                         "thresholds generous enough to stay green "
                         "on the CPU proxy, with burn=5 so a breach "
                         "stays REACHABLE at the 0.1 budgets; empty "
                         "string disables)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="also write the final result JSON to PATH "
                         "(e.g. BENCH_serving_pr3.json)")
    ap.add_argument("--arrival-rates", default="2,6,12",
                    metavar="R0,R1,...",
                    help="serving: open-loop arrival rates (req/s)")
    ap.add_argument("--record-reqlog", default=None, metavar="PATH",
                    help="serving: record every client arrival to a "
                         "request log at PATH (obs/reqlog.py JSONL; "
                         "programmatic twin of HVD_REQLOG) for later "
                         "--replay")
    ap.add_argument("--replay", default=None, metavar="LOG",
                    help="serving: re-serve a recorded request log "
                         "open-loop at the RECORDED arrival offsets "
                         "instead of the Poisson sweep — prompts are "
                         "synthesized from the log's prefix-chain "
                         "digests, so the recorded prefix-sharing "
                         "structure (and cache hit pattern) is "
                         "reproduced; token budgets, tenants and "
                         "priorities are the recorded ones. The "
                         "special value 'self' runs the normal sweep "
                         "with --record-reqlog, then replays the log "
                         "it just recorded (the in-artifact "
                         "round-trip)")
    ap.add_argument("--replay-speed", type=float, default=1.0,
                    metavar="X",
                    help="serving: replay time compression — "
                         "recorded arrival offsets are divided by "
                         "this (2.0 = twice as fast)")
    ap.add_argument("--chaos", action="store_true",
                    help="serving: self-healing cost mode — inject "
                         "one dispatch-thread crash per rate point "
                         "(engine runs with auto_restart) and record "
                         "recovery latency (time-to-requeue p50/p95) "
                         "plus restart/requeue counts in the BENCH "
                         "json (docs/resilience.md)")
    ap.add_argument("--decode-steps", type=int, default=256)
    ap.add_argument("--decode-prefix-block", type=int, default=256,
                    help="decode reads the filled cache prefix in "
                         "slices this big instead of masking against "
                         "all max_len slots (0 = cache-wide path; the "
                         "r4 10ms/tick suspect A/B)")
    ap.add_argument("--decode-prefix-impl", default=None,
                    choices=["lax", "pallas"],
                    help="force the prefix-attention engine: the lax "
                         "fori_loop (oracle) or the ragged Pallas "
                         "flash-decode kernel; default: the code "
                         "chooses (the kernel on a TPU)")
    ap.add_argument("--no-serve-cast", dest="serve_cast",
                    action="store_false", default=True,
                    help="keep decode params stored-f32 (double the "
                         "weight HBM bytes per tick) instead of "
                         "pre-casting matrices to bf16")
    ap.add_argument("--deadline", type=float, default=2700.0,
                    help="global wall-clock budget (s) enforced by a "
                         "watchdog thread that re-emits the best "
                         "completed result as the final line if a "
                         "later pass overruns; 0 disables")
    ap.add_argument("--weight-quant", default=None,
                    choices=["int8"],
                    help="weight-only quantization for --decode "
                         "(block kernels int8 + per-channel scales)")
    ap.add_argument("--kv-quant", default=None, choices=["int8"],
                    help="int8 decode KV cache (per-(position, head) "
                         "scales; 2x context per byte of cache HBM)")
    ap.add_argument("--arch", default="gpt", choices=["gpt", "llama"],
                    help="LM architecture preset: gpt (LayerNorm/gelu/"
                         "tied head) or llama (RMSNorm/fused SwiGLU/"
                         "untied head, RoPE default)")
    ap.add_argument("--flash-block-q", type=int, default=None,
                    help="Pallas flash kernel q-tile (LM, "
                         "--attn-impl flash only; default: chosen "
                         "from the shape by the kernel's plan; an "
                         "integer to sweep on hardware)")
    ap.add_argument("--flash-block-k", type=int, default=None,
                    help="Pallas flash kernel k-tile (LM, "
                         "--attn-impl flash only; default: from the "
                         "shape)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the timed "
                         "steps into DIR (overlap/MFU analysis)")
    ap.add_argument("--resume-check", action="store_true",
                    help="run the crash-restart equivalence harness "
                         "(exactly-once resumable training) and emit "
                         "its report as the artifact: batch streams "
                         "bitwise-identical across chaos-injected "
                         "kills+restarts, resume_gap_batches == 0, "
                         "recovery_ms recorded (docs/resilience.md)")
    ap.add_argument("--elastic-check", action="store_true",
                    help="run the elastic resize-equivalence harness "
                         "(membership: rank_death -> shrink -> shard "
                         "rebalance) and emit its report as the "
                         "artifact: union record stream bitwise-equal "
                         "to an uninterrupted run, resize count, "
                         "time-to-resume p50/max, records reassigned "
                         "(docs/resilience.md 'Elastic membership')")
    ap.add_argument("--real-procs", action="store_true",
                    help="with --elastic-check: run the REAL "
                         "multi-controller drill instead of the "
                         "in-process simulated world — hvdrun-"
                         "launched worker processes over the "
                         "rendezvous KV server, a real SIGKILL of "
                         "one worker, survivors detect -> resize -> "
                         "exact resume; records detect_s and "
                         "time_to_resume_s for the multi-process "
                         "path (resilience/drill.py)")
    args = ap.parse_args()

    if args.serving and args.serving_mesh > 1 and args.platform == "cpu":
        # The sharded-serving A/B needs N visible CPU devices, and
        # --xla_force_host_platform_device_count only takes effect
        # before the backend initializes — this runs ahead of the
        # lazy jax import below (the same window tests/conftest.py
        # uses for its virtual 8-device mesh).
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{args.serving_mesh}").strip()

    if args.resume_check:
        sys.exit(run_resume_check(args))
    if args.elastic_check:
        sys.exit(run_elastic_check(args))

    if args.model is None:  # driver default: full BASELINE.md coverage
        args.model = "resnet101"
        args.all_models = True

    is_lm = args.model == "transformer"
    is_bert = args.model == "bert"
    if args.batch is None:
        args.batch = 8 if (is_lm or is_bert) else 128
    # Resolve the --arch preset ONCE: only the causal LM (train and
    # decode) honors it; anything else must fail loudly, not record a
    # preset it never applied.
    if args.arch != "gpt" and not is_lm:
        fail("bert_tokens_per_sec_per_chip" if is_bert else
             f"{args.model}_images_per_sec_per_chip",
             "tokens/sec/chip" if is_bert else "images/sec/chip",
             "bad_arguments",
             f"--arch {args.arch} applies to --model transformer only")
    if args.pos_emb is None:
        args.pos_emb = "rope" if args.arch == "llama" else "learned"
    if args.serving and not is_lm:
        fail(f"{args.model}_images_per_sec_per_chip",
             "images/sec/chip", "bad_arguments",
             "--serving applies to --model transformer only")
    if is_bert:
        metric, unit = "bert_tokens_per_sec_per_chip", "tokens/sec/chip"
    else:
        metric = (("transformer_serving_tokens_per_sec_per_chip"
                   if args.serving
                   else "transformer_decode_tokens_per_sec_per_chip"
                   if args.decode
                   else "transformer_tokens_per_sec_per_chip")
                  if is_lm else f"{args.model}_images_per_sec_per_chip")
        unit = "tokens/sec/chip" if is_lm else "images/sec/chip"

    if args.deadline > 0:
        start_deadline_watchdog(metric, unit, args.deadline)

    from horovod_tpu.runtime.config import env_raw, env_str
    launched = (env_raw("HOROVOD_RANK") is not None
                or bool(env_str("HOROVOD_PLATFORM")))
    if not launched:
        # Under hvdrun, hvd.init() owns backend bring-up (platform
        # selection + jax.distributed.initialize).
        _force_platform(args.platform)

    try:
        import jax

        import horovod_tpu as hvd

        hvd.init()
        n_chips = hvd.size()
        devices = jax.devices()
        platform = devices[0].platform
        device_kind = getattr(devices[0], "device_kind", platform)
        log(f"devices: {devices} (platform={platform}, "
            f"kind={device_kind}, world={n_chips})")
        if (platform == "cpu" and args.platform != "cpu"
                and env_str("HOROVOD_PLATFORM") != "cpu"):
            # No fall-back: a CPU timing is not a device metric.
            log("bench.py: no accelerator — JAX reports only CPU "
                "devices. Run it where a TPU is attached, or pass "
                "--platform cpu for a functional smoke run of the "
                "harness.")
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(2)
        _bench_body(args, devices, n_chips, metric, unit,
                    platform, device_kind)
    except SystemExit:
        raise
    except Exception as e:  # noqa: BLE001 — diagnostic path
        import traceback
        traceback.print_exc(file=sys.stderr)
        fail(metric, unit, "benchmark_failed", repr(e))


def _make_cnn_model(args, name, stem):
    """(model, input shape, num_classes) for a CNN benchmark config."""
    import jax.numpy as jnp

    from horovod_tpu import models
    if args.bn_sample != 1 and name not in (
            "resnet50", "resnet101", "inception3"):
        raise ValueError(
            f"--bn-sample applies to the BatchNorm CNNs only, "
            f"not {name}")
    if name == "mnist":
        return (models.MnistConvNet(dtype=jnp.float32),
                (1, 28, 28, 1), 10)
    if name == "vgg16":
        return (models.VGG16(num_classes=1000),
                (1, args.image_size, args.image_size, 3), 1000)
    if name == "inception3":
        return (models.InceptionV3(num_classes=1000,
                                   s2d_stem=(stem == "s2d"),
                                   bn_sample=args.bn_sample),
                (1, max(args.image_size, 299),
                 max(args.image_size, 299), 3), 1000)
    if name == "vit":
        return (models.ViT_B16(num_classes=1000),
                (1, args.image_size, args.image_size, 3), 1000)
    cls = (models.ResNet50 if name == "resnet50" else models.ResNet101)
    return (cls(num_classes=1000, s2d_stem=(stem == "s2d"),
                bn_sample=args.bn_sample),
            (1, args.image_size, args.image_size, 3), 1000)


def _cnn_bench(args, name, stem, n_chips):
    """Build one CNN config and return its `run(threshold, batch=None,
    steps=None)` timing closure (img/s global). State init happens
    here, once; each run clones it (the train step donates buffers)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.models import make_cnn_train_step
    from horovod_tpu.models.train import init_cnn_state

    model, shape, num_classes = _make_cnn_model(args, name, stem)
    tx = optax.sgd(0.1, momentum=0.9)
    rng = jax.random.PRNGKey(0)
    log(f"initializing {name} ({stem} stem) params...")
    state = init_cnn_state(model, tx, rng,
                           jnp.zeros(shape, jnp.bfloat16))
    # ViT blocks carry TP partition annotations, which need the
    # full-axes mesh (size-1 defaults) rather than init()'s 1-D mesh.
    mesh = None
    if name == "vit":
        from horovod_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(devices=jax.devices()[:n_chips],
                         data=n_chips)

    _batches = {}  # per-chip size -> device arrays (fusion sweeps
    # reuse the same batch; only the batch sweep builds new shapes)

    def make_batch(per_chip):
        if per_chip not in _batches:
            gb = per_chip * n_chips
            x = np.random.RandomState(0).randn(
                gb, *shape[1:]).astype(np.float32)
            y = np.random.RandomState(1).randint(
                0, num_classes, size=(gb,))
            _batches[per_chip] = (jnp.asarray(x, jnp.bfloat16),
                                  jnp.asarray(y))
        return _batches[per_chip]

    def run(threshold, batch=None, steps=None, warmup=None,
            profile=True):
        steps = args.steps if steps is None else steps
        step = make_cnn_train_step(model, tx, mesh=mesh,
                                   fusion_threshold=threshold,
                                   remat=args.remat)
        xb, yb = make_batch(args.batch if batch is None else batch)
        gb = xb.shape[0]
        # Fresh state per run: the step donates its input buffers,
        # so a sweep's second run would otherwise read deleted
        # arrays.
        st0 = jax.tree.map(jnp.array, state)
        st, loss, dt, compile_s = time_steps(
            step, st0, (xb, yb), rng, steps,
            args.warmup if warmup is None else warmup,
            profile_dir=args.profile if profile else None)
        img_s = steps * gb / dt
        log(f"{name}[{stem}] thr={threshold} b={gb // n_chips}: "
            f"{img_s:.1f} img/s ({img_s / n_chips:.1f}/chip, "
            f"step {dt / steps * 1e3:.1f} ms, "
            f"warmup {compile_s:.1f}s, loss={loss:.3f})")
        return img_s

    run.shape = shape
    return run


def _measured_overlap(args):
    """Measured exposed-collective fraction α from the --profile trace
    (utils/profile_analysis) — None off-profile or when the capture has
    no device timeline (CPU backend). Replaces docs/scaling.md's
    modeled α=0.3 with a measurement whenever a profiled run lands.
    Bounded to traces written by THIS invocation (`_bench_t0`): a
    reused profile dir must not hand back yesterday's capture."""
    if not args.profile:
        return None
    from horovod_tpu.utils.profile_analysis import analyze_profile_dir
    try:
        r = analyze_profile_dir(args.profile,
                                min_mtime=getattr(args, "_bench_t0",
                                                  None))
    except Exception as e:  # noqa: BLE001 — diagnostics must not kill
        log(f"overlap analysis failed: {e!r}")
        return None
    if r is not None:
        log(f"measured overlap: alpha={r['alpha']} "
            f"(comm {r['t_comm_us']}us, exposed "
            f"{r['t_comm_exposed_us']}us over {r['n_collectives']} "
            f"collectives)")
    return r


def _cnn_mfu(name, shape, img_s_chip, device_kind):
    """Analytic-FLOPs MFU estimate (coarse but honest; docs/mfu.md) —
    the FLOP/s over the shared peak table via profile_analysis.mfu,
    the same math the obs plane's hvd_training_mfu gauge uses."""
    from horovod_tpu.utils.profile_analysis import mfu
    if name not in TRAIN_GFLOPS_PER_IMG:
        return None
    base = 299 if name == "inception3" else 224
    scale = 1.0 if name == "mnist" else (shape[1] / base) ** 2
    return mfu(img_s_chip * TRAIN_GFLOPS_PER_IMG[name] * scale * 1e9,
               device_kind)


def _bench_body(args, devices, n_chips, metric, unit,
                platform, device_kind):
    args._bench_t0 = time.time()  # staleness bound for --profile traces
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu import models
    from horovod_tpu.models import make_cnn_train_step
    from horovod_tpu.models.train import init_cnn_state

    # Warm-start fast pass FIRST: for a CNN primary, a tiny
    # configuration (batch 32, 1 warmup + 2 steps) of the SAME model
    # is timed and emitted as a real model number early — so if the
    # full-size pass below overruns the deadline, the driver's final
    # line is a measured throughput, not a zero. The full pass then
    # overwrites best, reusing the fast pass's model init (the
    # dominant fixed cost).
    cnn_run = None
    if (args.model not in ("transformer", "bert", "mnist")
            and not (args.sweep_batch or args.sweep_fusion)
            and args.batch > 32):
        cnn_run = _cnn_bench(args, args.model, args.stem, n_chips)
        try:
            v = cnn_run(args.fusion_threshold, batch=32, steps=2,
                        warmup=1, profile=False) / n_chips
        except Exception as e:  # noqa: BLE001 — the full pass decides
            log(f"warm-start pass failed: {e!r}")
        else:
            warm = {
                "metric": metric, "value": round(v, 2), "unit": unit,
                "vs_baseline": round(v / P100_RESNET101_IMG_S, 3)
                if args.model == "resnet101" else None,
                "platform": platform, "device_kind": device_kind,
                "chips": n_chips, "per_chip_batch": 32,
                "stem": args.stem, "warm_start": True,
                "mfu_estimate": _cnn_mfu(args.model, cnn_run.shape,
                                         v, device_kind),
            }
            _set_best(warm)
            emit(warm)

    # Flash-attention hardware proof next, as its own emitted JSON
    # line: the cheapest driver-visible artifact, so the hot kernel's
    # on-chip timing survives in the output tail even if the heavy
    # model bench below times out. The final model line is still the
    # LAST line (what the driver parses).
    flash_ms = flash_err = impl = None
    if not args.no_flash:
        try:
            flash_ms, impl = flash_attention_proof(platform)
        except Exception as e:  # noqa: BLE001 — report, don't die
            flash_err = repr(e)
            log(f"flash proof failed: {flash_err}")
        if flash_ms is not None:
            emit({"metric": "flash_attn_fwd_bwd_ms", "value": flash_ms,
                  "unit": "ms", "vs_baseline": None,
                  "platform": platform, "device_kind": device_kind,
                  "bwd_impl": impl,
                  "shape": "B4 S2048 H8 D128 bf16 causal"})

    is_lm = args.model == "transformer"
    if (is_lm or args.model == "bert") and args.all_models:
        log("--all-models applies to CNN primaries only; "
            f"ignored with --model {args.model}")
    if args.model == "bert" and args.decode:
        log("--decode applies to the causal LM only; ignored with "
            "--model bert (BertMLM has no autoregressive cache)")
    if args.model == "bert":
        r = run_bert(args, devices, n_chips, log)
        peak = _peak_bf16().get(device_kind)
        _set_best({
            "metric": metric,
            "value": round(r["tok_s_chip"], 1),
            "unit": unit,
            "vs_baseline": None,  # no MLM in the reference (2017)
            "platform": platform,
            "device_kind": device_kind,
            "chips": n_chips,
            "per_chip_batch": args.batch,
            "seq": args.seq,
            "params_m": round(r["n_params"] / 1e6, 1),
            "step_ms": round(r["step_ms"], 1),
            "attn_impl": args.attn_impl,
            "arch": args.arch,
            "mfu_estimate": round(
                r["tok_s_chip"] * r["flops_per_tok"] / peak, 4)
            if peak else None,
            "overlap_measured": _measured_overlap(args),
        })
        emit(_BEST_RESULT)
        write_out(args)
        return
    if is_lm and args.serving:
        r = run_serving(args, devices, n_chips, log)
        result = {
            "metric": metric,
            "value": round(r["tok_s_chip"], 1),
            "unit": unit,
            "vs_baseline": None,  # reference has no serving path
            "platform": platform,
            "device_kind": device_kind,
            "chips": 1,  # the engine runs on the default device
            "num_slots": r["num_slots"],
            "max_new_tokens": r["max_new_tokens"],
            "requests_per_rate": r["requests_per_rate"],
            "seq": args.seq,
            "params_m": round(r["n_params"] / 1e6, 1),
            "pipeline_depth": r["pipeline_depth"],
            "prefill_chunk_budget": r["prefill_chunk_budget"],
            "rates": r["rates"],
            "trace_check": r["trace_check"],
            "arch": args.arch,
        }
        if "slo" in r:
            # The SLO acceptance block (obs/slo.py): objectives, burn
            # rates, breach count at the highest rate point.
            result["slo"] = r["slo"]
        if "pipeline_ab" in r:
            result["pipeline_ab"] = r["pipeline_ab"]
        if "paged_ab" in r:
            result["paged_ab"] = r["paged_ab"]
            result["serving_shared_prefix"] = args.serving_shared_prefix
        if "spec_matrix" in r:
            # The decode-fast-path A/B matrix (docs/serving.md
            # "Decode fast path"): paged x {fp, int8 weights} x
            # {spec off, spec on} — accepted tokens/tick, acceptance
            # rate and TPOT per leg.
            result["spec_matrix"] = r["spec_matrix"]
        if "mesh_ab" in r:
            # The sharded-serving A/B (docs/serving.md "Sharded
            # serving"): 1 vs N mesh devices at equal per-device KV
            # bytes — TTFT/TPOT, tokens/s, peak concurrency per leg.
            result["mesh_ab"] = r["mesh_ab"]
            result["serving_mesh"] = args.serving_mesh
        if "router_ab" in r:
            # The fleet-failover A/B (docs/serving.md "Fleet
            # failover"): 1 vs N replicas, each +/- the seeded
            # router.replica_kill chaos, incl. the token-exact bit.
            result["router_ab"] = r["router_ab"]
            result["router_replicas"] = args.router_replicas
        if "disagg_ab" in r:
            # The disaggregated prefill/decode A/B (docs/serving.md
            # "Disaggregated serving"): shared-program fleet vs
            # prefill pool + decode pool with KV-block handoffs at
            # equal engine count, incl. the bitwise-vs-baseline bit.
            result["disagg_ab"] = r["disagg_ab"]
        if "overload_ab" in r:
            # The overload-control A/B (docs/serving.md "Overload
            # control"): shed-only vs token-exact preemption on an
            # equal undersized pool — paid-tenant TTFT, preemption
            # counts, the starvation-free and bitwise bits.
            result["overload_ab"] = r["overload_ab"]
        if "reqlog" in r:
            # Where --record-reqlog wrote the request log, and how
            # many client arrivals it captured.
            result["reqlog"] = r["reqlog"]
        if "replay" in r:
            # The record/replay leg (docs/observability.md
            # "Record/replay"): round-trip bits + perf of re-serving
            # the recorded workload shape.
            result["replay"] = r["replay"]
        _set_best(result)
        emit(_BEST_RESULT)
        write_out(args)
        return
    if is_lm and args.decode:
        r = run_decode(args, devices, n_chips, log)
        _set_best({
            "metric": metric,
            "value": round(r["tok_s_chip"], 1),
            "unit": unit,
            "vs_baseline": None,  # reference has no inference path
            "platform": platform,
            "device_kind": device_kind,
            "chips": 1,  # decode runs on the default device only
            "per_chip_batch": args.batch,
            "seq": args.seq,
            "params_m": round(r["n_params"] / 1e6, 1),
            "ms_per_tick": round(r["ms_per_tick"], 2),
            "roofline_ms_per_tick": round(
                r["hbm_bytes_per_tick"]
                / (HBM_GBPS[device_kind] * 1e9) * 1e3, 3)
            if device_kind in HBM_GBPS else None,
            "decode_prefix_block": r["decode_prefix_block"],
            "decode_prefix_impl": r["decode_prefix_impl"],
            "serve_cast": r["serve_cast"],
            "decode_steps": args.decode_steps,
            "weight_quant": args.weight_quant,
            "kv_quant": args.kv_quant,
            "arch": args.arch,
            "overlap_measured": _measured_overlap(args),
        })
        emit(_BEST_RESULT)
        write_out(args)
        return
    if is_lm:
        r = run_transformer(args, devices, n_chips, log)
        peak = _peak_bf16().get(device_kind)
        _set_best({
            "metric": metric,
            "value": round(r["tok_s_chip"], 1),
            "unit": unit,
            "vs_baseline": None,  # no LM in the reference (2017)
            "platform": platform,
            "device_kind": device_kind,
            "chips": n_chips,
            "per_chip_batch": args.batch,
            "seq": args.seq,
            "params_m": round(r["n_params"] / 1e6, 1),
            "step_ms": round(r["step_ms"], 1),
            "attn_impl": args.attn_impl,
            "arch": args.arch,
            "mfu_estimate": round(
                r["tok_s_chip"] * r["flops_per_tok"] / peak, 4)
            if peak else None,
            "overlap_measured": _measured_overlap(args),
        })
        emit(_BEST_RESULT)
        write_out(args)
        return

    # Reuse the warm start's init (params + opt state) for the full
    # pass; only sweeps and the LM paths build their own.
    run = cnn_run if cnn_run is not None else _cnn_bench(
        args, args.model, args.stem, n_chips)

    sweep = batch_sweep = None
    if args.sweep_batch:
        # Per-chip batch sweep — the first knob of the MFU hunt: a too-
        # small batch underfills the MXU, a too-large one spills HBM
        # into remat-less recompute or OOM. One invocation, one JSON.
        batch_sweep = {}
        best = (None, -1.0)
        for tok in args.sweep_batch.split(","):
            b = int(tok)
            try:
                r = run(args.fusion_threshold, batch=b) / n_chips
            except Exception as e:  # noqa: BLE001 — see filter below
                # Only a genuine capacity failure marks the size as
                # infeasible; anything else is a bug and propagates.
                msg = repr(e)
                if not any(t in msg for t in (
                        "RESOURCE_EXHAUSTED", "Out of memory",
                        "out of memory", "OOM")):
                    raise
                log(f"batch {b} OOM: {msg[:200]}")
                batch_sweep[str(b)] = None
                continue
            batch_sweep[str(b)] = round(r, 2)
            if r > best[1]:
                best = (b, r)
        if best[0] is None:
            raise RuntimeError(f"every batch failed: {batch_sweep}")
        args.batch = best[0]
        img_s_chip = best[1]
    if args.sweep_fusion:
        sweep = {}
        for tok in args.sweep_fusion.split(","):
            thr = int(tok)
            sweep[str(thr)] = round(run(thr) / n_chips, 2)
        img_s_chip = max(sweep.values())
    elif batch_sweep is None:
        img_s_chip = run(args.fusion_threshold) / n_chips

    # MFU estimate: analytic training FLOPs over the chip's bf16
    # peak — coarse but honest.
    result = {
        "metric": metric,
        "value": round(img_s_chip, 2),
        "unit": unit,
        "vs_baseline": round(img_s_chip / P100_RESNET101_IMG_S, 3)
        if args.model == "resnet101" else None,
        "platform": platform,
        "device_kind": device_kind,
        "chips": n_chips,
        "per_chip_batch": args.batch,
        "stem": args.stem,
        "bn_sample": args.bn_sample,
        "mfu_estimate": _cnn_mfu(args.model, run.shape, img_s_chip,
                                 device_kind),
        # Sweeps write one trace per configuration and the newest need
        # not be the headline config — an alpha from a different fusion
        # threshold/batch would misattribute, so only the single-config
        # run reports it.
        "overlap_measured": (
            None if (args.sweep_fusion or args.sweep_batch)
            else _measured_overlap(args)),
    }
    if sweep is not None:
        result["sweep_fusion_img_s_per_chip"] = sweep
    if batch_sweep is not None:
        result["sweep_batch_img_s_per_chip"] = batch_sweep
    if flash_ms is not None:
        result["flash_attn_ms"] = flash_ms
    if flash_err is not None:
        result["flash_attn_error"] = flash_err
    _set_best(result)
    if not args.all_models:
        emit(result)
        write_out(args)
        return

    # --all-models (the no-args driver default): one run yields
    # every BASELINE.md model plus the s2d-stem variant, each as its
    # OWN emitted line so a
    # late failure can't erase earlier numbers; the final line is the
    # primary metric again, augmented with the extras, because the
    # driver parses the LAST line.
    emit(result)  # primary survives even if an extra dies below
    run = None  # drop the primary's params/opt-state/batches from HBM
    extras = {}
    for name, stem in (("resnet101", "s2d"), ("inception3", "plain"),
                       ("vgg16", "plain")):
        if (name, stem) == (args.model, args.stem):
            continue  # already timed as the primary
        key = name if stem == "plain" else f"{name}_{stem}"
        r = None
        try:
            r = _cnn_bench(args, name, stem, n_chips)
            v = r(args.fusion_threshold) / n_chips
            extras[key] = {
                "img_s_per_chip": round(v, 2),
                "mfu_estimate": _cnn_mfu(name, r.shape, v, device_kind),
            }
            emit({"metric": f"{key}_images_per_sec_per_chip",
                  "value": round(v, 2), "unit": unit,
                  "vs_baseline": None, "platform": platform,
                  "device_kind": device_kind, "chips": n_chips,
                  "per_chip_batch": args.batch,
                  "mfu_estimate": extras[key]["mfu_estimate"]})
        except Exception as e:  # noqa: BLE001 — keep the artifact
            log(f"all-models extra {key} failed: {e!r}")
            extras[key] = {"error": repr(e)[:300]}
        finally:
            # Completed extras ride the watchdog's final line too — a
            # hang in a LATER extra must not drop finished ones.
            with _EMIT_LOCK:
                _BEST_RESULT["models"] = dict(extras)
            r = None  # free this model's state before the next init
    result["models"] = extras
    emit(result)
    _set_best(result)
    write_out(args)


if __name__ == "__main__":
    main()
