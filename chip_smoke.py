"""chip_smoke.py — the quickest proof that horovod_tpu still starts on
the chip.

Drives the system's main paths ONCE, through the entry points a user
calls, at the full width of the flagship LM
(`TransformerLM(vocab 32768, 12 layers, 8 heads x 128, max_len 2048,
bf16)`, 186.8 M parameters, `attn_impl="flash"`), with random weights
and tokens made from `--seed`:

  default (one chip)
    env      device, native control plane, compile cache, fence check
    trainer  hvd.init -> init_lm_state / make_lm_train_step (GSPMD)
             over make_mesh(data=hvd.size()) + adamw, 5 steps at
             8 x 2048 tokens; the losses against the same steps under
             plain XLA attention
    resnet   3 steps of ResNet-101, batch 128 @ 224^2 bf16, through
             hvd.DistributedOptimizer + make_cnn_train_step
    server   ServingEngine(num_slots=8, warmup=True): fixed pool and
             paged pool; 12 requests each, streams against
             `models.generate`

  --chips 4 (one host, four chips; no one-chip phase runs)
    dp       the LM at one global batch of 8 x 2048 by both
             data-parallel routes against the one-device run
    tp       ServingEngine(mesh="model=4", paged=True) against the
             one-device engine, with per-device bytes

Every phase prints what it ran and saw on its own lines (plain facts,
not benchmark numbers) and raises on a failed check; nothing lets the
run go on past a failure. The LAST stdout line is one JSON object
`{"ok": ..., "device": {"platform", "kind", "count"}}`. Without an
accelerator the script exits non-zero BEFORE any phase and prints no
result line at all.

One process uses the chip: everything below runs in this process.
"""

import argparse
import gc
import json
import math
import sys
import time
import traceback

# ---- sizes (the flagship LM) ------------------------------------------
LM_KW = dict(vocab_size=32768, num_layers=12, num_heads=8, head_dim=128,
             max_len=2048)
LM_BATCH, LM_STEPS = 8, 5            # per-chip batch x max_len tokens
RESNET_BATCH, RESNET_IMAGE, RESNET_STEPS = 128, 224, 3
SERVE_SLOTS, SERVE_NEW = 8, 32
PROMPT_LENS = (16, 37, 64, 129, 200, 333, 512, 700, 901, 1100, 1333,
               1500)                 # 12 requests > 8 slots: slots recycle
DP_GLOBAL_BATCH = 8                  # --chips 4: 2 sequences per chip

# ---- stated tolerances ------------------------------------------------
# Random init: the first loss sits near ln(vocab) = 10.4. On the one
# repeated batch it must fall at once and, within the five steps,
# by at least LOSS_MUST_FALL_BY. (Not "the last below the first":
# adamw(3e-4) without warm-up rises again at step 5 on the chip —
# 9.84 -> 10.77 under flash and under XLA attention alike, an
# optimizer overshoot — which is why the comparison below prints all
# five steps: it tells an overshoot from a kernel fault.)
LOSS0_NEAR_LN_VOCAB = 1.0
LOSS_MUST_FALL_BY = 0.25
# flash vs plain-XLA ("dot") attention, same params, same batch: both
# run bf16 matmuls with f32 accumulation and differ in summation order
# inside the softmax; the loss is an f32 mean over 16 k tokens. Step 1
# compares the forward, step 2 the backward + optimizer update too.
# Steps 3-5 are printed, not held to a bound: trajectories that close
# to an overshoot need not stay together.
FLASH_VS_XLA_LOSS_TOL = (0.03, 0.06)
# 4 x (2 sequences) vs 1 x (8 sequences): same math, different
# reduction order, drift compounding over the five adamw steps.
DP_VS_ONE_DEVICE_LOSS_TOL = 0.08
# A greedy stream may leave the oracle's only at a near-tie: bf16
# keeps 8 significant bits, so two programs that round activations in
# a different order disagree on a logit by a few units of
# 2^-8 x |largest logit|. A divergence whose top-2 margin (at the
# oracle, on that step) exceeds this bound is a FAILURE; inside it, it
# is a finding that gets reported.
BF16_EPS = 2.0 ** -8


def margin_bound(max_abs_logit):
    return 8 * BF16_EPS * max(1.0, max_abs_logit)


def say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def require_tpu(want_chips):
    """The devices, or exit non-zero with NO result line: a CPU run of
    this script proves nothing about the chip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no accelerator — JAX reports "
              f"{devs[0].platform} devices; this script only runs on "
              f"a TPU (reach one with the builder's chip tool)",
              file=sys.stderr, flush=True)
        sys.exit(3)
    if len(devs) != want_chips:
        print(f"chip_smoke: --chips {want_chips} but JAX reports "
              f"{len(devs)} device(s)", file=sys.stderr, flush=True)
        sys.exit(3)
    return devs


def mem(dev):
    s = dev.memory_stats() or {}
    return (f"peak {s.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB, "
            f"in use {s.get('bytes_in_use', 0) / 2**30:.2f} GiB of "
            f"{s.get('bytes_limit', 0) / 2**30:.2f} GiB")


class CompileCounter:
    """XLA backend compiles as JAX itself reports them (the engine's
    own `compiles` metric counts first-time program shapes)."""

    def __init__(self):
        self.n = 0

    def listen(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


XLA_COMPILES = CompileCounter()


def build_lm(attn_impl="flash", **kw):
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import TransformerLM
    return TransformerLM(dtype=jnp.bfloat16, attn_impl=attn_impl,
                         **LM_KW, **kw)


def compiled_text(jitted, mesh, *args, **kw):
    """(seconds, post-optimization text) of the program `jitted` runs
    for these arguments."""
    from horovod_tpu.parallel.mesh import use
    import contextlib
    t0 = time.time()
    with (use(mesh) if mesh is not None else contextlib.nullcontext()):
        txt = jitted.lower(*args, **kw).compile().as_text()
    return time.time() - t0, txt


# ======================================================================
# env
# ======================================================================

def phase_env(devs, cache_dir):
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.native import build as nbuild
    from horovod_tpu.runtime import state as _state
    from horovod_tpu.utils.profile_analysis import PEAK_BF16_FLOPS

    d = devs[0]
    say("env", f"jax {jax.__version__}, {len(devs)} x {d.platform} "
               f"device_kind={d.device_kind!r}; peak table entry: "
               f"{PEAK_BF16_FLOPS.get(d.device_kind)}")
    say("env", f"compile cache: {cache_dir}")
    hvd.init()
    check(hvd.size() == len(devs),
          f"hvd.size() {hvd.size()} != {len(devs)} devices")
    st = _state.global_state()
    plane = "native (C++)" if st.native is not None else "python"
    say("env", f"hvd.init(): size {hvd.size()}, rank {hvd.rank()}, "
               f"control plane: {plane}; native libraries: "
               f"{dict(nbuild.BUILD_ACTIONS) or 'none loaded'}")
    check(st.native is not None,
          "native control plane failed to build/load (bootstrap "
          "swallows the error; run horovod_tpu.native.load_native() "
          "to see it)")

    # Does block_until_ready fence? Queue a chain of matmuls, then
    # time: the dispatch, block_until_ready, and a scalar read-back
    # AFTER it. If the block fences, the read-back finds the value
    # ready and costs only a transfer.
    x = jnp.ones((4096, 4096), jnp.bfloat16)

    @jax.jit
    def chain(x):
        x = jax.lax.fori_loop(
            0, 256, lambda _, x: (x @ x) * jnp.bfloat16(2.0 ** -12), x)
        return x, x[0, 0].astype(jnp.float32)

    jax.block_until_ready(chain(x))       # compile
    t0 = time.time()
    y, corner = chain(x)
    t_dispatch = time.time() - t0
    y.block_until_ready()
    t_block = time.time() - t0
    float(corner)
    t_read = time.time() - t0 - t_block
    say("env", f"fence: 256 chained 4096^3 bf16 matmuls — dispatch "
               f"returned after {t_dispatch * 1e3:.2f} ms, "
               f"block_until_ready after {t_block * 1e3:.2f} ms, a "
               f"scalar read-back after the block took "
               f"{t_read * 1e3:.2f} ms more")
    check(t_block > 4 * t_dispatch,
          "dispatch was not asynchronous — nothing to fence")
    check(t_read < 0.25 * t_block,
          "block_until_ready returned before the work finished: the "
          "read-back after it still waited")
    say("env", "fence: block_until_ready fences (the read-back after "
               "it found the value ready)")


# ======================================================================
# trainer
# ======================================================================

def run_steps(label, n, one_step):
    """`n` (>= 2) calls of `one_step() -> loss`; says the losses and
    wall times, and checks that the losses are finite and that the
    step compiled once. Returns the losses."""
    losses, walls = [], []
    for i in range(n):
        if i == 1:
            after_first = XLA_COMPILES.n
        t0 = time.time()
        losses.append(float(one_step()))
        walls.append(time.time() - t0)
    recompiles = XLA_COMPILES.n - after_first
    say(label, "losses " + " ".join(f"{x:.4f}" for x in losses))
    say(label, f"first call (compile + step) {walls[0]:.1f} s; later "
               f"steps " + " ".join(f"{w * 1e3:.0f}" for w in walls[1:])
               + f" ms wall each; XLA compiles after the first call: "
                 f"{recompiles}")
    check(all(math.isfinite(x) for x in losses),
          f"{label}: non-finite loss {losses}")
    check(recompiles == 0,
          f"{label}: the step compiled again after its first call")
    return losses


def lm_run(model, mesh, toks, seed, steps, label, want_text=False):
    """`steps` train steps through init_lm_state/make_lm_train_step on
    `mesh`; returns (losses, compiled text or None)."""
    import jax
    import optax

    from horovod_tpu.models.transformer import (init_lm_state,
                                                make_lm_train_step)
    from horovod_tpu.parallel.mesh import shard_batch

    tx = optax.adamw(3e-4)
    t0 = time.time()
    params, opt_state = init_lm_state(
        model, tx, jax.random.PRNGKey(seed), mesh, toks)
    n_params = sum(math.prod(p.shape) for p in jax.tree.leaves(params))
    step = make_lm_train_step(model, tx, mesh)
    batch = shard_batch(mesh, toks)
    say(label, f"{n_params / 1e6:.1f} M params, batch "
               f"{toks.shape[0]} x {toks.shape[1]} on "
               f"{len(set(s.device for s in batch.addressable_shards))}"
               f" device(s), init {time.time() - t0:.1f} s")
    state = [params, opt_state]
    del params, opt_state

    def one_step():
        state[0], state[1], loss = step(state[0], state[1], batch)
        return loss

    losses = run_steps(label, steps, one_step)
    text = None
    if want_text:
        secs, text = compiled_text(step.__wrapped__, mesh, *state,
                                   batch)
        say(label, f"re-lowering the step for its text took "
                   f"{secs:.1f} s (a compile-cache hit when small)")
    del state, batch
    gc.collect()
    return losses, text


def phase_trainer(devs, seed):
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.parallel.mesh import make_mesh

    # The LM's TP/SP annotations name every canonical axis, so its
    # mesh is the 5-axis one over hvd's devices (size-1 axes are
    # free) — hvd.mesh() itself is 1-D `data`.
    mesh = make_mesh(devices=devs, data=hvd.size())
    toks = np.random.RandomState(seed).randint(
        0, LM_KW["vocab_size"],
        (LM_BATCH * hvd.size(), LM_KW["max_len"])).astype(np.int32)
    losses, text = lm_run(build_lm("flash"), mesh, toks, seed,
                          LM_STEPS, "trainer", want_text=True)
    n_cc = text.count("tpu_custom_call")
    say("trainer", f"compiled step holds {n_cc} tpu_custom_call(s) "
                   f"(flash forward + fused backward: 3 per layer x "
                   f"{LM_KW['num_layers']})")
    check(n_cc >= 3 * LM_KW["num_layers"],
          f"the train step's compiled text holds {n_cc} Pallas custom "
          f"calls, expected >= {3 * LM_KW['num_layers']}: the flash "
          f"kernels are not in the program")
    ln_v = math.log(LM_KW["vocab_size"])
    check(abs(losses[0] - ln_v) < LOSS0_NEAR_LN_VOCAB,
          f"first loss {losses[0]:.3f} is not near ln(vocab) "
          f"{ln_v:.3f}")
    check(losses[1] < losses[0]
          and min(losses) <= losses[0] - LOSS_MUST_FALL_BY,
          f"loss did not fall on the repeated batch: {losses}")
    say("trainer", mem(devs[0]))

    # The same steps under plain XLA attention. remat keeps the
    # [B, H, S, S] score residuals of 12 layers out of HBM (without
    # it the compiler wants 31.8 GiB for "blockwise" at this size).
    ref, _ = lm_run(build_lm("dot", remat=True), mesh, toks, seed,
                    LM_STEPS, "trainer/xla-attn")
    for i, (a, b) in enumerate(zip(losses, ref)):
        tol = (FLASH_VS_XLA_LOSS_TOL[i]
               if i < len(FLASH_VS_XLA_LOSS_TOL) else None)
        say("trainer", f"step {i + 1}: flash {a:.4f} vs XLA attention "
                       f"{b:.4f}, |diff| {abs(a - b):.4f} "
                       + (f"(tolerance {tol})" if tol else
                          "(printed, not bounded)"))
        check(tol is None or abs(a - b) <= tol,
              f"flash and XLA attention disagree at step {i + 1}: "
              f"{a} vs {b}")


# ======================================================================
# resnet (the reference's own model, the README's five-line path)
# ======================================================================

def phase_resnet(devs, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import models
    from horovod_tpu.models import make_cnn_train_step
    from horovod_tpu.models.train import init_cnn_state
    from horovod_tpu.ops.fusion import step_compiler_options

    model = models.ResNet101(num_classes=1000)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
    shape = (RESNET_IMAGE, RESNET_IMAGE, 3)
    t0 = time.time()
    state = init_cnn_state(model, tx, jax.random.PRNGKey(seed),
                           jnp.zeros((1,) + shape, jnp.bfloat16))
    step = make_cnn_train_step(model, tx)
    gb = RESNET_BATCH * hvd.size()
    rs = np.random.RandomState(seed)
    xb = jnp.asarray(rs.randn(gb, *shape).astype(np.float32),
                     jnp.bfloat16)
    yb = jnp.asarray(rs.randint(0, 1000, size=(gb,)))
    n_params = sum(math.prod(p.shape)
                   for p in jax.tree.leaves(state["params"]))
    say("resnet", f"ResNet-101 {n_params / 1e6:.1f} M params, batch "
                  f"{gb} @ {RESNET_IMAGE}^2 bf16, init "
                  f"{time.time() - t0:.1f} s; step compiled with "
                  f"compiler_options="
                  f"{step_compiler_options(hvd.mesh(), 'data')}")
    rng = jax.random.PRNGKey(seed + 1)
    state = [state]

    def one_step():
        state[0], loss = step(state[0], (xb, yb), rng)
        return loss

    run_steps("resnet", RESNET_STEPS, one_step)
    say("resnet", "the step's options "
                  "(ops/fusion.step_compiler_options) were "
                  "accepted by the TPU compiler: the step compiled "
                  "with them")
    say("resnet", mem(devs[0]))
    del state, xb, yb
    gc.collect()


# ======================================================================
# server
# ======================================================================

def make_prompts(seed, lens=None):
    import numpy as np
    rs = np.random.RandomState(seed + 7)
    return [rs.randint(0, LM_KW["vocab_size"], (n,)).astype(np.int32)
            for n in (lens or PROMPT_LENS)]


def oracle_streams(model, params, prompts, label):
    """Per-prompt greedy `models.generate` on this chip."""
    import numpy as np
    from horovod_tpu.models.transformer import generate
    t0 = time.time()
    out = []
    for p in prompts:
        full = np.asarray(generate(model, params, p[None, :],
                                   steps=SERVE_NEW))
        out.append(full[0, len(p):])
    say(label, f"oracle: models.generate over {len(prompts)} prompts "
               f"(one compile per length) {time.time() - t0:.1f} s")
    return out


class Margins:
    """The oracle's top-2 logit margin at one position, from a
    teacher-forced forward of the plain model (causal, so the padding
    after the position cannot reach it)."""

    def __init__(self, model, params):
        import jax
        self.model, self.params = model, params
        self._fwd = jax.jit(
            lambda p, t: model.apply({"params": p}, t))

    def at(self, prompt, stream, k):
        import jax.numpy as jnp
        import numpy as np
        seq = np.zeros((1, self.model.max_len), np.int32)
        ctx = np.concatenate([prompt, stream[:k]])
        seq[0, :len(ctx)] = ctx
        logits = np.asarray(
            self._fwd(self.params, jnp.asarray(seq))[0, len(ctx) - 1]
            .astype(jnp.float32))
        top = np.sort(logits)[-2:]
        return float(top[1] - top[0]), float(np.abs(logits).max())


def compare_streams(label, prompts, got, want, margins):
    """Per request: exact, or where it left the oracle and how close
    the oracle's call was there. Returns the number of exact streams;
    raises on a divergence bf16 rounding cannot explain."""
    exact = 0
    for i, (p, g, w) in enumerate(zip(prompts, got, want)):
        check(len(g) == SERVE_NEW,
              f"{label}: request {i} returned {len(g)} tokens, "
              f"expected {SERVE_NEW}")
        if (g == w).all():
            exact += 1
            continue
        k = int((g != w).argmax())
        margin, big = margins.at(p, w, k)
        bound = margin_bound(big)
        say(label, f"request {i} (prompt {len(p)}): leaves the oracle "
                   f"at generated token {k} (engine {int(g[k])}, "
                   f"oracle {int(w[k])}); oracle top-2 margin there "
                   f"{margin:.5f}, max |logit| {big:.3f}, bf16 "
                   f"near-tie bound {bound:.5f}")
        check(margin <= bound,
              f"{label}: request {i} diverges at step {k} where the "
              f"oracle's top-2 margin {margin} exceeds what bf16 "
              f"rounding explains ({bound})")
    say(label, f"streams token-exact vs oracle: {exact}/{len(prompts)}"
               + ("" if exact == len(prompts) else
                  " (the rest are near-tie flips inside the stated "
                  "bound)"))
    return exact


def tick_text(pool):
    """The compiled text of the decode tick this pool dispatches."""
    from horovod_tpu.models.transformer import (paged_decode_tick,
                                                slot_decode_tick)
    if hasattr(pool, "_pools"):
        return compiled_text(
            paged_decode_tick, pool.mesh, pool.dec_model, pool.spec,
            pool._pools, pool.params, pool._tables, pool._fills,
            pool._toks, pool._temps, pool._top_ps, pool._rngs,
            pool._live, pool._done, pool._eos, fused=pool._fused)[1]
    return compiled_text(
        slot_decode_tick, pool.mesh, pool.dec_model, pool.params,
        pool._cache, pool._toks, pool._temps, pool._top_ps,
        pool._rngs, pool._live, pool._done, pool._eos)[1]


def serve(label, model, params, prompts, **engine_kw):
    """One engine, every prompt submitted at once; returns (streams,
    engine) — the caller shuts the engine down."""
    import numpy as np
    from horovod_tpu.serving import ServingEngine

    t0 = time.time()
    eng = ServingEngine(model, params, num_slots=SERVE_SLOTS,
                        warmup=True, **engine_kw)
    info = eng.warmup_info
    say(label, f"engine up in {time.time() - t0:.1f} s (warm-up "
               f"{info['seconds']:.1f} s, {info['compiles']} program "
               f"shapes, prefill chunks {info['prefill_sizes']})")
    xla_before = XLA_COMPILES.n
    t0 = time.time()
    handles = [eng.submit(p, SERVE_NEW) for p in prompts]
    results = [h.result(timeout=600) for h in handles]
    wall = time.time() - t0
    snap = eng.metrics_snapshot()
    streams = [np.asarray(r.tokens) for r in results]
    say(label, f"{len(results)} requests x {SERVE_NEW} new tokens "
               f"over {SERVE_SLOTS} slots in {wall:.2f} s wall; "
               f"ttft p50 {snap['ttft_ms']['p50']} ms, tpot p50 "
               f"{snap['tpot_ms']['p50']} ms, peak active "
               f"{snap['peak_active']}")
    say(label, f"after warm-up: engine-counted compiles "
               f"{snap['compiles']}, XLA backend compiles "
               f"{XLA_COMPILES.n - xla_before}")
    check(snap["completed"] == len(prompts),
          f"{label}: {snap['completed']} of {len(prompts)} completed")
    check(snap["compiles"] == 0 and XLA_COMPILES.n == xla_before,
          f"{label}: compiles after warm-up — engine-counted "
          f"{snap['compiles']}, XLA backend "
          f"{XLA_COMPILES.n - xla_before}")
    return streams, eng


def phase_server(devs, seed):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import serving_params
    from horovod_tpu.parallel.tensor import unbox

    model = build_lm("flash")
    params = serving_params(unbox(jax.jit(model.init)(
        jax.random.PRNGKey(seed),
        jnp.zeros((1, 64), jnp.int32))["params"]))
    prompts = make_prompts(seed)
    say("server", f"{len(prompts)} prompts of lengths "
                  f"{[len(p) for p in prompts]}, {SERVE_NEW} new "
                  f"tokens each, greedy")
    want = oracle_streams(model, params, prompts, "server")
    margins = Margins(model, params)

    runs = (
        ("server/fixed", model, {}),
        ("server/paged", model, dict(paged=True)),
    )
    for label, m, kw in runs:
        got, eng = serve(label, m, params, prompts, **kw)
        try:
            compare_streams(label, prompts, got, want, margins)
            if not kw:
                # the fixed pool on the default rule: on the chip the
                # tick attends through the ragged decode kernel and
                # appends its K/V row in place - two calls a layer
                snap = eng.metrics_snapshot()
                n_cc = tick_text(eng.pool).count("tpu_custom_call")
                say(label, f"decode attention: "
                           f"{snap['decode_attn_plan']}; the compiled "
                           f"tick holds {n_cc} tpu_custom_call(s)")
                check(snap["decode_attn_path"] == "kernel"
                      and "write kernel" in snap["decode_attn_plan"]
                      and n_cc == 2 * LM_KW["num_layers"],
                      f"{label}: the default tick took "
                      f"{snap['decode_attn_plan']} with {n_cc} "
                      f"custom calls")
        finally:
            eng.shutdown()
        say(label, mem(devs[0]))
        del eng
        gc.collect()


# ======================================================================
# --chips 4: data-parallel training
# ======================================================================

def count_all_reduce(text):
    import re
    # Ops, not references to them: `all-reduce(` or the async pair's
    # `all-reduce-start(` (operands are written `%all-reduce.5`).
    return len(re.findall(r"\ball-reduce(?:-start)?\(", text))


def phase_dp(devs, seed):
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import lm_loss
    from horovod_tpu.ops.fusion import (plan_buckets,
                                        step_compiler_options)
    from horovod_tpu.parallel.mesh import make_mesh, shard_batch
    from horovod_tpu.parallel.tensor import unbox

    n = hvd.size()
    model = build_lm("flash")
    toks = np.random.RandomState(seed).randint(
        0, LM_KW["vocab_size"],
        (DP_GLOBAL_BATCH, LM_KW["max_len"])).astype(np.int32)

    # Eager collective first: the sum over ranks.
    vals = [np.full((4,), float(r + 1), np.float32) for r in range(n)]
    out = np.asarray(hvd.allreduce(hvd.per_rank(vals), average=False))
    say("dp", f"eager hvd.allreduce(per_rank(1..{n}), average=False) "
              f"-> {out.tolist()}")
    check(np.allclose(out, sum(range(1, n + 1))),
          f"eager allreduce returned {out}, expected the sum "
          f"{sum(range(1, n + 1))}")

    # What both routes are compared with: the same global batch on
    # one device.
    one = make_mesh(devices=devs[:1], data=1)
    ref, _ = lm_run(model, one, toks, seed, LM_STEPS, "dp/one-device")

    # Route 1: make_lm_train_step over the data=4 mesh; GSPMD inserts
    # the gradient all-reduce.
    mesh = make_mesh(devices=devs, data=n)
    batch = shard_batch(mesh, toks)
    homes = sorted(str(s.device) for s in batch.addressable_shards)
    say("dp", f"batch shards "
              f"{[tuple(s.data.shape) for s in batch.addressable_shards]}"
              f" on {homes}")
    check(len(set(homes)) == n,
          f"the batch's shards sit on {len(set(homes))} device(s), "
          f"expected {n} distinct ones")
    del batch
    gspmd, text = lm_run(model, mesh, toks, seed, LM_STEPS,
                         "dp/gspmd", want_text=True)
    n_ar = count_all_reduce(text)
    say("dp/gspmd", f"compiled step holds {n_ar} all-reduce op(s) and "
                    f"{text.count('tpu_custom_call')} tpu_custom_call(s)")
    check(n_ar >= 1, "the GSPMD step holds no all-reduce")

    # Route 2: the README's five lines — hvd.make_train_step over
    # shard_map with the DistributedOptimizer's fused psum buckets.
    def loss_fn(params, batch):
        return lm_loss(model.apply({"params": params}, batch), batch)

    tx = hvd.DistributedOptimizer(optax.adamw(3e-4))
    params = unbox(jax.jit(model.init)(jax.random.PRNGKey(seed),
                                       jnp.asarray(toks))["params"])
    params = hvd.broadcast_global_variables(params, 0)
    opt_state = tx.init(params)
    step = hvd.make_train_step(loss_fn, tx)
    batch = shard_batch(hvd.mesh(), toks)
    planned = len(plan_buckets(jax.tree.leaves(params)))
    state = [params, opt_state]
    del params, opt_state

    def one_step():
        state[0], state[1], loss = step(state[0], state[1], batch)
        return loss

    five = run_steps("dp/five-lines", LM_STEPS, one_step)
    _, text = compiled_text(step.__wrapped__, None, *state, batch)
    n_ar = count_all_reduce(text)
    n_async = len(re.findall(r"async-collective-start[.\d]* = ", text))
    say("dp/five-lines",
        f"compiled step holds {n_ar} all-reduce op(s), {n_async} of "
        f"them asynchronous pairs; plan_buckets fuses the leaves "
        f"into {planned} bucket(s) under the threshold, and on this "
        f"mesh a leaf of ALONE_BYTES or more is reduced alone "
        f"(+1 for the loss pmean); compiled "
        f"with {step_compiler_options(hvd.mesh(), 'data')} — "
        f"accepted by the TPU compiler")
    check(n_ar >= 1, "the shard_map step holds no all-reduce")
    say("dp", mem(devs[0]))
    del state, batch
    gc.collect()

    for name, got in (("gspmd", gspmd), ("five-lines", five)):
        diffs = [abs(a - b) for a, b in zip(got, ref)]
        say("dp", f"{name} vs one device, |loss diff| per step: "
                  + " ".join(f"{d:.4f}" for d in diffs)
                  + f" (tolerance {DP_VS_ONE_DEVICE_LOSS_TOL})")
        check(max(diffs) <= DP_VS_ONE_DEVICE_LOSS_TOL,
              f"dp/{name} leaves the one-device loss: {got} vs {ref}")


# ======================================================================
# --chips 4: model-parallel serving
# ======================================================================

def shard_report(label, tree_leaves_with_names, n_dev):
    """Per-device bytes of a list of (name, array); returns
    {device: bytes}."""
    per_dev = {}
    for _, a in tree_leaves_with_names:
        for s in a.addressable_shards:
            per_dev[str(s.device)] = (per_dev.get(str(s.device), 0)
                                      + s.data.nbytes)
    total = sum(a.nbytes for _, a in tree_leaves_with_names)
    say(label, f"global {total / 2**20:.1f} MiB; per device "
               + ", ".join(f"{d}: {b / 2**20:.1f} MiB"
                           for d, b in sorted(per_dev.items())))
    check(len(per_dev) == n_dev,
          f"{label}: shards on {len(per_dev)} devices, expected "
          f"{n_dev}")
    return per_dev


def phase_tp(devs, seed):
    import jax
    import jax.numpy as jnp
    from jax.tree_util import tree_flatten_with_path

    from horovod_tpu.models.transformer import serving_params
    from horovod_tpu.parallel.tensor import unbox

    n = len(devs)
    model = build_lm("flash")
    params = serving_params(unbox(jax.jit(model.init)(
        jax.random.PRNGKey(seed),
        jnp.zeros((1, 64), jnp.int32))["params"]))
    prompts = make_prompts(seed)
    margins = Margins(model, params)

    want, eng = serve("tp/one-device", model, params, prompts,
                      paged=True)
    eng.shutdown()
    del eng
    gc.collect()

    got, eng = serve("tp/model=4", model, params, prompts,
                     paged=True, mesh=f"model={n}")
    try:
        check(eng.mesh_devices == n,
              f"serving mesh spans {eng.mesh_devices} devices")
        # Parameters: every attention / MLP kernel holds 1/n per
        # device.
        flat, _ = tree_flatten_with_path(eng.pool.params)
        named = [("/".join(str(getattr(k, "key", k)) for k in path), a)
                 for path, a in flat]
        split = 0
        for name, a in named:
            if "kernel" in name and ("attn" in name or "mlp" in name):
                shard = a.addressable_shards[0].data
                check(shard.size * n == a.size,
                      f"tp: {name} {tuple(a.shape)} holds "
                      f"{tuple(shard.shape)} per device — not 1/{n}")
                split += 1
        check(split >= 4 * LM_KW["num_layers"],
              f"tp: only {split} attention/MLP kernels found")
        say("tp", f"{split} attention/MLP kernels hold 1/{n} per "
                  f"device")
        per_dev = shard_report("tp/params", named, n)
        total = sum(a.nbytes for _, a in named)
        check(max(per_dev.values()) < 0.6 * total,
              "tp: a device holds more than 60% of the parameter "
              "bytes — the model is replicated, not sharded")
        # KV: every pool leaf holds its 1/n head slice of every block.
        pools = [(f"kv_pool_{i}", p)
                 for i, p in enumerate(eng.pool._pools)]
        for name, p in pools:
            shard = p.addressable_shards[0].data
            check(shard.shape[3] * n == p.shape[3]
                  and shard.size * n == p.size,
                  f"tp: {name} {tuple(p.shape)} holds "
                  f"{tuple(shard.shape)} per device — not 1/{n}")
        say("tp", f"{len(pools)} KV pool leaves "
                  f"{tuple(pools[0][1].shape)} hold "
                  f"{tuple(pools[0][1].addressable_shards[0].data.shape)}"
                  f" per device")
        shard_report("tp/kv-pools", pools, n)
        text = tick_text(eng.pool)
        say("tp", f"compiled sharded tick holds "
                  f"{count_all_reduce(text)} all-reduce op(s)")
        compare_streams("tp/model=4 vs one-device", prompts, got,
                        want, margins)
    finally:
        eng.shutdown()
    for d in devs:
        say("tp", f"{d}: {mem(d)}")
    del eng
    gc.collect()


# ======================================================================

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1 (default): the one-chip phases; 4: only "
                         "the data-parallel and model-parallel phases "
                         "that exist across chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and tokens are made from this")
    args = ap.parse_args()

    # The same helper hvd.init() and ServingEngine use; here first so
    # even the fence check's compile lands in the placed cache. In a
    # directory that holds nothing else of the repo this import fails
    # and the script exits non-zero with no result.
    from horovod_tpu.runtime.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()

    devs = require_tpu(args.chips)
    XLA_COMPILES.listen()
    device = {"platform": devs[0].platform,
              "kind": devs[0].device_kind, "count": len(devs)}
    phases = ([phase_trainer, phase_resnet, phase_server]
              if args.chips == 1 else [phase_dp, phase_tp])
    t_start = time.time()
    ok = True
    try:
        phase_env(devs, cache_dir)
        for phase in phases:
            t0 = time.time()
            phase(devs, args.seed)
            say(phase.__name__.removeprefix("phase_"),
                f"phase passed in {time.time() - t0:.1f} s")
    except Exception:  # noqa: BLE001 — report, then fail the run
        traceback.print_exc()
        ok = False
    say("smoke", f"{'all phases passed' if ok else 'FAILED'} after "
                 f"{time.time() - t_start:.1f} s")
    sys.stderr.flush()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
