"""State-space layers (Mamba-2: a scalar decay a head) on the serving
path: the three forms of the recurrence, the layer with its convolution
tail and gated norm, the in-place state step against the XLA form, and a
tiny hybrid `TransformerLM` with Granite's four multipliers against the
plain reference the benchmark keeps (`benchmarks/arch/granite_hybrid.py`,
which imports nothing of the program) - on the CPU, seeded random
weights.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness.cells import load_module
from horovod_tpu.models.transformer import (
    RECURRENT_KINDS, TransformerLM, decode_attention_plan, generate,
    init_slot_cache, kernel_plans, slot_decode_model, slot_decode_tick,
    slot_prefill_chunk,
)
from horovod_tpu.ops.ssm_step import ssm_state_step, ssm_step_plan
from horovod_tpu.parallel.state_space import (
    Mamba2Mixer, SsmSpec, gated_norm, ssm_chunked, ssm_recurrent,
    ssm_step, step_rows,
)
from horovod_tpu.serving import ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
A = load_module(os.path.join(REPO, "benchmarks", "arch",
                             "granite_hybrid.py"),
                "arch_granite_hybrid_for_tests")
with open(os.path.join(REPO, "tests", "benchmark", "tiny",
                       "tiny-granite.json")) as f:
    ARCH = json.load(f)["arch"]     # hidden 64; ssm, ssm, attn, ssm
MAX_LEN = 64
# The same model with another state size: traced under `kernel_path`
# ONLY, so no program of it is ever traced on the lax path and the jit
# caches need no clearing. (8 heads x 16 = 128 channels a group: whole
# lanes, which `ops.ssm_step`'s kernel takes.)
ARCH_K = dict(ARCH, ssm_state=24)
# Logits here have a standard deviation of 1.6e-3 and reach 6.5e-3 (the
# tied table is drawn at 0.02 / 12 and the divisor is 8; the toy's
# matrices at 0.11) from O(1) activations through four layers in float32
# at highest precision: the two programs differ by the order of float32
# sums alone, which a thousandth of the logits' spread holds.
ATOL = 2e-6


def f32_model(arch=ARCH, **kw):
    return A.program_model(arch, max_len=MAX_LEN, attn_impl="dot",
                           dtype="float32", **kw)


def ref_logits(arch, params, toks):
    """The reference's full forward, `A.logits`, as ONE program: run
    op by op it compiled a primitive at a time, seconds a call."""
    return jax.jit(lambda p, t: A.logits(arch, p, t))(params, toks)


@pytest.fixture(scope="module")
def params():
    return A.make_params(ARCH, MAX_LEN, 11, "float32")


@pytest.fixture(scope="module")
def params_k():
    return A.make_params(ARCH_K, MAX_LEN, 11, "float32")


@pytest.fixture
def kernel_path(monkeypatch):
    """`ssm_step_plan` as on a TPU: the state's S = 1 step is the
    in-place kernel (interpret mode here)."""
    from horovod_tpu.ops import kda_step
    monkeypatch.setattr(kda_step, "_on_tpu", lambda: True)


@pytest.fixture(autouse=True)
def highest():
    """`highest`, set in the configuration: the context manager is
    thread-local, and under it an engine's dispatch thread compiled
    again, at the default, every program the warm-up had compiled."""
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", was)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, ARCH["vocab_size"], n).astype(np.int32)


# ---- (a) step = recurrence = the chunkwise form --------------------------
def ssm_inputs(T, seed, state, decay=1.0, B=2, H=4, P=8, N=16, G=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = decay * jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.8))
    bm, cm = (jax.random.normal(ks[i], (B, T, G, N)) for i in (3, 4))
    s0 = (jax.random.normal(ks[5], (B, G, N, H * P // G)) if state
          else jnp.zeros((B, G, N, H * P // G)))
    return s0, x, dt, a, bm, cm


@pytest.mark.parametrize("state", [False, True],
                         ids=["empty-state", "non-empty-state"])
@pytest.mark.parametrize("T,chunk,decay", [
    (2, 256, 1.0), (16, 8, 1.0), (37, 8, 0.05), (37, 16, 1.0),
    (64, 256, 1.0), (50, 16, 40.0), (100, 32, 1.0)])
def test_chunkwise_equals_recurrence(T, chunk, decay, state):
    """Blocks that do and do not divide the length, any state to start
    from; a decay of exp(-16 x 40) a step underflows to nothing and
    overflows nothing."""
    args = ssm_inputs(T, T, state, decay)
    y1, s1 = ssm_recurrent(*args)
    y2, s2 = jax.jit(ssm_chunked, static_argnames="chunk")(
        *args, chunk=chunk)
    assert bool(jnp.isfinite(y2).all()) and bool(jnp.isfinite(s2).all())
    np.testing.assert_allclose(y2, y1, atol=5e-5, rtol=1e-5)
    np.testing.assert_allclose(s2, s1, atol=5e-5, rtol=1e-5)


def test_step_is_the_equation_a_head():
    """`ssm_step` in the program's [G, N, Q] layout against the
    published form on a [P, N] state a head."""
    s0, x, dt, a, bm, cm = ssm_inputs(1, 5, True)
    x, dt, bm, cm = x[:, 0], dt[:, 0], bm[:, 0], cm[:, 0]
    y, s1 = ssm_step(s0, x, dt, a, bm, cm)
    B, H, P = x.shape
    G, N = bm.shape[-2:]
    h = s0.reshape(B, G, N, H // G, P)              # [b, g, n, h, p]
    bh = jnp.repeat(bm, H // G, axis=1)             # [b, H, n]
    ch = jnp.repeat(cm, H // G, axis=1)
    h = jnp.moveaxis(h, 2, -1).reshape(B, H, P, N)  # a head's [P, N]
    want = (jnp.exp(dt * a)[..., None, None] * h
            + (dt[..., None] * x)[..., None] * bh[:, :, None, :])
    np.testing.assert_allclose(y, (want * ch[:, :, None, :]).sum(-1),
                               atol=1e-5)
    got = jnp.moveaxis(s1.reshape(B, G, N, H // G, P), 2, -1)
    np.testing.assert_allclose(got.reshape(B, H, P, N), want, atol=1e-6)


def test_gated_norm_by_hand():
    """The gate multiplies BEFORE the norm, one group over the width."""
    y = jnp.asarray([[1.0, -2.0, 3.0, 0.5]])
    z = jnp.asarray([[0.0, 1.0, -1.0, 2.0]])
    w = jnp.asarray([1.0, 2.0, 0.5, 1.0])
    g = np.asarray(y) * np.asarray(z) / (1 + np.exp(-np.asarray(z)))
    want = g / np.sqrt((g * g).mean() + 1e-5) * np.asarray(w)
    np.testing.assert_allclose(gated_norm(y, z, w, 1e-5), want, rtol=1e-6)


# ---- (b) the layer: convolution tail, bias, gated norm -------------------
@pytest.mark.parametrize("split", [None, 24], ids=["whole", "two-chunks"])
def test_layer_equals_reference(params, split):
    """The layer against the reference's position-by-position layer; in
    chunks through the cache, each starts from the last one's state and
    convolution tail (S > 1, S > 1, then S = 1)."""
    p = params["block_1"]["ssm"]
    x = jax.random.normal(jax.random.PRNGKey(3), (40, ARCH["hidden_size"]))
    want = A.mamba_mixer(ARCH, p, x)
    layer = Mamba2Mixer(spec=f32_model().ssm,
                        out_features=ARCH["hidden_size"],
                        dtype=jnp.float32, decode=split is not None)
    # one program a length, not a compile a primitive
    if split is None:
        got = jax.jit(layer.apply)({"params": p}, x[None])[0]
    else:
        cache = jax.tree.map(
            jnp.zeros_like, jax.jit(layer.init)(
                jax.random.PRNGKey(0), x[None])["cache"])
        assert cache["state"].shape == (1, 1, 16, 128)
        assert cache["conv_tail"].shape == (1, 3, 128 + 2 * 16)
        step = jax.jit(lambda cache, part: layer.apply(
            {"params": p, "cache": cache}, part, mutable=["cache"]))
        parts = []
        for part in (x[:split], x[split:-1], x[-1:]):
            y, mut = step(cache, part[None])
            cache = mut["cache"]
            parts.append(y[0])
        got = jnp.concatenate(parts)
    np.testing.assert_allclose(got, want, atol=ATOL)


# ---- (c) the in-place step and its rule -----------------------------------
def test_kernel_step_equals_the_xla_form_and_keeps_a_lane():
    """Interpret mode: lanes that advance get `step_rows`' result, a
    lane that does not keeps its state bitwise; two groups, two channel
    blocks a group would need a state past 2 MiB, so one here."""
    L, G, N, Q = 5, 2, 16, 256
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    state = jax.random.normal(ks[0], (L, G, N, Q))
    decay = jax.random.uniform(ks[1], (L, G, Q))
    dtx = jax.random.normal(ks[2], (L, G, Q))
    bm, cm = (jax.random.normal(k, (L, G, N)) for k in ks[3:])
    adv = jnp.asarray([True, False, True, True, False])
    plan = ssm_step_plan(L, G, N, Q, on_tpu=True)
    assert (plan.path, plan.block, plan.grid) == ("kernel", 256, (5, 2, 1))
    y, s = ssm_state_step(state, decay, dtx, bm, cm, adv, plan=plan)
    yr, sr = step_rows(state, decay, dtx, bm, cm)
    keep = ~np.asarray(adv)
    np.testing.assert_allclose(y[~keep], yr[~keep], atol=1e-5)
    np.testing.assert_allclose(s[~keep], sr[~keep], atol=1e-6)
    np.testing.assert_array_equal(s[keep], state[keep])
    np.testing.assert_array_equal(y[keep], 0)
    with pytest.raises(ValueError, match="the plan says lax"):
        ssm_state_step(state, decay, dtx, bm, cm,
                       plan=ssm_step_plan(L, G, N, Q, on_tpu=False))


def test_the_plans_rule():
    kw = dict(on_tpu=True)
    granite = ssm_step_plan(64, 1, 128, 4096, **kw)
    assert (granite.path, granite.block, granite.grid) == (
        "kernel", 4096, (64, 1, 1))
    assert "a block of 4096 a step, in place" in granite.describe()
    assert granite.vmem_bytes < 16 * 2 ** 20
    # a state past the block's bytes is split over whole lanes
    assert ssm_step_plan(8, 1, 256, 4096, **kw).block == 2048
    for why, plan in {
            "not on a TPU": ssm_step_plan(64, 1, 128, 4096, on_tpu=False),
            "positions": ssm_step_plan(64, 1, 128, 4096, positions=8, **kw),
            "whole lanes": ssm_step_plan(64, 1, 128, 64 * 25, **kw),
            "serving mesh": ssm_step_plan(64, 1, 128, 4096,
                                          trivial_mesh=False, **kw),
            "sublane tiles": ssm_step_plan(64, 1, 12, 4096, **kw)}.items():
        assert plan.path == "lax" and why in plan.describe(), plan


# ---- the whole model --------------------------------------------------------
def test_program_equals_reference_full_forward(params):
    toks = tokens(40)
    want = ref_logits(ARCH, params, jnp.asarray(toks))
    got = f32_model().apply({"params": params}, toks[None])[0]
    np.testing.assert_allclose(got, want, atol=ATOL)
    A.check_layout(ARCH, MAX_LEN, f32_model())
    assert f32_model().kinds == ("ssm", "ssm", "attn", "ssm")
    assert "ssm" in RECURRENT_KINDS and f32_model().has_recurrent_state


@pytest.mark.parametrize("scalar,other", [
    ("embed_scale", 6), ("residual_scale", 0.5), ("logits_divisor", 4),
    ("attn_scale", 32.0)])
def test_each_multiplier_reaches_the_logits(params, scalar, other):
    """None of the four is silently dropped, in the program or in the
    reference: another value moves both, and they still agree. (Scores
    of weights of 0.02 are so small that the softmax is nearly flat at
    any published scale: the attention's is tried at 32.)"""
    arch = dict(ARCH, **{scalar: other})
    toks = tokens(24, 3)
    base = ref_logits(ARCH, params, jnp.asarray(toks))
    want = ref_logits(arch, params, jnp.asarray(toks))
    got = f32_model(arch).apply({"params": params}, toks[None])[0]
    assert float(jnp.abs(want - base).max()) > 100 * ATOL
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_the_new_fields_default_to_no_instruction():
    """A dense model with the new fields at their defaults: the logits
    are bit for bit those of the same model with every multiplier spelt
    out as 1 (head 16: 16 ** -0.5 x 16 ** 0.5 is exactly 1), and its
    program holds none of the multiplies the spelt-out one has."""
    from horovod_tpu.models.transformer import AttnSpec
    from horovod_tpu.parallel.tensor import unbox
    kw = dict(vocab_size=64, num_layers=2, num_heads=2, head_dim=16,
              max_len=32, dtype=jnp.float32, attn_impl="dot")
    plain = TransformerLM(**kw)
    spelt = TransformerLM(
        **kw, embed_scale=1.0, residual_scale=1.0, logits_divisor=1.0,
        attn_specs=(("attn", AttnSpec(scale=0.25)),))
    toks = tokens(12, 5)[None] % 64
    params = unbox(plain.init(jax.random.PRNGKey(0), toks)["params"])
    np.testing.assert_array_equal(plain.apply({"params": params}, toks),
                                  spelt.apply({"params": params}, toks))

    def muls(model):
        text = str(jax.make_jaxpr(
            lambda p: model.apply({"params": p}, toks))(params))
        return text.count(" mul ")
    # the embedding, two branches and q a layer, the hidden state
    assert muls(spelt) - muls(plain) == 1 + 2 * 3 + 1


# ---- slots: chunks, ticks, and a tick between another slot's chunks -------
@pytest.mark.parametrize("path", ["lax", "kernel"])
def test_slot_chunks_and_ticks_equal_reference_with_an_interleaved_tick(
        path, request):
    """Slot 0 decodes while slot 1's prompt streams in in two chunks
    with a tick between them: the tick must leave slot 1's half-built
    state, convolution tail and fill alone. Every logit - slot 0's
    ticks, slot 1's chunks and its later ticks - is the reference's
    full forward pass. On both executors of the state's step: XLA's
    `ssm_step`, frozen by the tick's select, and the in-place kernel,
    which keeps the state itself and is never selected after (the
    tail is selected on both)."""
    arch = ARCH if path == "lax" else ARCH_K
    if path == "kernel":
        request.getfixturevalue("kernel_path")
    params = request.getfixturevalue(
        "params" if path == "lax" else "params_k")
    model = f32_model(arch)
    dec = slot_decode_model(model)
    assert kernel_plans(dec, 3)["state_step"]["ssm"].path == path
    cache = init_slot_cache(model, 3)
    a, b = tokens(21, 1), tokens(30, 2)
    ref_a = ref_logits(arch, params, jnp.asarray(a))
    ref_b = ref_logits(arch, params, jnp.asarray(b))
    tick_args = (jnp.zeros(3, jnp.int32), jnp.zeros(3), jnp.ones(3),
                 jnp.stack([jax.random.PRNGKey(i) for i in range(3)]),
                 jnp.ones(3, bool), jnp.zeros(3, bool), jnp.int32(-1))
    text = str(jax.make_jaxpr(
        lambda c: slot_decode_tick(dec, params, c, *tick_args))(cache))
    N = arch["ssm_state"]
    selects = [ln for ln in text.splitlines() if "select_n" in ln
               and f"f32[3,1,1,{N},128]" in ln.split("=")[0]]
    # (a jaxpr prints a call its three layers share once)
    assert ("name=ssm_step" in text) == (path == "kernel")
    assert bool(selects) == (path == "lax")
    tails = [ln for ln in text.splitlines() if "select_n" in ln
             and f"f32[3,1,3,{128 + 2 * N}]" in ln.split("=")[0]]
    assert tails                        # the tail is always selected

    def chunk(cache, slot, toks):
        cache, lg, _ = slot_prefill_chunk(dec, params, cache,
                                          jnp.int32(slot),
                                          jnp.asarray(toks))
        return cache, lg

    @jax.jit
    def slot_logits(cache, slot, tok):
        # jitted: run op by op, this test's hundred-odd applies were
        # most of its time
        sub = jax.tree.map(lambda l: l[slot], cache)
        (h, emb), _ = dec.apply(
            {"params": params, "cache": sub}, tok[None, None],
            return_hidden=True, mutable=["cache"])
        return jnp.einsum("d,vd->v", h[0, -1], emb)

    def tick(cache, feed, live):
        """Greedy tick; returns each slot's logits too (recomputed by
        a B = 1 apply on the same cache rows)."""
        logits = [slot_logits(cache, s, jnp.asarray(feed[s], jnp.int32))
                  for s in range(3)]
        cache, *_ = slot_decode_tick(
            dec, params, cache, jnp.asarray(feed, jnp.int32),
            jnp.zeros(3), jnp.ones(3),
            jnp.stack([jax.random.PRNGKey(i) for i in range(3)]),
            jnp.asarray(live), jnp.zeros(3, bool), jnp.int32(-1))
        return cache, logits

    cache, lg = chunk(cache, 0, a[:16])
    np.testing.assert_allclose(lg, ref_a[15], atol=ATOL)
    cache, lg = chunk(cache, 1, b[:16])             # slot 1: first chunk
    np.testing.assert_allclose(lg, ref_b[15], atol=ATOL)
    # ticks of slot 0 alone; slot 1 (mid-prefill) and 2 (free) ride them
    for t in range(16, 19):
        cache, logits = tick(cache, [a[t], 7, 9], [True, False, False])
        np.testing.assert_allclose(logits[0], ref_a[t], atol=ATOL)
    cache, lg = chunk(cache, 1, b[16:24])           # slot 1: second chunk
    np.testing.assert_allclose(lg, ref_b[23], atol=ATOL)
    for t in range(24, 30):                         # both decode
        feed = [a[min(t - 5, 20)], b[t], 3]
        cache, logits = tick(cache, feed, [t - 5 <= 20, True, False])
        np.testing.assert_allclose(logits[1], ref_b[t], atol=ATOL)
        if t - 5 <= 20:
            np.testing.assert_allclose(logits[0], ref_a[t - 5], atol=ATOL)
    # the free lane never moved: state, tail and fill are as reset
    free = jax.tree.map(lambda l: np.abs(np.asarray(l[2])).max(), cache)
    from jax.tree_util import tree_flatten_with_path
    for leaf, v in tree_flatten_with_path(free)[0]:
        if "cached_" not in str(leaf):
            assert v == 0, leaf


# ---- the engine -----------------------------------------------------------------
def test_engine_greedy_equals_generate_and_reports_the_state(params):
    model = f32_model()
    prompts = [tokens(n, n) for n in (5, 19, 33, 12)]
    refs = [np.asarray(generate(model, params, p[None], 7))[0, len(p):]
            for p in prompts]
    with ServingEngine(model, params, num_slots=2, warmup=True,
                       prefill_chunk_budget=8) as eng:
        outs = [np.asarray(h.result(timeout=300).tokens) for h in
                [eng.submit(p, 7) for p in prompts]]
        snap = eng.metrics_snapshot()
        nbytes = eng.pool.cache_bytes()
    for got, want in zip(outs, refs):
        np.testing.assert_array_equal(got, want)
    assert snap["compiles"] == 0
    assert snap["state_step_paths"] == {"ssm": "lax"}
    assert "not on a TPU" in snap["state_step_plans"]["ssm"]
    # 3 layers x 2 lanes x (a [16, 128] state + 3 rows of 160), float32
    assert nbytes["state"] == snap["pool_bytes"]["state"] == (
        3 * 2 * (16 * 128 + 3 * 160) * 4)
    assert nbytes["kv"] == 2 * 2 * MAX_LEN * 2 * 16 * 4


# ---- granite's head of 64: two KV heads to a stored row ------------------------
# One state-space and one NoPE GQA layer at the real head, 4 heads of
# 64 over 2 KV heads at scale 1/64: the leaves store ONE row of 128
# (`ops.flash_attention.kv_pack`), whatever reads or writes them.
ARCH_64 = dict(ARCH, num_layers=2, layer_kinds=["mamba", "attention"],
               head_dim=64, attn_scale=1 / 64)


def test_head_of_64_is_served_from_packed_rows_on_every_path():
    """Chunks prefill over the packed leaf (the walk) and ticks step
    it - by the walk, and by the ragged kernel and the in-place append
    forced (interpret mode); `generate` fills it in one pass and steps
    it by the kernel: one greedy stream, the reference's, and the last
    logits of kernel and walk within the kernel-against-walk
    tolerance. A reader or writer of the leaf that missed the packing
    parts here."""
    from horovod_tpu.serving.slots import SlotPool
    params = A.make_params(ARCH_64, MAX_LEN, 5, "float32")
    model = f32_model(ARCH_64)
    kernel = model.clone(decode_prefix_impl="pallas")
    assert decode_attention_plan(kernel, 2).pack == 2
    prompts = [tokens(20, 3), tokens(4, 4)]     # chunks of 16 + 4, and 4
    new = 8

    def serve(m):
        pool = SlotPool(m, params, 2)
        kv = pool._cache["block_1"]["attn"]["cached_key"]
        assert kv.shape == (2, 1, MAX_LEN, 1, 128)
        slots = [pool.alloc() for _ in prompts]
        first = [pool.prefill(slot, p, 0.0, None, 0)
                 for slot, p in zip(slots, prompts)]
        streams = np.stack([first] + [pool.tick()[slots]
                                      for _ in range(new - 1)])

        @jax.jit
        def next_logits(cache, slot, tok):
            sub = jax.tree.map(lambda leaf: leaf[slot], cache)
            (h, emb), _ = pool.dec_model.apply(
                {"params": params, "cache": sub}, tok[None, None],
                return_hidden=True, mutable=["cache"])
            return jnp.einsum("d,vd->v", h[0, -1], emb)

        last = [next_logits(pool._cache, slot, jnp.asarray(tok))
                for slot, tok in zip(slots, streams[-1])]
        return streams.T, np.stack(last), pool.kernel_plans()["decode_attn"]

    want, want_l, plans = serve(model)
    assert plans["attn"].path == "lax"
    got, got_l, plans = serve(kernel)
    assert "2 heads a row" in plans["attn"].describe()
    assert plans["attn"].write == "kernel"
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got_l, want_l, atol=2e-5, rtol=2e-5)
    # one pass over an empty packed cache, then the kernel's steps
    out = np.asarray(generate(kernel, params, prompts[1][None], new))[0]
    np.testing.assert_array_equal(out[len(prompts[1]):], want[1])
    # and the stream is the reference's: a full forward pass a token
    seq = np.concatenate([prompts[0], want[0]])
    ref = ref_logits(ARCH_64, params, jnp.asarray(seq[:-1]))
    np.testing.assert_array_equal(
        np.argmax(np.asarray(ref[len(prompts[0]) - 1:]), -1), want[0])


def test_options_that_need_appended_kv_refuse_by_name(params):
    """As they refuse the delta-rule hybrid: ONE predicate over the
    kinds that overwrite (`RECURRENT_KINDS`)."""
    model = f32_model()
    for option, value in (("paged", True), ("mesh", 2),
                          ("spec_draft", (model, params))):
        with pytest.raises(ValueError,
                           match=f"{option}.*recurrent.*snapshot form"):
            ServingEngine(model, params, num_slots=2, **{option: value})
