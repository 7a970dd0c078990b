"""A model whose softmax layers are of two kinds on the serving path: full
attention over a linear cache and sliding-window attention over a ring,
with different head counts over the same K/V heads, a per-head output
gate, YaRN on half the head, a leading dense layer and softmax-routed held
experts - at a tiny size on the CPU, seeded random weights, against the
plain reference the benchmark keeps (`benchmarks/arch/laguna.py`, which
imports nothing of the program).
"""

import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness.cells import load_module
from horovod_tpu.models.transformer import (
    AttnSpec, TransformerLM, kernel_plans, generate,
    init_slot_cache, slot_decode_model, slot_decode_tick,
    slot_prefill_chunk,
)
from horovod_tpu.parallel.expert import HeldExpertsMoE
from horovod_tpu.parallel.tensor import (
    ParallelSelfAttention, RopeSpec, apply_rope, unbox,
)
from horovod_tpu.serving import ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
A = load_module(os.path.join(REPO, "benchmarks", "arch", "laguna.py"),
                "arch_laguna_for_tests")
with open(os.path.join(REPO, "tests", "benchmark", "tiny",
                       "tiny-laguna.json")) as f:
    ARCH = json.load(f)["arch"]     # hidden 64; 4 and 6 heads on 2 x 16
WINDOW = ARCH["attention"]["sliding"]["window"]     # 16
MAX_LEN = 128
# the published rule of the full-attention layers
YARN = RopeSpec(theta=500000, fraction=0.5, yarn_factor=128,
                yarn_original_len=8192, yarn_beta_fast=32,
                yarn_beta_slow=1, scale=1.4852030263919618)


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


# ---- (a) the pieces, each alone ------------------------------------------
def test_yarn_frequencies_against_the_closed_form():
    """ISSUE 30's formula at the published numbers: the ramp runs from
    9 to 18 of the 32 frequencies; cos and sin carry the factor."""
    d_r, theta, L0 = 64, 500000.0, 8192

    def c(n):
        return d_r * math.log(L0 / (2 * math.pi * n)) / (2 * math.log(theta))

    assert (math.floor(c(32)), math.ceil(c(1))) == (9, 18)
    assert YARN.yarn_ramp(128) == (9, 18)
    j = np.arange(32)
    ramp = np.clip((j - 9) / 9, 0, 1)
    want = theta ** (-2 * j / d_r) * ((1 - ramp) + ramp / 128)
    np.testing.assert_allclose(YARN.inv_freq(128), want, rtol=1e-12)
    np.testing.assert_allclose(
        A.inv_freq(ARCH_PUBLISHED_ROPE, 128), want, rtol=1e-6)
    # below the ramp the published frequency, above it a 128th
    assert YARN.inv_freq(128)[9] == want[9] == theta ** (-18 / 64)
    assert YARN.inv_freq(128)[18] == theta ** (-36 / 64) / 128
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 128))
    pos = jnp.asarray([0, 777, 9000])
    got = apply_rope(x, pos, **YARN.rotation(128))
    for row, p in enumerate((0, 777, 9000)):
        ang = np.float32(p) * want.astype(np.float32)   # as float32 has it
        cos, sin = (np.cos(ang) * YARN.scale, np.sin(ang) * YARN.scale)
        x1, x2 = np.asarray(x[row, :, :32]), np.asarray(x[row, :, 32:64])
        np.testing.assert_allclose(got[row, :, :32], x1 * cos - x2 * sin,
                                   atol=2e-5)
        np.testing.assert_allclose(got[row, :, 32:64], x1 * sin + x2 * cos,
                                   atol=2e-5)
        np.testing.assert_array_equal(got[row, :, 64:], x[row, :, 64:])


ARCH_PUBLISHED_ROPE = {
    "type": "yarn", "theta": 500000, "partial_rotary_factor": 0.5,
    "factor": 128, "original_max_position_embeddings": 8192,
    "beta_fast": 32, "beta_slow": 1,
    "attention_factor": 1.4852030263919618}


def test_partial_rotary_alone():
    """Half the head turns as a head of half the size would; the other
    half passes through. The whole-head rule is what it was."""
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 3, 32))
    pos = jnp.arange(5) + 40
    half = apply_rope(x, pos, **RopeSpec(theta=1e4, fraction=0.5
                                         ).rotation(32))
    np.testing.assert_allclose(half[..., :16],
                               apply_rope(x[..., :16], pos, 1e4), atol=1e-6)
    np.testing.assert_array_equal(half[..., 16:], x[..., 16:])
    assert RopeSpec(theta=1e6).rotation(32) == {"theta": 1e6}
    with pytest.raises(ValueError, match="rotary part"):
        RopeSpec(fraction=0.3).rotation(10)


@pytest.mark.parametrize("gate", ["head", True])
def test_output_gate_forms(gate):
    """One scalar a head (W_g: d -> H) against the same attention
    without a gate, head by head; the elementwise form keeps its
    H*D-wide kernel."""
    H, D, d = 3, 8, 24
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, d))
    attn = ParallelSelfAttention(num_heads=H, head_dim=D, out_gate=gate,
                                 dtype=jnp.float32)
    v = unbox(attn.init(jax.random.PRNGKey(3), x))
    p = v["params"]
    assert p["gate"]["kernel"].shape == (d, H if gate == "head" else H * D)
    plain = ParallelSelfAttention(num_heads=H, head_dim=D,
                                  dtype=jnp.float32)
    # the ungated heads' outputs: an identity output projection
    eye = dict(p, out={"kernel": jnp.eye(H * D)})
    eye.pop("gate")
    o = plain.apply({"params": eye}, x).reshape(1, 6, H, D)
    g = jax.nn.sigmoid(x @ p["gate"]["kernel"])
    g = g[..., None] if gate == "head" else g.reshape(1, 6, H, D)
    want = (o * g).reshape(1, 6, H * D) @ p["out"]["kernel"]
    np.testing.assert_allclose(attn.apply({"params": p}, x), want,
                               atol=1e-5)
    with pytest.raises(ValueError, match="out_gate"):
        ParallelSelfAttention(num_heads=H, head_dim=D,
                              out_gate="heads").init(
            jax.random.PRNGKey(0), x)


# ---- (b) the ring through the kernel = the dense oracle ------------------
@pytest.mark.parametrize("H,Hkv", [(12, 2), (18, 2)],
                         ids=["groups-of-6", "groups-of-9"])
def test_ring_tick_through_the_kernel_equals_the_dense_oracle(H, Hkv):
    """S = 1 steps of a sliding-window layer, two rows at once, from an
    empty ring to 2.5 laps: the ragged decode kernel (interpret mode)
    over the ring's first min(pos + 1, W) slots against the dense
    [ring ++ block] branch, outputs and ring contents alike."""
    D, W, d = 32, 16, 48
    kw = dict(num_heads=H, num_kv_heads=Hkv, head_dim=D, window=W,
              pos_emb="rope", decode=True, chunked_prefill=True,
              out_features=d, out_gate="head", dtype=jnp.float32)
    lax_attn = ParallelSelfAttention(decode_prefix_impl="lax", **kw)
    ker_attn = ParallelSelfAttention(decode_prefix_impl="pallas", **kw)
    v = unbox(lax_attn.init(jax.random.PRNGKey(0), jnp.zeros((2, 64, d))))
    assert v["cache"]["cached_key"].shape == (2, W, Hkv, D)
    xs = jax.random.normal(jax.random.PRNGKey(H), (40, 2, 1, d))

    def run(attn):
        @jax.jit
        def step(cache, x):
            y, mut = attn.apply({"params": v["params"], "cache": cache},
                                x, mutable=["cache"])
            return mut["cache"], y
        return jax.lax.scan(step, v["cache"], xs)

    (c_lax, y_lax), (c_ker, y_ker) = run(lax_attn), run(ker_attn)
    np.testing.assert_allclose(y_ker, y_lax, atol=2e-5)
    assert int(c_ker["cache_index"]) == 40
    for name in ("cached_key", "cached_value"):
        np.testing.assert_allclose(c_ker[name], c_lax[name], atol=1e-6)


def test_plans_answer_a_kind():
    model = f32_model()
    plans = kernel_plans(model, 4)["decode_attn"]
    assert list(plans) == ["attn", "swa"]
    assert all(p.path == "lax" for p in plans.values())      # the CPU
    forced = kernel_plans(model.clone(decode_prefix_impl="pallas"),
                          4)["decode_attn"]
    assert forced["attn"].grid == (4, 1) and forced["swa"].grid == (4, 1)
    assert f"ring of {WINDOW} slots" in forced["swa"].why
    # on the chip, at the published shape: 48 and 72 heads over 8
    from horovod_tpu.ops.flash_attention import decode_attention_plan
    full = decode_attention_plan(64, 12288, 48, 8, 128, on_tpu=True)
    ring = decode_attention_plan(64, 512, 72, 8, 128, on_tpu=True,
                                 ring=True)
    assert (full.path, full.grid) == ("kernel", (64, 48))
    assert (ring.path, ring.grid) == ("kernel", (64, 2))
    assert "ring" in ring.why


# ---- (c) the model = the reference -----------------------------------------
def test_full_forward_equals_the_reference(params):
    toks = tokens(96, 3)                # six windows long
    # the model's forward one program, not a compile a primitive
    got = jax.jit(f32_model().apply)(
        {"params": params}, jnp.asarray(toks)[None])[0]
    want = ref_logits(ARCH, params, jnp.asarray(toks))
    np.testing.assert_allclose(got, want, atol=3e-5)
    # and in blocks, as a served request is checked
    served = A.served_logits(ARCH, params, toks[:50], toks[50:80],
                             seq_block=32, row_block=16)
    np.testing.assert_allclose(served, want[49:79], atol=3e-5)


def test_the_model_says_what_it_is(params):
    model = f32_model()
    assert model.kinds == ("attn", "swa", "swa", "swa", "attn")
    assert model.softmax_kinds == ("attn", "swa")
    full, swa = model.attn_spec("attn"), model.attn_spec("swa")
    assert (full.num_heads, full.window) == (4, None)
    assert (swa.num_heads, swa.window) == (6, WINDOW)
    assert full.rope.yarn_factor == 8 and swa.rope.yarn_factor is None
    assert model.has_rolling_cache and model.rolling_window == WINDOW
    assert not model.has_recurrent_state
    assert not model.context_unbounded      # one full layer bounds it
    assert sorted(params["block_0"]) == ["attn", "ln_attn", "ln_mlp",
                                         "mlp"]
    assert sorted(params["block_2"]) == ["ln_attn", "ln_mlp", "moe",
                                         "swa"]
    assert "router_bias" not in params["block_2"]["moe"]
    # a model with a window and no kinds is what it was
    old = TransformerLM(vocab_size=64, num_layers=2, num_heads=2,
                        head_dim=8, pos_emb="rope", window=8, max_len=32)
    assert old.kinds == ("attn", "attn") and old.context_unbounded
    assert old.attn_spec("attn") == AttnSpec(
        num_heads=2, window=8, rope=RopeSpec(theta=10000.0))
    with pytest.raises(ValueError, match="needs a window"):
        TransformerLM(vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
                      layer_kinds=("swa",)).attn_spec("swa")
    with pytest.raises(ValueError, match="layer_kinds must be of"):
        TransformerLM(vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
                      layer_kinds=("local",)).kinds


def test_chunks_then_ticks_through_the_slot_pool_equal_the_reference(
        params):
    """Two requests in a pool of three lanes, logits against the
    reference's full forward: contexts that pass the window (16) and
    lap the rings up to six times, a tick between two chunks of one
    prompt, chunks that straddle the ring's end, lanes of different
    lengths in one tick, a free lane riding every tick."""
    model = f32_model()
    dec = slot_decode_model(model)
    cache = init_slot_cache(model, 3)
    a, b = tokens(60, 1), tokens(100, 2)
    ref_a = ref_logits(ARCH, params, jnp.asarray(a))
    ref_b = ref_logits(ARCH, params, jnp.asarray(np.pad(b, (0, 28))))

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

    cache, lg = chunk(cache, 0, a[:32])             # two laps in one chunk
    np.testing.assert_allclose(lg, ref_a[31], atol=3e-5)
    cache, lg = chunk(cache, 1, b[:8])
    cache, lg = chunk(cache, 1, b[8:40])            # straddles the ring's end
    np.testing.assert_allclose(lg, ref_b[39], atol=3e-5)
    # ticks of slot 0 alone; slot 1 (mid-prefill) and 2 (free) ride them
    for t in range(32, 37):
        cache, logits = tick(cache, [a[t], 7, 9], [True, False, False])
        np.testing.assert_allclose(logits[0], ref_a[t], atol=3e-5)
    cache, lg = chunk(cache, 1, b[40:56])           # slot 1: next chunk
    np.testing.assert_allclose(lg, ref_b[55], atol=3e-5)
    cache, lg = chunk(cache, 1, b[56:60])
    np.testing.assert_allclose(lg, ref_b[59], atol=3e-5)
    for t in range(60, 100):                        # both decode
        ta = t - 23
        feed = [a[min(ta, 59)], b[t], 3]
        cache, logits = tick(cache, feed, [ta <= 59, True, False])
        np.testing.assert_allclose(logits[1], ref_b[t], atol=3e-5)
        if ta <= 59:
            np.testing.assert_allclose(logits[0], ref_a[ta], atol=3e-5)
    from jax.tree_util import tree_flatten_with_path
    for path, leaf in tree_flatten_with_path(cache)[0]:
        rows = {"attn": MAX_LEN, "swa": WINDOW}[path[1].key]
        if "cached_" in str(path):
            assert leaf.shape == (3, 1, rows, 2, 16), path
        else:                           # the free lane never moved
            assert np.asarray(leaf).tolist() == [60, 100, 0], path


# ---- (d) the expert layer ------------------------------------------------------
def _moe(held, **kw):
    return HeldExpertsMoE(num_experts=16, hidden=32, k=4, held=held,
                          shared_hidden=32, router="softmax", scale=2.5,
                          dtype=jnp.float32, **kw)


def test_the_expert_shares_add_up():
    """Eight chips' shares - each the routed part of its two experts
    plus the shared expert - summed, with the shared expert counted
    once, are the uncut layer."""
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 48))
    whole = _moe(None)
    p = unbox(whole.init(jax.random.PRNGKey(6), x))["params"]
    assert "router_bias" not in p
    uncut = whole.apply({"params": p}, x)
    shared = whole.apply(
        {"params": dict(p, **{k: jnp.zeros_like(p[k]) for k in
                              ("w_gate", "w_up", "w_down")})}, x)
    total = shared
    for chip in range(8):
        part = dict(p, **{k: p[k][2 * chip:2 * chip + 2] for k in
                          ("w_gate", "w_up", "w_down")})
        total = total + _moe((2 * chip, 2)).apply({"params": part},
                                                  x) - shared
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    assert float(jnp.abs(uncut - shared).max()) > 1e-3


def test_softmax_routing_drops_no_pair_and_scales():
    """However uneven the router, every (token, expert) pair of a held
    expert is computed; weights sum to the scale."""
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 40, 48))
    layer = _moe(None)
    p = unbox(layer.init(jax.random.PRNGKey(8), x))["params"]
    p = dict(p, router=p["router"].at[:, :4].add(3.0))    # 4 hot experts
    y, mut = layer.apply({"params": p}, x,
                         mutable=["moe_stats", "intermediates"])
    pairs = np.asarray(mut["moe_stats"]["pairs"])
    assert pairs.sum() == 40 * 4 and pairs.max() >= 20
    # against the reference's loop over experts
    arch = dict(ARCH, num_experts=16, experts_held=[0, 16],
                experts_per_token=4)
    want = A.moe(arch, p, x[0])
    np.testing.assert_allclose(y[0], want, atol=3e-5)
    _, w = A.route(arch, p, x[0])
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)
    with pytest.raises(ValueError, match="router must be"):
        HeldExpertsMoE(num_experts=4, hidden=8, router="top").init(
            jax.random.PRNGKey(0), x)


# ---- (e) the engine ----------------------------------------------------------------
@pytest.mark.parametrize("impl", [None, "pallas"],
                         ids=["cpu-rule", "kernel-forced"])
def test_engine_greedy_equals_generate(params, impl):
    """`impl` "pallas": both kinds' ticks go through the ragged kernel
    (interpret mode) under the tick's vmap - the ring's among them."""
    model = f32_model()
    if impl:
        model = model.clone(decode_prefix_impl=impl)
    prompts = [tokens(n, n) for n in (5, 45, 70, 18)]
    refs = [np.asarray(generate(model, params, p[None], 24))[0, len(p):]
            for p in prompts]
    from horovod_tpu.obs import spans
    with ServingEngine(model, params, num_slots=2, warmup=True,
                       prefill_chunk_budget=8) as eng:
        outs = [np.asarray(h.result(timeout=300).tokens) for h in
                [eng.submit(p, 24) for p in prompts]]
        snap = eng.metrics_snapshot()
        with pytest.raises(ValueError, match="exceeds max_len"):
            eng.submit(tokens(100), 40)     # the full layers bound it
    for got, want in zip(outs, refs):
        np.testing.assert_array_equal(got, want)
    assert snap["compiles"] == 0
    kv = 2 * 2 * 2 * 16 * 4             # K, V x lanes x Hkv x D x f32
    assert snap["pool_bytes"] == {"kv": 2 * MAX_LEN * kv,
                                  "kv_window": 3 * WINDOW * kv, "state": 0}
    path = "kernel" if impl else "lax"
    assert snap["decode_attn_paths"] == {"attn": path, "swa": path}
    assert snap["decode_attn_path"] == path
    assert "ring of 16" in snap["decode_attn_plans"]["swa"]
    ticks = [r["attrs"] for r in spans.loop_tail(name="sched.tick_dispatch")
             if r["attrs"].get("context_window_sum")]
    assert ticks and all(
        t["lanes_decoding"] <= t["context_window_sum"]
        <= min(t["context_sum"], WINDOW * t["lanes_decoding"])
        for t in ticks)
    assert any(t["context_window_sum"] < t["context_sum"] for t in ticks)
    assert 0 < snap["tick_window_positions"] < snap["tick_context_positions"]
    assert snap["moe_layers_ticks"] % 4 == 0 and snap["moe_pairs"] > 0


@pytest.mark.parametrize("kw,name", [
    (dict(paged=True), "paged"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_draft="self", spec_k=2), "spec_draft"),
    (dict(preempt=True, swap_bytes=1 << 20), "swap_bytes"),
    (dict(mesh=2), "mesh"),
])
def test_engine_refuses_by_name_what_has_no_form_for_a_ring(params, kw,
                                                           name):
    model = f32_model()
    if kw.get("spec_draft") == "self":
        kw = dict(kw, spec_draft=(model, params))
    with pytest.raises(ValueError,
                       match=f"^{name}: .*ring.* of {WINDOW} slots.*"
                             "missing block form of the ring"):
        ServingEngine(model, params, num_slots=2, **kw)


def test_engine_refuses_a_block_transfer_and_pools_refuse_too(params):
    model = f32_model()
    with ServingEngine(model, params, num_slots=2) as eng:
        with pytest.raises(ValueError, match="^transfer: .*ring"):
            eng.offer_transfer(object())
        assert eng.offer_transfer(None) is False
    # the small repairs: a mixed model is refused where a windowed one is
    from horovod_tpu.models.transformer import paged_cache_spec
    from horovod_tpu.serving.slots import validate_spec_draft
    with pytest.raises(ValueError, match="rolling-window"):
        paged_cache_spec(model, 16)
    with pytest.raises(ValueError, match="rolling"):
        validate_spec_draft(model, (model, params), 2)


def test_models_without_a_window_report_none(params):
    """A hybrid (KDA + GQA) model's pool holds no ring and its ticks
    count no window positions."""
    solar = load_module(os.path.join(REPO, "benchmarks", "arch",
                                     "solar_open2.py"), "arch_solar_mixed")
    with open(os.path.join(REPO, "tests", "benchmark", "tiny",
                           "tiny-solar.json")) as f:
        arch = json.load(f)["arch"]
    model = solar.program_model(arch, max_len=64, attn_impl="dot",
                                dtype="float32")
    assert not model.has_rolling_cache and model.rolling_window is None
    from horovod_tpu.obs import spans
    with ServingEngine(model, solar.make_params(arch, 64, 3, "float32"),
                       num_slots=2) as eng:
        eng.submit(np.arange(9), 6).result(timeout=300)
        snap = eng.metrics_snapshot()
    assert snap["pool_bytes"]["kv_window"] == 0
    assert snap["pool_bytes"]["kv"] > 0 and snap["pool_bytes"]["state"] > 0
    assert snap["tick_window_positions"] == 0
    assert snap["decode_attn_paths"] == {"attn": "lax"}
    ticks = spans.loop_tail(name="sched.tick_dispatch")
    assert ticks and ticks[-1]["attrs"]["context_window_sum"] == 0
