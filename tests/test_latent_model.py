"""A model of LongCat-Flash layers on the serving path - two latent-
attention sublayers and two dense FFNs a layer, a shortcut-connected
expert layer whose router is wider than its experts - at a tiny size on
the CPU, seeded random weights, against the plain reference the
benchmark keeps (`benchmarks/arch/longcat.py`, which imports nothing of
the program): the full forward pass, prefill chunks then decode ticks
through the slot pool (logits), a lane that does not advance, the engine
with its defaults, and every engine option either proved on the latent
pool or refused by name.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness.cells import load_module
from horovod_tpu.models.transformer import (
    LAYER_KINDS, MOE_ROUTED_COLUMNS, TransformerLM, generate,
    init_slot_cache, kernel_plans, slot_decode_model, slot_decode_tick,
    slot_prefill_chunk, slot_reset,
)
from horovod_tpu.serving import ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
A = load_module(os.path.join(REPO, "benchmarks", "arch", "longcat.py"),
                "arch_longcat_for_tests")
with open(os.path.join(REPO, "tests", "benchmark", "tiny",
                       "tiny-longcat.json")) as f:
    ARCH = json.load(f)["arch"]     # 2 layers, hidden 64, 4 of 16 + 8
MAX_LEN = 128
STORED = 128                        # a row of 16 + 8, padded to the lanes


def f32_model(**kw):
    return A.program_model(ARCH, max_len=MAX_LEN, attn_impl="dot",
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


# ---- the model = the reference ---------------------------------------------
def test_full_forward_equals_the_reference(params):
    toks = tokens(96, 3)
    # the model's forward one program, not a compile a primitive
    got, mut = jax.jit(lambda p, t: f32_model().apply(
        {"params": p}, t, mutable=["intermediates"]))(
            params, jnp.asarray(toks)[None])
    want = ref_logits(ARCH, params, jnp.asarray(toks))
    np.testing.assert_allclose(got[0], want, atol=3e-5)
    # in blocks, as a served request is checked
    served = A.served_logits(ARCH, params, toks[:50], toks[50:80],
                             seq_block=32, row_block=16)
    np.testing.assert_allclose(served, want[49:79], atol=3e-5)
    # and the routing, as `serve_arch.routing_flips` reads both sides
    ref = A.reference_routing(ARCH, params, toks, seq_block=32)
    for i in range(ARCH["num_layers"]):
        chosen = np.sort(np.asarray(
            mut["intermediates"][f"block_{i}"]["moe"]["chosen"]), -1)
        np.testing.assert_array_equal(chosen, ref[i])
    assert ref.max() >= ARCH["num_experts"]     # identity experts chosen


def test_the_model_says_what_it_is(params):
    model = f32_model()
    assert "mla" in LAYER_KINDS
    assert model.kinds == ("mla", "mla") and model.softmax_kinds == ()
    assert model.has_latent_cache
    assert not model.has_recurrent_state and not model.has_rolling_cache
    assert (model.latent.row, model.latent.stored) == (24, STORED)
    assert sorted(params["block_0"]) == [
        "ln_attn_0", "ln_attn_1", "ln_mlp_0", "ln_mlp_1", "mla_0", "mla_1",
        "mlp_0", "mlp_1", "moe"]
    assert params["block_1"]["moe"]["router"].shape == (64, 16 + 8)
    assert params["block_1"]["moe"]["w_gate"].shape == (4, 64, 32)
    A.check_layout(ARCH, MAX_LEN, model)
    assert A.count(ARCH) == sum(a.size for a in jax.tree.leaves(params))
    plans = kernel_plans(model, 4)["decode_attn"]
    assert list(plans) == ["mla"] and plans["mla"].path == "lax"
    forced = kernel_plans(model.clone(decode_prefix_impl="pallas"),
                          4)["decode_attn"]["mla"]
    assert (forced.path, forced.grid, forced.write) == (
        "kernel", (4, 1), "kernel")
    assert "latent rows of 128 read once" in forced.why
    with pytest.raises(ValueError, match="needs `latent`"):
        TransformerLM(vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
                      pos_emb="rope", layer_kinds=("mla",)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="shortcut_moe is a block WITH"):
        f32_model().clone(moe_every=0).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_chunks_then_ticks_through_the_slot_pool_equal_the_reference(
        params):
    """Two requests in a pool of three lanes, logits against the
    reference's full forward: a tick between two chunks of one prompt
    (the lane that does not advance keeps its latent rows and index),
    lanes of different lengths in one tick, a free lane riding every
    tick; the counters are the decoding lanes' alone."""
    model = f32_model()
    dec = slot_decode_model(model)
    cache = init_slot_cache(model, 3)
    a, b = tokens(60, 1), tokens(100, 2)
    ref_a = ref_logits(ARCH, params, jnp.asarray(a))
    ref_b = ref_logits(ARCH, params, jnp.asarray(np.pad(b, (0, 28))))

    def chunk(cache, slot, toks):
        cache, lg, pairs = slot_prefill_chunk(
            dec, params, cache, jnp.int32(slot), jnp.asarray(toks))
        assert pairs.shape == (2, 4 + len(MOE_ROUTED_COLUMNS))
        assert pairs[:, -1].tolist() == [len(toks) * 4] * 2
        return cache, lg

    @jax.jit
    def lane_logits(cache, slot, tok):  # a B = 1 apply on its rows
        sub = jax.tree.map(lambda l: l[slot], cache)
        (h, emb), _ = dec.apply(
            {"params": params, "cache": sub}, tok[None, None],
            return_hidden=True, mutable=["cache"])
        return jnp.einsum("d,vd->v", h[0, -1], emb)

    def tick(cache, feed, live):
        logits = [lane_logits(cache, jnp.int32(s), jnp.int32(feed[s]))
                  for s in range(3)]
        cache, _, _, _, pairs = slot_decode_tick(
            dec, params, cache, jnp.asarray(feed, jnp.int32),
            jnp.zeros(3), jnp.ones(3),
            jnp.stack([jax.random.PRNGKey(i) for i in range(3)]),
            jnp.asarray(live), jnp.zeros(3, bool), jnp.int32(-1))
        # chosen pairs: 4 a decoding lane and layer; identity ones among
        assert pairs[:, -1].tolist() == [4 * sum(live)] * 2
        assert (pairs[:, -2] <= pairs[:, -1]).all()
        assert (pairs[:, :-2].sum(-1) <= pairs[:, -1] - pairs[:, -2]).all()
        return cache, logits

    cache, lg = chunk(cache, 0, a[:32])
    np.testing.assert_allclose(lg, ref_a[31], atol=3e-5)
    cache, lg = chunk(cache, 1, b[:8])
    cache, lg = chunk(cache, 1, b[8:40])
    np.testing.assert_allclose(lg, ref_b[39], atol=3e-5)
    def lane_1(cache):              # its index, and the rows under it
        sub = jax.tree.map(lambda l: np.asarray(l[1]), cache)
        return jax.tree.map(lambda l: l[:, :40] if l.ndim else l, sub)

    frozen = lane_1(cache)
    # ticks of slot 0 alone; slot 1 (mid-prefill) and 2 (free) ride them
    for t in range(32, 37):
        cache, logits = tick(cache, [a[t], 7, 9], [True, False, False])
        np.testing.assert_allclose(logits[0], ref_a[t], atol=3e-5)
    for was, now in zip(jax.tree.leaves(frozen),
                        jax.tree.leaves(lane_1(cache))):
        np.testing.assert_array_equal(now, was)     # rows AND indices
    cache, lg = chunk(cache, 1, b[40:56])           # slot 1: next chunk
    np.testing.assert_allclose(lg, ref_b[55], atol=3e-5)
    cache, lg = chunk(cache, 1, b[56:60])
    np.testing.assert_allclose(lg, ref_b[59], atol=3e-5)
    for t in range(60, 76):                         # both decode
        ta = t - 23
        feed = [a[min(ta, 59)], b[t], 3]
        cache, logits = tick(cache, feed, [ta <= 59, True, False])
        np.testing.assert_allclose(logits[1], ref_b[t], atol=3e-5)
        if ta <= 59:
            np.testing.assert_allclose(logits[0], ref_a[ta], atol=3e-5)
    from jax.tree_util import tree_flatten_with_path
    leaves = tree_flatten_with_path(cache)[0]
    assert len(leaves) == 2 * 2 * 2     # layers x sublayers x (rows, index)
    for path, leaf in leaves:
        assert path[1].key in ("mla_0", "mla_1")
        if path[-1].key == "cached_latent":
            assert leaf.shape == (3, 1, MAX_LEN, STORED), path
            assert not np.asarray(leaf[..., 24:]).any()     # the pad
        else:                           # the free lane never moved
            assert np.asarray(leaf).tolist() == [53, 76, 0], path
    cache = slot_reset(dec, cache, jnp.int32(1))
    assert all(np.asarray(leaf).tolist() == [53, 0, 0]
               for path, leaf in tree_flatten_with_path(cache)[0]
               if path[-1].key == "cache_index")


# ---- the engine ------------------------------------------------------------
@pytest.fixture(scope="module")
def served(params):
    """ONE compiled engine for the module (defaults but the chunk
    budget): four requests through two lanes, and what it said."""
    model = f32_model()
    prompts = [tokens(n, n) for n in (5, 45, 70, 18)]
    from horovod_tpu.obs import spans
    with ServingEngine(model, params, num_slots=2, warmup=True,
                       prefill_chunk_budget=8) as eng:
        info = eng.warmup_info
        outs = [np.asarray(h.result(timeout=300).tokens) for h in
                [eng.submit(p, 24) for p in prompts]]
        snap = eng.metrics_snapshot()
        with pytest.raises(ValueError, match="exceeds max_len"):
            eng.submit(tokens(100), 40)
        with pytest.raises(ValueError, match="^transfer: .*latent"):
            eng.offer_transfer(object())
        assert eng.offer_transfer(None) is False
    syncs = [r["attrs"] for r in spans.loop_tail(name="sched.tick_sync")]
    return model, prompts, outs, snap, syncs, info


def test_engine_greedy_equals_generate(params, served):
    model, prompts, outs, snap, _, _ = served
    for p, got in zip(prompts, outs):
        want = np.asarray(generate(model, params, p[None], 24))[0, len(p):]
        np.testing.assert_array_equal(got, want)
    assert snap["compiles"] == 0


def test_engine_reports_the_latent_pool_and_the_new_counters(served):
    _, _, _, snap, syncs, _ = served
    rows = 2 * 2 * 2 * MAX_LEN * STORED * 4     # layers, sublayers, lanes
    assert snap["pool_bytes"] == {"kv": 0, "kv_window": 0, "state": 0,
                                  "latent": rows}
    assert snap["decode_attn_paths"] == {"mla": "lax"}
    assert "not on a TPU" in snap["decode_attn_plans"]["mla"]
    assert snap["moe_layers_ticks"] % 2 == 0
    chosen, zero = snap["moe_chosen_pairs"], snap["moe_zero_pairs"]
    # 4 chosen a decoding lane, tick and layer; a third or so identity
    assert chosen % 4 == 0 and chosen <= 4 * 2 * snap["moe_layers_ticks"]
    assert 0 < zero < chosen
    assert snap["moe_pairs"] <= chosen - zero
    mine = [s for s in syncs if "moe_chosen_pairs" in s]
    assert mine and all(
        0 <= s["moe_zero_pairs"] <= s["moe_chosen_pairs"]
        and s["moe_pairs"] <= s["moe_chosen_pairs"] - s["moe_zero_pairs"]
        for s in mine)


def test_engine_ticks_through_the_forced_kernel_equal_generate(params):
    """The tick's latent sublayers through the ragged kernel and the
    in-place append (interpret mode) under the tick's vmap: the same
    stream, two Mosaic calls a sublayer in the tick and no loop."""
    model = f32_model().clone(decode_prefix_impl="pallas")
    dec = slot_decode_model(model)
    cache = init_slot_cache(model, 2)
    text = slot_decode_tick.lower(
        dec, params, cache, jnp.zeros(2, jnp.int32), jnp.zeros(2),
        jnp.ones(2), jnp.zeros((2, 2), jnp.uint32), jnp.ones(2, bool),
        jnp.zeros(2, bool), jnp.int32(-1)).as_text(debug_info=True)
    for sub in ("block_0/mla_0", "block_0/mla_1", "block_1/mla_0",
                "block_1/mla_1"):       # the scopes the readers match
        assert f"{sub}/mla_" in text and "latent_decode" in text, sub
    assert "block_1/moe/" in text and "block_1/mlp_1/" in text
    p = tokens(21, 5)
    want = np.asarray(generate(f32_model(), params, p[None], 6))[0, 21:]
    with ServingEngine(model, params, num_slots=2) as eng:
        got = np.asarray(eng.submit(p, 6).result(timeout=300).tokens)
        snap = eng.metrics_snapshot()
    np.testing.assert_array_equal(got, want)
    assert snap["decode_attn_paths"] == {"mla": "kernel"}
    assert "write kernel" in snap["decode_attn_plans"]["mla"]


# ---- every option: proved, or refused by name ---------------------------------
@pytest.mark.parametrize("kw,name", [
    (dict(paged=True), "paged"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(preempt=True, swap_bytes=1 << 20), "swap_bytes"),
    (dict(mesh=2), "mesh"),
])
def test_engine_refuses_by_name_what_has_no_form_for_a_latent_row(
        params, kw, name):
    with pytest.raises(ValueError,
                       match=f"^{name}: .*latent-attention layers.*rows "
                             "of 24 numbers without a head axis.*"
                             "missing block form of the latent row"):
        ServingEngine(f32_model(), params, num_slots=2, **kw)


def test_pools_and_specs_refuse_too(params):
    from horovod_tpu.models.transformer import paged_cache_spec
    with pytest.raises(ValueError, match="latent"):
        paged_cache_spec(f32_model(), 16)


def test_speculative_decoding_rewinds_a_latent_cache_by_its_index(params):
    """`spec_draft` runs on a latent pool as it is: rejected positions
    are rewound by the index, the rows past it are dead - the greedy
    stream is `generate`'s."""
    model = f32_model()
    p = tokens(19, 8)
    want = np.asarray(generate(model, params, p[None], 12))[0, 19:]
    with ServingEngine(model, params, num_slots=2,
                       spec_draft=(model, params), spec_k=2) as eng:
        got = np.asarray(eng.submit(p, 12).result(timeout=300).tokens)
        snap = eng.metrics_snapshot()
    np.testing.assert_array_equal(got, want)
    assert snap["spec_rounds"] > 0
