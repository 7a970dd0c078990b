"""A model with BOTH kinds of cache on the serving path - Kimi Linear's
period: delta-rule layers whose cache is a recurrent state that every
step overwrites, beside latent-attention layers without positions and
without a query rank whose cache is head-less rows a step appends; a
leading dense layer; sigmoid-routed held experts beside a shared one -
at a tiny size on the CPU, seeded random weights, against the plain
reference the benchmark keeps (`benchmarks/arch/kimi_linear.py`, which
imports nothing of the program).

ONE model and ONE engine for the module (engine defaults but the chunk
budget): the programs the engine compiled are the programs the logits
are read through - `slot_prefill_chunk` and `slot_decode_tick` are
jitted on the decode model, so a call of the test's own on a fresh cache
runs the engine's executables.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness import reference
from benchmarks.harness.cells import load_module
from horovod_tpu.models.transformer import (
    MOE_CHIPS_COLUMNS, TransformerLM, init_slot_cache, kernel_plans,
    moe_stat_columns, slot_decode_model, slot_decode_tick,
    slot_prefill_chunk,
)
from horovod_tpu.parallel.latent_attention import LatentSpec
from horovod_tpu.serving import ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
A = load_module(os.path.join(REPO, "benchmarks", "arch", "kimi_linear.py"),
                "arch_kimi_linear_for_tests")
with open(os.path.join(REPO, "tests", "benchmark", "tiny",
                       "tiny-kimi.json")) as f:
    TOY = json.load(f)["arch"]      # hidden 64, 6 of 24 experts, 8 layers
# one period of the toy's two (K K K M; the leading dense layer and three
# expert layers): every program of the engine compiles a layer at a time,
# and the suite's time is short
ARCH = dict(TOY, num_layers=4, layer_kinds=TOY["layer_kinds"][:4])
LAYERS, EXPERT_LAYERS, KDA_LAYERS = 4, 3, (0, 1, 2)
MAX_LEN, LANES, STORED, NEW = 128, 3, 128, 12
H, D, F3 = 4, 16, 3 * 4 * 16
# float32 program against the float32 reference at `highest`: what is
# left is the order of the sums (the chunkwise form of the delta rule
# against the recurrence, the cache's walk, the grouped product, the
# absorbed form) on logits of about 0.6 - the sibling tests' 3e-5. Every
# control below misses it by ten times and more.
ATOL = 3e-5


@pytest.fixture(scope="module", autouse=True)
def highest():
    """`highest` for the whole module, set in the configuration and not
    by the thread-local context manager: the engine's dispatch thread
    then looks its programs up under the key the warm-up compiled them
    under, and so do this module's own calls - one compile a shape."""
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", was)


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, ARCH["vocab_size"], n).astype(np.int32)


@pytest.fixture(scope="module")
def served():
    """The model, its weights, and what ONE engine served and said:
    four requests of ragged lengths through three lanes, the 70-token
    prompt in chunks of 8 between the others' ticks."""
    from horovod_tpu.obs import spans
    model = A.program_model(ARCH, max_len=MAX_LEN, attn_impl="dot",
                            dtype="float32")
    A.check_layout(ARCH, MAX_LEN, model)
    params = A.make_params(ARCH, MAX_LEN, 11, "float32")
    prompts = [tokens(n, n) for n in (5, 45, 70, 18)]
    with ServingEngine(model, params, num_slots=LANES, warmup=True,
                       prefill_chunk_budget=8) as eng:
        outs = [np.asarray(h.result(timeout=300).tokens, np.int32)
                for h in [eng.submit(p, NEW) for p in prompts]]
        snap = eng.metrics_snapshot()
        tree = jax.tree.map(lambda a: a.shape, eng.pool._cache)
    dispatches = [r["attrs"] for r in
                  spans.loop_tail(name="sched.tick_dispatch")]
    syncs = [r["attrs"] for r in spans.loop_tail(name="sched.tick_sync")]
    return dict(model=model, params=params, prompts=prompts, outs=outs,
                snap=snap, syncs=syncs, dispatches=dispatches, tree=tree)


# ---- the full forward = the reference ----------------------------------------------
def test_full_forward_equals_the_reference(served):
    toks = np.concatenate([served["prompts"][2], served["outs"][2]])
    got = np.asarray(jax.jit(lambda p, t: served["model"].apply(
        {"params": p}, t))(served["params"], jnp.asarray(toks)[None]))[0]
    want = np.asarray(jax.jit(lambda p, t: A.logits(ARCH, p, t))(
        served["params"], jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, atol=ATOL)


# ---- prefill, then decode, through the slot pool = the reference, on logits ----
def _tick(dec, params, cache, feed, live):
    cache, toks, _, _, pairs = slot_decode_tick(
        dec, params, cache, jnp.asarray(feed, jnp.int32),
        jnp.zeros(LANES, jnp.float32), jnp.ones(LANES, jnp.float32),
        jnp.zeros((LANES, 2), jnp.uint32), jnp.asarray(live),
        jnp.zeros(LANES, bool), jnp.int32(-1))
    return cache, np.asarray(toks), np.asarray(pairs)


@pytest.fixture(scope="module")
def pool_logits(served):
    """Logits read through the engine's own programs on a fresh cache,
    teacher-forced on what the engine served: lane 1 the 45-token
    prompt (chunks of 8 x 5, then the PADDED TAIL: 8 positions of which
    a traced count of 5 are real), lane 0 the 70-token one (chunked,
    with ticks of lane 1 between its chunks), lane 2 free: the logits at
    each chunk's last real position, and the tick's own greedy tokens.
    Beside them, what a tick of lane 1 alone left of lane 0's
    half-built caches."""
    model, params = served["model"], served["params"]
    dec = slot_decode_model(model)
    seqs = {1: np.concatenate([served["prompts"][1], served["outs"][1]]),
            0: np.concatenate([served["prompts"][2], served["outs"][2]])}
    got = {0: {}, 1: {}}
    cache = init_slot_cache(model, LANES)

    def chunk(cache, lane, lo, hi, width=None):
        toks = seqs[lane][lo:hi]
        count = ()
        if width is not None:           # the padded tail program
            toks = np.concatenate([toks, np.zeros(width - len(toks),
                                                  np.int32)])
            count = (jnp.int32(hi - lo),)
        cache, lg, pairs = slot_prefill_chunk(
            dec, params, cache, jnp.int32(lane), jnp.asarray(toks),
            *count)
        assert pairs.shape == (EXPERT_LAYERS,
                               6 + len(MOE_CHIPS_COLUMNS))
        got[lane][hi - 1] = np.asarray(lg)
        return cache

    for lo in range(0, 40, 8):
        cache = chunk(cache, 1, lo, lo + 8)
    cache = chunk(cache, 1, 40, 45, width=8)
    ticked, frozen = {}, []
    for lo in range(0, 64, 16):
        cache = chunk(cache, 0, lo, lo + 8)
        cache = chunk(cache, 0, lo + 8, lo + 16)
        # a tick of lane 1 alone between lane 0's chunks: lane 0
        # (mid-prefill) and lane 2 (free) ride it and must not move
        before = jax.tree.map(np.asarray, cache)
        t = 45 + lo // 16
        cache, toks, pairs = _tick(dec, params, cache,
                                   [7, seqs[1][t], 9],
                                   [False, True, False])
        frozen.append((before, jax.tree.map(np.asarray, cache)))
        # the chips column: 1 to 4 (of 4) a decoding lane and layer
        assert (1 <= pairs[:, -1]).all() and (pairs[:, -1] <= 4).all()
        ticked[(1, t)] = toks[1]
    cache = chunk(cache, 0, 64, 70, width=8)
    for t in range(70, 76):             # both decode, ragged fills
        cache, toks, _ = _tick(dec, params, cache,
                               [seqs[0][t], seqs[1][t - 21], 3],
                               [True, True, False])
        ticked[(0, t)], ticked[(1, t - 21)] = toks[0], toks[1]
    index = np.asarray(cache["block_3"]["mla"]["cache_index"])
    return seqs, got, ticked, index, frozen


@pytest.fixture(scope="module")
def full_forward(served, pool_logits):
    """The reference's full forward on the two teacher-forced
    sequences, ONCE: the shorter one padded to the longer's 82 tokens
    (the forward is causal: what follows a position does not move it),
    so that the reference's operations compile for one length."""
    seqs = pool_logits[0]
    longest = max(len(seq) for seq in seqs.values())
    logits = jax.jit(lambda p, t: A.logits(ARCH, p, t))
    want = {}
    for lane, seq in seqs.items():
        padded = np.zeros(longest, np.int32)
        padded[:len(seq)] = seq
        want[lane] = np.asarray(logits(
            served["params"], jnp.asarray(padded)))[:len(seq)]
    return want


def test_chunks_then_ticks_equal_the_reference_s_full_forward(
        pool_logits, full_forward):
    """`kda_chunked` from a cached state and the absorbed walk over
    cached rows in ONE chunk program, the padded tail among them; then
    the state's step and the rows' append in ONE tick."""
    seqs, got, ticked, index, _ = pool_logits
    for lane in seqs:
        want = full_forward[lane]
        assert len(got[lane]) >= 6
        for pos, lg in got[lane].items():
            np.testing.assert_allclose(lg, want[pos], atol=ATOL)
        for (ln, pos), tok in ticked.items():
            if ln == lane:      # the tick's greedy token is the
                assert tok == want[pos].argmax(), (ln, pos)  # reference's
    assert 44 in got[1] and 69 in got[0]        # the padded tails' logits
    assert len(ticked) == 4 + 2 * 6
    # the latent layer's index: lane 0 at 76, lane 1 at 55, the free lane 0
    assert index.tolist() == [76, 55, 0]


def test_a_lane_that_does_not_advance_keeps_both_kinds_of_cache(
        pool_logits):
    """Lane 0 mid-prefill and lane 2 free ride lane 1's tick: their
    `state` and `conv_tail` in every KDA layer AND their latent rows'
    write index stand as they were (bitwise); lane 1's all moved."""
    *_, frozen = pool_logits
    assert len(frozen) == 4
    for before, after in frozen:
        for i in KDA_LAYERS:
            for leaf in ("state", "conv_tail"):
                a = after[f"block_{i}"]["kda"][leaf]
                b = before[f"block_{i}"]["kda"][leaf]
                np.testing.assert_array_equal(a[[0, 2]], b[[0, 2]])
                assert (a[1] != b[1]).any(), (i, leaf)
        a, b = (t["block_3"]["mla"]["cache_index"] for t in (after, before))
        assert (a - b).tolist() == [0, 1, 0]
        # and lane 0's rows below its index are what its chunks wrote
        rows_a, rows_b = (t["block_3"]["mla"]["cached_latent"]
                          for t in (after, before))
        np.testing.assert_array_equal(rows_a[0, 0, :b[0]],
                                      rows_b[0, 0, :b[0]])


@pytest.mark.parametrize("control", ["beta2", "rotated", "state_bf16"])
def test_the_same_comparison_fails_without_each_mechanism(
        served, pool_logits, control):
    """beta = 2 sigmoid (Solar-Open2's layer), the rotation on (the
    DeepSeek family's default), and a state rounded to bf16 after every
    position: the program's logits are NOT the reference's then, by ten
    tolerances and more (the step to int8 is judged at the published
    widths: `tests/benchmark/test_kimi_rehearsal.py`)."""
    seqs, got, *_ = pool_logits
    low = np.asarray(A.logits(ARCH, served["params"],
                              jnp.asarray(seqs[0]), quant=control))
    worst = max(np.abs(lg - low[pos]).max()
                for pos, lg in got[0].items())
    assert worst > 10 * ATOL, (control, worst)


def test_the_engine_s_streams_are_the_reference_s_choice(
        served, full_forward):
    """What the engine served, teacher-forced through the reference:
    each served token lies within the tolerance of the reference's best
    (the benchmark's `correct`, `harness/reference.token_gaps`) - the
    two long requests by the full forward above, the last one by
    `served_logits`, the blocked form the benchmark calls."""
    assert all(len(out) == NEW for out in served["outs"])
    for lane, i in ((1, 1), (0, 2)):
        prompt, out = served["prompts"][i], served["outs"][i]
        ref = full_forward[lane][len(prompt) - 1:-1]
        assert reference.token_gaps(ref, out).max() <= ATOL
    prompt, out = served["prompts"][3], served["outs"][3]
    ref = A.served_logits(ARCH, served["params"], prompt, out,
                          seq_block=32, row_block=4)
    assert reference.token_gaps(ref, out).max() <= ATOL
    assert served["snap"]["compiles"] == 0


def test_the_program_chooses_the_reference_s_experts(served):
    """The full forward's `chosen` against the reference's
    `expert_routing`: the same 4 of 24 ids a token in each expert
    layer; the dense layer 0 sows none."""
    toks = np.concatenate([served["prompts"][2], served["outs"][2]])
    _, mut = jax.jit(lambda p, t: served["model"].apply(
        {"params": p}, t, mutable=["intermediates"]))(
            served["params"], jnp.asarray(toks)[None])
    ref = A.expert_routing(ARCH, served["params"], toks, seq_block=41)
    assert ref.shape == (EXPERT_LAYERS, len(toks), 4)
    assert "block_0" not in mut["intermediates"]
    for row, i in zip(ref, range(1, LAYERS)):
        chosen = np.sort(np.asarray(
            mut["intermediates"][f"block_{i}"]["moe"]["chosen"]), -1)
        np.testing.assert_array_equal(chosen, row)


# ---- the share ties to the model ----------------------------------------------------
def test_the_four_shares_add_up_to_the_uncut_layer():
    """One expert layer of the reference with ALL 24 experts, against
    the four chips' shares of 6 (each given its own experts' weights and
    told which they are) with the shared expert counted ONCE: the same
    [S, d] to float32 rounding. The program's share is the reference's
    share (`test_chunks_then_ticks...`), so the program's four shares add
    up to the uncut layer too."""
    whole = dict(ARCH, experts_held=[0, 24])
    p = A.make_params(whole, MAX_LEN, 5, "float32")["block_1"]["moe"]
    x = jnp.asarray(np.random.default_rng(2).normal(
        0, 1, (33, ARCH["hidden_size"])), jnp.float32)
    held = ("w_gate", "w_up", "w_down")

    @jax.jit
    def both(p, x):
        total = A._swiglu(x, p["shared"], None)     # counted once
        for first in range(0, 24, 6):
            share = dict(p, **{k: p[k][first:first + 6] for k in held})
            total = total + A.moe(ARCH, share, x, held=(first, 6),
                                  shared=False)
        alone = A.moe(ARCH, dict(p, **{k: p[k][:6] for k in held}), x)
        return A.moe(whole, p, x), total, alone

    full, total, alone = map(np.asarray, both(p, x))
    np.testing.assert_allclose(total, full, atol=2e-6)
    # and a share alone is NOT the layer: the cut is real
    assert np.abs(alone - full).max() > 1e-3


# ---- what the engine says of the model -------------------------------------------
def test_the_pool_takes_the_tree_the_model_declares(served):
    """`state` + `conv_tail` under `block_<i>/kda`, ONE `cached_latent`
    leaf and its index under `block_3/mla`, nothing else - no K/V leaf
    anywhere; the latent layer's queries are one projection `q`."""
    tree = served["tree"]
    assert sorted(tree) == [f"block_{i}" for i in range(LAYERS)]
    for i in KDA_LAYERS:
        assert tree[f"block_{i}"] == {"kda": {
            "state": (LANES, 1, H, D, D),
            "conv_tail": (LANES, 1, 3, F3)}}
    assert tree["block_3"] == {"mla": {
        "cached_latent": (LANES, 1, MAX_LEN, STORED),
        "cache_index": (LANES,)}}
    params = served["params"]
    assert sorted(params["block_0"]) == ["kda", "ln_attn", "ln_mlp", "mlp"]
    assert sorted(params["block_3"]) == ["ln_attn", "ln_mlp", "mla", "moe"]
    assert sorted(params["block_3"]["mla"]) == [
        "k_up", "kv_a", "kv_a_norm", "out", "q", "v_up"]
    assert params["block_3"]["mla"]["q"]["kernel"].shape == (64, 4 * 24)
    assert params["block_2"]["moe"]["router_bias"].shape == (24,)
    assert A.count(ARCH) == sum(a.size for a in jax.tree.leaves(params))


def test_the_records_that_exist_speak_for_the_model(served):
    """Nothing new was needed: `pool_bytes` by kind with `latent` AND
    `state` and no `kv`, the three plan families at once, the tick
    records' `context_sum` from the latent layer's index, and the chips
    column under ONE group."""
    snap, syncs = served["snap"], served["syncs"]
    state = 3 * LANES * (H * D * D * 4 + 3 * F3 * 4)
    assert snap["pool_bytes"] == {
        "kv": 0, "kv_window": 0, "state": state,
        "latent": 1 * LANES * MAX_LEN * STORED * 4}
    assert snap["decode_attn_paths"] == {"mla": "lax"}
    assert snap["state_step_paths"] == {"kda": "lax"}
    assert snap["moe_product_paths"] == {"tick": "lax", "prefill": "lax"}
    assert "not on a TPU" in snap["decode_attn_plans"]["mla"]
    plans = kernel_plans(served["model"], LANES, 8)
    assert (set(plans["decode_attn"]), set(plans["state_step"]),
            set(plans["moe_product"])) == ({"mla"}, {"kda"},
                                           {"tick", "prefill"})
    assert snap["moe_layers_ticks"] > 0
    assert snap["moe_layers_ticks"] % EXPERT_LAYERS == 0
    # contexts: the rows the latent layer holds for the decoding lanes
    busy = [d for d in served["dispatches"] if d["lanes_decoding"]]
    assert busy and all(
        d["context_sum"] >= 5 * d["lanes_decoding"] for d in busy)
    assert max(d["context_max"] for d in busy) >= 70
    # 4 chips of 6 experts, ONE group: a token's 4 experts lie on 1-4
    mine = [s for s in syncs if "moe_token_chips" in s]
    assert mine and all(s["moe_layers"] == EXPERT_LAYERS for s in mine)
    lane_layers = snap["lane_ticks_decoding"] * EXPERT_LAYERS
    assert lane_layers <= snap["moe_token_chips"] <= 4 * lane_layers
    assert moe_stat_columns(served["model"]) == MOE_CHIPS_COLUMNS


# ---- every option without a form for BOTH caches: refused, and both said -------------
@pytest.mark.parametrize("kw,name,latent_too", [
    (dict(paged=True), "paged", True),
    (dict(prefix_cache=True), "prefix_cache", True),
    (dict(preempt=True, swap_bytes=1 << 20), "swap_bytes", True),
    (dict(mesh=2), "mesh", True),
    # a latent pool alone takes speculative decoding; the state does not
    (dict(spec_draft="self"), "spec_draft", False),
])
def test_engine_refuses_by_name_and_names_both_caches(
        served, kw, name, latent_too):
    """The union of both tables: what either kind of cache has no form
    for is refused, and the message names EVERY kind that stands in the
    way - on the parent a model with both read the recurrent reason
    alone."""
    model = served["model"]
    if kw.get("spec_draft") == "self":
        kw = dict(spec_draft=(model, served["params"]))
    with pytest.raises(ValueError) as err:
        ServingEngine(model, served["params"], num_slots=2, **kw)
    said = str(err.value)
    assert said.startswith(f"{name}: "), said
    assert "recurrent" in said and "snapshot form" in said
    assert ("latent-attention layers" in said) == latent_too, said
    if latent_too:
        assert "block form of the latent row" in said
    assert "fixed slot pool" in said


# ---- the two new fields, and their defaults -------------------------------------------
def test_the_defaults_are_the_layers_as_they_were():
    """`allow_neg_eigval` True and `rotate` True: a model that names
    neither field lowers to the text of one that names the defaults,
    and each field off is a DIFFERENT program."""
    spec = dict(q_rank=8, kv_rank=16, nope_dim=16, rope_dim=8, v_dim=16)
    base = dict(vocab_size=64, num_layers=2, hidden_size=64, num_heads=4,
                head_dim=16, max_len=32, norm="rmsnorm", pos_emb="none",
                mlp_impl="swiglu", mlp_hidden=64, attn_impl="dot",
                layer_kinds=("kda", "mla"), dtype=jnp.float32)

    def text(**kw):
        model = TransformerLM(**dict(base, **kw))
        toks = jnp.zeros((1, 8), jnp.int32)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), toks)
        return jax.jit(model.apply).lower(params, toks).as_text()

    plain = text(latent=LatentSpec(**spec))
    assert plain == text(latent=LatentSpec(**spec, rotate=True),
                         kda_neg_eigval=True)
    assert plain != text(latent=LatentSpec(**spec), kda_neg_eigval=False)
    assert plain != text(latent=LatentSpec(**spec, rotate=False))
    assert LatentSpec(**spec).rotate and TransformerLM(
        **base).kda_neg_eigval
