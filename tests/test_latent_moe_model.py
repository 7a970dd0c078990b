"""A model of A.X-K1 layers on the serving path - the plain block
`x + MLA; x + MoE` (latent attention whose shared key turns at YaRN's
frequencies; a leading dense layer; sigmoid-routed held experts chosen
from a limited number of groups beside a shared expert) - at a tiny
size on the CPU, seeded random weights, against the plain reference the
benchmark keeps (`benchmarks/arch/axk1.py`, which imports nothing of
the program).

ONE model and ONE engine for the module (engine defaults but the chunk
budget): the programs the engine compiled are the programs the logits
are read through - `slot_prefill_chunk` and `slot_decode_tick` are
jitted on the decode model, so a call of the test's own on a fresh cache
runs the engine's executables. That `serving/` needed no change for this
model is what the module shows: the pool takes the tree `model.init`
declares, and `_NEEDS_APPENDED_KV` refuses by cache kind.
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
    MOE_CHIPS_COLUMNS, init_slot_cache, moe_stat_columns,
    slot_decode_model, slot_decode_tick, slot_prefill_chunk,
)
from horovod_tpu.serving import ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
A = load_module(os.path.join(REPO, "benchmarks", "arch", "axk1.py"),
                "arch_axk1_for_tests")
with open(os.path.join(REPO, "tests", "benchmark", "tiny",
                       "tiny-axk1.json")) as f:
    ARCH = json.load(f)["arch"]     # hidden 64, 6 of 24 experts in 4 groups
# the leading dense layer and two expert layers of the toy's five: every
# program of the engine compiles a layer at a time, and the suite's time
# is short (CHANGES.md, PR 41)
ARCH = dict(ARCH, num_layers=3)
LAYERS, EXPERT_LAYERS = 3, 2
MAX_LEN, LANES, STORED, NEW = 128, 3, 128, 12
# float32 program against the float32 reference at `highest`: what is
# left is the order of the sums (the cache's walk, the grouped product,
# the absorbed form) on logits of about 0.7 - the sibling tests' 3e-5.
# Every control below misses it by ten times and more.
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
    syncs = [r["attrs"] for r in spans.loop_tail(name="sched.tick_sync")]
    return dict(model=model, params=params, prompts=prompts, outs=outs,
                snap=snap, syncs=syncs, tree=tree)


# ---- prefill, then decode, through the slot pool = the reference, on logits ----
@pytest.fixture(scope="module")
def pool_logits(served):
    """Logits read through the engine's own programs on a fresh cache,
    teacher-forced on what the engine served: lane 1 the 45-token
    prompt (chunks of 8 x 5, 4, 1), lane 0 the 70-token one (chunked,
    with ticks of lane 1 between its chunks), lane 2 free: the logits at
    each chunk's last position, and the tick's own greedy tokens."""
    model, params = served["model"], served["params"]
    dec = slot_decode_model(model)
    seqs = {1: np.concatenate([served["prompts"][1], served["outs"][1]]),
            0: np.concatenate([served["prompts"][2], served["outs"][2]])}
    got = {0: {}, 1: {}}
    cache = init_slot_cache(model, LANES)

    def chunk(cache, lane, lo, hi):
        cache, lg, pairs = slot_prefill_chunk(
            dec, params, cache, jnp.int32(lane),
            jnp.asarray(seqs[lane][lo:hi]))
        assert pairs.shape == (EXPERT_LAYERS,
                               6 + len(MOE_CHIPS_COLUMNS))
        got[lane][hi - 1] = np.asarray(lg)
        return cache

    def tick(cache, feed, live):
        cache, toks, _, _, pairs = slot_decode_tick(
            dec, params, cache, jnp.asarray(feed, jnp.int32),
            jnp.zeros(LANES, jnp.float32),
            jnp.ones(LANES, jnp.float32),
            jnp.zeros((LANES, 2), jnp.uint32), jnp.asarray(live),
            jnp.zeros(LANES, bool), jnp.int32(-1))
        # the chips column: 1 or 2 (of 4) a decoding lane and layer
        chips = np.asarray(pairs[:, -1])
        assert ((sum(live) <= chips) & (chips <= 2 * sum(live))).all()
        return cache, np.asarray(toks)

    for lo, hi in ((0, 8), (8, 16), (16, 24), (24, 32), (32, 40),
                   (40, 44), (44, 45)):
        cache = chunk(cache, 1, lo, hi)
    ticked = {}
    for lo in range(0, 64, 16):
        cache = chunk(cache, 0, lo, lo + 8)
        cache = chunk(cache, 0, lo + 8, lo + 16)
        # a tick of lane 1 alone between lane 0's chunks: lane 0
        # (mid-prefill) and lane 2 (free) ride it and do not move
        t = 45 + lo // 16
        cache, toks = tick(cache, [7, seqs[1][t], 9],
                           [False, True, False])
        ticked[(1, t)] = toks[1]
    cache = chunk(cache, 0, 64, 68)
    cache = chunk(cache, 0, 68, 70)
    for t in range(70, 76):             # both decode, ragged fills
        cache, toks = tick(cache, [seqs[0][t], seqs[1][t - 21], 3],
                           [True, True, False])
        ticked[(0, t)], ticked[(1, t - 21)] = toks[0], toks[1]
    index = [np.asarray(cache[f"block_{i}"]["mla"]["cache_index"])
             for i in range(LAYERS)]
    return seqs, got, ticked, index


@pytest.fixture(scope="module")
def full_forward(served, pool_logits):
    """The reference's full forward on the two teacher-forced
    sequences, ONCE: the shorter one padded to the longer's 82 tokens
    (the forward is causal: what follows a position does not move it),
    so that the reference's operations compile for one length."""
    seqs = pool_logits[0]
    longest = max(len(seq) for seq in seqs.values())
    # one program for the lanes (all padded to one length): op by op
    # the reference compiled a primitive at a time
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
    seqs, got, ticked, index = pool_logits
    for lane in seqs:
        want = full_forward[lane]
        assert len(got[lane]) >= 5
        for pos, lg in got[lane].items():
            np.testing.assert_allclose(lg, want[pos], atol=ATOL)
        for (ln, pos), tok in ticked.items():
            if ln == lane:      # the tick's greedy token is the
                assert tok == want[pos].argmax(), (ln, pos)  # reference's
    assert len(ticked) == 4 + 2 * 6
    # every layer's index: lane 0 at 76, lane 1 at 55, the free lane 0
    assert all(i.tolist() == [76, 55, 0] for i in index)


@pytest.mark.parametrize("control", ["no_yarn", "plain_rope", "scale_1",
                                     "no_groups"])
def test_the_same_comparison_fails_without_each_mechanism(
        served, pool_logits, control):
    """YaRN off, the scale factor 1.0, both, and the group limit off:
    the program's logits are NOT the reference's then, by ten
    tolerances and more (the step to int8 is judged at the published
    widths: `tests/benchmark/test_axk1_rehearsal.py`)."""
    seqs, got, _, _ = pool_logits
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
    """The full forward's `chosen` (what `serve_arch.routing_flips`
    reads of a program) against the reference's `expert_routing`: the
    same 4 of 24 ids a token in each expert layer, all of them inside
    2 of the 4 groups; the dense layer 0 sows none."""
    toks = np.concatenate([served["prompts"][2], served["outs"][2]])
    _, mut = jax.jit(lambda p, t: served["model"].apply(
        {"params": p}, t, mutable=["intermediates"]))(
            served["params"], jnp.asarray(toks)[None])
    ref = A.expert_routing(ARCH, served["params"], toks, seq_block=30)
    assert ref.shape == (EXPERT_LAYERS, len(toks), 4)
    assert "block_0" not in mut["intermediates"]
    for row, i in zip(ref, range(1, LAYERS)):
        chosen = np.sort(np.asarray(
            mut["intermediates"][f"block_{i}"]["moe"]["chosen"]), -1)
        np.testing.assert_array_equal(chosen, row)
    assert max(len(set(tok // 6)) for tok in ref.reshape(-1, 4)) == 2


# ---- what the engine says of the model -------------------------------------------
def test_the_pool_takes_the_tree_the_model_declares(served):
    """One `cached_latent` leaf a layer under `block_<i>/mla` (LongCat's
    lie under `mla_0` / `mla_1`), one index each, nothing else."""
    tree = served["tree"]
    assert sorted(tree) == [f"block_{i}" for i in range(LAYERS)]
    for blk in tree.values():
        assert blk == {"mla": {"cached_latent": (LANES, 1, MAX_LEN, STORED),
                               "cache_index": (LANES,)}}
    params = served["params"]
    assert sorted(params["block_0"]) == ["ln_attn", "ln_mlp", "mla", "mlp"]
    assert sorted(params["block_2"]) == ["ln_attn", "ln_mlp", "mla", "moe"]
    assert params["block_2"]["moe"]["router"].shape == (64, 24)
    assert params["block_2"]["moe"]["w_gate"].shape == (6, 64, 32)
    assert A.count(ARCH) == sum(a.size for a in jax.tree.leaves(params))


def test_the_records_that_exist_speak_for_the_model(served):
    snap, syncs = served["snap"], served["syncs"]
    assert snap["pool_bytes"] == {
        "kv": 0, "kv_window": 0, "state": 0,
        "latent": LAYERS * LANES * MAX_LEN * STORED * 4}
    assert snap["decode_attn_paths"] == {"mla": "lax"}
    assert "not on a TPU" in snap["decode_attn_plans"]["mla"]
    assert set(snap["moe_product_plans"]) == {"tick", "prefill"}
    # two expert layers a tick (layer 0 is dense)
    assert snap["moe_layers_ticks"] > 0
    assert snap["moe_layers_ticks"] % EXPERT_LAYERS == 0
    assert snap["moe_zero_pairs"] == 0


def test_moe_token_chips_is_counted_carried_and_bounded(served):
    """4 chips of 6 experts = the 4 groups, 2 groups kept: a decoding
    token's 4 experts lie on 1 or 2 chips in each expert layer. On the tick's record and in the snapshot; a model that
    holds every expert, or chooses without groups, has no such count."""
    snap = served["snap"]
    mine = [s for s in served["syncs"] if "moe_token_chips" in s]
    assert mine and all(s["moe_layers"] == EXPERT_LAYERS for s in mine)
    assert all(EXPERT_LAYERS * s["tokens"] <= s["moe_token_chips"]
               for s in mine if s["tokens"])
    assert all(s["moe_token_chips"] <= 2 * EXPERT_LAYERS * LANES
               for s in mine)
    lane_layers = snap["lane_ticks_decoding"] * EXPERT_LAYERS
    assert lane_layers <= snap["moe_token_chips"] <= 2 * lane_layers
    assert snap["moe_token_chips"] >= sum(
        s["moe_token_chips"] for s in mine[-5:])
    model = served["model"]
    assert moe_stat_columns(model) == MOE_CHIPS_COLUMNS == (
        "moe_token_chips",)
    assert moe_stat_columns(model.clone(moe_held=None)) == ()
    assert moe_stat_columns(model.clone(moe_groups=None)) == ()


# ---- every option without a form for a latent row: refused by name -------------------
@pytest.mark.parametrize("kw,name", [
    (dict(paged=True), "paged"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(preempt=True, swap_bytes=1 << 20), "swap_bytes"),
    (dict(mesh=2), "mesh"),
])
def test_engine_refuses_by_name_what_has_no_form_for_a_latent_row(
        served, kw, name):
    with pytest.raises(ValueError,
                       match=f"^{name}: .*latent-attention layers.*rows "
                             "of 24 numbers without a head axis.*"
                             "missing block form of the latent row"):
        ServingEngine(served["model"], served["params"], num_slots=2, **kw)
