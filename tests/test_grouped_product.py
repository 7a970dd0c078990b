"""The held experts' grouped products through the weight-streaming kernel
(`ops.grouped_matmul`) on the CPU in interpret mode, against the
`lax.ragged_dot` path it replaces on the chip - the oracle: every routing a
dropless layer can meet, the rows no group owns, the tick's `vmap` and a
chunk, the rule (`grouped_product_plan`) at the serving cells' shapes and
at each refusal, gradients on both paths, and what an engine reports.
What Mosaic says of the same calls at the cells' widths is in
`tests/test_tpu_compile.py`; what the chip says, in PERF.md.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import (
    TransformerLM, generate, kernel_plans,
)
from horovod_tpu.ops import grouped_matmul
from horovod_tpu.ops.grouped_matmul import (
    expert_products, group_visits, grouped_product_plan,
)
from horovod_tpu.parallel.expert import HeldExpertsMoE, grouped_experts
from horovod_tpu.parallel.tensor import unbox
from horovod_tpu.serving import ServingEngine

D, F, E, K = 256, 128, 8, 4     # widths of whole lanes; 8 experts held


def weights(dtype, seed=0):
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(r.normal(size=s) / np.sqrt(s[1]), dtype)
                 for s in ((E, D, F), (E, D, F), (E, F, D)))


def routed(case, T, seed):
    """key [T, K] in 0..E (E: an expert held elsewhere) for a routing."""
    r = np.random.RandomState(seed)
    if case == "even":              # every held expert, equally often
        return (np.arange(T * K) % E).reshape(T, K)
    if case == "one-expert":        # a group longer than any row tile
        return np.full((T, K), 5)
    if case == "empty-experts":     # three of the eight, the rest none
        return r.choice([1, 4, 6, E], size=(T, K), p=[.1, .2, .1, .6])
    if case == "none-held":         # no pair for this chip
        return np.full((T, K), E)
    if case == "uneven":            # as a router leaves it: skewed, 3/4 away
        return r.choice(E + 1, size=(T, K),
                        p=[.02, .1, 0, .03, .05, .01, 0, .04, .75])
    raise KeyError(case)


ROUTINGS = ("even", "one-expert", "empty-experts", "none-held", "uneven")
TOL = {"float32": dict(rtol=2e-5, atol=2e-5),
       "bfloat16": dict(rtol=2e-2, atol=6e-2)}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("tokens", [48, 1], ids=["48-tokens", "one-token"])
@pytest.mark.parametrize("case", ROUTINGS)
def test_kernel_gives_what_ragged_dot_gives(case, tokens, dtype):
    """The whole layer's sum over the held experts - sort, products,
    weights, the select, the way back - on both paths. One token is a
    tail chunk: M = k rows, fewer than a row tile."""
    seed = len(case) + tokens
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.normal(size=(tokens, D)), dtype)
    key = jnp.asarray(routed(case, tokens, seed), jnp.int32)
    weight = jnp.asarray(r.uniform(0.1, 1.0, size=(tokens, K)),
                         jnp.float32)
    ws = weights(dtype)
    want = grouped_experts(x, key, weight, *ws, impl="lax")
    got = grouped_experts(x, key, weight, *ws, impl="pallas")
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])
    if case == "none-held":
        assert not np.asarray(got, np.float32).any()


@pytest.mark.parametrize("routed_over,tile", [(8, 64), (5, 128), (1, 256)])
def test_longer_groups_take_longer_row_tiles(routed_over, tile):
    """The row tile follows the rows an expert expects (tokens x k /
    router outputs): 64 rows up to 32 an expert, then 128, then 256 -
    each the same kernel, and what `lax.ragged_dot` gives."""
    T = 64
    r = np.random.RandomState(routed_over)
    x = jnp.asarray(r.normal(size=(T, D)), jnp.float32)
    key = jnp.asarray(r.randint(0, E, size=(T, K)), jnp.int32)
    weight = jnp.asarray(r.uniform(0.1, 1.0, size=(T, K)), jnp.float32)
    ws = weights(jnp.float32)
    plan = grouped_product_plan(T, K, routed_over, D, F, held=E,
                                dtype=jnp.float32, impl="pallas")
    assert plan.rows == tile, plan
    got = grouped_experts(x, key, weight, *ws, routed=routed_over,
                          impl="pallas")
    want = grouped_experts(x, key, weight, *ws, impl="lax")
    np.testing.assert_allclose(got, want, **TOL["float32"])


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("sizes", [
    (3, 0, 5, 2, 0, 0, 7, 1), (0, 0, 121, 0, 0, 3, 0, 0), (0,) * 8,
    (1, 1, 1, 1, 1, 1, 1, 1), (0, 0, 0, 0, 0, 0, 0, 128),
    (64, 0, 0, 0, 64, 0, 0, 0), (60, 9, 0, 0, 0, 0, 0, 0),
], ids=["mixed", "long-group", "nothing", "a-row-each", "last-expert-all",
        "tile-aligned", "across-a-tile-edge"])
def test_products_row_for_row(sizes, dtype):
    """`expert_products` on rows already sorted: each group's rows are
    its expert's SwiGLU, whatever lies in the rows past the last group
    (here NaN going in)."""
    M = 128
    n = sum(sizes)
    r = np.random.RandomState(n)
    xs = np.full((M, D), np.nan, np.float32)
    xs[:n] = r.normal(size=(n, D))
    xs, ws = jnp.asarray(xs, dtype), weights(dtype, 1)
    sizes = jnp.asarray(sizes, jnp.int32)
    plan = grouped_product_plan(M // K, K, 4 * E, D, F, held=E,
                                dtype=dtype, impl="pallas")
    assert plan.path == "kernel" and M % plan.rows == 0
    got = expert_products(xs, sizes, *ws, plan)
    want = expert_products(xs, sizes, *ws,
                           grouped_product_plan(1, 1, 1, D, F, impl="lax"))
    assert got.shape == want.shape == (M, D)
    np.testing.assert_allclose(np.asarray(got[:n], np.float32),
                               np.asarray(want[:n], np.float32),
                               **TOL[dtype])


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_rows_no_group_owns_are_selected_away(monkeypatch, dtype):
    """The kernel never writes the rows past the last group; on the
    chip they hold whatever the buffer held. Poisoned with NaN before
    the layer's select, they must not reach the sum: a select, not a
    multiply by a zero weight."""
    T = 24
    r = np.random.RandomState(3)
    x = jnp.asarray(r.normal(size=(T, D)), dtype)
    key = jnp.asarray(routed("uneven", T, 3), jnp.int32)
    weight = jnp.asarray(r.uniform(0.1, 1.0, size=(T, K)), jnp.float32)
    ws = weights(dtype)
    want = grouped_experts(x, key, weight, *ws, impl="lax")
    real = grouped_matmul.expert_products
    seen = []

    def poisoned(xs, sizes, *rest, **kw):
        y = real(xs, sizes, *rest, **kw)
        owned = jnp.arange(y.shape[0]) < sizes.sum()
        seen.append(y.shape[0])
        return jnp.where(owned[:, None], y, jnp.nan)

    monkeypatch.setattr(grouped_matmul, "expert_products", poisoned)
    # another `routed` than any other test: the layer is traced anew
    got = grouped_experts(x, key, weight, *ws, routed=4 * E + 1,
                          impl="pallas")
    assert seen == [128]            # T K = 96 in whole row tiles of 64
    assert int((key == E).sum()) > T * K // 2
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def _primitives(jaxpr, out=None):
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, out)
    return out


@pytest.mark.parametrize("how", ["tick-vmap", "chunk"])
def test_one_call_a_projection_and_no_ragged_dot(how):
    """Under the tick's `vmap` of B = 1 applies the lanes' tokens
    reach the kernel as ONE product - the gate | up call and the down
    call, no loop over lanes, nothing left of `lax.ragged_dot` - and a
    chunk (no vmap) is the same two calls; both equal the lax path."""
    lanes = 6
    r = np.random.RandomState(11)
    x = jnp.asarray(r.normal(size=(lanes, 1, D)), jnp.float32)
    key = jnp.asarray(routed("uneven", lanes, 11), jnp.int32)[:, None]
    weight = jnp.asarray(r.uniform(0.1, 1.0, size=(lanes, 1, K)),
                         jnp.float32)
    ws = weights(jnp.float32)

    def layer(impl):
        def one(x, key, weight):
            return grouped_experts(x, key, weight, *ws, routed=4 * E,
                                   impl=impl)
        if how == "tick-vmap":
            return jax.vmap(one)
        return lambda x, key, weight: one(
            x[:, 0], key[:, 0], weight[:, 0])[:, None]

    names = _primitives(jax.make_jaxpr(layer("pallas"))(
        x, key, weight).jaxpr)
    assert names.count("pallas_call") == 2, names
    assert "ragged_dot" not in names and "ragged_dot_general" not in names
    assert "while" not in names and "scan" not in names
    assert "ragged_dot_general" in _primitives(jax.make_jaxpr(
        layer("lax"))(x, key, weight).jaxpr)
    np.testing.assert_allclose(layer("pallas")(x, key, weight),
                               layer("lax")(x, key, weight),
                               **TOL["float32"])


def test_per_lane_weights_keep_the_lax_product():
    """A vmap over the WEIGHTS has nothing to merge: lanes of their
    own experts go through `lax.ragged_dot`'s batching, not the
    kernel's."""
    r = np.random.RandomState(5)
    x = jnp.asarray(r.normal(size=(2, 3, D)), jnp.float32)
    key = jnp.asarray(r.randint(0, E + 1, size=(2, 3, K)), jnp.int32)
    weight = jnp.ones((2, 3, K), jnp.float32)
    ws = [jnp.stack([w, 2 * w]) for w in weights(jnp.float32)]

    def one(x, key, weight, *ws):
        return grouped_experts(x, key, weight, *ws, impl="pallas")

    names = _primitives(jax.make_jaxpr(jax.vmap(one))(
        x, key, weight, *ws).jaxpr)
    assert "pallas_call" not in names
    got = jax.vmap(one)(x, key, weight, *ws)
    want = jnp.stack([grouped_experts(x[i], key[i], weight[i],
                                      *[w[i] for w in ws], impl="lax")
                      for i in range(2)])
    np.testing.assert_allclose(got, want, **TOL["float32"])


# ---- the schedule ------------------------------------------------------------
@pytest.mark.parametrize("tm", [16, 64, 128])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_visits_cover_every_group_tile_pair_once_in_order(seed, tm):
    r = np.random.RandomState(seed)
    rows = 256
    sizes = r.multinomial(r.randint(0, rows + 1),
                          r.dirichlet(np.full(12, 0.3))) * (
        r.uniform(size=12) > 0.3)
    offsets, group, tile, visits = jax.tree.map(
        np.asarray, group_visits(jnp.asarray(sizes, jnp.int32), rows, tm))
    ends = np.cumsum(sizes)
    want = [(g, t) for g in range(12) if sizes[g]
            for t in range((ends[g] - sizes[g]) // tm,
                           (ends[g] - 1) // tm + 1)]
    assert int(visits) == len(want) <= group.shape[0]
    assert list(zip(group[:visits], tile[:visits])) == want
    np.testing.assert_array_equal(offsets, np.concatenate([[0], ends]))
    # past the last visit: indices the pipeline may still look at
    assert (group >= 0).all() and (group < 12).all()
    assert (tile >= 0).all() and (tile < rows // tm).all()


# ---- the rule ----------------------------------------------------------------
CELLS = {   # tick tokens, k, router outputs, held, d, f
    "solar-open2-250b": (128, 8, 320, 40, 4096, 1280),
    "laguna-s-2.1": (64, 10, 256, 32, 3072, 1024),
    "longcat-flash-chat": (64, 12, 768, 16, 6144, 2048),
}


@pytest.mark.parametrize("tokens", ["tick", 128, 1])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_plan_takes_the_cells_ticks_and_chunks(cell, tokens):
    """One algorithm at three sets of sizes: on a TPU every tick and
    every chunk, the tail of one token included, streams whole weight
    rows in blocks that divide the contraction and fit VMEM twice."""
    T, k, routed, held, d, f = CELLS[cell]
    T = T if tokens == "tick" else tokens
    plan = grouped_product_plan(T, k, routed, d, f, held=held, on_tpu=True)
    assert plan.path == "kernel", plan
    assert plan.rows in grouped_matmul.ROW_TILES
    assert d % plan.k_gate_up == 0 and plan.k_gate_up % 128 == 0
    assert f % plan.k_down == 0 and plan.k_down % 128 == 0
    assert 2 * plan.k_gate_up * f * 2 <= grouped_matmul.BLOCK_BYTES
    assert plan.k_down * d * 2 <= grouped_matmul.BLOCK_BYTES
    rows = -(-T * k // plan.rows) * plan.rows
    assert plan.grid == (rows // plan.rows + held - 1, d // plan.k_gate_up)
    assert plan.vmem_bytes < 64 * 2 ** 20
    assert f"{T * k / routed:.2f} rows an expert" in plan.describe()


@pytest.mark.parametrize("kw,why", [
    (dict(on_tpu=False), "not on a TPU"),
    (dict(on_tpu=True, trivial_mesh=False), "a serving mesh"),
    (dict(on_tpu=True, d=4000), "not whole lanes"),
    (dict(on_tpu=True, f=1100), "not whole lanes"),
    (dict(on_tpu=True, tokens=16384), "over the ridge"),
    (dict(on_tpu=True, dtype=jnp.int8), "int8 weights"),
    (dict(on_tpu=True, impl="lax"), "forced"),
    (dict(impl="pallas", f=1100), "not whole lanes"),
    (dict(), "not on a TPU"),       # the CPU these tests run on
])
def test_plan_keeps_ragged_dot(kw, why):
    shape = dict(tokens=128, k=8, routed=320, d=4096, f=1280)
    shape.update({n: kw.pop(n) for n in list(kw) if n in shape})
    plan = grouped_product_plan(*shape.values(), held=40, **kw)
    assert plan.path == "lax" and why in plan.why, plan
    assert plan.describe() == f"lax ({plan.why})"


def test_plan_refuses_an_unknown_impl():
    with pytest.raises(ValueError, match="None\\|lax\\|pallas"):
        grouped_product_plan(1, 1, 1, 128, 128, impl="mosaic")


def test_products_refuse_rows_that_are_no_whole_tiles():
    plan = grouped_product_plan(8, K, 4 * E, D, F, impl="pallas")
    with pytest.raises(ValueError, match="row tile"):
        expert_products(jnp.zeros((plan.rows + 1, D)),
                        jnp.zeros(E, jnp.int32), *weights(jnp.float32),
                        plan)


# ---- through the layer and the model -----------------------------------------
def layer(**kw):
    return HeldExpertsMoE(num_experts=16, hidden=128, k=4, held=(4, 8),
                          shared_hidden=128, dtype=jnp.float32, **kw)


@pytest.mark.parametrize("router", ["sigmoid", "softmax"])
def test_gradients_are_the_lax_formulas_on_both_paths(monkeypatch, router):
    """`jax.grad` through `HeldExpertsMoE`: on the kernel's path the
    backward is `lax.ragged_dot`'s own (`jax.custom_vjp`), so the two
    paths' gradients agree as closely as their forward passes."""
    moe = layer(router=router)
    r = np.random.RandomState(7)
    x = jnp.asarray(r.normal(size=(2, 9, 128)), jnp.float32)
    params = unbox(moe.init(jax.random.PRNGKey(1), x)["params"])

    def loss(p, x):
        return jnp.sum(jnp.sin(moe.apply({"params": p}, x)))

    assert "pallas_call" not in _primitives(
        jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr)
    want_v, want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
        params, x)
    monkeypatch.setattr(grouped_matmul, "_on_tpu", lambda: True)
    jax.clear_caches()
    try:
        assert "pallas_call" in _primitives(
            jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr)
        got_v, got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
            params, x)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    np.testing.assert_allclose(got_v, want_v, rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want[0]["w_down"]).max()) > 0


def small_model(moe_impl="dropless"):
    return TransformerLM(
        vocab_size=64, num_layers=2, num_heads=2, head_dim=64, max_len=64,
        norm="rmsnorm", mlp_impl="swiglu", pos_emb="rope",
        dtype=jnp.float32, moe_every=1, moe_impl=moe_impl,
        num_experts=16, moe_k=4, moe_hidden=128, moe_held=(4, 8),
        moe_shared_hidden=128)


def test_model_plans_name_the_tick_and_the_chunk():
    plans = kernel_plans(small_model(), lanes=4,
                         chunk=16)["moe_product"]
    assert {k: p.path for k, p in plans.items()} == {
        "tick": "lax", "prefill": "lax"}
    assert kernel_plans(TransformerLM(
        vocab_size=64, num_layers=1, num_heads=2, head_dim=64,
        max_len=64))["moe_product"] == {}
    assert kernel_plans(small_model("gshard"))["moe_product"] == {}


def test_engine_says_which_product_its_expert_layers_took(monkeypatch):
    """`metrics_snapshot()` carries the plan of the tick and of the
    chunks; with the rule told it is on a TPU the engine's programs
    hold the kernel (interpret mode here) and serve the stream the lax
    path serves."""
    model = small_model()
    params = unbox(model.init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32))["params"])
    prompt = jnp.asarray(np.random.RandomState(2).randint(0, 64, 13))
    want = np.asarray(generate(model, params, prompt[None], 5))[0, 13:]
    with ServingEngine(model, params, num_slots=2,
                       prefill_chunk_budget=8) as eng:
        lax_tokens = np.asarray(eng.submit(prompt, 5).result(
            timeout=300).tokens)
        snap = eng.metrics_snapshot()
    np.testing.assert_array_equal(lax_tokens, want)
    assert snap["moe_product_paths"] == {"tick": "lax", "prefill": "lax"}
    assert "not on a TPU" in snap["moe_product_plans"]["tick"]

    monkeypatch.setattr(grouped_matmul, "_on_tpu", lambda: True)
    jax.clear_caches()
    try:
        with ServingEngine(model, params, num_slots=2,
                           prefill_chunk_budget=8) as eng:
            got = np.asarray(eng.submit(prompt, 5).result(
                timeout=300).tokens)
            snap = eng.metrics_snapshot()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    np.testing.assert_array_equal(got, want)
    assert snap["moe_product_paths"] == {"tick": "kernel",
                                         "prefill": "kernel"}
    assert "row tile 64" in snap["moe_product_plans"]["tick"]
    assert snap["moe_pairs"] > 0


def test_dense_engine_reports_no_expert_product():
    model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                          head_dim=16, max_len=32, dtype=jnp.float32)
    params = unbox(model.init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32))["params"])
    with ServingEngine(model, params, num_slots=1) as eng:
        snap = eng.metrics_snapshot()
    assert snap["moe_product_paths"] == {} == snap["moe_product_plans"]
