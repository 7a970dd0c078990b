"""A hybrid model on the serving path: delta-rule linear attention (KDA)
with recurrent state beside K/V, gated NoPE GQA, and the chip's share of a
dropless mixture of experts - at a tiny size on the CPU, seeded random
weights, against the plain reference the benchmark keeps
(`benchmarks/arch/solar_open2.py`, which imports nothing of the program).
"""

import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness.cells import load_module
from horovod_tpu.models.transformer import (
    generate, init_slot_cache, slot_decode_model, slot_decode_tick,
    kernel_plans, slot_prefill_chunk,
)
from horovod_tpu.parallel.linear_attention import (
    kda_chunked, kda_recurrent,
)
from horovod_tpu.serving import ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
A = load_module(os.path.join(REPO, "benchmarks", "arch", "solar_open2.py"),
                "arch_solar_open2_for_tests")
with open(os.path.join(REPO, "tests", "benchmark", "tiny",
                       "tiny-solar.json")) as f:
    ARCH = json.load(f)["arch"]     # hidden 64, 2 KDA heads x 16, 16 experts
MAX_LEN = 64
# The same period with heads of 128: what `ops.kda_step`'s kernel takes
# (a [Dk, Dv] state of whole lanes), at two layers. Used under
# `kernel_path` ONLY, so no program of it is ever traced on the lax
# path and the jit caches need no clearing.
ARCH128 = dict(ARCH, head_dim=128, num_layers=2,
               layer_kinds=["gqa", "kda"])


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
def params128():
    return A.make_params(ARCH128, MAX_LEN, 11, "float32")


@pytest.fixture
def kernel_path(monkeypatch):
    """`kda_step_plan` as on a TPU: the state's S = 1 step is the
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


# ---- (a) the chunkwise form = the recurrence = the reference -------------
def kda_inputs(T, seed, state, decay=1.0, B=2, H=3, D=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(ks[i], (B, T, H, D)) for i in (0, 1))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, D))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (B, T, H, D)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    s0 = (jax.random.normal(ks[5], (B, H, D, D)) if state
          else jnp.zeros((B, H, D, D)))
    return s0, q, k, v, g, beta


@pytest.mark.parametrize("state", [False, True],
                         ids=["empty-state", "non-empty-state"])
@pytest.mark.parametrize("T,decay", [(2, 1.0), (16, 1.0), (37, 0.05),
                                     (64, 1.0), (128, 8.0), (200, 1.0)])
def test_kda_chunkwise_equals_recurrence(T, decay, state):
    """Sub-chunks of 64 in blocks of 16, any length, any state to start
    from; a decay of exp(-8) a step overflows nothing."""
    args = kda_inputs(T, T, state, decay)
    o1, s1 = kda_recurrent(*args)
    o2, s2 = jax.jit(kda_chunked)(*args)
    assert bool(jnp.isfinite(o2).all())
    np.testing.assert_allclose(o2, o1, atol=2e-5)
    np.testing.assert_allclose(s2, s1, atol=2e-5)


@pytest.mark.parametrize("split", [None, 24], ids=["whole", "two-chunks"])
def test_kda_layer_equals_reference(params, split):
    """The layer (convolution, gates, chunkwise core, norm) against the
    reference's token-by-token layer; in two chunks through the cache,
    the second starts from the first's state and convolution tail."""
    from horovod_tpu.parallel.linear_attention import KDAAttention
    p = params["block_1"]["kda"]
    x = jax.random.normal(jax.random.PRNGKey(3), (40, ARCH["hidden_size"]))
    want = A.kda_mixer(ARCH, p, x)
    layer = KDAAttention(
        num_heads=ARCH["num_heads"], head_dim=ARCH["head_dim"],
        out_features=ARCH["hidden_size"], dtype=jnp.float32,
        decode=split is not None)
    # one program a length, not a compile a primitive
    if split is None:
        got = jax.jit(layer.apply)({"params": p}, x[None])[0]
    else:
        cache = jax.tree.map(
            jnp.zeros_like, jax.jit(layer.init)(
                jax.random.PRNGKey(0), x[None])["cache"])
        step = jax.jit(lambda cache, part: layer.apply(
            {"params": p, "cache": cache}, part, mutable=["cache"]))
        parts = []
        for part in (x[:split], x[split:-1], x[-1:]):   # S > 1, then S = 1
            y, mut = step(cache, part[None])
            cache = mut["cache"]
            parts.append(y[0])
        got = jnp.concatenate(parts)
    np.testing.assert_allclose(got, want, atol=2e-5)


# ---- (b) the expert shares add up ----------------------------------------
def moe_layer(held):
    from horovod_tpu.parallel.expert import HeldExpertsMoE
    return HeldExpertsMoE(
        num_experts=ARCH["num_experts"], hidden=ARCH["expert_hidden"],
        k=ARCH["experts_per_token"], held=held,
        shared_hidden=ARCH["shared_hidden"], dtype=jnp.float32)


def share_params(p, first, n):
    return dict(p, **{k: p[k][first:first + n]
                      for k in ("w_gate", "w_up", "w_down")})


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips hold 4 of 16 experts each: the four partial results,
    the shared expert counted once, equal the uncut reference's layer."""
    whole = dict(ARCH, experts_held=[0, 16])
    p = A.make_params(whole, MAX_LEN, 5, "float32")["block_0"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (24, ARCH["hidden_size"]))
    want = A.moe(whole, p, x)
    sh = p["shared"]
    shared = A._swiglu(x, sh["gate"]["kernel"], sh["up"]["kernel"],
                       sh["down"]["kernel"], None)
    total = shared
    for first in (0, 4, 8, 12):
        part = moe_layer((first, 4)).apply(
            {"params": share_params(p, first, 4)}, x)
        # the reference given the same share gives the same part
        np.testing.assert_allclose(
            part, A.moe(whole, share_params(p, first, 4), x,
                        held=(first, 4)), atol=2e-5)
        total = total + (part - shared)
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_no_pair_is_dropped_when_every_token_takes_one_expert():
    """A routing that sends every token to expert 5 first: 24 pairs on
    one held expert, 0 on its neighbours, and the result is still the
    reference's."""
    p = dict(A.make_params(ARCH, MAX_LEN, 6, "float32")["block_0"]["moe"])
    p["router_bias"] = p["router_bias"].at[5].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (24, ARCH["hidden_size"]))
    got, mut = moe_layer((4, 4)).apply({"params": p}, x,
                                       mutable=["moe_stats"])
    pairs = np.asarray(mut["moe_stats"]["pairs"])
    assert pairs[1] == 24 and pairs.sum() >= 24
    np.testing.assert_allclose(got, A.moe(ARCH, p, x), atol=2e-5)


def test_a_vmap_over_lanes_is_one_grouped_product(params):
    """Under the tick's vmap the layer sees every lane's token at once:
    the result equals lane-by-lane applies, and the traced program holds
    one sort over all lanes, not one per lane."""
    p = params["block_2"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4),
                          (6, 1, 1, ARCH["hidden_size"]))
    layer = moe_layer(tuple(ARCH["experts_held"]))
    one = lambda xi: layer.apply({"params": p}, xi)
    want = jnp.stack([one(x[i]) for i in range(6)])
    np.testing.assert_allclose(jax.vmap(one)(x), want, atol=2e-5)
    text = str(jax.make_jaxpr(jax.vmap(one))(x))
    k = ARCH["experts_per_token"]
    assert f"i32[{6 * k}]" in text      # the pairs of all six lanes, flat


# ---- the whole model ------------------------------------------------------
def test_program_equals_reference_full_forward(params):
    toks = tokens(40)
    want = ref_logits(ARCH, params, jnp.asarray(toks))
    got = jax.jit(f32_model().apply)({"params": params}, toks[None])[0]
    np.testing.assert_allclose(got, want, atol=2e-5)
    A.check_layout(ARCH, MAX_LEN, f32_model())


# ---- (c) slots: chunks, ticks, and a tick between another slot's chunks ---
@pytest.mark.parametrize("path", ["lax", "kernel"])
def test_slot_chunks_and_ticks_equal_reference_with_an_interleaved_tick(
        path, request):
    """Slot 0 decodes while slot 1's prompt streams in in two chunks
    with a tick between them: the tick must leave slot 1's half-built
    state, convolution tail and fill alone. Every logit - slot 0's
    ticks, slot 1's chunks and its later ticks - is the reference's
    full forward pass. On both executors of the state's step: XLA's
    `kda_step`, frozen by the tick's select, and the in-place kernel,
    which keeps the state itself and is never selected after."""
    arch = ARCH if path == "lax" else ARCH128
    if path == "kernel":
        request.getfixturevalue("kernel_path")
    params = request.getfixturevalue(
        "params" if path == "lax" else "params128")
    model = f32_model(arch)
    dec = slot_decode_model(model)
    assert kernel_plans(dec, 3)["state_step"]["kda"].path == path
    cache = init_slot_cache(model, 3)
    a, b = tokens(21, 1), tokens(30, 2)
    ref_a = ref_logits(arch, params, jnp.asarray(a))
    ref_b = ref_logits(arch, params, jnp.asarray(b))
    # the tick as traced: the kernel's call a KDA layer and no select
    # of a state leaf on its path, the select and no call on the other
    tick_args = (jnp.zeros(3, jnp.int32), jnp.zeros(3), jnp.ones(3),
                 jnp.stack([jax.random.PRNGKey(i) for i in range(3)]),
                 jnp.ones(3, bool), jnp.zeros(3, bool), jnp.int32(-1))
    text = str(jax.make_jaxpr(
        lambda c: slot_decode_tick(dec, params, c, *tick_args))(cache))
    H, D = arch["num_heads"], arch["head_dim"]
    selects = [ln for ln in text.splitlines() if "select_n" in ln
               and f"f32[3,1,{H},{D},{D}]" in ln.split("=")[0]]
    kda_layers = arch["layer_kinds"].count("kda")
    assert text.count("name=kda_step") == (
        kda_layers if path == "kernel" else 0)
    assert bool(selects) == (path == "lax")     # (printed once if shared)

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
    np.testing.assert_allclose(lg, ref_a[15], atol=3e-5)
    cache, lg = chunk(cache, 1, b[:16])             # slot 1: first chunk
    np.testing.assert_allclose(lg, ref_b[15], atol=3e-5)
    # ticks of slot 0 alone; slot 1 (mid-prefill) and 2 (free) ride them
    for t in range(16, 19):
        cache, logits = tick(cache, [a[t], 7, 9], [True, False, False])
        np.testing.assert_allclose(logits[0], ref_a[t], atol=3e-5)
    cache, lg = chunk(cache, 1, b[16:24])           # slot 1: second chunk
    np.testing.assert_allclose(lg, ref_b[23], atol=3e-5)
    for t in range(24, 30):                         # both decode
        feed = [a[min(t - 5, 20)], b[t], 3]
        cache, logits = tick(cache, feed, [t - 5 <= 20, True, False])
        np.testing.assert_allclose(logits[1], ref_b[t], atol=3e-5)
        if t - 5 <= 20:
            np.testing.assert_allclose(logits[0], ref_a[t - 5], atol=3e-5)
    # the free lane never moved
    free = jax.tree.map(lambda l: np.abs(np.asarray(l[2])).max(), cache)
    from jax.tree_util import tree_flatten_with_path
    for path, v in tree_flatten_with_path(free)[0]:
        if "cached_" not in str(path):
            assert v == 0, path


def test_tick_counts_pairs_of_decoding_lanes_only(params):
    model = f32_model()
    dec = slot_decode_model(model)
    cache = init_slot_cache(model, 4)
    args = (jnp.zeros(4), jnp.ones(4),
            jnp.stack([jax.random.PRNGKey(i) for i in range(4)]))
    out = slot_decode_tick(dec, params, cache, jnp.asarray([1, 2, 3, 4]),
                           *args, jnp.asarray([True, True, False, True]),
                           jnp.asarray([False, True, False, False]),
                           jnp.int32(-1))
    pairs = np.asarray(out[4])
    assert pairs.shape == (ARCH["num_layers"], ARCH["experts_held"][1])
    # two lanes decode; each brings at most k pairs a layer
    assert 0 < pairs.sum() <= 2 * ARCH["experts_per_token"] * 4


# ---- (d) the engine -------------------------------------------------------
def test_engine_greedy_equals_generate(params):
    model = f32_model()
    prompts = [tokens(n, n) for n in (5, 19, 33, 12)]
    refs = [np.asarray(generate(model, params, p[None], 7))[0, len(p):]
            for p in prompts]
    born_ns = time.time_ns()    # the ring is the process's: an earlier
    #                             test file's engine left its records
    with ServingEngine(model, params, num_slots=2, warmup=True,
                       prefill_chunk_budget=8) as eng:
        outs = [np.asarray(h.result(timeout=300).tokens) for h in
                [eng.submit(p, 7) for p in prompts]]
        snap = eng.metrics_snapshot()
    for got, want in zip(outs, refs):
        np.testing.assert_array_equal(got, want)
    assert snap["compiles"] == 0
    assert snap["moe_layers_ticks"] >= ARCH["num_layers"]
    assert 0 < snap["moe_pairs"] <= (
        snap["moe_layers_ticks"] * 2 * ARCH["experts_per_token"])
    assert snap["moe_expert_load_max"] <= snap["moe_pairs"]
    assert snap["moe_prefill_pairs"] > 0
    assert snap["pool_bytes"]["state"] > 0 and snap["pool_bytes"]["kv"] > 0
    assert snap["state_step_paths"] == {"kda": "lax"}
    assert "not whole lanes" in snap["state_step_plans"]["kda"]
    from horovod_tpu.obs import spans
    syncs = [r for r in spans.loop_tail(name="sched.tick_sync")
             if "moe_pairs" in r["attrs"] and r["t0_ns"] >= born_ns]
    assert syncs and all(r["attrs"]["moe_layers"] == ARCH["num_layers"]
                         for r in syncs)


def test_a_dense_model_reports_no_expert_counters():
    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.parallel.tensor import unbox
    model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                          head_dim=8, max_len=32, dtype=jnp.float32,
                          attn_impl="dot")
    p = unbox(model.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"])
    assert not model.has_recurrent_state
    with ServingEngine(model, p, num_slots=2) as eng:
        eng.submit(np.arange(5), 4).result(timeout=120)
        snap = eng.metrics_snapshot()
    assert snap["moe_layers_ticks"] == 0 and snap["moe_pairs"] == 0
    assert snap["pool_bytes"] == {"kv": 2 * 2 * 32 * 2 * 8 * 4,
                                  "kv_window": 0, "state": 0}
    assert snap["state_step_paths"] == {} == snap["state_step_plans"]


def test_engine_on_the_kernels_path_serves_the_lax_streams(
        params128, request, caplog):
    """The engine whose ticks step the state through the in-place
    kernel: the streams `generate` makes on the lax path (computed
    before the rule is switched), no compile after warm-up, and the
    plan in the snapshot and in the warm-up log line."""
    import logging
    model = f32_model(ARCH128)
    prompts = [tokens(n, n) for n in (5, 19, 12)]
    refs = [np.asarray(generate(model, params128, p[None], 6))[0, len(p):]
            for p in prompts]
    request.getfixturevalue("kernel_path")
    with caplog.at_level(logging.INFO, logger="horovod_tpu"):
        with ServingEngine(model, params128, num_slots=2, warmup=True,
                           prefill_chunk_budget=8) as eng:
            outs = [np.asarray(h.result(timeout=300).tokens) for h in
                    [eng.submit(p, 6) for p in prompts]]
            snap = eng.metrics_snapshot()
    for got, want in zip(outs, refs):
        np.testing.assert_array_equal(got, want)
    assert snap["compiles"] == 0
    assert snap["state_step_paths"] == {"kda": "kernel"}
    assert "a block of 2 a step, in place" in snap["state_step_plans"]["kda"]
    assert any("state step: kda: kernel (on a TPU)" in r.getMessage()
               for r in caplog.records)


# ---- (d2) a model without a recurrent layer: the parent's tick -------------
def _tiny(name, module):
    mod = load_module(os.path.join(REPO, "benchmarks", "arch", module),
                      "arch_" + name.replace("-", "_") + "_for_tick_text")
    with open(os.path.join(REPO, "tests", "benchmark", "tiny",
                           name + ".json")) as f:
        return mod.program_model(json.load(f)["arch"], max_len=MAX_LEN,
                                 attn_impl="dot", dtype="float32")


def _dense():
    from horovod_tpu.models.transformer import TransformerLM
    return TransformerLM(vocab_size=64, num_layers=2, num_heads=4,
                         num_kv_heads=2, head_dim=8, max_len=MAX_LEN,
                         pos_emb="rope", norm="rmsnorm",
                         mlp_impl="swiglu", dtype=jnp.float32,
                         attn_impl="dot")


@pytest.mark.parametrize("make", [
    _dense, lambda: _tiny("tiny-laguna", "laguna.py"),
    lambda: _tiny("tiny-longcat", "longcat.py")],
    ids=["dense", "window-and-full", "latent"])
def test_tick_without_a_recurrent_layer_lowers_to_the_select_on_indices(
        make):
    """The other serving cells' kinds of model (dense GQA, full +
    sliding-window layers over held experts, latent sublayers with a
    shortcut expert layer): `slot_decode_tick` lowers to the text of
    the tick as it stood before the state's step had a kernel - the
    apply is told nothing, and the freeze is one `where` on each fill
    index and on nothing else - so those programs cannot drift."""
    import functools
    from jax.tree_util import tree_flatten_with_path, tree_unflatten
    from horovod_tpu.models import transformer as T

    def before(dec_model, params, cache, toks, temps, top_ps, rngs, live,
               done, eos):
        def one(sub, tok, rng, lv, dn):
            (hidden, embed), mut = dec_model.apply(
                {"params": params, "cache": sub}, tok[None, None],
                return_hidden=True, mutable=["cache", "moe_stats"])
            advance = lv & ~dn
            flat, treedef = tree_flatten_with_path(mut["cache"])
            new = tree_unflatten(treedef, [
                jnp.where(advance, leaf, old) if "index" in str(path)
                else leaf for (path, leaf), old
                in zip(flat, jax.tree.leaves(sub))])
            logits = jnp.einsum("d,vd->v", hidden[0, -1],
                                embed.astype(hidden.dtype))
            rng, r = jax.random.split(rng)
            return (new, logits.astype(jnp.float32), rng, r,
                    T._moe_pairs(dec_model, mut))

        cache, logits, rngs, keys, pairs = jax.vmap(one)(
            cache, toks, rngs, live, done)
        nxt = T.sample_lanes(logits, temps, top_ps, keys).astype(
            toks.dtype)
        emit = jnp.where(done, eos.astype(toks.dtype), nxt)
        decoding = (live & ~done)[:, None, None]
        return cache, emit, rngs, done | (emit == eos), jnp.sum(
            jnp.where(decoding, pairs, 0), axis=0)

    before.__name__ = before.__qualname__ = "slot_decode_tick"
    before = functools.partial(
        jax.jit, static_argnames=("dec_model",), donate_argnums=(2,))(
        before)
    model = make()
    assert not model.has_recurrent_state
    assert kernel_plans(model, 3)["state_step"] == {}
    dec = slot_decode_model(model)
    shapes = jax.eval_shape(
        lambda: (dec.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, MAX_LEN), jnp.int32))["params"],
                 init_slot_cache(model, 3)))
    from horovod_tpu.parallel.tensor import unbox
    args = (unbox(shapes[0]), shapes[1], jnp.zeros(3, jnp.int32),
            jnp.zeros(3), jnp.ones(3),
            jnp.stack([jax.random.PRNGKey(i) for i in range(3)]),
            jnp.ones(3, bool), jnp.zeros(3, bool), jnp.int32(-1))
    now = slot_decode_tick.lower(dec, *args).as_text()
    assert "select" in now
    assert now == before.lower(dec, *args).as_text()


# ---- (e) what cannot serve a recurrent state says so ------------------------
@pytest.mark.parametrize("kw,names", [
    (dict(paged=True), "paged"),
    (dict(spec_draft="self", spec_k=2), "spec_draft"),
    (dict(preempt=True, swap_bytes=1 << 20), "swap_bytes"),
    (dict(mesh=2), "mesh"),
])
def test_engine_refuses_what_has_no_snapshot_form(params, kw, names):
    model = f32_model()
    assert model.has_recurrent_state
    if kw.get("spec_draft") == "self":
        kw = dict(kw, spec_draft=(model, params))
    with pytest.raises(ValueError, match=f"{names}.*snapshot form"):
        ServingEngine(model, params, num_slots=2, **kw)


def test_engine_refuses_a_block_transfer(params):
    with ServingEngine(f32_model(), params, num_slots=2) as eng:
        with pytest.raises(ValueError, match="transfer.*snapshot form"):
            eng.offer_transfer(object())
        assert eng.offer_transfer(None) is False
