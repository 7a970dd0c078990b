"""The decode programs' sampling epilogue (`sample_lanes`): decided once
a tick, on the batch, by a scalar the device computes.

Oracle style: the per-lane rule AS IT WAS before the epilogue left the
vmap is kept here (`_sample_token_as_it_was`), and each tick program is
run twice on the same state - as it stands, and with that rule vmapped
over the lanes in `sample_lanes`' place - for every mix of greedy,
sampling and nucleus lanes. Tokens, carried keys and stop flags must be
bitwise equal: the change is the same work done once instead of always,
never another draw.

Structure style: the vocabulary-wide sort and cumsum must sit under a
`cond` whose predicate is a scalar, so a later edit that puts the choice
back under the lanes' vmap (where it lowers to a select and runs every
branch) fails here and not in a ledger line.
"""

import time
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer as tr
from horovod_tpu.models.transformer import TransformerLM, generate
from horovod_tpu.parallel.tensor import unbox
from horovod_tpu.serving import ServingEngine, slots as slots_mod
from horovod_tpu.serving import paging as paging_mod
from horovod_tpu.serving.paging import PagedSlotPool
from horovod_tpu.serving.slots import SlotPool

VOCAB = 64
MAX_LEN = 32
BS = 4
EOS = 3


@pytest.fixture(scope="module")
def lm(hvd):
    model = TransformerLM(vocab_size=VOCAB, num_layers=2, num_heads=4,
                          head_dim=8, max_len=MAX_LEN,
                          dtype=jnp.float32)
    params = unbox(model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 16), jnp.int32))["params"])
    return model, params


def _sample_token_as_it_was(logits, temperature, top_p, key):
    """`sample_token`'s expression at the parent of PR 29, which the
    ticks called from under their vmap: every lane computes the sort
    and the draw, and the `where`s select."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    sampled = jax.random.categorical(
        key, jnp.where(top_p < 1.0, tr.nucleus_mask(scaled, top_p),
                       scaled))
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _with_the_rule_as_it_was(jitted, **jit_kw):
    """``jitted``'s own Python body, with `sample_lanes` bound to the
    old per-lane rule vmapped over the lanes - in a copy of the
    function's globals, so the program under test never sees it."""
    raw = jitted.__wrapped__
    glb = dict(raw.__globals__,
               sample_lanes=jax.vmap(_sample_token_as_it_was))
    fn = types.FunctionType(raw.__code__, glb, raw.__name__,
                            raw.__defaults__, raw.__closure__)
    fn.__kwdefaults__ = raw.__kwdefaults__
    return jax.jit(fn, **jit_kw)


@pytest.fixture(scope="module")
def oracle_ticks():
    return {
        "slot": (slots_mod, "slot_decode_tick", _with_the_rule_as_it_was(
            tr.slot_decode_tick, static_argnames=("dec_model",))),
        "paged": (paging_mod, "paged_decode_tick",
                  _with_the_rule_as_it_was(
                      tr.paged_decode_tick,
                      static_argnames=("dec_model", "spec", "fused"))),
    }


# (temperature, top_p) a lane; the LAST listed lane is marked done
# after its prefill, and one more lane of the pool stays free.
MIXES = {
    "all_greedy": [(0.0, None), (0.0, None), (0.0, None), (0.0, None)],
    "greedy_and_temperature": [
        (0.0, None), (0.7, None), (0.0, None), (1.3, None)],
    # a greedy lane may carry a top_p: it asks for no sort
    "greedy_with_top_p_and_temperature": [
        (0.0, 0.5), (0.9, None), (0.0, None), (0.0, 0.9)],
    "greedy_temperature_and_top_p": [
        (0.0, None), (0.7, None), (0.8, 0.9), (0.0, None)],
    "nucleus_lane_is_the_done_one": [
        (0.0, None), (1.1, None), (0.0, None), (0.8, 0.6)],
    "all_nucleus": [(0.8, 0.9), (1.0, 0.5), (0.6, 0.95), (1.2, 0.7)],
}


def _prompt(i):
    rs = np.random.RandomState(100 + i)
    return rs.randint(0, VOCAB, (3 + 2 * i,))


def _filled_pool(kind, model, params, mix):
    lanes = len(mix) + 1            # + one free lane
    if kind == "slot":
        pool = SlotPool(model, params, lanes, eos_id=EOS)
    else:
        pool = PagedSlotPool(model, params, lanes, block_size=BS,
                             eos_id=EOS)
    first = []
    for i, (temp, top_p) in enumerate(mix):
        if kind == "slot":
            slot = pool.alloc()
        else:
            slot = pool.admit(_prompt(i), 8).slot
        first.append(pool.prefill(slot, _prompt(i), temp, top_p,
                                  seed=11 + i))
    pool._done = pool._done.at[len(mix) - 1].set(True)
    return pool, first


@pytest.mark.parametrize("kind", ["slot", "paged"])
@pytest.mark.parametrize("mix", list(MIXES))
def test_tick_tokens_and_keys_bitwise_the_per_lane_rule(
        lm, oracle_ticks, monkeypatch, kind, mix):
    model, params = lm
    mod, name, oracle = oracle_ticks[kind]
    got, first_a = _filled_pool(kind, model, params, MIXES[mix])
    want, first_b = _filled_pool(kind, model, params, MIXES[mix])
    assert first_a == first_b
    done_lane = len(MIXES[mix]) - 1
    for _ in range(4):
        toks = got.tick()
        with monkeypatch.context() as m:
            m.setattr(mod, name, oracle)
            ref = want.tick()
        np.testing.assert_array_equal(toks, ref)
        assert toks[done_lane] == EOS
        for attr in ("_rngs", "_done", "_toks"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, attr)),
                np.asarray(getattr(want, attr)))


@pytest.mark.parametrize("temp,top_p", [(0.0, 1.0), (0.0, 0.5),
                                        (0.8, 1.0), (0.8, 0.9)])
@pytest.mark.parametrize("skips", [0, 3])
def test_first_token_bitwise_the_per_lane_rule(hvd, temp, top_p,
                                               skips):
    """The program that closes a prefill: one row, scalar parameters,
    the same three-way choice."""
    logits = jax.random.normal(jax.random.PRNGKey(7), (VOCAB,),
                               jnp.float32) * 3.0
    key = jax.random.PRNGKey(5)
    tok, rng = slots_mod._first_token(
        logits, jnp.float32(temp), jnp.float32(top_p), key,
        jnp.int32(skips))
    for _ in range(skips):
        key = jax.random.split(key)[0]
    want_rng, r0 = jax.random.split(key)
    want = _sample_token_as_it_was(logits, jnp.float32(temp),
                                   jnp.float32(top_p), r0)
    assert int(tok) == int(want)
    np.testing.assert_array_equal(np.asarray(rng),
                                  np.asarray(want_rng))


# -- structure ---------------------------------------------------------

HEAVY = ("sort", "cumsum")


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(j, "jaxpr"):          # ClosedJaxpr
                j = j.jaxpr
            if hasattr(j, "eqns"):
                yield j


def _find(jaxpr, names, conds=()):
    """(primitive name, the `cond` equations above it) for every
    equation of ``names`` in ``jaxpr``, however deep."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            yield eqn.primitive.name, conds
        above = conds + ((eqn,) if eqn.primitive.name == "cond" else ())
        for sub in _sub_jaxprs(eqn):
            yield from _find(sub, names, above)


def _tick_jaxpr(kind, model, params):
    mix = MIXES["greedy_temperature_and_top_p"]
    pool, _ = _filled_pool(kind, model, params, mix)
    if kind == "slot":
        return jax.make_jaxpr(
            tr.slot_decode_tick.__wrapped__, static_argnums=(0,))(
            pool.dec_model, pool.params, pool._cache, pool._toks,
            pool._temps, pool._top_ps, pool._rngs, pool._live,
            pool._done, pool._eos).jaxpr
    return jax.make_jaxpr(
        tr.paged_decode_tick.__wrapped__, static_argnums=(0, 1))(
        pool.dec_model, pool.spec, pool._pools, pool.params,
        pool._tables, pool._fills, pool._toks, pool._temps,
        pool._top_ps, pool._rngs, pool._live, pool._done,
        pool._eos).jaxpr


@pytest.mark.parametrize("kind", ["slot", "paged"])
def test_sort_and_cumsum_sit_under_a_scalar_switch(lm, kind):
    model, params = lm
    found = list(_find(_tick_jaxpr(kind, model, params), HEAVY))
    assert {n for n, _ in found} == set(HEAVY), found
    switches = set()
    for name, conds in found:
        assert conds, f"{name} runs for every batch (no cond above it)"
        for eqn in conds:
            assert eqn.invars[0].aval.shape == (), (
                f"{name} under a cond whose predicate is batched")
        switches.add(id(conds[0]))
    assert len(switches) == 1
    switch = found[0][1][0]
    greedy, plain, nucleus = (b.jaxpr for b in switch.params["branches"])
    # the greedy path: no sort, no cumsum, no divide, no noise
    assert not list(_find(greedy, HEAVY + ("div", "random_bits",
                                           "threefry2x32", "log")))
    assert any(e.primitive.name == "argmax" for e in greedy.eqns)
    # the sampled path draws, and does not sort
    assert not list(_find(plain, HEAVY))
    assert list(_find(plain, ("div",)))
    assert {n for n, _ in _find(nucleus, HEAVY)} == set(HEAVY)


def test_a_batched_predicate_would_fail_the_structure_test(lm):
    """The detector discriminates: the per-lane rule under the lanes'
    vmap (the parent's program) has its sort above every cond."""
    model, params = lm
    pool, _ = _filled_pool("slot", model, params, MIXES["all_greedy"])
    old = _with_the_rule_as_it_was(
        tr.slot_decode_tick, static_argnames=("dec_model",))
    jaxpr = jax.make_jaxpr(old.__wrapped__, static_argnums=(0,))(
        pool.dec_model, pool.params, pool._cache, pool._toks,
        pool._temps, pool._top_ps, pool._rngs, pool._live, pool._done,
        pool._eos).jaxpr
    found = list(_find(jaxpr, HEAVY))
    assert found and all(not conds for _, conds in found)


# -- the engine --------------------------------------------------------

def _wait(cond, timeout=120.0, dt=0.005):
    t0 = time.time()
    while not cond():
        if time.time() - t0 > timeout:
            raise AssertionError("condition not reached in time")
        time.sleep(dt)


@pytest.mark.parametrize("paged", [False, True])
def test_nucleus_request_joins_greedy_lanes_mid_stream(lm, paged):
    """One compiled program serves every mix: a sampled request that
    joins lanes of greedy requests compiles nothing, leaves their
    streams `generate`'s, and draws what it draws served alone; the
    tick records and the snapshot say which path each tick took."""
    model, params = lm
    kw = dict(paged=True, kv_block_size=BS) if paged else {}
    sampled = dict(temperature=0.8, top_p=0.9, seed=5)
    prompts = [_prompt(i) for i in range(3)]
    steps = 14
    with ServingEngine(model, params, num_slots=3, warmup=True,
                       **kw) as alone:
        ref = list(alone.submit(prompts[2], 6, **sampled)
                   .result(timeout=300).tokens)
        snap = alone.metrics_snapshot()
        assert snap["ticks_nucleus"] == snap["ticks"] > 0
        assert snap["ticks_greedy"] == snap["ticks_sampled"] == 0

    with ServingEngine(model, params, num_slots=3, warmup=True,
                       **kw) as eng:
        warm = eng.pool.compiles
        greedy = [eng.submit(p, steps) for p in prompts[:2]]
        _wait(lambda: all(len(h.tokens_so_far()) >= 3 for h in greedy))
        joined = eng.submit(prompts[2], 6, **sampled)
        out = list(joined.result(timeout=300).tokens)
        streams = [list(h.result(timeout=300).tokens) for h in greedy]
        # a sampled request with no nucleus, after the others: path 1
        tail = eng.submit(prompts[0], 4, temperature=0.7, seed=2)
        tail.result(timeout=300)
        assert eng.pool.compiles == warm
        snap = eng.metrics_snapshot()
    assert out == ref
    for p, s in zip(prompts, streams):
        want = np.asarray(generate(model, params, p[None], steps))[0]
        assert s == list(want[len(p):])
    assert snap["compiles"] == 0
    assert snap["ticks_greedy"] > 0
    assert snap["ticks_nucleus"] > 0
    assert snap["ticks_sampled"] > 0
    assert (snap["ticks_greedy"] + snap["ticks_sampled"]
            + snap["ticks_nucleus"]) == snap["ticks"]
