"""The group-limited choice of `HeldExpertsMoE` (`groups`, the
DeepSeek-V3 family's gate as A.X-K1 publishes it): pure functions and
one layer at a time, no engine.

The choice against a NumPy loop over tokens; `(1, 1)` and `None` the
unlimited choice bit for bit; THE SHARE TEST - the parts that all 16
shares give (12 experts each), the shared expert counted once, add up to
the uncut layer's output, in the program and in the benchmark's plain
reference alike; `moe_token_chips` against a Python count; `jax.grad`
passes.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.parallel.expert import (
    HeldExpertsMoE, group_limited, token_chips)
from horovod_tpu.parallel.tensor import unbox

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.harness.cells import load_module  # noqa: E402

A = load_module(os.path.join(REPO, "benchmarks", "arch", "axk1.py"),
                "arch_axk1_for_group_tests")

# A.X-K1's gate at its published counts, the widths a toy's
GATE = dict(num_experts=192, k=8, groups=(8, 4), hidden=16,
            shared_hidden=16, router="sigmoid", router_bias=False,
            scale=2.5, dtype=jnp.float32)
D = 32


def numpy_choice(pick, n_group, topk_group, k):
    """The issue's equations, a token at a time: [T, k] ids, each
    token's in the order `lax.top_k` gives (largest first, ties to the
    lower id)."""
    out = []
    for row in np.asarray(pick, np.float64):
        groups = row.reshape(n_group, -1)
        score = np.sort(groups, axis=-1)[:, -2:].sum(-1)
        kept = sorted(range(n_group), key=lambda g: (-score[g], g))[
            :topk_group]
        masked = np.full_like(groups, -np.inf)
        masked[kept] = groups[kept]
        flat = masked.reshape(-1)
        out.append(sorted(range(flat.size),
                          key=lambda e: (-flat[e], e))[:k])
    return np.asarray(out)


@pytest.mark.parametrize("outputs,groups,k,seed", [
    (192, (8, 4), 8, 0), (192, (8, 4), 8, 1), (24, (4, 2), 4, 2),
    (24, (4, 1), 4, 3), (16, (2, 2), 6, 4), (192, (8, 8), 8, 5)])
def test_the_choice_is_the_numpy_loop_s(outputs, groups, k, seed):
    rng = np.random.default_rng(seed)
    pick = jax.nn.sigmoid(jnp.asarray(
        rng.normal(size=(37, outputs)), jnp.float32))
    _, chosen = jax.lax.top_k(group_limited(pick, *groups), k)
    want = numpy_choice(pick, *groups, k)
    np.testing.assert_array_equal(np.asarray(chosen), want)
    # every chosen id lies in one of at most topk_group groups
    per = outputs // groups[0]
    assert max(len(set(row // per)) for row in want) <= groups[1]


def test_ties_go_to_the_lower_index_as_top_k_breaks_them():
    """Equal scores everywhere: groups 0 and 1 are kept, and their
    first ids chosen."""
    pick = jnp.full((2, 24), 0.5, jnp.float32)
    _, chosen = jax.lax.top_k(group_limited(pick, 4, 2), 4)
    np.testing.assert_array_equal(np.asarray(chosen),
                                  [[0, 1, 2, 3]] * 2)


def _layer(**kw):
    return HeldExpertsMoE(**{**GATE, **kw})


def _init(layer, x, seed=0):
    return unbox(layer.init(jax.random.PRNGKey(seed), x)["params"])


def _x(tokens=23, seed=3):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(tokens, D)), jnp.float32)


def _chosen(layer, params, x):
    _, mut = layer.apply({"params": params}, x,
                         mutable=["intermediates"])
    return np.asarray(mut["intermediates"]["chosen"])


@pytest.mark.parametrize("bias", [False, True])
def test_the_layer_chooses_by_the_group_rule_with_and_without_a_bias(
        bias):
    """One formula: a group's score is the sum of its two largest
    `scores + bias` where the gate has a bias, of `scores` where not;
    the weights are the chosen SCORES either way."""
    x = _x()
    layer = _layer(router_bias=bias)
    params = _init(layer, x)
    if bias:
        params = dict(params, router_bias=jnp.asarray(
            np.random.default_rng(9).normal(size=192) * 0.3, jnp.float32))
    scores = jax.nn.sigmoid(jnp.matmul(
        x, params["router"], precision=jax.lax.Precision.HIGHEST))
    pick = scores + params["router_bias"] if bias else scores
    np.testing.assert_array_equal(
        _chosen(layer, params, x), numpy_choice(pick, 8, 4, 8))


@pytest.mark.parametrize("held", [None, (12, 12)])
def test_one_group_of_one_and_none_are_the_old_choice_bit_for_bit(held):
    x = _x()
    old = _layer(groups=None, held=held)
    one = _layer(groups=(1, 1), held=held)
    limited = _layer(held=held)
    params = _init(old, x)
    y_old = old.apply({"params": params}, x)
    np.testing.assert_array_equal(np.asarray(y_old), np.asarray(
        one.apply({"params": params}, x)))
    np.testing.assert_array_equal(_chosen(old, params, x),
                                  _chosen(one, params, x))
    # ... and the limit is no no-op on this input
    assert (_chosen(limited, params, x)
            != _chosen(old, params, x)).any()
    # the same program: the unlimited choice's jaxpr knows no group
    text = str(jax.make_jaxpr(
        lambda p, x: old.apply({"params": p}, x))(params, x))
    assert text == str(jax.make_jaxpr(
        lambda p, x: one.apply({"params": p}, x))(params, x))


def _share(params, first, count):
    return dict(params, **{k: params[k][first:first + count]
                           for k in ("w_gate", "w_up", "w_down")})


def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """THE SHARE TEST (model-configs guide §4): 16 chips hold 12 of the
    192 experts each; every one scores all 192 and chooses over all 8
    groups; what each adds for its own experts, with the shared expert
    counted once, sums to the layer that holds every expert - in the
    program; the plain reference gives the same whole and, share for
    share (three of them read), the same parts."""
    x = _x()
    whole = _layer()
    params = _init(whole, x)
    want = np.asarray(whole.apply({"params": params}, x))
    no_shared = dict(GATE, shared_hidden=0)
    parts = [np.asarray(HeldExpertsMoE(**no_shared, held=(f, 12)).apply(
        {"params": {k: v for k, v in _share(params, f, 12).items()
                    if k != "shared"}}, x)) for f in range(0, 192, 12)]
    shared = np.asarray(_layer(held=(0, 12)).apply(
        {"params": _share(params, 0, 12)}, x)) - parts[0]
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-5,
                               atol=2e-6)
    # a share is a strict part: no single chip gives the whole
    assert np.abs(parts[0] + shared - want).max() > 1e-4
    # the reference, given the same shares
    arch = dict(num_experts=192, experts_held=[0, 192], n_group=8,
                topk_group=4, experts_per_token=8, norm_topk=True,
                routed_scale=2.5)
    ref_whole = np.asarray(A.moe(arch, params, x))
    ref_parts = [np.asarray(A.moe(arch, _share(params, f, 12), x,
                                  held=(f, 12), shared=f == 0))
                 for f in (0, 84, 180)]
    np.testing.assert_allclose(ref_parts[0] - parts[0] - shared, 0,
                               atol=2e-6)
    np.testing.assert_allclose(ref_parts[1:], [parts[7], parts[15]],
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(want, ref_whole, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(
        np.sort(_chosen(whole, params, x), -1),
        np.sort(np.asarray(A.route(arch, params, x)[0]), -1))


def test_moe_token_chips_is_the_python_count():
    x = _x()
    layer = _layer(held=(24, 12))
    params = _share(_init(_layer(), x), 24, 12)
    _, mut = layer.apply({"params": params}, x,
                         mutable=["moe_stats", "intermediates"])
    chosen = np.asarray(mut["intermediates"]["chosen"])
    want = sum(len({int(e) // 12 for e in row}) for row in chosen)
    assert int(mut["moe_stats"]["token_chips"]) == want
    assert int(token_chips(jnp.asarray(chosen), 12)) == want
    # 4 groups of 24 = 8 chips of 12 at most; more than one a token
    assert 23 < want <= 23 * 8
    # the limit bounds the fan-out: without it the same tokens reach
    # more chips
    free = _layer(groups=(1, 1), held=(24, 12))
    _, mut = free.apply({"params": params}, x, mutable=["moe_stats"])
    assert int(mut["moe_stats"]["token_chips"]) > want
    # counted for a share under a group rule alone: absent, not zero
    for other in (_layer(), _layer(groups=None, held=(24, 12))):
        p = params if other.held else _init(_layer(), x)
        _, mut = other.apply({"params": p}, x, mutable=["moe_stats"])
        assert set(mut["moe_stats"]) == {"pairs"}


def test_grad_passes_through_the_limited_choice():
    x = _x()
    layer = _layer(held=(0, 12))
    params = _share(_init(_layer(), x), 0, 12)

    def loss(p, x):
        return jnp.sum(layer.apply({"params": p}, x) ** 2)

    gp, gx = jax.grad(loss, argnums=(0, 1))(params, x)
    leaves = jax.tree.leaves(gp) + [gx]
    assert all(np.isfinite(np.asarray(g)).all() for g in leaves)
    assert float(jnp.abs(gx).max()) > 0
    assert float(jnp.abs(gp["router"]).max()) > 0       # through the weights
    assert float(jnp.abs(gp["w_down"]).max()) > 0


@pytest.mark.parametrize("kw,says", [
    (dict(zero_experts=8, router_bias=True), "zero_experts"),
    (dict(groups=(7, 4)), "does not divide"),
    (dict(groups=(8, 9)), "does not divide"),
    (dict(groups=(96, 3)), "does not divide"),     # 6 ids cannot give 8
])
def test_what_the_rule_refuses(kw, says):
    layer = _layer(**kw)
    with pytest.raises(ValueError, match=says):
        layer.init(jax.random.PRNGKey(0), _x())
