"""The delta-rule state's S = 1 step as one in-place Pallas call
(`ops.kda_step`) on the CPU in interpret mode: against `kda_step` and
`kda_recurrent` (`parallel.linear_attention`, untouched: the oracle),
the freeze of a lane that does not advance, the `custom_vmap` entry
the serving tick reaches it through, and what `kda_step_plan` says of
every case. What Mosaic says of the same call at solar's shape is in
`tests/test_tpu_compile.py`; what the chip says, in PERF.md.

Tolerance: the kernel multiplies and adds the same float32 numbers in
`kda_step`'s order of stages; only the two sums over Dk = 128 may run
in another order than XLA's, so a result differs by at most a few of
128 x 2^-24 = 7.6e-6 times its largest term (terms here are under 1
for `u` and `o`, and an update moves a state entry by beta k u, under
one). 1e-5 absolute + 1e-5 relative holds that with room; a wrong
stage order, a missed decay or a bf16 pass is off by 1e-2 and more.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops.kda_step import (
    BLOCK_BYTES, kda_state_step, kda_step_plan,
)
from horovod_tpu.parallel.linear_attention import kda_recurrent, kda_step

D = 128
TOL = dict(rtol=1e-5, atol=1e-5)


def inputs(L, H, seed, *, decay=1.0, beta_shift=0.0, T=None):
    """A state and one step's operands (or ``T`` steps', time on axis
    1) as `KDAAttention` makes them: q, k L2-normalised, g <= 0, beta
    in (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    t = () if T is None else (T,)
    q, k, v = (jax.random.normal(ks[i], (L, *t, H, D), jnp.float32)
               for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -decay * jax.nn.softplus(
        jax.random.normal(ks[3], (L, *t, H, D), jnp.float32))
    beta = 2 * jax.nn.sigmoid(
        jax.random.normal(ks[4], (L, *t, H), jnp.float32) + beta_shift)
    state = jax.random.normal(ks[5], (L, H, D, D), jnp.float32)
    return state, q, k, v, g, beta


def forced(L, H, heads=None):
    plan = kda_step_plan(L, H, D, D, impl="pallas")
    assert plan.path == "kernel"
    return plan if heads is None else dataclasses.replace(
        plan, block=heads, grid=(L, H // heads))


CASES = {
    "plain": dict(),
    # exp(g) down to e^-30: a state nearly forgotten each step
    "strong-decay": dict(decay=30.0),
    # exp(g) within 1e-3 of one: nothing forgotten
    "weak-decay": dict(decay=1e-3),
    "beta-near-0": dict(beta_shift=-12.0),
    "beta-near-2": dict(beta_shift=12.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_equals_kda_step_and_keeps_a_frozen_lane_bitwise(case):
    """Two head blocks a lane (16 heads in blocks of 8), three lanes,
    the middle one frozen."""
    L, H = 3, 16
    state, *step = inputs(L, H, 3, **CASES[case])
    advance = jnp.asarray([True, False, True])
    o, new = kda_state_step(state, *step, advance, plan=forced(L, H, 8))
    want_o, want = kda_step(state, *step)
    moves = np.asarray(advance)
    np.testing.assert_allclose(o[moves], want_o[moves], **TOL)
    np.testing.assert_allclose(new[moves], want[moves], **TOL)
    assert new.dtype == jnp.float32 and o.dtype == jnp.float32
    # the frozen lane: its tiles as they were read, bit for bit
    np.testing.assert_array_equal(new[1], state[1])
    np.testing.assert_array_equal(o[1], 0)
    if case != "beta-near-0":       # and the others did move
        assert float(jnp.abs(new[0] - state[0]).max()) > 1e-3


def test_no_flag_means_every_lane_advances():
    state, *step = inputs(2, 8, 5)
    o, new = kda_state_step(state, *step, plan=forced(2, 8))
    want_o, want = kda_step(state, *step)
    np.testing.assert_allclose(o, want_o, **TOL)
    np.testing.assert_allclose(new, want, **TOL)


def test_ticks_in_a_row_equal_the_recurrence():
    """Eight steps chained through the kernel, lane 1 frozen at steps
    2 and 5 (its inputs of those steps never happened), against
    `kda_recurrent` over each lane's own steps."""
    L, H, T = 2, 8, 8
    state, q, k, v, g, beta = inputs(L, H, 7, T=T)
    frozen = {2, 5}
    plan = forced(L, H)
    step = jax.jit(lambda s, *xs: kda_state_step(s, *xs, plan=plan))
    s, outs = state, []
    for t in range(T):
        advance = jnp.asarray([True, t not in frozen])
        o, s = step(s, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t],
                    advance)
        outs.append(o)
    outs = jnp.stack(outs, 1)
    want_o, want = kda_recurrent(state[:1], q[:1], k[:1], v[:1], g[:1],
                                 beta[:1])
    np.testing.assert_allclose(outs[:1], want_o, **TOL)
    np.testing.assert_allclose(s[:1], want, **TOL)
    kept = [t for t in range(T) if t not in frozen]
    want_o, want = kda_recurrent(
        state[1:], *(a[1:, kept] for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(outs[1:, kept], want_o, **TOL)
    np.testing.assert_allclose(s[1:], want, **TOL)


def test_a_vmap_of_b1_applies_is_one_call_over_all_lanes():
    """The serving tick's shape: `jax.vmap` over slots of a B = 1
    step. The slot axis joins the lanes of ONE `pallas_call` (no
    `while` over the lanes), and the result is the lax path's -
    `kda_step`, then the freeze's select."""
    L, H = 4, 8
    state, *step = inputs(L, H, 9)
    advance = jnp.asarray([True, False, True, True])
    plan = forced(1, H)

    def kernel_lane(s, q, k, v, g, beta, adv):
        o, s = kda_state_step(s[None], q[None], k[None], v[None],
                              g[None], beta[None], adv, plan=plan)
        return o[0], s[0]

    def lax_lane(s, q, k, v, g, beta, adv):
        o, new = kda_step(s, q, k, v, g, beta)
        return o, jnp.where(adv, new, s)

    text = str(jax.make_jaxpr(jax.vmap(kernel_lane))(
        state, *step, advance))
    assert text.count("pallas_call") == 1 and "while" not in text
    assert "name=kda_step" in text
    o, new = jax.vmap(kernel_lane)(state, *step, advance)
    want_o, want = jax.vmap(lax_lane)(state, *step, advance)
    moves = np.asarray(advance)
    np.testing.assert_allclose(o[moves], want_o[moves], **TOL)
    np.testing.assert_allclose(new, want, **TOL)
    np.testing.assert_array_equal(new[1], state[1])
    # a flag shared by the slots (not batched) is broadcast
    o_all, _ = jax.vmap(kernel_lane, in_axes=(0,) * 6 + (None,))(
        state, *step, jnp.asarray(True))
    np.testing.assert_allclose(o_all, jax.vmap(lax_lane, in_axes=(
        0,) * 6 + (None,))(state, *step, jnp.asarray(True))[0], **TOL)


def test_the_state_is_stepped_in_place():
    """The call's state output aliases its state operand, and a jitted
    step that donates the state needs no second buffer for it."""
    L, H = 2, 8
    state, *step = inputs(L, H, 11)
    plan = forced(L, H)
    jaxpr = str(jax.make_jaxpr(
        lambda s, *xs: kda_state_step(s, *xs, plan=plan))(state, *step))
    assert "input_output_aliases=((1, 1),)" in jaxpr
    lowered = jax.jit(
        lambda s, *xs: kda_state_step(s, *xs, plan=plan)[1],
        donate_argnums=0).lower(state, *step)
    assert "tf.aliasing_output" in lowered.as_text()


SOLAR = (128, 64, 128, 128)     # the cell's lanes, heads, Dk, Dv
PLAN_CASES = {
    # case: (arguments, keywords) -> (path, a word of the reason)
    "the-cpu": (SOLAR, dict(), "lax", "not on a TPU"),
    "a-tpu": (SOLAR, dict(on_tpu=True), "kernel", "on a TPU"),
    "a-mesh": (SOLAR, dict(on_tpu=True, trivial_mesh=False), "lax",
               "serving mesh"),
    "bf16-state": (SOLAR, dict(on_tpu=True, dtype=jnp.bfloat16), "lax",
                   "bfloat16 state"),
    "dk-not-a-whole-lane": ((128, 64, 64, 128), dict(on_tpu=True),
                            "lax", "not whole lanes"),
    "dv-not-a-whole-lane": ((128, 64, 128, 192), dict(on_tpu=True),
                            "lax", "not whole lanes"),
    "a-chunk": (SOLAR, dict(on_tpu=True, positions=128), "lax",
                "128 positions"),
    "forced-lax": (SOLAR, dict(on_tpu=True, impl="lax"), "lax",
                   "forced"),
    "forced-kernel-off-the-chip": (SOLAR, dict(impl="pallas"),
                                   "kernel", "forced"),
    "forced-kernel-still-needs-f32": (
        SOLAR, dict(impl="pallas", dtype=jnp.bfloat16), "lax",
        "bfloat16 state"),
    "no-block-of-heads": ((8, 36, 256, 256), dict(on_tpu=True), "lax",
                          "36 heads"),
}


@pytest.mark.parametrize("case", sorted(PLAN_CASES))
def test_plan(case):
    args, kw, path, why = PLAN_CASES[case]
    plan = kda_step_plan(*args, **kw)
    assert plan.path == path, plan
    assert why in plan.describe()
    assert plan.describe().startswith(path)
    if path == "lax":
        assert plan.block is None and plan.grid is None
        return
    lanes, H, Dk, Dv = args
    # heads a block from the shape: whole sublane tiles inside the
    # budget, the grid and the VMEM asked in the words the log prints
    assert plan.block == 32 and plan.grid == (lanes, 2)
    assert plan.block * Dk * Dv * 4 <= BLOCK_BYTES
    assert 4 * plan.block * Dk * Dv * 4 < plan.vmem_bytes < 16 * 2 ** 20
    assert "a block of 32 a step" in plan.describe()
    assert "grid (128, 2)" in plan.describe()


@pytest.mark.parametrize("H,heads", [(2, 2), (8, 8), (12, 12), (40, 8),
                                     (64, 32), (96, 32)])
def test_heads_a_step_come_from_the_shape(H, heads):
    plan = kda_step_plan(4, H, D, D, on_tpu=True)
    assert (plan.path, plan.block) == ("kernel", heads)
    assert H % heads == 0 and (heads % 8 == 0 or heads == H)


def test_plan_refuses_an_unknown_impl_and_the_entry_a_lax_plan():
    with pytest.raises(ValueError, match="impl must be"):
        kda_step_plan(*SOLAR, impl="mosaic")
    state, *step = inputs(1, 2, 0)
    with pytest.raises(ValueError, match="the plan says lax"):
        kda_state_step(state, *step, plan=kda_step_plan(1, 2, D, D))
