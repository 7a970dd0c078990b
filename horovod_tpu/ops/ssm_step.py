"""The S = 1 step of a scalar-decay state-space state
(`parallel.state_space`), in place: one Pallas TPU call over every lane
of the slot pool - `ops.kda_step`'s sibling.

A sibling and not a second rule of that kernel's call: the delta rule
needs a read-out of the decayed tile complete before its update can
start and scales a head's tile by four vectors down its rows; here a
group's tile [N, Q] takes one multiply-add - `decay` and `dt x` are
rows over the Q channels, B a column over the N states - and one
read-out, the sum down the rows against the column C. What the two
share they share by import: the plan's record (`StateStepPlan`), the
bytes a grid step may hold, the switch that tells a rule it is on a
TPU, and the custom_vmap entry that folds the tick's slot axis into the
lanes. As there, the state output aliases the state operand, a lane
that must not advance gets its tile stored as it was read, and nobody
may read the old state after the call - at 64 lanes of 36 layers the
state is 4.8 GB, and a second copy of it does not fit beside the
weights.

`ssm_step_plan` is THE rule: the kernel on a TPU with no serving mesh,
for a float32 state whose N is whole sublane tiles and whose Q is whole
lanes of 128, at one position a step; `state_space.step_rows` as XLA
compiles it - the oracle - everywhere else. `ssm_state_step` is the
kernel's entry.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import flash_attention as _flash
from horovod_tpu.ops import kda_step as _kda
from horovod_tpu.ops.kda_step import StateStepPlan

LANES = 128     # channels a tile of the kernel's inner loop holds
COLS = 8        # rows of the [COLS, N] operand that holds B and C


def _channels_a_step(N: int, Q: int) -> Optional[int]:
    """Channels of a group that one grid step holds: the most whole
    lanes that divide Q and keep the tile inside `kda_step.BLOCK_BYTES`;
    None where no count does."""
    fits = [c for c in range(LANES, Q + 1, LANES)
            if Q % c == 0 and N * c * 4 <= _kda.BLOCK_BYTES]
    return max(fits, default=None)


def _vmem(N: int, cb: int) -> int:
    """VMEM the call's plan sums to: the state's block in and out and
    the rows' blocks, each double buffered, the columns, a few tiles of
    temporaries, and 2 MiB for what Mosaic keeps."""
    return (4 * N * cb * 4 + 6 * 8 * cb * 4 + 4 * COLS * N * 4
            + 8 * N * LANES * 4 + 2 * 2 ** 20)


def ssm_step_plan(lanes: int, G: int, N: int, Q: int, *,
                  positions: int = 1, trivial_mesh: bool = True,
                  on_tpu: Optional[bool] = None) -> StateStepPlan:
    """THE rule for a state-space layer's state step over ``lanes``
    lanes of ``G`` groups with float32 [N, Q] states, at ``positions``
    positions a step. ``on_tpu`` True takes the rule as on the chip
    (the kernel then runs in interpret mode off it)."""
    if positions != 1:
        return StateStepPlan(
            "lax", f"{positions} positions a step: the chunkwise form")
    if N % 8 or Q % LANES:
        return StateStepPlan(
            "lax", f"a [{N}, {Q}] state is not whole sublane tiles of "
            f"whole lanes of {LANES}")
    if on_tpu is None:
        on_tpu = _kda._on_tpu()
    if not on_tpu:
        return StateStepPlan("lax", "not on a TPU")
    if not trivial_mesh:
        return StateStepPlan(
            "lax", "a serving mesh: XLA partitions its own step, "
            "a bare kernel does not")
    cb = _channels_a_step(N, Q)
    if cb is None:
        return StateStepPlan(
            "lax", f"no block of whole lanes of [{N}, {Q}] fits "
            f"{_kda.BLOCK_BYTES >> 20} MiB")
    return StateStepPlan(
        "kernel", "on a TPU", block=cb, grid=(lanes, G, Q // cb),
        vmem_bytes=_vmem(N, cb))


def _step_kernel(adv_ref, s_ref, r_ref, c_ref, o_ref, so_ref, *, cb: int):
    """One (lane, group, channel block) grid cell: `step_rows` on the
    block's [N, cb] tile, `LANES` channels at a time. ``r_ref`` holds
    the block's decay and dt x as rows; ``c_ref`` the group's B and C
    as rows, and a tile wants them down its rows, so they are turned
    once a cell."""
    lane = pl.program_id(0)

    @pl.when(adv_ref[lane] != 0)
    def _advance():
        cols = c_ref[0, 0].T                    # [N, COLS]
        b, c = cols[:, 0:1], cols[:, 1:2]
        for j in range(0, cb, LANES):
            at = slice(j, j + LANES)
            s = (s_ref[0, 0, :, at] * r_ref[0, 0, 0:1, at]
                 + b * r_ref[0, 0, 1:2, at])
            o_ref[0, 0, 0:1, at] = jnp.sum(s * c, axis=0, keepdims=True)
            so_ref[0, 0, :, at] = s

    @pl.when(adv_ref[lane] == 0)
    def _keep():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("cb", "interpret"))
def _ssm_call(state, decay, dtx, B, C, advance, *, cb, interpret):
    """The pallas_call: state [L, G, N, Q]; decay, dtx [L, G, Q]; B, C
    [L, G, N]; advance [L] -> (y [L, G, Q], the state, aliased to its
    input)."""
    L, G, N, Q = state.shape
    rows = jnp.stack([decay, dtx], axis=2)              # [L, G, 2, Q]
    cols = jnp.pad(jnp.stack([B, C], axis=2),
                   ((0, 0), (0, 0), (0, COLS - 2), (0, 0)))

    def block(lane, g, j, adv):
        return lane, g, 0, j

    def group(lane, g, j, adv):
        return lane, g, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L, G, Q // cb),
        in_specs=[pl.BlockSpec((1, 1, N, cb), block),
                  pl.BlockSpec((1, 1, 2, cb), block),
                  pl.BlockSpec((1, 1, COLS, N), group)],
        out_specs=[pl.BlockSpec((1, 1, 1, cb), block),
                   pl.BlockSpec((1, 1, N, cb), block)])
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, cb=cb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((L, G, 1, Q), state.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 0 is the scalar-prefetched `advance`
        input_output_aliases={1: 1},
        compiler_params=None if interpret else _flash._compiler_params(
            _vmem(N, cb), ("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=5 * L * G * N * Q, transcendentals=0,
            bytes_accessed=4 * (2 * L * G * N * Q
                                + L * G * (3 * Q + 2 * N))),
        interpret=interpret,
        name="ssm_step",
    )(advance.astype(jnp.int32), state, rows, cols)
    return y[:, :, 0], state


@functools.lru_cache(maxsize=None)
def _make_step(cb: int, interpret: bool):
    """`kda_step.over_slots` of the call: the tick's slot axis joins
    the lanes, and the pool's state leaf [num_slots, 1, G, N, Q] is
    stepped where it lies, by one call."""
    return _kda.over_slots(functools.partial(_ssm_call, cb=cb,
                                             interpret=interpret))


def ssm_state_step(state: jax.Array, decay: jax.Array, dtx: jax.Array,
                   B: jax.Array, C: jax.Array,
                   advance: Optional[jax.Array] = None, *,
                   plan: StateStepPlan):
    """`parallel.state_space.step_rows` over lanes through the kernel
    of ``plan`` (`ssm_step_plan`; a plan that says "lax" is the
    caller's to obey with `step_rows` itself), with the lanes' freeze:
    state [B, G, N, Q] float32; decay, dtx [B, G, Q]; B, C [B, G, N];
    ``advance`` bool, a scalar or [B] (None: every lane advances).
    Returns ``(y [B, G, Q], state)``.

    A lane that does not advance keeps its state bitwise, and its ``y``
    is zeros: nobody reads it. The returned state is aliased to its
    input: a donated state is stepped where it lies - ONE read and one
    write of it - and whoever reads the old state after the call makes
    XLA copy it first. `jax.vmap` over a leading slot axis - the
    serving tick - folds that axis into the lanes of the same one
    call."""
    if plan.path != "kernel":
        raise ValueError(
            f"ssm_state_step: the plan says {plan.describe()}")
    advance = jnp.broadcast_to(
        jnp.asarray(True if advance is None else advance, jnp.bool_),
        (state.shape[0],))
    return _make_step(plan.block, _flash._auto_interpret())(
        state, decay, dtx, B, C, advance)
