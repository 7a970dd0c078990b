"""The S = 1 step of a delta-rule state (`parallel.linear_attention`),
in place: one Pallas TPU call over every lane of the slot pool.

`kda_step` decays a head's [Dk, Dv] float32 state, reads it against k,
adds a rank-one update and reads it against q. `u` must be complete
over Dk before the update can start, XLA's fusions hold no tile across
a reduction, and the tick's freeze (`where(advance, new, old)`) wants
the old state readable until the new one is whole: XLA sweeps the
state twice and selects it once. A head's tile is 64 KB and everything
the step does to it is local to the tile, so here a grid step holds a
lane's block of heads in VMEM, does the four stages of `kda_step` in
their order - float32 throughout - and stores the tile where it came
from (the state output aliases the state operand). A lane that must
not advance gets its tiles stored as they were read: the freeze is
inside, and nobody may read the old state after the call.

`kda_step_plan` is THE rule (the twin of
`flash_attention.decode_attention_plan` and
`grouped_matmul.grouped_product_plan`): the kernel on a TPU with no
serving mesh, for a float32 state whose Dk and Dv are whole lanes of
128, at one position a step; `kda_step` itself - the oracle -
everywhere else. `kda_state_step` is the kernel's entry.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import flash_attention as _flash

# Bytes of state a grid step holds (in and out, each double buffered
# by the pipeline, come to four times this).
BLOCK_BYTES = 2 * 2 ** 20
ROWS = 4        # the vectors a head's tile is scaled by: decay, k, beta k, q


def _on_tpu() -> bool:
    """What the rule takes for "on a TPU" (apart from whether a call
    compiles or interprets, so that a test can drive the rule's kernel
    path in interpret mode)."""
    return not _flash._auto_interpret()


@dataclasses.dataclass(frozen=True)
class StateStepPlan:
    """Which way a recurrent layer's S = 1 state step goes at one
    shape, and why: the trace-time record of `kda_step_plan` - the
    rule `KDAAttention` and the slot tick's freeze obey, what the
    engine logs at warm-up and carries in ``metrics_snapshot()``."""
    path: str                       # "kernel" | "lax"
    why: str
    block: Optional[int] = None     # kernel path only: what a grid step
                                    # holds, in the rule's own unit (heads
                                    # here, channels in `ops.ssm_step`)
    grid: Optional[tuple] = None    # (lanes, blocks a lane)
    vmem_bytes: Optional[int] = None

    def describe(self) -> str:
        if self.path != "kernel":
            return f"{self.path} ({self.why})"
        return (f"kernel ({self.why}): a block of {self.block} a step, in "
                f"place, grid {self.grid}, VMEM "
                f"{self.vmem_bytes / 2 ** 20:.1f} MiB")


def _heads_a_step(H: int, Dk: int, Dv: int) -> Optional[int]:
    """Heads of a lane that one grid step holds: the most that divide
    H, keep their tiles inside `BLOCK_BYTES` and make a legal block of
    the [lanes, H, Dv] operands (whole sublane tiles of 8 heads, or
    all of H); None where no count does."""
    fits = [h for h in range(1, H + 1)
            if H % h == 0 and (h % 8 == 0 or h == H)
            and h * Dk * Dv * 4 <= BLOCK_BYTES]
    return max(fits, default=None)


def _vmem(hb: int, Dk: int, Dv: int) -> int:
    """VMEM the call's plan sums to: the state's block in and out and
    the vectors' block, each double buffered, the vectors transposed,
    a few tiles of temporaries, and 2 MiB for what Mosaic keeps."""
    return (4 * hb * Dk * Dv * 4 + 3 * ROWS * hb * max(Dk, 128) * 4
            + 4 * hb * Dv * 4 + 8 * Dk * Dv * 4 + 2 * 2 ** 20)


def kda_step_plan(lanes: int, H: int, Dk: int, Dv: int, *,
                  positions: int = 1, dtype=jnp.float32,
                  trivial_mesh: bool = True,
                  on_tpu: Optional[bool] = None,
                  impl: Optional[str] = None) -> StateStepPlan:
    """THE rule for a delta-rule layer's state step over ``lanes``
    lanes of ``H`` heads with [Dk, Dv] states of ``dtype``, at
    ``positions`` positions a step. ``impl`` "lax" / "pallas" force a
    path (the oracle, and the kernel in interpret mode off the chip);
    a forced kernel still needs a step and a state the kernel takes."""
    if impl not in (None, "lax", "pallas"):
        raise ValueError(f"impl must be None|lax|pallas, got {impl!r}")
    dtype = jnp.dtype(dtype)
    if impl == "lax":
        return StateStepPlan("lax", "forced")
    if positions != 1:
        return StateStepPlan(
            "lax", f"{positions} positions a step: the chunkwise form")
    if dtype != jnp.dtype(jnp.float32):
        return StateStepPlan("lax", f"{dtype.name} state")
    if Dk % 128 or Dv % 128:
        return StateStepPlan(
            "lax", f"a [{Dk}, {Dv}] state is not whole lanes of 128")
    if impl is None:
        if on_tpu is None:
            on_tpu = _on_tpu()
        if not on_tpu:
            return StateStepPlan("lax", "not on a TPU")
        if not trivial_mesh:
            return StateStepPlan(
                "lax", "a serving mesh: XLA partitions its own step, "
                "a bare kernel does not")
    hb = _heads_a_step(H, Dk, Dv)
    if hb is None:
        return StateStepPlan(
            "lax", f"no block of whole sublane tiles divides {H} heads "
            f"inside {BLOCK_BYTES >> 20} MiB")
    return StateStepPlan(
        "kernel", "forced" if impl else "on a TPU", block=hb,
        grid=(lanes, H // hb), vmem_bytes=_vmem(hb, Dk, Dv))


def _step_kernel(adv_ref, s_ref, r_ref, v_ref, o_ref, so_ref, *, hb: int):
    """One (lane, head block) grid cell: `kda_step` on each of the
    block's ``hb`` tiles. ``r_ref`` holds the block's vectors over Dk
    as rows - exp(g), k, beta k, q of head 0, then head 1's - and a
    tile wants them down its rows, so they are turned once a cell."""
    lane = pl.program_id(0)

    @pl.when(adv_ref[lane] != 0)
    def _advance():
        cols = r_ref[0, 0].T                    # [Dk, ROWS * hb]
        for j in range(hb):
            decay, k, bk, q = (cols[:, ROWS * j + i:ROWS * j + i + 1]
                               for i in range(ROWS))
            s = s_ref[0, j] * decay
            u = v_ref[0, j:j + 1, :] - jnp.sum(s * k, axis=0,
                                                keepdims=True)
            s = s + bk * u
            o_ref[0, j:j + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)
            so_ref[0, j] = s

    @pl.when(adv_ref[lane] == 0)
    def _keep():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _kda_call(state, q, k, v, g, beta, advance, *, hb, interpret):
    """The pallas_call: state [L, H, Dk, Dv], q, k, g [L, H, Dk],
    v [L, H, Dv], beta [L, H], advance [L] -> (o [L, H, Dv], the
    state, aliased to its input)."""
    L, H, Dk, Dv = state.shape
    nb = H // hb
    # exp(g) and beta k as `kda_step` has them, by the same XLA
    rows = jnp.stack([jnp.exp(g), k, beta[..., None] * k, q], axis=2)
    rows = rows.reshape(L, nb, ROWS * hb, Dk)

    def block(lane, h, adv):
        return lane, h, 0, 0

    def heads(lane, h, adv):
        return lane, h, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L, nb),
        in_specs=[pl.BlockSpec((1, hb, Dk, Dv), block),
                  pl.BlockSpec((1, 1, ROWS * hb, Dk), block),
                  pl.BlockSpec((1, hb, Dv), heads)],
        out_specs=[pl.BlockSpec((1, hb, Dv), heads),
                   pl.BlockSpec((1, hb, Dk, Dv), block)])
    return pl.pallas_call(
        functools.partial(_step_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((L, H, Dv), state.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 0 is the scalar-prefetched `advance`
        input_output_aliases={1: 1},
        compiler_params=None if interpret else _flash._compiler_params(
            _vmem(hb, Dk, Dv), ("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=8 * L * H * Dk * Dv, transcendentals=0,
            bytes_accessed=4 * (2 * L * H * Dk * Dv
                                + L * H * (ROWS * Dk + 2 * Dv))),
        interpret=interpret,
        name="kda_step",
    )(advance.astype(jnp.int32), state, rows, v)


def over_slots(call):
    """``call`` (a kernel's entry over [lanes, ...] operands whose
    outputs lead with the lanes too) wrapped in a custom_vmap
    (`flash_attention._make_append`'s twin): under the serving tick's
    `jax.vmap` over slots the slot axis JOINS the lane axis, and the
    pool's state leaf [num_slots, 1, ...] is stepped where it lies, by
    one call. `ops.ssm_step` wraps its call the same way."""

    @jax.custom_batching.custom_vmap
    def step(*operands):
        return tuple(call(*operands))

    @step.def_vmap
    def _rule(axis_size, in_batched, *args):
        outs = step(*(_flash._slots_into_lanes(x, b, axis_size)
                      for x, b in zip(args, in_batched)))
        return tuple(o.reshape((axis_size, -1) + o.shape[1:])
                     for o in outs), (True,) * len(outs)

    return step


@functools.lru_cache(maxsize=None)
def _make_step(hb: int, interpret: bool):
    return over_slots(functools.partial(_kda_call, hb=hb,
                                        interpret=interpret))


def kda_state_step(state: jax.Array, q: jax.Array, k: jax.Array,
                   v: jax.Array, g: jax.Array, beta: jax.Array,
                   advance: Optional[jax.Array] = None, *,
                   plan: StateStepPlan):
    """`parallel.linear_attention.kda_step` over lanes through the
    kernel of ``plan`` (`kda_step_plan`; a plan that says "lax" is the
    caller's to obey with `kda_step` itself), with the lanes' freeze:
    state [B, H, Dk, Dv] float32; q, k, g [B, H, Dk]; v [B, H, Dv];
    beta [B, H]; ``advance`` bool, a scalar or [B] (None: every lane
    advances). Returns ``(o [B, H, Dv], state)``.

    A lane that does not advance keeps its state bitwise, and its
    ``o`` is zeros: nobody reads it. The returned state is aliased to
    its input: a donated state is stepped where it lies - ONE read and
    one write of it - and whoever reads the old state after the call
    makes XLA copy it first. `jax.vmap` over a leading slot axis - the
    serving tick - folds that axis into the lanes of the same one
    call."""
    if plan.path != "kernel":
        raise ValueError(
            f"kda_state_step: the plan says {plan.describe()}")
    advance = jnp.broadcast_to(
        jnp.asarray(True if advance is None else advance, jnp.bool_),
        (state.shape[0],))
    return _make_step(plan.block, _flash._auto_interpret())(
        state, q, k, v, g, beta, advance)
