"""Grouped matrix products for FEW rows a group: the held experts'
three projections (`parallel.expert.HeldExpertsMoE`) as weight-streaming
Pallas TPU kernels.

A dropless expert layer sorts its (token, expert) pairs by expert and
multiplies each expert's rows with that expert's matrices. Where a
serving tick or a prefill chunk gives an expert one to five rows, the
product is bound by the bytes of the experts HIT and by nothing else:
every hit expert's [d, f] and [f, d] blocks must cross HBM once, the
rows are a rounding error. The kernel here is built for that regime:

* the grid walks VISITS - (group, row tile) pairs in the rows' order,
  as `jax.experimental.pallas.ops.tpu.megablox` does - and the number
  of visits is a traced grid bound: an expert without a pair is no
  visit and fetches nothing, and the rows past the last group (the
  pairs whose expert lives on another chip) belong to no visit;
* a visit streams its expert's block through VMEM in whole rows -
  [tk, f] of [E, d, f], [tk, d] of [E, f, d], contiguous in HBM -
  over an inner grid axis that accumulates the contraction in float32
  scratch; the operands go to the MXU in their own dtype (bf16 for a
  bf16 model) and the result is stored in it, as `lax.ragged_dot` has
  it;
* gate and up share one call (`grouped_swiglu`): the rows stream in
  once, both blocks of a visit arrive in the same step, and what is
  stored is `silu(g) * u` - the products rounded to the rows' dtype
  first, as the lax formula rounds them;
* a row tile that holds rows of several groups is visited by each in
  turn (consecutive steps: the output tile stays in VMEM) and a visit
  stores only its own group's rows. A group longer than a row tile is
  several visits. ROWS NO GROUP OWNS ARE NEVER WRITTEN: the caller
  selects them away (`jnp.where`, not a multiply - they may hold
  anything).

`grouped_product_plan` is THE rule (the twin of
`flash_attention.decode_attention_plan`): the kernel on a TPU with no
serving mesh, at widths that are whole lanes, for bf16 or float32
weights, while the rows an expert expects stay under the chip's ridge;
`lax.ragged_dot` - XLA's own lowering, the oracle - for everything
else. `expert_products` obeys it; `parallel.expert.grouped_experts`,
its one caller, differentiates through the lax formula whichever path
ran forward (`jax.custom_vjp`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops import flash_attention as _flash

# Rows a group above which a weight block is worth more MXU time than
# HBM time on the chips this runs on (v5e: 197e12 / 819e9 flops a
# byte, two flops a row and weight element): past it the product is
# compute-bound and XLA's own lowering, built for that, keeps it.
RIDGE_ROWS = 240
# Bytes of weight a grid step streams (both blocks of a fused step
# together): as large as leaves two of them (the pipeline's double
# buffer) well inside VMEM beside the rows and the accumulators. Alone
# on one v5e chip, at 64-row tiles, the three products of solar's,
# laguna's and longcat's ticks read 87.4 / 81.3 / 86.3 % of the hit
# experts' bytes at 8 MiB and 87.1 / 84.8 / 85.9 % at 16 (laguna: one
# step a visit instead of two; my chip run, PR 35).
BLOCK_BYTES = 16 * 2 ** 20
# Rows of a row tile, by the rows an expert expects. A tile's rows are
# multiplied with every block that visits it, whoever owns them, so a
# tile should not be much longer than a group; but at 1 to 5 rows an
# expert 16-row tiles read 5 to 9 points less of the bytes than 64-row
# ones (solar's tick 78.9 / 82.9 / 87.4 / 86.0 % at 16 / 32 / 64 / 128
# rows; my chip run, PR 35), and a group longer than a tile streams
# its block once a tile. The longer tiles are compiled for the chip
# (sandbox) and tested in interpret mode, not timed.
ROW_TILES = (64, 128, 256)


def _on_tpu() -> bool:
    """What the rule takes for "on a TPU" (apart from whether a call
    compiles or interprets, so that a test can drive the rule's kernel
    path in interpret mode)."""
    return not _flash._auto_interpret()


@dataclasses.dataclass(frozen=True)
class GroupedPlan:
    """Which way an expert layer's grouped products go at one shape,
    and why: the trace-time record of `grouped_product_plan` - the
    rule `parallel.expert.grouped_experts` obeys, what the engine logs
    at warm-up and carries in ``metrics_snapshot()``."""
    path: str                       # "kernel" | "lax"
    why: str
    rows: Optional[int] = None      # kernel path only, as below
    k_gate_up: Optional[int] = None  # contraction tile of gate | up
    k_down: Optional[int] = None
    grid: Optional[tuple] = None    # (most visits, k steps) of gate|up
    vmem_bytes: Optional[int] = None  # the larger call's

    def describe(self) -> str:
        if self.path != "kernel":
            return f"{self.path} ({self.why})"
        return (f"kernel ({self.why}): row tile {self.rows}, weight "
                f"blocks of {self.k_gate_up} and {self.k_down} rows, "
                f"grid up to {self.grid}, VMEM "
                f"{self.vmem_bytes / 2 ** 20:.1f} MiB")


def _k_tile(K: int, N: int, itemsize: int, blocks: int) -> int:
    """Rows of a weight block [tk, N]: the largest multiple of 128
    that divides the contraction and keeps a step's ``blocks`` blocks
    inside `BLOCK_BYTES` (never under 128)."""
    fits = [t for t in range(128, K + 1, 128)
            if K % t == 0 and blocks * t * N * itemsize <= BLOCK_BYTES]
    return max(fits, default=128)


def _row_tile(expected_rows: float) -> int:
    """The smallest row tile that holds two groups of the expected
    length (64 rows up to 32 an expert - every tick and chunk the
    benchmark serves)."""
    return next((t for t in ROW_TILES if t >= 2 * expected_rows),
                ROW_TILES[-1])


def _vmem(tm: int, tk: int, N: int, itemsize: int, blocks: int) -> int:
    """VMEM one call's plan sums to: the weight blocks and the rows'
    tile double buffered, the float32 products and accumulators, the
    output tile double buffered, the store's temporaries, and 2 MiB
    for what Mosaic keeps of its own."""
    return (2 * blocks * tk * N * itemsize + 2 * tm * tk * itemsize
            + 2 * blocks * tm * N * 4 + 2 * tm * N * itemsize
            + 3 * tm * N * 4 + 2 * 2 ** 20)


def grouped_product_plan(tokens: int, k: int, routed: int, d: int,
                         f: int, *, held: Optional[int] = None,
                         dtype=jnp.bfloat16, trivial_mesh: bool = True,
                         on_tpu: Optional[bool] = None,
                         impl: Optional[str] = None) -> GroupedPlan:
    """THE rule for a dropless expert layer's grouped products:
    ``tokens`` tokens each choose ``k`` of ``routed`` router outputs,
    ``held`` of them (None: all) are experts of width ``f`` over a
    model width ``d`` held here. ``impl`` "lax" / "pallas" force a
    path (the oracle, and the kernel in interpret mode off the chip);
    a forced kernel still needs widths and a dtype the kernel takes."""
    if impl not in (None, "lax", "pallas"):
        raise ValueError(f"impl must be None|lax|pallas, got {impl!r}")
    dtype = jnp.dtype(dtype)
    expected = tokens * k / max(routed, 1)
    if impl == "lax":
        return GroupedPlan("lax", "forced")
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return GroupedPlan("lax", f"{dtype.name} weights")
    if d % 128 or f % 128:
        return GroupedPlan(
            "lax", f"widths {d} and {f} are not whole lanes of 128")
    if impl is None:
        if on_tpu is None:
            on_tpu = _on_tpu()
        if not on_tpu:
            return GroupedPlan("lax", "not on a TPU")
        if not trivial_mesh:
            return GroupedPlan(
                "lax", "a serving mesh: XLA partitions its own grouped "
                "product, a bare kernel does not")
        if expected > RIDGE_ROWS:
            return GroupedPlan(
                "lax", f"{expected:.0f} rows an expert expected, over "
                f"the ridge of {RIDGE_ROWS}: compute-bound")
    tm = _row_tile(expected)
    size = dtype.itemsize
    tk_up, tk_down = _k_tile(d, f, size, 2), _k_tile(f, d, size, 1)
    vmem = max(_vmem(tm, tk_up, f, size, 2),
               _vmem(tm, tk_down, d, size, 1))
    rows = -(-tokens * k // tm) * tm
    held = routed if held is None else held
    return GroupedPlan(
        "kernel",
        ("forced" if impl else "on a TPU")
        + f", {expected:.2f} rows an expert expected",
        rows=tm, k_gate_up=tk_up, k_down=tk_down,
        grid=(rows // tm + held - 1, d // tk_up), vmem_bytes=vmem)


def group_visits(sizes: jax.Array, rows: int, tm: int):
    """The kernels' schedule for groups of ``sizes`` rows laid end to
    end from row 0 of ``rows`` (a multiple of ``tm``): ``(offsets
    [E + 1], group [V], tile [V], visits)`` - visit v multiplies row
    tile ``tile[v]`` with group ``group[v]``'s block, visits run in the
    rows' order, an empty group has none, and only the first ``visits``
    (a traced scalar, at most V = rows / tm + E - 1) exist. One
    schedule serves the three products of a layer."""
    E = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(tiles, dtype=jnp.int32)
    v = jnp.arange(rows // tm + E - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(upto[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        E - 1)
    tile = jnp.clip(first[group] + v - (upto - tiles)[group],
                    0, rows // tm - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile, upto[-1]


def _visit_kernel(offs_ref, group_ref, tile_ref, lhs_ref, *refs,
                  blocks: int, tm: int, steps: int):
    """One (visit, k step) grid cell. ``refs``: the ``blocks`` weight
    blocks [tk, N] of the visit's group, the output tile [tm, N], and
    - where the contraction takes several ``steps`` - a float32
    accumulator a block. The last k step stores the rows of the tile
    that the visit's group owns and leaves the others as they are: an
    earlier visit's, a later visit's, or nobody's."""
    rhs, out_ref, accs = refs[:blocks], refs[blocks], refs[blocks + 1:]
    v, step = pl.program_id(0), pl.program_id(1)
    x = lhs_ref[...]
    products = [jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
                for w_ref in rhs]

    def store(products):
        g = group_ref[v]
        row = tile_ref[v] * tm + lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        own = (row >= offs_ref[g]) & (row < offs_ref[g + 1])
        val = products[0].astype(out_ref.dtype)
        if blocks == 2:     # silu(gate) * up, rounded as lax rounds
            up = products[1].astype(out_ref.dtype)
            val = (jax.nn.silu(val.astype(jnp.float32))
                   * up.astype(jnp.float32)).astype(out_ref.dtype)
        out_ref[...] = jnp.where(own, val, out_ref[...])

    if steps == 1:          # a block is the whole contraction
        store(products)
        return

    @pl.when(step == 0)
    def _first():
        for acc, p in zip(accs, products):
            acc[...] = p

    @pl.when(step > 0)
    def _add():
        for acc, p in zip(accs, products):
            acc[...] += p

    @pl.when(step == steps - 1)
    def _last():
        store([acc[...] for acc in accs])


@functools.partial(jax.jit, static_argnames=("tm", "tk", "interpret"))
def _grouped_call(schedule, lhs, *rhs, tm, tk, interpret):
    """The pallas_call: lhs [M, K] (M a multiple of ``tm``), one rhs
    [E, K, N] (the product) or two (`silu(lhs a) * (lhs b)`) ->
    [M, N], rows outside the groups unwritten."""
    offsets, group, tile, visits = schedule
    M, K = lhs.shape
    N = rhs[0].shape[2]
    blocks = len(rhs)
    size = lhs.dtype.itemsize

    def rows_map(v, step, offs, group, tile):
        return tile[v], step

    def block_map(v, step, offs, group, tile):
        return group[v], step, 0

    def out_map(v, step, offs, group, tile):
        return tile[v], 0

    return pl.pallas_call(
        functools.partial(_visit_kernel, blocks=blocks, tm=tm,
                          steps=K // tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(visits, K // tk),
            in_specs=[pl.BlockSpec((tm, tk), rows_map),
                      *[pl.BlockSpec((None, tk, N), block_map)] * blocks],
            out_specs=pl.BlockSpec((tm, N), out_map),
            scratch_shapes=([pltpu.VMEM((tm, N), jnp.float32)] * blocks
                            if K // tk > 1 else [])),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=None if interpret else _flash._compiler_params(
            _vmem(tm, tk, N, size, blocks), ("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * blocks * M * K * N, transcendentals=0,
            bytes_accessed=size * (
                M * K + M * N + blocks * rhs[0].shape[0] * K * N)),
        interpret=interpret,
        name="grouped_swiglu" if blocks == 2 else "grouped_matmul",
    )(offsets, group, tile, lhs, *rhs)


def _lax_products(xs, sizes, w_gate, w_up, w_down):
    h = (jax.nn.silu(lax.ragged_dot(xs, w_gate, sizes))
         * lax.ragged_dot(xs, w_up, sizes))
    return lax.ragged_dot(h, w_down, sizes)


def expert_products(xs: jax.Array, sizes: jax.Array, w_gate: jax.Array,
                    w_up: jax.Array, w_down: jax.Array,
                    plan: GroupedPlan, *,
                    interpret: Optional[bool] = None) -> jax.Array:
    """SwiGLU experts over rows sorted by expert: rows
    ``sum(sizes[:e]) .. sum(sizes[:e + 1])`` of ``xs`` [M, d] go through
    expert e - ``silu(x w_gate[e]) * (x w_up[e])`` times ``w_down[e]`` -
    by the way ``plan`` says (`grouped_product_plan`). Returns [M, d] at
    xs.dtype. The rows past the last group hold zeros on the lax path
    and ANYTHING on the kernel's: select them away, never multiply. On
    the kernel path M must be a multiple of ``plan.rows``, and there is
    no reverse mode (`parallel.expert.grouped_experts` brings the lax
    formula's)."""
    if plan.path != "kernel":
        return _lax_products(xs, sizes, w_gate, w_up, w_down)
    if xs.shape[0] % plan.rows:
        raise ValueError(
            f"expert_products: {xs.shape[0]} rows are no multiple of "
            f"the plan's row tile {plan.rows}")
    if interpret is None:
        interpret = _flash._auto_interpret()
    schedule = group_visits(sizes, xs.shape[0], plan.rows)
    h = _grouped_call(schedule, xs, w_gate, w_up, tm=plan.rows,
                      tk=plan.k_gate_up, interpret=bool(interpret))
    return _grouped_call(schedule, h, w_down, tm=plan.rows,
                         tk=plan.k_down, interpret=bool(interpret))
