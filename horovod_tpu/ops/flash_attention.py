"""Pallas TPU flash-attention kernels.

The hot op of the flagship transformer, written for the hardware:
the [Sq, Sk] score matrix never touches HBM, the matmuls ride the MXU
in the inputs' own dtype (bf16 operands for a bf16 model, float32
accumulation), and the online-softmax rescale/exp traffic stays on the
VPU in float32.

How much one grid step does follows the shape (`_pick_tiles`), not a
default: a step owns a 512-row tile where the sequence allows and,
where the head's whole K and V fit the VMEM budget, holds them
RESIDENT as one block — the k sweep is a `lax.fori_loop` inside the
kernel whose bounds are the causal diagonal and the sliding-window
band, so blocks above the diagonal or outside the band do not exist,
and only the sub-blocks that straddle the diagonal, the band's edge
or the zero-padded tail build a mask. Where `Sk * D` does not fit, the
same kernels stream K/V one block a step over an innermost sequential
grid axis (the float32 accumulators live in VMEM scratch across it)
with the block index clamped to the band, so a skipped step re-uses
the block already in VMEM instead of fetching one it will not read.

No reference equivalent: Horovod v0.10 contains no attention at all
(SURVEY §5.7); this is part of the TPU-native long-context extension.
The backward is fused Pallas too (FlashAttention-2 style, the
default): the forward saves only the row logsumexp, and two kernels
rebuild each probability tile on the fly — dQ sweeps k like the
forward; dK/dV owns a k tile, holds the head group's Q, dO, lse and
dvec resident and sweeps q FROM the diagonal. The forward and dK/dV
compute the scores transposed (Sᵀ = K·Qᵀ, a query a lane), so the
softmax state, lse and dvec are [1, block_q] rows and no score-sized
tile is ever transposed on the XLU. O(S) residual memory, no scan-residual HBM
traffic. The same math in plain-XLA form lives in
`horovod_tpu.parallel.sequence.blockwise_attention`, the correctness
oracle for both directions and the recompute-VJP fallback
(HOROVOD_FLASH_BWD=recompute; banded for sliding-window training).

Layout is the framework-wide [batch, seq, heads, head_dim]; the kernel
internally works head-major. `ulysses_attention(attn_impl=
flash_attention)` composes this with sequence parallelism: all_to_all to
head-sharded layout, flash kernel locally, all_to_all back.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

NEG_INF = float("-inf")

# Mosaic's scoped-VMEM default on the chips this runs on (v5e: 16 MiB
# of 128): a kernel whose plan asks for more says so through
# `vmem_limit_bytes`, one that asks for less leaves the default alone.
VMEM_SCOPED_DEFAULT = 16 * 2 ** 20
# What the plan lets one kernel ask for before K/V (or the group's Q)
# stop being resident and stream instead: a quarter of v5e's VMEM, and
# under the smallest VMEM of the current generations (64 MiB).
VMEM_BUDGET = 32 * 2 ** 20
# Tiles the plan chooses from when the caller names none, each with
# the kernels' time per unit of score area relative to the largest
# (one v5e chip, B4 S1024 H16 D64 bf16, forward + backward: 0.94, 1.37
# and 2.65 ms a layer; my chip run, PR 25): a bigger tile amortizes the
# online-softmax bookkeeping and the MXU's weight loads over more
# columns, a smaller one pads a ragged length less. (1024-row tiles
# measured no faster than 512 at S 1024 and 2048.)
AUTO_TILES = ((512, 1.0), (256, 1.45), (128, 2.8))

_NT = (((1,), (1,)), ((), ()))      # a · bᵀ
_NN = (((1,), (0,)), ((), ()))      # a · b
_TN = (((0,), (0,)), ((), ()))      # aᵀ · b


def _compiler_params(vmem_bytes: int,
                     semantics=("parallel", "parallel", "parallel",
                                "arbitrary")):
    kw = {}
    if vmem_bytes > VMEM_SCOPED_DEFAULT:
        kw["vmem_limit_bytes"] = int(vmem_bytes)
    return pltpu.CompilerParams(dimension_semantics=semantics, **kw)


def _snap_tile(block: int, S: int) -> int:
    """Largest hardware-legal tile <= ``block`` for a length-``S``
    grid axis. The tiling rule of real Mosaic (v5e/v5-lite): the
    last two dims of a block must be multiples of (8, 128) or equal
    to the array's dims. So the second-minor block dim is a multiple
    of 8 OR the whole axis: a single block equal to the (padded)
    axis always qualifies, a multi-block tile must be 8-aligned — a
    user-swept tile like 100 snaps to 96 instead of tracing a kernel
    only interpret mode can run (interpret accepts shapes real
    Mosaic rejects). Shared by the forward and both
    backward grids so their tiles can never disagree."""
    b = min(block, max(S, 1))
    if b >= S:
        return b           # one block == the padded axis: always legal
    return max(8, b - b % 8)


def mosaic_block_ok(block_shape, array_shape) -> bool:
    """The v5-lite lowering rule for one (block, array) pair: the
    last two block dims must be multiples of (8, 128) respectively,
    or equal to the corresponding array dims. Introspection for
    `flash_tile_check` and the CPU regression tests — verifiable
    without a TPU window."""
    (b2, b1), (a2, a1) = block_shape[-2:], array_shape[-2:]
    return ((b1 % 128 == 0 or b1 == a1)
            and (b2 % 8 == 0 or b2 == a2))


class _Tiles(NamedTuple):
    """What `_pick_tiles` decides for one (Sq, Sk, D, dtype, group):
    ``bq`` query rows are one online-softmax state and one grid step
    of the forward and dQ, ``bk`` keys one sub-block of their sweep;
    dK/dV owns ``bk`` keys a step and sweeps ``bq``-row sub-blocks."""
    bq: int
    bk: int
    Sqp: int               # padded lengths (multiples of bq / bk)
    Skp: int
    kv_resident: bool      # fwd, dQ: the head's whole K and V one block
    q_resident: bool       # dK/dV: the group's whole Q, dO, lse, dvec
    vmem_fwd: int          # bytes each kernel's plan sums to
    vmem_dq: int
    vmem_dkv: int

    @property
    def nq(self) -> int:
        return self.Sqp // self.bq

    @property
    def nk(self) -> int:
        return self.Skp // self.bk

    @property
    def k_rows(self) -> int:
        """Rows of K (and V) a forward or dQ grid step holds."""
        return self.Skp if self.kv_resident else self.bk

    @property
    def q_rows(self) -> int:
        """Rows of each group member's Q a dK/dV grid step holds."""
        return self.Sqp if self.q_resident else self.bq

    def sweep_steps(self, window: Optional[int]):
        """Lengths of the innermost (sequential) grid axis of the
        forward / dQ and of dK/dV: the blocks of the swept side a step
        may need — one when resident; streamed, all of them, or under
        a sliding window only those its band can touch."""
        def steps(S, rows, own):
            n = S // rows
            if window is None:
                return n
            return min(n, -(-(own + window - 1) // rows) + 1)
        return (steps(self.Skp, self.k_rows, self.bq),
                steps(self.Sqp, self.q_rows, self.bk))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _axis_tile(S: int, block: Optional[int], sublane: int):
    """(tile, padded length) of one sequence axis."""
    if block is not None:
        b = _snap_tile(block, S)           # honoured as given
        return b, _round_up(S, b)
    if S <= AUTO_TILES[0][0]:
        b = _round_up(S, sublane)          # one tile == the padded axis
        return b, b
    b = min(AUTO_TILES, key=lambda tc: _round_up(S, tc[0]) * tc[1])[0]
    return b, _round_up(S, b)


def _vmem_plan(bq, bk, q_rows, k_rows, D, itemsize, group):
    """Bytes of VMEM the three kernels ask for: every pipelined block
    twice (double-buffered), the float32 scratch, and the float32
    temporaries of one [bq, bk] sub-block. ``q_rows``/``k_rows`` are
    the rows of the SWEPT side one step holds (the whole padded axis
    when resident, one tile when streamed). Minor dims count as
    padded to 128 lanes."""
    Dp = _round_up(D, 128)
    row = 8 * _round_up(bq, 128) * 4          # one [1, bq] f32 row
    temps = 6 * bq * _round_up(bk, 128) * 4   # s, p, dp, ds + casts
    fwd = (2 * (2 * bq * Dp * itemsize + row)             # q, o, lse
           + 2 * 2 * k_rows * Dp * itemsize               # k, v
           + _round_up(D, 8) * _round_up(bq, 128) * 4     # acc (Oᵀ)
           + 2 * row + temps)                             # m, l
    dq = (2 * (3 * bq * Dp * itemsize + 2 * row)          # q, do, dq
          + 2 * 2 * k_rows * Dp * itemsize                # + lse, dvec
          + bq * Dp * 4 + 2 * bq * 128 * 4 + temps)       # acc, columns
    dkv = (2 * group * (2 * q_rows * Dp * itemsize        # q, do
                        + 2 * (q_rows // bq) * row)       # lse, dvec
           + 2 * 4 * bk * Dp * itemsize                   # k, v, dk, dv
           + 2 * bk * Dp * 4 + temps)
    return fwd, dq, dkv


def _pick_tiles(Sq: int, Sk: int, D: int, itemsize: int, group: int,
                block_q: Optional[int] = None,
                block_k: Optional[int] = None) -> _Tiles:
    """Tiles from the shape. ``block_q``/``block_k`` given: that tile
    (snapped to a legal size), as before. Left None: the tile of
    `AUTO_TILES` that makes the padded axis cheapest, or the whole
    axis where it is no longer than the largest. Either way the swept
    side is RESIDENT where the kernels' VMEM sum stays inside
    `VMEM_BUDGET`, and streams one tile a grid step where it does
    not."""
    sublane = 8 * max(1, 4 // itemsize)       # f32 8, bf16 16 rows
    bq, Sqp = _axis_tile(Sq, block_q, sublane)
    bk, Skp = _axis_tile(Sk, block_k, sublane)
    plan = functools.partial(_vmem_plan, bq, bk, D=D,
                             itemsize=itemsize, group=group)
    fwd, dq, dkv = plan(q_rows=Sqp, k_rows=Skp)
    kv_resident = max(fwd, dq) <= VMEM_BUDGET
    q_resident = dkv <= VMEM_BUDGET
    if not kv_resident:
        fwd, dq, _ = plan(q_rows=bq, k_rows=bk)
    if not q_resident:
        _, _, dkv = plan(q_rows=bq, k_rows=bk)
    return _Tiles(bq, bk, Sqp, Skp, kv_resident, q_resident,
                  fwd, dq, dkv)


@dataclasses.dataclass(frozen=True)
class FlashPlan:
    """What the fwd + bwd pallas_calls will do at one shape — the
    record that says whether the shape-chosen tiling engages. Iterates
    as the block list `(name, block shape, array shape, legal)`."""
    block_q: int
    block_k: int
    kv_resident: bool
    q_resident: bool
    grid: dict              # kernel -> grid tuple (batch 1)
    grid_steps: dict        # kernel -> steps a call, per batch row
    vmem_bytes: dict        # kernel -> bytes the plan sums to
    vmem_limit_bytes: dict  # kernel -> what is asked of Mosaic, or None
    blocks: tuple

    def __iter__(self):
        return iter(self.blocks)


def flash_tile_check(Sq: int, Sk: int, H: int, Hkv: int, D: int, *,
                     block_q: Optional[int] = None,
                     block_k: Optional[int] = None,
                     itemsize: int = 2,
                     window: Optional[int] = None) -> FlashPlan:
    """The plan the fwd + bwd pallas_calls will use at these shapes:
    tiles, grid steps a call, resident or streamed, VMEM asked, and
    every (name, block shape, array shape, legal) after tile snapping —
    the static half of the v5e regression test: a config is
    hardware-lowerable iff every entry's ``legal`` bit is True, and
    that is checkable on CPU (interpret mode would happily run
    illegal tiles, which is exactly how the r04 failure shipped)."""
    group = H // Hkv
    t = _pick_tiles(Sq, Sk, D, itemsize, group, block_q, block_k)
    B = 1   # batch rides a leading grid dim, never a constrained one
    q_arr, k_arr = (B, H, t.Sqp, D), (B, Hkv, t.Skp, D)
    r_arr = (B, H, t.nq, 1, t.bq)
    entries = [
        ("fwd.q", (1, 1, t.bq, D), q_arr),
        ("fwd.kv", (1, 1, t.k_rows, D), k_arr),
        ("fwd.out", (1, 1, t.bq, D), q_arr),
        ("fwd.lse", (1, 1, 1, 1, t.bq), r_arr),
        ("bwd.dq.q", (1, 1, t.bq, D), q_arr),
        ("bwd.dq.lse", (1, 1, 1, 1, t.bq), r_arr),
        ("bwd.dq.kv", (1, 1, t.k_rows, D), k_arr),
        ("bwd.dkv.q", (1, group, t.q_rows, D), q_arr),
        ("bwd.dkv.lse", (1, group, t.q_rows // t.bq, 1, t.bq), r_arr),
        ("bwd.dkv.out", (1, 1, t.bk, D), k_arr),
    ]
    fwd_sweep, dkv_sweep = t.sweep_steps(window)
    grid = {"fwd": (B, H, t.nq, fwd_sweep),
            "bwd.dq": (B, H, t.nq, fwd_sweep),
            "bwd.dkv": (B, Hkv, t.nk, dkv_sweep)}
    vmem = {"fwd": t.vmem_fwd, "bwd.dq": t.vmem_dq,
            "bwd.dkv": t.vmem_dkv}
    return FlashPlan(
        block_q=t.bq, block_k=t.bk, kv_resident=t.kv_resident,
        q_resident=t.q_resident, grid=grid,
        grid_steps={k: math.prod(g) for k, g in grid.items()},
        vmem_bytes=vmem,
        vmem_limit_bytes={k: v if v > VMEM_SCOPED_DEFAULT else None
                          for k, v in vmem.items()},
        blocks=tuple((name, blk, arr, mosaic_block_ok(blk, arr))
                     for name, blk, arr in entries))


# Block-index arithmetic on traced int32 scalars, for the index maps
# and the kernels' sweep bounds. Written on `lax` primitives: Mosaic
# lowers each `//` or `%` of `jax.numpy` through a `sign` helper that
# costs tens of milliseconds of lowering apiece — with a dozen of them
# a layer that was 9 s of every process's set-up (my chip run, PR 25).
# Python ints (the bounds of a non-causal sweep) pass straight through.

def _traced(*xs) -> bool:
    return any(not isinstance(x, int) for x in xs)


def _fdiv(x, d: int):
    """floor(x / d) for a positive Python int ``d``."""
    if not _traced(x):
        return x // d
    if d == 1:
        return x
    # lax.div truncates toward zero; shift negatives down first
    return jax.lax.div(x - jnp.where(x < 0, d - 1, 0).astype(x.dtype),
                       jnp.asarray(d, x.dtype))


def _imin(a, b):
    return jnp.minimum(a, b) if _traced(a, b) else min(a, b)


def _imax(a, b):
    return jnp.maximum(a, b) if _traced(a, b) else max(a, b)


def _iclip(x, lo, hi):
    return _imin(_imax(x, lo), hi)


def _band_j0(qi, *, window, q_offset, k_offset, block_q, block_k):
    """First k-block index that can intersect q-block ``qi``'s band —
    the lower bound of the k sweep (shared by index_map and kernel so
    the DMA'd tile and the in-kernel positions cannot disagree)."""
    return _imax(0, _fdiv(
        q_offset + qi * block_q - (window - 1) - k_offset, block_k))


def _band_i0(j, *, q_offset, k_offset, block_q, block_k):
    """First q-block index whose rows can see k-block ``j`` under the
    causal band (q >= k) — the lower bound of dK/dV's q sweep."""
    return _imax(0, _fdiv(k_offset + j * block_k - q_offset, block_q))


def _k_sweep(qi, *, causal, window, q_offset, k_offset, kv_len,
             block_q, block_k, nk):
    """Bounds `(lo, a, b, hi)` of q-block ``qi``'s k sweep, in k-blocks:
    blocks [lo, a) straddle the window's far edge, [a, b) lie wholly
    inside the band and before the zero-padded tail (no mask), [b, hi)
    straddle the diagonal or the tail. Blocks outside [lo, hi) cannot
    be seen by any row of the q-block and are never visited."""
    full = kv_len // block_k
    if not causal:
        return 0, 0, full, nk
    q0 = q_offset + qi * block_q
    hi = _iclip(_fdiv(q0 + block_q - 1 - k_offset, block_k) + 1, 0, nk)
    b = _imin(_fdiv(q0 - k_offset + 1, block_k), _imin(hi, full))
    if window is None:
        return 0, 0, _imax(b, 0), hi
    lo = _imin(_band_j0(qi, window=window, q_offset=q_offset,
                        k_offset=k_offset, block_q=block_q,
                        block_k=block_k), hi)
    a = _iclip(_fdiv(q0 + block_q - 1 - window - k_offset, block_k) + 1,
               lo, hi)
    return lo, a, _imax(a, b), hi


def _q_sweep(j, *, causal, window, q_offset, k_offset, block_q,
             block_k, nq):
    """dK/dV's mirror of `_k_sweep`: bounds `(lo, a, b, hi)` of
    k-block ``j``'s q sweep, in q-blocks — [lo, a) straddle the
    diagonal, [a, b) see the whole k-block unmasked, [b, hi) straddle
    the window's far edge. (Zero-padded q rows need no mask: their dO
    and dvec are zero, so they add nothing to dK or dV.)"""
    if not causal:
        return 0, 0, nq, nq
    c0 = k_offset + j * block_k
    lo = _imin(_band_i0(j, q_offset=q_offset, k_offset=k_offset,
                        block_q=block_q, block_k=block_k), nq)
    a = _iclip(_fdiv(c0 + block_k - 2 - q_offset, block_q) + 1, lo, nq)
    if window is None:
        return lo, a, nq, nq
    hi = _iclip(_fdiv(window + c0 + block_k - 2 - q_offset, block_q) + 1,
                lo, nq)
    a = _imin(a, hi)
    b = _iclip(_fdiv(window - block_q + c0 - q_offset, block_q) + 1,
               a, hi)
    return lo, a, b, hi


def _streamed_block(own, step, *, sweep, n):
    """Index of the swept block a streamed grid step holds in VMEM:
    the ``step``-th of block ``own``'s band, clamped to the band's
    last (so a step past the band re-uses the block already there;
    `_swept` gives it an empty range) and to the axis's ``n`` blocks
    (a band that lies wholly outside the sequence)."""
    lo, _, _, hi = sweep(own)
    return _iclip(_imin(lo + step, hi - 1), 0, n - 1)


def _mask_block(q_start, k_start, *, causal, window, kv_len, k_local0,
                block_q, block_k, transposed=False):
    """The fwd/bwd-shared mask for one [block_q, block_k] tile, or None.

    `q_start`/`k_start` are GLOBAL positions (offset-aware, the
    `banded_causal_mask` band rule); `k_local0` is the block's LOCAL
    key index origin for the zero-pad tail test (None: the caller's
    padded keys need no mask). ``transposed``: the [block_k, block_q]
    tile of dK/dV's Sᵀ."""
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    qd, kd = (1, 0) if transposed else (0, 1)
    mask = None
    if causal:
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, qd)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, kd)
        mask = rows >= cols
        if window is not None:
            mask = jnp.logical_and(mask, rows - cols < window)
    if k_local0 is not None and kv_len % block_k:
        local = k_local0 + jax.lax.broadcasted_iota(
            jnp.int32, shape, kd)
        pad_ok = local < kv_len
        mask = pad_ok if mask is None else jnp.logical_and(mask, pad_ok)
    return mask


def _sweep_loops(bounds, body, *, masked_head, masked_tail):
    """Run ``body(masked)(j, carry)`` over the three ranges of a sweep:
    masked [lo, a), unmasked [a, b), masked [b, hi). A range that
    cannot exist for this configuration is not traced at all."""
    lo, a, b, hi = bounds
    if masked_head:
        jax.lax.fori_loop(lo, a, body(True), 0)
    jax.lax.fori_loop(a, b, body(False), 0)
    if masked_tail:
        jax.lax.fori_loop(b, hi, body(True), 0)


def _fold_scale(D: int, dtype) -> bool:
    """Whether the softmax scale goes onto the q (k) operand rather
    than the float32 logits: only where that is exact or was always
    done — a power of two (D 64: 0.125) in any dtype, any scale in
    float32. Never rounded into bf16."""
    return (jnp.dtype(dtype) == jnp.float32
            or math.frexp(D ** -0.5)[0] == 0.5)


def _kernel(fn, interpret: bool, **static):
    """``fn`` with its static arguments bound, handed its position on
    the two sequence grid axes `(index, sweep step, sweep steps)`.
    Under the interpreter the whole body sits inside a traced,
    trivially-true `pl.when`: the interpreter evaluates an unguarded
    body's loads primitive by primitive, and under
    `shard_map(check_vma=True)` those trip a varying-manual-axes
    mismatch (ref operands vary, their indices do not); inside a cond
    nothing is checked. Mosaic never sees the guard."""
    fn = functools.partial(fn, **static)

    def run(*refs):
        pos = (pl.program_id(2), pl.program_id(3), pl.num_programs(3))
        if interpret:
            pl.when(pos[0] >= 0)(lambda: fn(pos, *refs))
        else:
            fn(pos, *refs)
    return run


def _scaled(x, scale):
    """``x * scale`` in ``x``'s dtype, through float32 (v5e's VPU has
    no bf16 multiply); exact wherever `_fold_scale` allows it."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _swept(pos, own_sweep, streamed):
    """(bounds, base, first, last) of one grid step's sweep: the block
    ranges `(lo, a, b, hi)` to loop over, the index of the first swept
    block in VMEM, and whether this is the first / last step of the
    sequential axis. Resident: the whole band, all of it in VMEM.
    Streamed: the band's ``step``-th block alone (an empty range when
    the band has fewer)."""
    own, step, steps = pos
    bounds = own_sweep(own)
    if not streamed:
        return bounds, 0, True, True
    base = bounds[0] + step
    bounds = tuple(_iclip(x, base, base + 1) for x in bounds)
    return bounds, base, step == 0, step == steps - 1


def _when(cond, fn):
    """``fn()`` now where ``cond`` is statically true, else guarded."""
    fn() if cond is True else pl.when(cond)(fn)


def _flash_kernel(pos, q_ref, k_ref, v_ref, o_ref, lse_ref,
                  acc_ref, m_ref, l_ref, *,
                  scale: float, fold: bool, causal: bool,
                  window: "int | None", q_offset: int, k_offset: int,
                  kv_len: int, block_q: int, block_k: int, nk: int,
                  streamed: bool):
    """One (batch, head, q-block, sweep step) grid cell: the q-block
    sweeps the k sub-blocks its band can see (`_k_sweep`).

    Resident K/V (``streamed`` False): the sweep axis has one step,
    ``k_ref`` is the head's whole K, and the sweep is an in-kernel
    loop. Streamed: the axis runs over the band's blocks, one in VMEM
    a step (`_streamed_block` clamps the index, so a step past the
    band re-uses the block already there and its sweep is empty).

    The scores are computed TRANSPOSED, Sᵀ = K·Qᵀ [block_k, block_q]:
    a query is a lane, so the online-softmax state is a [1, block_q]
    row (4 vregs at 512, not the 64 of a lane-replicated column), the
    max and the sum over keys run down the sublanes (elementwise
    across vregs, no cross-lane reduce), and Oᵀ += Vᵀ·Pᵀ; the output
    is transposed back once, when the sweep ends.

    Scratch (persistent across the sweep axis):
      acc_ref [D, block_q] f32 — unnormalized output accumulator, Oᵀ
      m_ref   [1, block_q] f32 — running max of each query
      l_ref   [1, block_q] f32 — running softmax denominator
    """
    qi = pos[0]
    bounds, base, first, last = _swept(
        pos, functools.partial(
            _k_sweep, causal=causal, window=window, q_offset=q_offset,
            k_offset=k_offset, kv_len=kv_len, block_q=block_q,
            block_k=block_k, nk=nk), streamed)
    q_start = q_offset + qi * block_q

    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
    _when(first, _init)

    q = q_ref[0, 0]                                          # [bq, D]
    if fold:
        q = _scaled(q, scale)

    def body(masked):
        def _block(j, carry):
            off = pl.multiple_of((j - base) * block_k, block_k)
            k = k_ref[0, 0, pl.ds(off, block_k), :]          # [bk, D]
            st = jax.lax.dot_general(
                k, q, _NT, preferred_element_type=jnp.float32)
            if not fold:
                st = st * scale                              # [bk, bq]
            m_prev = m_ref[...]                              # [1, bq]
            if masked:
                mask = _mask_block(
                    q_start, k_offset + j * block_k, causal=causal,
                    window=window, kv_len=kv_len, k_local0=j * block_k,
                    block_q=block_q, block_k=block_k, transposed=True)
                st = jnp.where(mask, st, NEG_INF)
            m_new = jnp.maximum(
                m_prev, jnp.max(st, axis=0, keepdims=True))
            if masked:
                # Queries with every key masked so far keep m == -inf;
                # shift by 0 there so exp(-inf - 0) = 0 instead of
                # exp(-inf - -inf) = NaN.
                shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
                corr = jnp.where(m_prev == NEG_INF, 0.0,
                                 jnp.exp(m_prev - shift))
            else:
                # every score is finite, so m_new is, and
                # exp(-inf - m_new) is the 0 a fresh query needs
                shift = m_new
                corr = jnp.exp(m_prev - m_new)
            pt = jnp.exp(st - shift)                         # [bk, bq]
            l_ref[...] = (l_ref[...] * corr
                          + jnp.sum(pt, axis=0, keepdims=True))
            v = v_ref[0, 0, pl.ds(off, block_k), :]          # [bk, D]
            pvt = jax.lax.dot_general(
                v, pt.astype(v.dtype), _TN,
                preferred_element_type=jnp.float32)          # [D, bq]
            acc_ref[...] = acc_ref[...] * corr + pvt
            m_ref[...] = m_new
            return carry
        return _block

    _sweep_loops(bounds, body,
                 masked_head=causal and window is not None,
                 masked_tail=causal or kv_len % block_k != 0)

    def _finalize():
        l = l_ref[...]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[...] / denom).T.astype(o_ref.dtype)
        # Row logsumexp for the fused backward: L = m + log(l), -inf on
        # fully-masked rows (the bwd kernels turn those into p = 0).
        lse_ref[0, 0, 0] = jnp.where(l == 0.0, NEG_INF,
                                     m_ref[...] + jnp.log(denom))
    _when(last, _finalize)


def _head_major(x, rows):
    """[B, S, H, D] -> [B, H, rows, D], zero-padded along the
    sequence; XLA fuses the transposes."""
    xt = jnp.transpose(x, (0, 2, 1, 3))
    if rows != xt.shape[2]:
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, rows - xt.shape[2]),
                          (0, 0)))
    return xt


def _row_spec(bq: int):
    """BlockSpec of a q-block's [1, bq] float32 row (lse, dvec) on the
    fwd and dQ grids. The arrays are [B, H, nq, 1, bq], so the block's
    last two dims ARE the array's: legal on real Mosaic at any tile (a
    rank-3 [B, H, S] array with (1, 1, bq) blocks is not — it only
    ever worked in interpret mode — and a lane-replicated [S, 128]
    column costs 128x the HBM traffic)."""
    return pl.BlockSpec((1, 1, 1, 1, bq),
                        lambda b, h, i, s: (b, h, i, 0, 0))


def _column(row):
    """[1, n] row -> [n, 1] column, through the one relayout Mosaic
    always has: a 2D transpose of a full (128-sublane) tile."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


def _kv_spec(t: _Tiles, group: int, D: int, sweep):
    """BlockSpec of K (or V) on the fwd and dQ grids (b, h, i, step):
    the head's whole K when resident, else the ``step``-th block of
    q-block ``i``'s band."""
    if t.kv_resident:
        return pl.BlockSpec((1, 1, t.Skp, D),
                            lambda b, h, i, s: (b, _fdiv(h, group), 0, 0))
    return pl.BlockSpec(
        (1, 1, t.bk, D),
        lambda b, h, i, s: (b, _fdiv(h, group), _streamed_block(
            i, s, sweep=sweep, n=t.nk), 0))


# The wrappers are jitted INLINE: a model calls them once a layer with
# the same shapes, and an inlined jit traces the wrapper and its kernel
# once per shape and hands every layer the same pallas_call equation,
# which then lowers to Mosaic once — 24 layers of gpt2-medium trace and
# lower in 2.1 s instead of 4.9 (sandbox, PR 25), seconds of every
# process's set-up on the chip's host. Inlined, the jit adds no scope
# to the name stack, so a kernel keeps its caller's name in the trace.
@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "window", "q_offset", "k_offset", "block_q", "block_k",
    "interpret"))
def _flash_forward(q, k, v, *, causal, window, q_offset, k_offset,
                   block_q, block_k, interpret):
    """[B, S, H, D] flash attention forward via pallas_call.

    Returns `(out [B, Sq, H, D], lse [B, H, Sqp/bq, 1, bq] f32)` —
    the row logsumexp rides along for the fused Pallas backward as
    the kernels keep it: one [1, bq] row a q-block (head-major, padded
    to the block grid, -inf on fully-masked rows)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    group = _gqa_group(q, k, v)
    t = _pick_tiles(Sq, Sk, D, q.dtype.itemsize, group, block_q,
                    block_k)
    qt = _head_major(q, t.Sqp)
    kt = _head_major(k, t.Skp)
    vt = _head_major(v, t.Skp)

    # Sliding window: the sweep — the in-kernel loop, or the streamed
    # grid axis — covers only the k-blocks that can intersect each
    # q-block's band; out-of-band K/V is never read.
    common = dict(causal=causal, window=window, q_offset=q_offset,
                  k_offset=k_offset, kv_len=Sk, block_q=t.bq,
                  block_k=t.bk, nk=t.nk)
    k_spec = _kv_spec(t, group, D,
                      functools.partial(_k_sweep, **common))
    q_spec = pl.BlockSpec((1, 1, t.bq, D), lambda b, h, i, s: (b, h, i, 0))
    out, lse = pl.pallas_call(
        _kernel(_flash_kernel, interpret, scale=D ** -0.5,
                fold=_fold_scale(D, q.dtype),
                streamed=not t.kv_resident, **common),
        grid=(B, H, t.nq, t.sweep_steps(window)[0]),
        in_specs=[q_spec, k_spec, k_spec],
        out_specs=[q_spec, _row_spec(t.bq)],
        out_shape=[
            _sds((B, H, t.Sqp, D), q.dtype, qt, kt, vt),
            _sds((B, H, t.nq, 1, t.bq), jnp.float32, qt, kt, vt),
        ],
        scratch_shapes=[
            _scratch((D, t.bq), jnp.float32),
            _scratch((1, t.bq), jnp.float32),
            _scratch((1, t.bq), jnp.float32),
        ],
        compiler_params=(None if interpret
                         else _compiler_params(t.vmem_fwd)),
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :, :Sq, :]
    # lse stays in the kernels' layout so the fused backward can DMA
    # it straight back in; public surfaces flatten the rows.
    return jnp.transpose(out, (0, 2, 1, 3)), lse


def _scratch(shape, dtype):
    return _VMEM(shape, dtype)


def _gqa_group(q, k, v):
    """q heads per kv head (GQA, Ainslie et al. 2023) — the kernels
    index-map K/V head `h // group`, so grouped K/V is consumed
    NATIVELY, never materialized at full head count in HBM."""
    H, Hkv = q.shape[2], k.shape[2]
    if v.shape[2] != Hkv:
        raise ValueError(
            f"k and v head counts differ: {Hkv} vs {v.shape[2]}")
    if H % Hkv:
        raise ValueError(
            f"query heads ({H}) must be a multiple of kv heads "
            f"({Hkv}) for grouped-query attention")
    return H // Hkv


def _sds(shape, dtype, *like):
    """ShapeDtypeStruct whose varying-manual-axes are the union of the
    `like` operands' — lets the pallas_calls sit inside `shard_map`
    with its default `check_vma=True` (ring/Ulysses SP pass this
    kernel as `attn_impl`)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _group_q_map(t: _Tiles, sweep):
    """Index map of the head group's Q (dO, lse, dvec) on dK/dV's grid
    (b, hkv, j, step), in blocks of the group's heads: the whole
    sequence when resident, else the ``step``-th q-block of k-block
    ``j``'s band."""
    if t.q_resident:
        return lambda b, hkv, j, s: (b, hkv, 0, 0)
    return lambda b, hkv, j, s: (b, hkv, _streamed_block(
        j, s, sweep=sweep, n=t.nq), 0)


def _flash_bwd_dq_kernel(pos, q_ref, do_ref, lse_ref, dvec_ref, k_ref,
                         v_ref, dq_ref, dq_acc, *,
                         scale, fold, causal, window, q_offset,
                         k_offset, kv_len, block_q, block_k, nk,
                         streamed):
    """dQ: the forward's grid and sweep (`_flash_kernel`), rebuilding
    each probability block `p = exp(scale·q·kᵀ − lse)` exactly as the
    forward computed it (same operands, same mask, -inf lse rows → 0):
    dq = scale · Σ_j (p ∘ (dO·Vᵀ − dvec)) · K."""
    qi = pos[0]
    bounds, base, first, last = _swept(
        pos, functools.partial(
            _k_sweep, causal=causal, window=window, q_offset=q_offset,
            k_offset=k_offset, kv_len=kv_len, block_q=block_q,
            block_k=block_k, nk=nk), streamed)
    q_start = q_offset + qi * block_q

    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
    _when(first, _init)

    q = q_ref[0, 0]                                          # [bq, D]
    if fold:
        q = _scaled(q, scale)
    dob = do_ref[0, 0]
    lse = _column(lse_ref[0, 0, 0])                          # [bq, 1]
    dvec = _column(dvec_ref[0, 0, 0])

    def body(masked):
        def _block(j, carry):
            off = pl.multiple_of((j - base) * block_k, block_k)
            k = k_ref[0, 0, pl.ds(off, block_k), :]          # [bk, D]
            v = v_ref[0, 0, pl.ds(off, block_k), :]
            s = jax.lax.dot_general(
                q, k, _NT, preferred_element_type=jnp.float32)
            if not fold:
                s = s * scale
            if masked:
                mask = _mask_block(
                    q_start, k_offset + j * block_k, causal=causal,
                    window=window, kv_len=kv_len, k_local0=j * block_k,
                    block_q=block_q, block_k=block_k)
                p = jnp.where(
                    jnp.logical_and(mask, jnp.isfinite(lse)),
                    jnp.exp(s - lse), 0.0)
            else:
                # a row that sees a whole block has a finite lse
                p = jnp.exp(s - lse)                         # [bq, bk]
            dp = jax.lax.dot_general(
                dob, v, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - dvec)
            dq_acc[...] += jax.lax.dot_general(
                ds.astype(k.dtype), k, _NN,
                preferred_element_type=jnp.float32)          # [bq, D]
            return carry
        return _block

    _sweep_loops(bounds, body,
                 masked_head=causal and window is not None,
                 masked_tail=causal or kv_len % block_k != 0)

    def _fin():
        # dq = scale · Σ_j ds·k (ds was taken w.r.t. scale·q·kᵀ).
        dq_ref[0, 0, :, :] = (dq_acc[...] * scale).astype(dq_ref.dtype)
    _when(last, _fin)


def _flash_bwd_dkv_kernel(pos, q_ref, do_ref, lse_ref, dvec_ref, k_ref,
                          v_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                          scale, fold, causal, window, q_offset,
                          k_offset, block_q, block_k, group, nq,
                          streamed):
    """dK/dV: grid (B, Hkv, k-block, sweep step). The k-block sweeps,
    for every query head of its GQA group, the q sub-blocks FROM the
    diagonal (`_q_sweep`) of the Q/dO/lse/dvec block in VMEM — the
    whole group's sequence when resident, one q-block of it a step
    when streamed — so the accumulators fold the group in VMEM
    scratch and each dK/dV block is written to HBM exactly once AT KV
    WIDTH (no full-H gradient materialization + reduce pass).

    Everything is computed transposed — Sᵀ = K·Qᵀ, Pᵀ, dPᵀ = V·dOᵀ —
    with lse and dvec as [1, block_q] rows, so dV += Pᵀ·dO and
    dK += dSᵀ·Q are plain matmuls and nothing crosses the XLU."""
    kj = pos[0]
    bounds, base, first, last = _swept(
        pos, functools.partial(
            _q_sweep, causal=causal, window=window, q_offset=q_offset,
            k_offset=k_offset, block_q=block_q, block_k=block_k,
            nq=nq), streamed)
    k_start = k_offset + kj * block_k

    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
    _when(first, _init)

    k = k_ref[0, 0]                                          # [bk, D]
    if fold:
        k = _scaled(k, scale)
    v = v_ref[0, 0]

    def body(g, masked):
        def _block(i, carry):
            sub = i - base
            off = pl.multiple_of(sub * block_q, block_q)
            qb = q_ref[0, g, pl.ds(off, block_q), :]         # [bq, D]
            dob = do_ref[0, g, pl.ds(off, block_q), :]
            lse = lse_ref[0, g, sub]                         # [1, bq]
            dvec = dvec_ref[0, g, sub]
            st = jax.lax.dot_general(
                k, qb, _NT, preferred_element_type=jnp.float32)
            if not fold:
                st = st * scale                              # [bk, bq]
            if masked:
                # (zero-padded keys need no mask here: they only reach
                # their own dK/dV rows, which the wrapper drops)
                mask = _mask_block(
                    q_offset + i * block_q, k_start, causal=causal,
                    window=window, kv_len=0, k_local0=None,
                    block_q=block_q, block_k=block_k, transposed=True)
                pt = jnp.where(
                    jnp.logical_and(mask, jnp.isfinite(lse)),
                    jnp.exp(st - lse), 0.0)
            else:
                pt = jnp.exp(st - lse)
            dv_acc[...] += jax.lax.dot_general(
                pt.astype(dob.dtype), dob, _NN,
                preferred_element_type=jnp.float32)          # [bk, D]
            dpt = jax.lax.dot_general(
                v, dob, _NT, preferred_element_type=jnp.float32)
            dst = pt * (dpt - dvec)
            dk_acc[...] += jax.lax.dot_general(
                dst.astype(qb.dtype), qb, _NN,
                preferred_element_type=jnp.float32)          # [bk, D]
            return carry
        return _block

    def _group_member(g, carry):
        _sweep_loops(bounds, functools.partial(body, g),
                     masked_head=causal,
                     masked_tail=causal and window is not None)
        return carry
    if group == 1:
        _group_member(0, 0)
    else:
        jax.lax.fori_loop(0, group, _group_member, 0)

    def _fin():
        # s = scale·q·kᵀ, so dk = scale · dsᵀ·q: a folded scale sits on
        # k, not on the q that dsᵀ multiplies.
        dk_ref[0, 0, :, :] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[...].astype(dv_ref.dtype)
    _when(last, _fin)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "window", "q_offset", "k_offset", "block_q", "block_k",
    "interpret"))
def _flash_backward(q, k, v, o, lse, g, *, causal, window, q_offset,
                    k_offset, block_q, block_k, interpret, dlse=None):
    """Fused Pallas backward (FlashAttention-2 style): recompute each
    probability tile from Q/K and the saved row logsumexp, never
    materializing [Sq, Sk] — two kernels on the forward's tiles
    (`_pick_tiles`): dQ with the forward's grid and k sweep, dK/dV
    owning k-blocks and sweeping q from the diagonal, each output
    written once.

    vs the XLA recompute VJP it replaces on this path: no per-block
    scan residuals in HBM and no [B,Sq,H,D]-carry rewrite per k-block
    — the HBM traffic drops to the tensors themselves, which is what
    makes the fwd+bwd step time land near the ~2.5x-of-forward ideal.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    group = _gqa_group(q, k, v)
    Hkv = H // group
    # Same tiles as the forward (v5-lite divisibility).
    t = _pick_tiles(Sq, Sk, D, q.dtype.itemsize, group, block_q,
                    block_k)
    qt = _head_major(q, t.Sqp)
    ot = _head_major(o, t.Sqp)
    gt = _head_major(g, t.Sqp)
    kt = _head_major(k, t.Skp)
    vt = _head_major(v, t.Skp)
    # D_i = Σ_d dO_id · O_id (rowwise) — the softmax-jacobian term;
    # cheap elementwise+reduce, XLA fuses it into the transposes.
    # When the row logsumexp is itself an output with a cotangent
    # (`flash_attention_lse`, e.g. under a ring merge):
    # ∂lse_i/∂s_ij = p_ij, so ds = p·(dp − (D − dlse)) — the same
    # kernels run with dvec = D − dlse.
    dvec = (gt.astype(jnp.float32) * ot.astype(jnp.float32)).sum(-1)
    if dlse is not None:
        dvec = dvec - dlse.astype(jnp.float32)
    # Like lse from the forward, dvec is one [1, block_q] row a
    # q-block: what dK/dV, which works transposed, reads as it is, and
    # dQ turns into a column once a grid step.
    dvec = dvec.reshape(B, H, t.nq, 1, t.bq)

    # Sliding window: both sweeps shrink to the band, mirroring the
    # forward — out-of-band blocks are never read.
    dq_steps, dkv_steps = t.sweep_steps(window)
    common = dict(causal=causal, window=window, q_offset=q_offset,
                  k_offset=k_offset, block_q=t.bq, block_k=t.bk)
    scale_kw = dict(scale=D ** -0.5, fold=_fold_scale(D, q.dtype))
    k_sweep = dict(kv_len=Sk, nk=t.nk, **common)
    q_spec = pl.BlockSpec((1, 1, t.bq, D), lambda b, h, i, s: (b, h, i, 0))
    r_spec = _row_spec(t.bq)
    k_spec = _kv_spec(t, group, D,
                      functools.partial(_k_sweep, **k_sweep))

    dq = pl.pallas_call(
        _kernel(_flash_bwd_dq_kernel, interpret,
                streamed=not t.kv_resident, **scale_kw, **k_sweep),
        grid=(B, H, t.nq, dq_steps),
        in_specs=[q_spec, q_spec, r_spec, r_spec, k_spec, k_spec],
        out_specs=q_spec,
        out_shape=_sds((B, H, t.Sqp, D), q.dtype, qt, gt, kt, vt),
        scratch_shapes=[_scratch((t.bq, D), jnp.float32)],
        compiler_params=(None if interpret
                         else _compiler_params(t.vmem_dq)),
        interpret=interpret,
    )(qt, gt, lse, dvec, kt, vt)

    # Grid over KV heads; the in-kernel sweep folds the whole
    # query-head group into the VMEM accumulators, so dK/dV are
    # written once, at kv width — no full-H gradient + reduce pass.
    q_sweep = dict(nq=t.nq, **common)
    swept_map = _group_q_map(t, functools.partial(_q_sweep, **q_sweep))
    kq_spec = pl.BlockSpec((1, group, t.q_rows, D), swept_map)
    kr_spec = pl.BlockSpec(
        (1, group, t.q_rows // t.bq, 1, t.bq),
        lambda b, hkv, j, s: (*swept_map(b, hkv, j, s), 0))
    kk_spec = pl.BlockSpec((1, 1, t.bk, D),
                           lambda b, hkv, j, s: (b, hkv, j, 0))
    dk, dv = pl.pallas_call(
        _kernel(_flash_bwd_dkv_kernel, interpret, group=group,
                streamed=not t.q_resident, **scale_kw, **q_sweep),
        grid=(B, Hkv, t.nk, dkv_steps),
        in_specs=[kq_spec, kq_spec, kr_spec, kr_spec, kk_spec, kk_spec],
        out_specs=[kk_spec, kk_spec],
        out_shape=[
            _sds((B, Hkv, t.Skp, D), k.dtype, qt, gt, kt, vt),
            _sds((B, Hkv, t.Skp, D), v.dtype, qt, gt, kt, vt),
        ],
        scratch_shapes=[_scratch((t.bk, D), jnp.float32),
                        _scratch((t.bk, D), jnp.float32)],
        compiler_params=(None if interpret
                         else _compiler_params(t.vmem_dkv)),
        interpret=interpret,
    )(qt, gt, lse, dvec, kt, vt)

    dq = jnp.transpose(dq[:, :, :Sq], (0, 2, 1, 3))
    dk = jnp.transpose(dk[:, :, :Sk], (0, 2, 1, 3))
    dv = jnp.transpose(dv[:, :, :Sk], (0, 2, 1, 3))
    return dq, dk, dv


def _opt_int(x):
    return None if x is None else int(x)


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.lru_cache(maxsize=None)
def _make_flash(causal, window, q_offset, k_offset, block_q, block_k,
                interpret, bwd_impl="pallas"):
    """Config-specialized flash fn with a fused or recompute VJP.

    ``bwd_impl="pallas"`` (the default): the FlashAttention-2 style
    fused backward (`_flash_backward`) — probability tiles rebuilt
    from the saved row logsumexp in two Pallas kernels, O(S) residual
    memory (q, k, v, o, lse), no XLA scan-residual traffic; banded
    sweeps under a sliding window.

    ``bwd_impl="recompute"``: differentiate the blockwise
    online-softmax scan (`sequence.blockwise_attention`, the same
    math) — the conservative fallback (HOROVOD_FLASH_BWD=recompute).
    With a sliding window the recompute backward is BANDED like the
    forward (`_banded_bwd`): Q is scanned in `block_q` chunks and
    each chunk's VJP sees only the `block_q + window - 1` keys its
    band can touch, so SWA training moves O(S·(window+block))
    bytes/FLOPs end to end, not O(S²).
    """
    from horovod_tpu.parallel.sequence import blockwise_attention

    # The XLA recompute fallback scans in its own blocks: the caller's
    # where given, else the 128 it always used (the shape-chosen tiles
    # are the Pallas kernels').
    scan_q, scan_k = block_q or 128, block_k or 128

    def ref(q, k, v):
        # GQA: repeat kv INSIDE the vjp'd fn — jnp.repeat's transpose
        # is the per-group sum, so dk/dv come back at kv-head width.
        g_ = q.shape[2] // k.shape[2]
        if g_ > 1:
            k = jnp.repeat(k, g_, axis=2)
            v = jnp.repeat(v, g_, axis=2)
        return blockwise_attention(
            q, k, v, block_size=scan_k, causal=causal, window=window,
            q_offset=q_offset, k_offset=k_offset)

    def _banded_bwd(q, k, v, g):
        B, Sq, H, D = q.shape
        Sk = k.shape[1]
        C = min(scan_q, Sq)
        span = C + window - 1          # keys one q-chunk's band touches
        nc = -(-Sq // C)
        pad_q = nc * C - Sq
        if pad_q:
            # Padded q rows sit past the real sequence; their cotangent
            # rows are zero, so every gradient contribution they make
            # vanishes (dq row-local; dk/dv weighted by g rows).
            q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
            g = jnp.pad(g, ((0, 0), (0, pad_q), (0, 0), (0, 0)))

        def body(carry, ci):
            dq_a, dk_a, dv_a = carry
            qc = jax.lax.dynamic_slice_in_dim(q, ci * C, C, axis=1)
            gc = jax.lax.dynamic_slice_in_dim(g, ci * C, C, axis=1)
            # First key the chunk's band can touch, clamped so the
            # static-size slice stays in range; the k_offset handed to
            # the ref keeps masking exact under the clamp (keys pulled
            # into the slice but outside the band are masked out).
            lo = q_offset + ci * C - (window - 1) - k_offset
            start = jnp.clip(lo, 0, Sk - span)
            kc = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            g_ = qc.shape[2] // kc.shape[2]

            def fn(qc, kc, vc, _start=start, _g=g_):
                if _g > 1:  # GQA (see `ref`)
                    kc = jnp.repeat(kc, _g, axis=2)
                    vc = jnp.repeat(vc, _g, axis=2)
                return blockwise_attention(
                    qc, kc, vc, block_size=scan_k, causal=True,
                    window=window, q_offset=q_offset + ci * C,
                    k_offset=k_offset + _start)
            _, vjp = jax.vjp(fn, qc, kc, vc)
            dqc, dkc, dvc = vjp(gc)
            dq_a = jax.lax.dynamic_update_slice_in_dim(
                dq_a, dqc.astype(jnp.float32), ci * C, axis=1)
            # Adjacent bands overlap by window-1 keys: read-add-write.
            dk_a = jax.lax.dynamic_update_slice_in_dim(
                dk_a, jax.lax.dynamic_slice_in_dim(dk_a, start, span, 1)
                + dkc.astype(jnp.float32), start, axis=1)
            dv_a = jax.lax.dynamic_update_slice_in_dim(
                dv_a, jax.lax.dynamic_slice_in_dim(dv_a, start, span, 1)
                + dvc.astype(jnp.float32), start, axis=1)
            return (dq_a, dk_a, dv_a), None

        z = (jnp.zeros(q.shape, jnp.float32),
             jnp.zeros(k.shape, jnp.float32),
             jnp.zeros(v.shape, jnp.float32))
        (dq, dk, dv), _ = jax.lax.scan(body, z, jnp.arange(nc))
        return (dq[:, :Sq].astype(q.dtype), dk.astype(k.dtype),
                dv.astype(v.dtype))

    def _fwd_full(q, k, v):
        return _flash_forward(
            q, k, v, causal=causal, window=window,
            q_offset=q_offset, k_offset=k_offset,
            block_q=block_q, block_k=block_k, interpret=interpret)

    @jax.custom_vjp
    def flash(q, k, v):
        return _fwd_full(q, k, v)[0]

    if bwd_impl == "pallas":
        def fwd(q, k, v):
            out, lse = _fwd_full(q, k, v)
            return out, (q, k, v, out, lse)

        def bwd(res, g):
            q, k, v, o, lse = res
            return _flash_backward(
                q, k, v, o, lse, g, causal=causal, window=window,
                q_offset=q_offset, k_offset=k_offset,
                block_q=block_q, block_k=block_k, interpret=interpret)
    else:
        def fwd(q, k, v):
            return flash(q, k, v), (q, k, v)

        def bwd(res, g):
            q, k, v = res
            # Band the backward only when it shrinks the key span.
            if (causal and window is not None
                    and min(scan_q, q.shape[1]) + window - 1
                    < k.shape[1]):
                return _banded_bwd(q, k, v, g)
            _, vjp = jax.vjp(ref, q, k, v)
            return vjp(g)

    flash.defvjp(fwd, bwd)
    return flash


def _public_lse(lse, Sq):
    """[B, H, Sq] from the forward's kernel-layout logsumexp."""
    return lse.reshape(*lse.shape[:2], -1)[:, :, :Sq]


@functools.lru_cache(maxsize=None)
def _make_flash_lse(causal, window, q_offset, k_offset, block_q,
                    block_k, interpret):
    """`(o, lse)`-returning flash with a fused VJP that honors a
    cotangent on lse (∂lse/∂s = p folds into the dvec term) — the
    primitive for cross-block softmax merging (ring attention)."""

    @jax.custom_vjp
    def flash_lse(q, k, v):
        o, lse = _flash_forward(
            q, k, v, causal=causal, window=window,
            q_offset=q_offset, k_offset=k_offset,
            block_q=block_q, block_k=block_k, interpret=interpret)
        return o, _public_lse(lse, q.shape[1])

    def fwd(q, k, v):
        o, lse = _flash_forward(
            q, k, v, causal=causal, window=window,
            q_offset=q_offset, k_offset=k_offset,
            block_q=block_q, block_k=block_k, interpret=interpret)
        return (o, _public_lse(lse, q.shape[1])), (q, k, v, o, lse)

    def bwd(res, cot):
        q, k, v, o, lse = res
        g, dlse = cot
        pad = lse.shape[2] * lse.shape[4] - q.shape[1]
        if pad:
            dlse = jnp.pad(dlse, ((0, 0), (0, 0), (0, pad)))
        return _flash_backward(
            q, k, v, o, lse, g, causal=causal, window=window,
            q_offset=q_offset, k_offset=k_offset,
            block_q=block_q, block_k=block_k, interpret=interpret,
            dlse=dlse)

    flash_lse.defvjp(fwd, bwd)
    return flash_lse


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                        *, causal: bool = False,
                        window: Optional[int] = None,
                        q_offset: int = 0, k_offset: int = 0,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """Flash attention that ALSO returns the row logsumexp.

    Returns `(out [B, Sq, H, D], lse [B, H, Sq] float32)`; lse is -inf
    on fully-masked rows (their out rows are 0). Two partial
    attentions over disjoint key sets merge exactly via
    `m = max(lse1, lse2); w_i = exp(lse_i - m);
    out = Σ w_i·out_i / Σ w_i; lse = m + log Σ w_i` — how
    `parallel.sequence.ring_attention(block_impl="flash")` runs the
    Pallas kernel on every ring rotation. Differentiable in all of
    (out, lse); GQA-native like `flash_attention`.

    Fused-backward-only: the HOROVOD_FLASH_BWD=recompute escape hatch
    applies to `flash_attention`, not this entry point (the blockwise
    fallback has no lse output to differentiate through) — if the
    fused backward misbehaves, use `ring_attention(block_impl="xla")`
    instead."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    from horovod_tpu.parallel.sequence import check_window
    check_window(window)
    if interpret is None:
        interpret = _auto_interpret()
    fn = _make_flash_lse(bool(causal),
                         None if window is None else int(window),
                         int(q_offset), int(k_offset),
                         _opt_int(block_q), _opt_int(block_k),
                         bool(interpret))
    return fn(q, k, v)


flash_attention_lse.native_gqa = True


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask=None, *, causal: bool = False,
                    window: Optional[int] = None,
                    q_offset: int = 0, k_offset: int = 0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    bwd_impl: str = "auto") -> jax.Array:
    """Fused flash attention, [B, S, H, D] → [B, S, H, D].

    Args:
      q, k, v: [batch, seq, heads, head_dim], any float dtype. The
        matmuls take their operands in that dtype (bf16 inputs ride the
        MXU as bf16; `p` and `ds` are cast to it before theirs) and
        accumulate in float32; max, exp, sum, lse and the accumulators
        are float32; the output matches `q.dtype`. `head_dim` a multiple
        of 128 keeps the MXU fully tiled; smaller values work but fill
        half its contraction depth (D 64: a fifth of the roofline).
      mask: unsupported here (only `causal=`); pass explicit masks to
        `parallel.tensor.dot_product_attention`. Accepted positionally as
        None so the fn is drop-in for `ParallelSelfAttention.attn_fn`.
      causal: apply a causal mask using global positions
        `q_offset + i >= k_offset + j` (offsets support ring-attention
        style rotated blocks).
      window: sliding-window attention (last `window` positions only;
        requires causal; >= 1). Banded end to end: every sweep —
        the in-kernel loop over resident K/V, or the innermost grid
        axis where K/V stream — covers only the blocks intersecting
        the band (streamed out-of-band K/V is never read from HBM),
        and the recompute BACKWARD scans q in `block_q` chunks whose
        VJPs see only each band's `block_q + window - 1` keys — so an
        SWA training step does O(S·(window+block)) FLOPs, not O(S²).
      block_q, block_k: the q and k tiles. None (the default): chosen
        from the shape by `_pick_tiles` — 512 rows where the sequence
        is long enough (on one v5e chip 512 x 512 measured fastest at
        S 1024 and 2048, D 64 and 128; PR 25), the whole padded axis
        where it is shorter, a smaller tile where that pads a ragged
        length less. An integer is honoured (snapped to a
        hardware-legal size). Either way K/V (for dK/dV: the head
        group's Q and dO) stay resident in VMEM where they fit and
        stream where not; `flash_tile_check` returns the plan.
      interpret: run the kernel in interpreter mode (None = auto: True
        off-TPU, so the same tests run on the CPU mesh).
      bwd_impl: "auto" (default — the fused Pallas backward
        `_flash_backward`, banded under a sliding window), "pallas",
        or "recompute" (the blockwise-VJP fallback). The env var
        HOROVOD_FLASH_BWD overrides "auto" (escape hatch if the fused
        backward misbehaves on some toolchain).
    """
    if mask is not None:
        raise NotImplementedError(
            "flash_attention supports causal masking only; use "
            "dot_product_attention for arbitrary masks")
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    from horovod_tpu.parallel.sequence import check_window
    check_window(window)
    if interpret is None:
        interpret = _auto_interpret()
    if bwd_impl not in ("auto", "pallas", "recompute"):
        raise ValueError(
            f"bwd_impl must be auto|pallas|recompute, got {bwd_impl!r}")
    if bwd_impl == "auto":
        from horovod_tpu.runtime.config import env_raw
        env = env_raw("HOROVOD_FLASH_BWD")
        if env is not None and env not in ("pallas", "recompute"):
            # The escape hatch must never silently select the kernel
            # being escaped (e.g. a typo'd "recompue").
            raise ValueError(
                f"HOROVOD_FLASH_BWD must be pallas|recompute, "
                f"got {env!r}")
        # Default: fused Pallas backward everywhere — banded under a
        # sliding window, mirroring the forward grid.
        bwd_impl = env or "pallas"
    fn = _make_flash(bool(causal),
                     None if window is None else int(window),
                     int(q_offset), int(k_offset),
                     _opt_int(block_q), _opt_int(block_k),
                     bool(interpret),
                     bwd_impl)
    return fn(q, k, v)


# K/V may carry fewer heads than Q (must divide): the kernels index-map
# kv head h//group instead of reading a materialized repeat
# (`parallel.tensor.ParallelSelfAttention` checks this marker).
flash_attention.native_gqa = True


# ---------------------------------------------------------------------------
# Flash-decode: one S = 1 step of attention against the linear KV
# cache, every lane to its own length.
# ---------------------------------------------------------------------------

# Bytes of one K (or V) block a grid step streams, where the cache
# length allows: large enough that a step's ~0.35 us of grid overhead
# is small beside its DMA (PR 25's lesson), small enough that a lane's
# last, partly filled block does not cost more than a short context
# reads in all.
DECODE_BLOCK_BYTES = 512 * 2 ** 10
_DECODE_BLOCKS = (2048, 1024, 512, 256, 128)
_DECODE_M0 = -1e30      # the running maximum before any key


@dataclasses.dataclass(frozen=True)
class DecodePlan:
    """Which way an S = 1 decode step's attention goes at one shape,
    and why: the trace-time record of `decode_attention_plan` (the
    decode twin of `FlashPlan`) — the rule the model obeys, what the
    engine logs at warm-up and carries in ``metrics_snapshot()``."""
    path: str           # "kernel" | "lax" ("paged": the paged pool)
    why: str
    block_k: Optional[int] = None   # kernel path only, as below
    grid: Optional[tuple] = None    # (lanes, k-blocks a lane)
    vmem_bytes: Optional[int] = None
    vmem_limit_bytes: Optional[int] = None
    # how the step's new K/V row reaches the cache: "kernel" (the
    # in-place `flash_cache_append`, kernel path only) | "xla"
    # (`ParallelSelfAttention._cache_write`), and why
    write: str = "xla"
    write_why: str = "only the kernel path appends in place"
    # KV heads stored to one 128-lane cache row (`kv_pack`): block,
    # grid, VMEM and the append tile are planned at the STORED shape
    # [W, Hkv // pack, D * pack]
    pack: int = 1

    def describe(self) -> str:
        write = f"write {self.write} ({self.write_why})"
        if self.path != "kernel":
            return f"{self.path} ({self.why}); {write}"
        return (f"kernel ({self.why}): block_k {self.block_k}, grid "
                f"{self.grid}, VMEM {self.vmem_bytes / 2 ** 20:.1f} MiB"
                f"; {write}")


def kv_pack(Hkv: int, D: int) -> int:
    """KV heads a softmax layer's cache stores to one row: 128 // D of
    them side by side (heads ``pack * h .. pack * h + pack - 1`` in
    row h) where a head is narrower than the chip's 128 lanes and
    whole rows come out, else 1. A function of the shape alone - it
    decides the stored leaf (`pack_kv_rows`), not only the kernel's
    operand: a leaf whose rows are not lane-whole the TPU compiler
    stores position-minor, and the ragged kernel and the in-place
    append, which read whole rows where they lie, would each pay a
    relayout copy of the whole leaf (`parallel.latent_attention`
    records the same of a 576-wide latent row). The same bytes in the
    same order: packing and unpacking are reshapes."""
    pack = 128 // D if D < 128 and 128 % D == 0 else 1
    return pack if Hkv % pack == 0 else 1


def pack_kv_rows(t: jax.Array, pack: int) -> jax.Array:
    """K or V rows [..., Hkv, D] as the cache stores them,
    [..., Hkv // pack, D * pack] (`kv_pack`)."""
    if pack == 1:
        return t
    return t.reshape(*t.shape[:-2], t.shape[-2] // pack,
                     t.shape[-1] * pack)


def unpack_kv_rows(t: jax.Array, pack: int) -> jax.Array:
    """`pack_kv_rows`' inverse: stored rows as [..., Hkv, D]."""
    if pack == 1:
        return t
    return t.reshape(*t.shape[:-2], t.shape[-2] * pack,
                     t.shape[-1] // pack)


def _decode_block_k(W: int, Hkv: int, D: int, itemsize: int,
                    block_k: Optional[int] = None) -> Optional[int]:
    """Keys a grid step streams: the largest block of `_DECODE_BLOCKS`
    that divides the cache length and keeps a K block inside
    `DECODE_BLOCK_BYTES` (at least 128 keys), the whole cache where it
    is shorter than that, None where nothing divides it. ``block_k``
    given: that block, capped at W."""
    if block_k:
        bk = min(int(block_k), W)
        return bk if W % bk == 0 else None
    fits = [b for b in _DECODE_BLOCKS if W % b == 0]
    if not fits:
        return W if W < _DECODE_BLOCKS[-1] else None
    row = Hkv * D * itemsize
    return next((b for b in fits if b * row <= DECODE_BLOCK_BYTES),
                fits[-1])


def _append_rows(W: int, Hkv: int, itemsize: int) -> Optional[int]:
    """Rows of the cache seen as [W * Hkv, D] that `flash_cache_append`
    reads, changes and stores for one lane: the smallest run of whole
    positions that is whole sublane tiles too (a tile is 8 rows of 32
    bits: 16 of bf16). None where such runs do not divide the cache."""
    rows = math.lcm(8 * max(4 // itemsize, 1), Hkv)
    return None if (W * Hkv) % rows else rows


def _no_append_tile(W: int, Hkv: int) -> str:
    return (f"no sublane tile of whole positions divides a cache of "
            f"{W} x {Hkv} rows")


def _decode_vmem(bk: int, H: int, Hkv: int, D: int, itemsize: int,
                 latent: Optional[int] = None):
    """VMEM the decode kernel's plan sums to: K and V blocks double
    buffered (a latent cache has the one), q and the output, the
    float32 accumulator and softmax state, and a step's score tile
    (scores, mask, probabilities and their cast)."""
    Dp = -(-D // 128) * 128
    rows = -(-H // 8) * 8
    return ((1 if latent else 2) * 2 * bk * Hkv * Dp * itemsize
            + 2 * 2 * rows * Dp * itemsize
            + rows * (Dp + 2 * 128) * 4
            + 4 * rows * bk * Hkv * 4)


def decode_attention_plan(lanes: int, W: int, H: int, Hkv: int, D: int,
                          *, itemsize: int = 2, S: int = 1,
                          impl: Optional[str] = None,
                          quantized: bool = False,
                          trivial_mesh: bool = True,
                          on_tpu: Optional[bool] = None,
                          block_k: Optional[int] = None,
                          ring: bool = False,
                          latent: Optional[int] = None) -> DecodePlan:
    """THE rule for decode attention against the linear cache: the
    ragged kernel (`flash_decode_attention`) for an S = 1 step on an
    un-quantized cache with no serving mesh, at a shape Mosaic takes,
    on a TPU; the lax walk (`ParallelSelfAttention._prefix_attention`)
    for everything else. ``impl`` "lax" / "pallas" force a path (the
    oracle, and the kernel in interpret mode off the chip); a forced
    kernel still needs what the kernel cannot do without.

    ``Hkv`` and ``D`` are the layer's TRUE KV heads and head width. A
    head narrower than 128 lanes whose heads fill whole rows is
    stored `kv_pack` heads to a row, and block, grid, VMEM and the
    append's tile are planned at that stored shape [W, Hkv // pack,
    D * pack] (``DecodePlan.pack``; Granite's 8 heads of 64: 4 rows
    of 128, block 512). A width that is no multiple of 128 and does
    not pack (96, 80, three heads of 64) keeps the walk on a TPU:
    Mosaic pads or refuses its rows.

    ``ring``: the cache is a sliding-window layer's rolling buffer of
    W slots (slot = position mod W). The same kernel takes it - the
    ring's valid slots are its first min(position + 1, W), and rows
    rotated at their own positions need no order - at the ring's own
    one or two key blocks; "lax" there is the dense
    [ring ++ block] branch, not a walk.

    ``latent`` = Dv: the cache is a latent-attention layer's
    (`parallel.latent_attention`) - ONE leaf of rows D wide without a
    head axis (``Hkv`` is 1), whose first Dv columns are the values
    too. The same kernel takes it with the one operand: a row is read
    once, for the scores and for the weighted sum. "lax" there is the
    absorbed walk (`latent_attention.latent_walk`)."""
    if impl not in (None, "lax", "pallas"):
        raise ValueError(
            f"decode_prefix_impl must be None|lax|pallas, got {impl!r}")
    if ring:
        plan = decode_attention_plan(
            lanes, W, H, Hkv, D, itemsize=itemsize, S=S, impl=impl,
            quantized=quantized, trivial_mesh=trivial_mesh,
            on_tpu=on_tpu, block_k=block_k, latent=latent)
        return dataclasses.replace(
            plan, why=f"sliding-window ring of {W} slots: {plan.why}")
    if impl == "lax":
        return DecodePlan("lax", "forced")
    if S != 1:
        return DecodePlan("lax", f"S = {S}: a prefill chunk or a "
                          "verify block keeps the walk")
    if quantized:
        return DecodePlan("lax", "int8 KV is dequantized a block at a "
                          "time by the walk")
    if not trivial_mesh:
        return DecodePlan("lax", "a serving mesh: the walk's ops "
                          "partition over heads, a bare kernel does not")
    if H % Hkv:
        return DecodePlan("lax", f"{H} heads over {Hkv} KV heads")
    # the cache as it is stored: `pack` KV heads to a row
    pack = 1 if latent else kv_pack(Hkv, D)
    Hkv, D = Hkv // pack, D * pack
    bk = _decode_block_k(W, Hkv, D, itemsize, block_k)
    if bk is None:
        return DecodePlan("lax", f"no key block divides a cache of {W}")
    if impl is None:
        if on_tpu is None:
            on_tpu = not _auto_interpret()
        if not on_tpu:
            return DecodePlan("lax", "not on a TPU")
        if latent and latent % 128:
            return DecodePlan("lax", f"a latent's value width {latent} "
                              "is not a multiple of 128 lanes")
        if not latent and D % 128:
            fit = 128 // D if 128 % D == 0 else 0
            return DecodePlan(
                "lax", f"head_dim {D} is not a multiple of 128 lanes, "
                + (f"and {Hkv} KV heads do not pack {fit} to a row"
                   if fit else "nor a whole part of them"))
    vmem = _decode_vmem(bk, H, Hkv, D, itemsize, latent)
    rows = _append_rows(W, Hkv, itemsize)
    why = "forced" if impl else "S = 1 on a TPU"
    if latent:
        why += (f", latent rows of {D} read once as keys and as "
                f"{latent}-wide values")
    if pack > 1:
        why += f", {pack} heads a row"
    return DecodePlan(
        "kernel", why,
        block_k=bk, grid=(lanes, W // bk), vmem_bytes=vmem,
        vmem_limit_bytes=vmem if vmem > VMEM_SCOPED_DEFAULT else None,
        write="kernel" if rows else "xla",
        write_why=(f"one aliased call for all lanes, a tile of {rows} "
                   f"rows" if rows else _no_append_tile(W, Hkv)),
        pack=pack)


def _decode_kernel(s_ref, q_ref, k_ref, *rest,
                   scale: float, block_k: int, hkv: int, grp: int,
                   latent: Optional[int] = None):
    """One (lane, k-block) grid cell of the decode step. ``rest`` is
    (v_ref, o_ref, acc_ref, m_ref, l_ref), without the v_ref for a
    ``latent`` cache: its values are the first ``latent`` columns of
    the rows `k_ref` holds, so the block streams in once.

    The cache is consumed IN ITS STORED LAYOUT: the leaf [B, W, Hkv, D]
    seen as [B, W*Hkv, D] — position-major rows with the KV heads
    interleaved, which is how the bytes lie (the reshape is free, a
    head-major transpose would itself read the whole cache). A block
    is therefore ONE dense [bk*Hkv, D] tile, and the step is two plain
    products on the MXU with the cache's own dtype as operands and
    float32 accumulation (what the lax walk's einsums ask for):
    every query head against every row, [H, D] x [D, bk*Hkv], the
    columns of the OTHER KV heads masked like the unfilled tail, then
    [H, bk*Hkv] x [bk*Hkv, D], where the masked columns weigh nothing.
    No head is sliced out of the tile, the group's K/V is never
    repeated and never widened; what it costs is Hkv x the softmax's
    vector work on a score tile that is small at S = 1.

    Scratch persists across a lane's k-block sweep (innermost axis):
      acc_ref [H, D] f32, m_ref/l_ref [H, 128] f32 (lane-replicated).
    Scalar prefetch `s_ref` [B, 2]: per lane the number of VALID
    k-blocks and the filled prefix length. Blocks past a lane's count
    are skipped (and the index_map clamps them onto its last valid
    block, whose re-fetch the pipeline elides) — a lane's HBM traffic
    follows ITS context, not the cache allocation and not the longest
    context in flight.
    """
    v_ref = None if latent else rest[0]
    o_ref, acc_ref, m_ref, l_ref = rest[-4:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    nblk = s_ref[b, 0]
    length = s_ref[b, 1]

    @pl.when(j == 0)
    def _init():
        # finite, so a block with no key kept (length 0) rescales by
        # exp(0) and adds exp(-inf) = 0: never a NaN
        m_ref[...] = jnp.full_like(m_ref, _DECODE_M0)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _block():
        # scaled in q's dtype, as the walk scales it
        q = q_ref[0] * jnp.asarray(scale, q_ref.dtype)      # [H, D]
        kb = k_ref[0]                                  # [bk*Hkv, D]
        vb = kb[:, :latent] if latent else v_ref[0]
        s = jax.lax.dot_general(q.astype(kb.dtype), kb, _NT,
                                preferred_element_type=jnp.float32)
        # column c is key j*bk + c // Hkv of KV head c % Hkv; row r
        # is a query head of KV head r // grp
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = col < (length - j * block_k) * hkv
        if hkv > 1:
            first = jax.lax.rem(col, jnp.int32(hkv)) * grp
            keep &= (row >= first) & (row < first + grp)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[...]                                 # [H, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(vb.dtype), vb, _NN,
            preferred_element_type=jnp.float32)             # [H, D]
        m_ref[...] = m_new

    pl.when(j < nblk)(_block)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret",
                                             "scale", "latent"))
def _flash_decode(q, k_cache, v_cache, lengths, block_k, interpret,
                  scale=None, latent=None):
    """The batched pallas_call: q [B, H, D], caches [B, W, Hkv, D],
    lengths [B] -> [B, H, D]; with ``latent`` = Dv there is no
    `v_cache` (None) and the result is [B, H, Dv]."""
    B, W, Hkv, D = k_cache.shape
    H = q.shape[1]
    plan = decode_attention_plan(
        B, W, H, Hkv, D, itemsize=k_cache.dtype.itemsize,
        impl="pallas", block_k=block_k, latent=latent)
    if plan.path != "kernel":
        raise ValueError(f"flash_decode_attention: {plan.why}")
    bk = plan.block_k
    lengths = jnp.asarray(lengths, jnp.int32)
    # a lane at length 0 (never the engine's: the step's own token is
    # in the cache) still sweeps one block, fully masked
    nblk = jnp.maximum(_fdiv(lengths + (bk - 1), bk), 1)
    scalars = jnp.stack([nblk, lengths], axis=1)        # [B, 2]

    def kv_map(b, j, s):
        return (b, jnp.minimum(j, s[b, 0] - 1), 0)

    caches = [c.reshape(B, W * Hkv, D)
              for c in ((k_cache,) if latent else (k_cache, v_cache))]
    Dv = latent or D
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=plan.grid,
        in_specs=[
            # index_map args: (*grid_indices, *scalar_prefetch_refs)
            pl.BlockSpec((1, H, D), lambda b, j, s: (b, 0, 0)),
            *[pl.BlockSpec((1, bk * Hkv, D), kv_map) for _ in caches],
        ],
        out_specs=pl.BlockSpec((1, H, Dv), lambda b, j, s: (b, 0, 0)),
        scratch_shapes=[
            _scratch((H, Dv), jnp.float32),
            _scratch((H, 128), jnp.float32),
            _scratch((H, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel,
                          scale=D ** -0.5 if scale is None else scale,
                          block_k=bk, hkv=Hkv, grp=H // Hkv,
                          latent=latent),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dv), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=plan.vmem_limit_bytes),
        interpret=interpret,
        name="latent_decode" if latent else None,
    )(scalars, q, *caches)


def _slots_into_lanes(x, batched: bool, axis_size: int):
    """A batch rule's operand [slots, B, ...] (broadcast first where
    it is shared by the slots) as [slots * B, ...]: merging two
    leading axes is free."""
    if not batched:
        x = jnp.broadcast_to(x, (axis_size,) + jnp.shape(x))
    return x.reshape((axis_size * x.shape[1],) + x.shape[2:])


@functools.lru_cache(maxsize=None)
def _make_decode(block_k: Optional[int], interpret: bool,
                 scale: Optional[float] = None,
                 latent: Optional[int] = None):
    """custom_vmap-wrapped entry (the `_make_paged_decode` pattern):
    under the serving tick's `jax.vmap` over slots the batch rule
    fires and the slot axis JOINS the kernel's lane axis — the cache
    leaf [num_slots, 1, W, Hkv, D] is read where it lies (merging two
    leading axes is free), each lane to its own length. The default
    batching of a pallas_call would instead run the lanes one after
    another inside a `while` (its scalar-prefetch operand is
    batched)."""

    @jax.custom_batching.custom_vmap
    def decode(q, *caches_and_lengths):     # K and V, or the latent
        *caches, lengths = caches_and_lengths
        return _flash_decode(q, caches[0], None if latent else caches[1],
                             lengths, block_k, interpret, scale, latent)

    @decode.def_vmap
    def _rule(axis_size, in_batched, *args):
        out = decode(*(_slots_into_lanes(x, b, axis_size)
                       for x, b in zip(args, in_batched)))
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return decode


def flash_decode_attention(q: jax.Array, k_cache: jax.Array,
                           v_cache: Optional[jax.Array],
                           length: jax.Array, *,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           scale: Optional[float] = None,
                           latent: Optional[int] = None
                           ) -> jax.Array:
    """One decode step of attention against the filled cache prefix,
    ragged over the batch.

    q [B, 1, H, D]; k_cache/v_cache [B, W, Hkv, D] (the linear decode
    cache as it is stored - packed rows: below -, already containing
    the current token at position
    ``length - 1``); ``length`` traced int32, a scalar (`generate`:
    every row at the same index) or [B] (each lane its own filled
    prefix). Returns [B, 1, H, D] at q.dtype.

    One fused kernel, a grid cell per (lane, k-block): only a lane's
    own ceil(length/block_k) leading blocks are DMA'd (scalar-
    prefetched per-lane block counts; clamped index_map + pipeline
    elision make the tail free), GQA consumed natively at Hkv width
    with the cache dtype on the MXU, online softmax in f32 VMEM
    scratch. ``block_k`` None: from the shape (`_decode_block_k`).
    `jax.vmap` over a leading slot axis — the serving tick — folds
    that axis into the lanes of the same one call. The lax.fori_loop
    equivalent lives in `ParallelSelfAttention._prefix_attention` (the
    oracle, and what `decode_attention_plan` keeps for everything this
    kernel does not take). bf16/f32 caches only (int8 KV uses the
    walk's per-block dequant).

    ``scale``: the softmax scale where it is not D ** -0.5.
    ``latent`` = Dv: the absorbed step of a latent-attention layer -
    ``k_cache`` [B, W, 1, D] holds the latent rows, ``v_cache`` is
    None, the values are the rows' first Dv columns, and the result
    is [B, 1, H, Dv]: each row is streamed once for both products.

    A cache of PACKED rows (`kv_pack`: [B, W, Hkv // pack, D * pack],
    a head narrower than 128 lanes) is the same kernel on a row of
    ``pack`` heads: each query head is placed in its own KV head's
    part of a row-wide query and zeros in the rest, so its product
    with a stored row is its score against its own head exactly; the
    kernel's head mask keeps the rows of the head's row; and of the
    row-wide output - the sibling heads' values under this head's
    weights beside its own - its own part is taken. The scale stays
    the TRUE head's, q.shape[-1] ** -0.5.
    """
    if interpret is None:
        interpret = _auto_interpret()
    if q.ndim != 4 or q.shape[1] != 1:
        raise ValueError(f"flash_decode_attention wants q [B,1,H,D], "
                         f"got {q.shape}")
    if (v_cache is None) != bool(latent):
        raise ValueError("flash_decode_attention: a latent cache has "
                         "no v_cache, and every other cache has one")
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32),
                               (q.shape[0],))
    q = q[:, 0]
    B, H, D = q.shape
    pack = k_cache.shape[-1] // D
    if pack > 1:
        if scale is None:
            scale = D ** -0.5
        # [H, pack, 1]: the part of a row query head r's KV head
        # r // grp lies in
        kv_head = jnp.arange(H) // (H // (k_cache.shape[-2] * pack))
        own = (kv_head % pack)[:, None, None] == jnp.arange(
            pack)[None, :, None]
        q = jnp.where(own, q[:, :, None], 0).reshape(B, H, pack * D)
    fn = _make_decode(_opt_int(block_k), bool(interpret),
                      None if scale is None else float(scale),
                      _opt_int(latent))
    caches = (k_cache,) if latent else (k_cache, v_cache)
    out = fn(q, *caches, lengths)
    if pack > 1:
        out = jnp.where(own, out.reshape(B, H, pack, D), 0).sum(axis=2)
    return out[:, None]


# ---------------------------------------------------------------------------
# The decode step's cache write: one new K and V row a lane, in place.
# ---------------------------------------------------------------------------

def _append_kernel(pos_ref, *refs, hkv: int):
    """One lane's grid cell: the tile of each cache (K and V, or a
    latent layer's one) that holds position ``pos`` comes in, the
    position's `hkv` rows are taken from the new rows instead, and the
    tile goes back where it came from (the outputs alias the caches).
    The new rows arrive already repeated down the tile, so the choice
    is one select on a row index - no row is moved inside the kernel.
    ``refs``: the caches' tiles, the new rows', the outputs'."""
    n = len(refs) // 3
    rows = refs[0].shape[1]
    first = jax.lax.rem(pos_ref[pl.program_id(0)],
                        jnp.int32(rows // hkv)) * hkv
    row = jax.lax.broadcasted_iota(jnp.int32, refs[0].shape[1:], 0)
    new = (row >= first) & (row < first + hkv)
    for c_ref, n_ref, o_ref in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        o_ref[0] = jnp.where(new, n_ref[0], c_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _flash_append(caches, news, pos, interpret):
    """The batched pallas_call: ``caches`` a tuple of [B, W, Hkv, D]
    (K and V, or the one leaf of a latent layer), ``news`` their new
    rows [B, Hkv, D], pos [B] -> the caches, each aliased to its
    input."""
    B, W, Hkv, D = caches[0].shape
    rows = _append_rows(W, Hkv, caches[0].dtype.itemsize)
    if rows is None:
        raise ValueError(
            f"flash_cache_append: {_no_append_tile(W, Hkv)}")
    per = rows // Hkv       # positions a tile holds
    # where `lax.dynamic_update_slice` would put the row
    pos = jnp.clip(pos, 0, W - 1)

    def tile(b, pos):
        return (b, jax.lax.div(pos[b], jnp.int32(per)), 0)

    def lane(b, pos):
        return (b, 0, 0)

    def down_the_tile(new):         # [B, Hkv, D] -> [B, rows, D]
        return jnp.tile(new, (1, per, 1))

    flat = (B, W * Hkv, D)
    n = len(caches)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=([pl.BlockSpec((1, rows, D), tile)] * n
                  + [pl.BlockSpec((1, rows, D), lane)] * n),
        out_specs=[pl.BlockSpec((1, rows, D), tile)] * n,
    )
    outs = pl.pallas_call(
        functools.partial(_append_kernel, hkv=Hkv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(flat, c.dtype) for c in caches],
        # operand 0 is the scalar-prefetched `pos`
        input_output_aliases={1 + i: i for i in range(n)},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(pos, *(c.reshape(flat) for c in caches),
      *(down_the_tile(x) for x in news))
    return tuple(o.reshape(c.shape) for o, c in zip(outs, caches))


@functools.lru_cache(maxsize=None)
def _make_append(interpret: bool, n: int = 2):
    """custom_vmap-wrapped entry, `_make_decode`'s twin, for ``n``
    caches: under the serving tick's `jax.vmap` over slots the slot
    axis JOINS the lane axis and the cache leaf [num_slots, 1, W, Hkv,
    D] is written where it lies, by one call. (The default batching of
    the call, like that of `lax.dynamic_update_slice` at a batched
    index, runs the lanes one after another inside a `while`.)"""

    @jax.custom_batching.custom_vmap
    def append(*caches_news_pos):
        return _flash_append(caches_news_pos[:n],
                             caches_news_pos[n:2 * n],
                             caches_news_pos[-1], interpret)

    @append.def_vmap
    def _rule(axis_size, in_batched, *args):
        outs = append(*(_slots_into_lanes(x, b, axis_size)
                        for x, b in zip(args, in_batched)))
        return tuple(o.reshape((axis_size, -1) + o.shape[1:])
                     for o in outs), (True,) * n

    return append


def flash_cache_append(k_cache: jax.Array,
                       v_cache: Optional[jax.Array],
                       k_new: jax.Array, v_new: Optional[jax.Array],
                       pos: jax.Array, *,
                       interpret: Optional[bool] = None):
    """Put one decode step's new K and V rows into the caches, in
    place: `lax.dynamic_update_slice` at a position a lane, as one
    call.

    k_cache/v_cache [B, W, Hkv, D] (bf16 or f32; the linear cache or a
    sliding-window layer's ring); k_new/v_new [B, 1, Hkv, D]; ``pos``
    traced int32, a scalar (`generate`) or [B] (each lane its own),
    clamped to [0, W - 1] as `dynamic_update_slice` clamps it, so a
    lane frozen at a full cache writes where it wrote before and never
    outside its slot. Returns ``(k_cache, v_cache)``, each aliased to
    its input: a donated cache is written where it lies, nothing but
    the touched tiles is read or stored.

    One kernel, a grid cell a lane, K and V in the same call: the cell
    reads the sublane-aligned tile of the cache seen as [W * Hkv, D]
    that holds the lane's position (`_append_rows`: 16 rows of bf16, 8
    of f32, more where the KV heads ask), selects the new rows into
    it and stores it - 4 KB a lane and leaf. `jax.vmap` over a leading
    slot axis - the serving tick - folds that axis into the lanes of
    the same one call. `decode_attention_plan(...).write` says where
    the model takes this call ("kernel") and where it keeps
    `ParallelSelfAttention._cache_write` ("xla": every path but the
    ragged kernel's, and a shape no tile divides).

    A latent layer's cache is ONE leaf (`parallel.latent_attention`):
    pass ``v_cache`` and ``v_new`` as None, and the second result is
    None - the same call with one cache in it. A cache of packed rows
    (`kv_pack`: [B, W, Hkv // pack, D * pack]) takes the new rows
    [B, 1, Hkv, D] as they are: packing them is a reshape.
    """
    if interpret is None:
        interpret = _auto_interpret()
    if k_new.ndim != 4 or k_new.shape[1] != 1:
        raise ValueError(f"flash_cache_append wants new rows "
                         f"[B,1,Hkv,D], got {k_new.shape}")
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                           (k_cache.shape[0],))
    pack = k_cache.shape[-1] // k_new.shape[-1]
    k_new = pack_kv_rows(k_new[:, 0], pack)
    if v_cache is None:
        return (*_make_append(bool(interpret), 1)(
            k_cache, k_new, pos), None)
    return _make_append(bool(interpret))(
        k_cache, v_cache, k_new, pack_kv_rows(v_new[:, 0], pack), pos)
