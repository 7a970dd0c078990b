"""Pallas TPU flash-attention kernel.

The hot op of the flagship transformer, written for the hardware: one
fused kernel per (batch, head, q-block) that streams K/V blocks through
VMEM with online-softmax accumulation in float32 scratch — the [Sq, Sk]
score matrix never touches HBM, Q·Kᵀ and P·V ride the MXU, and the
rescale/exp traffic stays on the VPU.

No reference equivalent: Horovod v0.10 contains no attention at all
(SURVEY §5.7); this is part of the TPU-native long-context extension.
The backward is fused Pallas too (FlashAttention-2 style, the
default): the forward saves only the row logsumexp, and two kernels
rebuild each probability tile on the fly for dK/dV and dQ — O(S)
residual memory, no scan-residual HBM traffic; under a sliding window
both backward sweeps are banded like the forward grid. The same math
in plain-XLA form lives in
`horovod_tpu.parallel.sequence.blockwise_attention`, the correctness
oracle for both directions and the recompute-VJP fallback
(HOROVOD_FLASH_BWD=recompute; banded for sliding-window training).

Layout is the framework-wide [batch, seq, heads, head_dim]; the kernel
internally works head-major. `ulysses_attention(attn_impl=
flash_attention)` composes this with sequence parallelism: all_to_all to
head-sharded layout, flash kernel locally, all_to_all back.

Grid iteration order puts the K/V-block dimension innermost (sequential
on TPU), so the float32 accumulators live in VMEM scratch across the
whole K sweep and results are written to HBM exactly once per q-block.
Fully-masked causal blocks are skipped (compute guarded by `pl.when`,
~2x step speedup for long causal sequences).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))

NEG_INF = float("-inf")

# The row-logsumexp rides between the fwd and bwd kernels lane-
# replicated to a full 128-lane trailing dim: real Mosaic requires the
# last two block dims to be (8k, 128m) or equal to the array dims, so a
# rank-3 [B, H, S] lse with (1, 1, bq) blocks is UNLOWERABLE on
# hardware (it only ever worked in interpret mode); and after (8, 128)
# tile padding a narrower trailing dim would occupy the same HBM
# anyway. Kernel-internal only — the public API still returns [B,H,S].
LSE_LANES = 128


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


def flash_tile_check(Sq: int, Sk: int, H: int, Hkv: int, D: int, *,
                     block_q: int = 128, block_k: int = 128):
    """Every (name, block shape, array shape, legal) the fwd + bwd
    pallas_calls will use at these shapes after tile snapping — the
    static half of the v5e regression test: a config is
    hardware-lowerable iff every entry's ``legal`` bit is True, and
    that is checkable on CPU (interpret mode would happily run
    illegal tiles, which is exactly how the r04 failure shipped)."""
    bq = _snap_tile(block_q, Sq)
    bk = _snap_tile(block_k, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    B = 1   # batch rides a leading grid dim, never a constrained one
    entries = [
        ("fwd.q", (1, 1, bq, D), (B, H, nq * bq, D)),
        ("fwd.kv", (1, 1, bk, D), (B, Hkv, nk * bk, D)),
        ("fwd.out", (1, 1, bq, D), (B, H, nq * bq, D)),
        ("fwd.lse", (1, 1, bq, LSE_LANES), (B, H, nq * bq, LSE_LANES)),
        ("bwd.dq.q", (1, 1, bq, D), (B, H, nq * bq, D)),
        ("bwd.dq.lse", (1, 1, bq, LSE_LANES),
         (B, H, nq * bq, LSE_LANES)),
        ("bwd.dq.kv", (1, 1, bk, D), (B, Hkv, nk * bk, D)),
        ("bwd.dkv.q", (1, 1, bq, D), (B, H, nq * bq, D)),
        ("bwd.dkv.out", (1, 1, bk, D), (B, Hkv, nk * bk, D)),
    ]
    return [(name, blk, arr, mosaic_block_ok(blk, arr))
            for name, blk, arr in entries]


def _band_j0(qi, *, window, q_offset, k_offset, block_q, block_k):
    """First k-block index that can intersect q-block ``qi``'s band —
    the banded grid's offset (shared by index_map and kernel so the
    DMA'd block and the in-kernel positions cannot disagree)."""
    lo = (q_offset + qi * block_q - (window - 1) - k_offset) // block_k
    return jnp.maximum(0, lo)


def _band_i0(j, *, q_offset, k_offset, block_q, block_k):
    """First q-block index whose rows can see k-block ``j`` under the
    causal band (q >= k) — the dK/dV banded grid's offset."""
    lo = (k_offset + j * block_k - q_offset) // block_q
    return jnp.maximum(0, lo)


def _mask_block(q_start, k_start, *, causal, window, kv_len, k_local0,
                block_q, block_k):
    """The fwd/bwd-shared mask for one [block_q, block_k] tile, or None.

    `q_start`/`k_start` are GLOBAL positions (offset-aware, the
    `banded_causal_mask` band rule); `k_local0` is the block's LOCAL
    key index origin for the zero-pad tail test.
    """
    mask = None
    if causal:
        rows = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = rows >= cols
        if window is not None:
            mask = jnp.logical_and(mask, rows - cols < window)
    if kv_len % block_k:
        local = k_local0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        pad_ok = local < kv_len
        mask = pad_ok if mask is None else jnp.logical_and(mask, pad_ok)
    return mask


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                  acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: "int | None",
                  banded: bool, nk_total: int,
                  q_offset: int, k_offset: int,
                  kv_len: int, block_q: int, block_k: int):
    """One (batch, head, q-block, k-block) grid cell.

    ``banded``: the innermost grid axis runs over only the k-blocks
    that can intersect the sliding-window band of this q-block
    (index_map adds `_band_j0`); out-of-range logical blocks (clamped
    duplicates at the sequence end) are skipped by the validity guard.

    Scratch (persistent across the innermost k-block sweep):
      acc_ref [block_q, D] f32 — unnormalized output accumulator
      m_ref   [block_q, 128] f32 — running row max (lane-replicated)
      l_ref   [block_q, 128] f32 — running softmax denominator
    """
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Global positions of this block's rows/cols (for causal + pad masks).
    q_start = q_offset + qi * block_q
    if banded:
        jl = _band_j0(qi, window=window, q_offset=q_offset,
                      k_offset=k_offset, block_q=block_q,
                      block_k=block_k) + ki
        jc = jnp.minimum(jl, nk_total - 1)   # what the index_map DMA'd
        in_range = jl <= nk_total - 1
    else:
        jl = jc = ki
        in_range = True
    k_start = k_offset + jc * block_k

    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bq, bk]

        mask = _mask_block(q_start, k_start, causal=causal,
                           window=window, kv_len=kv_len,
                           k_local0=jc * block_k,
                           block_q=block_q, block_k=block_k)
        if mask is not None:
            logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]                                  # [bq, 128]
        l_prev = l_ref[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)      # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)                   # [bq, 128]
        # Rows with every key masked so far keep m == -inf; shift by 0
        # there so exp(-inf - 0) = 0 instead of exp(-inf - -inf) = NaN.
        shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(logits - shift[:, :1])                   # [bq, bk]
        corr = jnp.where(m_prev == NEG_INF, 0.0,
                         jnp.exp(m_prev - shift))            # [bq, 128]
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                  # [bk, D]
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bq, D]
        acc_ref[...] = acc_ref[...] * corr[:, :1] + pv
        m_ref[...] = m_new

    # Skip blocks entirely outside the causal band (future keys, or —
    # with a window — keys entirely in the past) and clamped
    # duplicates past the banded grid's end. Non-causal keeps a traced
    # trivially-true guard ("block intersects real keys"): an
    # UNGUARDED body trips a varying-manual-axes mismatch inside the
    # pallas interpreter under shard_map(check_vma=True).
    rel = _relevant_block(q_start, k_start, causal=causal,
                          window=window, block_q=block_q,
                          block_k=block_k)
    if rel is None:
        rel = jnp.asarray(jc) * block_k < kv_len
    if banded:
        rel = jnp.logical_and(rel, in_range)
    pl.when(rel)(_block)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_ref[...] / denom).astype(o_ref.dtype)
        # Row logsumexp for the fused backward: L = m + log(l), -inf on
        # fully-masked rows (the bwd kernels turn those into p = 0).
        m = m_ref[...][:, :1]
        lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(denom))
        lse_ref[0, 0, :, :] = jnp.broadcast_to(
            lse, (lse.shape[0], LSE_LANES))


def _flash_forward(q, k, v, *, causal, window, q_offset, k_offset,
                   block_q, block_k, interpret):
    """[B, S, H, D] flash attention forward via pallas_call.

    Returns `(out [B, Sq, H, D], lse [B, H, nq*bq, LSE_LANES] f32)` —
    the row logsumexp rides along for the fused Pallas backward
    (head-major, lane-replicated, padded to the block grid; -inf on
    fully-masked rows)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    group = _gqa_group(q, k, v)
    # Snapped tiles: multi-block tiles must be 8-aligned for real
    # Mosaic (last two block dims multiples of (8, 128) or equal to
    # the array dims) — see `_snap_tile` / `flash_tile_check`.
    bq = _snap_tile(block_q, Sq)
    bk = _snap_tile(block_k, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)

    # Head-major layout for the kernel; XLA fuses the transposes.
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if nq * bq != Sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, nq * bq - Sq), (0, 0)))
    if nk * bk != Sk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, nk * bk - Sk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, nk * bk - Sk), (0, 0)))

    # Sliding window: shrink the innermost grid to the k-blocks that
    # can intersect each q-block's band — out-of-band K/V blocks are
    # never DMA'd at all, so a long-context SWA step moves
    # O(S·(window+block)) bytes instead of O(S²).
    banded = causal and window is not None
    if banded:
        span = bq + window - 1                 # key span of one q-block
        nkb = min(nk, -(-span // bk) + 1)

        def k_map(b, h, i, j):
            j0 = _band_j0(i, window=window, q_offset=q_offset,
                          k_offset=k_offset, block_q=bq, block_k=bk)
            return (b, h // group, jnp.minimum(j0 + j, nk - 1), 0)
    else:
        nkb = nk

        def k_map(b, h, i, j):
            return (b, h // group, j, 0)

    kernel = functools.partial(
        _flash_kernel, scale=D ** -0.5, causal=causal, window=window,
        banded=banded, nk_total=nk,
        q_offset=q_offset, k_offset=k_offset, kv_len=Sk,
        block_q=bq, block_k=bk)

    grid = (B, H, nq, nkb)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), k_map),
            pl.BlockSpec((1, 1, bk, D), k_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, LSE_LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            _sds((B, H, nq * bq, D), q.dtype, qt, kt, vt),
            _sds((B, H, nq * bq, LSE_LANES), jnp.float32, qt, kt, vt),
        ],
        scratch_shapes=[
            _scratch((bq, D), jnp.float32),
            _scratch((bq, 128), jnp.float32),
            _scratch((bq, 128), jnp.float32),
        ],
        compiler_params=None if interpret else _compiler_params(),
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :, :Sq, :]
    # lse stays rank-4 (lane-replicated) so a fused backward can DMA it
    # straight back in without a 128x re-broadcast; public surfaces
    # slice `[..., 0]`.
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


def _recompute_p(q_ref, k_ref, lse_ref, *, scale, causal, window,
                 kv_len, q_start, k_start, k_local0, block_q, block_k):
    """Shared bwd-kernel tile: rebuild the probability block
    `p = exp(scale·q·kᵀ − lse)` exactly as the forward computed it
    (same f32 dot, same mask, -inf lse rows → 0)."""
    qs = q_ref[0, 0].astype(jnp.float32) * scale           # [bq, D]
    kb = k_ref[0, 0].astype(jnp.float32)                   # [bk, D]
    s = jax.lax.dot_general(qs, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    mask = _mask_block(q_start, k_start, causal=causal, window=window,
                       kv_len=kv_len, k_local0=k_local0,
                       block_q=block_q, block_k=block_k)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    lse = lse_ref[0, 0, :, :1]                             # [bq, 1]
    p = jnp.where(jnp.isfinite(lse),
                  jnp.exp(s - lse), 0.0)                   # [bq, bk]
    return qs, kb, p


def _relevant_block(q_start, k_start, *, causal, window, block_q,
                    block_k):
    """Causal/window block-skip predicate shared by the forward and
    both backward kernels (~2x for long causal sequences); None when
    nothing can be skipped."""
    if not causal:
        return None
    rel = k_start <= q_start + block_q - 1
    if window is not None:
        rel = jnp.logical_and(
            rel, k_start + block_k - 1 >= q_start - window + 1)
    return rel


def _flash_bwd_dkv_kernel(q_ref, do_ref, lse_ref, dvec_ref, k_ref,
                          v_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                          scale, causal, window, banded, nq_total,
                          nq_band, q_offset, k_offset,
                          kv_len, block_q, block_k):
    """dK/dV: grid (B, Hkv, k-block, group·q-block) — the innermost
    sequential sweep runs every (gqa-group, q-block) pair, so the
    accumulators fold the whole query-head group in VMEM scratch and
    each dK/dV block is written to HBM exactly once AT KV WIDTH (with
    GQA there is no full-H gradient materialization + reduce pass).

    ``banded``: the q sweep covers only the blocks whose rows can see
    this k-block under the sliding-window band (index_map adds
    `_band_i0`; clamped duplicates skipped by the validity guard)."""
    j = pl.program_id(2)
    inner = pl.program_id(3)
    nin = pl.num_programs(3)
    qi = inner % nq_band       # q-block within this query head
    # (inner // nq_band = the group member; only index maps need it)

    @pl.when(inner == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if banded:
        il = _band_i0(j, q_offset=q_offset, k_offset=k_offset,
                      block_q=block_q, block_k=block_k) + qi
        ic = jnp.minimum(il, nq_total - 1)   # what the index_map DMA'd
        in_range = il <= nq_total - 1
    else:
        ic = qi
        in_range = True
    q_start = q_offset + ic * block_q
    k_start = k_offset + j * block_k

    def _block():
        qs, kb, p = _recompute_p(
            q_ref, k_ref, lse_ref, scale=scale, causal=causal,
            window=window, kv_len=kv_len, q_start=q_start,
            k_start=k_start, k_local0=j * block_k,
            block_q=block_q, block_k=block_k)
        dob = do_ref[0, 0].astype(jnp.float32)             # [bq, D]
        dv_acc[...] += jax.lax.dot_general(
            p, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, D]
        vb = v_ref[0, 0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        ds = p * (dp - dvec_ref[0, 0, :, :1])
        # s = (scale·q)·kᵀ, so dk = dsᵀ·(scale·q) — qs carries scale.
        dk_acc[...] += jax.lax.dot_general(
            ds, qs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, D]

    rel = _relevant_block(q_start, k_start, causal=causal, window=window,
                        block_q=block_q, block_k=block_k)
    if rel is None:  # traced guard; see _flash_kernel
        rel = jnp.asarray(j) * block_k < kv_len
    if banded:
        rel = jnp.logical_and(rel, in_range)
    pl.when(rel)(_block)

    @pl.when(inner == nin - 1)
    def _fin():
        dk_ref[0, 0, :, :] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, do_ref, lse_ref, dvec_ref, k_ref,
                         v_ref, dq_ref, dq_acc, *,
                         scale, causal, window, banded, nk_total,
                         q_offset, k_offset,
                         kv_len, block_q, block_k):
    """dQ: grid (B, H, q-block, k-block) with the k sweep innermost.

    ``banded``: same banded k sweep as the forward (`_band_j0`)."""
    qi = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    if banded:
        jl = _band_j0(qi, window=window, q_offset=q_offset,
                      k_offset=k_offset, block_q=block_q,
                      block_k=block_k) + j
        jc = jnp.minimum(jl, nk_total - 1)
        in_range = jl <= nk_total - 1
    else:
        jc = j
        in_range = True
    q_start = q_offset + qi * block_q
    k_start = k_offset + jc * block_k

    def _block():
        qs, kb, p = _recompute_p(
            q_ref, k_ref, lse_ref, scale=scale, causal=causal,
            window=window, kv_len=kv_len, q_start=q_start,
            k_start=k_start, k_local0=jc * block_k,
            block_q=block_q, block_k=block_k)
        dob = do_ref[0, 0].astype(jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        ds = p * (dp - dvec_ref[0, 0, :, :1])
        dq_acc[...] += jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, D]

    rel = _relevant_block(q_start, k_start, causal=causal, window=window,
                        block_q=block_q, block_k=block_k)
    if rel is None:  # traced guard; see _flash_kernel
        rel = jnp.asarray(jc) * block_k < kv_len
    if banded:
        rel = jnp.logical_and(rel, in_range)
    pl.when(rel)(_block)

    @pl.when(j == nk - 1)
    def _fin():
        # dq = scale · Σ_j ds·k (ds was taken w.r.t. scale·q·kᵀ).
        dq_ref[0, 0, :, :] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, *, causal, window, q_offset,
                    k_offset, block_q, block_k, interpret, dlse=None):
    """Fused Pallas backward (FlashAttention-2 style): recompute each
    probability tile from Q/K and the saved row logsumexp, never
    materializing [Sq, Sk] — two kernels (dK/dV with q innermost, dQ
    with k innermost), each output written once.

    vs the XLA recompute VJP it replaces on this path: no per-block
    scan residuals in HBM and no [B,Sq,H,D]-carry rewrite per k-block
    — the HBM traffic drops to the tensors themselves, which is what
    makes the fwd+bwd step time land near the ~2.5x-of-forward ideal.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    # Same snapped tiles as the forward (v5-lite divisibility).
    bq = _snap_tile(block_q, Sq)
    bk = _snap_tile(block_k, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)

    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    ot = jnp.transpose(o, (0, 2, 1, 3))
    gt = jnp.transpose(g, (0, 2, 1, 3))
    if nq * bq != Sq:
        pad = ((0, 0), (0, 0), (0, nq * bq - Sq), (0, 0))
        qt, ot, gt = jnp.pad(qt, pad), jnp.pad(ot, pad), jnp.pad(gt, pad)
    if nk * bk != Sk:
        pad = ((0, 0), (0, 0), (0, nk * bk - Sk), (0, 0))
        kt, vt = jnp.pad(kt, pad), jnp.pad(vt, pad)
    # D_i = Σ_d dO_id · O_id (rowwise) — the softmax-jacobian term;
    # cheap elementwise+reduce, XLA fuses it into the transposes.
    # When the row logsumexp is itself an output with a cotangent
    # (`flash_attention_lse`, e.g. under a ring merge):
    # ∂lse_i/∂s_ij = p_ij, so ds = p·(dp − (D − dlse)) — the same
    # kernels run with dvec = D − dlse.
    dvec = (gt.astype(jnp.float32) * ot.astype(jnp.float32)).sum(-1)
    if dlse is not None:
        dvec = dvec - dlse.astype(jnp.float32)
    # dvec is born rank-3 here; lane-replicate it for Mosaic (see
    # LSE_LANES). lse arrives already rank-4 from the forward.
    dvec = jnp.broadcast_to(dvec[..., None], (*dvec.shape, LSE_LANES))

    # Sliding window: both sweeps shrink to the band, mirroring the
    # forward grid — out-of-band blocks are never DMA'd.
    banded = causal and window is not None
    group = _gqa_group(q, k, v)
    if banded:
        nkb = min(nk, -(-(bq + window - 1) // bk) + 1)
        nqb = min(nq, -(-(bk + window - 1) // bq) + 1)

        def dq_k_map(b, h, i, j):
            j0 = _band_j0(i, window=window, q_offset=q_offset,
                          k_offset=k_offset, block_q=bq, block_k=bk)
            return (b, h // group, jnp.minimum(j0 + j, nk - 1), 0)

        def dkv_q_map(b, hkv, j, inner):
            i0 = _band_i0(j, q_offset=q_offset, k_offset=k_offset,
                          block_q=bq, block_k=bk)
            i = jnp.minimum(i0 + inner % nqb, nq - 1)
            return (b, hkv * group + inner // nqb, i, 0)
    else:
        nkb, nqb = nk, nq

        def dq_k_map(b, h, i, j):
            return (b, h // group, j, 0)

        def dkv_q_map(b, hkv, j, inner):
            return (b, hkv * group + inner // nqb, inner % nqb, 0)

    common = dict(scale=D ** -0.5, causal=causal, window=window,
                  banded=banded, q_offset=q_offset, k_offset=k_offset,
                  kv_len=Sk, block_q=bq, block_k=bk)
    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    r_spec = pl.BlockSpec((1, 1, bq, LSE_LANES),
                          lambda b, h, i, j: (b, h, i, 0))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, nk_total=nk, **common),
        grid=(B, H, nq, nkb),
        in_specs=[
            q_spec, q_spec, r_spec, r_spec,
            pl.BlockSpec((1, 1, bk, D), dq_k_map),
            pl.BlockSpec((1, 1, bk, D), dq_k_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=_sds((B, H, nq * bq, D), q.dtype, qt, gt, kt, vt),
        scratch_shapes=[_scratch((bq, D), jnp.float32)],
        compiler_params=None if interpret else _compiler_params(),
        interpret=interpret,
    )(qt, gt, lse, dvec, kt, vt)

    kq_spec = pl.BlockSpec((1, 1, bq, D), dkv_q_map)
    kr_spec = pl.BlockSpec((1, 1, bq, LSE_LANES), dkv_q_map)
    kk_spec = pl.BlockSpec((1, 1, bk, D),
                           lambda b, hkv, j, inner: (b, hkv, j, 0))
    Hkv = H // group
    # Grid over KV heads; the inner sequential sweep folds the whole
    # query-head group into the VMEM accumulators, so dK/dV are
    # written once, at kv width — no full-H gradient + reduce pass.
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, nq_total=nq,
                          nq_band=nqb, **common),
        grid=(B, Hkv, nk, group * nqb),
        in_specs=[kq_spec, kq_spec, kr_spec, kr_spec,
                  kk_spec, kk_spec],
        out_specs=[kk_spec, kk_spec],
        out_shape=[
            _sds((B, Hkv, nk * bk, D), k.dtype, qt, gt, kt, vt),
            _sds((B, Hkv, nk * bk, D), v.dtype, qt, gt, kt, vt),
        ],
        scratch_shapes=[_scratch((bk, D), jnp.float32),
                        _scratch((bk, D), jnp.float32)],
        compiler_params=None if interpret else _compiler_params(),
        interpret=interpret,
    )(qt, gt, lse, dvec, kt, vt)

    dq = jnp.transpose(dq[:, :, :Sq], (0, 2, 1, 3))
    dk = jnp.transpose(dk[:, :, :Sk], (0, 2, 1, 3))
    dv = jnp.transpose(dv[:, :, :Sk], (0, 2, 1, 3))
    return dq, dk, dv


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

    def ref(q, k, v):
        # GQA: repeat kv INSIDE the vjp'd fn — jnp.repeat's transpose
        # is the per-group sum, so dk/dv come back at kv-head width.
        g_ = q.shape[2] // k.shape[2]
        if g_ > 1:
            k = jnp.repeat(k, g_, axis=2)
            v = jnp.repeat(v, g_, axis=2)
        return blockwise_attention(
            q, k, v, block_size=block_k, causal=causal, window=window,
            q_offset=q_offset, k_offset=k_offset)

    def _banded_bwd(q, k, v, g):
        B, Sq, H, D = q.shape
        Sk = k.shape[1]
        C = min(block_q, Sq)
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
                    qc, kc, vc, block_size=block_k, causal=True,
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
                    and min(block_q, q.shape[1]) + window - 1
                    < k.shape[1]):
                return _banded_bwd(q, k, v, g)
            _, vjp = jax.vjp(ref, q, k, v)
            return vjp(g)

    flash.defvjp(fwd, bwd)
    return flash


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
        return o, lse[:, :, :q.shape[1], 0]

    def fwd(q, k, v):
        o, lse = _flash_forward(
            q, k, v, causal=causal, window=window,
            q_offset=q_offset, k_offset=k_offset,
            block_q=block_q, block_k=block_k, interpret=interpret)
        return (o, lse[:, :, :q.shape[1], 0]), (q, k, v, o, lse)

    def bwd(res, cot):
        q, k, v, o, lse = res
        g, dlse = cot
        pad = lse.shape[2] - q.shape[1]
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
                        block_q: int = 128, block_k: int = 128,
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
                         int(block_q), int(block_k), bool(interpret))
    return fn(q, k, v)


flash_attention_lse.native_gqa = True


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask=None, *, causal: bool = False,
                    window: Optional[int] = None,
                    q_offset: int = 0, k_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None,
                    bwd_impl: str = "auto") -> jax.Array:
    """Fused flash attention, [B, S, H, D] → [B, S, H, D].

    Args:
      q, k, v: [batch, seq, heads, head_dim] (any float dtype; compute is
        float32, output matches `q.dtype`). `head_dim` a multiple of 128
        keeps the MXU fully tiled; smaller values work but underfill lanes.
      mask: unsupported here (only `causal=`); pass explicit masks to
        `parallel.tensor.dot_product_attention`. Accepted positionally as
        None so the fn is drop-in for `ParallelSelfAttention.attn_fn`.
      causal: apply a causal mask using global positions
        `q_offset + i >= k_offset + j` (offsets support ring-attention
        style rotated blocks).
      window: sliding-window attention (last `window` positions only;
        requires causal; >= 1). Banded end to end: the FORWARD's
        innermost grid axis covers only the k-blocks intersecting each
        q-block's band (out-of-band K/V never read from HBM), and the
        recompute BACKWARD scans q in `block_q` chunks whose VJPs see
        only each band's `block_q + window - 1` keys — so an SWA
        training step moves O(S·(window+block)) bytes and FLOPs, not
        O(S²).
      block_q, block_k: VMEM tile sizes (128 matches the MXU; raise
        block_k to 256/512 when head_dim is small).
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
                     int(block_q), int(block_k), bool(interpret),
                     bwd_impl)
    return fn(q, k, v)


# K/V may carry fewer heads than Q (must divide): the kernels index-map
# kv head h//group instead of reading a materialized repeat
# (`parallel.tensor.ParallelSelfAttention` checks this marker).
flash_attention.native_gqa = True


# ---------------------------------------------------------------------------
# Flash-decode: single-tick attention against the KV cache.
# ---------------------------------------------------------------------------

def _decode_kernel(s_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, block_k: int, hkv: int, grp: int):
    """One (batch, k-block) grid cell of the decode tick.

    The cache is consumed IN ITS STORED LAYOUT [B, W, Hkv, D] — a
    head-major transpose would itself read the whole cache, the exact
    traffic this kernel exists to avoid. Per kv-head 2D dots (grp q
    rows each) + one concatenated online-softmax update over the full
    [H, block] score matrix keep every op a plain Mosaic-lowerable
    2D primitive (the r4 lesson: interpret mode accepts shapes real
    Mosaic rejects — stick to [8k, 128m]-safe blocks).

    Scratch persists across the k-block sweep (innermost axis):
      acc_ref [H, D] f32, m_ref/l_ref [H, 128] f32 (lane-replicated).
    Scalar prefetch `s_ref`: [0] = number of VALID k-blocks for this
    tick, [1] = filled prefix length. Blocks past s_ref[0] are skipped
    (and the index_map clamps them onto the last valid block, whose
    re-fetch the pipeline elides) — per-tick HBM traffic follows the
    generated length, not the cache allocation.
    """
    j = pl.program_id(1)
    nblk = s_ref[0]
    length = s_ref[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _block():
        q = q_ref[0].astype(jnp.float32) * scale        # [H, D]
        kb = k_ref[0]                                   # [bk, Hkv, D]
        vb = v_ref[0]
        parts = []
        for h in range(hkv):
            qh = q[h * grp:(h + 1) * grp, :]
            kh = kb[:, h, :].astype(jnp.float32)        # [bk, D]
            parts.append(jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))    # [grp, bk]
        logits = parts[0] if hkv == 1 else jnp.concatenate(parts, 0)
        pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        logits = jnp.where(pos < length, logits, NEG_INF)

        m_prev = m_ref[...]                             # [H, 128]
        l_prev = l_ref[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(logits - shift[:, :1])              # [H, bk]
        corr = jnp.where(m_prev == NEG_INF, 0.0,
                         jnp.exp(m_prev - shift))
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv_parts = []
        for h in range(hkv):
            ph = p[h * grp:(h + 1) * grp, :]
            vh = vb[:, h, :].astype(jnp.float32)
            pv_parts.append(jax.lax.dot_general(
                ph, vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))    # [grp, D]
        pv = pv_parts[0] if hkv == 1 else jnp.concatenate(pv_parts, 0)
        acc_ref[...] = acc_ref[...] * corr[:, :1] + pv
        m_ref[...] = m_new

    pl.when(j < nblk)(_block)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_decode_attention(q: jax.Array, k_cache: jax.Array,
                           v_cache: jax.Array, length: jax.Array, *,
                           block_k: int = 512,
                           interpret: Optional[bool] = None
                           ) -> jax.Array:
    """One decode tick of attention against the filled cache prefix.

    q [B, 1, H, D]; k_cache/v_cache [B, W, Hkv, D] (the linear decode
    cache, already containing the current token at position
    ``length - 1``); ``length`` traced int32 — the filled prefix
    length. Returns [B, 1, H, D] at q.dtype.

    One fused kernel per (batch, k-block): only the
    ceil(length/block_k) leading cache blocks are DMA'd (scalar-
    prefetched block count; clamped index_map + pipeline elision make
    the tail free), GQA consumed natively at Hkv width, online softmax
    in f32 VMEM scratch. The lax.fori_loop equivalent lives in
    `ParallelSelfAttention._prefix_attention` (`decode_prefix_impl=
    "lax"`, the default + oracle); this kernel removes that loop's
    per-iteration overhead. bf16/f32 caches only (int8 KV uses the lax
    path's per-block dequant).
    """
    if interpret is None:
        interpret = _auto_interpret()
    B, W, Hkv, D = k_cache.shape
    if q.ndim != 4 or q.shape[1] != 1:
        raise ValueError(f"flash_decode_attention wants q [B,1,H,D], "
                         f"got {q.shape}")
    H = q.shape[2]
    if H % Hkv:
        raise ValueError(f"H={H} not divisible by Hkv={Hkv}")
    grp = H // Hkv
    bk = min(block_k, W)
    if W % bk:
        raise ValueError(
            f"block_k={bk} must divide cache length {W}")
    nk = W // bk
    length = jnp.asarray(length, jnp.int32)
    scalars = jnp.stack([(length + bk - 1) // bk, length])

    q3 = q[:, 0]                                        # [B, H, D]
    kernel = functools.partial(_decode_kernel, scale=D ** -0.5,
                               block_k=bk, hkv=Hkv, grp=grp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=[
            # index_map args: (*grid_indices, *scalar_prefetch_refs) —
            # the scalar ref comes LAST (jax pallas TPU convention).
            pl.BlockSpec((1, H, D), lambda b, j, s: (b, 0, 0)),
            pl.BlockSpec((1, bk, Hkv, D),
                         lambda b, j, s: (b, jnp.minimum(j, s[0] - 1),
                                          0, 0)),
            pl.BlockSpec((1, bk, Hkv, D),
                         lambda b, j, s: (b, jnp.minimum(j, s[0] - 1),
                                          0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, s: (b, 0, 0)),
        scratch_shapes=[
            _scratch((H, D), jnp.float32),
            _scratch((H, 128), jnp.float32),
            _scratch((H, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(scalars, q3, k_cache, v_cache)
    return out[:, None]
