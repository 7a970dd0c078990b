"""Paged-attention: decode attention that walks only FILLED KV blocks.

The paged serving pool (`serving.paging`, PR 7) made paged decode
bitwise-equal to the fixed slot pool by GATHERING each lane's whole
block table back into a linear [max_len] view every tick
(`models.transformer._paged_view`) — correct, but the gather touches
every allocated block whether or not the sequence ever filled it, so
per-tick HBM traffic followed the table's span where the fixed pool's
follows the fill. This module deletes that tax: attention reads the pool
THROUGH the block table, touching only the blocks the lane actually
filled, in two interchangeable forms:

* **`paged_prefix_attention`** (``impl="lax"``, the default and the
  oracle): a `lax.fori_loop` walk over ``walk_block``-token spans —
  each step takes exactly the table entries covering its span (a
  bounded gather of ``walk_block/block_size`` blocks, never the full
  table) and applies the SAME online-softmax update, in the same
  order, with the same masking constants, as
  `ParallelSelfAttention._prefix_attention` runs on the gathered
  view. Same values + same float-op order ⇒ the walk is BITWISE the
  legacy gather path (pinned by tests/test_paged_attention.py), so it
  can be the default without perturbing a single pinned token stream.
  Composes with GQA (``groups``), int8 KV (scale pools, per-block
  dequant via the one tested codec), S >= 1 (prefill chunks and the
  spec-decode verify block ride the same walk), and vmaps over the
  lane axis natively.
* **`paged_decode_attention`** (``impl="pallas"``): the fused Pallas
  kernel for the S=1 decode tick — one (lane, block) grid, the block
  table and per-lane filled-block counts scalar-prefetched so the
  index map DMAs pool blocks directly (skipped blocks clamp onto the
  last valid one, whose re-fetch the pipeline elides — the
  `flash_decode_attention` trick applied through a block table), the
  current token's K/V merged in-kernel at its block offset, online
  softmax in f32 VMEM scratch. Accumulation granularity is one pool
  block, so its bitwise oracle is the lax walk at
  ``walk_block == block_size`` (pinned in interpret mode on CPU CI —
  the same fallback that lets this file's kernels run under CPU
  tests). Batched over lanes via `jax.custom_batching.custom_vmap`
  (the pools must NOT carry the lane axis — one physical pool serves
  every lane), mirroring the r4 Mosaic lesson: every in-kernel op is
  a plain 2D primitive with [8k, 128m]-safe or array-equal blocks.

Dispatch policy lives with the caller
(`parallel.tensor.ParallelSelfAttention`): "pallas" engages only for
S=1, un-quantized caches, and a trivial mesh (a bare pallas_call is
opaque to GSPMD), falling back to the lax walk otherwise — the same
gating `decode_prefix_impl="pallas"` already uses for the linear
cache.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from horovod_tpu.annotations import hot_path
from horovod_tpu.ops.flash_attention import (
    _auto_interpret, _scratch, pltpu,
)

__all__ = ["paged_prefix_attention", "paged_decode_attention"]


def _read_span(pool, table, start, nblocks, block_size):
    """One ``nblocks * block_size``-row span of a lane's logical cache,
    read THROUGH the block table: take the covering table entries (a
    bounded gather — the operand is ``nblocks`` blocks, never the full
    table span) and lay the rows out exactly as `_paged_view` would
    ([1, span, ...] — same bytes, same order), so every downstream op
    sees values identical to the legacy gathered view's."""
    bids = lax.dynamic_slice_in_dim(table, start // block_size,
                                    nblocks)
    blk = jnp.take(pool, bids, axis=0)          # [r, 1, bs, ...]
    blk = jnp.moveaxis(blk, 1, 0)               # [1, r, bs, ...]
    return blk.reshape((1, nblocks * block_size) + blk.shape[3:])


@hot_path
def paged_prefix_attention(q, k_new, v_new, k_pool, v_pool, table,
                           fill, *, walk_block: int, groups: int = 1,
                           k_scale_pool=None, v_scale_pool=None,
                           compute_dtype=None):
    """Attention of ``q`` (positions ``fill .. fill+S-1``) against a
    paged cache, walking only the filled blocks of ``table``.

    q [1, S, H, D]; k_new/v_new [1, S, Hkv, D] — the CURRENT call's
    K/V rows (already rotated, already through the KV codec: exactly
    the bytes a gather-path view would hold at those positions),
    merged into their walked blocks so the accumulation order matches
    the gather path block for block. k_pool/v_pool
    [num_blocks, 1, block_size, Hkv, D] (the serving pool leaf
    layout); ``table`` [T] int32; ``fill`` traced int32. With int8 KV,
    the pools are int8 and ``k_scale_pool``/``v_scale_pool``
    [num_blocks, 1, block_size, Hkv] carry the per-(position, head)
    scales — dequantized per span via the one tested codec, exactly
    as `_cache_read_block` does on the view.

    ``walk_block`` is the accumulation granularity (must be a
    multiple of ``block_size``): at the model's ``decode_prefix_block``
    the walk is BITWISE `_prefix_attention` on the gathered view; at
    ``block_size`` it is the Pallas kernel's oracle. Returns
    [1, S, H, D] at q.dtype; per-call HBM traffic follows ``fill``,
    not the table span.
    """
    bs = int(k_pool.shape[2])
    if walk_block < bs or walk_block % bs:
        raise ValueError(
            f"walk_block ({walk_block}) must be a positive multiple "
            f"of the pool block size ({bs})")
    r = walk_block // bs
    S, H, D = q.shape[-3], q.shape[-2], q.shape[-1]
    dtype = q.dtype
    cdtype = compute_dtype or dtype
    q = q * jnp.asarray(D ** -0.5, dtype)
    fill = jnp.asarray(fill, jnp.int32)
    qpos = fill + jnp.arange(S, dtype=jnp.int32)           # [S]
    nblk = (fill + S + walk_block - 1) // walk_block       # traced
    neg = jnp.finfo(jnp.float32).min
    lead = q.shape[:-3]
    m0 = jnp.full((*lead, H, S), neg, jnp.float32)
    l0 = jnp.zeros((*lead, H, S), jnp.float32)
    a0 = jnp.zeros((*lead, H, S, D), jnp.float32)

    def read(pool, spool, new, start):
        blk = _read_span(pool, table, start, r, bs)
        if spool is not None:
            from horovod_tpu.ops.quantization import dequantize_int8
            sblk = _read_span(spool, table, start, r, bs)
            blk = dequantize_int8(blk, sblk, cdtype, axis=-1)
        # Merge the current call's rows at their positions — the
        # gather path's view holds them (the write lands before the
        # attention read), so the walked span must too, IN the same
        # accumulation step, for bitwise equality.
        rel = start + jnp.arange(walk_block, dtype=jnp.int32) - fill
        ins = (rel >= 0) & (rel < S)
        taken = jnp.take(new, jnp.clip(rel, 0, S - 1), axis=-3)
        blk = jnp.where(ins[:, None, None], taken, blk)
        if groups > 1:
            blk = jnp.repeat(blk, groups, axis=-2)
        return blk

    def body(j, carry):
        m, l, acc = carry
        start = j * walk_block
        kb = read(k_pool, k_scale_pool, k_new, start)
        vb = read(v_pool, v_scale_pool, v_new, start)
        logits = jnp.einsum("...qhd,...khd->...hqk", q, kb,
                            preferred_element_type=jnp.float32)
        kvpos = start + jnp.arange(walk_block, dtype=jnp.int32)
        keep = kvpos[None, :] <= qpos[:, None]             # [S, wb]
        logits = jnp.where(keep, logits, neg)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = (acc * alpha[..., None]
                   + jnp.einsum("...hqk,...khd->...hqd",
                                p.astype(vb.dtype), vb,
                                preferred_element_type=jnp.float32))
        return m_new, l_new, acc_new

    m, l, acc = lax.fori_loop(0, nblk, body, (m0, l0, a0))
    out = acc / l[..., None]                        # [..., H, S, D]
    return jnp.swapaxes(out, -3, -2).astype(dtype)


# ---------------------------------------------------------------------------
# The fused Pallas decode kernel (S = 1).
# ---------------------------------------------------------------------------

def _paged_decode_kernel(s_ref, t_ref, q_ref, kn_ref, vn_ref, k_ref,
                         v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                         scale: float, block_size: int, hkv: int,
                         grp: int):
    """One (lane, block) grid cell: the lax walk's body at
    ``walk_block == block_size``, fused.

    Scalar prefetch: ``s_ref`` [L, 2] = (filled-block count, fill) per
    lane — the index map clamps skipped blocks onto the last valid one
    (re-fetch elided by the pipeline), so per-tick HBM traffic follows
    the lane's fill, not its table span; ``t_ref`` [L, T] is the block
    table the K/V index maps read. Per-kv-head 2D dots (the
    `_decode_kernel` shape discipline — Mosaic-lowerable primitives
    only); the current token's K/V rows are merged at their in-block
    offset with a broadcast select, so the accumulation matches the
    lax walk update for update."""
    lane = pl.program_id(0)
    j = pl.program_id(1)
    nblk = s_ref[lane, 0]
    fill = s_ref[lane, 1]
    neg = jnp.finfo(jnp.float32).min

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, neg)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _block():
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_size, 1), 0)                # [bs, 1]
        ins = pos == fill                                 # [bs, 1]
        q = q_ref[0] * jnp.asarray(scale, q_ref.dtype)    # [H, D]
        parts = []
        for h in range(hkv):
            kh = k_ref[0, :, h, :]                        # [bs, D]
            kh = jnp.where(ins, kn_ref[0, h, :][None, :], kh)
            qh = q[h * grp:(h + 1) * grp, :]
            parts.append(jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))      # [grp, bs]
        logits = parts[0] if hkv == 1 else jnp.concatenate(parts, 0)
        keep = (j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)) <= fill
        logits = jnp.where(keep, logits, neg)

        m_prev = m_ref[...]                               # [H, 128]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev,
                            jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new[:, :1])                # [H, bs]
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1,
                                              keepdims=True)
        pv_parts = []
        for h in range(hkv):
            vh = v_ref[0, :, h, :]                        # [bs, D]
            vh = jnp.where(ins, vn_ref[0, h, :][None, :], vh)
            ph = p[h * grp:(h + 1) * grp, :].astype(vh.dtype)
            pv_parts.append(jax.lax.dot_general(
                ph, vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))      # [grp, D]
        pv = pv_parts[0] if hkv == 1 else jnp.concatenate(pv_parts, 0)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv
        m_ref[...] = m_new

    pl.when(j < nblk)(_block)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...][:, :1]).astype(
            o_ref.dtype)


def _paged_decode_call(q, k_new, v_new, table, fill, k_pool, v_pool,
                       interpret):
    """The batched pallas_call: q [L, H, D], k_new/v_new [L, Hkv, D],
    table [L, T], fill [L], pools [nb, bs, Hkv, D]."""
    L, H, D = q.shape
    nb, bs, hkv, _ = k_pool.shape
    T = table.shape[1]
    grp = H // hkv
    fill = jnp.asarray(fill, jnp.int32)
    scalars = jnp.stack([(fill + 1 + bs - 1) // bs, fill], axis=1)
    kernel = functools.partial(
        _paged_decode_kernel, scale=D ** -0.5, block_size=bs,
        hkv=hkv, grp=grp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L, T),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda l, j, s, t: (l, 0, 0)),
            pl.BlockSpec((1, hkv, D), lambda l, j, s, t: (l, 0, 0)),
            pl.BlockSpec((1, hkv, D), lambda l, j, s, t: (l, 0, 0)),
            pl.BlockSpec(
                (1, bs, hkv, D),
                lambda l, j, s, t: (t[l, jnp.minimum(j, s[l, 0] - 1)],
                                    0, 0, 0)),
            pl.BlockSpec(
                (1, bs, hkv, D),
                lambda l, j, s, t: (t[l, jnp.minimum(j, s[l, 0] - 1)],
                                    0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda l, j, s, t: (l, 0, 0)),
        scratch_shapes=[
            _scratch((H, D), jnp.float32),
            _scratch((H, 128), jnp.float32),
            _scratch((H, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, H, D), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(scalars, jnp.asarray(table, jnp.int32), q, k_new, v_new,
      k_pool, v_pool)


@functools.lru_cache(maxsize=None)
def _make_paged_decode(interpret: bool):
    """custom_vmap-wrapped single-lane kernel entry: under the serving
    tick's `jax.vmap` over lanes the batch rule fires, turning the
    lane axis into the kernel's leading grid dimension while the
    POOLS stay unbatched — one physical pool, L lanes walking it
    through their own tables (a naive vmap would have broadcast the
    pool per lane, materializing L copies of the very bytes the
    kernel exists not to touch)."""

    @jax.custom_batching.custom_vmap
    def paged_decode(q, k_new, v_new, table, fill, k_pool, v_pool):
        return _paged_decode_call(
            q[None], k_new[None], v_new[None], table[None],
            jnp.asarray(fill, jnp.int32)[None], k_pool, v_pool,
            interpret)[0]

    @paged_decode.def_vmap
    def _rule(axis_size, in_batched, q, k_new, v_new, table, fill,
              k_pool, v_pool):
        if in_batched[5] or in_batched[6]:
            raise NotImplementedError(
                "paged_decode_attention: the KV pools must not carry "
                "the vmapped lane axis (one shared pool serves every "
                "lane)")

        def bcast(x, batched):
            return x if batched else jnp.broadcast_to(
                x, (axis_size,) + jnp.shape(x))

        out = _paged_decode_call(
            bcast(q, in_batched[0]), bcast(k_new, in_batched[1]),
            bcast(v_new, in_batched[2]), bcast(table, in_batched[3]),
            bcast(jnp.asarray(fill, jnp.int32), in_batched[4]),
            k_pool, v_pool, interpret)
        return out, True

    return paged_decode


@hot_path
def paged_decode_attention(q, k_new, v_new, k_pool, v_pool, table,
                           fill, *, interpret: Optional[bool] = None):
    """One S=1 decode tick of paged attention, fused (Pallas).

    q [1, 1, H, D]; k_new/v_new [1, 1, Hkv, D] (the current token's
    rotated K/V); pools [num_blocks, 1, block_size, Hkv, D]; table
    [T]; fill traced int32. Returns [1, 1, H, D]. Accumulates at
    block_size granularity — bitwise the lax walk at
    ``walk_block == block_size`` (the interpret-mode oracle); only
    ceil((fill+1)/block_size) blocks are DMA'd. vmap over the lane
    axis dispatches ONE kernel with lanes as the leading grid dim
    (pools unbatched). Un-quantized caches only — int8 KV keeps the
    lax walk's per-block dequant.
    """
    if interpret is None:
        interpret = _auto_interpret()
    fn = _make_paged_decode(bool(interpret))
    out = fn(q[0, 0], k_new[0, 0], v_new[0, 0],
             jnp.asarray(table, jnp.int32), fill,
             k_pool[:, 0], v_pool[:, 0])
    return out[None, None]
