"""Paged-attention: decode attention that walks only FILLED KV blocks.

The paged serving pool (`serving.paging`, PR 7) made paged decode
bitwise-equal to the fixed slot pool by GATHERING each lane's whole
block table back into a linear [max_len] view every tick
(`models.transformer._paged_view`) — correct, but the gather touches
every allocated block whether or not the sequence ever filled it, so
per-tick HBM traffic followed the table's span where the fixed pool's
follows the fill. This module deletes that tax: attention reads the pool
THROUGH the block table, touching only the blocks the lane actually
filled:

**`paged_prefix_attention`**: a `lax.fori_loop` walk over
``walk_block``-token spans — each step takes exactly the table entries
covering its span (a bounded gather of ``walk_block/block_size``
blocks, never the full table) and applies the SAME online-softmax
update, in the same order, with the same masking constants, as
`ParallelSelfAttention._prefix_attention` runs on the gathered view.
Same values + same float-op order ⇒ the walk is BITWISE the gather
path (pinned by tests/test_paged_attention.py), so it is what a paged
pool takes wherever its geometry allows
(`serving.paging._paged_attention_way`) without perturbing a single
pinned token stream. Composes with GQA (``groups``), int8 KV (scale
pools, per-block dequant via the one tested codec), S >= 1 (prefill
chunks and the spec-decode verify block ride the same walk), and vmaps
over the lane axis natively.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.annotations import hot_path

__all__ = ["paged_prefix_attention"]


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
