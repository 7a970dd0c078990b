"""v5e/v5-lite flash-attention tile-legality regression tests.

The tiling rule of real v5e Mosaic: the last two block dims must be
divisible by (8, 128) or equal to the array dims. Breaking it is a
class of bug interpret mode happily hides, because the interpreter
runs any block shape (tests/test_tpu_compile.py asks the TPU compiler
itself). The rule is kept from two sides, both CPU-verifiable:

* the lse/dvec operands ride lane-replicated rank-4 (LSE_LANES), so
  the spec that once broke it (rank-3 lse with (1, 1, bq) blocks) no
  longer exists — `flash_tile_check` proves every block spec the
  fwd+bwd pallas_calls build at the captured shapes is legal;
* user-swept tiles snap to hardware-legal sizes (`_snap_tile`:
  multi-block tiles become 8-aligned), so a sweep config like
  block_q=100 lowers on v5-lite instead of tracing a kernel only the
  interpreter can run — and the snapped kernel's numerics still
  match the blockwise oracle in interpret mode.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops.flash_attention import (
    _snap_tile, flash_attention, flash_tile_check, mosaic_block_ok,
)
from horovod_tpu.parallel.sequence import blockwise_attention


class TestTileLegality:
    def test_snap_tile(self):
        assert _snap_tile(128, 2048) == 128      # already legal
        assert _snap_tile(100, 300) == 96        # multi-block snaps
        assert _snap_tile(20, 20) == 20          # single == array dim
        assert _snap_tile(128, 20) == 20
        assert _snap_tile(5, 300) == 8           # floor at one tile row
        assert _snap_tile(100, 2048) == 96

    def test_mosaic_block_rule(self):
        assert mosaic_block_ok((1, 1, 128, 128), (4, 8, 2048, 128))
        # The r04 failure shape: rank-3 lse block (1, 1, 128) on array
        # (4, 8, 2048) — second-minor 1 neither 8-aligned nor equal.
        assert not mosaic_block_ok((1, 1, 128), (4, 8, 2048))
        assert mosaic_block_ok((1, 1, 20, 64), (1, 8, 20, 64))

    @pytest.mark.parametrize("shape", [
        # (Sq, Sk, H, Hkv, D, block_q, block_k)
        (2048, 2048, 8, 8, 64, 128, 128),   # the r04 capture shape
        (2048, 2048, 8, 2, 64, 128, 128),   # GQA
        (300, 300, 4, 4, 64, 100, 100),     # odd user tiles -> snapped
        (20, 20, 4, 4, 64, 128, 128),       # seq below one tile
        (333, 333, 4, 4, 128, 128, 256),    # ragged seq, padded grid
        (2048, 2048, 8, 8, 64, 512, 512),   # sweep upper end
    ])
    def test_all_block_specs_legal(self, shape):
        Sq, Sk, H, Hkv, D, bq, bk = shape
        for name, blk, arr, ok in flash_tile_check(
                Sq, Sk, H, Hkv, D, block_q=bq, block_k=bk):
            assert ok, (name, blk, arr)


class TestSnappedTileNumerics:
    """The snapped tiles change only the grid, never the math — the
    interpret-mode kernel at the offending tile configs matches the
    blockwise oracle, forward and backward."""

    @pytest.mark.parametrize("S,bq,bk", [
        (100, 40, 24),     # 40 -> 40 (8k), 24 -> 24
        (300, 100, 100),   # 100 -> 96 (the snap case)
        (20, 128, 128),    # single-block
    ])
    def test_fwd_bwd_matches_blockwise(self, hvd, S, bq, bk):
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(1, S, 2, 16), jnp.float32)
        k = jnp.asarray(rs.randn(1, S, 2, 16), jnp.float32)
        v = jnp.asarray(rs.randn(1, S, 2, 16), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=bq,
                              block_k=bk, interpret=True)
        ref = blockwise_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) * v).sum()

        gq, gk, gv = jax.grad(
            loss(lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk,
                interpret=True)), argnums=(0, 1, 2))(q, k, v)
        rq, rk, rv = jax.grad(
            loss(lambda q, k, v: blockwise_attention(
                q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
        for a, b in ((gq, rq), (gk, rk), (gv, rv)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)
