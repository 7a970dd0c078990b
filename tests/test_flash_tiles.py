"""v5e/v5-lite flash-attention tile-legality regression tests.

The tiling rule of real v5e Mosaic: the last two block dims must be
divisible by (8, 128) or equal to the array dims. Breaking it is a
class of bug interpret mode happily hides, because the interpreter
runs any block shape (tests/test_tpu_compile.py asks the TPU compiler
itself). The rule is kept from two sides, both CPU-verifiable:

* the lse/dvec operands ride as one [1, bq] row a q-block (arrays
  [B, H, nq, 1, bq], so a block's last two dims ARE the array's), so
  the spec that once broke it (rank-3 lse with (1, 1, bq) blocks) no
  longer exists — `flash_tile_check` proves every block spec the
  fwd+bwd pallas_calls build at the captured shapes is legal;
* user-swept tiles snap to hardware-legal sizes (`_snap_tile`:
  multi-block tiles become 8-aligned), so a sweep config like
  block_q=100 lowers on v5-lite instead of tracing a kernel only the
  interpreter can run — and the snapped kernel's numerics still
  match the blockwise oracle in interpret mode.

Since PR 25 the tiles come from the shape (`_pick_tiles`): the plan is
checked over a grid of shapes (legal, inside its own VMEM budget,
resident where `Sk * D` fits and streamed where not), and the kernels
on the planned tiles - resident and streamed, f32 and bf16 - equal the
blockwise reference, forward and all three gradients.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.flash_attention import (
    _snap_tile, flash_attention, flash_attention_lse, flash_tile_check,
    mosaic_block_ok,
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


def _qkv(S, H, Hkv, D, dtype, Sk=None):
    rs = np.random.RandomState(0)
    Sk = S if Sk is None else Sk
    return (jnp.asarray(rs.randn(1, S, H, D), dtype),
            jnp.asarray(rs.randn(1, Sk, Hkv, D), dtype),
            jnp.asarray(rs.randn(1, Sk, Hkv, D), dtype),
            jnp.asarray(rs.randn(1, S, H, D), jnp.float32))


def _reference(q, k, v, **kw):
    """The blockwise oracle in float32, K/V repeated for GQA."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    return blockwise_attention(q, k, v, **kw)


BF16_ULP = 2.0 ** -8     # one bf16 ulp, relative


class TestTilePlan:
    """`flash_tile_check` is the plan the kernels will use."""

    @pytest.mark.parametrize("Sq,Sk,D,itemsize,group", [
        (1024, 1024, 64, 2, 1),      # gpt2-medium, a chip's batch rows
        (2048, 2048, 128, 2, 1),     # the flagship
        (2048, 2048, 128, 2, 6),     # Qwen2.5-1.5B one-pass prefill
        (1024, 1024, 64, 4, 1),      # f32 inputs
        (300, 300, 64, 2, 1),        # under one tile
        (600, 600, 64, 2, 2),        # ragged: a smaller tile pads less
        (1100, 1100, 128, 2, 4),
        (8192, 8192, 128, 2, 1),     # K/V resident at 2 MiB each
        (16384, 16384, 128, 2, 8),   # K/V resident, the group's Q not
        (32768, 32768, 128, 2, 1),   # neither fits: streamed
        (512, 65536, 64, 2, 1),      # short q against a long context
    ])
    def test_plan_is_legal_and_inside_its_budget(self, Sq, Sk, D,
                                                 itemsize, group):
        plan = flash_tile_check(Sq, Sk, 8 * group, 8, D,
                                itemsize=itemsize)
        for name, blk, arr, ok in plan:
            assert ok, (name, blk, arr)
        assert max(plan.vmem_bytes.values()) <= fa.VMEM_BUDGET
        for kernel, need in plan.vmem_bytes.items():
            want = need if need > fa.VMEM_SCOPED_DEFAULT else None
            assert plan.vmem_limit_bytes[kernel] == want
        # resident exactly where the resident sum fits the budget
        t = fa._pick_tiles(Sq, Sk, D, itemsize, group)
        fwd, dq, dkv = fa._vmem_plan(t.bq, t.bk, t.Sqp, t.Skp, D,
                                     itemsize, group)
        assert plan.kv_resident == (max(fwd, dq) <= fa.VMEM_BUDGET)
        assert plan.q_resident == (dkv <= fa.VMEM_BUDGET)
        # resident: one sweep step a q-block; streamed: one a k-block
        assert plan.grid["fwd"] == (1, 8 * group, t.nq,
                                    1 if plan.kv_resident else t.nk)
        assert plan.grid["bwd.dkv"] == (1, 8, t.nk,
                                        1 if plan.q_resident else t.nq)
        assert t.Sqp % t.bq == 0 and t.Skp % t.bk == 0
        assert t.Sqp >= Sq and t.Skp >= Sk

    def test_the_cells_plans(self):
        """The two configurations of the benchmark, as PERF.md quotes
        them."""
        gpt2 = flash_tile_check(1024, 1024, 16, 16, 64)
        assert (gpt2.block_q, gpt2.block_k) == (512, 512)
        assert gpt2.kv_resident and gpt2.q_resident
        assert gpt2.grid_steps == {"fwd": 32, "bwd.dq": 32,
                                   "bwd.dkv": 32}
        assert all(v is None for v in gpt2.vmem_limit_bytes.values())
        qwen = flash_tile_check(2048, 2048, 12, 2, 128)
        assert (qwen.block_q, qwen.block_k) == (512, 512)
        assert qwen.kv_resident and qwen.q_resident
        assert qwen.grid_steps == {"fwd": 48, "bwd.dq": 48,
                                   "bwd.dkv": 8}
        # the group's six Q and dO are resident: over the default
        assert qwen.vmem_limit_bytes["bwd.dkv"] > fa.VMEM_SCOPED_DEFAULT

    def test_window_shrinks_the_streamed_sweep(self):
        full = flash_tile_check(32768, 32768, 4, 4, 128)
        band = flash_tile_check(32768, 32768, 4, 4, 128, window=1024)
        assert not full.kv_resident and not band.kv_resident
        assert full.grid["fwd"][3] == 64
        assert band.grid["fwd"][3] == 4      # ceil(1535 / 512) + 1

    @pytest.mark.parametrize("bq,bk", [(128, 256), (64, 64), (100, 40)])
    def test_explicit_tiles_win_over_the_plan(self, bq, bk, monkeypatch):
        plan = flash_tile_check(1024, 1024, 4, 4, 64, block_q=bq,
                                block_k=bk)
        assert (plan.block_q, plan.block_k) == (_snap_tile(bq, 1024),
                                                _snap_tile(bk, 1024))
        assert flash_tile_check(1024, 1024, 4, 4, 64).block_q == 512
        # ... and it is that plan the kernels are built on (the
        # wrappers are jitted: a cached trace would not plan again)
        jax.clear_caches()
        seen = []
        pick = fa._pick_tiles

        def recording(*a, **kw):
            seen.append(pick(*a, **kw))
            return seen[-1]

        monkeypatch.setattr(fa, "_pick_tiles", recording)
        q, k, v, _ = _qkv(200, 2, 2, 16, jnp.float32)
        jax.grad(lambda q: flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk,
            interpret=True).sum())(q)
        assert len(seen) == 2          # forward, backward
        assert {(t.bq, t.bk) for t in seen} == {
            (_snap_tile(bq, 200), _snap_tile(bk, 200))}


class TestSnappedTileNumerics:
    """The snapped tiles change only the grid, never the math — the
    interpret-mode kernel at the offending tile configs matches the
    blockwise oracle, forward and backward."""

    @pytest.mark.parametrize("S,bq,bk", [
        (100, 40, 24),     # 40 -> 40 (8k), 24 -> 24
        (300, 100, 100),   # 100 -> 96 (the snap case)
        (20, 128, 128),    # single-block
        (20, None, None),  # the plan: one tile == the padded axis
        (600, None, None),  # the plan: three 256-row tiles, padded
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


CASES = {
    # S a multiple of the tile, and not
    "even": dict(S=128, bq=32, bk=64),
    "ragged": dict(S=100, bq=32, bk=48),
    "ragged-plan": dict(S=100),
    # window set (the banded sweep, both edges masked)
    "window": dict(S=128, bq=32, bk=32, window=40),
    "window-ragged": dict(S=90, bq=16, bk=48, window=24),
    # q_offset != 0 (a ring rotation's block pair)
    "q-offset": dict(S=64, bq=16, bk=32, q_offset=64),
    "offsets-window": dict(S=64, Sk=96, bq=32, bk=16, q_offset=40,
                           k_offset=8, window=48),
    # GQA group 6 (dK/dV folds the group), D 64 and 128
    "gqa6-d128": dict(S=80, H=6, Hkv=1, D=128, bq=32, bk=16),
    "gqa6-d64": dict(S=80, H=6, Hkv=1, D=64, bq=16, bk=40),
    "d64": dict(S=96, D=64, bq=48, bk=32),
    "non-causal-ragged": dict(S=70, Sk=90, bq=32, bk=32, causal=False),
}


def _case(name):
    """(S, Sk, H, Hkv, D, block_q, block_k, attention kwargs)."""
    c = dict(CASES[name])
    shape = (c.pop("S"), c.pop("Sk", None), c.pop("H", 2),
             c.pop("Hkv", 2), c.pop("D", 16))
    tiles = c.pop("bq", None), c.pop("bk", None)
    c.setdefault("causal", True)
    return (*shape, *tiles, c)


@pytest.fixture
def zero_vmem_budget(monkeypatch):
    """Nothing fits, so K/V and the group's Q stream. The wrappers are
    jitted and plan at trace time: drop their traces on both sides, or
    the resident and the streamed runs of a shape would share one."""
    monkeypatch.setattr(fa, "VMEM_BUDGET", 0)
    jax.clear_caches()
    yield
    jax.clear_caches()


class TestPlannedKernelNumerics:
    """The kernels on the plan's tiles equal the blockwise reference,
    forward and all three gradients - with K/V (and the group's Q)
    resident, and with `VMEM_BUDGET` at zero so that both stream."""

    @staticmethod
    def _grads(fn, q, k, v, w):
        return jax.value_and_grad(
            lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum(),
            argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("streamed", [False, True],
                             ids=["resident", "streamed"])
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_f32_matches_blockwise(self, hvd, request, case, streamed):
        S, Sk, H, Hkv, D, bq, bk, c = _case(case)
        if streamed:
            request.getfixturevalue("zero_vmem_budget")
            plan = flash_tile_check(S, Sk or S, H, Hkv, D, itemsize=4,
                                    block_q=bq, block_k=bk)
            assert not plan.kv_resident and not plan.q_resident
        q, k, v, w = _qkv(S, H, Hkv, D, jnp.float32, Sk)
        lo, (gq, gk, gv) = self._grads(
            lambda q, k, v: flash_attention(
                q, k, v, block_q=bq, block_k=bk, interpret=True, **c),
            q, k, v, w)
        lr, (rq, rk, rv) = self._grads(
            lambda q, k, v: _reference(q, k, v, **c), q, k, v, w)
        np.testing.assert_allclose(float(lo), float(lr), rtol=2e-5,
                                   atol=2e-4)
        for a, b in ((gq, rq), (gk, rk), (gv, rv)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)

    @pytest.mark.parametrize("case", ["ragged-plan", "window",
                                      "gqa6-d128", "d64", "q-offset"])
    def test_bf16_matches_f32_of_the_same_inputs(self, hvd, case):
        """bf16 inputs ride the MXU as bf16 (float32 accumulation, `p`
        and `ds` cast before their matmuls): output and gradients
        stay within 4 bf16 ulps of the reference's largest entry,
        the reference being float32 arithmetic on the SAME bf16
        values."""
        S, Sk, H, Hkv, D, bq, bk, c = _case(case)
        q, k, v, w = _qkv(S, H, Hkv, D, jnp.bfloat16, Sk)
        out = flash_attention(q, k, v, block_q=bq, block_k=bk,
                              interpret=True, **c)
        assert out.dtype == jnp.bfloat16
        ref = _reference(q, k, v, **c)
        _, got = self._grads(
            lambda q, k, v: flash_attention(
                q, k, v, block_q=bq, block_k=bk, interpret=True, **c),
            q, k, v, w)
        _, want = self._grads(
            lambda q, k, v: _reference(q, k, v, **c), q, k, v, w)
        for a, b in ((out, ref), *zip(got, want)):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert a.shape == b.shape
            assert np.abs(a - b).max() <= 4 * BF16_ULP * np.abs(b).max()

    @pytest.mark.parametrize("streamed", [False, True],
                             ids=["resident", "streamed"])
    def test_lse_cotangent(self, hvd, request, streamed):
        """`flash_attention_lse` with a cotangent on lse (the ring
        merge's `dlse` term in dvec), on ragged tiles."""
        if streamed:
            request.getfixturevalue("zero_vmem_budget")
        q, k, v, w = _qkv(70, 2, 1, 16, jnp.float32)

        def merged(q, k, v, attn):
            o, lse = attn(q, k, v)
            return (o * w).sum() + (jnp.sin(lse) * 0.5).sum()

        def ref_attn(q, k, v):
            kk, vv = jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * 16 ** -0.5
            s = jnp.where(jnp.tril(jnp.ones((70, 70), bool)), s,
                          -jnp.inf)
            lse = jax.nn.logsumexp(s, -1)
            o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]),
                           vv)
            return o, lse

        got = jax.grad(
            lambda q, k, v: merged(q, k, v, lambda q, k, v:
                                   flash_attention_lse(
                                       q, k, v, causal=True, block_q=32,
                                       block_k=24, interpret=True)),
            argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda q, k, v: merged(q, k, v, ref_attn),
                        argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)
