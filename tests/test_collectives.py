"""Collective correctness sweep.

TPU-native mirror of the reference correctness tests
(`mpi_ops_test.py:85-539`): dtype × dimensionality sweeps with shape
[17]^dim, allreduce == sum of per-rank tensors, allgather slice-per-rank
checks (fixed and variable dim 0), broadcast over every root rank, and
negative tests for cross-rank metadata mismatch (the reference's
FailedPreconditionError paths, here CollectiveMismatchError).
"""

import itertools

import numpy as np
import pytest

from horovod_tpu.ops.validation import CollectiveMismatchError

ALLREDUCE_DTYPES = [np.int32, np.int64, np.float32, np.float64]
# allgather/broadcast add small int types (mpi_ops.cc:1827,1890)
GATHER_DTYPES = ALLREDUCE_DTYPES + [np.uint8, np.int8, np.uint16, np.int16]
DIMS = [1, 2, 3]


@pytest.mark.parametrize("dtype,dim",
                         list(itertools.product(ALLREDUCE_DTYPES, DIMS)))
def test_allreduce_sum(hvd, dtype, dim):
    """allreduce(sum) == elementwise sum of all ranks' tensors
    (mpi_ops_test.py:85-114)."""
    rng = np.random.RandomState(1234)
    shape = [17] * dim
    vals = [(rng.uniform(-100, 100, shape)).astype(dtype)
            for _ in range(hvd.size())]
    result = np.asarray(hvd.allreduce(hvd.per_rank(vals), average=False))
    expected = np.sum(np.stack(vals), axis=0)
    if np.issubdtype(np.dtype(dtype), np.floating):
        # Threshold logic follows mpi_ops_test.py:96-104.
        np.testing.assert_allclose(result, expected, rtol=1e-5)
    else:
        np.testing.assert_array_equal(result, expected)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_allreduce_average(hvd, dtype):
    rng = np.random.RandomState(5)
    vals = [rng.uniform(-1, 1, (17, 3)).astype(dtype)
            for _ in range(hvd.size())]
    result = np.asarray(hvd.allreduce(hvd.per_rank(vals), average=True))
    np.testing.assert_allclose(result, np.mean(np.stack(vals), axis=0),
                               rtol=1e-5)


def test_allreduce_integer_average_keeps_dtype(hvd):
    """Integer average floor-divides and preserves dtype (tf.div parity,
    `horovod/tensorflow/__init__.py:75-78`)."""
    vals = [np.full((4,), r + 1, np.int32) for r in range(hvd.size())]
    out = np.asarray(hvd.allreduce(hvd.per_rank(vals), average=True))
    assert out.dtype == np.int32
    total = sum(r + 1 for r in range(hvd.size()))
    np.testing.assert_array_equal(out, total // hvd.size())


def test_allreduce_replicated_value(hvd):
    """A plain (replicated) tensor behaves as N identical ranks."""
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    out_sum = np.asarray(hvd.allreduce(x, average=False))
    np.testing.assert_allclose(out_sum, x * hvd.size())
    out_avg = np.asarray(hvd.allreduce(x, average=True))
    np.testing.assert_allclose(out_avg, x)


@pytest.mark.parametrize("dtype,dim",
                         list(itertools.product(GATHER_DTYPES, DIMS)))
def test_allgather_fixed(hvd, dtype, dim):
    """Each rank's slice of the gathered result equals its own tensor
    (mpi_ops_test.py:358-386): rank r contributes r * ones([17]*dim)."""
    shape = [17] * dim
    vals = [np.full(shape, r, dtype=dtype) for r in range(hvd.size())]
    result = np.asarray(hvd.allgather(hvd.per_rank(vals)))
    assert result.shape[0] == 17 * hvd.size()
    for r in range(hvd.size()):
        sl = result[r * 17:(r + 1) * 17]
        np.testing.assert_array_equal(sl, vals[r])


@pytest.mark.parametrize("dim", DIMS)
def test_allgather_variable_dim0(hvd, dim):
    """Variable per-rank dim-0 sizes (MPI_Allgatherv parity,
    mpi_ops_test.py:388-427): rank r contributes (r+1) rows."""
    tail = [17] * (dim - 1)
    vals = [np.full([r + 1] + tail, r, dtype=np.float32)
            for r in range(hvd.size())]
    result = np.asarray(hvd.allgather(hvd.per_rank(vals)))
    total = sum(r + 1 for r in range(hvd.size()))
    assert result.shape[0] == total
    off = 0
    for r in range(hvd.size()):
        np.testing.assert_array_equal(result[off:off + r + 1], vals[r])
        off += r + 1


@pytest.mark.parametrize("dtype,root",
                         list(itertools.product(
                             [np.int32, np.float32], range(8))))
def test_broadcast_all_roots(hvd, dtype, root):
    """Result equals the root's tensor for every (dtype, root)
    (mpi_ops_test.py:465-487)."""
    vals = [np.full((17, 2), r, dtype=dtype) for r in range(hvd.size())]
    result = np.asarray(hvd.broadcast(hvd.per_rank(vals), root))
    np.testing.assert_array_equal(result, vals[root])


@pytest.mark.parametrize("dtype", GATHER_DTYPES)
def test_broadcast_dtypes(hvd, dtype):
    vals = [np.full((5,), r + 1, dtype=dtype) for r in range(hvd.size())]
    result = np.asarray(hvd.broadcast(hvd.per_rank(vals), 3))
    np.testing.assert_array_equal(result, vals[3])


def test_broadcast_root_out_of_range(hvd):
    with pytest.raises(ValueError):
        hvd.broadcast(np.zeros(3), hvd.size())


def test_alltoall(hvd):
    """rank r receives slice r from every rank, concatenated."""
    n = hvd.size()
    vals = [np.arange(n * 2, dtype=np.float32).reshape(n * 2) + 100 * r
            for r in range(n)]
    result = np.asarray(hvd.alltoall(hvd.per_rank(vals)))
    # Row r of the [world, ...] output = concat of chunk r from all ranks.
    for r in range(n):
        expected = np.concatenate(
            [vals[src][r * 2:(r + 1) * 2] for src in range(n)])
        np.testing.assert_array_equal(result[r], expected)


def test_reducescatter(hvd):
    n = hvd.size()
    vals = [np.arange(n * 3, dtype=np.float32) * (r + 1) for r in range(n)]
    result = np.asarray(hvd.reducescatter(hvd.per_rank(vals)))
    summed = np.sum(np.stack(vals), axis=0)
    for r in range(n):
        np.testing.assert_allclose(result[r], summed[r * 3:(r + 1) * 3])


def test_alltoall_replicated_and_dim0_contract(hvd):
    """Plain (replicated) alltoall: row r = size copies of slice r —
    consistent with reducescatter's replicated convention; non-divisible
    dim 0 is a clear ValueError (r4: no eager API raises
    NotImplementedError)."""
    n = hvd.size()
    x = np.arange(n * 2, dtype=np.float32)
    out = np.asarray(hvd.alltoall(x))
    for r in range(n):
        np.testing.assert_array_equal(
            out[r], np.tile(x[r * 2:(r + 1) * 2], n))
    with pytest.raises(ValueError, match="divisible"):
        hvd.alltoall(np.zeros((n * 2 + 1,), np.float32))
    with pytest.raises(ValueError, match="divisible"):
        hvd.reducescatter(np.zeros((n * 2 + 1,), np.float32))
    with pytest.raises(ValueError, match="divisible"):
        hvd.alltoall(hvd.per_rank(
            [np.zeros((n * 2 + 1,), np.float32)] * n))


def test_alltoall_reducescatter_mismatch(hvd):
    """Cross-rank dtype disagreement raises the precondition error on
    the new PerRank validation of alltoall/reducescatter too."""
    n = hvd.size()
    vals = [np.zeros((n * 2,), np.float32 if r == 0 else np.float64)
            for r in range(n)]
    with pytest.raises(CollectiveMismatchError):
        hvd.alltoall(hvd.per_rank(vals))
    with pytest.raises(CollectiveMismatchError):
        hvd.reducescatter(hvd.per_rank(vals))


# ---- negative tests: coordinator validation parity (mpi_ops_test.py:284+)

def test_allreduce_shape_mismatch(hvd):
    """Mismatched shape across ranks fails (mpi_ops_test.py:284-311)."""
    vals = [np.zeros((17,) if r % 2 == 0 else (18,), np.float32)
            for r in range(hvd.size())]
    with pytest.raises(CollectiveMismatchError):
        hvd.allreduce(hvd.per_rank(vals))


def test_allreduce_dtype_mismatch(hvd):
    """Mismatched dtype across ranks fails (mpi_ops_test.py:313-330)."""
    vals = [np.zeros((17,), np.float32 if r % 2 == 0 else np.int32)
            for r in range(hvd.size())]
    with pytest.raises(CollectiveMismatchError):
        hvd.allreduce(hvd.per_rank(vals))


def test_allgather_nondim0_mismatch(hvd):
    """allgather allows dim-0 mismatch but not other dims
    (mpi_ops_test.py:429-445)."""
    vals = [np.zeros((r + 1, 17 if r % 2 == 0 else 18), np.float32)
            for r in range(hvd.size())]
    with pytest.raises(CollectiveMismatchError):
        hvd.allgather(hvd.per_rank(vals))


def test_allgather_dtype_mismatch(hvd):
    vals = [np.zeros((17,), np.float32 if r % 2 == 0 else np.float64)
            for r in range(hvd.size())]
    with pytest.raises(CollectiveMismatchError):
        hvd.allgather(hvd.per_rank(vals))


def test_broadcast_rank_mismatch(hvd):
    """Ranks disagreeing on root rank fails (mpi_ops_test.py:525-539);
    exercised through the validator since the single-controller API takes
    one root argument."""
    from horovod_tpu.ops.validation import validate_requests
    with pytest.raises(CollectiveMismatchError):
        validate_requests(
            name="t", op="broadcast",
            dtypes=["float32"] * 2, shapes=[(17,)] * 2,
            root_ranks=[0, 1])


def test_wrong_world_size_rejected(hvd):
    with pytest.raises(ValueError):
        hvd.allreduce(hvd.per_rank([np.zeros(3)] * (hvd.size() - 1)))


def test_allgather_object(hvd):
    """later-Horovod `hvd.allgather_object`: one picklable object per
    rank, returned as a rank-ordered list."""
    out = hvd.allgather_object({"rank": 0, "tag": "x"})
    assert len(out) == hvd.size()
    assert all(o == {"rank": 0, "tag": "x"} for o in out)


def test_grouped_allreduce(hvd):
    """later-Horovod `hvd.grouped_allreduce`: a list reduced as one
    fused collective; per-tensor results equal individual allreduces."""
    import numpy as np
    ts = [np.arange(4, dtype=np.float32),
          np.ones((2, 3), np.float32) * 2,
          np.arange(6, dtype=np.int32)]
    outs = hvd.grouped_allreduce(ts, average=False)
    assert len(outs) == 3
    for t, o in zip(ts, outs):
        assert o.shape == t.shape and o.dtype == t.dtype
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(hvd.allreduce(t, average=False)))
    avg = hvd.grouped_allreduce(ts[:2], average=True)
    np.testing.assert_allclose(np.asarray(avg[0]), ts[0])


def test_grouped_allreduce_interleaved_dtypes_and_per_rank(hvd):
    import numpy as np
    import pytest as _pytest
    ts = [np.ones(2, np.float32), np.ones(3, np.int32),
          np.ones(4, np.float32)]  # f32 tensors pack despite the i32
    outs = hvd.grouped_allreduce(ts, average=False)
    for t, o in zip(ts, outs):
        np.testing.assert_allclose(np.asarray(o), hvd.size())
        assert o.dtype == t.dtype
    with _pytest.raises(TypeError, match="per_rank"):
        hvd.grouped_allreduce(
            [hvd.per_rank([np.ones(2, np.float32)] * hvd.size())])


class TestBroadcastLowering:
    def test_single_allreduce_no_gather_no_loop(self, hvd):
        """Pin the broadcast lowering: the
        masked psum must compile to exactly ONE all-reduce HLO with the
        mask fused in — no all-gather, no while loop, no all-to-all.
        (XLA has no collective-broadcast rewrite for this pattern; the
        single all-reduce is the accepted one-shot cost, documented in
        `ops/collectives.py:broadcast`.)"""
        import re

        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.ops.collectives import broadcast
        from horovod_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(data=hvd.size())
        fn = jax.jit(jax.shard_map(
            lambda x: broadcast(x, 3), mesh=mesh,
            in_specs=P("data", None), out_specs=P(None, None),
            check_vma=False))
        x = jnp.arange(float(8 * hvd.size())).reshape(hvd.size(), 8)
        hlo = fn.lower(x).compile().as_text()

        def count(op):
            return len(re.findall(rf"\b{op}\b", hlo))

        assert count("all-reduce") == 1, hlo
        assert count("all-gather") == 0
        assert count("all-to-all") == 0
        assert count("collective-permute") == 0
        assert count("while") == 0
        # and it is numerically a broadcast of rank 3's block
        out = fn(x)
        import numpy as np
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(x[3:4]))
