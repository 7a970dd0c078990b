"""Tensor fusion (bucketed allreduce) tests.

Mirrors the intent of the reference's fused tests
(`mpi_ops_test.py:116-148` — batching many allreduces so fusion actually
triggers) and the fusion config contract (`docs/tensor-fusion.md:18-28`:
threshold in bytes, 0 disables).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops.fusion import plan_buckets, fused_allreduce_tree


class _Leaf:
    """Shape/dtype stub for bucket planning."""
    def __init__(self, shape, dtype):
        self.shape = shape
        self.dtype = np.dtype(dtype)
        self.ndim = len(shape)


def test_plan_buckets_threshold():
    leaves = [_Leaf((1024,), np.float32) for _ in range(10)]  # 4 KB each
    buckets = plan_buckets(leaves, threshold=8192)  # 2 leaves per bucket
    assert [len(b) for b in buckets] == [2] * 5
    assert sorted(i for b in buckets for i in b) == list(range(10))


def test_plan_buckets_disabled():
    leaves = [_Leaf((8,), np.float32) for _ in range(4)]
    assert plan_buckets(leaves, threshold=0) == [[0], [1], [2], [3]]


def test_plan_buckets_dtype_grouping():
    """Only same-dtype tensors fuse (mpi_ops.cc:1397-1404)."""
    leaves = [_Leaf((8,), np.float32), _Leaf((8,), np.float64),
              _Leaf((8,), np.float32)]
    buckets = plan_buckets(leaves, threshold=1 << 20)
    assert buckets == [[0], [1], [2]]


def test_hvd_fusion_mb_env_controls_bucket_plans(monkeypatch):
    """HVD_FUSION_MB (megabytes, HOROVOD_FUSION_THRESHOLD parity)
    reaches `plan_buckets` through the runtime config and actually
    changes the plan; the byte-exact reference variable wins when both
    are set; fractions of a MB parse."""
    from horovod_tpu.runtime.config import (DEFAULT_FUSION_THRESHOLD,
                                            config)
    leaves = [_Leaf((1 << 18,), np.float32)   # 1 MiB each
              for _ in range(8)]
    try:
        # Default: 64 MiB — everything in one bucket.
        monkeypatch.delenv("HOROVOD_FUSION_THRESHOLD", raising=False)
        monkeypatch.delenv("HVD_FUSION_MB", raising=False)
        config.refresh()
        assert config.fusion_threshold == DEFAULT_FUSION_THRESHOLD
        assert [len(b) for b in plan_buckets(leaves)] == [8]
        # 2 MB buckets -> pairs.
        monkeypatch.setenv("HVD_FUSION_MB", "2")
        config.refresh()
        assert config.fusion_threshold == 2 << 20
        assert [len(b) for b in plan_buckets(leaves)] == [2] * 4
        # Fractional MB: 0.5 MB < leaf size -> singletons.
        monkeypatch.setenv("HVD_FUSION_MB", "0.5")
        config.refresh()
        assert config.fusion_threshold == 1 << 19
        assert [len(b) for b in plan_buckets(leaves)] == [1] * 8
        # The reference's byte-exact variable takes precedence.
        monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD",
                           str(4 << 20))
        config.refresh()
        assert config.fusion_threshold == 4 << 20
        assert [len(b) for b in plan_buckets(leaves)] == [4, 4]
    finally:
        monkeypatch.delenv("HOROVOD_FUSION_THRESHOLD", raising=False)
        monkeypatch.delenv("HVD_FUSION_MB", raising=False)
        config.refresh()


@pytest.mark.parametrize("threshold", [0, 64, 1 << 20])
def test_fused_allreduce_matches_unfused(hvd, threshold):
    """Fused result == per-tensor psum for any threshold."""
    mesh = hvd.mesh()
    rng = np.random.RandomState(7)
    n = hvd.size()
    tree = {
        "w": rng.randn(n, 8, 4).astype(np.float32),
        "b": rng.randn(n, 4).astype(np.float32),
        "scale": rng.randn(n, 1).astype(np.float32),
    }

    def kernel(t):
        local = jax.tree.map(lambda x: x[0], t)
        return fused_allreduce_tree(local, axis_name="data",
                                    average=True, threshold=threshold)

    fn = jax.jit(jax.shard_map(kernel, mesh=mesh,
                               in_specs=P("data"), out_specs=P()))
    out = fn(tree)
    for k in tree:
        np.testing.assert_allclose(
            np.asarray(out[k]), tree[k].mean(axis=0), rtol=1e-5)


def test_fusion_env_var(hvd, monkeypatch):
    """HOROVOD_FUSION_THRESHOLD is honored (mpi_ops.cc:1278-1281)."""
    from horovod_tpu.runtime.config import config
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "128")
    config.refresh()
    try:
        leaves = [_Leaf((16,), np.float32) for _ in range(4)]  # 64 B each
        assert [len(b) for b in plan_buckets(leaves)] == [2, 2]
    finally:
        monkeypatch.delenv("HOROVOD_FUSION_THRESHOLD")
        config.refresh()


class TestOverlapStructure:
    """Pin the PRECONDITION for backward/allreduce overlap: the IR handed to XLA must contain one INDEPENDENT
    all_reduce per gradient bucket — none chained through another
    collective — so the latency-hiding scheduler is free to issue each
    bucket's collective as soon as its grads exist, instead of one
    monolithic all-reduce that can only trail the whole backward.

    What this test deliberately does NOT claim: the CPU test backend's
    AllReduceCombiner pass re-merges these into one tuple all-reduce
    in the compiled module (observed: the merged op schedules after
    the last backward convolution), so a CPU schedule cannot evidence
    overlap; exposed-comm fraction is measurable only on >=2 real
    chips (docs/scaling.md carries the full analysis)."""

    def _stablehlo(self, threshold):
        import jax
        import jax.numpy as jnp
        import optax

        from horovod_tpu import models
        from horovod_tpu.models import make_cnn_train_step
        from horovod_tpu.models.train import init_cnn_state

        model = models.MnistConvNet(dtype=jnp.float32)
        tx = optax.sgd(0.1)
        state = init_cnn_state(model, tx, jax.random.PRNGKey(0),
                               jnp.zeros((1, 28, 28, 1), jnp.float32))
        step = make_cnn_train_step(model, tx,
                                   fusion_threshold=threshold)
        x = jnp.zeros((8, 28, 28, 1))
        y = jnp.zeros((8,), jnp.int64).astype(jnp.int32)
        return step.__wrapped__.lower(
            state, (x, y), jax.random.PRNGKey(1)).as_text()

    def test_one_independent_all_reduce_per_bucket(self, hvd):
        import re

        n_grad_leaves = 8  # MnistConvNet: 4 layers x (kernel, bias)

        # threshold=1 byte: every grad leaf is its own bucket.
        txt = self._stablehlo(1)
        ops = re.findall(
            r'(%\d+(?::\d+)?) = "stablehlo.all_reduce"\(([^)]*)\)', txt)
        # 8 grad buckets + the scalar loss pmean.
        assert len(ops) == n_grad_leaves + 1, txt[:500]

        # Independence: no all_reduce consumes another's result — the
        # buckets form an antichain the scheduler may freely reorder.
        results = {name.split(":")[0] for name, _ in ops}
        for _, operands in ops:
            for op in re.findall(r"%\d+", operands):
                assert op not in results, (
                    f"all_reduce chained through {op}")

        # 64 MB threshold: all same-dtype grads fuse into ONE bucket
        # (+ the loss pmean) — HOROVOD_FUSION_THRESHOLD controls the
        # collective granularity of the IR end to end.
        txt = self._stablehlo(1 << 26)
        assert len(re.findall(r"stablehlo\.all_reduce", txt)) == 2

    @staticmethod
    def _mnist_step_text(mesh=None):
        """Post-optimization HLO of `make_cnn_train_step` on
        MnistConvNet (4 layers x (kernel, bias) = 8 gradient leaves),
        one bucket a leaf, compiled as the factory compiles it."""
        import jax
        import jax.numpy as jnp
        import optax

        from horovod_tpu import models
        from horovod_tpu.models import make_cnn_train_step
        from horovod_tpu.models.train import init_cnn_state

        model = models.MnistConvNet(dtype=jnp.float32)
        tx = optax.sgd(0.1)
        state = init_cnn_state(model, tx, jax.random.PRNGKey(0),
                               jnp.zeros((1, 28, 28, 1), jnp.float32))
        step = make_cnn_train_step(model, tx, mesh=mesh,
                                   fusion_threshold=1)
        return step.__wrapped__.lower(
            state, (jnp.zeros((8, 28, 28, 1)),
                    jnp.zeros((8,), jnp.int32)),
            jax.random.PRNGKey(1)).compile().as_text()

    def test_post_optimization_bucket_structure(self, hvd):
        """Close the overlap-model loophole: the
        backend AllReduceCombiner re-merges our independent bucket
        all-reduces into one tuple all-reduce (the hazard
        docs/scaling.md flags), and `combiner_override_options()` —
        applied by the train-step factories under the default
        HOROVOD_XLA_COMBINER=pin — provably keeps one independent
        all-reduce per bucket in the POST-optimization HLO, not just
        the pre-pass IR."""
        import re

        from horovod_tpu.ops.fusion import combiner_override_options

        n_grad_leaves = 8

        def count_all_reduces(txt):
            return len(re.findall(r"= \S+ all-reduce\(", txt))

        # The factory's jit carries the pin (HOROVOD_XLA_COMBINER
        # defaults to "pin"): 8 per-leaf buckets + the loss pmean
        # survive every backend pass as INDEPENDENT all-reduces.
        txt = self._mnist_step_text()
        assert count_all_reduces(txt) == n_grad_leaves + 1, txt[:2000]
        # Independence in the optimized module: no all-reduce operand
        # is another all-reduce's result.
        results = {m.lstrip("%") for m in
                   re.findall(r"(\S+) = \S+ all-reduce\(", txt)}
        for operands in re.findall(r"= \S+ all-reduce\(([^)]*)\)", txt):
            for name in re.findall(r"%?[\w.-]+", operands):
                assert name.lstrip("%") not in results

        # And the hazard is real: the same step built with
        # HOROVOD_XLA_COMBINER=xla (combiner left on) re-merges the
        # antichain into fewer (tuple) all-reduces — this is what the
        # default pin defends against. (Counted, not assumed, so a
        # future XLA that stops combining makes this assertion fail
        # loudly and the pin can be retired.)
        from horovod_tpu.runtime.config import config as hvd_config
        assert combiner_override_options() == {
            "xla_disable_hlo_passes":
                "all-reduce-combiner,cpu-all-reduce-combiner"}
        old = hvd_config.xla_combiner
        try:
            hvd_config.xla_combiner = "xla"
            assert combiner_override_options() == {}
            n_merged = count_all_reduces(self._mnist_step_text())
        finally:
            hvd_config.xla_combiner = old
        assert n_merged < n_grad_leaves + 1, (
            f"backend no longer combines ({n_merged}); "
            f"revisit combiner_override_options")

    @pytest.mark.parametrize("combiner", ["pin", "xla"])
    @pytest.mark.parametrize("data", [8, 1])
    def test_step_options_off_a_tpu_are_the_combiner_pin(
            self, hvd, data, combiner):
        """The step factories' `compiler_options` follow the mesh
        (`step_compiler_options`): on the CPU backend, and at a data
        axis of 1 on any backend, they are EXACTLY the combiner pin
        (`{}` under HOROVOD_XLA_COMBINER=xla) - no TPU key reaches a
        compiler that would refuse it - and the post-optimization
        bucket structure is what it was: one independent all-reduce a
        bucket and the loss's under the pin (the CPU compiler keeps
        them over a single device too), fewer with XLA's combiner
        left on."""
        import re

        import jax

        from horovod_tpu.ops.fusion import (combiner_override_options,
                                            step_compiler_options)
        from horovod_tpu.parallel.mesh import make_mesh
        from horovod_tpu.runtime.config import config as hvd_config

        mesh = make_mesh(devices=jax.devices()[:data], data=data)
        old = hvd_config.xla_combiner
        try:
            hvd_config.xla_combiner = combiner
            pin = combiner_override_options()
            assert pin == ({} if combiner == "xla" else {
                "xla_disable_hlo_passes":
                    "all-reduce-combiner,cpu-all-reduce-combiner"})
            assert step_compiler_options(mesh, "data") == pin
            text = self._mnist_step_text(mesh)
        finally:
            hvd_config.xla_combiner = old
        # (a merged all-reduce's result is a tuple: match the opcode)
        n = len(re.findall(r" all-reduce\(", text))
        if combiner == "pin":
            assert n == 8 + 1, text[:2000]   # 8 leaves + the loss
        else:
            assert 0 < n < 8 + 1, text[:2000]


def _overlapped_exchange(hvd, monkeypatch, sizes, threshold):
    """The all-reduced shapes and the results of `fused_allreduce_tree`
    over float32 leaves of `sizes` numbers, traced as a step factory
    traces it for a mesh that `overlaps` (here the CPU's, taken for
    one; `ALONE_BYTES` cut to 4 KiB = 1024 numbers so the leaves stay
    small)."""
    import re

    from horovod_tpu.ops import fusion

    monkeypatch.setattr(fusion, "ALONE_BYTES", 4096)
    monkeypatch.setattr(fusion, "overlaps", lambda mesh, axis: True)
    rng = np.random.RandomState(3)
    tree = [rng.randn(hvd.size(), *np.atleast_1d(s)).astype(np.float32)
            for s in sizes]

    def kernel(t):
        with fusion.exchange_for(hvd.mesh(), "data"):
            return fused_allreduce_tree(
                [x[0] for x in t], axis_name="data", average=False,
                threshold=threshold)

    fn = jax.jit(jax.shard_map(kernel, mesh=hvd.mesh(),
                               in_specs=P("data"), out_specs=P()))
    shapes = re.findall(r"\}\) : \(tensor<([\dx]+)xf32>\) -> tensor<",
                        fn.lower(tree).as_text())
    for got, x in zip(fn(tree), tree):
        np.testing.assert_allclose(np.asarray(got), x.sum(axis=0),
                                   rtol=1e-5)
    return sorted(shapes)


@pytest.mark.parametrize("sizes, threshold, want", [
    # a large leaf goes alone, in its own shape; the small ones on both
    # sides of it fuse into one [rows, 128]
    ([256, (8, 256), 256, (64, 128), 256], 1 << 26,
     ["64x128", "6x128", "8x256"]),
    # exactly ALONE_BYTES is large, just under it is not
    ([1024, 1023, 1023], 1 << 26, ["1024", "16x128"]),
    # the threshold still closes the small leaves' bucket
    ([512, (16, 128), 512, 512], 4096, ["16x128", "8x128", "512"]),
    # and 0 still means one collective a tensor
    ([256, (16, 128), 256], 0, ["16x128", "256", "256"]),
], ids=["around", "edge", "threshold", "disabled"])
def test_overlapped_exchange_reduces_a_large_leaf_alone(
        hvd, monkeypatch, sizes, threshold, want):
    """Where a step's exchange `overlaps` (a TPU mesh, data axis > 1)
    a leaf of `ALONE_BYTES` or more is no part of a bucket: fusion buys
    back a collective's latency, which such a leaf outweighs, and the
    flat bucket costs a matrix a relayout both ways and the compiler's
    overlap (ops/fusion.py). `plan_buckets` under the threshold still
    plans the rest."""
    assert _overlapped_exchange(hvd, monkeypatch, sizes,
                                threshold) == sorted(want)


@pytest.mark.parametrize("sizes", [(128, 256), (100, 27), (384,)],
                         ids=["whole-rows", "padded", "single"])
def test_overlapped_bucket_is_reduced_as_rows_of_128(hvd, monkeypatch,
                                                     sizes):
    """There a fused bucket crosses the wire as [rows, 128] (what lets
    a TPU run its all-reduce asynchronously), zero-padded where its
    size is no multiple of 128, and the leaves come back as they went
    in; a single leaf keeps its shape. Outside such a step the bucket
    is the flat array it was."""
    got = _overlapped_exchange(hvd, monkeypatch, sizes, 1 << 20)
    rows = -(-sum(sizes) // 128)
    assert got == ([f"{rows}x128"] if len(sizes) > 1 else ["384"])

    def flat(t):
        return fused_allreduce_tree([x[0] for x in t], axis_name="data",
                                    average=False, threshold=1 << 20)

    tree = [np.zeros((hvd.size(), s), np.float32) for s in sizes]
    text = jax.jit(jax.shard_map(
        flat, mesh=hvd.mesh(), in_specs=P("data"),
        out_specs=P())).lower(tree).as_text()
    assert f"tensor<{sum(sizes)}xf32>) -> tensor<" in text
