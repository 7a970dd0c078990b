"""Tests for horovod_tpu.parallel — tp/sp/pp/ep over the virtual 8-device
CPU mesh (same harness as the collective tests, SURVEY §4)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import parallel as par


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------

class TestMesh:
    def test_default_absorbs_data(self):
        mesh = par.make_mesh()
        assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
            "pipe": 1, "data": 8, "seq": 1, "expert": 1, "model": 1}

    def test_explicit_axes(self):
        mesh = par.make_mesh(data=2, seq=2, model=2)
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        assert shape["data"] == 2 and shape["seq"] == 2
        assert shape["model"] == 2 and shape["pipe"] == 1

    def test_bad_product_raises(self):
        with pytest.raises(ValueError):
            par.make_mesh(data=3, model=2)
        with pytest.raises(ValueError):
            par.MeshSpec(data=-1, seq=-1).resolve(8)

    def test_shard_batch_and_replicate(self):
        mesh = par.make_mesh(data=4, model=2)
        x = np.arange(32, dtype=np.float32).reshape(8, 4)
        xs = par.shard_batch(mesh, x)
        assert xs.sharding.spec == P("data")
        w = par.replicate(mesh, {"w": np.ones((3,), np.float32)})
        assert w["w"].sharding.spec == P()


# ---------------------------------------------------------------------------
# tensor parallel
# ---------------------------------------------------------------------------

class TestTensorParallel:
    def test_column_row_pair_matches_dense(self):
        """Explicit shard_map column→row pair == plain two-layer matmul."""
        mesh = par.make_mesh(data=2, model=4)
        rng = np.random.RandomState(0)
        x = rng.randn(8, 16).astype(np.float32)
        w1 = rng.randn(16, 32).astype(np.float32)
        w2 = rng.randn(32, 16).astype(np.float32)

        def spmd(x, w1, w2):
            h = par.column_parallel_matmul(x, w1)
            return par.row_parallel_matmul(h, w2)

        out = jax.jit(jax.shard_map(
            spmd, mesh=mesh,
            in_specs=(P("data"), P(None, "model"), P("model", None)),
            out_specs=P("data")))(x, w1, w2)
        np.testing.assert_allclose(np.asarray(out), (x @ w1) @ w2,
                                   rtol=2e-5, atol=2e-5)

    def test_parallel_mlp_matches_unsharded(self):
        """GSPMD ParallelMLP on a TP mesh == same module on 1 device."""
        mesh = par.make_mesh(data=2, model=4)
        mlp = par.ParallelMLP(hidden=64, out=16)
        x = np.random.RandomState(1).randn(8, 16).astype(np.float32)
        variables = mlp.init(jax.random.PRNGKey(0), x)
        want = mlp.apply(par.unbox(variables), x)

        sharded_params = par.shard_params(mesh, variables)
        xs = par.shard_batch(mesh, x)
        with par.use_mesh(mesh):
            got = jax.jit(mlp.apply)(sharded_params, xs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_parallel_attention_matches_unsharded(self):
        mesh = par.make_mesh(data=2, model=4)
        attn = par.ParallelSelfAttention(num_heads=4, head_dim=8)
        x = np.random.RandomState(2).randn(2, 10, 32).astype(np.float32)
        variables = attn.init(jax.random.PRNGKey(0), x)
        want = attn.apply(par.unbox(variables), x)
        sharded_params = par.shard_params(mesh, variables)
        xs = par.shard_batch(mesh, x)
        with par.use_mesh(mesh):
            got = jax.jit(attn.apply)(sharded_params, xs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_param_specs(self):
        mlp = par.ParallelMLP(hidden=8, out=4)
        v = mlp.init(jax.random.PRNGKey(0), jnp.ones((1, 4)))
        specs = par.param_specs(v)
        assert specs["params"]["wi"]["kernel"] == P(None, "model")
        assert specs["params"]["wo"]["kernel"] == P("model", None)


class TestCollectiveMatmul:
    """Ring-overlapped AG/RS matmuls == their monolithic forms —
    forward and gradient — over even (8, 4, 2) and odd axis sizes."""

    def _ag_case(self, mesh, axis, x, w):
        # Rows of x sharded over the ring, w column-sharded (the
        # sequence-parallel column layer's layout); every device ends
        # with the FULL row range of its column shard.
        got = jax.jit(jax.shard_map(
            functools.partial(par.allgather_matmul, axis_name=axis),
            mesh=mesh, in_specs=(P(axis, None), P(None, axis)),
            out_specs=P(None, axis)))(x, w)
        np.testing.assert_allclose(np.asarray(got), x @ w,
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("tp", [2, 4, 8])
    def test_allgather_matmul_matches_gather_then_matmul(self, tp):
        mesh = par.make_mesh(model=tp, data=8 // tp)
        rng = np.random.RandomState(0)
        self._ag_case(mesh, "model",
                      rng.randn(16, 12).astype(np.float32),
                      rng.randn(12, 16).astype(np.float32))

    def test_allgather_matmul_odd_axis(self):
        # Odd ring: the bidirectional streams never collide, and the
        # final half-step (even-N special case) must not fire.
        if jax.device_count() < 5:
            pytest.skip("needs 5 virtual devices")
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:5]), ("model",))
        rng = np.random.RandomState(3)
        self._ag_case(mesh, "model",
                      rng.randn(15, 8).astype(np.float32),
                      rng.randn(8, 10).astype(np.float32))

    @pytest.mark.parametrize("tp", [2, 4, 8])
    def test_matmul_reducescatter_matches_matmul_then_scatter(self, tp):
        mesh = par.make_mesh(model=tp, data=8 // tp)
        rng = np.random.RandomState(1)
        R, K, F = 16, 16, 10
        x = rng.randn(R, K).astype(np.float32)
        w = rng.randn(K, F).astype(np.float32)
        got = jax.jit(jax.shard_map(
            functools.partial(par.matmul_reducescatter,
                              axis_name="model"),
            mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
            out_specs=P("model", None)))(x, w)
        np.testing.assert_allclose(np.asarray(got), x @ w,
                                   rtol=2e-5, atol=2e-5)

    def test_matmul_reducescatter_rejects_indivisible(self):
        mesh = par.make_mesh(model=4, data=2)
        x = jnp.ones((10, 8))   # 10 % 4 != 0
        w = jnp.ones((8, 6))
        with pytest.raises(ValueError, match="not divisible"):
            jax.jit(jax.shard_map(
                par.matmul_reducescatter, mesh=mesh,
                in_specs=(P(None, "model"), P("model", None)),
                out_specs=P("model", None)))(x, w)

    @pytest.mark.parametrize("tp", [4, 5])
    def test_collective_matmul_grads_match(self, tp):
        """d/dx, d/dw of the overlapped sequence-parallel pair
        (AG-matmul up, matmul-RS down) == the monolithic pair's —
        at an even ring (the half-step dedup branch fires) and an odd
        one (it must not)."""
        if tp == 5:
            if jax.device_count() < 5:
                pytest.skip("needs 5 virtual devices")
            from jax.sharding import Mesh
            mesh = Mesh(np.array(jax.devices()[:5]), ("model",))
        else:
            mesh = par.make_mesh(model=4, data=2)
        rng = np.random.RandomState(2)
        x = rng.randn(4 * tp, 12).astype(np.float32)
        w1 = rng.randn(12, 4 * tp).astype(np.float32)
        w2 = rng.randn(4 * tp, 12).astype(np.float32)
        specs = (P("model", None), P(None, "model"), P("model", None))

        def overlapped(x, w1, w2):
            h = par.allgather_matmul(x, w1, axis_name="model")
            return par.matmul_reducescatter(h, w2, axis_name="model")

        def monolithic(x, w1, w2):
            full = lax.all_gather(x, "model", tiled=True)
            h = full @ w1
            return lax.psum_scatter(h @ w2, "model", tiled=True)

        def loss(fn):
            def f(x, w1, w2):
                out = jax.shard_map(fn, mesh=mesh, in_specs=specs,
                                    out_specs=P("model", None))(x, w1, w2)
                return jnp.sum(out * out)
            return jax.jit(jax.grad(f, argnums=(0, 1, 2)))

        got = loss(overlapped)(x, w1, w2)
        want = loss(monolithic)(x, w1, w2)
        for g, wnt in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(wnt),
                                       rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# sequence parallel
# ---------------------------------------------------------------------------

def _ref_attention(q, k, v, causal):
    mask = None
    if causal:
        S = q.shape[1]
        mask = np.tril(np.ones((S, S), bool))[None, None]
    return np.asarray(par.dot_product_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        None if mask is None else jnp.asarray(mask)))


class TestSequenceParallel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_blockwise_matches_full(self, causal):
        rng = np.random.RandomState(0)
        q = rng.randn(2, 24, 2, 8).astype(np.float32)
        k = rng.randn(2, 24, 2, 8).astype(np.float32)
        v = rng.randn(2, 24, 2, 8).astype(np.float32)
        got = par.blockwise_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), block_size=7,
                                      causal=causal)
        np.testing.assert_allclose(np.asarray(got),
                                   _ref_attention(q, k, v, causal),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_attention_matches_full(self, causal):
        mesh = par.make_mesh(data=2, seq=4)
        rng = np.random.RandomState(1)
        q = rng.randn(2, 32, 2, 8).astype(np.float32)
        k = rng.randn(2, 32, 2, 8).astype(np.float32)
        v = rng.randn(2, 32, 2, 8).astype(np.float32)
        spec = P("data", "seq", None, None)
        fn = jax.jit(jax.shard_map(
            functools.partial(par.ring_attention, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
        got = fn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(np.asarray(got),
                                   _ref_attention(q, k, v, causal),
                                   rtol=2e-5, atol=2e-5)

    def test_ring_attention_gspmd(self):
        mesh = par.make_mesh(data=2, seq=2, model=2)
        rng = np.random.RandomState(2)
        q = rng.randn(2, 16, 4, 8).astype(np.float32)
        k = rng.randn(2, 16, 4, 8).astype(np.float32)
        v = rng.randn(2, 16, 4, 8).astype(np.float32)
        got = jax.jit(functools.partial(
            par.ring_attention_gspmd, mesh, causal=True))(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(np.asarray(got),
                                   _ref_attention(q, k, v, True),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ulysses_matches_full(self, causal):
        mesh = par.make_mesh(data=2, seq=4)
        rng = np.random.RandomState(3)
        q = rng.randn(2, 32, 4, 8).astype(np.float32)  # H=4 % sp=4 == 0
        k = rng.randn(2, 32, 4, 8).astype(np.float32)
        v = rng.randn(2, 32, 4, 8).astype(np.float32)
        spec = P("data", "seq", None, None)
        fn = jax.jit(jax.shard_map(
            functools.partial(par.ulysses_attention, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
        got = fn(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(np.asarray(got),
                                   _ref_attention(q, k, v, causal),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal,window", [(False, None),
                                               (True, None), (True, 6)])
    def test_ring_flash_matches_full(self, causal, window):
        """Ring attention with the Pallas kernel per rotation
        (block_impl='flash'): logsumexp-merged partials equal full
        attention, fwd and grads, for non-causal, causal, and
        sliding-window — including the lse-cotangent path through
        `flash_attention_lse`'s fused VJP."""
        mesh = par.make_mesh(seq=4, data=2)
        rng = np.random.RandomState(2)
        q, k, v = (jnp.asarray(rng.randn(2, 32, 2, 8), jnp.float32)
                   for _ in range(3))
        spec = P("data", "seq", None, None)
        S = q.shape[1]
        mask = None
        if causal:
            from horovod_tpu.parallel.sequence import banded_causal_mask
            mask = banded_causal_mask(jnp.arange(S), jnp.arange(S),
                                      window)[None, None]
        fn = functools.partial(par.ring_attention, causal=causal,
                               window=window, block_impl="flash")
        sm = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec)
        # jitted: an eager shard_map compiles a primitive at a time
        got = jax.jit(sm)(q, k, v)
        ref = par.dot_product_attention(q, k, v, mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

        g1 = jax.jit(jax.grad(
            lambda q, k, v: (sm(q, k, v) ** 2).sum(),
            argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.jit(jax.grad(
            lambda q, k, v: (par.dot_product_attention(
                q, k, v, mask) ** 2).sum(),
            argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_ring_flash_bf16_causal(self):
        """bf16 inputs through the causal lax.cond path (regression:
        the empty-partial branch built its lse in q.dtype, so bf16
        tripped the cond's equal-output-types check)."""
        mesh = par.make_mesh(seq=4, data=2)
        rng = np.random.RandomState(3)
        q, k, v = (jnp.asarray(rng.randn(2, 32, 2, 8), jnp.bfloat16)
                   for _ in range(3))
        spec = P("data", "seq", None, None)
        fn = functools.partial(par.ring_attention, causal=True,
                               block_impl="flash")
        got = jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=spec))(q, k, v)
        assert got.dtype == jnp.bfloat16
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
        ref = par.dot_product_attention(q, k, v, mask)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=3e-2)  # bf16 tolerance

    def test_ring_flash_rejects_bad_block_impl(self):
        q = jnp.zeros((1, 8, 1, 4))
        with pytest.raises(ValueError, match="block_impl"):
            par.ring_attention(q, q, q, block_impl="nope")

    def test_ulysses_flash_pallas_bwd_grads(self):
        """The flagship long-context composition: Ulysses SP with the
        Pallas flash kernel (fused backward) as attn_impl — gradients
        through shard_map + all_to_all match the full oracle, under
        shard_map's default check_vma=True (the kernels propagate
        varying-manual-axes into their out_shapes)."""
        from horovod_tpu.ops.flash_attention import flash_attention
        mesh = par.make_mesh(seq=4, data=2)
        rng = np.random.RandomState(7)
        q, k, v = (jnp.asarray(rng.randn(2, 32, 4, 8), jnp.float32)
                   for _ in range(3))
        spec = P("data", "seq", None, None)

        def loss_ul(q, k, v):
            o = jax.shard_map(functools.partial(
                par.ulysses_attention, causal=True,
                attn_impl=functools.partial(flash_attention,
                                            block_q=8, block_k=8)),
                mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec)(q, k, v)
            return (o ** 2).sum()

        def loss_ref(q, k, v):
            S = q.shape[1]
            m = jnp.tril(jnp.ones((S, S), bool))[None, None]
            return (par.dot_product_attention(q, k, v, m) ** 2).sum()

        g1 = jax.jit(jax.grad(loss_ul, argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_ulysses_grouped_kv_non_gqa_impl_repeats(self):
        """Grouped K/V (GQA) through ulysses with a NON-GQA-native
        attn_impl (the default blockwise path): K/V are repeated to
        full head count after the all_to_all instead of dying on an
        opaque downstream shape error (advisor r3 #3)."""
        mesh = par.make_mesh(data=4, seq=2)
        rng = np.random.RandomState(11)
        q = jnp.asarray(rng.randn(4, 16, 4, 8), jnp.float32)
        k = jnp.asarray(rng.randn(4, 16, 2, 8), jnp.float32)  # Hkv=2
        v = jnp.asarray(rng.randn(4, 16, 2, 8), jnp.float32)
        spec = P("data", "seq", None, None)
        got = jax.shard_map(
            functools.partial(par.ulysses_attention, causal=True),
            mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=spec)(q, k, v)
        ref = _ref_attention(np.asarray(q),
                             np.repeat(np.asarray(k), 2, axis=2),
                             np.repeat(np.asarray(v), 2, axis=2), True)
        np.testing.assert_allclose(np.asarray(got), ref,
                                   rtol=2e-5, atol=2e-5)

    def test_ulysses_rejects_windowless_custom_attn_impl(self):
        """window= with a custom attn_impl that can't take it must be a
        clear ValueError naming the contract, not a TypeError from
        inside the shard_map trace (advisor r2 #4)."""
        mesh = par.make_mesh(seq=4, data=2)
        rng = np.random.RandomState(5)
        q = jnp.asarray(rng.randn(2, 32, 4, 8), jnp.float32)
        spec = P("data", "seq", None, None)

        def no_window_impl(q, k, v, *, causal=False):
            return par.dot_product_attention(q, k, v)

        fn = jax.shard_map(
            functools.partial(par.ulysses_attention, causal=True,
                              window=4, attn_impl=no_window_impl),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        with pytest.raises(ValueError, match="window"):
            fn(q, q, q)
        # …and an impl that does take window= still composes.
        ok = jax.shard_map(
            functools.partial(
                par.ulysses_attention, causal=True, window=4,
                attn_impl=functools.partial(par.blockwise_attention,
                                            block_size=8)),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        got = ok(q, q, q)
        assert np.isfinite(np.asarray(got)).all()

    def test_ring_attention_grad(self):
        """Gradients flow through the ppermute ring."""
        mesh = par.make_mesh(seq=4, data=2)
        rng = np.random.RandomState(4)
        q = rng.randn(2, 16, 2, 4).astype(np.float32)
        k = rng.randn(2, 16, 2, 4).astype(np.float32)
        v = rng.randn(2, 16, 2, 4).astype(np.float32)
        spec = P("data", "seq", None, None)

        def loss_ring(q, k, v):
            o = jax.shard_map(
                functools.partial(par.ring_attention, causal=True),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            )(q, k, v)
            return (o ** 2).sum()

        def loss_ref(q, k, v):
            S = q.shape[1]
            m = jnp.tril(jnp.ones((S, S), bool))[None, None]
            return (par.dot_product_attention(q, k, v, m) ** 2).sum()

        g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        g2 = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# pipeline parallel
# ---------------------------------------------------------------------------

class TestPipelineParallel:
    def _stage_fn(self, params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    def _make(self, nstages, d):
        rng = np.random.RandomState(5)
        per_stage = [
            {"w": rng.randn(d, d).astype(np.float32) * 0.5,
             "b": rng.randn(d).astype(np.float32) * 0.1}
            for _ in range(nstages)]
        stacked = par.PipelineStage.stack(
            [jax.tree.map(jnp.asarray, p) for p in per_stage])
        return per_stage, stacked

    def test_matches_sequential(self):
        mesh = par.make_mesh(pipe=4, data=2)
        d, M, mb = 8, 8, 4
        per_stage, stacked = self._make(4, d)
        x = np.random.RandomState(6).randn(M, mb, d).astype(np.float32)

        got = jax.jit(functools.partial(
            par.pipeline_apply_gspmd, mesh, self._stage_fn))(
                stacked, jnp.asarray(x))

        want = x.copy()
        for p in per_stage:
            want = np.tanh(want @ p["w"] + p["b"])
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=2e-5, atol=2e-5)

    def test_gradient_matches_sequential(self):
        mesh = par.make_mesh(pipe=4, data=2)
        d, M, mb = 4, 8, 2
        per_stage, stacked = self._make(4, d)
        x = jnp.asarray(
            np.random.RandomState(7).randn(M, mb, d).astype(np.float32))

        def loss_pp(stacked, x):
            y = par.pipeline_apply_gspmd(mesh, self._stage_fn, stacked, x)
            return (y ** 2).mean()

        def loss_seq(stacked, x):
            y = x
            for i in range(4):
                p = jax.tree.map(lambda a: a[i], stacked)
                y = self._stage_fn(p, y)
            return (y ** 2).mean()

        g1 = jax.jit(jax.grad(loss_pp))(stacked, x)
        g2 = jax.jit(jax.grad(loss_seq))(stacked, x)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4),
            g1, g2)

    def test_unstack_roundtrip(self):
        _, stacked = self._make(4, 4)
        stages = par.PipelineStage.unstack(stacked)
        assert len(stages) == 4
        re = par.PipelineStage.stack(stages)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), re, stacked)

    @pytest.mark.parametrize("v", [2, 3])
    def test_interleaved_matches_sequential(self, v):
        """Interleaved schedule (v chunks/device, S = v*P global
        stages) is numerically the same program as running the S
        stages sequentially — the GPipe-path oracle."""
        P_, M, mb, d = 4, 8, 2, 6
        mesh = par.make_mesh(pipe=P_, data=2)
        per_stage, _ = self._make(v * P_, d)
        inter = par.PipelineStage.stack_interleaved(
            [jax.tree.map(jnp.asarray, p) for p in per_stage], P_)
        assert jax.tree.leaves(inter)[0].shape[:2] == (P_, v)
        x = np.random.RandomState(8).randn(M, mb, d).astype(np.float32)

        got = jax.jit(functools.partial(
            par.pipeline_apply_gspmd, mesh, self._stage_fn,
            num_chunks=v))(inter, jnp.asarray(x))

        want = x.copy()
        for p in per_stage:  # global stage order
            want = np.tanh(want @ p["w"] + p["b"])
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=2e-5, atol=2e-5)

    def test_interleaved_gradient_matches_sequential(self):
        P_, v, M, mb, d = 2, 2, 4, 4, 4
        mesh = par.make_mesh(pipe=P_, data=4)
        per_stage, _ = self._make(v * P_, d)
        inter = par.PipelineStage.stack_interleaved(
            [jax.tree.map(jnp.asarray, p) for p in per_stage], P_)
        x = jnp.asarray(
            np.random.RandomState(9).randn(M, mb, d).astype(np.float32))

        def loss_pp(inter, x):
            y = par.pipeline_apply_gspmd(mesh, self._stage_fn, inter, x,
                                         num_chunks=v)
            return (y ** 2).mean()

        def loss_seq(inter, x):
            y = x
            for c in range(v):
                for dev in range(P_):  # global stage c*P + dev
                    p = jax.tree.map(lambda a: a[dev, c], inter)
                    y = self._stage_fn(p, y)
            return (y ** 2).mean()

        g1 = jax.jit(jax.grad(loss_pp))(inter, x)
        g2 = jax.jit(jax.grad(loss_seq))(inter, x)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4),
            g1, g2)

    @pytest.mark.parametrize("v", [1, 2])
    def test_remat_matches_and_bounds_residuals(self, v):
        """remat=True: gradients are bit-compatible with the plain
        path, and the backward's per-tick residuals shrink from every
        stage INTERIOR intermediate to just the stage input — the
        memory-bounding promise of `pipeline_apply(remat=)`. Measured structurally: the forward scan's
        stacked [ticks, ...] residual outputs in the grad jaxpr.
        v=2 additionally pins that the interleaved chunk-param
        indexing happens INSIDE the checkpoint (no [ticks, params]
        residual stack)."""
        mesh = par.make_mesh(pipe=4, data=2)
        d, hidden, M, mb = 8, 64, 8, 4
        P_ = 4
        ticks = v * M + P_ - 1

        def fat_stage(p, x):   # interior is hidden/d = 8x wider than x
            h = jnp.tanh(x @ p["w1"])
            h = jnp.tanh(h @ p["w2"])
            return jnp.tanh(h @ p["w3"])

        rng = np.random.RandomState(11)
        per_stage = [
            {"w1": jnp.asarray(rng.randn(d, hidden) * .3, jnp.float32),
             "w2": jnp.asarray(rng.randn(hidden, hidden) * .1,
                               jnp.float32),
             "w3": jnp.asarray(rng.randn(hidden, d) * .3, jnp.float32)}
            for _ in range(v * P_)]
        if v == 1:
            stacked = par.PipelineStage.stack(per_stage)
        else:
            stacked = par.PipelineStage.stack_interleaved(per_stage, P_)
        x = jnp.asarray(rng.randn(M, mb, d), jnp.float32)

        def residual_bytes(remat):
            def loss(sp, mbatch):
                y = par.pipeline_apply_gspmd(mesh, fat_stage, sp,
                                             mbatch, num_chunks=v,
                                             remat=remat)
                return (y ** 2).mean()
            jaxpr = jax.make_jaxpr(jax.grad(loss))(stacked, x)
            total = 0

            def walk(jx):
                nonlocal total
                for eqn in jx.eqns:
                    if eqn.primitive.name == "scan":
                        for ov in eqn.outvars:
                            shp = ov.aval.shape
                            if len(shp) > 1 and shp[0] == ticks:
                                total += (int(np.prod(shp))
                                          * ov.aval.dtype.itemsize)
                    for sub in eqn.params.values():
                        inner = getattr(sub, "jaxpr", sub)
                        if hasattr(inner, "eqns"):
                            walk(inner)

            walk(jaxpr.jaxpr)
            return total

        plain, bounded = residual_bytes(False), residual_bytes(True)
        # Plain stores interior (~3 x hidden wide) per tick; remat only
        # the d-wide stage input: expect ~(3*hidden+d)/d ~ 25x here.
        assert bounded > 0
        assert plain / bounded > 5, (plain, bounded)
        # Per-tick bound: with remat, residuals are O(ticks * input) —
        # in particular NO [ticks, chunk-params] stack at v=2 (a w2
        # slice alone would be ticks*hidden*hidden*4 ~ 3.1 MB >> this
        # bound).
        per_shard_mb = mb // 2  # data axis = 2
        input_bytes = ticks * per_shard_mb * d * 4
        assert bounded <= 4 * input_bytes, (bounded, input_bytes)

        def loss(remat):
            def f(sp, mbatch):
                y = par.pipeline_apply_gspmd(mesh, fat_stage, sp,
                                             mbatch, num_chunks=v,
                                             remat=remat)
                return (y ** 2).mean()
            return f

        g1 = jax.jit(jax.grad(loss(False)))(stacked, x)
        g2 = jax.jit(jax.grad(loss(True)))(stacked, x)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
            g1, g2)

    def test_interleaved_remat_matches(self):
        """remat composes with the interleaved (v>1) schedule."""
        P_, v, M, mb, d = 2, 2, 4, 4, 4
        mesh = par.make_mesh(pipe=P_, data=4)
        per_stage, _ = self._make(v * P_, d)
        inter = par.PipelineStage.stack_interleaved(
            [jax.tree.map(jnp.asarray, p) for p in per_stage], P_)
        x = jnp.asarray(
            np.random.RandomState(12).randn(M, mb, d).astype(np.float32))

        def loss(remat):
            def f(sp, mbatch):
                y = par.pipeline_apply_gspmd(
                    mesh, self._stage_fn, sp, mbatch,
                    num_chunks=v, remat=remat)
                return (y ** 2).mean()
            return f

        g1 = jax.jit(jax.grad(loss(False)))(inter, x)
        g2 = jax.jit(jax.grad(loss(True)))(inter, x)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4),
            g1, g2)

    def test_interleaved_rejects_ragged_microbatches(self):
        mesh = par.make_mesh(pipe=4, data=2)
        per_stage, _ = self._make(8, 4)
        inter = par.PipelineStage.stack_interleaved(
            [jax.tree.map(jnp.asarray, p) for p in per_stage], 4)
        x = jnp.zeros((6, 2, 4), jnp.float32)  # 6 % 4 != 0
        with pytest.raises(ValueError, match="microbatches % pipe"):
            par.pipeline_apply_gspmd(mesh, self._stage_fn, inter, x,
                                     num_chunks=2)


# ---------------------------------------------------------------------------
# expert parallel
# ---------------------------------------------------------------------------

class TestExpertParallel:
    def test_top_k_gating(self):
        logits = jnp.asarray(
            np.random.RandomState(8).randn(16, 4).astype(np.float32))
        gates, idx, aux = par.top_k_gating(logits, 2)
        assert gates.shape == (16, 2) and idx.shape == (16, 2)
        np.testing.assert_allclose(np.asarray(gates.sum(-1)),
                                   np.ones(16), rtol=1e-6)
        assert float(aux) >= 1.0 - 1e-6  # E·Σ f·p ≥ 1 (uniform optimum)

    def test_moe_layer_sharded_matches_unsharded(self):
        mesh = par.make_mesh(data=2, expert=4)
        moe = par.MoELayer(num_experts=4, hidden=32, k=2,
                           capacity_factor=2.0)
        x = np.random.RandomState(9).randn(4, 8, 16).astype(np.float32)
        variables = moe.init(jax.random.PRNGKey(0), x)
        want = moe.apply(par.unbox(variables), x)
        sharded_params = par.shard_params(mesh, variables)
        xs = par.shard_batch(mesh, x)
        with par.use_mesh(mesh):
            got = jax.jit(moe.apply)(sharded_params, xs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_moe_capacity_drops_are_bounded(self):
        """With capacity_factor ≥ E/k·(worst skew) nothing is dropped;
        with tiny capacity the layer still runs and outputs are finite."""
        moe = par.MoELayer(num_experts=2, hidden=8, k=1,
                           capacity_factor=0.25)
        x = np.random.RandomState(10).randn(2, 8, 4).astype(np.float32)
        v = moe.init(jax.random.PRNGKey(1), x)
        y = moe.apply(par.unbox(v), x)
        assert np.isfinite(np.asarray(y)).all()

    def test_alltoall_dispatch_roundtrip(self):
        mesh = par.make_mesh(expert=4, data=2)
        rng = np.random.RandomState(11)
        # Global view: capacity dim stacks the 4 expert-ranks' local
        # [E=4, C_local=6, d] dispatch buffers.
        buf = rng.randn(4, 4 * 6, 8).astype(np.float32)

        def body(b):
            shuffled = par.expert_alltoall_dispatch(b)
            assert shuffled.shape == (1, 4 * 6, 8)  # my expert, all ranks
            return par.expert_alltoall_combine(shuffled)

        spec = P(None, "expert", None)
        out = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(spec,), out_specs=spec))(
                jnp.asarray(buf))
        np.testing.assert_allclose(np.asarray(out), buf, rtol=1e-6)

    def test_moe_aux_loss_sown(self):
        moe = par.MoELayer(num_experts=4, hidden=8, k=2)
        x = jnp.ones((2, 4, 8))
        v = moe.init(jax.random.PRNGKey(2), x)
        y, state = moe.apply(par.unbox(v), x, mutable=["losses"])
        leaves = jax.tree.leaves(state["losses"])
        assert leaves and all(np.isfinite(float(a)) for a in leaves)
