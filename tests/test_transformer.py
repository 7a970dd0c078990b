"""Flagship transformer + flash-attention kernel tests.

Extends the reference's correctness strategy (`mpi_ops_test.py`: exact
equality of the distributed result against a locally-computable oracle,
SURVEY §4) to the TPU-native model stack: every attention kernel and
every parallelism composition must match the materialized-softmax
baseline, and the full multi-axis train step must match a single-device
replica of the same model.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import (
    TransformerLM, TransformerBlockStack, init_lm_state, lm_loss,
    make_lm_train_step,
)
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.mesh import make_mesh
from horovod_tpu.parallel.tensor import dot_product_attention


def _qkv(B=2, S=64, H=4, D=16, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, S, H, D), dtype)
                 for _ in range(3))


class TestFlashAttention:
    def test_matches_reference(self):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, block_q=16, block_k=16)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_causal_matches_reference(self):
        q, k, v = _qkv(seed=1)
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))[None, None]
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        ref = dot_product_attention(q, k, v, mask)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_uneven_block_sizes(self):
        q, k, v = _qkv(S=80, seed=2)
        out = flash_attention(q[:, :50], k, v, block_q=32, block_k=32)
        ref = dot_product_attention(q[:, :50], k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    @pytest.mark.parametrize("bwd_impl", ["pallas", "recompute"])
    def test_gradients_match_reference(self, bwd_impl):
        """Both backward implementations — the fused Pallas kernels
        (default) and the blockwise recompute fallback — match the
        materialized-softmax oracle."""
        q, k, v = _qkv(S=32, seed=3)
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))[None, None]

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, causal=True, block_q=16,
                                    block_k=16,
                                    bwd_impl=bwd_impl) ** 2).sum()

        def loss_ref(q, k, v):
            return (dot_product_attention(q, k, v, mask) ** 2).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=1e-4)

    @pytest.mark.parametrize("case", ["full", "uneven", "cross",
                                      "offset"])
    def test_pallas_bwd_shapes_and_offsets(self, case):
        """The fused backward across the fwd kernel's shape edge
        cases: non-causal full, pad tails on both axes, Sq != Sk, and
        ring-style global offsets."""
        causal, S, Sk, qo, seed = {
            "full": (False, 48, 48, 0, 101),
            "uneven": (True, 50, 50, 0, 102),
            "cross": (False, 32, 80, 0, 103),
            "offset": (True, 32, 32, 32, 104),
        }[case]
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(2, S, 2, 16), jnp.float32)
        k = jnp.asarray(rng.randn(2, Sk, 2, 16), jnp.float32)
        v = jnp.asarray(rng.randn(2, Sk, 2, 16), jnp.float32)
        mask = None
        if causal:
            pos_q = qo + jnp.arange(S)
            mask = (pos_q[:, None] >= jnp.arange(Sk)[None, :]
                    )[None, None]

        def lf(q, k, v):
            return (flash_attention(
                q, k, v, causal=causal, q_offset=qo, block_q=16,
                block_k=16, bwd_impl="pallas") ** 2).sum()

        def lr(q, k, v):
            return (dot_product_attention(q, k, v, mask) ** 2).sum()

        gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    def test_pallas_bwd_composes_with_window(self):
        """The fused backward under a sliding window (the default —
        auto resolves to 'pallas' with banded backward sweeps) matches
        the banded oracle."""
        from horovod_tpu.parallel.sequence import banded_causal_mask
        q, k, v = _qkv(S=64, seed=9)
        pos = jnp.arange(64)
        mask = banded_causal_mask(pos, pos, 8)[None, None]

        def lf(q, k, v):
            return (flash_attention(
                q, k, v, causal=True, window=8, block_q=16,
                block_k=16, bwd_impl="pallas") ** 2).sum()

        def lr(q, k, v):
            return (dot_product_attention(q, k, v, mask) ** 2).sum()

        gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("bwd_impl", ["pallas", "recompute"])
    @pytest.mark.parametrize("window", [None, 8])
    def test_native_gqa(self, bwd_impl, window):
        """K/V at Hkv < H heads consumed natively (index-mapped kv
        head h//group, never a materialized repeat): fwd and both
        backward impls match the repeated-KV oracle, with and without
        a sliding window."""
        rng = np.random.RandomState(4)
        B, S, H, Hkv, D = 2, 48, 8, 2, 16
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
        g = H // Hkv
        from horovod_tpu.parallel.sequence import banded_causal_mask
        mask = banded_causal_mask(jnp.arange(S), jnp.arange(S),
                                  window)[None, None]

        out = flash_attention(q, k, v, causal=True, window=window,
                              block_q=16, block_k=16,
                              bwd_impl=bwd_impl)
        ref = dot_product_attention(q, jnp.repeat(k, g, 2),
                                    jnp.repeat(v, g, 2), mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

        def lf(q, k, v):
            return (flash_attention(q, k, v, causal=True,
                                    window=window, block_q=16,
                                    block_k=16,
                                    bwd_impl=bwd_impl) ** 2).sum()

        def lr(q, k, v):
            return (dot_product_attention(
                q, jnp.repeat(k, g, 2), jnp.repeat(v, g, 2),
                mask) ** 2).sum()

        gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            assert a.shape == b.shape  # dk/dv at Hkv width
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4)

    def test_gqa_rejects_nondivisible_heads(self):
        q, k, v = _qkv(S=16, H=4)
        with pytest.raises(ValueError, match="kv heads"):
            flash_attention(q, k[:, :, :3], v[:, :, :3], causal=True)

    def test_bwd_impl_validation_and_env_override(self, monkeypatch):
        q, k, v = _qkv(S=16)
        with pytest.raises(ValueError, match="bwd_impl"):
            flash_attention(q, k, v, bwd_impl="nope")
        # env escape hatch: auto must RESOLVE to recompute (spy on the
        # config factory — finiteness alone would pass either way).
        from horovod_tpu.ops import flash_attention as fa
        resolved = []
        orig = fa._make_flash

        def spy(*a):
            resolved.append(a[-1])
            return orig(*a)

        monkeypatch.setattr(fa, "_make_flash", spy)
        monkeypatch.setenv("HOROVOD_FLASH_BWD", "recompute")
        out = fa.flash_attention(q, k, v, causal=True, block_q=16,
                                 block_k=16)
        assert resolved == ["recompute"], resolved
        assert np.isfinite(np.asarray(out)).all()
        monkeypatch.delenv("HOROVOD_FLASH_BWD")
        fa.flash_attention(q, k, v, causal=True, block_q=16,
                           block_k=16)
        assert resolved[-1] == "pallas", resolved

    def test_offsets_for_rotated_blocks(self):
        # Ring-attention style: keys are a rotated block with a global
        # offset; causal masking must follow global positions.
        q, k, v = _qkv(S=32, seed=4)
        out = flash_attention(q, k, v, causal=True, q_offset=32,
                              k_offset=0, block_q=16, block_k=16)
        # q rows 32..63 vs keys 0..31: all visible => plain attention.
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5)
        out2 = flash_attention(q, k, v, causal=True, q_offset=0,
                               k_offset=32, block_q=16, block_k=16)
        # keys all in the future: output must be 0 (empty softmax).
        np.testing.assert_allclose(out2, jnp.zeros_like(out2), atol=0)

    def test_rejects_explicit_mask(self):
        q, k, v = _qkv(S=16)
        with pytest.raises(NotImplementedError):
            flash_attention(q, k, v, jnp.ones((16, 16), bool))


def _tiny_model(attn_impl, moe_every=0, dtype=jnp.float32):
    return TransformerLM(vocab_size=64, num_layers=2, num_heads=4,
                         head_dim=8, max_len=32, dtype=dtype,
                         attn_impl=attn_impl, moe_every=moe_every,
                         num_experts=4)


def _tokens(B=8, S=16, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, 64, (B, S)))


class TestTransformerLM:
    @pytest.mark.parametrize("attn_impl",
                             ["dot", "blockwise", "flash"])
    def test_forward_impls_agree(self, attn_impl):
        toks = _tokens()
        ref_model = _tiny_model("dot")
        variables = ref_model.init(jax.random.PRNGKey(0), toks)
        model = _tiny_model(attn_impl)
        logits = model.apply(variables, toks)
        ref = ref_model.apply(variables, toks)
        np.testing.assert_allclose(np.asarray(logits, np.float32),
                                   np.asarray(ref, np.float32), atol=2e-4)

    def test_gqa_flash_model_matches_dot(self):
        """TransformerLM(num_kv_heads<heads, attn_impl='flash'): the
        native-GQA kernel path (no repeated K/V materialization)
        matches the dot baseline — logits and grads."""
        toks = _tokens(B=2, S=16, seed=11)
        kw = dict(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
                  num_kv_heads=2, max_len=32, dtype=jnp.float32)
        dot_model = TransformerLM(attn_impl="dot", **kw)
        fla_model = TransformerLM(attn_impl="flash", **kw)
        # every side one program, not a compile a primitive
        variables = jax.jit(dot_model.init)(jax.random.PRNGKey(12), toks)
        a = jax.jit(dot_model.apply)(variables, toks)
        b = jax.jit(fla_model.apply)(variables, toks)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-4)

        from horovod_tpu.parallel.tensor import unbox
        params = unbox(variables["params"])
        g1 = jax.jit(jax.grad(lambda p: lm_loss(
            dot_model.apply({"params": p}, toks), toks)))(params)
        g2 = jax.jit(jax.grad(lambda p: lm_loss(
            fla_model.apply({"params": p}, toks), toks)))(params)
        jax.tree.map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), atol=2e-4, rtol=2e-3),
            g1, g2)

    @pytest.mark.parametrize("chunk", [5, 8, 32])
    def test_chunked_lm_loss_matches_plain(self, chunk):
        """The fused head+loss (no [B,S,V] logits materialization) is
        numerically the plain path: same loss, same grads — including
        ragged chunking (P=15 with chunk 5/8) and chunk > P."""
        from horovod_tpu.models.transformer import chunked_lm_loss
        toks = _tokens(B=4, S=16, seed=3)
        model = _tiny_model("dot")
        variables = jax.jit(model.init)(jax.random.PRNGKey(1), toks)
        from horovod_tpu.parallel.tensor import unbox
        params = unbox(variables["params"])

        def plain(p):
            return lm_loss(model.apply({"params": p}, toks), toks)

        def chunked(p):
            h, e = model.apply({"params": p}, toks, return_hidden=True)
            return chunked_lm_loss(h, e, toks, chunk=chunk)

        # one program a side: op by op, each primitive of the forward
        # and of its transpose compiled alone (28 s a case)
        l1, g1 = jax.jit(jax.value_and_grad(plain))(params)
        l2, g2 = jax.jit(jax.value_and_grad(chunked))(params)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
            g1, g2)

    def test_lm_train_step_loss_chunk_option(self, hvd):
        """make_lm_train_step(loss_chunk=...) trains identically to the
        plain loss for one step."""
        import optax
        # B divisible by the data axis — the standard SPMD input
        # contract (a ragged batch trips an XLA partitioner CHECK
        # under x64 inside the loss scan).
        toks = np.asarray(_tokens(B=8, S=16, seed=5))
        mesh = make_mesh(data=8)
        model = _tiny_model("blockwise")

        def one(loss_chunk):
            params, opt_state = init_lm_state(
                model, tx := optax.sgd(0.1), jax.random.PRNGKey(0),
                mesh, toks)
            step = make_lm_train_step(model, tx, mesh,
                                      loss_chunk=loss_chunk)
            params, _, loss = step(params, opt_state, toks)
            return float(loss), params

        l_plain, p_plain = one(None)
        l_chunk, p_chunk = one(8)
        np.testing.assert_allclose(l_plain, l_chunk, rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
            p_plain, p_chunk)

    def test_sharded_at_birth_init(self, hvd):
        """init_lm_state(sharded_init=True) jits the init with
        out_shardings so no device materializes the full tree; values
        must equal the default init path and TP leaves must actually
        land sharded over ``model``."""
        import optax
        toks = np.asarray(_tokens(B=8, S=16, seed=11))
        mesh = make_mesh(data=2, model=4)
        model = _tiny_model("blockwise")
        tx = optax.sgd(0.1)
        rng = jax.random.PRNGKey(3)
        p_ref, _ = init_lm_state(model, tx, rng, mesh, toks)
        p_sh, opt_sh = init_lm_state(model, tx, rng, mesh, toks,
                                     sharded_init=True)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
            p_ref, p_sh)
        embed = p_sh["embed"]
        spec = embed.sharding.spec
        assert "model" in str(spec), spec  # vocab-sharded at birth
        # and the state is usable: one train step runs.
        step = make_lm_train_step(model, tx, mesh)
        _, _, loss = step(p_sh, opt_sh, toks)
        assert np.isfinite(float(loss))

    @pytest.mark.parametrize("axes,attn_impl", [
        (dict(data=2, model=2, seq=2), "ring"),
        (dict(data=2, model=2, seq=2), "ulysses"),
        (dict(data=2, model=4), "blockwise"),
        (dict(data=8), "dot"),
    ])
    def test_sharded_forward_matches_single_device(self, hvd, axes,
                                                   attn_impl):
        """The multi-axis sharded forward equals the unsharded oracle —
        the reference's `allreduce == tensor*size` idea (mpi_ops_test.py:
        85-114) lifted to whole-model SPMD."""
        from horovod_tpu.parallel.mesh import use
        toks = _tokens()
        ref_model = _tiny_model("dot")
        variables = ref_model.init(jax.random.PRNGKey(0), toks)
        ref = ref_model.apply(variables, toks)

        mesh = make_mesh(**axes)
        model = _tiny_model(attn_impl)
        from horovod_tpu.parallel.tensor import shard_params
        with use(mesh):
            params = shard_params(mesh, variables["params"])
            toks_sh = jax.device_put(
                toks, NamedSharding(mesh, P("data", "seq")))
            logits = jax.jit(
                lambda p, t: model.apply({"params": p}, t))(
                    params["params"] if "params" in params else params,
                    toks_sh)
        np.testing.assert_allclose(np.asarray(logits, np.float32),
                                   np.asarray(ref, np.float32), atol=2e-4)

    def test_train_step_matches_single_device(self, hvd):
        """One multi-axis train step == one single-device step."""
        toks = _tokens()
        model = _tiny_model("blockwise")
        tx = optax.sgd(0.1)

        # Single-device oracle.
        variables = jax.jit(model.init)(jax.random.PRNGKey(0), toks)
        from horovod_tpu.parallel.tensor import unbox
        ref_params = unbox(variables["params"])

        @jax.jit        # one program, not a compile a primitive
        def ref_step(params, toks):
            loss, grads = jax.value_and_grad(
                lambda p: lm_loss(model.apply({"params": p}, toks),
                                  toks))(params)
            updates, _ = tx.update(grads, tx.init(params), params)
            return optax.apply_updates(params, updates), loss

        ref_new, ref_loss = ref_step(ref_params, toks)

        mesh = make_mesh(data=2, seq=2, model=2)
        params, opt_state = init_lm_state(
            model, tx, jax.random.PRNGKey(0), mesh, toks)
        step = make_lm_train_step(model, tx, mesh)
        toks_sh = jax.device_put(toks,
                                 NamedSharding(mesh, P("data", "seq")))
        new_params, _, loss = step(params, opt_state, toks_sh)

        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5)
        flat_new = jax.tree.leaves(new_params)
        flat_ref = jax.tree.leaves(ref_new)
        for a, b in zip(flat_new, flat_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    def test_moe_train_step_runs_and_improves(self, hvd):
        toks = _tokens()
        model = _tiny_model("blockwise", moe_every=2)
        tx = optax.adam(1e-2)
        mesh = make_mesh(data=2, expert=2, model=2)
        params, opt_state = init_lm_state(
            model, tx, jax.random.PRNGKey(0), mesh, toks)
        step = make_lm_train_step(model, tx, mesh)
        toks_sh = jax.device_put(toks,
                                 NamedSharding(mesh, P("data", None)))
        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, toks_sh)
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_param_sharding_layout(self, hvd):
        """TP/EP weights actually land sharded on the mesh (not just
        annotated): column kernels split over ``model``, expert weights
        over ``expert``."""
        toks = _tokens()
        model = _tiny_model("blockwise", moe_every=2)
        mesh = make_mesh(data=2, expert=2, model=2)
        params, _ = init_lm_state(model, tx := optax.sgd(0.1),
                                  jax.random.PRNGKey(0), mesh, toks)
        qkv = params["block_0"]["attn"]["qkv"]["kernel"]
        assert qkv.sharding.spec == P(None, "model")
        w1 = params["block_1"]["moe"]["w1"]
        assert w1.sharding.spec == P("expert", None, None)
        embed = params["embed"]
        assert embed.sharding.spec == P("model", None)

    def test_remat_variant_runs(self, hvd):
        toks = _tokens()
        model = TransformerLM(vocab_size=64, num_layers=2, num_heads=4,
                              head_dim=8, max_len=32, dtype=jnp.float32,
                              attn_impl="blockwise", remat=True)
        tx = optax.sgd(0.1)
        mesh = make_mesh(data=4, model=2)
        params, opt_state = init_lm_state(
            model, tx, jax.random.PRNGKey(0), mesh, toks)
        step = make_lm_train_step(model, tx, mesh)
        toks_sh = jax.device_put(toks,
                                 NamedSharding(mesh, P("data", None)))
        _, _, loss = step(params, opt_state, toks_sh)
        assert np.isfinite(float(loss))


class TestBf16Flagship:
    @pytest.mark.parametrize("attn_impl", ["flash", "ring_flash"])
    def test_bf16_train_step_decreases(self, hvd, attn_impl):
        """The flagship configs at their PRODUCTION dtype (bf16 —
        most oracle tests run f32): full train step over dp x sp x tp,
        finite and decreasing loss. Guards dtype drift like the
        bf16-vs-f32 lse branch mismatch the f32 suite can't see."""
        mesh = make_mesh(data=2, seq=2, model=2)
        model = TransformerLM(vocab_size=64, num_layers=2, num_heads=4,
                              head_dim=8, num_kv_heads=2,
                              pos_emb="rope", window=8,
                              max_len=32, dtype=jnp.bfloat16,
                              attn_impl=attn_impl)
        toks = _tokens(B=4, S=16, seed=40)
        tx = optax.adamw(1e-2)
        params, opt_state = init_lm_state(
            model, tx, jax.random.PRNGKey(0), mesh, toks)
        step = make_lm_train_step(model, tx, mesh)
        toks_sh = jax.device_put(
            toks, NamedSharding(mesh, P("data", "seq")))
        losses = []
        for _ in range(4):
            params, opt_state, loss = step(params, opt_state, toks_sh)
            losses.append(float(loss))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses


class TestPipelineTransformer:
    def test_blockstack_pipeline_matches_sequential(self, hvd):
        """GPipe over ``pipe`` on transformer blocks == applying the
        stages sequentially on one device."""
        from horovod_tpu.parallel.pipeline import (
            PipelineStage, pipeline_apply_gspmd)
        from horovod_tpu.parallel.tensor import unbox

        B, S, H, D = 4, 16, 2, 8
        d = H * D
        stage = TransformerBlockStack(num_heads=H, head_dim=D,
                                      dtype=jnp.float32,
                                      attn_impl="blockwise")
        x = jnp.asarray(np.random.RandomState(0).randn(8, B, S, d),
                        jnp.float32)  # [M, mb, S, d] microbatches

        keys = jax.random.split(jax.random.PRNGKey(0), 2)
        per_stage = [unbox(stage.init(k, x[0])["params"]) for k in keys]

        # Sequential oracle.
        ref = x
        for p in per_stage:
            ref = jax.vmap(
                lambda mb, p=p: stage.apply({"params": p}, mb))(ref)

        mesh = make_mesh(pipe=2, data=2, model=2)
        stacked = PipelineStage.stack(per_stage)

        def stage_fn(p, mb):
            return stage.apply({"params": p}, mb)

        from horovod_tpu.parallel.mesh import use
        with use(mesh):
            out = jax.jit(lambda sp, mb: pipeline_apply_gspmd(
                mesh, stage_fn, sp, mb))(stacked, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


    def test_blockstack_forwards_window(self, hvd):
        """The pipeline stage body honors sliding-window attention:
        stack(window=w) == manually chaining TransformerBlock(window=w)
        with the same params, and differs from the window-less stack
        (advisor r2 #1 — window was silently dropped)."""
        from horovod_tpu.models.transformer import TransformerBlock
        from horovod_tpu.parallel.tensor import unbox

        B, S, H, D = 2, 16, 2, 8
        x = jnp.asarray(np.random.RandomState(7).randn(B, S, H * D),
                        jnp.float32)
        stack = TransformerBlockStack(num_heads=H, head_dim=D,
                                      layers_per_stage=2, window=4,
                                      dtype=jnp.float32,
                                      attn_impl="blockwise")
        variables = stack.init(jax.random.PRNGKey(8), x)
        out = stack.apply(variables, x)

        params = unbox(variables["params"])
        block = TransformerBlock(num_heads=H, head_dim=D, window=4,
                                 dtype=jnp.float32, attn_impl="blockwise")
        ref = x
        for i in range(2):
            ref = block.apply({"params": params[f"block_{i}"]}, ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

        plain = stack.clone(window=None).apply(variables, x)
        assert not np.allclose(np.asarray(out), np.asarray(plain))


class TestSPMDCleanCompile:
    """The multi-axis train step must compile without GSPMD's
    replicate-then-repartition fallback ("Involuntary full
    rematerialization" in the partitioner log) — the hidden all-gather
    that destroys scaling. Runs in a subprocess so
    the C++ glog stderr can be captured."""

    def test_no_involuntary_rematerialization(self):
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # worker sets its own device count
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        # The grep below is vacuous if W-level C++ logs are suppressed.
        env["TF_CPP_MIN_LOG_LEVEL"] = "0"
        res = subprocess.run(
            [sys.executable, "tests/spmd_clean_worker.py"],
            cwd=repo, env=env, capture_output=True, text=True,
            timeout=420)
        assert res.returncode == 0, res.stdout + res.stderr
        if repo not in sys.path:  # __graft_entry__ lives at repo root
            sys.path.insert(0, repo)
        from __graft_entry__ import DRYRUN_LM_CONFIGS
        assert (res.stdout.count("SPMD_CLEAN_OK")
                == len(DRYRUN_LM_CONFIGS)), res.stdout
        assert "Involuntary full rematerialization" not in res.stderr, (
            "\n".join(l for l in res.stderr.splitlines()
                      if "Involuntary" in l))
