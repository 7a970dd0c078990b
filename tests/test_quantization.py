"""Weight-only int8 quantization (`ops/quantization.py`).

Oracle structure: the quantized model must equal the PLAIN model run on
the dequantized tree (the only approximation is the rounding inside
`quantize_int8`, bounded by half a step per element) — so equivalence
is tested exactly, and quantization error separately.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import TransformerLM, generate
from horovod_tpu.ops.quantization import (
    dequantize_int8, dequantize_lm_params, quantize_int8,
    quantize_lm_params,
)
from horovod_tpu.parallel.tensor import unbox


def small_lm(**kw):
    kw.setdefault("dtype", jnp.float32)
    return TransformerLM(vocab_size=64, num_layers=2, num_heads=2,
                         head_dim=8, max_len=32,
                         attn_impl="blockwise", **kw)


class TestQuantizeInt8:
    def test_roundtrip_error_bounded(self):
        w = np.random.RandomState(0).randn(32, 16).astype(np.float32)
        q, scale = quantize_int8(w, axis=0)
        assert q.dtype == jnp.int8 and scale.shape == (16,)
        back = np.asarray(dequantize_int8(q, scale))
        # Symmetric rounding: error <= scale/2 per element, column-wise.
        assert (np.abs(back - w) <= np.asarray(scale)[None, :] / 2
                + 1e-7).all()

    def test_zero_channel_safe(self):
        w = np.zeros((8, 3), np.float32)
        w[:, 1] = 2.0
        q, scale = quantize_int8(w, axis=0)
        assert np.isfinite(np.asarray(scale)).all()
        np.testing.assert_allclose(np.asarray(dequantize_int8(q, scale)),
                                   w, atol=2.0 / 127 / 2 + 1e-7)

    def test_extreme_values_clip_to_int8(self):
        w = np.array([[3.0, -5.0], [-3.0, 5.0]], np.float32)
        q, _ = quantize_int8(w, axis=0)
        assert np.abs(np.asarray(q)).max() <= 127


class TestQuantizedLM:
    def test_tree_structure_matches_quant_init(self):
        """quantize_lm_params output loads into the weight_quant model:
        identical key structure and leaf shapes/dtypes."""
        model = small_lm()
        params = unbox(model.init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32))["params"])
        qtree = quantize_lm_params(params)
        qinit = unbox(small_lm(weight_quant="int8").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
        flat_a = jax.tree_util.tree_flatten_with_path(qtree)[0]
        flat_b = jax.tree_util.tree_flatten_with_path(qinit)[0]
        assert [p for p, _ in flat_a] == [p for p, _ in flat_b]
        for (pa, a), (_, b) in zip(flat_a, flat_b):
            assert a.shape == b.shape and a.dtype == b.dtype, pa

    def test_quantized_apply_equals_plain_on_dequantized(self):
        """EXACT oracle: qmodel(qtree) == model(dequantize(qtree))."""
        model = small_lm()
        toks = jnp.asarray(
            np.random.RandomState(1).randint(0, 64, (2, 12)))
        params = unbox(model.init(jax.random.PRNGKey(0), toks)["params"])
        qtree = quantize_lm_params(params)
        got = small_lm(weight_quant="int8").apply(
            {"params": qtree}, toks)
        want = model.apply(
            {"params": dequantize_lm_params(qtree)}, toks)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    def test_quantized_logits_close_to_float(self):
        """int8 error on a trained-scale random model stays small
        relative to the logit magnitude (sanity, not exactness)."""
        model = small_lm()
        toks = jnp.asarray(
            np.random.RandomState(2).randint(0, 64, (2, 12)))
        params = unbox(model.init(jax.random.PRNGKey(0), toks)["params"])
        want = np.asarray(model.apply({"params": params}, toks))
        got = np.asarray(small_lm(weight_quant="int8").apply(
            {"params": quantize_lm_params(params)}, toks))
        denom = np.abs(want).max()
        assert np.abs(got - want).max() / denom < 0.05

    def test_generate_quantized_matches_dequantized_exactly(self):
        """Greedy decode through the KV cache: quantized model ==
        plain model on the dequantized tree, token-exact."""
        model = small_lm()
        prompt = np.random.RandomState(3).randint(0, 64, (2, 4))
        params = unbox(model.init(jax.random.PRNGKey(0),
                                  jnp.zeros((2, 8), jnp.int32))["params"])
        qtree = quantize_lm_params(params)
        got = generate(small_lm(weight_quant="int8"), qtree,
                       prompt, steps=8)
        want = generate(model, dequantize_lm_params(qtree),
                        prompt, steps=8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_unsupported_quant_rejected(self):
        model = small_lm(weight_quant="int4")
        with pytest.raises(ValueError, match="weight_quant"):
            model.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))

    def test_tp_sharding_specs_cover_quant_params(self):
        """Quantized kernels keep the Megatron partitioning: q sharded
        like the kernel, scale like the kernel's output dim."""
        from jax.sharding import PartitionSpec as P
        from horovod_tpu.parallel.tensor import param_specs
        model = small_lm(weight_quant="int8")
        v = model.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))
        specs = param_specs(v)["params"]["block_0"]
        attn, mlp = specs["attn"], specs["mlp"]
        assert attn["qkv"]["kernel_q"] == P(None, "model")
        assert attn["qkv"]["kernel_scale"] == P("model")
        assert attn["out"]["kernel_q"] == P("model", None)
        assert attn["out"]["kernel_scale"] == P(None)
        assert mlp["wi"]["kernel_q"] == P(None, "model")
        assert mlp["wo"]["kernel_q"] == P("model", None)


class TestKVCacheInt8:
    def test_kv_codec_roundtrip_bounded(self):
        from horovod_tpu.parallel.tensor import _kv_quantize
        t = jnp.asarray(
            np.random.RandomState(0).randn(2, 5, 3, 16), jnp.float32)
        q, scale = _kv_quantize(t)
        assert q.dtype == jnp.int8 and scale.shape == (2, 5, 3)
        back = q.astype(jnp.float32) * np.asarray(scale)[..., None]
        assert (np.abs(np.asarray(back) - np.asarray(t))
                <= np.asarray(scale)[..., None] / 2 + 1e-6).all()

    def test_cache_vars_are_int8_with_scales(self):
        model = small_lm(kv_quant="int8").clone(decode=True)
        v = model.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 16), jnp.int32))
        c = v["cache"]["block_0"]["attn"]
        assert c["cached_key"].dtype == jnp.int8
        assert c["cached_value"].dtype == jnp.int8
        assert c["cached_key_scale"].dtype == jnp.float32
        # cache [B, L, H, D] -> scales [B, L, H]
        assert (c["cached_key_scale"].shape
                == c["cached_key"].shape[:-1])

    @pytest.mark.parametrize("window", [None, 6])
    def test_kv_int8_decode_ticks_close_to_plain(self, window):
        """Sequential single-token decode: int8-cache logits track the
        plain-cache logits within the quantization error budget, tick
        after tick (linear and rolling-window caches)."""
        plain = small_lm(window=window, pos_emb="rope").clone(
            decode=True)
        quant = small_lm(window=window, pos_emb="rope",
                         kv_quant="int8").clone(decode=True)
        toks16 = jnp.zeros((2, 16), jnp.int32)
        # one program a model, not a compile a primitive a tick
        init_p = jax.jit(plain.init)(jax.random.PRNGKey(0), toks16)
        params, cache_p = unbox(init_p["params"]), init_p["cache"]
        cache_q = jax.jit(quant.init)(jax.random.PRNGKey(0),
                                      toks16)["cache"]
        tick_p, tick_q = (jax.jit(lambda p, c, tok, m=m: m.apply(
            {"params": p, "cache": c}, tok, mutable=["cache"]))
            for m in (plain, quant))
        rng = np.random.RandomState(4)
        for t in range(8):
            tok = jnp.asarray(rng.randint(0, 64, (2, 1)))
            lp, mp = tick_p(params, cache_p, tok)
            lq, mq = tick_q(params, cache_q, tok)
            cache_p, cache_q = mp["cache"], mq["cache"]
            denom = float(np.abs(np.asarray(lp)).max())
            err = float(np.abs(np.asarray(lq) - np.asarray(lp)).max())
            assert err / denom < 0.08, (t, err, denom)

    def test_kv_int8_generate_runs_and_matches_shapes(self):
        """End-to-end generate with the int8 cache: runs through the
        prefill + scan path; output shape/dtype contract intact."""
        model = small_lm(kv_quant="int8")
        prompt = np.random.RandomState(5).randint(0, 64, (2, 4))
        params = unbox(model.init(jax.random.PRNGKey(0),
                                  jnp.zeros((2, 8), jnp.int32))["params"])
        out = generate(model, params, prompt, steps=6)
        assert out.shape == (2, 10)
        assert (np.asarray(out) >= 0).all()

    def test_bad_kv_quant_rejected(self):
        model = small_lm(kv_quant="int4").clone(decode=True)
        with pytest.raises(ValueError, match="kv_quant"):
            model.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))
