"""Autoregressive decoding (KV cache) tests.

Oracle style (SURVEY §4): the cached decode path must produce exactly
the tokens the full-forward path picks — greedy decode tick by tick
equals re-running the whole prefix through the training-mode model and
taking argmax of the last position, for every generated position.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import TransformerLM, generate
from horovod_tpu.parallel.mesh import make_mesh, use
from horovod_tpu.parallel.tensor import shard_params, unbox


def _tiny_model(attn_impl="blockwise", **kw):
    return TransformerLM(vocab_size=64, num_layers=2, num_heads=4,
                         head_dim=8, max_len=32, dtype=jnp.float32,
                         attn_impl=attn_impl, **kw)


def _tokens(B=8, S=16, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, 64, (B, S)))


def _oracle_greedy(model, params, prompt, steps):
    """Full-prefix recompute: the O(S²)-per-token reference decoder.
    ONE program for every length: the sequence stands padded to its
    final length and the token after position n - 1 is read from that
    row, which in a causal model sees nothing of what follows it (a
    program a length compiles the same forward 40 times over in each
    rolling-window case)."""
    B, P = prompt.shape

    @jax.jit
    def extend(p, seq, n):
        logits = model.apply({"params": p}, seq)
        row = jax.lax.dynamic_index_in_dim(logits, n - 1, axis=1,
                                           keepdims=False)
        nxt = jnp.argmax(row.astype(jnp.float32), axis=-1)
        return jax.lax.dynamic_update_slice_in_dim(
            seq, nxt[:, None].astype(seq.dtype), n, axis=1)

    seq = jnp.pad(jnp.asarray(prompt), ((0, 0), (0, steps)))
    for n in range(P, P + steps):
        seq = extend(params, seq, n)
    return seq


class TestGenerate:
    @pytest.mark.parametrize("attn_impl", ["dot", "blockwise"])
    def test_greedy_matches_full_forward_oracle(self, hvd, attn_impl):
        model = _tiny_model(attn_impl)
        prompt = jnp.asarray(
            np.random.RandomState(0).randint(0, 64, (2, 5)))
        params = unbox(model.init(
            jax.random.PRNGKey(1),
            jnp.zeros((2, 16), jnp.int32))["params"])
        out = generate(model, params, prompt, steps=8)
        ref = _oracle_greedy(model, params, prompt, steps=8)
        assert out.shape == (2, 13)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_zero_steps_returns_prompt(self, hvd):
        model = _tiny_model()
        prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
        params = unbox(model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 16), jnp.int32))["params"])
        out = generate(model, params, prompt, steps=0)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(prompt))

    def test_single_token_prompt(self, hvd):
        model = _tiny_model()
        prompt = jnp.asarray([[7], [13]], jnp.int32)
        params = unbox(model.init(
            jax.random.PRNGKey(2),
            jnp.zeros((2, 16), jnp.int32))["params"])
        out = generate(model, params, prompt, steps=6)
        ref = _oracle_greedy(model, params, prompt, steps=6)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_tensor_parallel_decode_matches(self, hvd):
        """Greedy decode over a dp×tp mesh == the single-device oracle
        (cache heads ride ``model``; no resharding in the tick)."""
        model = _tiny_model()
        prompt = jnp.asarray(
            np.random.RandomState(3).randint(0, 64, (2, 4)))
        variables = model.init(jax.random.PRNGKey(4),
                               jnp.zeros((2, 16), jnp.int32))
        ref = _oracle_greedy(model, unbox(variables["params"]), prompt,
                             steps=6)
        mesh = make_mesh(data=2, model=4)
        with use(mesh):
            params = shard_params(mesh, variables["params"])
            prompt_sh = jax.device_put(
                prompt, NamedSharding(mesh, P("data", None)))
            out = generate(model, params, prompt_sh, steps=6,
                           mesh=mesh)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_tp_decode_pallas_impl_falls_back_to_lax(self, hvd):
        """decode_prefix_impl='pallas' under a dp×tp mesh: a bare
        pallas_call has no GSPMD partitioning rule, so sharded decode
        silently keeps the lax prefix path — tokens still match the
        single-device oracle."""
        model = _tiny_model(decode_prefix_impl="pallas",
                            decode_prefix_block=8)
        prompt = jnp.asarray(
            np.random.RandomState(70).randint(0, 64, (2, 4)))
        variables = model.init(jax.random.PRNGKey(71),
                               jnp.zeros((2, 16), jnp.int32))
        ref = _oracle_greedy(model, unbox(variables["params"]), prompt,
                             steps=6)
        mesh = make_mesh(data=2, model=4)
        with use(mesh):
            params = shard_params(mesh, variables["params"])
            prompt_sh = jax.device_put(
                prompt, NamedSharding(mesh, P("data", None)))
            out = generate(model, params, prompt_sh, steps=6,
                           mesh=mesh)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_batch_one_decode_on_data_mesh(self, hvd):
        """B=1 decode under an ambient data=4 mesh: the batch dim can't
        shard over ``data``, so `constrain` must replicate it instead of
        erroring (regression: found driving the user flow)."""
        model = _tiny_model()
        prompt = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
        variables = model.init(jax.random.PRNGKey(8),
                               jnp.zeros((1, 16), jnp.int32))
        ref = _oracle_greedy(model, unbox(variables["params"]), prompt,
                             steps=5)
        mesh = make_mesh(data=4, model=2)
        with use(mesh):
            params = shard_params(mesh, variables["params"])
            out = generate(model, params, prompt, steps=5, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_sampling_respects_temperature_and_rng(self, hvd):
        model = _tiny_model()
        prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
        params = unbox(model.init(
            jax.random.PRNGKey(5),
            jnp.zeros((1, 16), jnp.int32))["params"])
        a = generate(model, params, prompt, steps=8, temperature=1.0,
                     rng=jax.random.PRNGKey(0))
        b = generate(model, params, prompt, steps=8, temperature=1.0,
                     rng=jax.random.PRNGKey(0))
        c = generate(model, params, prompt, steps=8, temperature=5.0,
                     rng=jax.random.PRNGKey(9))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))
        # prompt is always preserved verbatim
        np.testing.assert_array_equal(np.asarray(a[:, :3]),
                                      np.asarray(prompt))
        with pytest.raises(ValueError):
            generate(model, params, prompt, steps=2, temperature=1.0)

    def test_temperature_change_does_not_recompile(self, hvd):
        """temperature is a traced operand of the compiled decode loop:
        sampling at a new temperature (and top_p) reuses the program —
        only greedy<->sampling and top_k recompile (advisor r2 #2)."""
        from horovod_tpu.models.transformer import _generate_scan
        model = _tiny_model()
        prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
        params = unbox(model.init(
            jax.random.PRNGKey(6),
            jnp.zeros((1, 16), jnp.int32))["params"])
        generate(model, params, prompt, steps=4, temperature=0.7,
                 rng=jax.random.PRNGKey(0))
        n0 = _generate_scan._cache_size()
        generate(model, params, prompt, steps=4, temperature=1.3,
                 rng=jax.random.PRNGKey(0))
        generate(model, params, prompt, steps=4, temperature=2.0,
                 top_p=0.9, rng=jax.random.PRNGKey(0))
        n1 = _generate_scan._cache_size()
        # one extra entry for the top_p branch (None -> float changes
        # the arg pytree), none for the temperature changes
        assert n1 == n0 + 1, (n0, n1)
        generate(model, params, prompt, steps=4, temperature=3.0,
                 top_p=0.5, rng=jax.random.PRNGKey(0))
        assert _generate_scan._cache_size() == n1

    def test_gqa_decode_matches_oracle_and_shrinks_cache(self, hvd):
        """GQA (num_kv_heads < num_heads): decode is token-exact vs the
        full-forward oracle, and the KV cache physically carries only
        the KV heads (the GQA memory win)."""
        model = _tiny_model(num_kv_heads=2)  # 4 query heads, 2 KV
        prompt = jnp.asarray(
            np.random.RandomState(9).randint(0, 64, (2, 4)))
        variables = model.init(jax.random.PRNGKey(10),
                               jnp.zeros((2, 16), jnp.int32))
        params = unbox(variables["params"])
        out = generate(model, params, prompt, steps=6)
        ref = _oracle_greedy(model, params, prompt, steps=6)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        # Cache shape check: [B, max_len, Hkv, D], not H.
        cache = model.clone(decode=True).init(
            jax.random.PRNGKey(0), jnp.zeros((2, 32), jnp.int32))["cache"]
        ck = cache["block_0"]["attn"]["cached_key"]
        assert ck.shape == (2, 32, 2, 8), ck.shape

    def test_gqa_full_kv_heads_equals_mha(self, hvd):
        """num_kv_heads == num_heads is bit-identical MHA (same param
        tree, same projection split)."""
        toks = _tokens(B=2, S=8, seed=12)
        mha = _tiny_model()
        gqa = _tiny_model(num_kv_heads=4)
        variables = mha.init(jax.random.PRNGKey(11), toks)
        a = mha.apply(variables, toks)
        b = gqa.apply(variables, toks)  # same params load directly
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_gqa_trains(self, hvd):
        """GQA composes with the training step on a dp×tp mesh (KV
        heads shard over ``model`` too: Hkv=2 on tp=2)."""
        import optax
        from horovod_tpu.models.transformer import (
            init_lm_state, make_lm_train_step)
        from horovod_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(data=4, model=2)
        model = _tiny_model(num_kv_heads=2)
        toks = _tokens(B=8, S=16, seed=13)
        params, opt = init_lm_state(model, tx := optax.sgd(0.1),
                                    jax.random.PRNGKey(0), mesh, toks)
        step = make_lm_train_step(model, tx, mesh)
        toks_sh = jax.device_put(
            toks, NamedSharding(mesh, P("data", None)))
        losses = []
        for _ in range(3):
            params, opt, loss = step(params, opt, toks_sh)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def test_rope_decode_matches_oracle(self, hvd):
        """RoPE decode: keys cached post-rotation at absolute
        positions — token-exact vs the full-forward oracle."""
        model = _tiny_model(pos_emb="rope")
        prompt = jnp.asarray(
            np.random.RandomState(14).randint(0, 64, (2, 5)))
        params = unbox(model.init(
            jax.random.PRNGKey(15),
            jnp.zeros((2, 16), jnp.int32))["params"])
        assert "pos" not in params  # no learned table under rope
        out = generate(model, params, prompt, steps=7)
        ref = _oracle_greedy(model, params, prompt, steps=7)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("sp_impl", ["ring", "ulysses"])
    def test_rope_sequence_parallel_matches_unsharded(self, hvd,
                                                      sp_impl):
        """RoPE is applied at the logical level before the attention,
        so sequence parallelism sees already-rotated q/k — the
        ring/Ulysses forward over a seq mesh equals the unsharded
        forward."""
        from horovod_tpu.parallel.mesh import make_mesh, use
        from horovod_tpu.parallel.tensor import shard_params
        toks = _tokens(B=4, S=16, seed=16)
        ref_model = _tiny_model("blockwise", pos_emb="rope")
        variables = ref_model.init(jax.random.PRNGKey(17), toks)
        ref = ref_model.apply(variables, toks)

        mesh = make_mesh(data=2, seq=2, model=2)
        sp_model = _tiny_model(sp_impl, pos_emb="rope")
        with use(mesh):
            params = shard_params(mesh, variables["params"])
            toks_sh = jax.device_put(
                toks, NamedSharding(mesh, P("data", "seq")))
            out = jax.jit(lambda p, t: sp_model.apply(
                {"params": p}, t))(params, toks_sh)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=2e-4)

    def test_rope_theta_and_validation(self, hvd):
        """rope_theta reaches the attention (different theta ⇒
        different logits) and bad pos_emb raises."""
        toks = _tokens(B=2, S=8, seed=19)
        m1 = _tiny_model(pos_emb="rope")
        m2 = _tiny_model(pos_emb="rope", rope_theta=500000.0)
        variables = m1.init(jax.random.PRNGKey(20), toks)
        a = m1.apply(variables, toks)
        b = m2.apply(variables, toks)
        assert not np.allclose(np.asarray(a), np.asarray(b))
        bad = _tiny_model(pos_emb="Rope")
        with pytest.raises(ValueError):
            bad.init(jax.random.PRNGKey(0), toks)

    def test_rope_trains(self, hvd):
        import optax
        from horovod_tpu.models.transformer import (
            init_lm_state, make_lm_train_step)
        from horovod_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(data=8)
        model = _tiny_model(pos_emb="rope")
        toks = _tokens(seed=18)
        params, opt = init_lm_state(model, tx := optax.sgd(0.1),
                                    jax.random.PRNGKey(0), mesh, toks)
        step = make_lm_train_step(model, tx, mesh)
        toks_sh = jax.device_put(
            toks, NamedSharding(mesh, P("data", None)))
        losses = []
        for _ in range(3):
            params, opt, loss = step(params, opt, toks_sh)
            losses.append(float(loss))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def test_top_k_one_equals_greedy(self, hvd):
        model = _tiny_model()
        prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        params = unbox(model.init(
            jax.random.PRNGKey(21),
            jnp.zeros((1, 16), jnp.int32))["params"])
        greedy = generate(model, params, prompt, steps=6)
        k1 = generate(model, params, prompt, steps=6, temperature=1.0,
                      top_k=1, rng=jax.random.PRNGKey(5))
        np.testing.assert_array_equal(np.asarray(greedy),
                                      np.asarray(k1))
        # A tiny nucleus keeps only the argmax token too.
        p_small = generate(model, params, prompt, steps=6,
                           temperature=1.0, top_p=1e-9,
                           rng=jax.random.PRNGKey(6))
        np.testing.assert_array_equal(np.asarray(greedy),
                                      np.asarray(p_small))
        with pytest.raises(ValueError):
            generate(model, params, prompt, steps=2, top_k=5)  # temp=0
        with pytest.raises(ValueError):
            generate(model, params, prompt, steps=2, temperature=1.0,
                     top_p=1.5, rng=jax.random.PRNGKey(0))

    def test_eval_step_matches_train_loss(self, hvd):
        """make_lm_eval_step == the train step's reported loss at the
        same params (loss is computed pre-update)."""
        import optax
        from horovod_tpu.models.transformer import (
            init_lm_state, make_lm_eval_step, make_lm_train_step)
        from horovod_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(data=4, model=2)
        model = _tiny_model()
        toks = _tokens(seed=22)
        params, opt = init_lm_state(model, tx := optax.sgd(0.1),
                                    jax.random.PRNGKey(0), mesh, toks)
        ev = make_lm_eval_step(model, mesh)
        step = make_lm_train_step(model, tx, mesh, donate=False)
        toks_sh = jax.device_put(
            toks, NamedSharding(mesh, P("data", None)))
        eval_loss = float(ev(params, toks_sh))
        _, _, train_loss = step(params, opt, toks_sh)
        np.testing.assert_allclose(eval_loss, float(train_loss),
                                   rtol=1e-5)
        # chunked variant agrees too
        ev_c = make_lm_eval_step(model, mesh, loss_chunk=8)
        np.testing.assert_allclose(float(ev_c(params, toks_sh)),
                                   eval_loss, rtol=1e-4)

    def test_window_blockwise_matches_banded_dot(self, hvd):
        """Sliding-window blockwise == dot with an explicit banded
        mask (same params)."""
        toks = _tokens(B=2, S=16, seed=23)
        dot_model = _tiny_model("dot", window=5)
        blk_model = _tiny_model("blockwise", window=5)
        variables = dot_model.init(jax.random.PRNGKey(24), toks)
        a = dot_model.apply(variables, toks)
        b = blk_model.apply(variables, toks)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-5)
        # window >= S degenerates to plain causal
        full = _tiny_model("blockwise").apply(variables, toks)
        wide = _tiny_model("blockwise", window=16).apply(variables, toks)
        np.testing.assert_allclose(np.asarray(wide), np.asarray(full),
                                   atol=2e-5)

    def test_chunked_prefill_matches_one_pass(self, hvd):
        """chunked_prefill=True: two S>1 appends onto a growing cache
        equal the one-pass prefill's cache + logits — the general
        cache-wide-mask path stays correct for any cache_index (the
        default fast path is contractually empty-cache-only)."""
        model = _tiny_model("blockwise")
        toks = _tokens(B=2, S=12, seed=31)
        variables = model.init(jax.random.PRNGKey(32), toks)
        params = unbox(variables["params"])

        dec = model.clone(decode=True, chunked_prefill=True)
        shapes = jax.eval_shape(
            dec.init, jax.random.PRNGKey(0),
            jnp.zeros((2, model.max_len), toks.dtype))
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             shapes["cache"])
        # chunk 1: positions 0..5; chunk 2: positions 6..11
        out1, mut = dec.apply({"params": params, "cache": cache},
                              toks[:, :6], mutable=["cache"])
        out2, mut = dec.apply(
            {"params": params, "cache": mut["cache"]},
            toks[:, 6:], mutable=["cache"])
        # oracle: the training-mode forward over the full prefix
        ref = model.apply(variables, toks)
        np.testing.assert_allclose(
            np.asarray(out2, np.float32),
            np.asarray(ref[:, 6:], np.float32), atol=2e-4)

    def test_eos_stops_sequence_and_pads(self, hvd):
        """eos_id: each row emits tokens identically to the no-eos run
        up to and including its first eos, then pad_id fills the rest
        of the fixed rectangle; rows that never emit eos are unchanged
        (the batched-serving stop contract)."""
        model = _tiny_model()
        prompt = _tokens(B=4, S=4, seed=80)[:, :4]
        params = unbox(model.init(
            jax.random.PRNGKey(81),
            jnp.zeros((4, 16), jnp.int32))["params"])
        steps, P = 12, 4
        base = np.asarray(generate(model, params, prompt, steps=steps))
        gen = base[:, P:]
        # Choose an eos that actually occurs mid-stream in some row.
        eos = int(gen[0, steps // 2])
        out = np.asarray(generate(model, params, prompt, steps=steps,
                                  eos_id=eos, pad_id=63))
        np.testing.assert_array_equal(out[:, :P], base[:, :P])
        for b in range(4):
            row, ref = out[b, P:], gen[b]
            hits = np.where(ref == eos)[0]
            if hits.size == 0:
                np.testing.assert_array_equal(row, ref)
            else:
                k = hits[0]
                np.testing.assert_array_equal(row[:k + 1], ref[:k + 1])
                np.testing.assert_array_equal(
                    row[k + 1:], np.full(steps - k - 1, 63))

    def test_generate_bucketed_matches_per_prompt(self, hvd):
        """Mixed-length serving: bucketed output == each prompt run
        alone (rows are independent), order preserved, eos composes."""
        from horovod_tpu.models.transformer import generate_bucketed
        model = _tiny_model()
        params = unbox(model.init(
            jax.random.PRNGKey(90),
            jnp.zeros((2, 16), jnp.int32))["params"])
        rng = np.random.RandomState(91)
        prompts = [jnp.asarray(rng.randint(0, 64, (n,)))
                   for n in (3, 5, 3, 7)]
        outs = generate_bucketed(model, params, prompts, steps=6)
        assert [o.shape[0] for o in outs] == [9, 11, 9, 13]
        for p, o in zip(prompts, outs):
            solo = generate(model, params, p[None], steps=6)[0]
            np.testing.assert_array_equal(np.asarray(o),
                                          np.asarray(solo))
        # Kwargs pass through: eos_id/pad_id reach each bucket call.
        eos = int(np.asarray(outs[0])[4])
        outs_e = generate_bucketed(model, params, prompts, steps=6,
                                   eos_id=eos, pad_id=63)
        for p, o in zip(prompts, outs_e):
            solo = generate(model, params, p[None], steps=6,
                            eos_id=eos, pad_id=63)[0]
            np.testing.assert_array_equal(np.asarray(o),
                                          np.asarray(solo))
        with pytest.raises(ValueError, match="1-D"):
            generate_bucketed(model, params,
                              [jnp.zeros((2, 3), jnp.int32)], steps=2)

    def test_early_stop_matches_fixed_scan(self, hvd):
        """early_stop=True (while_loop exits at the last finisher)
        produces the SAME [B, P + steps] rectangle as the fixed-length
        scan — eos positions, pads, and unfinished rows all identical;
        it only stops paying for ticks nobody needs."""
        model = _tiny_model()
        prompt = _tokens(B=4, S=4, seed=82)[:, :4]
        params = unbox(model.init(
            jax.random.PRNGKey(83),
            jnp.zeros((4, 16), jnp.int32))["params"])
        steps, P = 12, 4
        base = np.asarray(generate(model, params, prompt, steps=steps))
        eos = int(base[0, P + steps // 2])
        ref = generate(model, params, prompt, steps=steps,
                       eos_id=eos, pad_id=63)
        out = generate(model, params, prompt, steps=steps,
                       eos_id=eos, pad_id=63, early_stop=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        with pytest.raises(ValueError, match="early_stop"):
            generate(model, params, prompt, steps=steps,
                     early_stop=True)

    def test_bucketed_early_stop_parity(self, hvd):
        """Satellite contract: eos/pad + early_stop propagate through
        the bucketed path — each bucket stops early yet returns exactly
        the per-prompt `generate` rows (same post-eos padding)."""
        from horovod_tpu.models.transformer import generate_bucketed
        model = _tiny_model()
        params = unbox(model.init(
            jax.random.PRNGKey(92),
            jnp.zeros((2, 16), jnp.int32))["params"])
        rng = np.random.RandomState(93)
        prompts = [jnp.asarray(rng.randint(0, 64, (n,)))
                   for n in (3, 5, 3, 7)]
        probe = generate_bucketed(model, params, prompts, steps=8)
        eos = int(np.asarray(probe[0])[5])
        outs = generate_bucketed(model, params, prompts, steps=8,
                                 eos_id=eos, pad_id=63,
                                 early_stop=True)
        for p, o in zip(prompts, outs):
            solo = generate(model, params, p[None], steps=8,
                            eos_id=eos, pad_id=63)[0]
            np.testing.assert_array_equal(np.asarray(o),
                                          np.asarray(solo))

    def test_bucketed_early_stop_no_post_eos_tail(self, hvd):
        """Per-bucket EOS exit contract, pinned directly (not just via
        parity): in every bucket, once a row emits eos the remainder
        of its rectangle is EXACTLY pad — a post-eos tail is never
        emitted by the per-bucket while_loop exit."""
        from horovod_tpu.models.transformer import generate_bucketed
        model = _tiny_model()
        params = unbox(model.init(
            jax.random.PRNGKey(94),
            jnp.zeros((2, 16), jnp.int32))["params"])
        rng = np.random.RandomState(97)
        prompts = [jnp.asarray(rng.randint(0, 64, (n,)))
                   for n in (3, 5, 3, 7, 5)]
        steps, pad = 10, 63
        probe = generate_bucketed(model, params, prompts, steps=steps)
        # An eos that fires mid-stream in at least one row per bucket
        # length would be ideal; picking from one probe row still
        # exercises every bucket's exit (rows without eos must run the
        # full budget).
        eos = int(np.asarray(probe[1])[5 + 4])
        outs = generate_bucketed(model, params, prompts, steps=steps,
                                 eos_id=eos, pad_id=pad,
                                 early_stop=True)
        stopped = 0
        for p, o in zip(prompts, outs):
            gen = np.asarray(o)[p.shape[0]:]
            assert gen.shape[0] == steps
            hits = np.where(gen == eos)[0]
            if hits.size:
                stopped += 1
                k = hits[0]
                # eos is emitted, then NOTHING but pad follows.
                np.testing.assert_array_equal(
                    gen[k + 1:], np.full(steps - k - 1, pad))
        assert stopped >= 1      # the contract was actually exercised

    def test_bucketed_early_stop_cache_keys_stable(self, hvd):
        """Bucket program cache keys stay stable: re-running the same
        bucket set (same lengths, same batch split, same eos/early-
        stop flags) must not grow `_generate_scan`'s jit cache — the
        serving-bucket trade is one compile per distinct
        (length, batch) pair, never one per call."""
        from horovod_tpu.models.transformer import (_generate_scan,
                                                    generate_bucketed)
        if not hasattr(_generate_scan, "_cache_size"):
            pytest.skip("jit cache introspection unavailable")
        model = _tiny_model()
        params = unbox(model.init(
            jax.random.PRNGKey(98),
            jnp.zeros((2, 16), jnp.int32))["params"])
        rng = np.random.RandomState(99)
        prompts = [jnp.asarray(rng.randint(0, 64, (n,)))
                   for n in (3, 5, 3, 7)]
        kw = dict(steps=6, eos_id=7, pad_id=63, early_stop=True)
        generate_bucketed(model, params, prompts, **kw)
        n0 = _generate_scan._cache_size()
        for _ in range(2):
            generate_bucketed(model, params, prompts, **kw)
        assert _generate_scan._cache_size() == n0
        # A NEW bucket length legitimately adds (at most) one entry.
        generate_bucketed(
            model, params,
            prompts + [jnp.asarray(rng.randint(0, 64, (9,)))], **kw)
        n1 = _generate_scan._cache_size()
        assert n0 < n1 <= n0 + 1

    def test_serving_params_cast_rules(self, hvd):
        """serving_params: ndim>=2 float params cast to bf16; 1-D
        (norm scales/biases) stay f32; int8 leaves untouched; and at
        a rope/bf16 model the cast is token-exact (each use site's
        astype becomes a no-op)."""
        from horovod_tpu.models.transformer import serving_params
        tree = {"k": jnp.ones((4, 4), jnp.float32),
                "s": jnp.ones((4,), jnp.float32),
                "q": jnp.ones((2, 2), jnp.int8)}
        out = serving_params(tree)
        assert out["k"].dtype == jnp.bfloat16
        assert out["s"].dtype == jnp.float32
        assert out["q"].dtype == jnp.int8

        model = _tiny_model(pos_emb="rope").clone(dtype=jnp.bfloat16)
        prompt = _tokens(B=2, S=5, seed=95)[:, :5]
        params = unbox(model.init(
            jax.random.PRNGKey(96),
            jnp.zeros((2, 16), jnp.int32))["params"])
        a = generate(model, params, prompt, steps=8)
        b = generate(model, serving_params(params), prompt, steps=8)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_eos_validation(self, hvd):
        model = _tiny_model()
        params = unbox(model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 16), jnp.int32))["params"])
        with pytest.raises(ValueError, match="eos_id"):
            generate(model, params, jnp.asarray([[1, 2]]), steps=2,
                     eos_id=64)
        with pytest.raises(ValueError, match="pad_id"):
            generate(model, params, jnp.asarray([[1, 2]]), steps=2,
                     eos_id=3, pad_id=64)

    def test_prefix_attention_matches_cache_wide(self, hvd):
        """Linear-cache prefix-block decode (`decode_prefix_block`):
        multi-block online-softmax accumulation over only the filled
        prefix produces the SAME greedy tokens as the cache-wide-mask
        path — the HBM-traffic fix changes bytes
        read, never the result."""
        prompt = _tokens(B=2, S=5, seed=50)[:, :5]
        base = _tiny_model("blockwise", decode_prefix_block=None)
        params = unbox(base.init(
            jax.random.PRNGKey(51),
            jnp.zeros((2, 16), jnp.int32))["params"])
        ref = generate(base, params, prompt, steps=20)
        for blk in (4, 8, 32):   # multi-block through single-block
            fast = base.clone(decode_prefix_block=blk)
            out = generate(fast, params, prompt, steps=20)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(ref))

    def test_prefix_attention_gqa_rope_matches(self, hvd):
        """Prefix-block decode composes with GQA (per-block KV-head
        broadcast) and RoPE (keys cached post-rotation)."""
        prompt = _tokens(B=2, S=6, seed=52)[:, :6]
        base = _tiny_model("blockwise", num_kv_heads=2,
                           pos_emb="rope", decode_prefix_block=None)
        params = unbox(base.init(
            jax.random.PRNGKey(53),
            jnp.zeros((2, 16), jnp.int32))["params"])
        ref = generate(base, params, prompt, steps=16)
        out = generate(base.clone(decode_prefix_block=8), params,
                       prompt, steps=16)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_prefix_attention_int8_kv_matches(self, hvd):
        """Prefix-block decode under kv_quant="int8": the per-block
        dequant reads the same codec the cache-wide path does, so the
        two paths stay token-exact against each other."""
        prompt = _tokens(B=2, S=5, seed=54)[:, :5]
        base = _tiny_model("blockwise", kv_quant="int8",
                           decode_prefix_block=None)
        params = unbox(base.init(
            jax.random.PRNGKey(55),
            jnp.zeros((2, 16), jnp.int32))["params"])
        ref = generate(base, params, prompt, steps=16)
        out = generate(base.clone(decode_prefix_block=8), params,
                       prompt, steps=16)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_prefix_attention_chunked_prefill_matches(self, hvd):
        """S>1 chunked appends route through the prefix path too: two
        chunk appends match the training-mode oracle logits."""
        model = _tiny_model("blockwise", chunked_prefill=True,
                            decode_prefix_block=8)
        toks = _tokens(B=2, S=12, seed=56)
        variables = model.init(jax.random.PRNGKey(57), toks)
        params = unbox(variables["params"])
        dec = model.clone(decode=True)
        shapes = jax.eval_shape(
            dec.init, jax.random.PRNGKey(0),
            jnp.zeros((2, model.max_len), toks.dtype))
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             shapes["cache"])
        _, mut = dec.apply({"params": params, "cache": cache},
                           toks[:, :6], mutable=["cache"])
        out2, _ = dec.apply({"params": params, "cache": mut["cache"]},
                            toks[:, 6:], mutable=["cache"])
        ref = model.apply(variables, toks)
        np.testing.assert_allclose(
            np.asarray(out2, np.float32),
            np.asarray(ref[:, 6:], np.float32), atol=2e-4)

    def test_flash_decode_kernel_matches_lax_prefix(self, hvd):
        """decode_prefix_impl="pallas" (the fused flash-decode
        kernel, interpret mode on CPU): greedy tokens match the lax
        fori_loop prefix path exactly, MHA and GQA."""
        prompt = _tokens(B=2, S=5, seed=60)[:, :5]
        for kw in ({}, {"num_kv_heads": 2, "pos_emb": "rope"}):
            base = _tiny_model("blockwise", decode_prefix_block=8,
                               **kw)
            params = unbox(base.init(
                jax.random.PRNGKey(61),
                jnp.zeros((2, 16), jnp.int32))["params"])
            ref = generate(base, params, prompt, steps=16)
            out = generate(base.clone(decode_prefix_impl="pallas"),
                           params, prompt, steps=16)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(ref))

    def test_flash_decode_int8_kv_falls_back_to_lax(self, hvd):
        """A quantized cache routes the pallas impl onto the lax
        per-block-dequant path (the kernel is bf16/f32-only) —
        token-exact vs the explicit lax impl."""
        prompt = _tokens(B=2, S=5, seed=62)[:, :5]
        base = _tiny_model("blockwise", kv_quant="int8",
                           decode_prefix_block=8)
        params = unbox(base.init(
            jax.random.PRNGKey(63),
            jnp.zeros((2, 16), jnp.int32))["params"])
        ref = generate(base, params, prompt, steps=12)
        out = generate(base.clone(decode_prefix_impl="pallas"),
                       params, prompt, steps=12)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_decode_prefix_impl_validated(self, hvd):
        base = _tiny_model("blockwise",
                           decode_prefix_impl="cuda")
        with pytest.raises(ValueError, match="lax\\|pallas"):
            generate(base, unbox(base.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 16), jnp.int32))["params"]),
                jnp.asarray([[1, 2]]), steps=2)

    def test_prefix_block_not_dividing_cache_falls_back(self, hvd):
        """A block size that doesn't divide max_len silently uses the
        cache-wide path (a clamped dynamic_slice would re-read
        overlapping slots with wrong positions) — tokens still match."""
        prompt = _tokens(B=2, S=5, seed=58)[:, :5]
        base = _tiny_model("blockwise", decode_prefix_block=None)
        params = unbox(base.init(
            jax.random.PRNGKey(59),
            jnp.zeros((2, 16), jnp.int32))["params"])
        ref = generate(base, params, prompt, steps=10)
        out = generate(base.clone(decode_prefix_block=7), params,
                       prompt, steps=10)   # 32 % 7 != 0
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_one_pass_prefill_nonempty_cache_raises(self, hvd):
        """One-pass prefill (chunked_prefill=False) contractually
        requires an empty cache; an eager S>1 append onto a non-empty
        cache (concrete cache_index > 0) is a hard ValueError naming
        chunked_prefill, not a silently-wrong output (advisor r3 #1)."""
        model = _tiny_model("blockwise")
        toks = _tokens(B=2, S=12, seed=41)
        variables = model.init(jax.random.PRNGKey(42), toks)
        params = unbox(variables["params"])
        dec = model.clone(decode=True, chunked_prefill=False)
        shapes = jax.eval_shape(
            dec.init, jax.random.PRNGKey(0),
            jnp.zeros((2, model.max_len), toks.dtype))
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             shapes["cache"])
        _, mut = dec.apply({"params": params, "cache": cache},
                           toks[:, :6], mutable=["cache"])
        with pytest.raises(ValueError, match="chunked_prefill"):
            dec.apply({"params": params, "cache": mut["cache"]},
                      toks[:, 6:], mutable=["cache"])

    @pytest.mark.parametrize("sp_impl", ["ring_flash", "ulysses_flash"])
    def test_gqa_sp_flash_matches(self, hvd, sp_impl):
        """GQA + SP flash impls: K/V ride the ring hops / all_to_alls
        at kv-head width (native_gqa) and still match the blockwise
        reference (which sees repeated K/V)."""
        toks = _tokens(B=4, S=16, seed=27)
        ref_model = _tiny_model("blockwise", num_kv_heads=2)
        variables = ref_model.init(jax.random.PRNGKey(28), toks)
        ref = ref_model.apply(variables, toks)
        # model=1: ulysses needs kv_heads % seq == 0 after the head
        # shard (2 kv heads over seq=2).
        mesh = make_mesh(data=4, seq=2, model=1)
        sp_model = _tiny_model(sp_impl, num_kv_heads=2)
        with use(mesh):
            params = shard_params(mesh, variables["params"])
            toks_sh = jax.device_put(
                toks, NamedSharding(mesh, P("data", "seq")))
            out = jax.jit(lambda p, t: sp_model.apply(
                {"params": p}, t))(params, toks_sh)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=2e-4)

    @pytest.mark.parametrize("sp_impl", ["ring", "ring_flash",
                                         "ulysses", "ulysses_flash"])
    def test_window_sequence_parallel_matches(self, hvd, sp_impl):
        """Window masking uses GLOBAL positions, so it is exact across
        ring-rotated / Ulysses-swapped sequence shards."""
        from horovod_tpu.parallel.mesh import make_mesh, use
        from horovod_tpu.parallel.tensor import shard_params
        toks = _tokens(B=4, S=16, seed=25)
        ref_model = _tiny_model("blockwise", window=6)
        variables = ref_model.init(jax.random.PRNGKey(26), toks)
        ref = ref_model.apply(variables, toks)
        mesh = make_mesh(data=2, seq=2, model=2)
        sp_model = _tiny_model(sp_impl, window=6)
        with use(mesh):
            params = shard_params(mesh, variables["params"])
            toks_sh = jax.device_put(
                toks, NamedSharding(mesh, P("data", "seq")))
            out = jax.jit(lambda p, t: sp_model.apply(
                {"params": p}, t))(params, toks_sh)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=2e-4)

    def test_window_decode_matches_oracle(self, hvd):
        """Decode with a sliding window == full-forward oracle of the
        same windowed model. The prompt (5) exceeds the window (4), so
        the rolling cache's prefill eviction path is exercised."""
        model = _tiny_model(window=4, pos_emb="rope")
        prompt = jnp.asarray(
            np.random.RandomState(27).randint(0, 64, (2, 5)))
        params = unbox(model.init(
            jax.random.PRNGKey(28),
            jnp.zeros((2, 16), jnp.int32))["params"])
        out = generate(model, params, prompt, steps=8)
        ref = _oracle_greedy(model, params, prompt, steps=8)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_window_rolling_cache_size_and_unbounded(self, hvd):
        """With a window the KV cache is a rolling buffer of `window`
        slots (not max_len), and RoPE + window generates PAST max_len
        — token-exact vs the full-forward oracle throughout."""
        model = _tiny_model(window=6, pos_emb="rope")
        cache = model.clone(decode=True).init(
            jax.random.PRNGKey(0),
            jnp.zeros((2, 32), jnp.int32))["cache"]
        ck = cache["block_0"]["attn"]["cached_key"]
        assert ck.shape == (2, 6, 4, 8), ck.shape  # window, not max_len

        prompt = jnp.asarray(
            np.random.RandomState(31).randint(0, 64, (2, 4)))
        params = unbox(model.init(
            jax.random.PRNGKey(32),
            jnp.zeros((2, 32), jnp.int32))["params"])
        # 4 + 40 tokens >> max_len=32: unbounded streaming generation.
        out = generate(model, params, prompt, steps=40)
        ref = _oracle_greedy(model, params, prompt, steps=40)
        assert out.shape == (2, 44)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        # learned-pos models must still refuse past max_len.
        lm = _tiny_model(window=6)
        p2 = unbox(lm.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 16), jnp.int32))["params"])
        with pytest.raises(ValueError):
            generate(lm, p2, prompt, steps=40)

    def test_window_larger_than_max_len_cache_not_truncated(self, hvd):
        """window > max_len: the rolling cache must still hold `window`
        slots (regression: min(init_len, window) silently evicted
        in-band keys once positions passed the init length)."""
        model = _tiny_model(window=40, pos_emb="rope")  # max_len=32
        cache = model.clone(decode=True).init(
            jax.random.PRNGKey(0),
            jnp.zeros((2, 32), jnp.int32))["cache"]
        ck = cache["block_0"]["attn"]["cached_key"]
        assert ck.shape == (2, 40, 4, 8), ck.shape
        prompt = jnp.asarray(
            np.random.RandomState(33).randint(0, 64, (2, 4)))
        params = unbox(model.init(
            jax.random.PRNGKey(34),
            jnp.zeros((2, 32), jnp.int32))["params"])
        out = generate(model, params, prompt, steps=44)  # past window
        ref = _oracle_greedy(model, params, prompt, steps=44)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("window,S", [(1, 64), (12, 64), (12, 57),
                                          (40, 64)])
    def test_window_flash_multiblock_banded_grid(self, hvd, window, S):
        """Direct kernel check with block 16 so the banded grid runs
        multiple k-blocks per q-block: band masking across block
        boundaries, clamped-duplicate skipping at the sequence end,
        and the pad tail (S=57) must all match the banded dot oracle
        — fwd and bwd."""
        from horovod_tpu.ops.flash_attention import flash_attention
        from horovod_tpu.parallel.sequence import banded_causal_mask
        from horovod_tpu.parallel.tensor import dot_product_attention
        rng = np.random.RandomState(window + S)
        q, k, v = (jnp.asarray(rng.randn(2, S, 4, 16), jnp.float32)
                   for _ in range(3))
        pos = jnp.arange(S)
        mask = banded_causal_mask(pos, pos, window)[None, None]
        ref = dot_product_attention(q, k, v, mask)
        out = flash_attention(q, k, v, causal=True, window=window,
                              block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

        def loss_f(q, k, v):
            return (flash_attention(q, k, v, causal=True, window=window,
                                    block_q=16, block_k=16) ** 2).mean()

        def loss_r(q, k, v):
            return (dot_product_attention(q, k, v, mask) ** 2).mean()

        g_f = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        g_r = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_f, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-4)

    def test_window_flash_matches_banded_dot(self, hvd):
        """The Pallas kernel's in-block band mask + block skipping
        (interpret mode here) == the banded dot oracle, fwd and bwd."""
        toks = _tokens(B=2, S=16, seed=29)
        dot_model = _tiny_model("dot", window=5)
        flash_model = _tiny_model("flash", window=5)
        # every side one program, not a compile a primitive
        variables = jax.jit(dot_model.init)(jax.random.PRNGKey(30), toks)
        a = jax.jit(dot_model.apply)(variables, toks)
        b = jax.jit(flash_model.apply)(variables, toks)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-5)

        from horovod_tpu.models.transformer import lm_loss
        from horovod_tpu.parallel.tensor import unbox as _unbox
        params = _unbox(variables["params"])
        g_dot = jax.jit(jax.grad(lambda p: lm_loss(
            dot_model.apply({"params": p}, toks), toks)))(params)
        g_fla = jax.jit(jax.grad(lambda p: lm_loss(
            flash_model.apply({"params": p}, toks), toks)))(params)
        jax.tree.map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=2e-4, atol=2e-5),
            g_dot, g_fla)

    def test_moe_decode_matches_when_dropfree(self, hvd):
        """Per-token top-k routing works one tick at a time. Expert
        capacity C = ceil(k·T/E·factor) depends on tokens-per-call, so
        a capacity that drops tokens routes the full sequence and the
        1-token tick differently (both valid MoE programs) — a
        drop-free capacity factor makes the two paths exactly equal."""
        model = _tiny_model(moe_every=2, num_experts=4,
                            moe_capacity_factor=8.0)  # C ≥ all tokens
        prompt = jnp.asarray(
            np.random.RandomState(6).randint(0, 64, (2, 4)))
        params = unbox(model.init(
            jax.random.PRNGKey(7),
            jnp.zeros((2, 16), jnp.int32))["params"])
        out = generate(model, params, prompt, steps=5)
        ref = _oracle_greedy(model, params, prompt, steps=5)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_generate_example_runs():
    """examples/transformer_generate.py: train-then-generate demo
    (single device — generation is single-replica anyway)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["HOROVOD_PLATFORM"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    res = subprocess.run(
        [sys.executable, "examples/transformer_generate.py",
         "--steps", "20", "--gen-len", "8"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=420)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "generated:" in res.stdout
