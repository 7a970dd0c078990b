"""Speculative decoding in the serving engine (docs/serving.md
"Decode fast path").

Contract stack:

* **Token-exactness for ANY draft.** Greedy acceptance makes the
  engine's stream EXACTLY the target's greedy decode regardless of
  draft quality — a perfect (self-)draft and a noise-perturbed draft
  must both reproduce the plain engine's streams token for token, on
  the fixed AND the paged pool (the perturbed draft exercises the
  rejection/rewind path; the self-draft exercises full acceptance).
* **Multi-token ticks.** With the self-draft, rounds retire k+1
  tokens: metrics must show tokens_per_tick > 1 and >= 1
  multi-token tick (the ci.sh --spec-check evidence).
* **Migration equivalence.** The PR-9 contract extended to spec
  decode: a request resubmitted with its first n tokens as
  forced_prefix continues bitwise — the accepted-token COUNT (not the
  round count) is the resume state, and the rng-ordinal machinery
  stays aligned because every emitted token is one ordinal. Kill
  points are swept across round boundaries and mid-round.
* **Composition.** weight_quant="int8" at the engine door composes
  with paged pools and spec decode; streams equal `generate` on the
  quantized model (the paged×int8 token-stream equality the roadmap
  flags as untested at serving scale).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import TransformerLM, generate
from horovod_tpu.ops.quantization import quantize_lm_params
from horovod_tpu.parallel.tensor import unbox
from horovod_tpu.serving import ServingEngine

VOCAB = 64
MAX_LEN = 32


def _model():
    return TransformerLM(vocab_size=VOCAB, num_layers=2, num_heads=4,
                         head_dim=8, max_len=MAX_LEN,
                         dtype=jnp.float32)


@pytest.fixture(scope="module")
def lm(hvd):
    model = _model()
    params = unbox(model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 16), jnp.int32))["params"])
    return model, params


@pytest.fixture(scope="module")
def noisy_draft(lm):
    """The target perturbed: agrees often enough to accept, disagrees
    often enough to exercise rejection + rewind every few rounds."""
    model, params = lm
    noise = jax.tree.map(
        lambda p: (p + 0.05 * jax.random.normal(
            jax.random.PRNGKey(7), p.shape, p.dtype))
        if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
    return model, noise


def _prompts(n, seed=0, lo=1, hi=8):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, VOCAB, (int(rs.randint(lo, hi)),))
            for _ in range(n)]


def _streams(model, params, prompts, steps, **kw):
    with ServingEngine(model, params, num_slots=2, **kw) as eng:
        hs = [eng.submit(p, steps) for p in prompts]
        out = [list(h.result(timeout=300).tokens) for h in hs]
        snap = eng.metrics_snapshot()
    return out, snap


class TestSpecTokenExact:
    @pytest.mark.parametrize("paged", [False, True])
    def test_any_draft_matches_plain_greedy(self, lm, noisy_draft,
                                            paged):
        model, params = lm
        prompts = _prompts(6, seed=0)
        steps = 8
        kw = dict(paged=True, kv_block_size=8) if paged else {}
        plain, _ = _streams(model, params, prompts, steps, **kw)
        perfect, snap_p = _streams(model, params, prompts, steps,
                                   spec_draft=(model, params),
                                   spec_k=3, **kw)
        noisy, snap_n = _streams(model, params, prompts, steps,
                                 spec_draft=noisy_draft, spec_k=3,
                                 **kw)
        assert plain == perfect
        assert plain == noisy
        # ...and both equal sequential generate (the base oracle).
        for p, s in zip(prompts, plain):
            ref = np.asarray(generate(
                model, params, jnp.asarray(p)[None], steps))[0]
            np.testing.assert_array_equal(ref[len(p):], s)
        # The perfect draft accepts everything; the noisy one must
        # have actually REJECTED something, or the rewind path went
        # untested.
        assert snap_p["spec_acceptance_rate"] == 1.0
        assert snap_n["spec_acceptance_rate"] < 1.0

    def test_paged_draft_rewind_acceptance_parity(self, lm,
                                                  noisy_draft):
        """Regression: `paged_spec_round` must rewind the DRAFT cache
        exactly as the linear round does — without it the draft index
        creeps k+1 per round regardless of acceptance (wrong RoPE
        offsets, attention over rejected KV) and acceptance decays
        while output stays bitwise (the verify decides), so only the
        acceptance ACCOUNTING can catch it. Same workload, same noisy
        draft: the paged engine's proposed/accepted counters must
        equal the fixed engine's (everything is deterministic), and
        one long single-request stream keeps them aligned round by
        round."""
        model, params = lm
        prompt = _prompts(1, seed=41, lo=2, hi=4)[0]
        steps = 20
        kw = dict(spec_draft=noisy_draft, spec_k=3)
        fixed, snap_f = _streams(model, params, [prompt], steps, **kw)
        paged, snap_p = _streams(model, params, [prompt], steps,
                                 paged=True, kv_block_size=8, **kw)
        assert fixed == paged
        assert snap_f["spec_proposed"] == snap_p["spec_proposed"]
        assert snap_f["spec_accepted"] == snap_p["spec_accepted"]
        assert snap_f["spec_rounds"] == snap_p["spec_rounds"]

    def test_multi_token_ticks_and_accounting(self, lm):
        model, params = lm
        prompts = _prompts(4, seed=3)
        out, snap = _streams(model, params, prompts, 8,
                             spec_draft=(model, params), spec_k=3)
        assert snap["spec_multi_token_ticks"] >= 1
        assert snap["tokens_per_tick"] > 1
        assert snap["spec_rounds"] >= 1
        assert snap["spec_proposed"] > 0
        assert snap["spec_accepted"] == snap["spec_proposed"]
        assert snap["completed"] == len(prompts)

    def test_eos_mid_round_truncates(self, lm):
        """An eos landing inside a multi-token round must truncate the
        stream exactly where the plain engine's does."""
        model, params = lm
        prompt = _prompts(1, seed=5)[0]
        steps = 10
        probe = np.asarray(generate(
            model, params, jnp.asarray(prompt)[None], steps))[0]
        eos = int(probe[len(prompt) + steps // 2])
        plain, _ = _streams(model, params, [prompt], steps,
                            eos_id=eos)
        spec, _ = _streams(model, params, [prompt], steps,
                           spec_draft=(model, params), spec_k=3,
                           eos_id=eos)
        assert plain == spec
        assert plain[0][-1] == eos

    def test_sampling_rejected_in_spec_mode(self, lm):
        model, params = lm
        with ServingEngine(model, params, num_slots=1,
                           spec_draft=(model, params),
                           spec_k=2) as eng:
            with pytest.raises(ValueError, match="greedy-only"):
                eng.submit(np.array([1, 2]), 4, temperature=0.7)

    def test_spec_headroom_bound(self, lm):
        """The verify block's k-token overshoot must fit the cache:
        submits that would clamp a linear-cache write shed at the
        door."""
        model, params = lm
        with ServingEngine(model, params, num_slots=1,
                           spec_draft=(model, params),
                           spec_k=4) as eng:
            with pytest.raises(ValueError, match="headroom"):
                eng.submit(np.arange(8), MAX_LEN - 8 - 1)
            # The same request fits once k is budgeted for.
            h = eng.submit(np.arange(8), MAX_LEN - 8 - 4)
            h.result(timeout=300)

    def test_draft_validation(self, lm):
        model, params = lm
        small_vocab = TransformerLM(
            vocab_size=VOCAB // 2, num_layers=1, num_heads=2,
            head_dim=8, max_len=MAX_LEN, dtype=jnp.float32)
        with pytest.raises(ValueError, match="vocab"):
            ServingEngine(model, params, num_slots=1,
                          spec_draft=(small_vocab, params), spec_k=2)


class TestSpecMigration:
    """Forced-prefix migration stays bitwise under spec decode: the
    resume state is the accepted-token COUNT (len(tokens)), not the
    round count — kill points are swept so resumes land both on round
    boundaries and mid-round."""

    @pytest.mark.parametrize("paged", [False, True])
    def test_forced_prefix_bitwise_all_kill_points(self, lm, paged):
        model, params = lm
        prompt = _prompts(1, seed=17)[0]
        steps = 10
        kw = dict(spec_draft=(model, params), spec_k=3)
        if paged:
            kw.update(paged=True, kv_block_size=8)
        ref, _ = _streams(model, params, [prompt], steps, **kw)
        ref = ref[0]
        for k in (1, 2, 3, 4, 7, steps - 1):
            out, _ = _streams(model, params, [prompt], steps, **kw)
            with ServingEngine(model, params, num_slots=2,
                               **kw) as eng:
                r = eng.submit(prompt, steps,
                               forced_prefix=ref[:k]).result(
                    timeout=300)
            assert list(r.tokens) == ref, (paged, k)
            assert len(r.tokens) == steps

    def test_watchdog_restart_replays_exact(self, lm):
        """A dispatch crash mid-spec-serving heals in place and the
        requeued requests replay bitwise (clone_fresh carries the
        draft cache config; replay-from-prompt is deterministic)."""
        from horovod_tpu.resilience import chaos
        model, params = lm
        prompts = _prompts(4, seed=31)
        ref, _ = _streams(model, params, prompts, 8,
                          spec_draft=(model, params), spec_k=3)
        eng = ServingEngine(model, params, num_slots=2,
                            spec_draft=(model, params), spec_k=3,
                            auto_restart=True, max_restarts=4)
        try:
            hs = [eng.submit(p, 8) for p in prompts]
            chaos.install(chaos.ChaosMonkey("serving_dispatch_crash:1"))
            out = [list(h.result(timeout=300).tokens) for h in hs]
            snap = eng.metrics_snapshot()
        finally:
            eng.shutdown()
            chaos.install(None)
        assert snap["restarts"] >= 1
        assert out == ref

    def test_cross_engine_resume(self, lm, noisy_draft):
        """A stream started on a SPEC engine resumes bitwise on a
        plain engine and vice versa (greedy streams are
        engine-agnostic — the router can migrate across heterogeneous
        replicas)."""
        model, params = lm
        prompt = _prompts(1, seed=23)[0]
        steps = 9
        spec, _ = _streams(model, params, [prompt], steps,
                           spec_draft=noisy_draft, spec_k=3)
        plain, _ = _streams(model, params, [prompt], steps)
        assert spec == plain
        k = 4
        with ServingEngine(model, params, num_slots=1) as eng:
            on_plain = list(eng.submit(
                prompt, steps,
                forced_prefix=spec[0][:k]).result(timeout=300).tokens)
        with ServingEngine(model, params, num_slots=1,
                           spec_draft=noisy_draft, spec_k=3) as eng:
            on_spec = list(eng.submit(
                prompt, steps,
                forced_prefix=plain[0][:k]).result(timeout=300).tokens)
        assert on_plain == spec[0]
        assert on_spec == plain[0]


class TestWeightQuantServing:
    def test_paged_int8_token_stream_equality(self, lm):
        """ServingEngine(weight_quant="int8"): fixed == paged ==
        generate on the quantized tree (scales as pooled leaves at
        serving scale)."""
        model, params = lm
        qm = model.clone(weight_quant="int8")
        qp = quantize_lm_params(params)
        prompts = _prompts(5, seed=9)
        steps = 7
        refs = [list(np.asarray(generate(
            qm, qp, jnp.asarray(p)[None], steps))[0][len(p):])
            for p in prompts]
        fixed, snap = _streams(model, params, prompts, steps,
                               weight_quant="int8")
        paged, _ = _streams(model, params, prompts, steps,
                            weight_quant="int8", paged=True,
                            kv_block_size=8)
        assert fixed == refs
        assert paged == refs
        assert snap["completed"] == len(prompts)

    def test_pre_quantized_params_pass_through(self, lm):
        """A caller who already quantized gets no double transform."""
        model, params = lm
        qm = model.clone(weight_quant="int8")
        qp = quantize_lm_params(params)
        a, _ = _streams(model, params, _prompts(2, seed=2), 5,
                        weight_quant="int8")
        b, _ = _streams(qm, qp, _prompts(2, seed=2), 5,
                        weight_quant="int8")
        assert a == b

    def test_spec_paged_int8_compose(self, lm):
        model, params = lm
        qm = model.clone(weight_quant="int8")
        qp = quantize_lm_params(params)
        prompts = _prompts(4, seed=4)
        steps = 7
        refs = [list(np.asarray(generate(
            qm, qp, jnp.asarray(p)[None], steps))[0][len(p):])
            for p in prompts]
        out, snap = _streams(model, params, prompts, steps,
                             weight_quant="int8", paged=True,
                             kv_block_size=8,
                             spec_draft=(qm, qp), spec_k=3)
        assert out == refs
        assert snap["spec_multi_token_ticks"] >= 1

    def test_env_knob_weight_quant(self, lm, monkeypatch):
        model, params = lm
        monkeypatch.setenv("HVD_WEIGHT_QUANT", "int8")
        from horovod_tpu.runtime.config import config
        config.refresh()
        try:
            with ServingEngine(model, params, num_slots=1) as eng:
                assert eng.weight_quant == "int8"
        finally:
            monkeypatch.delenv("HVD_WEIGHT_QUANT")
            config.refresh()
