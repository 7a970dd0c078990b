"""Serving engine tests: continuous batching, admission, faults.

Oracle style (SURVEY §4): the continuous-batching engine must produce
EXACTLY the tokens sequential `generate` produces for every request,
no matter how requests interleave across slots — greedy decode is the
token-exact contract, sampling is reproducible per request seed.

Fault style (the admission contract): overload sheds (`QueueFullError`
at submit), deadlines raise (`DeadlineExceededError`, never a hang),
cancellation frees the slot, shutdown drains cleanly.

Everything runs one tiny f32 model config so the slot-tick / prefill
jit caches are shared across the whole module (flax modules hash by
their dataclass fields).
"""

import functools
import time
from concurrent.futures import CancelledError

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import (
    TransformerLM, chunk_width, generate, prefill_chunks,
    slot_prefill_chunk,
)
from horovod_tpu.parallel.tensor import unbox
from horovod_tpu.serving import (
    DeadlineExceededError, EngineClosedError, QueueFullError,
    ServingEngine,
)

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


def _prompts(n, seed=0, lo=1, hi=8):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, VOCAB, (int(rs.randint(lo, hi)),))
            for _ in range(n)]


# ---- the padded prompt tail, by cache kind ---------------------------------
TAIL_B = 8          # the chunk budget (and width) of these cases
TAIL_LEN = 28       # no multiple of it: a tail can pass the cache's end
# P % B in {1, 5, B - 1}; P < B; no tail; a prompt that ends within B
# rows of max_len (the tail starts at 24: a `dynamic_update_slice` of
# 8 rows there would be clamped to 20 and shift over real rows)
TAIL_CASES = {"mod-1": 17, "mod-5": 13, "mod-7": 23, "short": 5,
              "whole": 16, "clamp": 27}


def _tail_fields(kind):
    from horovod_tpu.models.transformer import AttnSpec
    from horovod_tpu.parallel.latent_attention import LatentSpec
    from horovod_tpu.parallel.state_space import SsmSpec
    experts = dict(moe_every=1, moe_impl="dropless", num_experts=8,
                   moe_k=2, moe_hidden=32)
    return {
        # learned positions: the table's slice clamps as K/V's does
        "kv-128": dict(num_heads=2, head_dim=128, num_kv_heads=1),
        # two 64-wide KV heads to a stored row
        "kv-64x2": dict(num_heads=4, head_dim=64, num_kv_heads=2,
                        pos_emb="rope"),
        "kv-int8": dict(num_heads=4, head_dim=8, kv_quant="int8",
                        pos_emb="rope"),
        # a ring of 12 slots: the tail's pads would lap live rows
        "ring": dict(num_heads=4, head_dim=8, pos_emb="rope",
                     layer_kinds=("swa",),
                     attn_specs=(("swa", AttnSpec(window=12)),)),
        "latent": dict(num_heads=4, head_dim=8, pos_emb="rope",
                       layer_kinds=("mla",),
                       latent=LatentSpec(q_rank=16, kv_rank=16,
                                         nope_dim=8, rope_dim=8,
                                         v_dim=8)),
        "kda": dict(num_heads=4, head_dim=8, pos_emb="none",
                    layer_kinds=("kda",)),
        "ssm": dict(num_heads=4, head_dim=8, pos_emb="none",
                    layer_kinds=("ssm",),
                    ssm=SsmSpec(num_heads=4, head_dim=8, state_size=16,
                                groups=2)),
        # identity experts: `moe_zero_pairs` / `moe_chosen_pairs`
        "experts-zero": dict(num_heads=4, head_dim=8, pos_emb="rope",
                             moe_held=(2, 4), moe_zero_experts=2,
                             moe_router="softmax", **experts),
        # a group-limited choice over a share: `moe_token_chips`
        "experts-groups": dict(num_heads=4, head_dim=8, pos_emb="rope",
                               moe_held=(0, 4), moe_groups=(4, 2),
                               moe_shared_hidden=16, **experts),
    }[kind]


TAIL_KINDS = ("kv-128", "kv-64x2", "kv-int8", "ring", "latent", "kda",
              "ssm", "experts-zero", "experts-groups")


@functools.lru_cache(maxsize=None)
def _tail_model(kind):
    """(model, params) of one tiny float32 layer holding the kind."""
    model = TransformerLM(vocab_size=VOCAB, num_layers=1,
                          max_len=TAIL_LEN, dtype=jnp.float32,
                          **_tail_fields(kind))
    params = unbox(model.init(
        jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))["params"])
    return model, params


def _wait(cond, timeout=60.0, dt=0.005):
    t0 = time.time()
    while not cond():
        if time.time() - t0 > timeout:
            raise AssertionError("condition not reached in time")
        time.sleep(dt)


class TestEngineOracle:
    def test_mixed_lengths_token_exact(self, lm):
        """Acceptance: >= 8 concurrent mixed-length requests through 3
        slots (so retire/refill actually happens) == sequential
        `generate` per request, token for token."""
        model, params = lm
        prompts = _prompts(8, seed=0)
        steps = 8
        with ServingEngine(model, params, num_slots=3,
                           max_queue=16) as eng:
            handles = [eng.submit(p, steps) for p in prompts]
            results = [h.result(timeout=300) for h in handles]
        assert eng.metrics_snapshot()["completed"] == 8
        for p, r in zip(prompts, results):
            ref = np.asarray(
                generate(model, params, jnp.asarray(p)[None], steps))[0]
            np.testing.assert_array_equal(r.full_sequence, ref)
            assert r.finish_reason == "length"
            assert len(r.tokens) == steps

    def test_staggered_arrival_token_exact(self, lm):
        """A request admitted into a slot that sat FREE for many ticks
        must still be token-exact: idle slots keep riding the shared
        vmapped tick and creep their fill index, so prefill must
        reset the slot at use time (regression — staggered arrivals
        used to prefill at the crept index and corrupt the output)."""
        model, params = lm
        pa, pb = _prompts(2, seed=7)
        with ServingEngine(model, params, num_slots=2) as eng:
            a = eng.submit(pa, 20)
            # Let the free slot idle-tick alongside A's decode.
            _wait(lambda: len(a.tokens_so_far()) >= 6, timeout=120)
            b = eng.submit(pb, 8)
            ra, rb = a.result(timeout=300), b.result(timeout=300)
        for p, r, steps in ((pa, ra, 20), (pb, rb, 8)):
            ref = np.asarray(generate(model, params,
                                      jnp.asarray(p)[None], steps))[0]
            np.testing.assert_array_equal(r.full_sequence, ref)

    def test_eos_matches_generate_contract(self, lm):
        """With eos_id, the engine's output equals `generate`'s row
        truncated just past the first eos."""
        model, params = lm
        prompt = _prompts(1, seed=3)[0]
        steps = 10
        probe = np.asarray(
            generate(model, params, jnp.asarray(prompt)[None], steps))[0]
        P = prompt.shape[0]
        eos = int(probe[P + steps // 2])   # occurs mid-stream
        ref = np.asarray(
            generate(model, params, jnp.asarray(prompt)[None], steps,
                     eos_id=eos, pad_id=VOCAB - 1))[0]
        gen = ref[P:]
        hit = np.where(gen == eos)[0]
        want = gen[:hit[0] + 1] if hit.size else gen
        with ServingEngine(model, params, num_slots=3,
                           eos_id=eos) as eng:
            out = eng.submit(prompt, steps).result(timeout=300)
        np.testing.assert_array_equal(out.tokens, want)
        if hit.size:
            assert out.finish_reason == "eos"

    def test_sampling_reproducible_per_seed(self, lm):
        """Same request seed => same sampled tokens regardless of what
        shares the batch; different seeds diverge."""
        model, params = lm
        prompt = _prompts(1, seed=5)[0]

        def run(seed, extra):
            with ServingEngine(model, params, num_slots=3) as eng:
                hs = [eng.submit(prompt, 8, temperature=1.0,
                                 top_p=0.9, seed=seed)]
                for i in range(extra):
                    hs.append(eng.submit(_prompts(1, seed=9 + i)[0], 8,
                                         temperature=0.7, seed=i))
                return [h.result(timeout=300).tokens for h in hs][0]

        a = run(seed=42, extra=0)
        b = run(seed=42, extra=2)   # different batch-mates
        c = run(seed=43, extra=0)
        np.testing.assert_array_equal(a, b)
        assert not np.array_equal(a, c)


class TestForcedPrefix:
    """`submit(forced_prefix=...)` — the token-exact continuation
    primitive behind router migration (docs/serving.md 'Fleet
    failover'): tokens an earlier engine already generated are
    teacher-forced into the cache and the sample stream resumes at
    the right ordinal, so the completed stream is bitwise what an
    uninterrupted run produces."""

    @pytest.mark.parametrize("temp,top_p,seed",
                             [(0.0, None, 0), (0.8, None, 5),
                              (1.1, 0.9, 3)])
    def test_continuation_bitwise_exact(self, lm, temp, top_p, seed):
        model, params = lm
        prompt = _prompts(1, seed=17)[0]
        steps = 12
        with ServingEngine(model, params, num_slots=2) as eng:
            ref = list(eng.submit(prompt, steps, temperature=temp,
                                  top_p=top_p, seed=seed)
                       .result(timeout=300).tokens)
        for k in (1, 5, steps - 1):
            with ServingEngine(model, params, num_slots=2) as eng:
                r = eng.submit(prompt, steps, temperature=temp,
                               top_p=top_p, seed=seed,
                               forced_prefix=ref[:k]).result(
                    timeout=300)
            assert list(r.tokens) == ref, (temp, k)
            # The forced span pre-seeds the stream: the handle's view
            # and the result both contain the WHOLE stream.
            assert len(r.tokens) == steps

    def test_paged_continuation_bitwise_exact(self, lm):
        """The paged pool path: the forced prefix rides the prefix
        matcher (prompt ++ forced) and continues bitwise."""
        model, params = lm
        prompt = _prompts(1, seed=23)[0]
        steps = 10
        kw = dict(paged=True, kv_block_size=4)
        with ServingEngine(model, params, num_slots=2, **kw) as eng:
            ref = list(eng.submit(prompt, steps, temperature=0.7,
                                  seed=2).result(timeout=300).tokens)
        with ServingEngine(model, params, num_slots=2, **kw) as eng:
            r = eng.submit(prompt, steps, temperature=0.7, seed=2,
                           forced_prefix=ref[:6]).result(timeout=300)
        assert list(r.tokens) == ref

    def test_eos_in_continuation_still_stops(self, lm):
        """A continuation whose next sampled token is eos retires as
        'eos' exactly like the uninterrupted run."""
        model, params = lm
        prompt = _prompts(1, seed=3)[0]
        steps = 10
        probe = np.asarray(generate(
            model, params, jnp.asarray(prompt)[None], steps))[0]
        eos = int(probe[prompt.shape[0] + steps // 2])
        with ServingEngine(model, params, num_slots=1,
                           eos_id=eos) as eng:
            ref = eng.submit(prompt, steps).result(timeout=300)
        assert ref.finish_reason == "eos"
        k = len(ref.tokens) - 1    # everything but the eos itself
        if k:
            with ServingEngine(model, params, num_slots=1,
                               eos_id=eos) as eng:
                r = eng.submit(prompt, steps,
                               forced_prefix=list(ref.tokens)[:k]
                               ).result(timeout=300)
            assert r.finish_reason == "eos"
            np.testing.assert_array_equal(r.tokens, ref.tokens)

    def test_forced_prefix_validation(self, lm):
        model, params = lm
        with ServingEngine(model, params, num_slots=1,
                           eos_id=7) as eng:
            with pytest.raises(ValueError, match="decode budget"):
                eng.submit(np.array([1]), 4, forced_prefix=[1, 2, 3, 4])
            with pytest.raises(ValueError, match="eos_id"):
                eng.submit(np.array([1]), 4, forced_prefix=[3, 7])
            with pytest.raises(ValueError, match="integer"):
                eng.submit(np.array([1]), 4, forced_prefix=[1.5])

    def test_trace_id_override(self, lm):
        """submit(trace_id=...) keeps a migrated request's identity —
        the handle, the result and the retire event all carry it."""
        model, params = lm
        with ServingEngine(model, params, num_slots=1) as eng:
            h = eng.submit(np.array([4]), 3, trace_id="cafe" * 4)
            out = h.result(timeout=300)
        assert h.trace_id == "cafe" * 4
        assert out.trace_id == "cafe" * 4


class TestAdmission:
    def test_full_queue_sheds_immediately(self, lm):
        """Queue at capacity => submit raises QueueFullError NOW (no
        blocking), and the engine keeps serving what it admitted."""
        model, params = lm
        with ServingEngine(model, params, num_slots=1,
                           max_queue=1) as eng:
            a = eng.submit(np.array([2]), 31)   # hold the slot a while
            # Wait until A owns the slot so B is deterministically the
            # one queued entry and C the shed one.
            _wait(lambda: eng.metrics_snapshot()["slots_busy"] == 1
                  or a.done(), timeout=120)
            b = eng.submit(_prompts(1, seed=21)[0], 4)
            t0 = time.time()
            with pytest.raises(QueueFullError):
                eng.submit(_prompts(1, seed=22)[0], 4)
            assert time.time() - t0 < 5.0   # shed, not blocked
            assert eng.metrics_snapshot()["rejected"] == 1
            a.result(timeout=300)
            b.result(timeout=300)

    def test_queued_deadline_expires_as_timeout(self, lm):
        """A request whose deadline passes while still queued gets
        DeadlineExceededError — not a hang, not a late run."""
        model, params = lm
        with ServingEngine(model, params, num_slots=1) as eng:
            a = eng.submit(np.array([3]), 16)
            _wait(lambda: eng.metrics_snapshot()["slots_busy"] == 1
                  or a.done(), timeout=120)
            b = eng.submit(_prompts(1, seed=31)[0], 16, timeout_s=1e-4)
            with pytest.raises(DeadlineExceededError):
                b.result(timeout=300)
            assert a.result(timeout=300).finish_reason == "length"
        assert eng.metrics_snapshot()["timed_out"] == 1

    def test_running_deadline_expires_with_partial(self, lm):
        """Deadline passing mid-decode retires the request with its
        partial tokens attached (deterministic via the scheduler
        directly: admit, then age the clock past the deadline)."""
        import horovod_tpu.serving as sv
        from concurrent.futures import Future
        from horovod_tpu.serving.admission import Request, SamplingParams
        model, params = lm
        pool = sv.SlotPool(model, params, 1)
        queue = sv.AdmissionQueue(4)
        metrics = sv.EngineMetrics()
        sched = sv.ContinuousBatchingScheduler(pool, queue, metrics)
        now = time.time()
        req = Request(id=0, prompt=_prompts(1, seed=40)[0],
                      max_new_tokens=16, sampling=SamplingParams(),
                      deadline=now + 3600, future=Future(),
                      t_submit=now)
        queue.offer(req)
        sched.step()                       # admit + first tick
        assert sched.has_active() and len(req.tokens) >= 1
        req.deadline = time.time() - 1.0   # age past the deadline
        sched.step()
        assert not sched.has_active()      # slot freed
        assert pool.free_slots == 1
        with pytest.raises(DeadlineExceededError) as ei:
            req.future.result(timeout=0)
        assert len(ei.value.partial_tokens) >= 1
        assert metrics.timed_out == 1

    def test_queued_death_resolves_with_all_slots_busy(self, lm):
        """Dying needs no slot: a queued request's cancel/expiry must
        resolve at the next tick even while EVERY slot is busy — not
        minutes later when one frees (review regression: _admit's
        pop was the only resolution point and it is gated on a free
        slot)."""
        import horovod_tpu.serving as sv
        from concurrent.futures import Future
        from horovod_tpu.serving.admission import (Request,
                                                   SamplingParams)
        model, params = lm
        pool = sv.SlotPool(model, params, 1)
        queue = sv.AdmissionQueue(4)
        metrics = sv.EngineMetrics()
        sched = sv.ContinuousBatchingScheduler(pool, queue, metrics)
        now = time.time()

        def req(i, deadline=None):
            return Request(id=i, prompt=np.array([3 + i]),
                           max_new_tokens=16,
                           sampling=SamplingParams(),
                           deadline=deadline, future=Future(),
                           t_submit=now)

        a = req(0)
        queue.offer(a)
        sched.step()                    # a takes the only slot
        assert sched.has_active()
        b = req(1, deadline=now - 1.0)  # expired while queued
        c = req(2)
        c.cancel()                      # cancelled while queued
        queue.offer(b)
        queue.offer(c)
        sched.step()                    # slot still busy: sweep runs
        assert sched.has_active()       # a unaffected
        with pytest.raises(DeadlineExceededError):
            b.future.result(timeout=0)
        with pytest.raises(CancelledError):
            c.future.result(timeout=0)
        assert metrics.timed_out == 1 and metrics.cancelled == 1

    def test_idle_slot_fill_index_frozen(self, lm):
        """A never-allocated free slot rides the shared vmapped tick
        but its fill index must stay FROZEN at 0 (the PR-3 live mask;
        the vmapped prefix-attention loop runs to the MAX lane's trip
        count, so any creep would tax every active slot). The old
        periodic-idle-reset machinery is gone — its RESET_IDLE_TICKS
        ceiling survives only as a deprecation shim."""
        from horovod_tpu.serving.slots import SlotPool
        model, params = lm
        pool = SlotPool(model, params, 2)
        slot = pool.alloc()
        pool.prefill(slot, np.array([5, 9]), 0.0, None, 0)
        for _ in range(80):
            pool.tick()
        fills = pool.fill_indices()
        assert fills[1 - slot] == 0, fills

    def test_reset_idle_ticks_shim_warns(self, hvd):
        """Importing the obsoleted constant still works (deprecation
        shim) but warns; anything else raises AttributeError."""
        import horovod_tpu.serving.slots as slots_mod
        with pytest.warns(DeprecationWarning, match="RESET_IDLE_TICKS"):
            assert slots_mod.RESET_IDLE_TICKS == 64
        with pytest.raises(AttributeError):
            slots_mod.NOT_A_REAL_NAME

    def test_cancel_frees_slot_for_next_request(self, lm):
        """Cancelling a running request retires it at the next tick;
        its slot immediately serves the next request."""
        model, params = lm
        with ServingEngine(model, params, num_slots=1) as eng:
            a = eng.submit(np.array([5]), 31)   # long budget: no racy
            _wait(lambda: len(a.tokens_so_far()) >= 1, timeout=120)
            b = eng.submit(_prompts(1, seed=51)[0], 4)
            a.cancel()
            with pytest.raises(CancelledError):
                a.result(timeout=300)
            out = b.result(timeout=300)    # b got the freed slot
            assert out.finish_reason == "length"
        snap = eng.metrics_snapshot()
        assert snap["cancelled"] == 1 and snap["completed"] == 1

    def test_cancel_queued_releases_admission_slot_immediately(
            self, lm):
        """Regression (the hedging dependency, docs/serving.md 'Fleet
        failover'): cancelling a still-QUEUED request must release its
        admission slot NOW — its future resolves without waiting for a
        dispatcher pop, and a new submit admits into the freed
        capacity instead of shedding."""
        model, params = lm
        with ServingEngine(model, params, num_slots=1,
                           max_queue=2) as eng:
            blocker = eng.submit(np.array([5]), 31)
            _wait(lambda: len(blocker.tokens_so_far()) >= 1,
                  timeout=120)
            q1 = eng.submit(_prompts(1, seed=60)[0], 4)
            q2 = eng.submit(_prompts(1, seed=61)[0], 4)
            with pytest.raises(QueueFullError):
                eng.submit(_prompts(1, seed=62)[0], 4)   # queue full
            q1.cancel()
            # The cancel resolved the future inline — no dispatcher
            # involvement, no sweep latency.
            with pytest.raises(CancelledError):
                q1.result(timeout=0.5)
            # ...and the slot is free for the next submit RIGHT NOW.
            q3 = eng.submit(_prompts(1, seed=63)[0], 4)
            blocker.cancel()
            assert q2.result(timeout=300).finish_reason == "length"
            assert q3.result(timeout=300).finish_reason == "length"
        snap = eng.metrics_snapshot()
        assert snap["cancelled"] == 2 and snap["completed"] == 2

    def test_submit_validation(self, lm):
        model, params = lm
        with ServingEngine(model, params, num_slots=1) as eng:
            with pytest.raises(ValueError, match="1-D"):
                eng.submit(np.zeros((2, 3), np.int32), 4)
            with pytest.raises(ValueError, match="max_new_tokens"):
                eng.submit(np.array([1, 2]), 0)
            with pytest.raises(ValueError, match="max_len"):
                eng.submit(np.arange(MAX_LEN), 8)
            with pytest.raises(ValueError, match="top_p"):
                eng.submit(np.array([1]), 4, temperature=1.0, top_p=1.5)
            with pytest.raises(ValueError, match="temperature"):
                eng.submit(np.array([1]), 4, temperature=-0.1)


class TestShutdown:
    def test_drain_finishes_everything(self, lm):
        """shutdown(drain=True) completes queued AND running requests
        before returning — the clean-exit acceptance path."""
        model, params = lm
        eng = ServingEngine(model, params, num_slots=2, max_queue=16)
        handles = [eng.submit(p, 6) for p in _prompts(6, seed=60)]
        eng.shutdown(drain=True)
        assert all(h.done() for h in handles)
        assert {h.result(0).finish_reason for h in handles} == {"length"}
        assert eng.metrics_snapshot()["completed"] == 6

    def test_no_drain_fails_fast_and_closes_submit(self, lm):
        model, params = lm
        eng = ServingEngine(model, params, num_slots=1, max_queue=8)
        a = eng.submit(np.array([7]), 31)
        b = eng.submit(_prompts(1, seed=71)[0], 16)
        eng.shutdown(drain=False)
        with pytest.raises(EngineClosedError):
            a.result(timeout=0)
        with pytest.raises(EngineClosedError):
            b.result(timeout=0)
        with pytest.raises(EngineClosedError):
            eng.submit(np.array([1]), 4)

    def test_shutdown_idempotent(self, lm):
        model, params = lm
        eng = ServingEngine(model, params, num_slots=1)
        eng.shutdown()
        eng.shutdown()

    def test_submit_racing_shutdown_never_hangs(self, lm):
        """A submit whose offer lands after the dispatcher exited but
        before the queue flipped closed (the shutdown race window)
        must still resolve — shutdown re-closes the queue after the
        join and fails stragglers (review regression)."""
        model, params = lm
        eng = ServingEngine(model, params, num_slots=1)
        with eng._lock:
            eng._closing = True           # dispatcher exits...
        eng._thread.join(30)
        assert not eng._thread.is_alive()
        h = eng.submit(np.array([1]), 4)  # ...queue still open: lands
        eng.shutdown(drain=True)
        with pytest.raises(EngineClosedError):
            h.result(timeout=10)

    def test_force_stop_after_drain_fails_queued(self, lm):
        """Downgrade path: shutdown(drain=False) AFTER a drain began
        must still fail whatever is queued — no future may be left
        pending (review finding: the first close used to win)."""
        model, params = lm
        eng = ServingEngine(model, params, num_slots=1, max_queue=8)
        a = eng.submit(np.array([7]), 31)
        b = eng.submit(np.array([8]), 31)
        with eng._lock:        # freeze the drain decision mid-flight
            eng._closing, eng._drain = True, True
        eng.shutdown(drain=False)
        for h in (a, b):
            with pytest.raises(EngineClosedError):
                h.result(timeout=60)

    def test_dispatcher_fault_fails_futures_not_hangs(self, lm):
        """Degrade-by-shedding extends to engine faults: if the
        dispatch thread dies (poisoned prefill), every pending future
        resolves with EngineClosedError instead of hanging, and later
        submits are rejected."""
        model, params = lm
        eng = ServingEngine(model, params, num_slots=1, max_queue=8)

        def boom(*a, **kw):
            raise RuntimeError("injected prefill fault")

        eng.pool.prefill_chunk = boom
        a = eng.submit(np.array([1, 2]), 4)
        b = eng.submit(np.array([3]), 4)
        for h in (a, b):
            with pytest.raises(EngineClosedError):
                h.result(timeout=60)
        with pytest.raises(EngineClosedError):
            eng.submit(np.array([1]), 2)

    def test_submit_rejects_non_integer_prompt(self, lm):
        model, params = lm
        with ServingEngine(model, params, num_slots=1) as eng:
            with pytest.raises(ValueError, match="integer"):
                eng.submit(np.array([1.5, 2.5]), 4)


class TestHotPathPipelining:
    """PR-3 tentpole: async tick ring, interleaved chunked prefill,
    on-device stop detection, program warmup."""

    def test_pipeline_depths_token_exact_and_syncs_reduced(self, lm):
        """Depth 0 (sync every tick, the PR-1 shape) and depth 1 (the
        one-deep in-flight ring) must produce identical tokens; the
        ring must strictly reduce exposed host syncs per token (the
        tentpole's metric) by overlapping tick reads with the next
        tick's compute."""
        model, params = lm
        prompts = _prompts(5, seed=11)
        steps = 10

        def run(depth):
            with ServingEngine(model, params, num_slots=2,
                               max_queue=16,
                               pipeline_depth=depth) as eng:
                hs = [eng.submit(p, steps) for p in prompts]
                toks = [h.result(timeout=300).tokens for h in hs]
            return toks, eng.metrics_snapshot()

        t0, s0 = run(0)
        t1, s1 = run(1)
        for a, b in zip(t0, t1):
            np.testing.assert_array_equal(a, b)
        assert s0["ticks_overlapped"] == 0
        assert s1["ticks_overlapped"] > 0
        assert s1["host_syncs"] < s0["host_syncs"]
        assert (s1["host_syncs_per_token"]
                < s0["host_syncs_per_token"])
        assert s0["pipeline_depth"] == 0 and s1["pipeline_depth"] == 1

    def test_long_prompt_prefill_interleaves_with_decode(self, lm):
        """A long prompt admitted while another slot decodes must NOT
        stream all its chunks in one scheduler step: the budget caps
        prompt tokens per step, the victim gains tokens between the
        chunks, and both outputs stay token-exact (driven through the
        scheduler directly so interleaving is observable)."""
        import horovod_tpu.serving as sv
        from concurrent.futures import Future
        from horovod_tpu.serving.admission import (Request,
                                                   SamplingParams)
        model, params = lm
        pool = sv.SlotPool(model, params, 2)
        queue = sv.AdmissionQueue(4)
        metrics = sv.EngineMetrics()
        sched = sv.ContinuousBatchingScheduler(
            pool, queue, metrics, prefill_chunk_budget=2,
            pipeline_depth=1)
        now = time.time()
        short = np.array([5, 9, 11])
        long_p = np.arange(1, 15)   # 14 tokens -> 7 budget-2 chunks

        def req(i, prompt, steps):
            return Request(id=i, prompt=prompt, max_new_tokens=steps,
                           sampling=SamplingParams(), deadline=None,
                           future=Future(), t_submit=now)

        a, b = req(0, short, 16), req(1, long_p, 4)
        queue.offer(a)
        sched.step()
        assert sched.has_active()
        queue.offer(b)
        interleaved_steps = 0
        victim_gains = 0
        while not b.future.done() or not a.future.done():
            n_before = len(a.tokens)
            sched.step()
            if sched.prefilling:
                interleaved_steps += 1
                victim_gains += len(a.tokens) - n_before
        # The 7-chunk prefill spread over >= 3 scheduler steps and the
        # victim kept decoding through them.
        assert interleaved_steps >= 3, interleaved_steps
        assert victim_gains >= 2, victim_gains
        # 3 tokens = one whole chunk + a tail of 1 padded to 2; 14 = 7
        # whole chunks: pads are in no token count
        assert metrics.prefill_chunks == 9
        assert metrics.prefill_tokens == 17
        assert metrics.prefill_tail_chunks == 1
        assert metrics.prefill_pad_tokens == 1
        for prompt, r, steps in ((short, a, 16), (long_p, b, 4)):
            ref = np.asarray(generate(
                model, params, jnp.asarray(prompt)[None], steps))[0]
            np.testing.assert_array_equal(
                np.concatenate([prompt, r.future.result(0).tokens]),
                ref)

    def test_on_device_stop_masks_post_eos(self, lm):
        """On-device stop detection: once a lane emits eos, every
        later tick re-emits eos for it (the done flag masks the lane
        on device) and its fill index freezes — no second host sync is
        needed to stop a finished slot from corrupting the stream."""
        from horovod_tpu.serving.slots import SlotPool
        model, params = lm
        prompt = _prompts(1, seed=3)[0]
        probe = np.asarray(generate(model, params,
                                    jnp.asarray(prompt)[None], 10))[0]
        eos = int(probe[prompt.shape[0] + 4])   # occurs mid-stream
        pool = SlotPool(model, params, 2, eos_id=eos)
        slot = pool.alloc()
        seen = [pool.prefill(slot, prompt, 0.0, None, 0)]
        for _ in range(10):
            seen.append(int(pool.tick()[slot]))
        hit = seen.index(eos)
        assert hit <= 5
        assert all(t == eos for t in seen[hit:]), seen
        fills = pool.fill_indices()
        # Done lane frozen at its stop fill; free lane never crept.
        assert fills[slot] <= prompt.shape[0] + hit + 1
        assert fills[1 - slot] == 0

    def test_mid_prefill_cancel_frees_slot(self, lm):
        """Cancelling a request whose prompt is still streaming in
        chunks frees its slot without paying the remaining chunks."""
        import horovod_tpu.serving as sv
        from concurrent.futures import Future
        from horovod_tpu.serving.admission import (Request,
                                                   SamplingParams)
        model, params = lm
        pool = sv.SlotPool(model, params, 1)
        queue = sv.AdmissionQueue(4)
        metrics = sv.EngineMetrics()
        sched = sv.ContinuousBatchingScheduler(
            pool, queue, metrics, prefill_chunk_budget=2)
        req = Request(id=0, prompt=np.arange(1, 15),
                      max_new_tokens=8, sampling=SamplingParams(),
                      deadline=None, future=Future(),
                      t_submit=time.time())
        queue.offer(req)
        sched.step()
        assert sched.prefilling and not req.future.done()
        chunks_before = metrics.prefill_chunks
        req.cancel()
        sched.step()
        assert not sched.prefilling and not sched.has_active()
        assert pool.free_slots == 1
        assert metrics.prefill_chunks == chunks_before
        with pytest.raises(CancelledError):
            req.future.result(timeout=0)
        assert metrics.cancelled == 1

    def test_warmup_precompiles_hot_path(self, lm):
        """ServingEngine(warmup=True): the tick + pinned prefill
        bucket set compile at construction, so the serving window is
        compile-free (`compiles == 0`) — the guarantee the ci.sh
        smoke asserts and the PR-2 watchdog no longer needs
        `maybe_compiling` to paper over."""
        model, params = lm
        with ServingEngine(model, params, num_slots=2, max_queue=16,
                           warmup=True) as eng:
            assert eng.warmup_info is not None
            hs = [eng.submit(p, 6) for p in _prompts(4, seed=13)]
            for h in hs:
                h.result(timeout=300)
            snap = eng.metrics_snapshot()
        assert snap["compiles"] == 0, snap["compiles"]
        assert snap["warmup_s"] is not None
        # A pool-level cold run of the same shapes registers them as
        # first-time (the warmup's own count is >= the tick + chunk
        # set it pinned).
        assert snap["warmup_compiles"] >= 3

    def test_warmup_is_two_chunk_programs_for_every_prompt_length(
            self, lm):
        """Under a budget the warm-up compiles TWO chunk programs -
        the whole chunk and the padded tail, once, at one count - and
        they are the scheduler's own: prompts of 1 ... 3 budgets of
        every remainder then run with `compiles` unchanged (a tail
        call that missed the warmed jit entry - another dtype, a weak
        type - would count here, and on the chip re-trace inside the
        measured window)."""
        model, params = lm
        B = 8
        with ServingEngine(model, params, num_slots=2, max_queue=16,
                           warmup=True, prefill_chunk_budget=B) as eng:
            assert eng.warmup_info["prefill_sizes"] == [B, "1..7"]
            # reset, two chunk programs, first token, tick
            assert eng.warmup_info["compiles"] == 5
            entries = slot_prefill_chunk._cache_size()
            rs = np.random.RandomState(5)
            prompts = [rs.randint(0, VOCAB, (n,))
                       for n in (1, 5, 7, 8, 9, 13, 16, 23, 24)]
            hs = [eng.submit(p, 4) for p in prompts]
            for p, h in zip(prompts, hs):
                got = h.result(timeout=300).full_sequence
                if len(p) in (5, 24):   # (`generate` compiles a length)
                    np.testing.assert_array_equal(got, np.asarray(
                        generate(model, params, jnp.asarray(p)[None],
                                 4))[0])
            snap = eng.metrics_snapshot()
        assert snap["compiles"] == 0, snap["compiles"]
        assert slot_prefill_chunk._cache_size() == entries
        assert snap["prefill_tokens"] == sum(map(len, prompts))
        assert snap["prefill_tail_chunks"] == 6     # all but 8, 16, 24
        assert snap["prefill_pad_tokens"] == 7 + 3 + 1 + 7 + 3 + 1
        assert snap["prefill_chunks"] == 3 * 1 + 1 + 3 * 2 + 2 * 3

    def test_prefill_budget_env_default(self, lm, monkeypatch):
        """HVD_PREFILL_CHUNK_BUDGET reaches the engine through the
        runtime config when no kwarg is passed."""
        from horovod_tpu.runtime.config import config
        monkeypatch.setenv("HVD_PREFILL_CHUNK_BUDGET", "3")
        config.refresh()
        try:
            model, params = lm
            eng = ServingEngine(model, params, num_slots=1)
            assert eng.prefill_chunk_budget == 3
            assert eng.scheduler.prefill_chunk_budget == 3
            # pow2 floor of the budget caps chunk sizes
            assert eng.scheduler._max_chunk == 3
            assert eng.scheduler._chunk_width == 2
            eng.shutdown()
        finally:
            monkeypatch.delenv("HVD_PREFILL_CHUNK_BUDGET")
            config.refresh()


class TestPlumbing:
    def test_prefill_chunks_binary_decomposition(self, hvd):
        assert prefill_chunks(13) == [8, 4, 1]
        assert prefill_chunks(1) == [1]
        assert prefill_chunks(32) == [32]
        for n in range(1, 70):
            cs = prefill_chunks(n)
            assert sum(cs) == n
            assert cs == sorted(cs, reverse=True)
        with pytest.raises(ValueError):
            prefill_chunks(0)

    def test_prefill_chunks_budget_cap(self, hvd):
        """Under a budget the schedule is whole chunks of the
        budget's power-of-two floor and at most ONE tail (run padded
        to that width, its count traced): it sums to the prompt
        length in ceil(n / width) chunks - two programs, whatever the
        length. ``pad_tail=False`` (the paged pool) keeps the
        remainder's binary decomposition."""
        assert prefill_chunks(200, 64) == [64, 64, 64, 8]
        assert prefill_chunks(13, 4) == [4, 4, 4, 1]
        assert prefill_chunks(13, 5) == [4, 4, 4, 1]   # pow2 floor
        assert prefill_chunks(3, 8) == [3]
        assert prefill_chunks(7, 8) == [7]
        assert prefill_chunks(15, 8) == [8, 7]
        assert prefill_chunks(8, 1) == [1] * 8
        assert chunk_width(None) is None and chunk_width(0) is None
        assert chunk_width(5) == 4 and chunk_width(128, 48) == 32
        for n in range(1, 70):
            for cap in (1, 2, 3, 8, 64):
                cs = prefill_chunks(n, cap)
                w = chunk_width(cap)
                assert sum(cs) == n and len(cs) == -(-n // w)
                assert all(c == w for c in cs[:-1])
                assert 1 <= cs[-1] <= w
        assert prefill_chunks(200, 64, pad_tail=False) == [64, 64, 64, 8]
        assert prefill_chunks(13, 5, pad_tail=False) == [4, 4, 4, 1]
        assert prefill_chunks(3, 8, pad_tail=False) == [2, 1]
        assert prefill_chunks(7, 8, pad_tail=False) == [4, 2, 1]
        for n in range(1, 70):
            for cap in (1, 2, 3, 8, 64):
                cs = prefill_chunks(n, cap, pad_tail=False)
                assert sum(cs) == n
                assert all(c & (c - 1) == 0 for c in cs)
                assert max(cs) <= cap

    @pytest.mark.parametrize("kind", TAIL_KINDS)
    def test_padded_tail_leaves_the_cache_the_binary_schedule_leaves(
            self, hvd, kind):
        """A prompt streamed as whole chunks + ONE padded tail (the
        schedule under a budget, `TAIL_B` here) against the same
        prompt streamed as whole chunks + the tail's binary
        decomposition (the schedule before the tail program), for
        every cache kind a pool can hold and every case of
        `TAIL_CASES`: every leaf equal - the pads wrote no row, lapped
        no ring slot, decayed no state, kept no convolution row - the
        indices advanced by exactly P, the same first token, and the
        experts' pair counts equal (a pad is routed nowhere). Exact
        where no tail runs; at float32's rounding where the chunk
        boundary moves a summation."""
        from jax.tree_util import keystr, tree_flatten_with_path
        from horovod_tpu.serving.slots import SlotPool
        model, params = _tail_model(kind)
        new, old = (SlotPool(model, params, 2) for _ in range(2))
        assert new.chunk_width(TAIL_B) == TAIL_B
        for case, P in TAIL_CASES.items():
            prompt = np.random.RandomState(P).randint(1, VOCAB, (P,))
            tail = P % TAIL_B
            assert new.prefill_schedule(P, TAIL_B) == (
                [TAIL_B] * (P // TAIL_B) + [tail] * bool(tail)), case
            t_new = new.prefill(1, prompt, 0.0, None, 0,
                                max_chunk=TAIL_B)
            old.begin_prefill(1)
            off = 0
            for c in prefill_chunks(P, TAIL_B, pad_tail=False):
                logits = old.prefill_chunk(1, prompt[off:off + c])
                off += c
            assert t_new == old.finish_prefill(1, logits, 0.0, None,
                                               0), case
            for (path, a), (_, b) in zip(
                    tree_flatten_with_path(new._cache)[0],
                    tree_flatten_with_path(old._cache)[0]):
                a, b, where = np.asarray(a), np.asarray(b), (
                    case + keystr(path))
                if "index" in where:
                    assert (a == [0, P]).all() and (b == a).all(), where
                elif not tail or a.dtype == np.int8:
                    np.testing.assert_array_equal(a, b, err_msg=where)
                else:
                    np.testing.assert_allclose(a, b, atol=2e-5,
                                               rtol=1e-5, err_msg=where)
            pairs = [sum(np.asarray(x) for x in pool._prefill_pairs)
                     for pool in (new, old)]
            if "experts" in kind:
                assert pairs[0].sum() > 0
                np.testing.assert_array_equal(*pairs, err_msg=case)
            else:
                assert pairs == [0, 0]
            new._prefill_pairs.clear(), old._prefill_pairs.clear()
        # every length was TWO chunk programs (beside the reset and the
        # first token), where the binary schedule took four
        assert new.compiles == 4 and old.compiles == 6

    def test_pad_tokens_claim_no_capacity_of_a_capacity_layer(self, hvd):
        """The capacity-bound expert layer under ``count``: what the
        pad positions hold cannot reach a real position's output - a
        pad's first choice would otherwise claim an expert's slot
        ahead of every real token's second choice."""
        from horovod_tpu.parallel.expert import MoELayer
        layer = MoELayer(num_experts=4, hidden=16, k=2,
                         capacity_factor=0.5)
        # (a draw at which the crowd does take a real token's slot)
        x = jax.random.normal(jax.random.PRNGKey(7), (1, 8, 8))
        params = layer.init(jax.random.PRNGKey(1), x)
        crowd = x.at[0, 3:].set(x[0, 0])    # pads that want row 0's experts
        y, y_crowd, y_all = (
            layer.apply(params, inp, *count)
            for inp, count in ((x, (jnp.int32(3),)),
                               (crowd, (jnp.int32(3),)), (crowd, ())))
        np.testing.assert_array_equal(y[0, :3], y_crowd[0, :3])
        assert not np.array_equal(y_crowd[0, :3], y_all[0, :3])

    def test_metrics_snapshot_shape(self, lm):
        model, params = lm
        with ServingEngine(model, params, num_slots=2) as eng:
            eng.submit(_prompts(1, seed=80)[0], 4).result(timeout=300)
            snap = eng.metrics_snapshot()
        assert snap["completed"] == 1
        assert snap["ttft_ms"]["n"] == 1
        assert snap["ttft_ms"]["p50"] is not None
        assert snap["tpot_ms"]["p95"] is not None
        assert snap["tokens_per_s"] > 0
        assert snap["num_slots"] == 2


@pytest.mark.slow
class TestSoak:
    def test_open_loop_soak(self, lm):
        """Multi-second soak: open-loop Poisson-ish arrivals (slots
        genuinely idle between them — the staggered regime); every
        request completes TOKEN-EXACT vs sequential generate, queue
        returns to empty, occupancy returns to 0."""
        model, params = lm
        rs = np.random.RandomState(0)
        n, steps = 24, 8
        prompts = [_prompts(1, seed=100 + i)[0] for i in range(n)]
        with ServingEngine(model, params, num_slots=4,
                           max_queue=n) as eng:
            handles = []
            for p in prompts:
                handles.append(eng.submit(p, steps))
                time.sleep(float(rs.exponential(0.02)))
            results = [h.result(timeout=600) for h in handles]
        snap = eng.metrics_snapshot()
        assert snap["completed"] == n
        assert snap["queue_depth"] == 0 and snap["slots_busy"] == 0
        assert snap["tokens_out"] == sum(len(r.tokens)
                                         for r in results)
        assert snap["ttft_ms"]["p95"] is not None
        for p, r in zip(prompts, results):
            ref = np.asarray(generate(model, params,
                                      jnp.asarray(p)[None], steps))[0]
            np.testing.assert_array_equal(r.full_sequence, ref)
