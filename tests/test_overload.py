"""Overload control plane tests (docs/serving.md "Overload control").

The contract under test: when the pool cannot admit a higher-priority
request, the scheduler MAKES ROOM by preempting lower-priority decode
streams — and a preempted stream, whether it resumes by swap
(re-grafted KV blocks) or recompute (forced-prefix re-prefill), is
BITWISE the uninterrupted stream, across {fixed, paged} x {fp32, int8}
x {greedy, seeded} and across preemption points. Around that core:
the WFQ/priority admission queue (weighted shares, anti-starvation
aging, per-tenant shed caps), the per-tenant SLO monitors feeding the
brownout ladder (hedge off -> spec-k capped -> tenant preempted,
never a fleet-wide 503), and the block pool's invariants under
preempt/resume/evict churn.
"""

import threading
import time
from concurrent.futures import CancelledError, Future

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import TransformerLM, generate
from horovod_tpu.parallel.tensor import unbox
from horovod_tpu.resilience import chaos
from horovod_tpu.serving import (
    QueueFullError, ServingEngine, ServingRouter,
)
from horovod_tpu.serving.admission import (
    AdmissionQueue, Request, SamplingParams,
)
from horovod_tpu.serving.overload import (
    BROWNOUT_MAX_LEVEL, BrownoutController, PreemptionPolicy,
    SwapStore, parse_tenant_weights,
)
from horovod_tpu.serving.paging import BlockPool

VOCAB = 64
MAX_LEN = 32
BS = 4


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


def _prompts(n, seed=0, length=6):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, VOCAB, (length,)) for _ in range(n)]


def _wait(cond, timeout=120.0, dt=0.005):
    t0 = time.time()
    while not cond():
        if time.time() - t0 > timeout:
            raise AssertionError("condition not reached in time")
        time.sleep(dt)


class _StepGate:
    """Parks an engine's dispatch thread between scheduler steps, so
    that what the next step finds queued is the test's doing and not
    the clock's. `park_when(cond)` lets a parked thread go on, stops
    it again after the first step that leaves `cond()` true (asked on
    that thread after every step) and returns once it stands still."""

    def __init__(self, eng):
        self._step = eng.scheduler.step
        self._cond = None
        self._parked = threading.Event()
        self._go = threading.Event()
        eng.scheduler.step = self._gated

    def _gated(self, *args, **kwargs):
        progressed = self._step(*args, **kwargs)
        cond = self._cond
        if cond is not None and cond():
            self._cond = None
            go = self._go = threading.Event()
            self._parked.set()
            go.wait(120.0)
        return progressed

    def park_when(self, cond):
        self._parked.clear()
        self._cond = cond
        self._go.set()
        assert self._parked.wait(120.0), "dispatch thread never parked"

    def release(self):
        self._cond = None
        self._go.set()


def _rq(i, prio=0, tenant="", t=0.0, deadline=None):
    return Request(id=i, prompt=np.zeros(4, np.int64),
                   max_new_tokens=4, sampling=SamplingParams(),
                   deadline=deadline, future=Future(),
                   priority=prio, tenant=tenant, t_submit=t)


# ---------------------------------------------------------------------------
# Admission queue: priority bands, WFQ, aging, shed caps
# ---------------------------------------------------------------------------


class TestAdmissionWFQ:
    def test_single_lane_degenerates_to_fifo(self):
        q = AdmissionQueue(8)
        reqs = [_rq(i) for i in range(5)]
        for r in reqs:
            q.offer(r)
        got = [q.pop_ready(0.0).id for _ in range(5)]
        assert got == [0, 1, 2, 3, 4]
        assert q.pop_ready(0.0) is None

    def test_priority_bands_served_first(self):
        q = AdmissionQueue(8, aging_s=None)
        for i in range(3):
            q.offer(_rq(i, prio=0))
        for i in range(3, 5):
            q.offer(_rq(i, prio=5))
        got = [q.pop_ready(0.0).id for _ in range(5)]
        assert got == [3, 4, 0, 1, 2]

    def test_wfq_weighted_share(self):
        """weights paid=3 free=1: over any run of pops the paid lane
        gets ~3x the service (exactly 12/4 over the first 16 with the
        virtual-time schedule)."""
        q = AdmissionQueue(64, tenant_weights={"paid": 3.0, "free": 1.0},
                           aging_s=None)
        for i in range(16):   # within both tenants' shed caps
            q.offer(_rq(2 * i, tenant="paid"))
            q.offer(_rq(2 * i + 1, tenant="free"))
        popped = [q.pop_ready(0.0).tenant for _ in range(16)]
        assert popped.count("paid") == 12
        assert popped.count("free") == 4

    def test_aging_prevents_starvation(self):
        """A low-priority head older than aging_s is served before a
        younger high-priority flood — oldest aged head wins globally."""
        q = AdmissionQueue(32, aging_s=1.0)
        old = _rq(0, prio=0, t=0.0)
        q.offer(old)
        for i in range(1, 6):
            q.offer(_rq(i, prio=9, t=10.0))
        # At now=10 the low-priority request is 10s old (aged); the
        # high-priority ones are 0s old.
        assert q.pop_ready(10.0).id == 0
        assert q.pop_ready(10.0).priority == 9

    def test_tenant_shed_cap(self):
        """A configured tenant's queue share is capped at its weight
        fraction of max_depth; unconfigured tenants see only the
        global bound."""
        q = AdmissionQueue(8, tenant_weights={"a": 1.0, "b": 1.0})
        for i in range(4):      # cap = ceil(8 * 1/2) = 4
            q.offer(_rq(i, tenant="a"))
        with pytest.raises(QueueFullError):
            q.offer(_rq(99, tenant="a"))
        # Tenant b and the unconfigured tenant still get in.
        q.offer(_rq(100, tenant="b"))
        q.offer(_rq(101, tenant="c"))

    def test_cancel_releases_queue_slot_immediately(self):
        q = AdmissionQueue(2)
        a, b = _rq(0), _rq(1)
        q.offer(a)
        q.offer(b)
        with pytest.raises(QueueFullError):
            q.offer(_rq(2))
        a.cancel()
        with pytest.raises(CancelledError):
            a.future.result(timeout=5)
        q.offer(_rq(3))          # slot came back without a sweep
        assert len(q) == 2

    def test_parse_tenant_weights(self):
        assert parse_tenant_weights("paid=4, free=1") == {
            "paid": 4.0, "free": 1.0}
        assert parse_tenant_weights("") == {}
        assert parse_tenant_weights(None) == {}
        for bad in ("paid", "=3", "paid=x", "paid=0", "paid=-1"):
            with pytest.raises(ValueError):
                parse_tenant_weights(bad)


# ---------------------------------------------------------------------------
# SwapStore + PreemptionPolicy units
# ---------------------------------------------------------------------------


class _FakeTransfer:
    def __init__(self, nbytes):
        self.nbytes = nbytes


class TestSwapStore:
    def test_put_pop_budget(self):
        s = SwapStore(max_bytes=100)
        assert s.put(1, _FakeTransfer(60))
        assert not s.put(2, _FakeTransfer(60))   # over budget -> False
        assert s.put(2, _FakeTransfer(40))
        assert s.bytes_used == 100 and len(s) == 2
        assert s.pop(1).nbytes == 60
        assert s.bytes_used == 40
        assert s.pop(1) is None
        assert s.discard(2) and not s.discard(2)
        assert s.bytes_used == 0

    def test_put_replaces_same_key(self):
        s = SwapStore(max_bytes=100)
        assert s.put(1, _FakeTransfer(80))
        assert s.put(1, _FakeTransfer(90))   # replace, not 80+90
        assert s.bytes_used == 90 and len(s) == 1


class _FakeBlocks:
    def __init__(self, held):
        self._held = held

    def blocks_of(self, slot):
        return [0] * self._held.get(slot, 0)


class _FakePool:
    def __init__(self, held):
        self.blocks = _FakeBlocks(held)


class TestPreemptionPolicy:
    def test_victim_order(self):
        """Lowest priority first, then most blocks held, then fewest
        tokens; lanes at/above the head's priority are ineligible."""
        active = {0: _rq(0, prio=0), 1: _rq(1, prio=0),
                  2: _rq(2, prio=1), 3: _rq(3, prio=5)}
        active[0].tokens = [1, 2, 3]
        active[1].tokens = [1]
        pool = _FakePool({0: 2, 1: 2, 2: 9, 3: 1})
        head = _rq(9, prio=5)
        order = PreemptionPolicy().order_victims(head, active, pool)
        assert [s for s, _ in order] == [1, 0, 2]   # prio 0 band: slot
        # 1 holds as much as 0 but generated fewer tokens (cheaper).
        # head=None (stranded/brownout): everyone is eligible.
        order = PreemptionPolicy().order_victims(None, active, pool)
        assert [s for s, _ in order] == [1, 0, 2, 3]


# ---------------------------------------------------------------------------
# Brownout ladder (controller unit)
# ---------------------------------------------------------------------------


class _FakeSLO:
    def __init__(self):
        self.burn = {}

    def tenant_breaching(self, now=None):
        return self.burn


class TestBrownoutController:
    def test_storm_escalates_and_cooldown_recovers(self):
        bc = BrownoutController(slo=None, hold_s=1.0, cooldown_s=5.0,
                                interval_s=0.0)
        bc.touch("t")
        with chaos.armed("serving.overload_storm:3"):
            assert bc.step(now=100.0) == [("t", 0, 1)]
            assert bc.step(now=100.1) == [("t", 1, 2)]
            assert bc.step(now=100.2) == [("t", 2, 3)]
        assert bc.level("t") == BROWNOUT_MAX_LEVEL
        assert bc.step(now=101.0) == []          # cooldown not met
        assert bc.step(now=105.3) == [("t", 3, 2)]
        assert bc.step(now=110.4) == [("t", 2, 1)]
        assert bc.step(now=115.5) == [("t", 1, 0)]
        assert bc.level("t") == 0

    def test_slo_burn_escalates_with_hold(self):
        slo = _FakeSLO()
        bc = BrownoutController(slo=slo, hold_s=1.0, cooldown_s=5.0,
                                interval_s=0.0)
        slo.burn = {"x": ["ttft"]}
        assert bc.step(now=10.0) == [("x", 0, 1)]
        assert bc.step(now=10.5) == []           # hold_s gates rung 2
        assert bc.step(now=11.1) == [("x", 1, 2)]
        slo.burn = {}
        assert bc.step(now=16.2) == [("x", 2, 1)]

    def test_on_level_callback_and_max_level(self):
        seen = []
        bc = BrownoutController(
            slo=None, interval_s=0.0,
            on_level=lambda t, o, n: seen.append((t, o, n)))
        bc.touch("a")
        with chaos.armed("serving.overload_storm:2"):
            bc.step(now=1.0)
            bc.step(now=2.0)
        assert seen == [("a", 0, 1), ("a", 1, 2)]
        assert bc.max_level() == 2
        assert bc.summary()["levels"] == {"a": 2}


# ---------------------------------------------------------------------------
# Per-tenant SLO isolation
# ---------------------------------------------------------------------------


class TestPerTenantSLO:
    def test_tenant_burn_isolated_from_parent(self):
        from horovod_tpu.obs.slo import Objective, SLOMonitor
        mon = SLOMonitor(
            [Objective("ttft", "latency", threshold_s=0.05,
                       budget=0.1)],
            fast_window_s=30, slow_window_s=600, fast_burn=2.0)
        now = time.time()
        for _ in range(10):                       # free: 100% bad
            mon.record("ttft", 1.0, now=now, tenant="free")
        for _ in range(200):                      # paid: all good
            mon.record("ttft", 0.001, now=now, tenant="paid")
        tb = mon.tenant_breaching(now=now + 1)
        assert tb.get("free") == ["ttft"]
        assert "paid" not in tb
        # The fleet-wide monitor sees 10/210 bad (~4.8% against a 10%
        # budget) — the bad tenant did NOT trip the fleet: /healthz
        # stays green while the brownout ladder handles "free".
        mon.evaluate(now=now + 1)
        assert mon.breaching() == []


# ---------------------------------------------------------------------------
# Block pool: watermark admission + 400-op churn fuzz
# ---------------------------------------------------------------------------


class TestPoolChurn:
    def test_watermark_admission_and_extend(self):
        pool = BlockPool(12, BS)
        pool.watermark = BS
        prompt = np.arange(8)
        adm = pool.admit(1, prompt, 16)
        assert adm is not None
        # Watermark reservation: prompt blocks + ~1 decode block, not
        # the worst-case ceil((8+16)/4).
        assert len(pool.blocks_of(1)) <= 4
        assert pool.extend(1, 16)                # grow on demand
        assert len(pool.blocks_of(1)) == 4
        pool.check_invariants()
        pool.free_seq(1)
        pool.check_invariants()

    def test_fuzz_400_ops_invariants_hold(self):
        """400 random admit/extend/publish/free (preempt = free then
        re-admit the same stream) ops against a small watermarked pool:
        `check_invariants` after every op."""
        rs = np.random.RandomState(1234)
        pool = BlockPool(24, BS)
        pool.watermark = BS
        live = {}                                # key -> np tokens
        fills = {}                               # key -> covered tokens
        next_key = [0]

        def _admit(toks):
            key = next_key[0]
            next_key[0] += 1
            adm = pool.admit(key, toks, int(rs.randint(1, 9)))
            if adm is None:
                return
            live[key] = toks
            fills[key] = len(toks)

        for _ in range(400):
            op = rs.randint(0, 5)
            if op == 0 or not live:
                _admit(rs.randint(0, VOCAB, (int(rs.randint(1, 13)),)))
            elif op == 1:                        # decode growth
                key = list(live)[rs.randint(len(live))]
                want = fills[key] + int(rs.randint(1, 4))
                if pool.extend(key, want):
                    grown = rs.randint(0, VOCAB, (want - fills[key],))
                    live[key] = np.concatenate([live[key], grown])
                    fills[key] = want
                else:                            # stranded -> preempt
                    pool.free_seq(key)
                    del live[key], fills[key]
            elif op == 2:                        # prefill done
                key = list(live)[rs.randint(len(live))]
                pool.publish(key, live[key])
            elif op == 3:                        # retire
                key = list(live)[rs.randint(len(live))]
                pool.free_seq(key)
                del live[key], fills[key]
            else:                                # preempt + resume
                key = list(live)[rs.randint(len(live))]
                toks = live[key]
                pool.publish(key, toks)
                pool.free_seq(key)
                del live[key], fills[key]
                _admit(toks)                     # prefix-cache resume
            pool.check_invariants()
        for key in list(live):
            pool.free_seq(key)
        pool.check_invariants()
        assert pool.used_blocks == 0


# ---------------------------------------------------------------------------
# The tentpole: token-exact preemption across the engine matrix
# ---------------------------------------------------------------------------


_MODES = [
    pytest.param(
        dict(paged=True, kv_block_size=BS, kv_blocks=9,
             swap_bytes=64 << 20), "swap", id="paged-swap"),
    pytest.param(
        dict(paged=True, kv_block_size=BS, kv_blocks=9,
             swap_bytes=0), "recompute", id="paged-recompute"),
    pytest.param(dict(paged=False), "recompute", id="fixed"),
]

_FLAVORS = [
    pytest.param(None, 0.0, id="fp32-greedy"),
    pytest.param(None, 0.8, id="fp32-seeded"),
    pytest.param("int8", 0.0, id="int8-greedy"),
    pytest.param("int8", 0.8, id="int8-seeded"),
]


class TestPreemptResumeBitwise:
    @pytest.mark.parametrize("pool_kw,expect", _MODES)
    @pytest.mark.parametrize("quant,temp", _FLAVORS)
    def test_preempt_resume_bitwise(self, lm, pool_kw, expect, quant,
                                    temp):
        """Two low-priority decodes fill the pool; a priority-5 submit
        forces a preemption at a swept point; every stream (victims
        after resume AND the preemptor) is bitwise the uninterrupted
        run — for swap-resume and recompute-resume alike."""
        model, params = lm
        prompts = _prompts(3, seed=31)
        steps = [12, 12, 8]
        seeds = [11, 12, 13]
        kw = {k: v for k, v in pool_kw.items() if k != "swap_bytes"}
        kw.update(num_slots=2, max_queue=8, weight_quant=quant)
        # Oracle: the same engine flavor, roomy pool, no pressure.
        okw = dict(kw)
        if okw.get("paged"):
            okw["kv_blocks"] = 64
        refs = []
        with ServingEngine(model, params, **okw) as eng:
            for p, st, sd in zip(prompts, steps, seeds):
                refs.append(list(
                    eng.submit(p, st, temperature=temp, seed=sd)
                    .result(timeout=300).tokens))
        for point in (1, 5):
            ekw = dict(kw, preempt=True)
            if "swap_bytes" in pool_kw:
                ekw["swap_bytes"] = pool_kw["swap_bytes"]
            with ServingEngine(model, params, **ekw) as eng:
                # The victims are queued, and later the preemptor,
                # while the dispatch thread stands between two steps:
                # both victims are admitted by the steps that follow,
                # and the step after the one that gave the slower of
                # them its `point`-th token (of 12) finds the
                # preemptor queued and both slots held.
                gate = _StepGate(eng)
                try:
                    gate.park_when(lambda: True)
                    va = eng.submit(prompts[0], steps[0],
                                    temperature=temp, seed=seeds[0],
                                    tenant="free")
                    vb = eng.submit(prompts[1], steps[1],
                                    temperature=temp, seed=seeds[1],
                                    tenant="free")
                    gate.park_when(
                        lambda: min(len(va.tokens_so_far()),
                                    len(vb.tokens_so_far())) >= point)
                    hi = eng.submit(prompts[2], steps[2],
                                    temperature=temp, seed=seeds[2],
                                    priority=5, tenant="paid")
                finally:
                    gate.release()
                got = [list(h.result(timeout=300).tokens)
                       for h in (va, vb, hi)]
                snap = eng.metrics_snapshot()
            assert got == refs, (point,)
            total = (snap["preemptions_swap"]
                     + snap["preemptions_recompute"])
            assert total >= 1, (point, snap)
            if expect == "swap":
                assert snap["preemptions_swap"] >= 1, (point, snap)
                assert snap["preempt_swap_bytes"] > 0
            else:
                assert snap["preemptions_swap"] == 0, (point, snap)
                assert snap["preempt_tokens_recomputed"] > 0

    def test_paged_invariants_after_preempt_churn(self, lm):
        """The engine-level cousin of the pool fuzz: after a run with
        preemptions the block pool's invariants still hold and
        everything was freed."""
        model, params = lm
        prompts = _prompts(5, seed=77)
        with ServingEngine(model, params, num_slots=2, max_queue=16,
                           paged=True, kv_block_size=BS, kv_blocks=9,
                           preempt=True) as eng:
            hs = [eng.submit(p, 10, priority=i % 2, tenant="t")
                  for i, p in enumerate(prompts)]
            for h in hs:
                h.result(timeout=300)
            eng.pool.blocks.check_invariants()
            assert eng.pool.blocks.used_blocks == 0
            snap = eng.metrics_snapshot()
        assert snap["completed"] == 5


# ---------------------------------------------------------------------------
# Satellites: cancel-mid-prefill block release, remaining_new reservation
# ---------------------------------------------------------------------------


class TestSatellites:
    def test_cancel_mid_prefill_releases_blocks(self, lm):
        """A cancelled request whose prefill is still chunking must
        release its reserved-but-unfilled blocks (regression: they
        used to sit reserved until the lane's would-be retirement)."""
        model, params = lm
        rs = np.random.RandomState(5)
        prompt = rs.randint(0, VOCAB, (24,))
        with ServingEngine(model, params, num_slots=1, paged=True,
                           kv_block_size=BS, kv_blocks=16,
                           prefill_chunk_budget=4) as eng:
            h = eng.submit(prompt, 4)
            _wait(lambda: eng.pool.blocks.used_blocks > 0)
            h.cancel()
            with pytest.raises(CancelledError):
                h.result(timeout=60)
            _wait(lambda: eng.pool.blocks.used_blocks == 0)
            eng.pool.blocks.check_invariants()
            # And the pool is immediately usable again.
            r = eng.submit(prompt[:6], 4).result(timeout=300)
            assert len(r.tokens) == 4

    def test_forced_prefix_reserves_remaining_not_max(self, lm):
        """submit(forced_prefix=...) must reserve blocks for
        remaining_new (= max_new - len(forced)), not the full
        max_new: a pool sized for the remaining-based need (but NOT
        the worst case) admits and completes bitwise."""
        model, params = lm
        rs = np.random.RandomState(9)
        prompt = rs.randint(0, VOCAB, (8,))
        steps = 16
        with ServingEngine(model, params, num_slots=2, paged=True,
                           kv_block_size=BS, kv_blocks=64) as eng:
            ref = list(eng.submit(prompt, steps)
                       .result(timeout=300).tokens)
        # full_prompt = 8 + 12 = 20 tokens, remaining_new = 4:
        # remaining-based need is 6 blocks; a max_new-based
        # reservation would want 9+ and shed/deadlock on this pool.
        with ServingEngine(model, params, num_slots=1, paged=True,
                           kv_block_size=BS, kv_blocks=9) as eng:
            r = eng.submit(prompt, steps,
                           forced_prefix=ref[:12]).result(timeout=300)
        assert list(r.tokens) == ref


# ---------------------------------------------------------------------------
# Brownout through the engine
# ---------------------------------------------------------------------------


class TestBrownoutEngine:
    def test_storm_ladder_hedge_gate_and_bitwise(self, lm):
        """The storm chaos site walks the noisy tenant up the ladder
        on the live dispatch thread: hedging locks out at rung 1+,
        and the streams still complete token-exactly (degradation is
        graceful, not corrupting)."""
        model, params = lm
        pa, pb = _prompts(2, seed=51, length=4)
        with chaos.armed("serving.overload_storm:-1"):
            with ServingEngine(model, params, num_slots=2,
                               max_queue=8, preempt=True,
                               brownout=True) as eng:
                a = eng.submit(pa, 24, tenant="noisy")
                b = eng.submit(pb, 24, tenant="noisy", priority=1)
                _wait(lambda: eng.brownout.level("noisy")
                      >= BROWNOUT_MAX_LEVEL)
                assert not eng.hedge_allowed("noisy")
                ra = a.result(timeout=300)
                rb = b.result(timeout=300)
                snap = eng.metrics_snapshot()
        for p, r in ((pa, ra), (pb, rb)):
            ref = np.asarray(generate(
                model, params, jnp.asarray(p)[None], 24))[0]
            np.testing.assert_array_equal(r.full_sequence, ref)
        assert snap["brownout_transitions"] >= BROWNOUT_MAX_LEVEL
        assert snap["brownout"]["levels"].get("noisy") \
            == BROWNOUT_MAX_LEVEL
        # Off-storm, a fresh tenant is at rung 0 and may hedge.
        assert snap["brownout"]["levels"].get("quiet") is None

    def test_rung3_preempts_tenant_lane(self, lm):
        """Rung 3's teeth, driven deterministically: the brownout
        callback queues the tenant in the scheduler's preemption
        mailbox, and the next step preempts its lowest-priority lane
        (leaving at least one) — both streams still bitwise."""
        model, params = lm
        pa, pb = _prompts(2, seed=52, length=4)
        with ServingEngine(model, params, num_slots=2, max_queue=8,
                           paged=True, kv_block_size=BS, kv_blocks=32,
                           preempt=True, brownout=True) as eng:
            a = eng.submit(pa, 26, tenant="noisy")
            b = eng.submit(pb, 26, tenant="noisy", priority=1)
            _wait(lambda: min(len(a.tokens_so_far()),
                              len(b.tokens_so_far())) >= 2)
            eng._apply_brownout("noisy", 2, 3)
            _wait(lambda: (eng.metrics_snapshot()["preemptions_swap"]
                           + eng.metrics_snapshot()
                           ["preemptions_recompute"]) >= 1)
            ra = a.result(timeout=300)
            rb = b.result(timeout=300)
        for p, r in ((pa, ra), (pb, rb)):
            ref = np.asarray(generate(
                model, params, jnp.asarray(p)[None], 26))[0]
            np.testing.assert_array_equal(r.full_sequence, ref)


# ---------------------------------------------------------------------------
# Composed: preemption x disagg handoff x replica-death migration
# ---------------------------------------------------------------------------


class TestComposedOverload:
    def test_preempt_disagg_kill_still_bitwise(self, lm):
        """The full gauntlet: tight preempt-enabled decode pools
        behind a disagg router, a low-priority flood plus a
        high-priority submit (forcing preemptions), then a decode
        replica killed mid-stream (forcing token-exact migration).
        Every stream is still bitwise the unpressured run."""
        model, params = lm
        prompts = _prompts(5, seed=61, length=10)
        steps = 14
        seeds = [1, 2, 3, 4, 5]

        def factory():
            return ServingEngine(model, params, num_slots=2,
                                 max_queue=16, paged=True,
                                 kv_block_size=BS, kv_blocks=10,
                                 preempt=True)

        refs = []
        with ServingEngine(model, params, num_slots=2, max_queue=16,
                           paged=True, kv_block_size=BS,
                           kv_blocks=64) as eng:
            for p, sd in zip(prompts, seeds):
                refs.append(list(
                    eng.submit(p, steps, temperature=0.8, seed=sd)
                    .result(timeout=300).tokens))
        router = ServingRouter(factory,
                               disagg={"prefill": 1, "decode": 2},
                               health_poll_s=0.01)
        try:
            hs = [router.submit(p, steps, temperature=0.8, seed=sd,
                                tenant="free")
                  for p, sd in zip(prompts[:4], seeds[:4])]
            _wait(lambda: any(len(h.tokens_so_far()) >= 2
                              for h in hs))
            hs.append(router.submit(prompts[4], steps,
                                    temperature=0.8, seed=seeds[4],
                                    priority=5, tenant="paid"))
            def _total_preempts():
                tot = 0
                for rid in router.replicas():
                    try:
                        s = (router.engine_of(rid)
                             .metrics_snapshot())
                    except (KeyError, RuntimeError):
                        continue   # replica died/replaced mid-scan
                    tot += (s["preemptions_swap"]
                            + s["preemptions_recompute"])
                return tot

            # Tight pools + the priority-5 submit force at least one
            # preemption BEFORE the kill, so the kill migrates a
            # fleet that has already preempted and resumed.
            _wait(lambda: _total_preempts() >= 1)
            preempts = _total_preempts()
            victim = max(
                router.replicas(),
                key=lambda rid:
                router.engine_of(rid).pool.busy_slots)
            router.kill_replica(victim)
            got = [list(h.result(timeout=300).tokens) for h in hs]
            snap = router.metrics_snapshot()
        finally:
            router.shutdown()
        assert got == refs
        assert snap["completed"] == 5
        assert snap["replica_deaths"] == 1
        assert preempts >= 1
