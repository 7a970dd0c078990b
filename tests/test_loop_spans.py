"""Loop spans (`obs.spans.loop_span`): the ring, the mirror into the
JAX profiler, and the tick record the scheduler stores on
`sched.tick_dispatch`.

The serving loop and the train step are seen from inside through one
recorder: each span goes to a bounded ring of its own and, while a
profiler session runs, into the trace's host plane under the same
name, so program spans and device ops share a time axis.
"""

import glob
import os
import statistics
import time
from concurrent.futures import Future

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.obs import spans

# The ring stamps time.time_ns() just outside the annotation; the
# profiler stamps inside it. A pair of stamps disagrees by the cost of
# entering an annotation (a few us) unless the thread is descheduled
# between the two: the skew a reader may rely on.
SKEW_NS = 500_000


def _since(seq):
    return [r for r in spans.loop_tail() if r["seq"] > seq]


def _last_seq():
    # a parent closes after its child: the newest record is not the
    # highest seq
    return max((r["seq"] for r in spans.loop_tail()), default=0)


class TestRing:
    def test_record_parent_and_attrs(self):
        mark = _last_seq()
        with spans.loop_span("sched.step", tick=7) as outer:
            with spans.loop_span("sched.tick_sync",
                                 overlapped=True) as inner:
                time.sleep(0.001)
                inner.set(tokens=3, retired=1)
        recs = _since(mark)
        # appended on close: the child first
        assert [r["name"] for r in recs] == ["sched.tick_sync",
                                             "sched.step"]
        child, parent = recs
        assert parent["parent"] == 0 and parent["attrs"] == {"tick": 7}
        assert child["parent"] == parent["seq"] == outer.seq
        assert child["attrs"] == {"overlapped": True, "tokens": 3,
                                  "retired": 1}
        assert (parent["t0_ns"] <= child["t0_ns"] < child["t1_ns"]
                <= parent["t1_ns"])
        assert child["t1_ns"] - child["t0_ns"] >= 1_000_000
        assert abs(parent["t0_ns"] - time.time_ns()) < 5e9  # wall clock

    def test_step_num_is_a_step_annotation_and_an_attr(self):
        mark = _last_seq()
        with spans.loop_span("train.step", step_num=41):
            pass
        (rec,) = _since(mark)
        assert rec["attrs"] == {"step": 41}

    def test_an_exception_still_closes_the_span(self):
        mark = _last_seq()
        with pytest.raises(KeyError):
            with spans.loop_span("sched.step", tick=1):
                with spans.loop_span("sched.housekeeping"):
                    raise KeyError("x")
        assert [r["name"] for r in _since(mark)] == [
            "sched.housekeeping", "sched.step"]
        with spans.loop_span("sched.step", tick=2):
            pass
        assert _since(mark)[-1]["parent"] == 0   # the stack unwound

    def test_ring_is_bounded_and_its_own(self):
        rec = spans.configure()         # a fresh request ring
        try:
            for i in range(spans.LOOP_RING + 50):
                with spans.loop_span("sched.housekeeping"):
                    pass
            tail = spans.loop_tail()
            assert len(tail) == spans.LOOP_RING
            assert [r["seq"] for r in tail] == sorted(
                r["seq"] for r in tail)
            # loop spans never enter (or evict) the request ring
            assert len(rec) == 0
            assert spans.loop_tail(0) == []
            assert len(spans.loop_tail(5)) == 5
            assert all(r["name"] == "sched.housekeeping"
                       for r in spans.loop_tail(
                           name="sched.housekeeping"))
        finally:
            spans.install(None)

    def test_no_session_costs_one_append_and_writes_nothing(
            self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        appended = []

        class Ring:
            def append(self, rec):
                appended.append(rec)

        monkeypatch.setattr(spans, "_LOOP", Ring())
        with spans.loop_span("engine.bookkeeping"):
            pass
        assert len(appended) == 1
        assert appended[0][1] == "engine.bookkeeping"
        assert os.listdir(tmp_path) == []

    def test_threads_keep_their_own_parents(self):
        import threading
        mark = _last_seq()
        seen = {}

        def other():
            with spans.loop_span("engine.idle_wait") as sp:
                seen["parent"] = sp.parent

        with spans.loop_span("sched.step", tick=0):
            t = threading.Thread(target=other)
            t.start()
            t.join()
        assert seen["parent"] == 0      # not the other thread's span
        assert len(_since(mark)) == 2

    def test_every_loop_name_is_in_the_catalog(self):
        for name in ("sched.step", "sched.housekeeping", "sched.admit",
                     "sched.prefill_chunk", "sched.first_token",
                     "sched.tick_dispatch", "sched.tick_sync",
                     "sched.spec_round", "engine.bookkeeping",
                     "engine.idle_wait", "train.step",
                     "train.shard_batch"):
            assert name in spans.SPAN_CATALOG
            assert spans.SPAN_CATALOG[name].startswith("Loop span:")


# One reading of a clock is good to its grain, and the ring stamps the
# wall clock outside the CPU clock: cpu_ns can pass the wall time by
# the two clocks' grain, no more.
CLOCK_GRAIN_NS = 50_000


class TestCpuTime:
    def test_every_record_carries_cpu_ns_within_its_wall_time(self):
        mark = _last_seq()
        with spans.loop_span("sched.step", tick=3):
            with spans.loop_span("sched.housekeeping"):
                sum(range(20_000))
            with spans.loop_span("sched.tick_sync", overlapped=False):
                time.sleep(0.002)
        with spans.loop_span("engine.bookkeeping"):
            pass
        with spans.loop_span("train.step", step_num=1):
            pass
        recs = _since(mark)
        assert len(recs) == 5
        for r in recs:
            assert isinstance(r["cpu_ns"], int)
            assert 0 <= r["cpu_ns"] <= (r["t1_ns"] - r["t0_ns"]
                                        + CLOCK_GRAIN_NS), r
        by = {r["name"]: r for r in recs}
        # a parent's CPU time holds its children's
        assert by["sched.step"]["cpu_ns"] >= (
            by["sched.housekeeping"]["cpu_ns"]
            + by["sched.tick_sync"]["cpu_ns"] - CLOCK_GRAIN_NS)

    def test_a_sleeping_span_burns_no_cpu_and_a_spinning_one_all(self):
        with spans.loop_span("engine.idle_wait") as slept:
            time.sleep(0.05)
        with spans.loop_span("sched.housekeeping") as spun:
            t0 = time.thread_time_ns()      # 30 ms of this thread's CPU
            while time.thread_time_ns() - t0 < 30_000_000:
                pass
        wall = slept.t1_ns - slept.t0_ns
        assert wall >= 50_000_000
        assert slept.cpu_ns < wall / 20     # far under: a twentieth
        wall = spun.t1_ns - spun.t0_ns
        assert spun.cpu_ns >= 30_000_000
        # near its wall time unless the machine took the thread off the
        # core meanwhile: the CPU clock, not the wall clock, is the one
        # that does not care
        assert spun.cpu_ns <= wall + CLOCK_GRAIN_NS
        rec = spans.loop_tail(1)[0]
        assert rec["name"] == "sched.housekeeping"
        assert rec["cpu_ns"] == spun.cpu_ns

    def test_another_threads_work_is_not_this_spans_cpu(self):
        import threading

        def burn():
            t0 = time.thread_time_ns()
            while time.thread_time_ns() - t0 < 20_000_000:
                pass

        t = threading.Thread(target=burn)
        with spans.loop_span("sched.tick_sync", overlapped=True) as sp:
            t.start()
            t.join(timeout=30)
        assert not t.is_alive()
        assert sp.t1_ns - sp.t0_ns >= 20_000_000
        assert sp.cpu_ns < 10_000_000   # the join waited, it did not work


def _host_events(trace_dir):
    """{line name: [(name, start_ns, dur_ns)]} of the host planes."""
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(path)
    lines = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, int(e.start_ns), int(e.duration_ns))
                for e in line.events)
    return lines


class TestProfilerMirror:
    @pytest.fixture(scope="class")
    def session(self, tmp_path_factory):
        """Twenty scripted steps recorded under a profiler session."""
        trace_dir = str(tmp_path_factory.mktemp("loop_trace"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        mark = _last_seq()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            for i in range(20):
                with spans.loop_span("sched.step", tick=i):
                    with spans.loop_span("sched.tick_dispatch",
                                         lanes_decoding=2):
                        time.sleep(0.0005)
                    with spans.loop_span("sched.tick_sync",
                                         overlapped=True) as sp:
                        time.sleep(0.001)
                        sp.set(tokens=2)
                with spans.loop_span("engine.bookkeeping"):
                    time.sleep(0.0002)
        finally:
            jax.profiler.stop_trace()
        return _host_events(trace_dir), _since(mark)

    def test_spans_are_in_the_host_plane_under_their_names(self,
                                                           session):
        lines, ring = session
        (line,) = [evs for evs in lines.values()
                   if any(n == "sched.step" for n, _, _ in evs)]
        for name in ("sched.step", "sched.tick_dispatch",
                     "sched.tick_sync", "engine.bookkeeping"):
            assert sum(n == name for n, _, _ in line) == 20, name
        assert sum(r["name"] == "sched.step" for r in ring) == 20

    def test_nested_in_the_trace_as_in_the_ring(self, session):
        lines, ring = session
        line = next(evs for evs in lines.values()
                    if any(n == "sched.step" for n, _, _ in evs))
        steps = sorted((s, s + d) for n, s, d in line
                       if n == "sched.step")
        for child in ("sched.tick_dispatch", "sched.tick_sync"):
            kids = sorted((s, s + d) for n, s, d in line if n == child)
            for (ps, pe), (cs, ce) in zip(steps, kids):
                assert ps <= cs and ce <= pe
        # ... and engine.bookkeeping lies outside every step
        for n, s, d in line:
            if n == "engine.bookkeeping":
                assert not any(ps < s + d and s < pe
                               for ps, pe in steps)
        by_seq = {r["seq"]: r for r in ring}
        for r in ring:
            if r["name"] in ("sched.tick_dispatch", "sched.tick_sync"):
                assert by_seq[r["parent"]]["name"] == "sched.step"
            else:
                assert r["parent"] == 0

    def test_ring_clock_is_the_profilers_up_to_a_constant(self, session):
        """An xplane counts from its session's start, so the two
        clocks differ by one constant; with it removed every pair of
        stamps agrees within SKEW_NS, starts and ends alike."""
        lines, ring = session
        line = next(evs for evs in lines.values()
                    if any(n == "sched.step" for n, _, _ in evs))
        for name in ("sched.step", "sched.tick_sync"):
            t = sorted((s, d) for n, s, d in line if n == name)
            r = sorted((x["t0_ns"], x["t1_ns"]) for x in ring
                       if x["name"] == name)
            assert len(t) == len(r) == 20
            offs = [r0 - ts for (r0, _), (ts, _) in zip(r, t)]
            off = statistics.median(offs)
            assert max(abs(o - off) for o in offs) < SKEW_NS
            ends = [r1 - (ts + td) for (_, r1), (ts, td) in zip(r, t)]
            assert max(abs(e - off) for e in ends) < SKEW_NS

    def test_attributes_reach_the_trace(self, session, tmp_path):
        """The tick record travels as the annotation's stats."""
        trace_dir = str(tmp_path)
        jax.profiler.start_trace(trace_dir)
        try:
            with spans.loop_span("sched.tick_dispatch",
                                 lanes_decoding=3, context_sum=17) as sp:
                sp.set(queue_depth=1)
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        data = jax.profiler.ProfileData.from_file(path)
        stats = [dict(e.stats) for p in data.planes for ln in p.lines
                 for e in ln.events if e.name == "sched.tick_dispatch"]
        assert stats and stats[0]["lanes_decoding"] == 3
        assert stats[0]["context_sum"] == 17
        assert stats[0]["queue_depth"] == 1


VOCAB, MAX_LEN = 64, 32


@pytest.fixture(scope="module")
def lm(hvd):
    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.parallel.tensor import unbox
    model = TransformerLM(vocab_size=VOCAB, num_layers=2, num_heads=4,
                          head_dim=8, max_len=MAX_LEN, dtype=jnp.float32)
    params = unbox(model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 16), jnp.int32))["params"])
    return model, params


class TestTickRecord:
    def test_three_request_run(self, lm):
        """A scripted scheduler: three slots, a budget of two prompt
        tokens a step, ticks synced at once (depth 0). A (2 prompt
        tokens, 6 new) and B (6, 2) are queued, C (1, 2) arrives
        before the third step. By hand:

          step  prefill                          tick sees
          1     A admitted, its chunk, first     A decodes; B queued
          2     B admitted, chunk 1 of 3         A decodes, B prefills
          3     B chunk 2 (C arrives, waits)     A decodes, B prefills
          4     B chunk 3, first token           A and B decode; C queued
          5     C admitted, chunk, first token   A and C decode

        B retires at step 4's sync, A and C at step 5's."""
        import horovod_tpu.serving as sv
        from horovod_tpu.serving.admission import Request, SamplingParams
        model, params = lm
        pool = sv.SlotPool(model, params, 3)
        queue = sv.AdmissionQueue(4)
        metrics = sv.EngineMetrics()
        sched = sv.ContinuousBatchingScheduler(
            pool, queue, metrics, prefill_chunk_budget=2,
            pipeline_depth=0)
        now = time.time()

        def req(i, n_prompt, steps, **sampling):
            return Request(id=i, prompt=np.arange(1, n_prompt + 1),
                           max_new_tokens=steps,
                           sampling=SamplingParams(**sampling),
                           deadline=None, future=Future(),
                           t_submit=now)

        # a is greedy, b asks for a nucleus, c samples without one:
        # lengths are budgets, so the schedule does not depend on
        # what they draw
        a = req(0, 2, 6)
        b = req(1, 6, 2, temperature=0.8, top_p=0.9, seed=1)
        c = req(2, 1, 2, temperature=0.7, seed=2)
        mark = _last_seq()
        queue.offer(a)
        queue.offer(b)
        sched.step()
        sched.step()
        queue.offer(c)
        for _ in range(3):
            sched.step()
        assert not sched.has_active()
        assert not sched.step()         # nothing left: no tick
        assert all(r.future.done() for r in (a, b, c))

        recs = _since(mark)
        ticks = [r for r in recs if r["name"] == "sched.tick_dispatch"]
        want = [
            # decoding, prefilling, free, queue, context sum, max,
            # sampling, nucleus
            (1, 0, 2, 1, 3, 3, 0, 0),
            (1, 1, 1, 0, 4, 4, 0, 0),
            (1, 1, 1, 1, 5, 5, 0, 0),
            (2, 0, 1, 1, 13, 7, 1, 1),
            (2, 0, 1, 0, 9, 7, 1, 0),
        ]
        got = [tuple(t["attrs"][k] for k in (
            "lanes_decoding", "lanes_prefilling", "lanes_free",
            "queue_depth", "context_sum", "context_max",
            "lanes_sampling", "lanes_nucleus"))
            for t in ticks]
        assert got == want
        for t in ticks:
            assert (t["attrs"]["lanes_decoding"]
                    + t["attrs"]["lanes_prefilling"]
                    + t["attrs"]["lanes_free"]) == pool.num_slots

        # the same, accumulated, for an operator's scrape
        snap = metrics.snapshot()
        assert snap["ticks"] == 5
        assert snap["lane_ticks_decoding"] == 7
        assert snap["lane_ticks_prefilling"] == 2
        assert snap["lane_ticks_free"] == 6
        assert snap["tick_context_positions"] == 34
        # which of `sample_lanes`' paths each tick took
        assert snap["ticks_greedy"] == 3
        assert snap["ticks_nucleus"] == 1
        assert snap["ticks_sampled"] == 1
        assert (snap["lane_ticks_decoding"] + snap["lane_ticks_prefilling"]
                + snap["lane_ticks_free"]) == 5 * pool.num_slots

    def test_phases_of_a_step_in_the_ring(self, lm):
        """One request through the scheduler leaves every phase span,
        each a child of its step, with the attrs the table in
        docs/observability.md promises."""
        import horovod_tpu.serving as sv
        from horovod_tpu.serving.admission import Request, SamplingParams
        model, params = lm
        pool = sv.SlotPool(model, params, 2)
        queue = sv.AdmissionQueue(4)
        metrics = sv.EngineMetrics()
        sched = sv.ContinuousBatchingScheduler(pool, queue, metrics)
        mark = _last_seq()
        r = Request(id=0, prompt=np.array([3, 5, 7]), max_new_tokens=3,
                    sampling=SamplingParams(), deadline=None,
                    future=Future(), t_submit=time.time())
        queue.offer(r)
        while not r.future.done():
            sched.step()
        recs = _since(mark)
        by_seq = {x["seq"]: x for x in recs}
        steps = [x for x in recs if x["name"] == "sched.step"]
        assert [s["attrs"]["tick"] for s in steps] == sorted(
            s["attrs"]["tick"] for s in steps)
        names = {x["name"] for x in recs}
        assert names >= {"sched.step", "sched.housekeeping",
                         "sched.admit", "sched.prefill_chunk",
                         "sched.first_token", "sched.tick_dispatch",
                         "sched.tick_sync"}
        for x in recs:
            if x["name"] != "sched.step":
                assert by_seq[x["parent"]]["name"] == "sched.step"
        (admit,) = [x for x in recs if x["name"] == "sched.admit"]
        assert admit["attrs"] == {
            "slot": admit["attrs"]["slot"], "prompt_tokens": 3,
            "prefix_cached": 0,
            "queue_wait_ms": admit["attrs"]["queue_wait_ms"]}
        assert 0 <= admit["attrs"]["queue_wait_ms"] < 60_000
        chunks = [x["attrs"]["tokens"] for x in recs
                  if x["name"] == "sched.prefill_chunk"]
        assert sum(chunks) == 3
        (first,) = [x for x in recs if x["name"] == "sched.first_token"]
        assert first["attrs"] == {"slot": admit["attrs"]["slot"],
                                  "prompt_tokens": 3,
                                  "chunks": len(chunks)}
        syncs = [x["attrs"] for x in recs if x["name"] == "sched.tick_sync"]
        assert sum(s["tokens"] for s in syncs) == 2   # 3 less the first
        assert sum(s["retired"] for s in syncs) == 1
        # the one chunk site makes both records: the request's tree too
        tree = spans.trace(r.trace_id) if r.trace_id else None
        assert tree is None or any(
            s["name"] == "serving.prefill_chunk" for s in tree)

    def test_engine_loop_spans(self, lm):
        """Through the engine: bookkeeping after every step, the idle
        wait when nothing is queued, and the four snapshot keys."""
        from horovod_tpu.serving import ServingEngine
        model, params = lm
        mark = _last_seq()
        with ServingEngine(model, params, num_slots=2) as eng:
            out = eng.submit(np.array([3, 5, 7]), 4).result(timeout=300)
            time.sleep(0.05)            # the loop parks on the queue
            snap = eng.metrics_snapshot()
        assert len(out.tokens) == 4
        names = [x["name"] for x in _since(mark)]
        assert "engine.bookkeeping" in names
        assert "engine.idle_wait" in names
        assert names.count("engine.bookkeeping") >= names.count(
            "sched.step") - 1
        assert snap["lane_ticks_decoding"] >= 3
        assert (snap["lane_ticks_decoding"] + snap["lane_ticks_prefilling"]
                + snap["lane_ticks_free"]) == snap["ticks"] * 2
        assert snap["tick_context_positions"] >= 3 * 4


def _three_requests(lm, t_submit=None, clock=None):
    """The script of `test_three_request_run` again: A (2 prompt
    tokens, 6 new) and B (6, 2) queued, C (1, 2) offered before the
    third step; five steps in all. `clock(step)` is called before
    each step (a fake clock moves there). Returns (records of the
    run, metrics, the three requests)."""
    import horovod_tpu.serving as sv
    from horovod_tpu.serving.admission import Request, SamplingParams
    model, params = lm
    pool = sv.SlotPool(model, params, 3)
    queue = sv.AdmissionQueue(4)
    metrics = sv.EngineMetrics()
    sched = sv.ContinuousBatchingScheduler(
        pool, queue, metrics, prefill_chunk_budget=2,
        pipeline_depth=0)
    t_submit = t_submit or [time.time()] * 3

    def req(i, n_prompt, steps):
        return Request(id=i, prompt=np.arange(1, n_prompt + 1),
                       max_new_tokens=steps, sampling=SamplingParams(),
                       deadline=None, future=Future(),
                       t_submit=t_submit[i])

    a, b, c = req(0, 2, 6), req(1, 6, 2), req(2, 1, 2)
    mark = _last_seq()
    queue.offer(a)
    queue.offer(b)
    for step in range(1, 6):
        if step == 3:
            queue.offer(c)
        if clock is not None:
            clock(step)
        sched.step()
    assert not sched.has_active()
    assert all(r.future.done() for r in (a, b, c))
    return _since(mark), metrics, (a, b, c)


class TestPeriod:
    def test_queue_wait_on_the_admit_record_under_a_fake_clock(
            self, lm, monkeypatch):
        """`sched.admit` carries the wait that ended in its step:
        `t_prefill - t_submit`, by hand. A is submitted at 99.5 s and
        B at 99.25; step 1 runs at 100 s and admits A (500 ms), step
        2 at 100.25 admits B (1000 ms: B prefills through steps 2-4);
        C is submitted at 100.5 and admitted by step 5 at 101.125
        (625 ms)."""
        from horovod_tpu.serving import scheduler as sched_mod

        class Clock:            # stands in for the module `time`
            now = 0.0
            sleep = time.sleep

            def time(self):
                return self.now

        clock = Clock()
        monkeypatch.setattr(sched_mod, "time", clock)
        at = {1: 100.0, 2: 100.25, 3: 100.5, 4: 100.75, 5: 101.125}
        recs, metrics, reqs = _three_requests(
            lm, t_submit=[99.5, 99.25, 100.5],
            clock=lambda step: setattr(clock, "now", at[step]))
        admits = [x["attrs"] for x in recs if x["name"] == "sched.admit"]
        assert [a["prompt_tokens"] for a in admits] == [2, 6, 1]
        assert [a["queue_wait_ms"] for a in admits] == [500.0, 1000.0,
                                                        625.0]
        # ... and it is the number the engine's series gets when the
        # request finishes
        assert sorted(metrics.queue_wait_s._buf) == [0.5, 0.625, 1.0]
        firsts = [x["attrs"] for x in recs
                  if x["name"] == "sched.first_token"]
        # chunk programs a prompt took at a budget of 2: 2 -> [2],
        # 6 -> [2, 2, 2], 1 -> [1]
        assert [(f["prompt_tokens"], f["chunks"]) for f in firsts] == [
            (2, 1), (6, 3), (1, 1)]
        chunks = [x["attrs"]["tokens"] for x in recs
                  if x["name"] == "sched.prefill_chunk"]
        assert chunks == [2, 2, 2, 2, 1]

    def test_tokens_out_is_counted_once_a_tick_and_adds_up(self, lm):
        """One `count("tokens_out", n)` a tick gives the counter the
        per-token calls gave: every generated token, the first ones
        (sampled by the prefill) included."""
        recs, metrics, reqs = _three_requests(lm)
        snap = metrics.snapshot()
        assert [len(r.tokens) for r in reqs] == [6, 2, 2]
        assert snap["tokens_out"] == 10
        assert snap["prefill_first_tokens"] == 3
        syncs = [x["attrs"]["tokens"] for x in recs
                 if x["name"] == "sched.tick_sync"]
        # what the ticks appended, by hand: A alone, A, A, A and B,
        # A and C
        assert syncs == [1, 1, 1, 2, 2]
        assert snap["tokens_out"] == sum(syncs) + 3
        calls = []
        count = metrics.count
        metrics.count = lambda name, n=1: (calls.append((name, n)),
                                           count(name, n))
        try:
            import horovod_tpu.serving as sv
            from horovod_tpu.serving.admission import (Request,
                                                       SamplingParams)
            model, params = lm
            sched = sv.ContinuousBatchingScheduler(
                sv.SlotPool(model, params, 2), sv.AdmissionQueue(4),
                metrics, pipeline_depth=0)
            two = [Request(id=i, prompt=np.array([3, 5]),
                           max_new_tokens=3, sampling=SamplingParams(),
                           deadline=None, future=Future(),
                           t_submit=time.time()) for i in (7, 8)]
            for r in two:
                sched.queue.offer(r)
            while not all(r.future.done() for r in two):
                sched.step()
        finally:
            metrics.count = count
        outs = [n for name, n in calls if name == "tokens_out"]
        # two first tokens one at a time, then two lanes a tick
        assert outs == [1, 1, 2, 2]
        assert metrics.snapshot()["tokens_out"] == 10 + 6

    def test_leaf_spans_tile_the_step(self, lm):
        """Every stretch of a scheduler step lies in a leaf span: over
        the scripted run the children's wall time is at least 97 % of
        `sched.step`'s (one more try if the machine took the thread
        off the core inside the few microseconds between two spans)."""
        for attempt in range(3):
            recs, _, _ = _three_requests(lm)
            steps = {x["seq"]: x for x in recs
                     if x["name"] == "sched.step"}
            assert len(steps) == 5
            whole = sum(x["t1_ns"] - x["t0_ns"] for x in steps.values())
            leaves = [x for x in recs if x["parent"] in steps]
            assert {x["name"] for x in leaves} >= {
                "sched.housekeeping", "sched.admit",
                "sched.prefill_chunk", "sched.first_token",
                "sched.tick_dispatch", "sched.tick_sync"}
            # leaves do not overlap: each starts where one before ended
            ordered = sorted(leaves, key=lambda x: x["t0_ns"])
            for before, after in zip(ordered, ordered[1:]):
                assert before["t1_ns"] <= after["t0_ns"]
            covered = sum(x["t1_ns"] - x["t0_ns"] for x in leaves)
            if covered >= 0.97 * whole:
                break
        assert covered >= 0.97 * whole, (covered, whole)
        # the same in CPU time: the thread's own work is in the leaves
        cpu_whole = sum(x["cpu_ns"] for x in steps.values())
        cpu_leaves = sum(x["cpu_ns"] for x in leaves)
        assert cpu_leaves <= cpu_whole + len(leaves) * CLOCK_GRAIN_NS


class TestTrainStepSpans:
    def test_step_bracket_and_shard_batch(self, hvd):
        from horovod_tpu.parallel.mesh import shard_batch
        from horovod_tpu.utils.timeline import step_bracket
        calls = []
        stepped = step_bracket(lambda x: calls.append(x) or x + 1)
        mark = _last_seq()
        assert stepped(1) == 2 and stepped(5) == 6
        n = hvd.size()
        batch = shard_batch(hvd.mesh(), np.zeros((n, 4), np.float32))
        assert batch.shape == (n, 4)
        recs = _since(mark)
        assert [(r["name"], r["attrs"]) for r in recs] == [
            ("train.step", {"step": 0}), ("train.step", {"step": 1}),
            ("train.shard_batch", {})]
