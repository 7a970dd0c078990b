"""Observability-plane tests (horovod_tpu.obs, docs/observability.md).

Three layers of proof:

* **Registry / exporter units** — counter/gauge/histogram semantics,
  fixed-bucket mergeability, and a Prometheus text-format PARSE of the
  `/metrics` output (HELP/TYPE lines, label escaping, the histogram
  invariants: cumulative buckets monotonic, +Inf == `_count`).
* **Cross-subsystem tracing** — one serving request's ``trace_id``
  must appear in the event log, the span recorder's tree, AND the
  shared-registry histogram exemplars; and a watchdog-restart requeue
  must carry the ORIGINAL trace_id through recovery (continuity).
* **Registrants** — the stall monitor, chaos sites, the training step
  bracket and the engine snapshot (scrape_seq/uptime_s) all feed the
  shared plane.
"""

import json
import math
import os
import re
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.obs import catalog, events, spans
from horovod_tpu.obs.exporter import MetricsServer, render_prometheus
from horovod_tpu.obs.registry import (
    DEFAULT_BUCKETS, MetricRegistry, quantile_from_buckets, registry,
)

VOCAB = 64


def _wait(cond, timeout=120.0, dt=0.005):
    t0 = time.time()
    while not cond():
        if time.time() - t0 > timeout:
            raise AssertionError("condition not reached in time")
        time.sleep(dt)


@pytest.fixture(scope="module")
def lm(hvd):
    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.parallel.tensor import unbox
    model = TransformerLM(vocab_size=VOCAB, num_layers=2, num_heads=4,
                          head_dim=8, max_len=32, dtype=jnp.float32)
    params = unbox(model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 16), jnp.int32))["params"])
    return model, params


@pytest.fixture
def event_log(tmp_path):
    """Point the global event log at a temp JSONL for one test;
    restore the previous log after (the scoped-swap pattern: a
    user-configured log must survive)."""
    path = str(tmp_path / "events.jsonl")
    log = events.EventLog(path)
    prev = events.install(log)
    yield log
    restored = events.install(prev)
    assert restored is log


# ---------------------------------------------------------------------------
# Registry units
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_labels(self):
        reg = MetricRegistry()
        c = reg.counter("t_total", "doc", ("kind",))
        c.inc(kind="a")
        c.inc(2, kind="b")
        assert c.value(kind="a") == 1 and c.value(kind="b") == 2
        assert c.value(kind="missing") == 0
        with pytest.raises(ValueError):
            c.inc(-1, kind="a")
        with pytest.raises(ValueError):
            c.inc(wrong="a")

    def test_get_or_create_and_conflicts(self):
        reg = MetricRegistry()
        a = reg.counter("x_total", "doc")
        assert reg.counter("x_total", "other doc") is a
        with pytest.raises(ValueError):
            reg.gauge("x_total", "doc")          # kind conflict
        with pytest.raises(ValueError):
            reg.counter("x_total", "doc", ("l",))  # label conflict
        with pytest.raises(ValueError):
            reg.counter("bad name", "doc")       # invalid name

    def test_gauge_set_fn_pulls_at_collect(self):
        reg = MetricRegistry()
        g = reg.gauge("g", "doc")
        g.set(1.0)
        box = [7.0]
        g.set_fn(lambda: box[0])
        assert g.value() == 7.0
        box[0] = 9.0
        assert g.samples() == [({}, 9.0)]

    def test_histogram_quantile_log_estimate(self):
        reg = MetricRegistry()
        h = reg.histogram("h_seconds", "doc")
        for v in [0.010] * 50 + [0.080] * 50:
            h.observe(v)
        # Log-bucket estimates: right bucket, within one bucket width.
        p50 = h.quantile(0.50)
        p99 = h.quantile(0.99)
        assert 0.0051 < p50 <= 0.0205, p50
        assert 0.051 < p99 <= 0.205, p99
        s = h.summary(scale=1e3)
        assert s["n"] == 100 and s["p99"] >= s["p50"]
        assert s["mean"] == pytest.approx(45.0, rel=1e-3)

    def test_histogram_merges_across_instances(self):
        """The fixed-bucket contract: two ranks' histograms merge by
        ADDING counts and the merged quantile equals the quantile of
        the union — the property a sample reservoir cannot offer."""
        ra, rb, rm = (MetricRegistry() for _ in range(3))
        ha = ra.histogram("h", "doc")
        hb = rb.histogram("h", "doc")
        hm = rm.histogram("h", "doc")
        xs_a = [0.003, 0.01, 0.04]
        xs_b = [0.1, 0.5, 2.0, 8.0]
        for v in xs_a:
            ha.observe(v)
        for v in xs_b:
            hb.observe(v)
        for src in (ha, hb):
            child = src.samples()[0][1]
            hm.merge_counts(list(child.counts), child.sum)
        union = MetricRegistry().histogram("h", "doc")
        for v in xs_a + xs_b:
            union.observe(v)
        for q in (0.25, 0.5, 0.9):
            assert hm.quantile(q) == pytest.approx(union.quantile(q))
        child = hm.samples()[0][1]
        assert child.count == len(xs_a) + len(xs_b)
        assert child.sum == pytest.approx(sum(xs_a) + sum(xs_b))

    def test_quantile_from_buckets_empty(self):
        assert quantile_from_buckets(DEFAULT_BUCKETS,
                                     [0] * 23, 0.5) is None

    def test_histogram_bucket_conflict_raises(self):
        """Re-declaring a histogram with different buckets must be a
        conflict, not a silent hand-back of the existing edges (a
        later merge_counts sized for the requested edges would then
        fold into the wrong ones)."""
        reg = MetricRegistry()
        h = reg.histogram("h", "doc", buckets=(0.1, 1.0))
        assert reg.histogram("h", "doc", buckets=(0.1, 1.0)) is h
        assert reg.histogram("h", "doc") is h   # no buckets = accept
        with pytest.raises(ValueError, match="buckets"):
            reg.histogram("h", "doc", buckets=(0.5, 5.0))

    def test_histogram_samples_are_snapshots(self):
        """samples() must hand back copies, not the live mutable
        children — a scrape reading while observe() runs must never
        see a torn +Inf-vs-count pair."""
        reg = MetricRegistry()
        h = reg.histogram("h", "doc", buckets=(1.0,))
        h.observe(0.5)
        snap = h.samples()[0][1]
        h.observe(0.5)
        assert snap.count == 1 and snap.counts[0] == 1
        assert h.samples()[0][1].count == 2

    def test_remove_drops_labeled_child(self):
        """Gauge rows of dead instances must be removable so scrape
        cardinality tracks live label values (the engine-shutdown
        path)."""
        reg = MetricRegistry()
        g = reg.gauge("g", "doc", ("engine",))
        g.set(5, engine="0")
        g.set(7, engine="1")
        g.remove(engine="0")
        assert g.samples() == [({"engine": "1"}, 7.0)]
        g.remove(engine="0")   # idempotent

    def test_gauge_callback_fault_is_contained(self):
        """ANY plausible callback failure must read as NaN, never
        propagate into (and abort) a scrape."""
        reg = MetricRegistry()
        g = reg.gauge("g", "doc")
        g.set_fn(lambda: {}["missing"])      # KeyError
        assert math.isnan(g.value())
        assert math.isnan(g.samples()[0][1])

    def test_gauge_callback_may_touch_own_gauge(self):
        """value() runs the callback OUTSIDE the non-reentrant lock
        (like samples()) — a set_fn touching its own gauge must not
        deadlock."""
        reg = MetricRegistry()
        g = reg.gauge("g", "doc")

        def fn():
            g.set(9.0)     # deadlocked under a lock-held callback
            return 4.0

        g.set_fn(fn)
        assert g.value() == 4.0

    def test_exemplar_kept_per_child(self):
        reg = MetricRegistry()
        h = reg.histogram("h", "doc")
        h.observe(0.5, exemplar={"trace_id": "aa"})
        h.observe(0.7, exemplar={"trace_id": "bb"})
        ex = h.samples()[0][1].exemplar
        assert ex["trace_id"] == "bb" and ex["value"] == 0.7


# ---------------------------------------------------------------------------
# Prometheus text format (satellite: parse with the format's regex)
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (\S+)$')
_HELP_RE = re.compile(r"^# HELP ([a-zA-Z_:][a-zA-Z0-9_:]*) (.*)$")
_TYPE_RE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
    r"(counter|gauge|histogram|untyped)$")


def _parse_prom(text):
    """{family: type}, [(name, labels_str, value_str)] — every line
    must match the exposition grammar (the test's point)."""
    types, samples = {}, []
    for line in text.strip().splitlines():
        if line.startswith("# HELP "):
            assert _HELP_RE.match(line), line
            continue
        if line.startswith("# TYPE "):
            m = _TYPE_RE.match(line)
            assert m, line
            types[m.group(1)] = m.group(2)
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        samples.append((m.group(1), m.group(2) or "", m.group(3)))
    return types, samples


class TestPrometheusText:
    def _registry(self):
        reg = MetricRegistry()
        c = reg.counter("req_total", "requests — by kind", ("kind",))
        c.inc(3, kind='weird"label\\with\nstuff')
        reg.gauge("depth", "queue depth").set(4)
        h = reg.histogram("lat_seconds", "latency",
                          buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        return reg

    def test_help_type_and_samples_parse(self):
        types, samples = _parse_prom(
            render_prometheus(self._registry()))
        assert types == {"req_total": "counter", "depth": "gauge",
                         "lat_seconds": "histogram"}
        names = {n for n, _, _ in samples}
        assert {"req_total", "depth", "lat_seconds_bucket",
                "lat_seconds_sum", "lat_seconds_count"} <= names

    def test_non_finite_values_render_not_crash(self):
        """A gauge whose set_fn callback fails reads NaN — the scrape
        must render the format's 'NaN' spelling, never abort (one bad
        callback must not take down /metrics)."""
        reg = MetricRegistry()
        g = reg.gauge("bad", "doc")
        g.set_fn(lambda: (_ for _ in ()).throw(ValueError("boom")))
        reg.gauge("inf", "doc2").set(float("-inf"))
        text = render_prometheus(reg)
        assert "bad NaN" in text and "inf -Inf" in text
        _parse_prom(text)

    def test_label_escaping_round_trips(self):
        text = render_prometheus(self._registry())
        (line,) = [l for l in text.splitlines()
                   if l.startswith("req_total{")]
        # Escaped forms on the wire; the raw quote/backslash/newline
        # never appear un-escaped inside the braces.
        assert '\\"' in line and "\\\\" in line and "\\n" in line
        assert "\n" not in line

    def test_histogram_bucket_invariants(self):
        text = render_prometheus(self._registry())
        buckets = []
        s = count = None
        for name, labels, val in _parse_prom(text)[1]:
            if name == "lat_seconds_bucket":
                le = re.search(r'le="([^"]+)"', labels).group(1)
                buckets.append((le, int(val)))
            elif name == "lat_seconds_sum":
                s = float(val)
            elif name == "lat_seconds_count":
                count = int(val)
        # Cumulative and monotonic, closed by +Inf == _count.
        counts = [c for _, c in buckets]
        assert counts == sorted(counts)
        assert buckets[-1][0] == "+Inf"
        assert buckets[-1][1] == count == 5
        assert counts == [1, 3, 4, 5]
        assert s == pytest.approx(56.05)

    def test_shared_registry_has_all_standard_families(self):
        catalog.declare_standard_metrics()
        types, _ = _parse_prom(render_prometheus(registry()))
        for fam in ("hvd_serving_ttft_seconds",
                    "hvd_serving_tpot_seconds",
                    "hvd_serving_queue_depth",
                    "hvd_serving_slot_occupancy",
                    "hvd_serving_events_total",
                    "hvd_serving_compiles_total",
                    "hvd_resilience_restarts_total",
                    "hvd_resilience_requeued_total",
                    "hvd_resilience_faults_injected_total",
                    "hvd_resilience_stalls_total",
                    "hvd_training_step_seconds",
                    "hvd_training_tokens_per_s",
                    "hvd_training_mfu",
                    "hvd_collectives_total",
                    "hvd_events_total"):
            assert fam in types, fam


# ---------------------------------------------------------------------------
# HTTP exporter
# ---------------------------------------------------------------------------

class TestExporter:
    def test_endpoints(self):
        with MetricsServer(port=0) as srv:
            text = urllib.request.urlopen(
                srv.url + "/metrics", timeout=10).read().decode()
            _parse_prom(text)      # the whole scrape must parse
            health = json.loads(urllib.request.urlopen(
                srv.url + "/healthz", timeout=10).read())
            assert health["status"] in ("ok", "degraded")
            assert health["uptime_s"] >= 0
            full = json.loads(urllib.request.urlopen(
                srv.url + "/metrics.json", timeout=10).read())
            assert "hvd_training_mfu" in full["metrics"]
            assert isinstance(full["events"], list)
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(srv.url + "/nope", timeout=10)

    def test_fixed_port_conflict_disables_not_crashes(self):
        """An occupied fixed HVD_METRICS_PORT must warn-and-disable,
        never raise out of hvd.init()/engine construction — on a
        multi-rank host every local rank sees the same port and only
        one can own it."""
        from horovod_tpu.obs import exporter as exp
        with MetricsServer(port=0) as srv:
            try:
                got = exp.start_exporter(port=srv.port)
                assert got is None
            finally:
                exp.stop_exporter()

    def test_healthz_degraded_returns_503(self):
        """A component self-reporting healthy=false (a dead dispatch
        thread) must flip /healthz to 503 — status-code probes (k8s
        liveness, LBs) never read bodies."""
        reg = MetricRegistry()
        reg.register_health("dead_engine",
                            lambda: {"healthy": False})
        with MetricsServer(reg, port=0) as srv:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + "/healthz",
                                       timeout=10)
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["status"] == "degraded"

    def test_health_provider_surfaces(self):
        reg = MetricRegistry()
        reg.register_health("unit", lambda: {"generation": 3})
        with MetricsServer(reg, port=0) as srv:
            health = json.loads(urllib.request.urlopen(
                srv.url + "/healthz", timeout=10).read())
        assert health["components"]["unit"]["generation"] == 3
        reg.unregister_health("unit")
        assert "components" not in reg.health()


# ---------------------------------------------------------------------------
# Event log
# ---------------------------------------------------------------------------

class TestEvents:
    def test_ring_bounded_and_seq_monotonic(self, tmp_path):
        log = events.EventLog(maxlen=4)
        for i in range(10):
            log.emit("k", i=i)
        tail = log.tail()
        assert len(log) == 4
        assert [r["i"] for r in tail] == [6, 7, 8, 9]
        assert [r["seq"] for r in tail] == [7, 8, 9, 10]

    def test_jsonl_file_and_rotation(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        log = events.EventLog(path, max_bytes=300)
        for i in range(32):
            log.emit("fill", i=i, pad="x" * 32)
        assert os.path.exists(path) and os.path.exists(path + ".1")
        assert os.path.getsize(path) <= 300 + 128
        recs = [json.loads(l) for l in open(path)]
        assert all(r["kind"] == "fill" for r in recs)

    def test_global_emit_mirrors_counter(self, event_log):
        c = catalog.event_metrics()["events"]
        before = c.value(kind="unit.test")
        events.emit("unit.test", a=1)
        assert c.value(kind="unit.test") == before + 1
        assert events.tail(1)[0]["a"] == 1
        assert json.loads(open(event_log.path).read().splitlines()[-1]
                          )["kind"] == "unit.test"


# ---------------------------------------------------------------------------
# Series (serving.metrics) — the sort-once + p99 satellite
# ---------------------------------------------------------------------------

class TestSeries:
    def test_summary_has_p99_and_matches_nearest_rank(self):
        from horovod_tpu.serving.metrics import Series
        s = Series()
        xs = list(range(1, 101))     # 1..100
        for v in xs:
            s.add(v)
        out = s.summary()
        assert out["n"] == 100
        assert out["p50"] == pytest.approx(s.percentile(50))
        assert out["p95"] == pytest.approx(s.percentile(95))
        assert out["p99"] == pytest.approx(s.percentile(99))
        assert out["p99"] >= out["p95"] >= out["p50"]
        assert out["mean"] == pytest.approx(50.5)

    def test_summary_empty(self):
        from horovod_tpu.serving.metrics import Series
        assert Series().summary() == {
            "p50": None, "p95": None, "p99": None,
            "mean": None, "n": 0}

    def test_summary_sorts_reservoir_once(self, monkeypatch):
        """The satellite's regression guard: one summary() pays ONE
        sort, not one per percentile (the old shape sorted per
        `percentile` call — twice per series per snapshot)."""
        import horovod_tpu.serving.metrics as M
        s = M.Series()
        for v in (3.0, 1.0, 2.0):
            s.add(v)
        calls = {"n": 0}
        real_sorted = sorted

        def counting_sorted(xs, *a, **kw):
            calls["n"] += 1
            return real_sorted(xs, *a, **kw)

        monkeypatch.setattr(M, "sorted", counting_sorted,
                            raising=False)
        out = s.summary()
        assert out["p50"] == 2.0
        assert calls["n"] == 1


# ---------------------------------------------------------------------------
# Cross-subsystem request tracing
# ---------------------------------------------------------------------------

class TestTracing:
    def test_trace_id_format(self):
        a, b = spans.new_trace_id(), spans.new_trace_id()
        assert re.fullmatch(r"[0-9a-f]{16}", a)
        assert a != b
        assert re.fullmatch(r"[0-9a-f]{8}", spans.new_span_id())

    def test_trace_id_in_three_subsystems(self, lm, event_log,
                                          tmp_path):
        """The acceptance path: ONE request's trace_id recovered from
        the event log, the span recorder's tree, and the registry
        histogram exemplar — all for the same request."""
        from horovod_tpu.serving import ServingEngine
        model, params = lm
        with ServingEngine(model, params, num_slots=2) as eng:
            h = eng.submit(np.array([3, 5, 7]), 6)
            out = h.result(timeout=300)
        tid = h.trace_id
        assert re.fullmatch(r"[0-9a-f]{16}", tid)
        # 0) the result itself carries it
        assert out.trace_id == tid
        # 1) event log: submit and retire, same id
        recs = [json.loads(l) for l in open(event_log.path)]
        kinds = {r["kind"] for r in recs if r.get("trace_id") == tid}
        assert {"serving.submit", "serving.retire"} <= kinds, kinds
        # 2) the span recorder: the request's tree under the same id
        tree = spans.trace(tid) or []
        assert {sp["name"] for sp in tree} >= {
            "serving.request", "serving.queued", "serving.prefill",
            "serving.decode"}
        # 3) registry histogram exemplar (the LAST finished request
        #    was this one — the only one submitted)
        ex = (registry().get("hvd_serving_e2e_seconds")
              .samples()[0][1].exemplar)
        assert ex is not None and ex["trace_id"] == tid

    def test_requeued_after_restart_keeps_trace_id(self, lm,
                                                   event_log):
        """Satellite: trace continuity across the watchdog restart —
        the replayed request completes under its ORIGINAL trace_id
        and the restart event names that id in its requeue list."""
        from horovod_tpu.resilience import chaos
        from horovod_tpu.serving import ServingEngine
        model, params = lm
        eng = ServingEngine(model, params, num_slots=2, max_queue=16,
                            auto_restart=True, max_restarts=2)
        try:
            handles = [eng.submit(p, 10) for p in
                       (np.array([3, 5, 7]), np.array([2, 4]))]
            _wait(lambda: eng.pool.busy_slots > 0)
            with chaos.armed("serving_dispatch_crash:1"):
                _wait(lambda:
                      eng.metrics_snapshot()["restarts"] == 1)
                results = [h.result(timeout=300) for h in handles]
            for h, r in zip(handles, results):
                assert r.trace_id == h.trace_id
            recs = [json.loads(l) for l in open(event_log.path)]
            restarts = [r for r in recs
                        if r["kind"] == "serving.restart"]
            assert restarts and restarts[0]["requeued"] >= 1
            requeued_ids = set(restarts[0]["requeued_trace_ids"])
            assert requeued_ids <= {h.trace_id for h in handles}
            # ...and the replayed request RETIRED under the same id.
            retired = {r["trace_id"] for r in recs
                       if r["kind"] == "serving.retire"}
            assert requeued_ids <= retired
            # chaos fire reached the per-site resilience counter
            c = catalog.resilience_metrics()["faults_injected"]
            assert c.value(site="serving_dispatch_crash") >= 1
        finally:
            eng.shutdown()

    def test_snapshot_scrape_seq_and_uptime(self, lm):
        """Satellite: metrics_snapshot() carries a monotonic
        scrape_seq and uptime_s (restart-vs-reset disambiguation for
        scrapers)."""
        from horovod_tpu.serving import ServingEngine
        model, params = lm
        with ServingEngine(model, params, num_slots=1) as eng:
            eng.submit(np.array([5, 9]), 4).result(timeout=300)
            a = eng.metrics_snapshot()
            b = eng.metrics_snapshot()
        assert b["scrape_seq"] == a["scrape_seq"] + 1
        assert b["uptime_s"] >= a["uptime_s"] > 0
        assert a["ttft_ms"]["p99"] is not None


# ---------------------------------------------------------------------------
# Registrants: stall monitor, training bracket, engine health
# ---------------------------------------------------------------------------

class TestRegistrants:
    def test_stall_registers_counter_and_event(self, event_log):
        from horovod_tpu.utils.stall import StallMonitor
        c = catalog.resilience_metrics()["stalls"]
        before = c.value()
        mon = StallMonitor(warning_time_s=60.0, check_every_s=3600.0)
        try:
            mon.begin("obs_test_op")
            stalled = mon.check_once(now=time.time() + 120.0)
        finally:
            mon.stop()
        assert stalled == ["obs_test_op"]
        assert c.value() == before + 1
        assert any(r["kind"] == "stall"
                   and r["op"] == "obs_test_op"
                   for r in events.tail(50))

    def test_step_profiler_records_and_mfu(self):
        from horovod_tpu.obs.profiling import StepProfiler
        m = catalog.training_metrics()
        before = m["steps"].value()
        prof = StepProfiler("unit_step", tokens_per_step=1000,
                            flops_per_step=275e12 * 0.25,
                            device_kind="TPU v4")
        prof.observe(1.0)   # 1 s step => 25% of v4 peak
        assert m["steps"].value() == before + 1
        assert m["mfu"].value() == pytest.approx(0.25)
        assert m["tokens_per_s"].value() == pytest.approx(1000.0)

    def test_profile_step_context(self):
        from horovod_tpu.obs.profiling import profile_step
        m = catalog.training_metrics()
        before = m["steps"].value()
        with profile_step("unit_step2"):
            pass
        assert m["steps"].value() == before + 1

    def test_profiler_session_noop_without_knob(self, monkeypatch):
        from horovod_tpu.obs.profiling import profiler_session
        monkeypatch.delenv("HVD_PROFILE_DIR", raising=False)
        with profiler_session() as d:
            assert d is None

    def test_obs_step_wrapper_preserves_wrapped(self):
        from horovod_tpu.models.train import _obs_step
        m = catalog.training_metrics()

        def inner(state, batch, rng):
            return state, 0.5

        inner.__wrapped__ = "sentinel"
        stepped = _obs_step(inner)
        before = m["steps"].value()
        assert stepped({}, None, None) == ({}, 0.5)
        assert m["steps"].value() == before + 1
        assert stepped.__wrapped__ == "sentinel"

    def test_engine_health_provider_lifecycle(self, lm):
        from horovod_tpu.serving import ServingEngine
        model, params = lm
        eng = ServingEngine(model, params, num_slots=1)
        key = f"serving_engine_{eng._engine_id}"
        health = registry().health()
        assert key in health.get("components", {})
        comp = health["components"][key]
        assert comp["engine_generation"] == 0
        assert comp["dispatch_alive"] is True
        # Engine-scoped gauges are labeled per engine, so a second
        # engine's construction cannot erase this one's generation.
        gen = catalog.serving_metrics()["engine_generation"]
        assert gen.value(engine=str(eng._engine_id)) == 0
        eng2 = ServingEngine(model, params, num_slots=1)
        assert eng2._engine_id != eng._engine_id
        assert gen.value(engine=str(eng._engine_id)) == 0
        eng2.shutdown()
        eng.shutdown()
        assert key not in registry().health().get("components", {})
        # Shutdown removed both engines' gauge rows from the shared
        # registry — no frozen per-dead-engine series on /metrics.
        live = {labels.get("engine") for labels, _ in gen.samples()}
        assert str(eng._engine_id) not in live
        assert str(eng2._engine_id) not in live

    def test_mfu_math(self):
        from horovod_tpu.utils.profile_analysis import (
            device_peak_flops, mfu)
        assert device_peak_flops("TPU v4") == 275e12
        assert device_peak_flops("cpu") is None
        assert device_peak_flops(None) is None
        assert mfu(275e12 / 2, "TPU v4") == pytest.approx(0.5)
        assert mfu(1e12, "unknown") is None

    def test_new_knobs_registered(self):
        from horovod_tpu.runtime.config import KNOBS
        for name in ("HVD_METRICS_PORT", "HVD_EVENTS_LOG",
                     "HVD_PROFILE_DIR"):
            assert name in KNOBS, name
