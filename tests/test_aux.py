"""Auxiliary subsystems: timeline, stall detection, config, sparse.

Mirrors SURVEY §5.1/§5.3/§5.6.
"""

import json
import time

import numpy as np
import pytest


def test_timeline_writes_chrome_trace(hvd, tmp_path):
    """HOROVOD_TIMELINE-equivalent produces parseable Chrome-trace JSON
    with per-tensor process metadata (timeline.cc:59-92 parity)."""
    path = str(tmp_path / "timeline.json")
    hvd.start_timeline(path)
    hvd.allreduce(hvd.per_rank(
        [np.ones((4,), np.float32)] * hvd.size()), name="tl_tensor")
    hvd.stop_timeline()
    with open(path) as f:
        events = json.load(f)
    names = {e.get("name") for e in events}
    assert "process_name" in names      # tensor modeled as a process
    assert "NEGOTIATE" in names
    phases = {e.get("ph") for e in events if e}
    assert {"B", "E"} <= phases


def test_timeline_step_bracket_covers_jitted_hot_path(hvd, tmp_path):
    """The SPMD train step is invisible to per-collective tracing
    (collectives live inside the compiled program); the host-side
    step bracket records its cadence in the same trace."""
    import optax

    path = str(tmp_path / "timeline_step.json")
    hvd.start_timeline(path)

    def loss_fn(params, batch):
        x, y = batch
        return ((x @ params["w"] - y) ** 2).mean()

    params = {"w": np.zeros((3, 1), np.float32)}
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    opt_state = tx.init(params)
    step = hvd.make_train_step(loss_fn, tx)
    rng = np.random.RandomState(0)
    batch = (rng.randn(16, 3).astype(np.float32),
             rng.randn(16, 1).astype(np.float32))
    for _ in range(3):
        params, opt_state, _ = step(params, opt_state, batch)
    hvd.stop_timeline()

    with open(path) as f:
        events = json.load(f)
    begins = [e for e in events
              if e.get("name") == "train_step" and e.get("ph") == "B"]
    assert len(begins) == 3, len(begins)
    ends = [e for e in events if e.get("ph") == "E"]
    assert ends, "step brackets must close"


def test_stall_monitor_detects(hvd):
    """Pending op past threshold triggers the stall warning
    (mpi_ops.cc:1150-1193 parity, warning not fatal)."""
    from horovod_tpu.utils.stall import StallMonitor
    mon = StallMonitor(warning_time_s=0.01, check_every_s=1000)
    mon.begin("stuck_tensor")
    time.sleep(0.05)
    stalled = mon.check_once()
    assert stalled == ["stuck_tensor"]
    # Warn once, not repeatedly (mpi_ops.cc warned set behavior).
    assert mon.check_once() == []
    mon.end("stuck_tensor")
    mon.stop()


def _chrome_trace(events, tmp_path):
    import gzip
    d = tmp_path / "plugins" / "profile" / "2026_01_01"
    d.mkdir(parents=True)
    p = d / "m.trace.json.gz"
    with gzip.open(p, "wt") as f:
        json.dump({"traceEvents": events}, f)
    return str(tmp_path)


def test_overlap_alpha_from_trace(hvd, tmp_path):
    """Measured-α extraction: async
    all-reduce-start/done pairs count only their non-compute-covered
    window as exposed; sync collectives are fully exposed; CPU-only
    traces (no device pid) yield None."""
    from horovod_tpu.utils.profile_analysis import analyze_profile_dir

    def ev(pid, name, ts, dur):
        return {"ph": "X", "pid": pid, "tid": 1, "name": name,
                "ts": ts, "dur": dur}

    meta = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
    ]
    events = meta + [
        ev(1, "fusion.1", 0, 50),            # compute
        ev(1, "all-reduce-start.5", 50, 2),  # async issue
        ev(1, "fusion.2", 52, 38),           # overlaps the window
        ev(1, "all-reduce-done.5", 90, 10),  # blocked wait
        ev(1, "all-gather.3", 100, 20),      # sync: fully exposed
        ev(1, "fusion.3", 120, 30),
        ev(9, "host-junk", 0, 1000),         # host pid ignored
    ]
    r = analyze_profile_dir(_chrome_trace(events, tmp_path))
    # all-reduce window [50, 100) = 50us, compute covers [52, 90) = 38
    # -> 12 exposed; all-gather 20us fully exposed. alpha = 32/70.
    assert r is not None
    assert r["t_comm_us"] == 70.0
    assert r["t_comm_exposed_us"] == 32.0
    assert r["alpha"] == round(32 / 70, 4)
    assert r["n_collectives"] == 2
    names = [t["name"] for t in r["top_exposed"]]
    assert "all-gather.3" in names and "all-reduce-done.5" in names

    # Host-only trace (the CPU backend's shape): no device timeline.
    r2 = analyze_profile_dir(_chrome_trace(
        meta[1:] + [ev(9, "x", 0, 10)], tmp_path / "cpuonly"))
    assert r2 is None

    # Repeated executions of the SAME op name (one per profiled step)
    # pair per-occurrence in time order — three fully-exposed 60us
    # windows count 3x, not last-one-wins.
    steps = meta[:1] + [e for s in range(3) for e in (
        ev(1, "all-reduce-start.9", 1000 * s, 5),
        ev(1, "all-reduce-done.9", 1000 * s + 55, 5),
    )]
    r3 = analyze_profile_dir(_chrome_trace(steps,
                                           tmp_path / "multistep"))
    assert r3["n_collectives"] == 3
    assert r3["t_comm_us"] == 180.0  # 3 x (start.ts -> done end) = 60
    assert r3["alpha"] == 1.0


def test_op_breakdown_from_trace(hvd, tmp_path):
    """Per-category device-time breakdown (every
    profiled capture must carry its own cost ranking): hlo_category
    args win, name-prefix fallback strips trailing indices, shares sum
    over device events only."""
    from horovod_tpu.utils.profile_analysis import analyze_profile_dir

    def ev(pid, name, ts, dur, cat=None):
        e = {"ph": "X", "pid": pid, "tid": 1, "name": name,
             "ts": ts, "dur": dur}
        if cat:
            e["args"] = {"hlo_category": cat}
        return e

    meta = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
    ]
    meta = meta + [
        {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
    ]
    events = meta + [
        ev(1, "fusion.1", 0, 60, cat="convolution fusion"),
        ev(1, "fusion.2", 60, 20, cat="convolution fusion"),
        ev(1, "fusion.7", 80, 15, cat="loop fusion"),
        ev(1, "copy.3", 95, 5),              # no category: prefix
        # Aggregate module lane spanning the whole step: must NOT be
        # summed into the per-op breakdown (it would double-count and
        # crown itself the top category).
        dict(ev(1, "jit_train_step", 0, 100), tid=2),
        ev(9, "host-junk", 0, 500),          # host pid excluded
    ]
    r = analyze_profile_dir(_chrome_trace(events, tmp_path))
    b = r["op_breakdown"]
    assert b["t_total_us"] == 100.0
    cats = {c["category"]: c for c in b["categories"]}
    assert cats["convolution fusion"]["us"] == 80.0
    assert cats["convolution fusion"]["share"] == 0.8
    assert cats["loop fusion"]["share"] == 0.15
    assert cats["copy"]["us"] == 5.0         # "copy.3" -> "copy"
    assert "jit_train_step" not in cats      # module lane excluded
    top = {o["name"]: o["us"] for o in b["top_ops"]}
    assert top["fusion.1"] == 60.0
    assert "jit_train_step" not in top


def test_mc_negotiation_stall_names_missing_ranks(hvd, capsys,
                                                  monkeypatch):
    """Coordinator stall sweep parity (CheckForStalledTensors,
    mpi_ops.cc:1150-1193): when a peer never
    posts its negotiation request, the periodic warning names the op
    AND lists ready vs missing processes, then the fatal timeout names
    the laggards and publishes the error so peers don't hang."""
    from types import SimpleNamespace

    from horovod_tpu.ops import eager
    from horovod_tpu.runtime.config import config

    published = {}

    class FakeNative:
        def ping(self):
            return True

        def kv_set(self, k, v):
            published[k] = v
            return True

        def kv_get(self, k, timeout_ms=60000):
            return None  # peer 1 never submits

    st = SimpleNamespace(native=FakeNative(), process_rank=0,
                         num_processes=2, size=2, op_cache={},
                         devices=[SimpleNamespace(process_index=0)])
    monkeypatch.setattr(config, "stall_warning_time", 1.0)
    with pytest.raises(RuntimeError, match=r"process\(es\) \[1\] never"):
        eager._mc_negotiate(st, "HorovodAllreduce", "allreduce",
                            np.zeros((2,), np.float32), None, False,
                            timeout_s=3.0)
    err = capsys.readouterr().err
    assert "Stalled op: HorovodAllreduce" in err
    assert "ready processes: [0]" in err
    assert "missing processes: [1]" in err
    assert err.count("Stalled op") == 1  # warn once, not per poll
    assert any(k.startswith("resp/") for k in published)


def test_config_env_vars(hvd, monkeypatch):
    from horovod_tpu.runtime.config import config
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "1024")
    monkeypatch.setenv("HOROVOD_CYCLE_TIME", "2.5")
    config.refresh()
    assert config.fusion_threshold == 1024
    assert config.cycle_time_ms == 2.5
    monkeypatch.delenv("HOROVOD_FUSION_THRESHOLD")
    monkeypatch.delenv("HOROVOD_CYCLE_TIME")
    config.refresh()
    assert config.fusion_threshold == 64 * 1024 * 1024


def test_indexed_slices_dense_roundtrip(hvd):
    from horovod_tpu.ops.sparse import IndexedSlices
    import jax.numpy as jnp
    ts = IndexedSlices(jnp.ones((2, 3)), jnp.array([0, 2]),
                       dense_shape=(4, 3))
    dense = np.asarray(ts.to_dense())
    assert dense.shape == (4, 3)
    np.testing.assert_allclose(dense[0], 1.0)
    np.testing.assert_allclose(dense[1], 0.0)


def test_sparse_allreduce_eager(hvd):
    """Eager IndexedSlices allreduce: allgather values+indices then
    divide (`horovod/tensorflow/__init__.py:61-72`)."""
    from horovod_tpu.ops.sparse import IndexedSlices
    import jax.numpy as jnp
    ts = IndexedSlices(jnp.full((2, 3), 8.0), jnp.array([1, 2]),
                       dense_shape=(4, 3))
    out = hvd.allreduce(ts, average=True)
    assert isinstance(out, IndexedSlices)
    # Replicated input: each of size() ranks contributes the same slices.
    assert out.values.shape == (2 * hvd.size(), 3)
    np.testing.assert_allclose(np.asarray(out.values), 1.0)
