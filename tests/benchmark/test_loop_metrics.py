"""The per-layer metrics that read the program's loop spans: the
matching of the ring against a trace, each reader on recorded runs
(`benchmarks/data/loop_*.json.gz`, written by
`benchmarks/tools/record_loop.py`) and on an empty input, the idle
gaps by program span, and `decode_tick_roofline`'s byte and flop
functions at a hand-computed size.
"""

import gzip
import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks.harness import loopspans  # noqa: E402
from benchmarks.harness.cells import load_module  # noqa: E402

BENCH = os.path.join(REPO, "benchmarks")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("sched_cpu_ms_per_tick", "lanes_prefilling_share",
           "lanes_free_share", "decode_tick_roofline",
           "train_host_ms_per_step")


def reader(name):
    mod = load_module(os.path.join(BENCH, "layer_metrics", name + ".py"),
                      "metric_" + name)
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        args = json.load(f).get("args", {})
    return lambda ctx: mod.read(ctx, **args)


def recorded(name):
    """A recording as the context its run handed the readers."""
    with gzip.open(os.path.join(BENCH, "data", name), "rt") as f:
        rec = json.load(f)
    cell = types.SimpleNamespace(config={"arch": rec["arch"]})
    ctx = dict(rec["ctx"], trace=rec["trace"], cell=cell,
               peaks=rec["peaks"], loop_ring=rec["loop_ring"],
               trace_window_s=rec["trace_window_s"])
    return rec, ctx


def arch_of(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["arch"]


# ---- required bytes and flops of a decode tick, by hand ---------------
def test_decode_tick_bytes_and_flops_by_hand():
    roof = load_module(os.path.join(BENCH, "layer_metrics",
                                    "decode_tick_roofline.py"), "roof")
    a = arch_of("qwen2.5-1.5b")
    # a layer: q, k, v, o 1536 x (12 + 2 + 2) x 128 + 12 x 128 x 1536,
    # SwiGLU 3 x 1536 x 8960; 28 layers and the 151936 x 1536 head
    params = 28 * (1536 * 2048 + 1536 * 1536 + 3 * 1536 * 8960) \
        + 151936 * 1536
    assert params == 1_543_569_408
    # K and V of one position: 2 x 28 layers x 2 heads x 128 x 2 bytes
    assert roof.kv_bytes_per_position(a) == 28_672
    # 32 lanes that hold 20 000 positions between them
    assert roof.tick_bytes(a, 32, 20_000) == (
        2 * params + (20_000 + 32) * 28_672) == 3_661_496_320
    assert roof.tick_flops(a, 32, 20_000) == (
        2 * params * 32 + 4 * 28 * 12 * 128 * 20_000) == 102_229_082_112
    # 3.66 GB at 819 GB/s is 4.47 ms; 102 GFLOP at 197 TFLOP/s 0.52 ms
    tick = {"lanes_decoding": 32, "context_sum": 20_000}
    assert roof.least_seconds(a, tick, PEAKS) == pytest.approx(
        3_661_496_320 / 819e9)
    assert roof.least_seconds(a, tick, PEAKS) == pytest.approx(
        4.4707e-3, rel=1e-4)
    # one lane, nothing cached: the weights alone
    assert roof.tick_bytes(a, 1, 0) == 2 * params + 28_672
    # an empty tick record list reads nothing
    assert roof.read({"trace": None}, "^jit_slot_decode_tick") is None


# ---- the ring against a trace: synthetic ------------------------------
def synthetic(offset=1_000_000_000_000, steps=6, tick_ns=60_000_000):
    """`steps` scheduler steps of `tick_ns`, a trace that holds the
    middle ones, one device whose tick programs leave a 2.1 ms gap at
    the start of every step (most of it under sched.first_token) and
    a 1 ms gap no span covers after the last. Every record carries
    `cpu_ns`, the thread's own work inside it: a step 1 ms (0.3 ms
    dispatching the tick, 0.5 reading the first token, 0.1 in the
    sync, the rest its own), the bookkeeping 80 us."""
    ring, host, modules = [], [], []
    t = 5_000_000
    for i in range(steps):
        dur = tick_ns + (i * 37 % 11) * 90_000    # no two alike
        children = [
            ("sched.housekeeping", t + 1_000, 8_000, 8_000, {}),
            ("sched.first_token", t + 100_000, 2_000_000, 500_000,
             {"slot": i}),
            ("sched.tick_dispatch", t + 2_200_000, 300_000, 300_000,
             {"lanes_decoding": 3, "lanes_prefilling": 1,
              "lanes_free": 0, "queue_depth": 0,
              "context_sum": 1000 + i, "context_max": 500}),
            ("sched.tick_sync", t + 2_600_000, dur - 2_700_000, 100_000,
             {"overlapped": True, "tokens": 3, "retired": 0}),
        ]
        step_seq = len(ring) + len(children) + 1    # appended last
        for name, s, d, cpu, attrs in children:
            ring.append({"seq": len(ring) + 1, "name": name,
                         "t0_ns": s + offset, "t1_ns": s + d + offset,
                         "parent": step_seq, "attrs": attrs,
                         "cpu_ns": cpu})
            if 0 < i < steps - 1 and d >= 20_000:
                host.append([name, s, d])
        ring.append({"seq": step_seq, "name": "sched.step",
                     "t0_ns": t + offset - 3_000,
                     "t1_ns": t + dur + offset + 2_000,
                     "parent": 0, "attrs": {"tick": i},
                     "cpu_ns": 1_000_000})
        ring.append({"seq": len(ring) + 1, "name": "engine.bookkeeping",
                     "t0_ns": t + dur + offset + 10_000,
                     "t1_ns": t + dur + offset + 110_000,
                     "parent": 0, "attrs": {}, "cpu_ns": 80_000})
        if 0 < i < steps - 1:
            host.append(["sched.step", t, dur])
            host.append(["engine.bookkeeping", t + dur + 10_000, 100_000])
            host.append(["PjitFunction(scatter)", t + 150_000, 400_000])
            # device: busy except while the host reads the first token
            modules.append(["jit_slot_decode_tick(1)", t + 2_100_000,
                            dur + 200_000 - 2_100_000])
        t += dur + 200_000
    # 1 ms after the last traced tick: a gap under no span
    host.append(["late", modules[-1][1] + modules[-1][2] + 1_000_000,
                 50_000])
    modules.append(["jit_other", host[-1][1], 50_000])
    trace = {"devices": {"/device:TPU:0": {"ops": [], "modules": modules}},
             "host": host}
    return ring, trace


def test_ring_is_matched_to_the_trace_by_its_steps():
    ring, trace = synthetic()
    off, pairs, spread = loopspans.clock_offset(trace, ring)
    assert pairs == 4 and spread == 0
    assert off == 1_000_000_000_000 - 3_000     # ring stamps outside
    ctx = {"trace": trace, "loop_ring": ring}
    found = loopspans.traced(ctx)
    steps = [x["attrs"]["tick"] for x in found["records"]
             if x["name"] == "sched.step"]
    assert steps == [1, 2, 3, 4]    # the traced ones, not 0 and 5
    assert loopspans.traced(ctx) is found       # matched once
    # durations that match nowhere: no offset, nothing read
    for x in ring:
        if x["name"] == "sched.step":
            x["t1_ns"] += 700_000 * x["attrs"]["tick"] ** 2
    assert loopspans.clock_offset(trace, ring) is None


def test_readers_on_a_synthetic_run():
    ring, trace = synthetic()
    ctx = {"trace": trace, "loop_ring": ring, "traced_ticks": 4,
           "window_ticks": 3, "num_slots": 4, "peaks": PEAKS,
           "lanes_live_share": 0.74,
           "cell": types.SimpleNamespace(
               config={"arch": arch_of("qwen2.5-1.5b")})}
    # the measured window's three steps: 1 ms of the thread's own work
    # in each and 80 us in the bookkeeping after it
    assert reader("sched_cpu_ms_per_tick")(ctx) == pytest.approx(1.08)
    assert reader("lanes_prefilling_share")(ctx) == pytest.approx(25.0)
    assert reader("lanes_free_share")(ctx) == pytest.approx(0.0)
    roof = reader("decode_tick_roofline")(ctx)
    # 3.087 GB of weights and about 1000 positions: 3.77 ms against
    # ticks of about 58 ms
    assert roof == pytest.approx(6.5, abs=0.2)
    assert reader("train_host_ms_per_step")(ctx) is None


def test_idle_gaps_by_program_span():
    ring, trace = synthetic()
    g = loopspans.gap_phases(trace)
    # three gaps of 2.1 ms before a tick's program starts (2.0 ms of
    # each under sched.first_token, the rest sched.step's own); the
    # 1 ms after the last span is not judged: a span still open when
    # the session stops leaves no annotation
    assert g["gap_s"] == pytest.approx(6.3e-3, rel=1e-6)
    assert g["covered_s"] == pytest.approx(6.3e-3, rel=1e-6)
    assert "(none)" not in g["by_phase"]
    assert g["by_phase"]["sched.first_token"] == pytest.approx(
        6e-3, rel=1e-6)
    assert g["by_phase"]["sched.step (own)"] == pytest.approx(
        0.3e-3, rel=1e-6)
    # PJRT's name for those gaps falls in sched.first_token
    per = g["by_event"]["PjitFunction(scatter)"]
    assert max(per, key=per.get) == "sched.first_token"
    # a step whose annotations are missing: its gap is under no span
    steps = sorted(e[1] for e in trace["host"] if e[0] == "sched.step")
    lo, hi = steps[2], steps[3]
    holed = dict(trace, host=[e for e in trace["host"]
                              if not (lo <= e[1] < hi
                                      and e[0].startswith("sched."))])
    g = loopspans.gap_phases(holed)
    assert g["gap_s"] == pytest.approx(6.3e-3, rel=1e-6)
    assert g["by_phase"]["(none)"] == pytest.approx(2.1e-3, rel=1e-6)
    assert g["covered_s"] == pytest.approx(4.2e-3, rel=1e-6)
    assert loopspans.gap_phases({"devices": {}, "host": []}) is None
    assert loopspans.gap_phases(dict(trace, host=[])) is None


# ---- every reader on nothing ------------------------------------------
@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("what", ["no_trace", "no_ring", "no_spans"])
def test_readers_return_none_on_an_empty_input(name, what):
    ring, trace = synthetic()
    ctx = {"trace": trace, "loop_ring": ring, "traced_ticks": 4,
           "traced_steps": 4, "window_ticks": 3, "num_slots": 4,
           "peaks": PEAKS, "cell": types.SimpleNamespace(
               config={"arch": arch_of("qwen2.5-1.5b")})}
    if what == "no_trace":
        ctx.update(trace=None, window_ticks=None)
    elif what == "no_ring":
        ctx["loop_ring"] = []       # a program without loop spans
    else:                           # a trace without annotations
        ctx["trace"] = dict(trace, host=[e for e in trace["host"]
                                         if "." not in e[0][:6]])
        ctx["loop_ring"] = [x for x in ring
                            if x["name"] != "sched.tick_dispatch"]
    assert reader(name)(ctx) is None


# ---- every reader on a recorded run ------------------------------------
# `loop_tiny_*_cpu`: the rehearsal cells (tests/benchmark/tiny) on the
# CPU - host plane only, so what needs a device reads nothing there.
# The others: the benchmark's cells on the chip; `loop_period_closed32`
# is the one whose ring has `cpu_ns` (PR 36), which the thread's own
# work a tick is read from.
SERVE_RUNS = ["loop_tiny_serve_cpu.json.gz", "loop_serve_closed32.json.gz",
              "loop_period_closed32.json.gz"]
TRAIN_RUNS = ["loop_tiny_train_cpu.json.gz", "loop_train_1chip.json.gz"]


@pytest.mark.parametrize("name", SERVE_RUNS)
def test_serve_readers_on_a_recorded_run(name):
    rec, ctx = recorded(name)
    on_chip = bool(rec["trace"]["devices"])
    has_cpu = all("cpu_ns" in x for x in rec["loop_ring"])
    for metric in READERS[:4]:
        got = reader(metric)(ctx)
        if metric in rec["metrics"]:
            assert got == pytest.approx(
                rec["metrics"][metric]["value"], rel=1e-9), metric
        elif metric == "sched_cpu_ms_per_tick":     # an older ring
            assert got is None and not has_cpu
        else:
            assert got is None and not on_chip, metric
    assert ("sched_cpu_ms_per_tick" in rec["metrics"]) == has_cpu
    assert reader("train_host_ms_per_step")(ctx) is None
    found = loopspans.traced(ctx)
    assert found["pairs"] >= 10
    assert found["spread_ns"] == rec["clock"]["spread_ns"] < 200_000
    ticks = loopspans.window_ticks(ctx)
    assert len(ticks) == ctx["window_ticks"]
    assert all(t["lanes_decoding"] + t["lanes_prefilling"]
               + t["lanes_free"] == ctx["num_slots"] for t in ticks)
    if not on_chip:
        return
    assert 0 < rec["metrics"]["decode_tick_roofline"]["value"] < 100
    # the three shares of the lanes, from two sources, sum to 100
    total = (ctx["lanes_live_share"] * 100
             + rec["metrics"]["lanes_prefilling_share"]["value"]
             + rec["metrics"]["lanes_free_share"]["value"])
    assert total == pytest.approx(100, abs=2)
    # the idle gaps of 0.5 ms and more lie under the program's spans
    g = loopspans.gap_phases(ctx["trace"])
    assert g["covered_s"] / g["gap_s"] >= 0.95


@pytest.mark.parametrize("name", TRAIN_RUNS)
def test_train_reader_on_a_recorded_run(name):
    rec, ctx = recorded(name)
    got = reader("train_host_ms_per_step")(ctx)
    assert got == pytest.approx(
        rec["metrics"]["train_host_ms_per_step"]["value"], rel=1e-9)
    for metric in READERS[:4]:
        assert reader(metric)(ctx) is None
    steps = [x for x in loopspans.traced(ctx)["records"]
             if x["name"] == "train.step"]
    assert len(steps) == ctx["traced_steps"]
    first = steps[0]["attrs"]["step"]
    assert [s["attrs"]["step"] for s in steps] == list(
        range(first, first + len(steps)))
