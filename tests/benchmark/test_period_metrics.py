"""The per-layer metrics of the dispatch thread's period
(`benchmarks/harness/period.py`, PR 36): each reader on a synthetic
ring and trace with answers worked by hand, on a run recorded on the
chip by `benchmarks/tools/record_loop.py`
(`benchmarks/data/loop_period_closed32.json.gz`), on the older
recordings (whose rings carry no `cpu_ns`: nothing to read) and on
empty input.
"""

import gzip
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402
from benchmarks.harness import loopspans, period  # noqa: E402
from benchmarks.harness import trace as _trace  # noqa: E402
from benchmarks.harness.cells import load_module  # noqa: E402

BENCH = os.path.join(REPO, "benchmarks")
READERS = (
    "sched_cpu_ms_per_tick", "sched_wait_ms_per_tick",
    "prefill_chunks_per_tick", "prefill_tokens_per_chunk",
    "chunk_device_ms_per_tick", "queue_wait_p50_ms.saturated",
    "queue_wait_p95_ms.saturated", "idle_ms_per_tick.admit",
    "idle_ms_per_tick.prefill_chunk", "idle_ms_per_tick.first_token",
    "idle_ms_per_tick.tick", "idle_ms_per_tick.other")
SERVING = ("qwen2.5-1.5b.serve-closed32",
           "solar-open2-250b.reason-closed128",
           "laguna-s-2.1.code-closed64",
           "longcat-flash-chat.assist-closed64")


def reader(name):
    mod = load_module(os.path.join(BENCH, "layer_metrics", name + ".py"),
                      "metric_" + name.replace(".", "_"))
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        args = json.load(f).get("args", {})
    return lambda ctx: mod.read(ctx, **args)


def recorded(name):
    with gzip.open(os.path.join(BENCH, "data", name), "rt") as f:
        rec = json.load(f)
    cell = types.SimpleNamespace(config={"arch": rec["arch"]})
    ctx = dict(rec["ctx"], trace=rec["trace"], cell=cell,
               peaks=rec["peaks"], loop_ring=rec["loop_ring"],
               trace_window_s=rec["trace_window_s"])
    return rec, ctx


# ---- a ring and a trace made by hand -----------------------------------
MS = 1_000_000
OFFSET = 1_000_000_000_000          # ring clock - trace clock
STEP_MS = (20.0, 20.3, 20.1, 20.4, 20.2, 20.5)    # no two alike


def synthetic():
    """Six scheduler steps, the trace holding steps 1-4. Within a step
    that starts at t (ms; cpu us in brackets):

      housekeeping  0.01-0.03 [20]
      admit         0.1-0.3   [150]   odd steps, queue_wait_ms 100 (i+1)
      prefill_chunk 0.4-1.4   [300]   128 tokens; program 0.5-1.5
      prefill_chunk 1.5-2.0   [100]   odd steps, 8 tokens; program 1.6-1.9
      first_token   2.1-4.1   [200]   even steps
      tick_dispatch 4.3-4.8   [400]   the tick program starts at 4.6 ...
      tick_sync     5.0-(D-0.1) [500]
      (the step itself -0.003 to D+0.002, 50 us of its own)
      bookkeeping   D+0.01 to D+0.11 [80]

    ... and runs until 0.2 ms into the next step, which starts 0.2 ms
    after this one ends. So the device idles 0.2-0.5, (odd) 1.5-1.6
    and from the last chunk program's end to 4.6 of every step."""
    ring, host, modules = [], [], []

    def span(name, s_ms, e_ms, cpu_us, parent, traced, **attrs):
        s, e = round(s_ms * MS), round(e_ms * MS)
        ring.append({"seq": len(ring) + 1, "name": name,
                     "t0_ns": s + OFFSET, "t1_ns": e + OFFSET,
                     "parent": parent, "attrs": attrs,
                     "cpu_ns": cpu_us * 1000})
        if traced:
            host.append([name, s, e - s])
        return ring[-1]["seq"]

    t = 5.0
    for i, dur in enumerate(STEP_MS):
        odd, traced = i % 2 == 1, 1 <= i <= 4
        kids = [("sched.housekeeping", 0.01, 0.03, 20, {})]
        if odd:
            kids.append(("sched.admit", 0.1, 0.3, 150,
                         {"slot": i, "prompt_tokens": 200,
                          "prefix_cached": 0,
                          "queue_wait_ms": 100.0 * (i + 1)}))
        kids.append(("sched.prefill_chunk", 0.4, 1.4, 300,
                     {"slot": i, "tokens": 128}))
        if odd:
            kids.append(("sched.prefill_chunk", 1.5, 2.0, 100,
                         {"slot": i, "tokens": 8}))
        else:
            kids.append(("sched.first_token", 2.1, 4.1, 200,
                         {"slot": i, "prompt_tokens": 200, "chunks": 3}))
        kids.append(("sched.tick_dispatch", 4.3, 4.8, 400,
                     {"lanes_decoding": 3, "lanes_prefilling": 1,
                      "lanes_free": 0, "queue_depth": 1,
                      "context_sum": 900, "context_max": 400}))
        kids.append(("sched.tick_sync", 5.0, dur - 0.1, 500,
                     {"overlapped": True, "tokens": 3, "retired": 0}))
        step_seq = len(ring) + len(kids) + 1     # appended last
        for name, a, b, cpu, attrs in kids:
            span(name, t + a, t + b, cpu, step_seq, traced, **attrs)
        own = 50 + sum(k[3] for k in kids)
        assert span("sched.step", t - 0.003, t + dur + 0.002, own, 0,
                    traced, tick=i) == step_seq
        span("engine.bookkeeping", t + dur + 0.01, t + dur + 0.11, 80,
             0, traced)
        if traced:
            modules.append(["jit_slot_prefill_chunk(1)",
                            round((t + 0.5) * MS), 1 * MS])
            if odd:
                modules.append(["jit_slot_prefill_chunk(2)",
                                round((t + 1.6) * MS), round(0.3 * MS)])
        if i <= 4:      # step 0's tick program ends inside step 1
            modules.append(["jit_slot_decode_tick(3)",
                            round((t + 4.6) * MS),
                            round((dur - 4.2) * MS)])
        t += dur + 0.2
    trace = {"devices": {"/device:TPU:0": {"ops": [], "modules": modules}},
             "host": host}
    return ring, trace


def context(**over):
    ring, trace = synthetic()
    busy = _trace.busy_seconds(dict(trace))
    ctx = {"trace": trace, "loop_ring": ring, "traced_ticks": 4,
           "window_ticks": 3, "num_slots": 4, "tpot_p50_ms": 20.5,
           # the device idles 13 ms in the traced steps (below)
           "trace_window_s": busy + 13.0e-3}
    ctx.update(over)
    return ctx


def test_the_ring_is_found_in_the_trace():
    ctx = context()
    found = loopspans.traced(ctx)
    assert found["pairs"] == 4 and found["spread_ns"] == 0
    assert found["offset_ns"] == OFFSET
    assert [x["attrs"]["tick"] for x in found["records"]
            if x["name"] == "sched.step"] == [1, 2, 3, 4]
    # the measured window: the last three ticks, from their first step
    win = period.window(ctx)
    assert [x["attrs"]["tick"] for x in win
            if x["name"] == "sched.step"] == [3, 4, 5]
    assert win[0]["name"] == "sched.housekeeping"
    assert period.window(ctx) is win            # worked out once


def test_cpu_and_wait_a_tick_by_hand(capsys):
    ctx = context()
    # steps 3, 4, 5: an odd step burns 20 + 150 + 300 + 100 + 400 + 500
    # + 50 of its own + 80 of bookkeeping = 1600 us, an even one 20 +
    # 300 + 200 + 400 + 500 + 50 + 80 = 1550
    assert reader("sched_cpu_ms_per_tick")(ctx) == pytest.approx(
        (1600 + 1550 + 1600) / 3 / 1e3)
    # their wall: 20.4 + 20.2 + 20.5, 5 us a step outside the phases'
    # clock, 0.1 ms of bookkeeping each
    wall = 20.4 + 20.2 + 20.5 + 3 * 0.005 + 3 * 0.1
    assert reader("sched_wait_ms_per_tick")(ctx) == pytest.approx(
        (wall - 4.75) / 3)
    got = period.cpu_and_wait(period.window(ctx))
    # leaves: an odd step D - 2.78 ms, an even one D - 1.48
    assert got["covered"] == pytest.approx(
        (17.62 + 18.72 + 17.72) / wall)
    assert got["phases"]["sched.tick_dispatch"] == pytest.approx(
        (0.4, 0.1))
    assert got["phases"]["sched.admit"] == pytest.approx(
        (0.3 / 3, 0.1 / 3))
    assert got["phases"][period.OWN][0] == pytest.approx(0.05)
    out = capsys.readouterr().out
    assert "cpu is 7.7% of tpot_p50 20.50 ms" in out
    # the traced window beside it: steps 1-4 and the bookkeeping after
    # step 0, which the trace's range still holds:
    # ((1600 + 1550) x 2 + 80) / 4
    assert "under the profiler cpu 1.595 ms" in out
    assert "UNDER 97 %" in out and "sched.tick_sync 0.500 + " in out


def test_chunks_and_queue_waits_by_hand(capsys):
    ctx = context()
    # steps 3 and 5 send 128 + 8, step 4 sends 128
    assert reader("prefill_chunks_per_tick")(ctx) == pytest.approx(5 / 3)
    assert reader("prefill_tokens_per_chunk")(ctx) == pytest.approx(80)
    out = capsys.readouterr().out
    assert "128: 3 (60.0%), 8: 2 (40.0%)" in out
    assert "1, mean 200.0 tokens in 3.00 chunk programs" in out
    # admitted in steps 3 and 5 after 400 and 600 ms
    assert reader("queue_wait_p50_ms.saturated")(ctx) == pytest.approx(500)
    assert reader("queue_wait_p95_ms.saturated")(ctx) == pytest.approx(590)
    # the traced steps' chunk programs: 4 of 1 ms, 2 of 0.3 ms
    assert reader("chunk_device_ms_per_tick")(ctx) == pytest.approx(1.15)


def test_idle_gaps_are_split_over_the_phases_by_hand(capsys):
    """A gap that crosses phases is split, not given whole. An even
    step's long gap runs 1.5-4.6: 0.6 ms under no phase (the step's
    own), 2.0 under sched.first_token, 0.2 own again, 0.3 under
    sched.tick_dispatch. By step, in ms:

      odd   0.2-0.5  admit 0.1, own 0.1, prefill_chunk 0.1
            1.5-1.6  prefill_chunk 0.1
            1.9-4.6  prefill_chunk 0.1, own 2.3, tick 0.3
      even  0.2-0.5  own 0.2, prefill_chunk 0.1
            1.5-4.6  own 0.8, first_token 2.0, tick 0.3
    """
    ctx = context()
    want = {"admit": 0.2 / 4, "prefill_chunk": (0.3 + 0.1) * 2 / 4,
            "first_token": 4.0 / 4, "tick": 1.2 / 4,
            "other": (2.4 + 1.0) * 2 / 4}
    got = {part: reader("idle_ms_per_tick." + part)(ctx)
           for part in want}
    assert got == pytest.approx(want)
    # the five parts sum to the gaps' total: 3.1 + 3.4 ms a pair
    split = period.idle_split(ctx)
    assert sum(got.values()) == pytest.approx(split["gap_ms"])
    assert split["gap_ms"] == pytest.approx(13.0 / 4)
    assert split["by_phase"][period.OWN] == pytest.approx(6.8 / 4)
    assert "(none)" not in split["by_phase"]
    out = capsys.readouterr().out
    assert out.count("idle ms a traced tick:") == 1    # "other" prints
    assert "sum 3.250 against device_idle_share.serve x window / " \
           "ticks 3.250 (within 5 %)" in out
    # tick programs of 15.8, 16.1, 15.9, 16.2, 16.0 ms; starts 20.5,
    # 20.3, 20.6 ms apart
    assert ("period: tick 16.000 + chunk programs 1.150 + idle 3.250 = "
            "20.400 ms against the ring's tick_dispatch start-to-start "
            "median 20.500 ms over 3 steps (within 3 %); their mean "
            "20.467 ms (within 3 %)") in out
    # a trace whose window is longer than its gaps says so
    ctx = context()
    ctx["trace_window_s"] += 2e-3
    reader("idle_ms_per_tick.other")(ctx)
    assert "(NOT within 5 %)" in capsys.readouterr().out


def test_spans_under_the_traces_floor_are_kept():
    """The ring, not the trace's host plane, names the gaps: a phase
    of 8 us (the trace drops annotations under 20 us) still gets its
    share."""
    ctx = context()
    ring, trace = ctx["loop_ring"], ctx["trace"]
    step1 = next(x for x in ring if x["name"] == "sched.step"
                 and x["attrs"]["tick"] == 1)
    t = step1["t0_ns"] + 3_000 + round(0.35 * MS)   # inside 0.2-0.5
    ring.append({"seq": 999, "name": "sched.a_later_phase", "t0_ns": t,
                 "t1_ns": t + 8_000, "parent": step1["seq"],
                 "attrs": {}, "cpu_ns": 8_000})
    ring.sort(key=lambda x: x["t1_ns"])
    assert all(e[0] != "sched.a_later_phase" for e in trace["host"])
    split = period.idle_split(ctx)
    assert split["by_phase"]["sched.a_later_phase"] == pytest.approx(
        0.008 / 4)
    assert split["parts"]["other"] == pytest.approx(6.8 / 4)   # still


def test_the_result_lines_idle_gaps_name_the_phases():
    """`breakdown.idle_gaps` of a traced serving run: the leaf spans,
    each with its part of every gap in seconds of the traced window;
    without a split (a train cell, a CPU's trace, a ring without
    `cpu_ns`) each gap goes whole to one host event, as before."""
    from benchmarks.harness import result
    ctx = context()
    gaps = result.idle_gaps(ctx)
    assert [name for name, _ in gaps[:2]] == [period.OWN,
                                              "sched.first_token"]
    assert dict(gaps) == pytest.approx({
        period.OWN: 6.8e-3, "sched.first_token": 4.0e-3,
        "sched.tick_dispatch": 1.2e-3, "sched.prefill_chunk": 0.8e-3,
        "sched.admit": 0.2e-3})
    assert sum(v for _, v in gaps) == pytest.approx(13.0e-3)
    assert len(result.idle_gaps(ctx, k=2)) == 2
    whole = _trace.idle_gaps(ctx["trace"], 10)
    assert whole and {name for name, _ in whole} != set(dict(gaps))
    for over in ({"traced_ticks": None}, {"loop_ring": []}):
        assert result.idle_gaps(context(**over)) == whole
    ctx = context()
    for x in ctx["loop_ring"]:
        del x["cpu_ns"]
    assert result.idle_gaps(ctx) == whole


# ---- nothing to read ---------------------------------------------------
@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("what", ["no_trace", "no_ring", "no_cpu_ns",
                                  "no_device"])
def test_readers_return_none_on_an_empty_input(name, what):
    ctx = context()
    if what == "no_trace":
        ctx.update(trace=None, window_ticks=None, traced_ticks=None)
    elif what == "no_ring":
        ctx["loop_ring"] = []
    elif what == "no_cpu_ns":           # the parent's records
        for x in ctx["loop_ring"]:
            del x["cpu_ns"]
    else:                               # a CPU's trace, untimed window
        ctx["trace"] = dict(ctx["trace"], devices={})
        ctx["window_ticks"] = None
    assert reader(name)(ctx) is None


@pytest.mark.parametrize("name", ["loop_tiny_serve_cpu.json.gz",
                                  "loop_serve_closed32.json.gz"])
def test_the_older_recordings_give_nothing_to_read(name):
    """Their programs stamped no CPU time: the family is left out,
    those that read what the ring had then too."""
    rec, ctx = recorded(name)
    assert all("cpu_ns" not in x for x in rec["loop_ring"])
    for metric in READERS:
        assert reader(metric)(ctx) is None, metric


# ---- the run recorded on the chip --------------------------------------
def test_readers_on_the_recorded_run(capsys):
    rec, ctx = recorded("loop_period_closed32.json.gz")
    assert rec["trace"]["devices"]                  # made on the chip
    got = {m: reader(m)(ctx) for m in READERS}
    for metric, value in got.items():
        assert value == pytest.approx(
            rec["metrics"][metric]["value"], rel=1e-9), metric
    out = capsys.readouterr().out
    # work and wait make up the thread's wall time in the two spans
    wall = sum(x["t1_ns"] - x["t0_ns"] for x in period.window(ctx)
               if x["name"] in period.TOP) / 1e6 / ctx["window_ticks"]
    assert (got["sched_cpu_ms_per_tick"] + got["sched_wait_ms_per_tick"]
            == pytest.approx(wall))
    assert 0 < got["sched_cpu_ms_per_tick"] < wall
    every = period.cpu_and_wait(period.window(ctx))
    assert every["covered"] >= 0.97
    assert every["ticks"] == ctx["window_ticks"]
    # the recorded host's thread clock ticks every 10 ms: one span reads
    # 0 or a multiple of it, and only sums over many spans say anything
    for x in period.window(ctx):
        assert x["cpu_ns"] % 10_000_000 == 0
        assert 0 <= x["cpu_ns"] <= x["t1_ns"] - x["t0_ns"] + 10_000_000
    # the five parts are the gaps, and the gaps the device's idle time
    split = period.idle_split(ctx)
    assert sum(split["parts"].values()) == pytest.approx(split["gap_ms"])
    whole = ((ctx["trace_window_s"] - _trace.busy_seconds(ctx["trace"]))
             * 1e3 / ctx["traced_ticks"])
    assert split["gap_ms"] == pytest.approx(whole, rel=0.05)
    assert "(within 5 %)" in out
    assert 0 < got["prefill_tokens_per_chunk"] <= 128
    assert got["queue_wait_p50_ms.saturated"] <= got[
        "queue_wait_p95_ms.saturated"]


# ---- the family's files, and the tool that reads them -------------------
def test_the_family_is_entered_for_the_serving_cells():
    """`BENCHMARK.json` lists the family (PR 38) for the four serving
    cells in the order the benchmark lists them; more waiting and more
    tokens a chunk program are better, less of everything else. (That
    each entry agrees with its file pair and finds its reader is
    `test_benchmark_entries.py`'s, as for every entry.)"""
    assert period.METRICS == READERS
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert tuple(w["name"] for w in bench["workloads"]
                 if w["name"] in SERVING) == SERVING
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        entry = entries[name]
        # the four, in that order; a later cell may stand behind them
        assert [c for c in entry["workloads"]
                if c in SERVING] == list(SERVING)
        assert entry["moves"] == "serve_tokens_per_s"
        assert entry["better"] == ("higher" if name in (
            "sched_wait_ms_per_tick", "prefill_tokens_per_chunk")
            else "lower")
    # the metric the family supersedes is gone, file pair and entry
    assert "sched_host_ms_per_tick" not in entries
    assert not os.path.exists(os.path.join(
        BENCH, "layer_metrics", "sched_host_ms_per_tick.json"))


def test_the_report_tool_reads_the_family_from_a_traced_run(tmp_path):
    """`tools/period_report.py` on the CPU's tiny serving cell: the
    result line of a `--trace 1` run, and under `period` what the ring
    alone gives - a CPU's trace has no device plane, so the chunk
    programs' time and the idle parts have nothing to read."""
    root = tiny.make_copy(tmp_path)
    child = (
        "import sys\n"
        f"sys.path.insert(0, {root!r})\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from benchmarks.tools import period_report\n"
        "period_report.main(['--workload', 'tiny-qwen2.serve', '--seed',"
        " '2147483999', '--seconds', '1.0'], accept_platform=('cpu',),"
        " peaks_kind='TPU v5 lite')\n")
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_X64="0",
               JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""))
    p = subprocess.run([sys.executable, "-c", child], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = p.stdout.strip().splitlines()
    line = json.loads(out[-1])
    assert line["correct"] and "breakdown" in line
    got = line["period"]
    assert set(got) == {
        "sched_cpu_ms_per_tick", "sched_wait_ms_per_tick",
        "prefill_chunks_per_tick", "prefill_tokens_per_chunk",
        "queue_wait_p50_ms.saturated", "queue_wait_p95_ms.saturated"}
    assert got["sched_cpu_ms_per_tick"] == {
        "value": got["sched_cpu_ms_per_tick"]["value"], "unit": "ms"}
    assert got["sched_cpu_ms_per_tick"]["value"] > 0
    assert 0 < got["prefill_tokens_per_chunk"]["value"] <= 128
    text = "\n".join(out)
    assert "leaf spans cover" in text
    assert "period idle_ms_per_tick.other: nothing to read" in text
    assert "period chunk_device_ms_per_tick: nothing to read" in text
    # the family is entered: the run's own metrics hold the same
    assert {k: line["metrics"][k] for k in got} == got
    assert set(line["metrics"]) & set(READERS) == set(got)
