"""The dispatch thread's period, from the program's loop ring: what the
thread worked and what it waited a decode tick, the chunk programs and
the queue waits of the measured window, and the device's idle gaps of
the traced window split over the loop's phases.

Every reader under `layer_metrics/` that reads one of these calls in
here, so that the window, the leaf spans and the split are worked out
in one place. All of it rests on records that carry `cpu_ns` - the
thread's own CPU time inside a span, beside its wall time
(`horovod_tpu.obs.spans.loop_span`, PR 36). A program whose ring has
none (the parent of that PR, the older recordings under
`benchmarks/data/`) gives every reader here nothing to read: the
metrics of the period are one family, reported together or not at all.
`BENCHMARK.json` lists the family for the serving cells (PR 38), and
`harness/result.py` names the traced run's idle gaps by `idle_split`;
`tools/period_report.py` reads the family for a cell it does not list.

The measured window is found as `loopspans.window_ticks` finds it: the
last `ctx["window_ticks"]` decode ticks of the ring, from the start of
the scheduler step that dispatched the first of them. The traced
window's records are `loopspans.traced`'s: the ring put on the trace's
clock, so spans shorter than the trace's 20 us floor and every
attribute are kept.
"""

import collections
import statistics

from benchmarks.harness import loopspans
from benchmarks.harness import trace as _trace
from benchmarks.harness import traffic

TOP = ("sched.step", "engine.bookkeeping")
OWN = "sched.step (own)"
# the five parts the idle gaps are reported in, by leaf span; what is
# not named here (housekeeping, bookkeeping, the wait for a request,
# the step's own and what no span covers) is other
IDLE_PARTS = {
    "sched.admit": "admit",
    "sched.prefill_chunk": "prefill_chunk",
    "sched.first_token": "first_token",
    "sched.tick_dispatch": "tick",
    "sched.tick_sync": "tick",
    "sched.spec_round": "tick",
}
PARTS = ("admit", "prefill_chunk", "first_token", "tick", "other")
# the family's file pairs under `layer_metrics/`, in the order they are
# read (the last one prints the checks)
METRICS = (
    "sched_cpu_ms_per_tick", "sched_wait_ms_per_tick",
    "prefill_chunks_per_tick", "prefill_tokens_per_chunk",
    "chunk_device_ms_per_tick", "queue_wait_p50_ms.saturated",
    "queue_wait_p95_ms.saturated") + tuple(
        "idle_ms_per_tick." + part for part in PARTS)


def say(text):
    print(text, flush=True)


def _with_cpu(records):
    """The records, or None where the program stamps no CPU time."""
    steps = [x for x in records if x["name"] == "sched.step"]
    if not steps or any("cpu_ns" not in x for x in steps):
        return None
    return records


def window(ctx):
    """The measured window's records, oldest first, or None."""
    if "_period_window" not in ctx:
        ctx["_period_window"] = _window(ctx)
    return ctx["_period_window"]


def _window(ctx):
    n = ctx.get("window_ticks")
    ring = _with_cpu(loopspans.ring(ctx)) if n else None
    if not ring:
        return None
    ticks = [x for x in ring if is_tick(x)][-n:]
    if not ticks:
        return None
    steps = {x["seq"]: x for x in ring if x["name"] == "sched.step"}
    first = steps.get(ticks[0]["parent"], ticks[0])
    return [x for x in ring if x["t0_ns"] >= first["t0_ns"]]


def traced(ctx):
    """The traced window's records (ring clock), or None."""
    found = loopspans.traced(ctx) if ctx.get("traced_ticks") else None
    return None if found is None else _with_cpu(found["records"])


def is_tick(x):
    return (x["name"] == "sched.tick_dispatch"
            and x["attrs"].get("lanes_decoding", 0) > 0)


# ---- the thread's own work and its waits ------------------------------
def cpu_and_wait(records):
    """Over `records`: {"ticks", "cpu_ms", "wait_ms" (both a tick, of
    sched.step + engine.bookkeeping), "covered" (the leaf spans' wall
    over those two spans' wall), "phases": {leaf: (cpu ms, wait ms) a
    tick}}; None without a tick. The leaves are the children of a
    step and `engine.bookkeeping`; what a step's children leave of it
    is "sched.step (own)"."""
    ticks = sum(is_tick(x) for x in records)
    if not ticks:
        return None
    steps = {x["seq"] for x in records if x["name"] == "sched.step"}
    wall, cpu = collections.Counter(), collections.Counter()
    for x in records:
        name = x["name"]
        if name in TOP or x["parent"] in steps:
            wall[name] += x["t1_ns"] - x["t0_ns"]
            cpu[name] += x["cpu_ns"]
    leaves = [k for k in wall if k != "sched.step"]
    in_step = [k for k in leaves if k != "engine.bookkeeping"]
    wall[OWN] = wall["sched.step"] - sum(wall[k] for k in in_step)
    cpu[OWN] = cpu["sched.step"] - sum(cpu[k] for k in in_step)
    top_wall = sum(wall[k] for k in TOP)
    top_cpu = sum(cpu[k] for k in TOP)
    per = 1e6 * ticks
    return {
        "ticks": ticks, "cpu_ms": top_cpu / per,
        "wait_ms": (top_wall - top_cpu) / per,
        "covered": (sum(wall[k] for k in leaves) / top_wall
                    if top_wall else 0.0),
        "phases": {k: (cpu[k] / per, (wall[k] - cpu[k]) / per)
                   for k in leaves + [OWN]}}


def thread_ms_per_tick(ctx, what):
    """`what` = "cpu" | "wait": the dispatch thread's own CPU time, or
    its wall time less that, inside sched.step + engine.bookkeeping of
    the measured window, per decode tick dispatched. The "cpu" call
    prints the measured and the traced window side by side and the
    thread's utilisation; the "wait" call the split by leaf phase and
    how much of the two spans their leaves cover."""
    rec = window(ctx)
    got = cpu_and_wait(rec) if rec else None
    if got is None:
        return None
    if what == "cpu":
        line = (f"dispatch thread: cpu {got['cpu_ms']:.3f} ms + wait "
                f"{got['wait_ms']:.3f} ms a tick over {got['ticks']} "
                f"ticks of the measured window")
        tpot = ctx.get("tpot_p50_ms")
        if tpot:
            line += (f"; cpu is {got['cpu_ms'] / tpot:.1%} of "
                     f"tpot_p50 {tpot:.2f} ms")
        under = traced(ctx)
        under = cpu_and_wait(under) if under else None
        if under is not None:
            line += (f"; under the profiler cpu {under['cpu_ms']:.3f} "
                     f"ms + wait {under['wait_ms']:.3f} ms over "
                     f"{under['ticks']} ticks")
        say(line)
        return got["cpu_ms"]
    say("dispatch thread by phase, cpu + wait ms a tick: " + ", ".join(
        f"{k} {c:.3f} + {w:.3f}" for k, (c, w) in sorted(
            got["phases"].items(), key=lambda kv: -sum(kv[1]))))
    say(f"leaf spans cover {got['covered']:.2%} of sched.step + "
        f"engine.bookkeeping ("
        f"{'at least' if got['covered'] >= 0.97 else 'UNDER'} 97 %)")
    return got["wait_ms"]


# ---- chunk programs and queue waits of the measured window ------------
def chunks(ctx):
    """(chunk records, decode ticks) of the measured window."""
    rec = window(ctx)
    if not rec:
        return None
    ticks = sum(is_tick(x) for x in rec)
    found = [x for x in rec if x["name"] == "sched.prefill_chunk"
             and "tokens" in x["attrs"]]
    return (found, ticks) if ticks else None


def prefill_chunks_per_tick(ctx):
    got = chunks(ctx)
    return None if got is None else len(got[0]) / got[1]


def prefill_tokens_per_chunk(ctx):
    """Mean prompt tokens a chunk program; prints the chunks by size
    and, from the `sched.first_token` records, the chunks a prompt."""
    got = chunks(ctx)
    if got is None or not got[0]:
        return None
    sizes = collections.Counter(x["attrs"]["tokens"] for x in got[0])
    total = sum(k * v for k, v in sizes.items())
    say(f"prefill chunks by tokens (count, share of chunks): "
        + ", ".join(f"{k}: {v} ({v / len(got[0]):.1%})"
                    for k, v in sorted(sizes.items(), reverse=True)))
    firsts = [x["attrs"] for x in window(ctx)
              if x["name"] == "sched.first_token"
              and "chunks" in x["attrs"]]
    if firsts:
        say(f"prompts finished prefilling: {len(firsts)}, mean "
            f"{sum(a['prompt_tokens'] for a in firsts) / len(firsts):.1f}"
            f" tokens in "
            f"{sum(a['chunks'] for a in firsts) / len(firsts):.2f} "
            f"chunk programs each")
    return total / len(got[0])


def queue_wait_ms(ctx, percentile):
    """A percentile of `queue_wait_ms` over the measured window's
    `sched.admit` records: submit to the lane's reservation."""
    rec = window(ctx)
    waits = [x["attrs"]["queue_wait_ms"] for x in rec or ()
             if x["name"] == "sched.admit"
             and "queue_wait_ms" in x["attrs"]]
    if not waits:
        return None
    return traffic.percentile(waits, percentile)


# ---- the traced window: chunk programs and idle gaps a tick -----------
def chunk_device_ms_per_tick(ctx, module):
    n = ctx.get("traced_ticks")
    if not n or traced(ctx) is None:
        return None
    times = _trace.module_times(ctx["trace"], module)
    return sum(times) * 1e3 / n if times else None


def idle_split(ctx):
    """The first device's idle gaps of 2 us and more in the traced
    window, split over the leaf spans that overlap them (the ring's
    records on the trace's clock, `loopspans.gap_phases`' arithmetic):
    {"parts": {part: ms a traced tick}, "by_phase": {span: ms a
    tick}, "gap_ms", "ticks"}; None without device, ring or ticks."""
    if "_period_idle" not in ctx:
        ctx["_period_idle"] = _idle_split(ctx)
    return ctx["_period_idle"]


def _idle_split(ctx):
    rec, n = traced(ctx), ctx.get("traced_ticks")
    if not rec or not ctx["trace"]["devices"]:
        return None
    off = loopspans.traced(ctx)["offset_ns"]
    on_trace = dict(ctx["trace"], host=[
        [x["name"], x["t0_ns"] - off, x["t1_ns"] - x["t0_ns"]]
        for x in rec])
    gaps = loopspans.gap_phases(on_trace, min_gap_ns=2_000)
    if gaps is None:
        return None
    parts = dict.fromkeys(PARTS, 0.0)
    for name, seconds in gaps["by_phase"].items():
        parts[IDLE_PARTS.get(name, "other")] += seconds * 1e3 / n
    return {"parts": parts, "ticks": n,
            "gap_ms": gaps["gap_s"] * 1e3 / n,
            "by_phase": {k: v * 1e3 / n
                         for k, v in gaps["by_phase"].items()}}


def idle_ms_per_tick(ctx, part, tick_module=None, chunk_module=None):
    """One of the five parts. The call that is given the programs'
    names (the last part, "other") prints all five, their sum beside
    what `device_idle_share.serve` counts, and the period: a tick
    program + the chunk programs + the idle a tick against the ring's
    start-to-start of `sched.tick_dispatch` in the traced window."""
    got = idle_split(ctx)
    if got is None:
        return None
    if tick_module is not None:
        _say_period(ctx, got, tick_module, chunk_module)
    return got["parts"][part]


def _say_period(ctx, got, tick_module, chunk_module):
    n, total = got["ticks"], sum(got["parts"].values())
    say("idle ms a traced tick by span: " + ", ".join(
        f"{k} {v:.3f}" for k, v in sorted(
            got["by_phase"].items(), key=lambda kv: -kv[1])))
    line = ("idle ms a traced tick: " + ", ".join(
        f"{k} {got['parts'][k]:.3f}" for k in PARTS)
        + f"; sum {total:.3f}")
    window_s = ctx.get("trace_window_s")
    if window_s:
        whole = (window_s - _trace.busy_seconds(ctx["trace"])) * 1e3 / n
        line += (f" against device_idle_share.serve x window / ticks "
                 f"{whole:.3f} ("
                 f"{_within(total, whole, 0.05)} 5 %)")
    say(line)
    ticks = _trace.module_times(ctx["trace"], tick_module)
    starts = sorted(x["t0_ns"] for x in traced(ctx) if is_tick(x))
    if not ticks or len(starts) < 3:
        return
    tick_ms = sum(ticks) * 1e3 / len(ticks)
    chunk_ms = sum(_trace.module_times(ctx["trace"],
                                       chunk_module)) * 1e3 / n
    steps = [(b - a) / 1e6 for a, b in zip(starts, starts[1:])]
    period, mean = statistics.median(steps), sum(steps) / len(steps)
    made = tick_ms + chunk_ms + total
    say(f"period: tick {tick_ms:.3f} + chunk programs {chunk_ms:.3f} "
        f"+ idle {total:.3f} = {made:.3f} ms against the ring's "
        f"tick_dispatch start-to-start median {period:.3f} ms over "
        f"{len(steps)} steps ({_within(made, period, 0.03)} 3 %); "
        f"their mean {mean:.3f} ms ({_within(made, mean, 0.03)} 3 %)")


def _within(a, b, rel):
    return "within" if abs(a - b) <= rel * abs(b) else "NOT within"
