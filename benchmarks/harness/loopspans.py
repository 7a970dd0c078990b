"""The program's loop spans, for the per-layer readers that need them.

The program records its loops (`horovod_tpu.obs.spans.loop_span`:
`sched.*`, `engine.*`, `train.*`) twice: into a ring in memory, stamped
with `time.time_ns()`, and - while a profiler session runs - into the
trace's host plane as annotations of the same names. The trace is on
the device's time axis but drops what is shorter than 20 us and keeps
no attributes (`harness/trace.py`); the ring has every span and its
attributes (the tick record) but a clock of its own: an xplane's
times count from the session's start. `traced` puts the two together:
it finds the trace's `sched.step` (or `train.step`) annotations in the
ring by their durations, takes the constant between the clocks from
the matched pairs, and returns the ring's records inside the trace's
time range.

A program without the ring (the parent of the PR that brought it), a
run without a trace and a trace without annotations all give None:
the reader then returns None and its metric is left out of the line.
A test hands the ring in as `ctx["loop_ring"]`.
"""

import bisect
import statistics

ANCHORS = ("sched.step", "train.step")
PROGRAM_PREFIXES = ("sched.", "engine.", "train.")


def ring(ctx):
    """The loop ring, oldest first: [{"name", "t0_ns", "t1_ns",
    "attrs", ...}], or [] where the program has none. Read from the
    program once a run and kept in the context."""
    if "loop_ring" not in ctx:
        try:
            from horovod_tpu.obs import spans
        except ImportError:
            spans = None
        tail = getattr(spans, "loop_tail", None)
        ctx["loop_ring"] = tail() if tail is not None else []
    return ctx["loop_ring"]


def is_program_span(name):
    return name.startswith(PROGRAM_PREFIXES)


def trace_range(trace):
    """(first start, last end) over everything the trace holds, on the
    trace's clock; None for an empty trace."""
    lo = hi = None
    events = list(trace["host"])
    for dev in trace["devices"].values():
        events += dev["ops"][:1] + dev["ops"][-1:] + dev["modules"]
    for _, s, d in events:
        lo = s if lo is None else min(lo, s)
        hi = s + d if hi is None else max(hi, s + d)
    return None if lo is None else (lo, hi)


def clock_offset(trace, records, max_mean_diff_ns=100_000):
    """ring clock - trace clock, in ns, from the anchor spans both
    hold: the trace's anchors are a run of consecutive anchors of the
    ring, found where durations and start-to-start distances agree
    best. None without anchors or where no run agrees to
    `max_mean_diff_ns` a span. Also returns
    how many pairs it rests on and their spread: (offset, n, spread)."""
    for anchor in ANCHORS:
        t = sorted((s, d) for n, s, d in trace["host"] if n == anchor)
        r = sorted((x["t0_ns"], x["t1_ns"] - x["t0_ns"])
                   for x in records if x["name"] == anchor)
        if not t or len(r) < len(t):
            continue
        best = None
        for k in range(len(r) - len(t) + 1):
            # durations, and (steady loops have steps all alike) the
            # distances from one start to the next
            cost = sum(abs(r[k + i][1] - t[i][1]) for i in range(len(t)))
            cost += sum(abs((r[k + i + 1][0] - r[k + i][0])
                            - (t[i + 1][0] - t[i][0]))
                        for i in range(len(t) - 1))
            if best is None or cost < best[0]:
                best = (cost, k)
        cost, k = best
        if cost / len(t) > max_mean_diff_ns:
            continue
        diffs = [r[k + i][0] - t[i][0] for i in range(len(t))]
        return (int(statistics.median(diffs)), len(diffs),
                max(diffs) - min(diffs))
    return None


def traced(ctx):
    """The ring's records that lie inside the trace's time range, with
    the constant between the clocks: {"records", "offset_ns", "pairs",
    "spread_ns", "range"} (`range` on the ring's clock), or None."""
    trace = ctx.get("trace")
    if trace is None:
        return None
    if "_loop_traced" not in ctx:       # five readers, one matching
        records = ring(ctx)
        found = clock_offset(trace, records) if records else None
        span = trace_range(trace)
        if found is None or span is None:
            ctx["_loop_traced"] = None
        else:
            off, n, spread = found
            lo, hi = span[0] + off, span[1] + off
            ctx["_loop_traced"] = {
                "records": [x for x in records
                            if x["t0_ns"] >= lo and x["t1_ns"] <= hi],
                "offset_ns": off, "pairs": n, "spread_ns": spread,
                "range": (lo, hi)}
    return ctx["_loop_traced"]


def total_ms(records, name):
    return sum(x["t1_ns"] - x["t0_ns"] for x in records
               if x["name"] == name) / 1e6


def window_ticks(ctx):
    """The tick records (attrs of `sched.tick_dispatch`) of the
    measured window: the last `ctx["window_ticks"]` of the ring that
    had a decoding lane. None without them."""
    n = ctx.get("window_ticks")
    if not n:
        return None
    ticks = [x["attrs"] for x in ring(ctx)
             if x["name"] == "sched.tick_dispatch"
             and x["attrs"].get("lanes_decoding", 0) > 0]
    return ticks[-n:] or None


def lane_share(ctx, key):
    """Mean of one lane count of the tick record over the measured
    window's ticks, over the lanes: %."""
    ticks, slots = window_ticks(ctx), ctx.get("num_slots")
    if not ticks or not slots:
        return None
    return sum(t[key] for t in ticks) / len(ticks) / slots * 100.0


def gap_phases(trace, min_gap_ns=500_000):
    """The first device's idle gaps of at least `min_gap_ns` between
    the trace's first and last program span, by the program span (an
    annotation in the trace's host plane) that covers them. Returns {"gap_s", "covered_s", "by_phase": {span: s},
    "by_event": {event: {span: s}}}. A gap's time is split over the
    phase spans that overlap it; `sched.step` takes what its phases
    leave ("sched.step (own)"), "(none)" what no program span covers.
    `by_event` gives each gap, whole, to the other host event that
    overlaps it most, as `trace.idle_gaps` does, split the same way:
    in which phase each of PJRT's names falls."""
    from benchmarks.harness import trace as _trace
    names = sorted(trace["devices"])
    if not names:
        return None
    dev = trace["devices"][names[0]]
    busy = _trace.merge([(s, s + d) for _, s, d in dev["ops"]]
                        + [(s, s + d) for _, s, d in dev["modules"]])
    spans = _Overlaps((s, s + d, n) for n, s, d in trace["host"]
                      if is_program_span(n))
    if not spans.items:
        return None
    # A span that was open when the session started, or still open
    # when it stopped, leaves no annotation: only what lies between
    # the first span's start and the last one's end can be judged.
    lo = spans.starts[0]
    hi = max(e for _, e, _ in spans.items)
    gaps = [(max(a[1], lo), min(b[0], hi)) for a, b in zip(busy, busy[1:])]
    gaps = [(s, e) for s, e in gaps if e - s >= min_gap_ns]
    others = _Overlaps((s, s + d, n) for n, s, d in trace["host"]
                       if not is_program_span(n))
    out = {"gap_s": 0.0, "covered_s": 0.0, "by_phase": {},
           "by_event": {}}
    for gs, ge in gaps:
        split, cover = {}, []
        for s, e, n in spans.over(gs, ge):
            split[n] = split.get(n, 0) + min(ge, e) - max(gs, s)
            cover.append((max(gs, s), min(ge, e)))
        covered = sum(e - s for s, e in _trace.merge(cover))
        if "sched.step" in split:       # its phases lie inside it
            own = split.pop("sched.step") - sum(
                v for k, v in split.items() if k.startswith("sched."))
            split["sched.step (own)"] = max(0, own)
        split["(none)"] = (ge - gs) - covered
        best, best_name, best_len = 0, "(no host event)", 0
        for s, e, n in others.over(gs, ge):
            ov = min(ge, e) - max(gs, s)
            if ov > best or (ov == best and e - s < best_len):
                best, best_name, best_len = ov, n, e - s
        out["gap_s"] += (ge - gs) / 1e9
        out["covered_s"] += covered / 1e9
        per = out["by_event"].setdefault(best_name, {})
        for k, v in split.items():
            if v > 0:
                out["by_phase"][k] = out["by_phase"].get(k, 0.0) + v / 1e9
                per[k] = per.get(k, 0.0) + v / 1e9
    return out


class _Overlaps:
    """Intervals (start, end, name), asked many times which of them
    overlap a window."""

    def __init__(self, intervals):
        self.items = sorted(intervals)
        self.starts = [x[0] for x in self.items]
        self.longest = max((e - s for s, e, _ in self.items), default=0)

    def over(self, lo, hi):
        a = bisect.bisect_left(self.starts, lo - self.longest)
        b = bisect.bisect_left(self.starts, hi)
        return [x for x in self.items[a:b] if x[1] > lo]
