"""The result line: `correct` from the rows compared, the metrics of
the cell by name, the device, and the traced run's breakdown."""

import math

from benchmarks.harness import period
from benchmarks.harness import trace as _trace


def idle_gaps(ctx, k=10):
    """[[name, seconds of the traced window], ...], the `k` largest.
    Where the program's loop ring splits the gaps over its leaf spans
    (a serving cell: `period.idle_split`) the names are those spans,
    each with its own part of every gap; elsewhere each gap goes whole
    to the host event that overlaps it most (`trace.idle_gaps`)."""
    split = period.idle_split(ctx)
    if split is None:
        return _trace.idle_gaps(ctx["trace"], k)
    best = sorted(split["by_phase"].items(), key=lambda kv: -kv[1])[:k]
    return [[name, ms * split["ticks"] / 1e3] for name, ms in best]


def build(cell, args, env, out):
    ok = True
    for what, value, limit, passed in out["rows"]:
        env.say(f"correct: {what} {value:.6g} (limit {limit:g}) "
                f"{'ok' if passed else 'FAILED'}")
        ok = ok and bool(passed)
    device = dict(out["device"])
    line = {"correct": ok, "attempted": int(out["attempted"]),
            "failed": int(out["failed"])}
    metrics = {}
    if not args.trace:
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": out["values"][m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = dict(out["ctx"], trace=env.trace, cell=cell,
                   peaks=env.peaks, values=out["values"],
                   trace_window_s=env.trace_window_s)
        for m in cell.per_layer():
            value = cell.reader(m["name"])(ctx)
            if value is None:
                env.say(f"per-layer {m['name']}: nothing to read")
                continue
            if not math.isfinite(value):
                raise SystemExit(f"per-layer {m['name']} read {value}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = _trace.busy_seconds(env.trace)
        device["window_s"] = env.trace_window_s
        line["breakdown"] = {
            "device_ops": _trace.top_ops(env.trace, 10),
            "idle_gaps": idle_gaps(ctx)}
    for name, m in metrics.items():
        env.say(f"metric {name} = {m['value']:.6g} {m['unit']}")
    line["metrics"] = metrics
    line["device"] = device
    return line
