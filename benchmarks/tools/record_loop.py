"""One traced run of a cell, kept: what the readers of the program's
loop spans were handed, cut to what they read, as a file a test can
replay - and the run's idle gaps by the program span that covers them.

    python3 benchmarks/tools/record_loop.py --workload <cell> --seed <n> \
        --seconds <s> --out chiprun_out/<name>.json.gz

Runs the cell as `run.py --trace 1` does (the result line is printed
too), then writes `--out`: the numbers of the run's context, the
trace's host events that are program spans (and the longest others),
the first device's program runs and busy intervals, the loop ring's
records from the traced window on, and `gap_phases`. The files under
`benchmarks/data/` named `loop_*.json.gz` were made by this tool: on
the chip from the benchmark's cells, and (`loop_tiny_*_cpu`) on the CPU
from the rehearsal cells of `tests/benchmark/tiny`. A benchmark run
never runs this.
"""

import argparse
import gzip
import json
import os
import sys
import time

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def cut(trace, keep_other=4000):
    """The trace, cut to what the loop-span readers and `gap_phases`
    read: program spans, the `keep_other` longest other host events,
    the first device's program runs and its busy intervals (as one
    pseudo-operation each)."""
    from benchmarks.harness import loopspans
    from benchmarks.harness import trace as _trace
    spans = [e for e in trace["host"] if loopspans.is_program_span(e[0])]
    other = sorted((e for e in trace["host"]
                    if not loopspans.is_program_span(e[0])),
                   key=lambda e: -e[2])[:keep_other]
    devices = {}
    for name in sorted(trace["devices"])[:1]:
        dev = trace["devices"][name]
        busy = _trace.merge([(s, s + d) for _, s, d in dev["ops"]]
                            + [(s, s + d) for _, s, d in dev["modules"]])
        devices[name] = {"ops": [["busy", s, e - s] for s, e in busy],
                         "modules": dev["modules"]}
    return {"devices": devices, "host": sorted(spans + other,
                                               key=lambda e: e[1])}


def main(argv=None, *, accept_platform=("tpu",), peaks_kind=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    args.trace = 1
    from benchmarks.harness import loopspans, result
    from benchmarks.harness.runenv import open_cell

    cell, env = open_cell(args.workload, T_START,
                          accept_platform=accept_platform,
                          peaks_kind=peaks_kind)
    out = cell.driver().run(cell, args, env)
    line = result.build(cell, args, env, out)
    ctx = dict(out["ctx"], trace=env.trace)
    found = loopspans.traced(ctx)
    ring = loopspans.ring(ctx)
    if found is not None:       # the traced window on: what is read
        ring = [x for x in ring if x["t0_ns"] >= found["range"][0]]
    gaps = loopspans.gap_phases(env.trace)
    # every gap the breakdown's idle_gaps counts (2 us and more)
    all_gaps = loopspans.gap_phases(env.trace, min_gap_ns=2_000)
    rec = {
        "note": f"{args.workload} seed {args.seed}, {args.seconds:g} s, "
                f"recorded by benchmarks/tools/record_loop.py on "
                f"{line['device']['kind']}",
        "ctx": {k: v for k, v in out["ctx"].items()
                if isinstance(v, (int, float)) or v is None},
        "arch": cell.config["arch"], "peaks": env.peaks,
        "trace": cut(env.trace), "trace_window_s": env.trace_window_s,
        "loop_ring": ring,
        "clock": None if found is None else {
            k: found[k] for k in ("offset_ns", "pairs", "spread_ns")},
        "gap_phases": gaps, "gap_phases_2us": all_gaps,
        "metrics": line["metrics"]}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(rec, f)
    env.say(f"recorded {args.out}: {len(ring)} ring records, clock "
            f"{rec['clock']}")
    for what, g in (("0.5 ms", gaps), ("2 us", all_gaps)):
        if not g:
            continue
        env.say(f"idle gaps >= {what}: {g['gap_s'] * 1e3:.2f} ms, "
                f"{g['covered_s'] / max(g['gap_s'], 1e-12):.1%} "
                f"under a program span; by phase: "
                + ", ".join(f"{k} {v * 1e3:.2f} ms" for k, v in sorted(
                    g["by_phase"].items(), key=lambda kv: -kv[1])))
        for ev, per in sorted(g["by_event"].items(),
                              key=lambda kv: -sum(kv[1].values()))[:14]:
            env.say(f"  gap event {ev}: " + ", ".join(
                f"{k} {v * 1e3:.2f} ms" for k, v in sorted(
                    per.items(), key=lambda kv: -kv[1])))
    print(json.dumps(line), flush=True)
    return rec


if __name__ == "__main__":
    main()
