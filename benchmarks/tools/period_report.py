"""One traced run of a serving cell, and the dispatch thread's period
read from it: the twelve readers of `harness/period.py`'s family
(`layer_metrics/<name>.json` + `.py`), whether or not `BENCHMARK.json`
lists them for the cell.

    python3 benchmarks/tools/period_report.py --workload <cell> \
        --seed <n> --seconds <s>

Runs the cell as `run.py --trace 1` does (its lines and its result line
are printed too), then each reader on the same context: a `metric`
line each, the readers' own lines (the split by phase, the chunks by
size, the five idle parts beside the device's idle share, the period
check) and, last, the result line with the numbers under `period`.
The family is entered in `BENCHMARK.json` for the four serving cells
(PR 38), so `run.py --trace 1` prints it and the driver's lines carry
it. This tool is for a run outside the driver, of a serving cell the
entries do not list (a later PR's, before it enters its cell in their
`workloads`). A benchmark run never runs this.
"""

import argparse
import json
import math
import os
import sys
import time

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def read_family(cell, ctx, say):
    """{name: {"value", "unit"}} of the family's readers that find
    something to read in `ctx`."""
    from benchmarks.harness import period
    found = {}
    for name in period.METRICS:
        value = cell.reader(name)(ctx)
        if value is None:
            say(f"period {name}: nothing to read")
            continue
        if not math.isfinite(value):
            raise SystemExit(f"period {name} read {value}")
        with open(os.path.join(cell.bench_dir, "layer_metrics",
                               name + ".json")) as f:
            unit = json.load(f)["unit"]
        say(f"metric {name} = {value:.6g} {unit}")
        found[name] = {"value": value, "unit": unit}
    return found


def main(argv=None, *, accept_platform=("tpu",), peaks_kind=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    args.trace = 1
    from benchmarks.harness import result
    from benchmarks.harness.runenv import open_cell

    cell, env = open_cell(args.workload, T_START,
                          accept_platform=accept_platform,
                          peaks_kind=peaks_kind)
    env.say(f"seed {args.seed}, {args.seconds:g} s, trace 1, then the "
            f"period's readers")
    out = cell.driver().run(cell, args, env)
    line = result.build(cell, args, env, out)
    # the context `result.build` hands a per-layer reader
    ctx = dict(out["ctx"], trace=env.trace, cell=cell, peaks=env.peaks,
               values=out["values"], trace_window_s=env.trace_window_s)
    line["period"] = read_family(cell, ctx, env.say)
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
