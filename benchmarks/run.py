"""The benchmark's command: one cell, once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints what it does on lines of their own and, as the LAST line of its
standard output, one JSON object: `correct`, `attempted`, `failed`,
`metrics`, `device` (and `breakdown` with `--trace 1`). With `--trace 0`
the metrics are the cell's end-to-end metrics, with `--trace 1` its
per-layer metrics. No TPU, fewer chips than the cell asks for, or a
device kind that `peaks.json` does not know: a non-zero exit and no
result line. See `benchmarks/README.md`.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, accept_platform=("tpu",), peaks_kind=None,
         t_start=None):
    """Run one cell; returns the result object it printed. The keyword
    arguments are for the rehearsal tests alone (`open_cell`); the
    command passes none."""
    args = parse(argv)
    from benchmarks.harness import result
    from benchmarks.harness.runenv import open_cell

    cell, env = open_cell(args.workload, t_start or T_START,
                          accept_platform=accept_platform,
                          peaks_kind=peaks_kind)
    env.say(f"seed {args.seed}, {args.seconds:g} s, trace {args.trace}")
    out = cell.driver().run(cell, args, env)
    line = result.build(cell, args, env, out)
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
