"""Readings for the limits of `correct`: on each seed, the numbers a
sound run of the program gives and the numbers the control gives (the
plain reference computed one precision lower, put in the program's
place), both against the reference, in ONE process on the chip.

    python3 benchmarks/tools/readings.py --workload <cell> --seeds 1,2,3 [--seconds 12]

Prints one JSON line per seed and, at the end, per number the largest
sound reading, the smallest control reading and their ratio. A limit
goes above the first and below the second, with room on both sides
(`benchmarks/limits/<cell>.json`); where the ratio is under three, no
limit will hold. A benchmark run never runs this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None, *, accept_platform=("tpu",), peaks_kind=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--controls-only", action="store_true",
                    help="a train cell's controls alone, on one chip")
    args = ap.parse_args(argv)
    from benchmarks.harness.runenv import open_cell

    seeds = [int(x) for x in args.seeds.split(",")]
    seen = {}                   # who -> number -> readings
    for seed in seeds:
        cell, env = open_cell(
            args.workload, time.time(),
            chips=1 if args.controls_only else None,
            accept_platform=accept_platform, peaks_kind=peaks_kind)
        got = cell.driver().readings(cell, seed, args.seconds, env,
                                     program=not args.controls_only)
        print(json.dumps({"seed": seed, **got}), flush=True)
        for who, nums in got.items():
            for k, v in nums.items():
                seen.setdefault(who, {}).setdefault(
                    k.split(".")[0], []).append(v)
    controls = sorted(w for w in seen if w != "program")
    for k in seen[controls[0]]:
        hi = max(seen["program"][k]) if "program" in seen else None
        for who in controls:
            lo = min(seen[who][k])
            print(f"{k}: largest sound "
                  f"{'not read' if hi is None else format(hi, '.6g')}, "
                  f"smallest {who} {lo:.6g}"
                  + (f", ratio {lo / hi:.2f}" if hi else "")
                  + f"; limit now {cell.limits.get(k)}", flush=True)


if __name__ == "__main__":
    main()
