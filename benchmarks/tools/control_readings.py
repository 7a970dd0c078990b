"""`readings.py`'s loop with the controls named on the command line, for
a cell whose architecture module knows more controls than its kind reads
by default (`kinds/serve_arch.py` reads "int8" and "fp8";
`arch/granite_hybrid.py` also knows the state's: "state_bf16",
"state_lost"):

    python3 benchmarks/tools/control_readings.py --workload <cell> \\
        --seeds 1,2,3 --controls int8,fp8,state_bf16,state_lost [--seconds 20]

On each seed ONE sound run of the program; then the kind's own
`readings` compares the served tokens, and each control's tokens at the
same positions, with the reference (`gaps_against_reference`, `numbers`:
the numbers of `correct`). One JSON line a seed: the numbers of each
and, under `within_limits`, what `correct`'s comparison says of them at
the cell's limits as they stand (a control has to come out false). With
`--out` the lines are appended to that file too. `--controls ""` reads
the program alone. A benchmark run never runs this.
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
    ap.add_argument("--controls", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from benchmarks.harness.runenv import open_cell

    for seed in (int(x) for x in args.seeds.split(",")):
        cell, env = open_cell(args.workload, time.time(),
                              accept_platform=accept_platform,
                              peaks_kind=peaks_kind)
        driver = cell.driver()
        driver.CONTROLS = tuple(c for c in args.controls.split(",") if c)
        got = driver.readings(cell, seed, args.seconds, env)
        within = {who: all(v <= cell.limits[k] for k, v in nums.items()
                           if k in cell.limits)
                  for who, nums in got.items()}
        line = json.dumps({"seed": seed, **got, "within_limits": within})
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
