"""The delta-rule state step's share of its roofline: the least time of
one call (the architecture module's `kda_step_least_seconds` at the mean
of `lanes_decoding` over the traced ticks' `sched.tick_dispatch` records
- the decoding lanes' float32 state read and written over the HBM peak)
over the mean device time of the kernel's events INSIDE the tick
programs' runs, by the name the trace prints - `ssm_step_roofline`'s
reduction (`calls_inside`) over the sibling kernel. Means on both sides,
so a tick cut by the trace's edge moves nothing. A program without such
a kernel - a tick that steps the state as XLA compiles it - gives
nothing to read."""

import os

from benchmarks.harness import cells, loopspans

_ssm = cells.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "ssm_step_roofline.py"),
    "benchmarks_metric_ssm_step_roofline")


def read(ctx, op, module):
    if ctx.get("trace") is None or not ctx["trace"]["devices"]:
        return None
    hits = _ssm.calls_inside(ctx["trace"], op, module)
    found = loopspans.traced(ctx)
    arch_mod = ctx.get("arch_module")
    if not hits or found is None or not hasattr(
            arch_mod, "kda_step_least_seconds"):
        return None
    lanes = [x["attrs"]["lanes_decoding"] for x in found["records"]
             if x["name"] == "sched.tick_dispatch"
             and x["attrs"].get("lanes_decoding", 0) > 0]
    if not lanes:
        return None
    asked = {"lanes_decoding": sum(lanes) / len(lanes)}
    least, bound = arch_mod.kda_step_least_seconds(
        ctx["cell"].config["arch"], ctx["peaks"], **asked)
    mean = sum(hits) / len(hits) / 1e9
    print(f"kda step kernel: {len(hits)} calls inside the tick "
          f"programs, mean {mean * 1e6:.1f} us; a call at the mean tick "
          f"{asked} takes at least {least * 1e6:.1f} us, bound by "
          f"{bound}", flush=True)
    return least / mean * 100.0
