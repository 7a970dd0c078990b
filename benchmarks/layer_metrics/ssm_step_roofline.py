"""The state-space state step's share of its roofline: the least time of
one call (the architecture module's `ssm_step_least_seconds` at the mean
of `lanes_decoding` over the traced ticks' `sched.tick_dispatch` records
- the decoding lanes' state read and written over the HBM peak) over the
mean device time of the kernel's events INSIDE the tick programs' runs,
by the name the trace prints. (A prompt chunk of one token takes the
call too, over its one lane, in a few microseconds: counted in, those
calls would pull the mean down and the share up.) Means on both sides,
so a tick cut by the trace's edge moves nothing. A program without such
a kernel - the parent, or a tick that steps the state as XLA compiles
it - gives nothing to read."""

import bisect
import re

from benchmarks.harness import loopspans


def calls_inside(trace, op, module):
    """Device durations (ns) of the operations matching `op` that start
    inside a run of a program matching `module`, on the first device."""
    if not trace["devices"]:
        return []
    dev = trace["devices"][sorted(trace["devices"])[0]]
    rx_op, rx_mod = re.compile(op), re.compile(module)
    runs = sorted((s, s + d) for n, s, d in dev["modules"]
                  if rx_mod.search(n))
    starts = [s for s, _ in runs]
    hits = []
    for name, s, d in dev["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1] and rx_op.search(name):
            hits.append(d)
    return hits


def read(ctx, op, module):
    if ctx.get("trace") is None or not ctx["trace"]["devices"]:
        return None
    hits = calls_inside(ctx["trace"], op, module)
    found = loopspans.traced(ctx)
    arch_mod = ctx.get("arch_module")
    if not hits or found is None or not hasattr(
            arch_mod, "ssm_step_least_seconds"):
        return None
    lanes = [x["attrs"]["lanes_decoding"] for x in found["records"]
             if x["name"] == "sched.tick_dispatch"
             and x["attrs"].get("lanes_decoding", 0) > 0]
    if not lanes:
        return None
    asked = {"lanes_decoding": sum(lanes) / len(lanes)}
    least, bound = arch_mod.ssm_step_least_seconds(
        ctx["cell"].config["arch"], ctx["peaks"], **asked)
    mean = sum(hits) / len(hits) / 1e9
    print(f"state step kernel: {len(hits)} calls inside the tick "
          f"programs, mean {mean * 1e6:.1f} us; a call at the mean tick "
          f"{asked} takes at least {least * 1e6:.1f} us, bound by "
          f"{bound}", flush=True)
    return least / mean * 100.0
