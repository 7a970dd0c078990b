"""Device time of the decode tick by the program's scopes.

The trace's op line prints HLO instruction names (`fusion.12`), not the
scope the program gave the operation (`block_1/moe/shared/up`). A driver
that wants shares by scope puts `tick_op_scopes` into the context:
{instruction name: op_name} from the compiled tick's text
(`kinds/serve_arch.py`). Here the operations that ran INSIDE a run of the
tick program (instruction names repeat from program to program) are
summed by instruction and given to the scopes a pattern names.

Control-flow instructions (`while`, `conditional`, `call`) are left out:
where the trace prints them, their extent covers the operations of their
bodies, which the trace prints too.
"""

import bisect
import re

_CONTAINER = re.compile(r"^(while|conditional|call)([.\-_]|$)")


def tick_seconds_by_op(ctx, module):
    """({instruction: seconds}, seconds of the tick programs) over the
    runs of the programs matching `module` on the first device; None
    without a device plane, without such runs or without scopes."""
    trace = ctx.get("trace")
    if trace is None or not trace["devices"] or not ctx.get(
            "tick_op_scopes"):
        return None
    key = "_tick_seconds_by_op:" + module
    if key not in ctx:
        dev = trace["devices"][sorted(trace["devices"])[0]]
        rx = re.compile(module)
        runs = sorted((s, s + d) for n, s, d in dev["modules"]
                      if rx.search(n))
        starts = [s for s, _ in runs]
        by_op = {}
        for name, s, d in dev["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if (i >= 0 and s < runs[i][1]
                    and not _CONTAINER.match(name)):
                by_op[name] = by_op.get(name, 0) + d
        total = sum(e - s for s, e in runs)
        ctx[key] = (({k: v / 1e9 for k, v in by_op.items()},
                     total / 1e9) if total else None)
    return ctx[key]


def scope_share(ctx, module, pattern):
    """Device time of the tick's operations whose scope matches
    `pattern` over the tick programs' device time, in %. An
    instruction the compiled text gave no op_name (or an empty one)
    is matched by its own name."""
    found = tick_seconds_by_op(ctx, module)
    if found is None:
        return None
    by_op, total = found
    rx, scopes = re.compile(pattern), ctx["tick_op_scopes"]
    return sum(t for op, t in by_op.items()
               if rx.search(scopes.get(op) or op)) / total * 100.0


def say_remainder(ctx, module, patterns):
    """One line: each pattern's share, the operations' share that no
    pattern covers, and the tick time in which no operation of the op
    line ran (or that the containers left out would cover)."""
    found = tick_seconds_by_op(ctx, module)
    if found is None:
        return
    by_op, total = found
    shares = {p: scope_share(ctx, module, p) for p in patterns}
    ops = sum(by_op.values()) / total * 100.0
    print("tick by scope: " + ", ".join(
        f"{p} {v:.1f} %" for p, v in shares.items())
        + f", other operations {ops - sum(shares.values()):.1f} %, "
        f"no operation {100.0 - ops:.1f} %", flush=True)
