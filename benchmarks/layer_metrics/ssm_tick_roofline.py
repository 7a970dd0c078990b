"""A state-space hybrid's decode tick against its roofline: as
`hybrid_tick_roofline`, with the required bytes and flops of the
configuration's architecture module (`tick_least_seconds`: every weight
once, the decoding lanes' state and tails, K/V) - a dense model, so what
a tick was asked to do is on one record of the scheduler's loop,
`sched.tick_dispatch` (lanes_decoding, context_sum). The least time is
taken at the MEANS of the records (it is linear in each of them but for
the choice of the bound), so a tick cut by the trace's edge moves
nothing."""

from benchmarks.harness import loopspans, trace

ASKED = ("lanes_decoding", "context_sum")


def read(ctx, module):
    if ctx.get("trace") is None or not ctx["trace"]["devices"]:
        return None
    found = loopspans.traced(ctx)
    arch_mod = ctx.get("arch_module")
    # a state-space module's `tick_least_seconds` takes these two alone
    if found is None or not hasattr(arch_mod, "ssm_step_least_seconds"):
        return None
    ticks = [x["attrs"] for x in found["records"]
             if x["name"] == "sched.tick_dispatch"]
    times = trace.module_times(ctx["trace"], module)
    if not times or not ticks or any(
            k not in t for t in ticks for k in ASKED):
        return None
    asked = {k: sum(t[k] for t in ticks) / len(ticks) for k in ASKED}
    least, bound = arch_mod.tick_least_seconds(
        ctx["cell"].config["arch"], ctx["peaks"], **asked)
    print(f"state-space tick: mean tick asked {asked}; least "
          f"{least * 1e3:.3f} ms, bound by {bound}", flush=True)
    return least / (sum(times) / len(times)) * 100.0
