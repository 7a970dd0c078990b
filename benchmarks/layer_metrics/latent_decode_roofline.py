"""The latent decode kernel's share of its roofline: the least time of
one call (the architecture module's `latent_decode_least_seconds` at
the means of `lanes_decoding` and `context_sum` over the traced ticks'
`sched.tick_dispatch` records - the larger of the latent rows' bytes
over the HBM peak and the absorbed flops over the bf16 peak) over the
mean device time of the kernel's events, by the name the trace prints.
Means on both sides, so a tick cut by the trace's edge moves nothing."""

from benchmarks.harness import loopspans, trace

ASKED = ("lanes_decoding", "context_sum")


def read(ctx, op):
    if ctx.get("trace") is None or not ctx["trace"]["devices"]:
        return None
    seconds, n = trace.op_seconds(ctx["trace"], op)
    found = loopspans.traced(ctx)
    arch_mod = ctx.get("arch_module")
    if not n or found is None or not hasattr(
            arch_mod, "latent_decode_least_seconds"):
        return None
    ticks = [x["attrs"] for x in found["records"]
             if x["name"] == "sched.tick_dispatch"
             and x["attrs"].get("lanes_decoding", 0) > 0]
    if not ticks or any(k not in t for t in ticks for k in ASKED):
        return None
    asked = {k: sum(t[k] for t in ticks) / len(ticks) for k in ASKED}
    least, bound = arch_mod.latent_decode_least_seconds(
        ctx["cell"].config["arch"], ctx["peaks"], **asked)
    print(f"latent decode kernel: {n} calls, mean {seconds / n * 1e6:.1f} "
          f"us; a call at the mean tick {asked} takes at least "
          f"{least * 1e6:.1f} us, bound by {bound}", flush=True)
    return least / (seconds / n) * 100.0
