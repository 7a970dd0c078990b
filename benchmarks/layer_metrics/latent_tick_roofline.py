"""A decode tick over latent-attention sublayers against its roofline:
as `mixed_tick_roofline`, with the required bytes and flops of the
configuration's architecture module (`tick_least_seconds`: experts hit,
other weights, the latent rows by `context_sum`, read once).

What a tick was asked to do is on two records of the scheduler's loop:
`sched.tick_dispatch` (lanes_decoding, context_sum) and, one step
later, `sched.tick_sync` (moe_experts_hit, moe_pairs - counted on the
device while the tick ran). The least time is taken at the MEANS of the
records (it is linear in each of them but for the choice of the bound).
A program whose tick records lack one of them gives nothing to read."""

from benchmarks.harness import loopspans, trace

ASKED = {"lanes_decoding": ("sched.tick_dispatch", "lanes_decoding"),
         "context_sum": ("sched.tick_dispatch", "context_sum"),
         "experts_hit": ("sched.tick_sync", "moe_experts_hit"),
         "pairs": ("sched.tick_sync", "moe_pairs")}


def mean(records, name, key):
    vals = [x["attrs"][key] for x in records
            if x["name"] == name and key in x["attrs"]]
    return sum(vals) / len(vals) if vals else None


def read(ctx, module):
    if ctx.get("trace") is None or not ctx["trace"]["devices"]:
        return None
    found = loopspans.traced(ctx)
    arch_mod = ctx.get("arch_module")
    if found is None or not hasattr(arch_mod, "tick_least_seconds"):
        return None
    asked = {k: mean(found["records"], *at) for k, at in ASKED.items()}
    times = trace.module_times(ctx["trace"], module)
    if not times or any(v is None for v in asked.values()):
        return None
    least, bound = arch_mod.tick_least_seconds(
        ctx["cell"].config["arch"], ctx["peaks"], **asked)
    print(f"latent tick: mean tick asked {asked}; least "
          f"{least * 1e3:.3f} ms, bound by {bound}", flush=True)
    return least / (sum(times) / len(times)) * 100.0
