"""Share of the decoding lanes' chosen pairs that fell on identity
experts, over the measured window: `moe_zero_pairs` over
`moe_chosen_pairs` on the `sched.tick_sync` records - the last
`window_ticks` of the ring that carry them (kind `serve_arch` hands its
readers the four older counters alone)."""

from benchmarks.harness import loopspans


def read(ctx):
    n = ctx.get("window_ticks")
    if not n:
        return None
    ticks = [x["attrs"] for x in loopspans.ring(ctx)
             if x["name"] == "sched.tick_sync"
             and x["attrs"].get("moe_chosen_pairs")][-n:]
    chosen = sum(t["moe_chosen_pairs"] for t in ticks)
    if not chosen:
        return None
    return sum(t["moe_zero_pairs"] for t in ticks) / chosen * 100.0
