"""Distinct chips of the stated deployment that a decoding token's
chosen experts lie on, a token and expert layer, over the measured
window: `moe_token_chips` on the `sched.tick_sync` records - the last
`window_ticks` of the ring that carry it - over the decoding lanes of
the window's `sched.tick_dispatch` records times the expert layers
(kind `serve_arch` hands its readers the four older counters alone).
The two records of one tick lie a step apart in the ring, so the sums
may differ by one tick's lanes of thousands."""

from benchmarks.harness import loopspans


def read(ctx):
    n = ctx.get("window_ticks")
    if not n:
        return None
    syncs = [x["attrs"] for x in loopspans.ring(ctx)
             if x["name"] == "sched.tick_sync"
             and "moe_token_chips" in x["attrs"]][-n:]
    ticks = loopspans.window_ticks(ctx)
    if not syncs or not ticks:
        return None
    tokens = (sum(t["lanes_decoding"] for t in ticks) / len(ticks)
              * len(syncs))
    layers = syncs[-1]["moe_layers"]
    if not tokens or not layers:
        return None
    return sum(t["moe_token_chips"] for t in syncs) / tokens / layers
