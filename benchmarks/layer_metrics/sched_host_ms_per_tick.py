"""The dispatch thread's own work per decode tick in the traced
window: everything `ContinuousBatchingScheduler.step` and the engine's
bookkeeping after it take, less the span that waits for the device
and appends its tokens (`sched.tick_sync`). `engine.idle_wait`, the
wait for a request, is in neither sum."""

from benchmarks.harness import loopspans


def read(ctx):
    found = loopspans.traced(ctx) if ctx.get("traced_ticks") else None
    if found is None:
        return None
    rec = found["records"]
    ticks = sum(x["name"] == "sched.tick_dispatch" for x in rec)
    if not ticks:
        return None
    busy = (loopspans.total_ms(rec, "sched.step")
            + loopspans.total_ms(rec, "engine.bookkeeping")
            - loopspans.total_ms(rec, "sched.tick_sync"))
    return busy / ticks
