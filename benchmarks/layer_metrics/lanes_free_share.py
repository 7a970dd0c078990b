"""Share of the lanes no request holds, over the measured window's
ticks. Checked here: the three shares of the lanes - decoding
(`lanes_live_share`, from the engine's token counters), prefilling
and free (both from the tick records) - come from two sources and
should sum to 100; the line says how close they come."""

from benchmarks.harness import loopspans


def read(ctx, key):
    free = loopspans.lane_share(ctx, key)
    live = ctx.get("lanes_live_share")
    if free is not None and live is not None:
        total = (live * 100.0 + free
                 + loopspans.lane_share(ctx, "lanes_prefilling"))
        print(f"lanes: live + prefilling + free = {total:.2f} % "
              f"({'within' if abs(total - 100) <= 2 else 'NOT within'}"
              f" 2 points of 100)", flush=True)
    return free
