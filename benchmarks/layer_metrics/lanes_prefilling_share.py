"""Share of the lanes that hold a request which waits for its turn to
prefill or is in the middle of it, over the measured window's ticks."""

from benchmarks.harness import loopspans


def read(ctx, key):
    return loopspans.lane_share(ctx, key)
