"""Device time of the chunk programs per traced decode tick."""

from benchmarks.harness import period


def read(ctx, module):
    return period.chunk_device_ms_per_tick(ctx, module)
