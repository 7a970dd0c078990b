"""Chunk programs per decode tick of the measured window."""

from benchmarks.harness import period


def read(ctx):
    return period.prefill_chunks_per_tick(ctx)
