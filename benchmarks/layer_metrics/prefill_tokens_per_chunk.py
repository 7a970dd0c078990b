"""Mean prompt tokens a chunk program of the measured window; prints
the chunks by size."""

from benchmarks.harness import period


def read(ctx):
    return period.prefill_tokens_per_chunk(ctx)
