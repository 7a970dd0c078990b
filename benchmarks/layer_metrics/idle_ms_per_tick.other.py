"""The device's idle time per traced tick under no named phase:
housekeeping, the admission peek, bookkeeping, the step's own and
what no span covers (`harness/period.py` `idle_ms_per_tick`). Prints
the five parts, their sum beside the device's idle share, and the
period check."""

from benchmarks.harness import period


def read(ctx, part, tick_module, chunk_module):
    return period.idle_ms_per_tick(ctx, part, tick_module=tick_module,
                                   chunk_module=chunk_module)
