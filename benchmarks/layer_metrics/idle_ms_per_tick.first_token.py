"""The device's idle time per traced tick under `sched.first_token`
(`harness/period.py` `idle_ms_per_tick`)."""

from benchmarks.harness import period


def read(ctx, part):
    return period.idle_ms_per_tick(ctx, part)
