"""The device's idle time per traced tick under the tick's spans (`sched.tick_dispatch`,
`sched.tick_sync`, `sched.tick_prepare`, `sched.spec_round`)
(`harness/period.py` `idle_ms_per_tick`)."""

from benchmarks.harness import period


def read(ctx, part):
    return period.idle_ms_per_tick(ctx, part)
