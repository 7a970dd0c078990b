"""The 95th percentile of the queue wait over the measured window's
admissions (`harness/period.py` `queue_wait_ms`)."""

from benchmarks.harness import period


def read(ctx, percentile):
    return period.queue_wait_ms(ctx, percentile)
