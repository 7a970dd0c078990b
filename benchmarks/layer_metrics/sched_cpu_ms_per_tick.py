"""The dispatch thread's own CPU time per decode tick of the measured
window (`harness/period.py`); prints the traced window's beside it."""

from benchmarks.harness import period


def read(ctx, what):
    return period.thread_ms_per_tick(ctx, what)
