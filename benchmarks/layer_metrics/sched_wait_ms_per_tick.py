"""What the dispatch thread waited per decode tick of the measured
window (`harness/period.py`); prints the split by leaf phase."""

from benchmarks.harness import period


def read(ctx, what):
    return period.thread_ms_per_tick(ctx, what)
