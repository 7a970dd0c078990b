"""horovod_tpu.obs — the unified observability plane.

One process-wide layer (docs/observability.md) that serving,
resilience, training, collectives and the stall monitor all register
into, replacing per-subsystem silos:

* `registry` — thread-safe `Counter`/`Gauge`/`Histogram` with label
  sets; histograms use fixed log-scale buckets so percentiles merge
  across ranks.
* `catalog` — the single declaration site for every standard metric
  family (the Grafana-ready catalog in the docs).
* `exporter` — stdlib HTTP daemon: Prometheus text at ``/metrics``,
  liveness + engine generation at ``/healthz``, full JSON (quantiles,
  exemplars, recent events) at ``/metrics.json``. Enable with
  ``HVD_METRICS_PORT``.
* `events` — bounded JSONL structured-event log for discrete events
  (restarts, requeues, sheds, chaos fires, stalls, compiles);
  ``HVD_EVENTS_LOG=/path`` persists it.
* `spans` — the one recorder: a ``trace_id`` minted per serving
  request and carried through queue → prefill → decode → (requeue)
  as a span tree (also stamped into events and histogram
  exemplars), and the loop spans (``sched.*``, ``engine.*``,
  ``train.*``) mirrored into the JAX profiler.
* `profiling` — `profile_step` brackets + the opt-in `jax.profiler`
  session (``HVD_PROFILE_DIR``).
* `aggregate` — the FLEET layer: a rank-0 collector pulling every
  rank's snapshot, merging histograms bucket-by-bucket
  (``hvd_fleet_*`` percentiles, ``hvd_rank_skew_*`` gauges) and
  serving the result at ``/fleet``.
* `straggler` — collective straggler attribution: per-rank host-side
  dispatch timing windows, exchanged every ``HVD_STRAGGLER_CYCLES``
  and merged into a report naming the slowest rank (linked into the
  StallMonitor's stall events).
* `flightrec` — the crash flight recorder: on watchdog restarts,
  chaos fires, stall trips, NaN rollbacks and dispatch crashes, an
  atomic post-mortem bundle (event ring + metric snapshot + in-flight
  trace_ids + config) lands in ``HVD_FLIGHT_DIR``; pretty-print with
  ``python -m horovod_tpu.obs.flightrec <bundle>``.
* `slo` — TTFT/TPOT/shed-rate objectives as multi-window error-budget
  burn rates (``HVD_SLO``); a fast-burn breach flips ``/healthz`` to
  503.
"""

# NOTE: `flightrec` is deliberately NOT imported here — it is also a
# `python -m horovod_tpu.obs.flightrec` CLI, and importing it from the
# package __init__ would make runpy warn about the double import.
# `from horovod_tpu.obs import flightrec` still works (submodule).
from horovod_tpu.obs import (aggregate, catalog, events, slo, spans,
                             straggler)
from horovod_tpu.obs.aggregate import FleetAggregator, rank_snapshot
from horovod_tpu.obs.exporter import (MetricsServer, render_prometheus,
                                      start_exporter, stop_exporter)
from horovod_tpu.obs.profiling import (StepProfiler, profile_step,
                                       profiler_session)
from horovod_tpu.obs.registry import (Counter, Gauge, Histogram,
                                      MetricRegistry, registry)
from horovod_tpu.obs.slo import Objective, SLOMonitor

__all__ = [
    "registry", "MetricRegistry", "Counter", "Gauge", "Histogram",
    "catalog", "events", "spans",
    "aggregate", "straggler", "slo",
    "FleetAggregator", "rank_snapshot", "SLOMonitor", "Objective",
    "MetricsServer", "render_prometheus", "start_exporter",
    "stop_exporter",
    "StepProfiler", "profile_step", "profiler_session",
]
