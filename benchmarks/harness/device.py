"""The device a run is on: the look for the chip, the table of peaks,
XLA's own compile count, and the memory reading.

Copied in substance from `chip_smoke.py` (`require_tpu`,
`CompileCounter`) so that later PRs may change the program and not the
yardstick.
"""

import json
import os
import sys

from benchmarks.harness.cells import BENCH_DIR


def require_tpu(want_chips, *, accept_platform=("tpu",)):
    """The `want_chips` devices the cell runs on, or exit non-zero with
    NO result line: a CPU run proves nothing about the chip. (The
    rehearsal tests pass `accept_platform=("cpu",)` from their own
    code; no option or environment variable of the command does.)"""
    import jax
    devs = jax.devices()
    if devs[0].platform not in accept_platform:
        print(f"benchmarks: no accelerator - JAX reports "
              f"{devs[0].platform} devices; a cell is measured on a "
              f"TPU only", file=sys.stderr, flush=True)
        sys.exit(3)
    if len(devs) < want_chips:
        print(f"benchmarks: the cell asks for {want_chips} chip(s) "
              f"but JAX reports {len(devs)}", file=sys.stderr,
              flush=True)
        sys.exit(3)
    return devs[:want_chips]


def peaks_for(device_kind, path=None):
    """This device kind's row of `peaks.json`; an unknown kind is an
    error, never a default."""
    with open(path or os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        print(f"benchmarks: device_kind {device_kind!r} is not in "
              f"peaks.json ({sorted(table)})", file=sys.stderr,
              flush=True)
        sys.exit(4)
    return table[device_kind]


class CompileCounter:
    """XLA backend compiles as JAX itself reports them: `n` programs
    asked for, of which `hits` came out of the persistent cache, and
    the seconds both took."""

    def __init__(self):
        self.n = self.hits = 0
        self.seconds = 0.0
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def memory_peak_bytes(devs):
    """`peak_bytes_in_use` of the fullest chip (0 where the backend
    reports none, as the CPU does)."""
    peak = 0
    for d in devs:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0)))
    return peak


def describe(devs):
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs),
            "memory_peak_bytes": memory_peak_bytes(devs)}
