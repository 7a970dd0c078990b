"""What a driver (`kinds/<kind>.py`) gets from the harness: lines for a
human, the set-up clock and its parts, XLA's compile count, the
profiler, and the device description."""

import os
import shutil
import time

from benchmarks.harness import device as _device
from benchmarks.harness import trace as _trace


def open_cell(workload, t_start, *, chips=None,
              accept_platform=("tpu",), peaks_kind=None):
    """(cell, env) for one run: the cell's files, the look for the
    chip, the peaks of its kind, and the persistent compile cache
    placed by the program's own rule - where JAX_COMPILATION_CACHE_DIR
    is set nothing is set in code, else <checkout>/.jax_cache, a fixed
    path inside the checkout. Every program goes into it, however
    quickly it compiled: only a checkout's first run of a cell
    compiles. The keyword arguments after `chips` are for the
    rehearsal tests alone (a CPU stands in for the chip under a named
    row of `peaks.json`)."""
    import jax
    from benchmarks.harness.cells import ROOT, Cell
    from horovod_tpu.runtime.compile_cache import configure_compile_cache

    cell = Cell(workload)
    devs = _device.require_tpu(chips or cell.chips,
                               accept_platform=accept_platform)
    peaks = _device.peaks_for(peaks_kind or devs[0].device_kind)
    cache = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = Env(t_start, devs, peaks, os.path.join(ROOT, ".bench_trace"))
    env.say(f"cell {cell.name}: config {cell.entry['config']}, traffic "
            f"{cell.entry['traffic']} (kind {cell.kind}), {len(devs)} "
            f"chip(s) of {devs[0].device_kind!r}; compile cache {cache}")
    return cell, env


class Env:
    def __init__(self, t_start, devs, peaks, trace_dir):
        self.t_start = t_start          # time.time() at process start
        self.devs, self.peaks = devs, peaks
        self.trace_dir = trace_dir
        self.compiles = _device.CompileCounter()
        self.phases = []                # [(name, seconds)]
        self._t_phase = t_start
        self._excluded = 0.0            # seconds not counted in setup_s
        self.tracing = False
        self.trace = None
        self.trace_window_s = None

    def say(self, msg):
        print(f"[{time.time() - self.t_start:7.2f}s] {msg}", flush=True)

    def phase(self, name, *, excluded=False):
        """Close the part of set-up that ends now under `name`."""
        now = time.time()
        self.phases.append((name, now - self._t_phase))
        if excluded:
            self._excluded += now - self._t_phase
        self._t_phase = now

    def exclude(self, seconds):
        """Seconds of the comparison that fell inside set-up: not
        counted in setup_s."""
        self._excluded += seconds

    def setup_done(self):
        """Process start to now, less what was marked excluded; prints
        the parts."""
        total = time.time() - self.t_start - self._excluded
        c = self.compiles
        self.say("setup_s parts: " + ", ".join(
            f"{n} {s:.2f}" for n, s in self.phases)
            + f" -> setup_s {total:.2f}; {c.n} programs compiled or "
              f"loaded in {c.seconds:.1f} s, {c.hits} of them from the "
              f"persistent cache")
        return total

    def start_trace(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.tracing = True

    def stop_trace(self, window_s):
        import jax
        jax.profiler.stop_trace()
        self.tracing = False
        self.trace_window_s = window_s
        t0 = time.time()
        self.trace = _trace.load_xplane(self.trace_dir)
        for line in _trace.summary(self.trace):
            self.say("trace: " + line)
        self.say(f"trace: read in {time.time() - t0:.1f} s")
        shutil.rmtree(self.trace_dir, ignore_errors=True)

    def describe_device(self):
        return _device.describe(self.devs)
