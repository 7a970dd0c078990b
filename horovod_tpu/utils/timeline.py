"""Horovod Timeline — Chrome-trace (chrome://tracing) profiler.

Parity with the reference timeline (SURVEY §5.1; `timeline.h`/`timeline.cc`):
tensors are modeled as trace *processes* (pid = interned tensor index,
`timeline.cc:59-76`); events are `B`/`E` duration pairs and `X` instants
(`timeline.cc:78-92`); a per-tensor state machine
{UNKNOWN, NEGOTIATING, TOP_LEVEL, ACTIVITY} guards transitions
(`timeline.h:37-42`); writes flush on a ~1 s cadence (`timeline.h:35`).
Enabled via `HOROVOD_TIMELINE=/path/file.json` (`mpi_ops.cc:1272-1275`).

Device-side profiling is deferred to `jax.profiler` (the XLA/TPU
profiler); this timeline covers the host-side schedule — negotiation is
compile-time under SPMD, so NEGOTIATING brackets validation + dispatch.

When the native control plane is available the same format is written by
the C++ writer (`horovod_tpu/native/control_plane.cc`); this Python
implementation is the in-process default and fallback.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

UNKNOWN, NEGOTIATING, TOP_LEVEL, ACTIVITY = range(4)

FLUSH_INTERVAL_S = 1.0  # timeline.h:35


class Timeline:
    def __init__(self, path: str, native=None):
        # Prefer the C++ writer (horovod_tpu/native/control_plane.cc) when
        # the control plane is loaded; same format, off the Python lock.
        self._native = None
        if native is not None:
            try:
                if native.timeline_start(path) == 0:
                    self._native = native
            # hvd: disable=HVD006(the C++ writer is optional — ANY probe fault falls back to the Python writer, never fails tracing)
            except Exception:
                self._native = None
        self._path = path
        self._lock = threading.Lock()
        self._pids = {}           # tensor name -> pid
        self._states = {}         # tensor name -> state
        self._events = []
        self._last_flush = time.time()
        self._start = time.time()
        self._closed = False
        if self._native is None:
            try:
                # Truncate/create the file with the JSON array opener.
                with open(self._path, "w") as f:
                    f.write("[\n")
            except OSError as e:
                # Warn and disable, don't fail training — the reference's
                # behavior on an unwritable timeline (timeline.cc:32-34,
                # 100-103).
                import sys
                sys.stderr.write(
                    f"WARNING: Error opening the Horovod Timeline file "
                    f"{self._path!r}, will not write a timeline: {e}\n")
                self._closed = True

    def _ts_us(self) -> int:
        return int((time.time() - self._start) * 1e6)

    def _pid(self, name: str) -> int:
        pid = self._pids.get(name)
        if pid is None:
            pid = len(self._pids)
            self._pids[name] = pid
            self._events.append({
                "name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": name},
            })
        return pid

    def _emit(self, ph: str, name: str, pid: int, **kw):
        ev = {"ph": ph, "name": name, "pid": pid, "ts": self._ts_us()}
        ev.update(kw)
        self._events.append(ev)

    def record(self, tensor: str, phase: str, activity: Optional[str] = None):
        """Record a phase transition for `tensor`.

        phase ∈ {NEGOTIATING, TOP_LEVEL, DONE}; `activity` opens a nested
        activity span (the reference's ACTIVITY_START_ALL vocabulary:
        ALLREDUCE, ALLGATHER, BCAST, MEMCPY_IN_FUSION_BUFFER, ...).
        """
        if self._native is not None:
            if not self._closed:
                self._native.timeline_record(tensor, phase, activity)
            return
        with self._lock:
            if self._closed:
                return
            pid = self._pid(tensor)
            state = self._states.get(tensor, UNKNOWN)
            if phase == "NEGOTIATING":
                self._emit("B", "NEGOTIATE", pid)
                self._states[tensor] = NEGOTIATING
            elif phase == "TOP_LEVEL":
                if state == NEGOTIATING:
                    self._emit("E", "NEGOTIATE", pid)
                self._emit("B", tensor, pid)
                self._states[tensor] = TOP_LEVEL
                if activity:
                    self._emit("B", activity, pid)
                    self._states[tensor] = ACTIVITY
            elif phase == "DONE":
                if state == ACTIVITY:
                    self._emit("E", "", pid)
                if state in (TOP_LEVEL, ACTIVITY):
                    self._emit("E", tensor, pid)
                elif state == NEGOTIATING:
                    self._emit("E", "NEGOTIATE", pid)
                self._states[tensor] = UNKNOWN
            self._maybe_flush()

    def mark(self, tensor: str, name: str):
        """Instant event (`X`, timeline.cc:78-92)."""
        if self._native is not None:
            if not self._closed:
                self._native.timeline_mark(tensor, name)
            return
        with self._lock:
            if self._closed:
                return
            self._emit("X", name, self._pid(tensor), dur=0)
            self._maybe_flush()

    def _maybe_flush(self):
        if time.time() - self._last_flush >= FLUSH_INTERVAL_S:
            self._flush_locked()

    def _flush_locked(self):
        if not self._events:
            return
        try:
            with open(self._path, "a") as f:
                for ev in self._events:
                    f.write(json.dumps(ev) + ",\n")
        except OSError as e:
            # Same warn-and-disable contract as the constructor
            # (timeline.cc:32-34): a mid-run I/O failure (disk full,
            # file removed) must cost the trace, never the training
            # step or serving request that happened to trigger the
            # flush.
            import sys
            sys.stderr.write(
                f"WARNING: Error writing the Horovod Timeline file "
                f"{self._path!r}, disabling the timeline: {e}\n")
            # hvd: disable=HVD004(_flush_locked runs with self._lock held — every caller is inside a `with self._lock` block, per the name)
            self._closed = True
        self._events = []
        self._last_flush = time.time()

    def close(self):
        if self._native is not None:
            # The native writer is its own serialization point: every
            # C++ entry (Record/Mark/Stop) takes the internal mutex
            # and no-ops once Stop nulled the file, so a record racing
            # this close is SAFE without Python-side locking — the
            # unlocked `_closed` checks in record/begin/end/mark are
            # only a cheap fast-path short-circuit. The lock here just
            # keeps close() itself idempotent and `_closed` writes
            # single-writer (hvdlint HVD004).
            with self._lock:
                if not self._closed:
                    self._native.timeline_stop()
                    self._closed = True
            return
        with self._lock:
            if self._closed:
                return
            self._flush_locked()
            # Chrome tolerates a trailing comma without a closing bracket
            # (the reference also streams without closing, timeline.cc);
            # write a terminator for strict parsers.
            try:
                with open(self._path, "a") as f:
                    f.write("{}]\n")
            except OSError:
                pass  # flush already warned; close stays quiet
            self._closed = True


def start_timeline(path: str):
    """Programmatic timeline start (env-var HOROVOD_TIMELINE also works)."""
    from horovod_tpu.runtime import state as _state
    st = _state.check_initialized()
    if st.timeline is not None:
        st.timeline.close()
    st.timeline = Timeline(path, native=st.native)
    return st.timeline


def stop_timeline():
    from horovod_tpu.runtime import state as _state
    st = _state.check_initialized()
    if st.timeline is not None:
        st.timeline.close()
        st.timeline = None


def step_bracket(fn, name: str = "train_step"):
    """Wrap a jitted train step so every invocation is seen from the
    host: a ``train.step`` loop span (`obs.spans.loop_span`: the loop
    ring, and under a `jax.profiler` session a ``StepTraceAnnotation``
    numbered by this wrapper's own call count, on the same time axis
    as the device's ops) and, inside it, a B/E span on the
    HOROVOD_TIMELINE trace.

    Under SPMD the per-collective events the reference logs do not
    exist at runtime — collectives are compiled into the XLA program
    and are invisible to the host (device traces belong to
    `jax.profiler`, see docs/timeline.md). What the host CAN see, and
    what this bracket records, is the step cadence: dispatch duration,
    gaps between steps (input pipeline stalls), and how eager
    collectives interleave with the jitted hot path. With neither a
    timeline nor a profiler session it costs one ring append a step.
    """
    import functools
    import itertools

    from horovod_tpu.obs import spans as _spans
    from horovod_tpu.runtime import state as _state

    calls = itertools.count()

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with _spans.loop_span("train.step", step_num=next(calls)):
            tl = _state.global_state().timeline
            if tl is None:
                return fn(*args, **kwargs)
            tl.record(name, "TOP_LEVEL", "DISPATCH")
            try:
                return fn(*args, **kwargs)
            finally:
                tl.record(name, "DONE")

    return wrapper
