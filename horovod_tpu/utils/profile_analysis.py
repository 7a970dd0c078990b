"""Overlap analysis of `jax.profiler` traces — measuring α.

`docs/scaling.md`'s efficiency model rests on the exposed-collective
fraction α (the share of collective time NOT hidden under compute).
The reference measured its 90/79 % efficiencies on hardware
(`README.md:27-32` there); this module turns an `HVD_PROFILE_DIR`
capture (`obs.profiling.profiler_session`) into a *measured* α to set
against the modeled numbers.

Works on the Chrome-trace JSON (`*.trace.json.gz`) the profiler writes
next to the xplane protobuf — dependency-free parsing. Device timelines
(pids whose `process_name` names a TPU/accelerator) carry one `X` event
per executed HLO op; async collectives appear as `*-start` / `*-done`
pairs. For every collective we take its WINDOW (start-issue to
done-retire for async pairs; the op's own extent for sync ops),
subtract the union of compute intervals inside it, and call the
remainder exposed:

    alpha = exposed_collective_time / total_collective_window_time

A fully hidden all-reduce (compute covering its whole start→done span)
contributes 0; a synchronous blocking one contributes its full
duration. Union arithmetic makes nested/overlapping trace events safe
to double-count-free.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

# Peak bf16 FLOP/s by device kind (public TPU specs) — the MFU
# denominator of the obs-plane `hvd_training_mfu` gauge
# (obs/profiling.StepProfiler).
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12, "TPU v5 lite": 197e12, "TPU v5e": 197e12,
    "TPU v5p": 459e12, "TPU v6 lite": 918e12, "TPU v6e": 918e12,
}


def device_peak_flops(device_kind: Optional[str]) -> Optional[float]:
    """Peak bf16 FLOP/s for a jax ``device_kind`` string; None for
    unknown hardware (CPU, unlisted TPU generations) — MFU is then
    unreported rather than fabricated."""
    if not device_kind:
        return None
    return PEAK_BF16_FLOPS.get(device_kind)


def mfu(flops_per_s: float,
        device_kind: Optional[str]) -> Optional[float]:
    """Model FLOPs utilization: achieved FLOP/s over the device peak
    (coarse but honest — docs/mfu.md); None when the peak is
    unknown."""
    peak = device_peak_flops(device_kind)
    if not peak:
        return None
    return round(flops_per_s / peak, 4)


# HLO collective op names (TPU device timeline), e.g. "all-reduce.1",
# "all-reduce-start.7", "all-gather-done.3", "collective-permute.2".
_COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)"
    r"(-start|-done)?(\.|$|-)", re.IGNORECASE)


def find_trace_file(profile_dir: str,
                    min_mtime: Optional[float] = None) -> Optional[str]:
    """Newest `*.trace.json.gz` under a jax.profiler trace directory.

    `min_mtime` guards against a REUSED profile dir: each capture
    writes a new timestamped subdir and old ones are never cleaned, so
    without the bound a failed serialization would silently hand back a
    previous run's trace as this run's measurement. 2s of slack
    tolerates coarse-mtime filesystems / slight clock skew without
    readmitting day-old captures."""
    paths = [p for p in glob.glob(
        os.path.join(profile_dir, "**", "*.trace.json.gz"),
        recursive=True)
        if min_mtime is None or os.path.getmtime(p) >= min_mtime - 2.0]
    return max(paths, key=os.path.getmtime) if paths else None


def load_trace(profile_dir_or_file: str,
               min_mtime: Optional[float] = None) -> Dict[str, Any]:
    path = profile_dir_or_file
    if os.path.isdir(path):
        found = find_trace_file(path, min_mtime=min_mtime)
        if found is None:
            raise FileNotFoundError(
                f"no *.trace.json.gz under {path!r}"
                + (" (newer than min_mtime)" if min_mtime else ""))
        path = found
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _merge(intervals: List[Tuple[float, float]]):
    """Sorted union of half-open intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _covered(window: Tuple[float, float],
             union: List[Tuple[float, float]]) -> float:
    """Length of `window` covered by the (merged) union."""
    s, e = window
    total = 0.0
    for us, ue in union:
        if ue <= s:
            continue
        if us >= e:
            break
        total += min(e, ue) - max(s, us)
    return total


def _device_pids(events, device_hint: str = ""):
    """pids whose process_name marks a device timeline (TPU /
    accelerator, not host) — the one TPU/host classification heuristic,
    shared by the overlap and breakdown analyses."""
    proc_names: Dict[Any, str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            proc_names[e["pid"]] = (e.get("args") or {}).get("name", "")

    def is_device(name: str) -> bool:
        if device_hint:
            return device_hint in name
        low = name.lower()
        if "host" in low or "cpu" in low:
            return False
        return any(k in low for k in ("tpu", "device", "accelerator"))

    return {pid for pid, n in proc_names.items() if is_device(n)}


def analyze_overlap(trace: Dict[str, Any],
                    device_hint: str = "") -> Optional[Dict[str, Any]]:
    """Measured α from a loaded Chrome trace.

    Returns None when no device timeline is present (e.g. a CPU-only
    capture — the CPU backend emits host events only). `device_hint`
    optionally narrows which process_name counts as the device (by
    substring); by default anything naming a TPU / device / accelerator
    that is not the host.
    """
    events = (trace if isinstance(trace, list)
              else trace.get("traceEvents", []))
    device_pids = _device_pids(events, device_hint)
    if not device_pids:
        return None

    from collections import defaultdict, deque

    # (label, window) per collective — labels feed the top-exposed
    # report, windows the headline numbers, so both rank by the same
    # start→done extent.
    comm: List[Tuple[str, Tuple[float, float]]] = []
    compute: List[Tuple[float, float]] = []
    # Per-occurrence FIFO pairing: a profiled run repeats each HLO op
    # once per step under the SAME name, so start/done must pair in
    # time order per name — a name-keyed scalar would collapse N steps
    # into the last occurrence and undercount t_comm N-fold.
    start_q: Dict[str, deque] = defaultdict(deque)

    dev_events = sorted(
        (e for e in events
         if e.get("ph") == "X" and e.get("pid") in device_pids
         and e.get("dur") is not None),
        key=lambda e: float(e["ts"]))
    for e in dev_events:
        name = e.get("name", "")
        iv = (float(e["ts"]), float(e["ts"]) + float(e["dur"]))
        m = _COLLECTIVE_RE.match(name)
        if not m:
            compute.append(iv)
            continue
        kind = m.group(2)
        if kind == "-start":
            start_q[name.replace("-start", "-done", 1)].append(
                (name, iv))
        elif kind == "-done":
            q = start_q.get(name)
            _, siv = q.popleft() if q else (None, None)
            # Async window = issue of start → retire of done; a done
            # with no matched start falls back to its own extent.
            comm.append((name, (siv[0] if siv else iv[0], iv[1])))
        else:
            comm.append((name, iv))       # sync collective
    for q in start_q.values():            # starts with no done
        comm.extend(q)
    comm_windows = [w for _, w in comm]
    if not comm_windows:
        return {"alpha": None, "t_comm_us": 0.0, "t_comm_exposed_us": 0.0,
                "t_compute_us": round(sum(e - s for s, e in
                                          _merge(compute)), 3),
                "n_collectives": 0, "device_pids": len(device_pids)}

    compute_union = _merge(compute)
    merged_comm = _merge(comm_windows)
    t_comm = sum(e - s for s, e in merged_comm)
    exposed = sum((e - s) - _covered((s, e), compute_union)
                  for s, e in merged_comm)
    # Per-window attribution for the top offenders, from the SAME
    # paired start→done windows as the headline numbers (un-merged, so
    # overlapping windows may double-count individually).
    per_op = sorted(
        ((name, (w[1] - w[0]) - _covered(w, compute_union))
         for name, w in comm),
        key=lambda kv: -kv[1])

    return {
        "alpha": round(exposed / t_comm, 4) if t_comm else None,
        "t_comm_us": round(t_comm, 3),
        "t_comm_exposed_us": round(exposed, 3),
        "t_compute_us": round(sum(e - s for s, e in compute_union), 3),
        "n_collectives": len(comm_windows),
        "device_pids": len(device_pids),
        "top_exposed": [
            {"name": n, "exposed_us": round(v, 3)}
            for n, v in per_op[:5]],
    }


def analyze_op_breakdown(trace: Dict[str, Any],
                         device_hint: str = "",
                         top_k: int = 10) -> Optional[Dict[str, Any]]:
    """Where the device step time goes, by HLO op category.

    The r4 ResNet diagnosis (BN statistics = 37.8 % of the step,
    docs/mfu.md) was assembled by hand from a trace; this automates it
    so every capture carries its own cost ranking (named top costs,
    not just a number).

    Category = the event's `hlo_category` arg when the profiler
    provides it, else the op-name prefix with trailing `.N` indices
    stripped ("fusion.123" → "fusion"). Returns total device-op time,
    per-category shares, and the top individual ops.
    """
    events = (trace if isinstance(trace, list)
              else trace.get("traceEvents", []))
    device_pids = _device_pids(events, device_hint)
    if not device_pids:
        return None

    # A real capture's device pid carries SEVERAL lanes — per-op
    # "XLA Ops" plus aggregate "XLA Modules"/"Steps" rows whose events
    # span whole steps. Summing every lane double-counts and crowns
    # the module event the top "category", so when thread_name
    # metadata identifies an op lane, only those tids count; traces
    # without lane names (synthetic tests) keep all tids.
    thread_names: Dict[Any, str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            thread_names[(e.get("pid"), e.get("tid"))] = (
                (e.get("args") or {}).get("name", ""))
    op_tids = {k for k, n in thread_names.items()
               if k[0] in device_pids and "xla ops" in n.lower()}

    from collections import defaultdict
    cat_us: Dict[str, float] = defaultdict(float)
    op_us: Dict[str, float] = defaultdict(float)
    total = 0.0
    for e in events:
        if (e.get("ph") != "X" or e.get("pid") not in device_pids
                or e.get("dur") is None):
            continue
        if op_tids and (e.get("pid"), e.get("tid")) not in op_tids:
            continue
        name = e.get("name", "")
        dur = float(e["dur"])
        cat = (e.get("args") or {}).get("hlo_category")
        if not cat:
            cat = re.sub(r"[.\d]+$", "", name) or name
        cat_us[cat] += dur
        op_us[name] += dur
        total += dur
    if total <= 0:
        return None
    cats = sorted(cat_us.items(), key=lambda kv: -kv[1])
    ops = sorted(op_us.items(), key=lambda kv: -kv[1])
    return {
        "t_total_us": round(total, 3),
        "categories": [
            {"category": c, "us": round(v, 3),
             "share": round(v / total, 4)}
            for c, v in cats[:top_k]],
        "top_ops": [
            {"name": n, "us": round(v, 3),
             "share": round(v / total, 4)}
            for n, v in ops[:top_k]],
    }


def analyze_profile_dir(profile_dir: str,
                        min_mtime: Optional[float] = None
                        ) -> Optional[Dict[str, Any]]:
    """Convenience: load the newest trace under `profile_dir` (written
    at or after `min_mtime`, when given) and analyze — overlap α plus
    the per-category op breakdown (`op_breakdown` key); None when there
    is no (fresh enough) trace or no device timeline."""
    try:
        trace = load_trace(profile_dir, min_mtime=min_mtime)
    except (FileNotFoundError, OSError, ValueError):
        return None
    out = analyze_overlap(trace)
    if out is not None:
        out["op_breakdown"] = analyze_op_breakdown(trace)
    return out
