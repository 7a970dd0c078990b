"""From a profiler trace to numbers: device busy time, per-program and
per-operation device time, exposed collective time, and the idle gaps
by what the host was doing.

The reduction works on a plain dictionary, so that the tests can check
it against a small recorded trace (`benchmarks/data/`):

    {"devices": {"/device:TPU:0": {"ops": [[name, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...],
     "labels": {name: the start of the op's HLO text}}

The op line of a TPU prints each event as its whole HLO instruction,
`%fusion.12 = f32[...] fusion(...)`; an op's `name` here is the part
before ` = ` without the `%`, and `labels` keeps a little of the rest
for a reader of the breakdown.

`load_xplane` fills it from the `.xplane.pb` the JAX profiler writes,
with nothing but JAX. Interval union and the exposed-collective
arithmetic are copied from `horovod_tpu/utils/profile_analysis.py`
(`_merge`, `_covered`, `analyze_overlap`).
"""

import bisect
import collections
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

# A collective on a TPU's op line. The event's name is the HLO
# instruction's, which XLA takes from the HLO opcode ("all-reduce.1",
# "all-reduce-start.7", "all-gather-done.3") or from the JAX primitive
# that made it ("psum.5" - what the four-chip trace of PR 23 prints);
# where the whole instruction is printed, its opcode decides.
_COLLECTIVE_RE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|psum|pmean|pmax|pmin|"
    r"all_gather|psum_scatter|all_to_all|ppermute|pbroadcast)"
    r"(-start|-done)?(\.|$|-| )", re.IGNORECASE)
_COLLECTIVE_OPCODE_RE = re.compile(
    r"[\s)}](all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(-start|-done)?\(")


def collective_kind(name, hlo_rest=""):
    """None, or "" / "-start" / "-done" for a collective op."""
    m = _COLLECTIVE_OPCODE_RE.search(" " + hlo_rest) if hlo_rest else None
    m = m or _COLLECTIVE_RE.match(name)
    return None if m is None else (m.group(2) or "").lower()


def find_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_xplane(trace_dir, *, host_min_ns=20_000):
    """The dictionary above from the newest trace under `trace_dir`.
    Host events shorter than `host_min_ns` are dropped: they explain
    no gap worth naming."""
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(find_xplane(trace_dir))
    out = {"devices": {}, "host": [], "lines": {}, "labels": {},
           "collectives": {}}
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        lines = {}
        for line in plane.lines:
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in line.events]
            lines[line.name] = len(evs)
            if is_dev and line.name == OPS_LINE:
                for e in evs:
                    hlo = e[0]
                    e[0] = short_name(hlo, out["labels"])
                    if e[0] not in out["collectives"]:
                        out["collectives"][e[0]] = collective_kind(
                            e[0], hlo.partition(" = ")[2])
                out["devices"].setdefault(plane.name, {})["ops"] = evs
            elif is_dev and line.name == MODULES_LINE:
                out["devices"].setdefault(plane.name, {})["modules"] = evs
            elif plane.name.startswith("/host:"):
                out["host"].extend(e for e in evs if e[2] >= host_min_ns)
        out["lines"][plane.name] = lines
    for dev in out["devices"].values():
        dev.setdefault("ops", [])
        dev.setdefault("modules", [])
    return out


def short_name(hlo, labels=None, keep=96):
    """`%fusion.12 = f32[8]{0} fusion(...)` -> `fusion.12`; the start
    of the right-hand side goes to `labels`."""
    name, sep, rest = hlo.partition(" = ")
    name = name.lstrip("%")
    if sep and labels is not None and name not in labels:
        labels[name] = rest[:keep]
    return name


# ---- interval arithmetic ---------------------------------------------
def merge(intervals):
    """Sorted union of half-open intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(window, union):
    """Length of `window` covered by the (merged) union."""
    s, e = window
    total = 0
    for us, ue in union:
        if ue <= s:
            continue
        if us >= e:
            break
        total += min(e, ue) - max(s, us)
    return total


def _intervals(events):
    return [(s, s + d) for _, s, d in events]


# ---- device busy / idle -----------------------------------------------
def busy_seconds(trace):
    """Seconds in which an operation or a program ran on the device:
    the union of the op line's and the module line's intervals,
    averaged over the devices traced. (On this chip the two lines
    cover the same time to 0.2 %; the module line holds a few hundred
    events where the op line holds hundreds of thousands, so it stays
    whole where a long trace cuts the op line short.)"""
    if "busy_s" not in trace:       # half a million intervals: once
        per_dev = [sum(e - s for s, e in merge(
            _intervals(d["ops"]) + _intervals(d["modules"])))
            for d in trace["devices"].values()]
        trace["busy_s"] = (sum(per_dev) / len(per_dev) / 1e9
                           if per_dev else 0.0)
    return trace["busy_s"]


# ---- per-program and per-operation time -------------------------------
def module_times(trace, pattern):
    """Device durations (seconds) of every run of the programs whose
    module name matches `pattern`, on the first device."""
    rx = re.compile(pattern)
    dev = _first_device(trace)
    if dev is None:
        return []
    return [d / 1e9 for n, _, d in dev["modules"] if rx.search(n)]


def op_seconds(trace, pattern):
    """(total seconds, count) of the operations whose name matches, on
    the first device."""
    rx = re.compile(pattern)
    dev = _first_device(trace)
    if dev is None:
        return 0.0, 0
    hits = [d for n, _, d in dev["ops"] if rx.search(n)]
    return sum(hits) / 1e9, len(hits)


def _first_device(trace):
    names = sorted(trace["devices"])
    return trace["devices"][names[0]] if names else None


def top_ops(trace, k=10):
    """[[name, seconds], ...]: the operations that took most device
    time on the first device, by the names the trace prints."""
    dev = _first_device(trace)
    if dev is None:
        return []
    tot = collections.defaultdict(int)
    for n, _, d in dev["ops"]:
        tot[n] += d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    labels = trace.get("labels", {})
    return [[f"{n} = {labels[n]}" if n in labels else n, v / 1e9]
            for n, v in best]


def idle_gaps(trace, k=10, min_ns=2_000):
    """[[what the host was doing, seconds], ...]: the first device's
    idle gaps (between merged op intervals), each given to the host
    event that overlaps it most, summed by that event's name."""
    dev = _first_device(trace)
    if dev is None:
        return []
    busy = merge(_intervals(dev["ops"]) + _intervals(dev["modules"]))
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])
            if b[0] - a[1] >= min_ns]
    host = sorted((s, s + d, n) for n, s, d in trace["host"])
    starts = [h[0] for h in host]
    longest = max((h[1] - h[0] for h in host), default=0)
    tot = collections.defaultdict(int)
    for gs, ge in gaps:
        best, best_name, best_len = 0, "(no host event)", 0
        lo = bisect.bisect_left(starts, gs - longest)
        hi = bisect.bisect_right(starts, ge)
        for hs, he, name in host[lo:hi]:
            ov = min(ge, he) - max(gs, hs)
            # the narrowest event that covers most of the gap says
            # most: prefer more overlap, then the shorter event
            if ov > best or (ov == best and ov > 0
                             and he - hs < best_len):
                best, best_name, best_len = ov, name, he - hs
        tot[best_name] += ge - gs
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / 1e9] for n, v in best]


# ---- collectives ------------------------------------------------------
def collective_overlap(trace):
    """Per device: time inside collective windows (an async pair's
    start-issue to done-retire; a sync op's own extent) and the part
    of it that no compute covers. Returns seconds, averaged over the
    devices: {"window_s", "exposed_s", "n"}; None with no collective.
    """
    win, exp, n = [], [], 0
    known = trace.get("collectives", {})
    for dev in trace["devices"].values():
        comm, compute = [], []
        starts = collections.defaultdict(collections.deque)
        for name, s, d in sorted(dev["ops"], key=lambda e: e[1]):
            iv = (s, s + d)
            kind = (known[name] if name in known
                    else collective_kind(name))
            if kind is None:
                compute.append(iv)
                continue
            if kind == "-start":
                starts[name.replace("-start", "-done", 1)].append(iv)
            elif kind == "-done":
                q = starts.get(name)
                siv = q.popleft() if q else None
                comm.append((siv[0] if siv else iv[0], iv[1]))
            else:
                comm.append(iv)
        for q in starts.values():
            comm.extend(q)
        if not comm:
            continue
        n += len(comm)
        cu, mc = merge(compute), merge(comm)
        win.append(sum(e - s for s, e in mc))
        exp.append(sum((e - s) - covered((s, e), cu) for s, e in mc))
    if not win:
        return None
    return {"window_s": sum(win) / len(win) / 1e9,
            "exposed_s": sum(exp) / len(exp) / 1e9, "n": n}


def summary(trace, k=12):
    """A few lines for a human: planes, lines, the commonest names."""
    out = []
    for plane, lines in trace.get("lines", {}).items():
        out.append(f"{plane}: " + ", ".join(
            f"{n} ({c})" for n, c in lines.items()))
    dev = _first_device(trace)
    if dev:
        for what in ("ops", "modules"):
            iv = _intervals(dev[what])
            if iv:
                out.append(
                    f"{what} line: {len(iv)} events over "
                    f"{(max(e for _, e in iv) - min(s for s, _ in iv)) / 1e9:.4f}"
                    f" s, their union "
                    f"{sum(e - s for s, e in merge(iv)) / 1e9:.4f} s")
        mods = collections.Counter(n for n, _, _ in dev["modules"])
        out.append("modules: " + ", ".join(
            f"{n} x{c}" for n, c in mods.most_common(k)))
    return out
