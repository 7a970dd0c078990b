"""Admission control: the bounded front door of the serving engine.

Robustness contract (the reference's background-coordinator lesson,
SURVEY §L2, applied to serving): under overload the engine DEGRADES BY
SHEDDING, never by hanging — a full queue rejects at `submit` time with
`QueueFullError` (the caller learns immediately and can retry
elsewhere), a request whose deadline passes while still queued is
failed with `DeadlineExceededError` the moment the dispatcher would
otherwise have started work it can no longer finish in time, and a
cancelled request is dropped at the next pop. Nothing here blocks the
submitting thread beyond one mutex.

Since the overload control plane landed (docs/serving.md "Overload
control"), the queue is no longer one FIFO: requests carry a
``priority`` (higher preempts lower at the block pool) and a
``tenant`` (the fairness/SLO isolation domain), and the queue keeps
one lane per (priority, tenant) pair. Selection is priority bands
first, then weighted fair queuing across tenants inside the band
(virtual-time accounting: each pop charges the tenant 1/weight, the
smallest virtual time goes next), with anti-starvation aging — a head
older than ``aging_s`` is served oldest-first REGARDLESS of band, so
a low-priority tenant under sustained high-priority load is delayed,
never starved. When explicit tenant weights are configured
(``HVD_TENANT_WEIGHTS``), each configured tenant's queue share is
also capped at its weight fraction of ``max_depth`` — one tenant's
burst sheds against its own share, not the fleet's. Single-tenant
default-priority traffic degenerates to the old FIFO exactly.
"""

from __future__ import annotations

import collections
import math
import threading
import time
from concurrent.futures import CancelledError, InvalidStateError
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from horovod_tpu.analysis import lockcheck


class ServingError(RuntimeError):
    """Base class for serving-engine errors."""


class QueueFullError(ServingError):
    """submit() found the admission queue at capacity — the request was
    shed immediately (load shedding, the degrade-don't-hang contract)."""


class DeadlineExceededError(ServingError, TimeoutError):
    """The request's deadline passed (in queue or mid-decode).

    ``partial_tokens`` carries whatever the engine had produced by
    then (empty for queue-expired requests) so a caller can still use
    a truncated answer.
    """

    def __init__(self, msg: str, partial_tokens: Optional[list] = None):
        super().__init__(msg)
        self.partial_tokens = partial_tokens or []


class EngineClosedError(ServingError):
    """submit() after shutdown, or the request was abandoned by a
    non-draining shutdown."""


@dataclass
class SamplingParams:
    """Per-request sampling configuration.

    ``temperature <= 0`` is greedy (argmax); otherwise softmax sampling
    from a per-request RNG stream seeded by ``seed``, optionally
    truncated to the ``top_p`` nucleus. (Per-request ``top_k`` would
    make the tick's compiled shape request-dependent — one program per
    k — so the continuous-batching tick deliberately offers the traced
    knobs only; use ``top_p``.)
    """

    temperature: float = 0.0
    top_p: Optional[float] = None
    seed: int = 0

    def validate(self):
        if self.temperature < 0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.top_p is not None and not 0 < self.top_p <= 1:
            raise ValueError(
                f"top_p must be in (0, 1], got {self.top_p}")


@dataclass
class Request:
    """One submitted generation request and its lifecycle state.

    Crosses the submit-thread / dispatch-thread boundary: the future
    and the cancel event are the only write points shared by both
    sides; everything else is owned by the dispatcher once admitted.
    """

    id: int
    prompt: Any                      # np.ndarray [P] int tokens
    max_new_tokens: int
    sampling: SamplingParams
    deadline: Optional[float]        # absolute time.time() or None
    future: Any                      # concurrent.futures.Future
    # Observability identity (docs/observability.md): minted once at
    # submit() and carried for the request's whole life — across the
    # queue, prefill chunks, pipelined ticks AND watchdog-restart
    # requeues (dataclasses.replace preserves it), so the event log,
    # span tree and metric exemplars all correlate on it.
    trace_id: str = ""
    t_submit: float = 0.0
    t_prefill: float = 0.0           # dispatcher: prefill started
    t_first: float = 0.0             # dispatcher: first token emitted
    # Prompt tokens the paged pool's prefix cache already held at
    # admission (prefill skipped them); 0 on the fixed pool and on
    # every cache miss. Set by the dispatcher, surfaced on
    # CompletedRequest — the per-request cache-hit evidence the
    # ci.sh --prefix-check reads.
    prefix_cached: int = 0
    # Token-exact continuation (docs/serving.md "Fleet failover"): a
    # request migrated off a dead replica is resubmitted with the
    # tokens it had already generated as a FORCED prefix — prefilled
    # (teacher-forced) into the cache after the prompt, counted
    # against max_new_tokens, and pre-seeded into ``tokens`` so the
    # caller's stream continues without a seam. The sample stream
    # resumes at ordinal len(forced) (`SlotPool.finish_prefill`'s
    # rng_skip), so the continuation is bitwise the original's.
    forced: tuple = ()
    tokens: List[int] = field(default_factory=list)  # generated so far
    # Overload control plane (docs/serving.md "Overload control"):
    # priority orders admission bands and bounds preemption (victims
    # are strictly LOWER-priority than the blocked head); tenant names
    # the WFQ lane, the shed-share cap and the per-tenant SLO domain.
    # Defaults put everyone in one best-effort lane — single-tenant
    # callers see plain FIFO.
    priority: int = 0
    tenant: str = ""
    # Causal span plumbing (obs/spans.py): ``parent_span`` is the
    # caller's span this engine leg hangs under (a router attempt, a
    # disagg root; "" = this engine minted the trace and owns the
    # root). ``span_ids`` maps the leg's OPEN span slots ("root",
    # "queued", "prefill", "decode", "paused") to span ids; a shared
    # MUTABLE dict on purpose — dataclasses.replace (preemption
    # resume, restart requeue) copies the reference, so the resumed
    # leg closes the spans its predecessor opened.
    parent_span: str = ""
    span_ids: Dict = field(default_factory=dict, repr=False,
                           compare=False)
    _cancel: threading.Event = field(default_factory=threading.Event)
    # Set by AdmissionQueue.offer/requeue: lets cancel() release the
    # queue slot IMMEDIATELY instead of at the next dispatcher sweep
    # (hedging cancels queued losers and needs the capacity back now).
    _on_cancel: Any = field(default=None, repr=False, compare=False)

    @property
    def full_prompt(self) -> np.ndarray:
        """prompt ++ forced — what actually prefills into the cache
        (and what the paged pool's prefix matcher sees)."""
        if not self.forced:
            return np.asarray(self.prompt)
        return np.concatenate([
            np.asarray(self.prompt),
            np.asarray(self.forced, np.asarray(self.prompt).dtype)])

    @property
    def remaining_new(self) -> int:
        """Decode budget left after the forced prefix."""
        return self.max_new_tokens - len(self.forced)

    def cancel(self):
        """Request cancellation. Queued requests are dropped (and
        their admission slot released) immediately; running requests
        retire (freeing their slot) at the next decode tick. The
        future then raises `concurrent.futures.CancelledError`."""
        self._cancel.set()
        cb = self._on_cancel
        if cb is not None:
            cb(self)

    @property
    def cancelled(self) -> bool:
        return self._cancel.is_set()

    def expired(self, now: Optional[float] = None) -> bool:
        return (self.deadline is not None
                and (now if now is not None else time.time())
                >= self.deadline)


class AdmissionQueue:
    """Bounded priority/WFQ queue between `submit()` and the dispatch
    thread.

    `offer` never blocks (full ⇒ `QueueFullError`); `pop_ready` is the
    dispatcher's non-blocking take that resolves dead requests
    (cancelled / deadline-expired) on the way instead of wasting a
    prefill on them; `wait` parks the idle dispatcher until work (or
    shutdown) arrives. Internally one deque lane per
    (priority, tenant): selection is aged-head-first (anti-starvation,
    oldest wins globally once past ``aging_s``), then highest priority
    band, then the tenant with the smallest WFQ virtual time inside
    the band (each pop charges 1/weight). With no priorities, tenants
    or weights in play there is exactly one lane and every method
    behaves as the original FIFO did.
    """

    def __init__(self, max_depth: int, *,
                 tenant_weights: Optional[dict] = None,
                 aging_s: Optional[float] = 5.0):
        if max_depth < 1:
            raise ValueError(f"max_depth must be >= 1, got {max_depth}")
        self.max_depth = max_depth
        for t, w in (tenant_weights or {}).items():
            if not w > 0:
                raise ValueError(
                    f"tenant weight must be > 0, got {t!r}={w!r}")
        self._weights = dict(tenant_weights or {})
        # None disables aging (pure priority/WFQ order).
        self.aging_s = aging_s
        # (priority, tenant) -> deque of Requests, oldest left. Lanes
        # are created on first offer and deleted when empty so
        # selection iterates live lanes only.
        self._lanes: dict = {}
        self._n = 0
        # WFQ virtual-time accounting: per-tenant finish tags plus the
        # global virtual clock lanes re-anchor to when they go idle
        # (an idle tenant must not bank unbounded credit).
        self._vtime: dict = {}
        self._vclock = 0.0
        self._lock = lockcheck.register(
            "AdmissionQueue._lock", threading.Lock())
        self._event = threading.Event()
        self._closed = False
        # Metrics/tracing hook for drops resolved OUTSIDE a dispatcher
        # call (the cancel fast path below); the scheduler installs
        # its `_queue_drop` here so a cancel-released request is
        # counted exactly like a swept one.
        self.on_drop = None

    def __len__(self) -> int:
        return self._n

    def snapshot(self) -> List[Request]:
        """The queued requests, oldest first — a consistent copy for
        read-only introspection (the flight recorder's in-flight
        provider). The Requests themselves stay live; callers must
        not mutate them."""
        with self._lock:
            reqs = [r for dq in self._lanes.values() for r in dq]
        return sorted(reqs, key=lambda r: (r.t_submit, r.id))

    # -- WFQ internals (lock held) ------------------------------------

    def _tenant_cap(self, tenant: str) -> Optional[int]:
        """Queue-share cap for a CONFIGURED tenant: its weight
        fraction of max_depth (>= 1 so a configured tenant can always
        queue something). Unconfigured tenants are bounded only by
        the global depth — caps exist to stop a named tenant's burst
        from squeezing the others, not to strand capacity."""
        if not self._weights or tenant not in self._weights:
            return None
        total = sum(self._weights.values())
        share = self.max_depth * self._weights[tenant] / total
        return max(1, math.ceil(share))

    def _tenant_depth(self, tenant: str) -> int:
        return sum(len(dq) for (_, t), dq in self._lanes.items()
                   if t == tenant)

    def _select_locked(self, now: float):
        """The lane to serve next, or None when empty. Aged heads win
        globally oldest-first (starvation-freedom: every queued
        request's age only grows, so it eventually becomes the oldest
        aged head and is served); otherwise highest priority band,
        then smallest tenant virtual time, then tenant name."""
        best_aged = None
        best = None
        for key, dq in self._lanes.items():
            if not dq:
                continue
            prio, tenant = key
            head = dq[0]
            if (self.aging_s is not None
                    and now - head.t_submit >= self.aging_s):
                cand = (head.t_submit, -prio, tenant)
                if best_aged is None or cand < best_aged[0]:
                    best_aged = (cand, key)
            v = max(self._vtime.get(tenant, 0.0), self._vclock)
            cand = (-prio, v, tenant)
            if best is None or cand < best[0]:
                best = (cand, key)
        if best_aged is not None:
            return best_aged[1]
        return None if best is None else best[1]

    def _charge_locked(self, tenant: str):
        """One pop's WFQ charge: advance the tenant's virtual finish
        tag by 1/weight from max(own tag, virtual clock) — the
        re-anchor forgets credit a lane banked while idle."""
        w = float(self._weights.get(tenant, 1.0))
        v = max(self._vtime.get(tenant, 0.0), self._vclock)
        self._vclock = v
        self._vtime[tenant] = v + 1.0 / w

    @property
    def closed(self) -> bool:
        return self._closed

    def offer(self, req: Request):
        with self._lock:
            if self._closed:
                raise EngineClosedError(
                    "engine is shut down; submit rejected")
            cap = self._tenant_cap(req.tenant)
            if cap is not None and self._tenant_depth(req.tenant) >= cap:
                raise QueueFullError(
                    f"tenant {req.tenant!r} queue share full "
                    f"({cap} of {self.max_depth}); request "
                    f"{req.id} shed")
            if self._n >= self.max_depth:
                raise QueueFullError(
                    f"admission queue full ({self.max_depth} requests "
                    f"waiting); request {req.id} shed")
            lane = self._lanes.setdefault(
                (req.priority, req.tenant), collections.deque())
            lane.append(req)
            self._n += 1
            # Armed under the lock so a cancel landing after submit
            # returns finds the request already discardable.
            req._on_cancel = self._discard_cancelled
        self._event.set()

    def _discard_cancelled(self, req: Request):
        """`Request.cancel()`'s fast path: drop a still-queued request
        and release its admission slot NOW, not at the dispatcher's
        next sweep — a hedge's cancelled loser must not hold queue
        capacity against live traffic. No-op if the dispatcher already
        popped it (the running-request cancel path retires it at the
        next tick as before)."""
        key = (req.priority, req.tenant)
        with self._lock:
            dq = self._lanes.get(key)
            if dq is None:
                return   # lane gone — the dispatcher owns the request
            try:
                dq.remove(req)
            except ValueError:
                return   # already popped/swept — the dispatcher owns it
            self._n -= 1
            if not dq:
                del self._lanes[key]
        self._resolve_dead(req, "cancelled", time.time(), self.on_drop)

    @staticmethod
    def _resolve_dead(req: Request, kind: str, now: float, on_drop):
        try:
            if kind == "cancelled":
                req.future.set_exception(CancelledError())
            else:
                req.future.set_exception(DeadlineExceededError(
                    f"request {req.id}: deadline passed after "
                    f"{now - req.t_submit:.3f}s in queue"))
        except InvalidStateError:
            return   # cancel raced another resolver; first one counted
        if on_drop is not None:
            on_drop(req, kind)

    def _next_ready(self, now: float, on_drop,
                    pop: bool) -> Optional[Request]:
        """THE head-drain loop behind both `peek_ready` and
        `pop_ready`: dead requests (cancelled / deadline-expired) at
        the head are removed and resolved inline either way; the
        first live one is returned, removed only when ``pop``.
        Single-consumer contract (the dispatch thread) — submitters
        only ever append, so a peeked head stays selected until this
        thread pops it, it dies, or a NEW offer changes the selection
        (the scheduler's peek-check-pop admission gate tolerates the
        pop returning a different, higher-ranked request: `admit`
        returning None requeues it at the front of its lane)."""
        while True:
            with self._lock:
                if not self._n:
                    self._event.clear()
                    return None
                key = self._select_locked(now)
                dq = self._lanes[key]
                req = dq[0]
                dead = req.cancelled or req.expired(now)
                if dead or pop:
                    dq.popleft()
                    self._n -= 1
                    if not dq:
                        del self._lanes[key]
                    if not dead:
                        self._charge_locked(key[1])
            if not dead:
                return req
            self._resolve_dead(
                req, "cancelled" if req.cancelled else "timeout",
                now, on_drop)

    def peek_ready(self, now: float, on_drop=None) -> Optional[Request]:
        """The next live request WITHOUT removing it — the paged
        pool's admission gate peeks, checks block affordability
        (`can_admit`), and only then pops, so a request that does not
        fit yet stays at the queue head (FIFO preserved, no
        pop/requeue churn) while dead requests ahead of it still
        resolve inline exactly as `pop_ready` would."""
        return self._next_ready(now, on_drop, pop=False)

    def pop_ready(self, now: float, on_drop=None) -> Optional[Request]:
        """Next live request, resolving cancelled/expired ones inline
        (``on_drop(req, kind)`` with kind "cancelled"/"timeout" fires
        for each, for metrics/tracing); None when the queue holds no
        admissible work."""
        return self._next_ready(now, on_drop, pop=True)

    def requeue(self, reqs: List[Request]) -> int:
        """Recovery-path re-admission (engine watchdog restart): put
        `reqs` at the FRONT of the queue in their original order —
        they were admitted once already, so they bypass the depth
        bound and keep their head start over later submits. If the
        queue closed while the watchdog was working, the requests are
        failed with `EngineClosedError` instead (never silently
        dropped). Returns how many were re-admitted."""
        if not reqs:
            return 0
        with self._lock:
            doomed = list(reqs) if self._closed else []
            if not self._closed:
                for r in reversed(reqs):
                    lane = self._lanes.setdefault(
                        (r.priority, r.tenant), collections.deque())
                    lane.appendleft(r)
                    self._n += 1
                    r._on_cancel = self._discard_cancelled
        for req in doomed:
            if not req.future.done():
                req.future.set_exception(EngineClosedError(
                    f"engine shut down while request {req.id} awaited "
                    f"requeue"))
        self._event.set()
        return len(reqs) - len(doomed)

    def force_expire(self, now: float) -> int:
        """Chaos site ``serving_deadline_storm``'s hammer: every queued
        request's deadline collapses to `now`, so the next sweep fails
        them all with `DeadlineExceededError` at once — the thundering-
        expiry worst case for the dispatcher. Returns how many
        deadlines were tightened."""
        with self._lock:
            n = 0
            for dq in self._lanes.values():
                for r in dq:
                    if r.deadline is None or r.deadline > now:
                        r.deadline = now
                        n += 1
        return n

    def sweep(self, now: float, on_drop=None) -> int:
        """Resolve cancelled/expired requests ANYWHERE in the queue —
        dying needs no slot, so the dispatcher runs this every tick:
        a queued request's deadline/cancel must not wait for a slot to
        free before its future resolves (the never-hang contract with
        every slot busy). Returns how many were resolved."""
        with self._lock:
            dead = []
            for key in list(self._lanes):
                dq = self._lanes[key]
                doomed = [r for r in dq
                          if r.cancelled or r.expired(now)]
                if not doomed:
                    continue
                dead.extend(doomed)
                gone = set(map(id, doomed))
                kept = collections.deque(
                    r for r in dq if id(r) not in gone)
                self._n -= len(doomed)
                if kept:
                    self._lanes[key] = kept
                else:
                    del self._lanes[key]
        for req in dead:
            self._resolve_dead(
                req, "cancelled" if req.cancelled else "timeout",
                now, on_drop)
        return len(dead)

    def wait(self, timeout: float) -> bool:
        """Park until offer()/close() signals (True) or timeout."""
        signalled = self._event.wait(timeout)
        return signalled

    def close(self, drain: bool) -> List[Request]:
        """Stop admissions. ``drain=False`` additionally fails every
        queued request with `EngineClosedError` right now (the failed
        requests are returned for metrics); with ``drain=True`` the
        dispatcher keeps popping until empty."""
        with self._lock:
            self._closed = True
            doomed = ([] if drain else
                      [r for dq in self._lanes.values() for r in dq])
            if not drain:
                self._lanes.clear()
                self._n = 0
        for req in doomed:
            req.future.set_exception(EngineClosedError(
                f"engine shut down before request {req.id} started"))
        self._event.set()
        return doomed
