"""Slot-pool KV cache: the device state behind continuous batching.

The linear decode cache (`parallel.tensor.ParallelSelfAttention`,
``decode=True``) keeps ONE scalar ``cache_index`` shared by the whole
batch — every row must sit at the same fill level, which is exactly
what continuous batching breaks (each slot holds a different request
at a different depth). `models.transformer`'s slot primitives
generalize that cache to a pool: every leaf gains a leading
[num_slots] axis (the per-layer fill scalars become per-slot vectors),
prefill streams a prompt into ONE slot through the `chunked_prefill`
cache-wide-mask path, and the decode tick vmaps the B=1 decode step
over the slot axis. This module wraps those primitives with the
host-side bookkeeping the scheduler needs: a free list, per-slot
sampling state (temperature / top_p / RNG stream), per-slot live/done
occupancy flags, and reset-on-retire hygiene.

Slot lifecycle::

    FREE --alloc()--> begin_prefill() [reset]
      ^                 --prefill_chunk()*--> finish_prefill()
      |                                           |  (live flag set)
      +------------------- free() <--- ACTIVE --tick_dispatch()*

Hot-path pipelining (the PR-3 rebuild): the decode tick is split into
`tick_dispatch()` (enqueue the vmapped tick + start an async
device->host copy of the token buffer) and `tick_sync(handle)` (the
blocking read). The scheduler dispatches tick N+1 BEFORE syncing tick
N, so the host-side bookkeeping and the transfer hide behind the
device's compute — one exposed host sync per token becomes ~one per
request. Occupancy is device state too: a ``live`` mask freezes the
fill index of FREE and mid-prefill lanes (no idle creep, no corruption
of a half-streamed prompt), and a ``done`` flag implements on-device
stop detection — a lane that emitted eos keeps emitting eos, so the
host can retire a pipeline-depth late purely from the async token
buffer.
"""

from __future__ import annotations

import contextlib
import time
import warnings
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from horovod_tpu.annotations import hot_path
from horovod_tpu.models.transformer import (
    TransformerLM, chunk_width, init_slot_cache, kernel_plans,
    moe_stat_columns, prefill_chunks, recurrent_leaf, sample_lanes,
    shard_slot_cache, slot_decode_model, slot_decode_tick,
    slot_prefill_advance, slot_prefill_chunk, slot_reset,
    slot_spec_round,
)
from horovod_tpu.parallel.mesh import replicate, use


def validate_spec_draft(model: TransformerLM, spec_draft,
                        spec_k: int):
    """Shared spec-decode construction checks (both pools and the
    engine): the draft must share the target's vocab, neither model
    may roll a sliding-window cache (rewind would overwrite live
    slots — `models.speculative`'s constraint), the draft cache must
    cover every position the target can reach, and k must leave room
    for at least one proposal."""
    draft_model, _ = spec_draft
    if spec_k < 1:
        raise ValueError(f"spec_k must be >= 1, got {spec_k}")
    if draft_model.vocab_size != model.vocab_size:
        raise ValueError(
            f"spec draft vocab ({draft_model.vocab_size}) != target "
            f"vocab ({model.vocab_size})")
    if model.has_rolling_cache or draft_model.has_rolling_cache:
        raise ValueError(
            "speculative decoding cannot rewind a sliding-window "
            "(rolling) cache; use models without a window on any "
            "layer")
    if draft_model.max_len < model.max_len:
        raise ValueError(
            f"spec draft max_len ({draft_model.max_len}) must cover "
            f"the target's ({model.max_len})")


@jax.jit
def _first_token(logits, temp, top_p, key, skips):
    """First-token sample closing the prefill: split the request key
    exactly as `generate` does (``rng, r0 = split(key)``; the tick
    keeps splitting ``rng``), so a request's sample stream is
    reproducible from its seed regardless of which slot it lands in or
    what else shares the batch.

    ``skips`` (traced int32, normally 0) advances the key by that many
    carry-splits FIRST — the forced-prefix continuation hook
    (docs/serving.md "Fleet failover"): a request resubmitted with its
    first k generated tokens folded into the prompt must sample token
    k+1 from the SAME r_k the original stream would have used, since
    the per-request stream is keyed by token ordinal (each token
    consumes one ``rng, r = split(rng)``), not by position. A traced
    bound keeps this one compiled program for every k.

    The draw is `sample_lanes` over a batch of this one row: a greedy
    request pays an argmax, and only a request with ``top_p < 1``
    pays the sort of its vocabulary."""
    key = jax.lax.fori_loop(
        0, skips, lambda i, k: jax.random.split(k)[0], key)
    rng, r0 = jax.random.split(key)
    tok = sample_lanes(logits[None], temp[None], top_p[None],
                       r0[None])[0]
    return tok.astype(jnp.int32), rng


def __getattr__(name):
    """Deprecation shim for the PR-1/2 idle-reset machinery. The PR-3
    tick freezes non-live lanes' fill indices ON DEVICE
    (`slot_decode_tick`'s ``live`` mask), so idle creep is exactly 0,
    no periodic reset runs, and the old ceiling constant is
    meaningless — importers get the historical value plus a warning
    until they migrate."""
    if name == "RESET_IDLE_TICKS":
        warnings.warn(
            "RESET_IDLE_TICKS is obsolete: idle lanes' fill indices "
            "are frozen on device since the PR-3 tick (live mask) — "
            "idle creep is 0 and no periodic reset exists to bound",
            DeprecationWarning, stacklevel=2)
        return 64
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


@dataclass(frozen=True)
class Admission:
    """One granted admission: the decode lane, how many prompt tokens
    the KV cache already holds (``skipped`` — prefill starts there; 0
    outside the paged pool's prefix cache), and the block-level hit
    accounting behind it (`serving.paging`)."""

    slot: int
    skipped: int = 0          # prompt tokens covered by matched blocks
    matched_blocks: int = 0   # prefix blocks pinned from the cache
    queried_blocks: int = 0   # block-aligned prefix blocks looked up


class TickHandle:
    """One in-flight decode tick: the device token buffer (its host
    copy already started via `copy_to_host_async`). `tick_sync` turns
    it into the [num_slots] numpy vector."""

    __slots__ = ("toks", "moe_pairs", "moe_prefill_pairs",
                 "routed_columns")

    def __init__(self, toks, moe_pairs=None, moe_prefill_pairs=(),
                 routed_columns=()):
        self.toks = toks
        # names of the counts that the pair arrays' last columns hold
        # (`models.transformer.moe_stat_columns`; () for most models)
        self.routed_columns = routed_columns
        # int32 [expert layers, experts held] (token, expert) pairs of
        # this tick's decoding lanes (None for a model without a
        # dropless expert layer), and one such array for each prefill
        # chunk since the last tick
        self.moe_pairs = moe_pairs
        self.moe_prefill_pairs = moe_prefill_pairs


class SlotPool:
    """A fixed pool of ``num_slots`` decode slots over one shared
    slot-pool KV cache.

    All device work (prefill chunks, the vmapped tick, slot resets)
    happens on the caller's thread — the engine's dispatch thread —
    so jax never sees concurrent mutation of the pool state.

    ``eos_id`` arms on-device stop detection (None = disabled): the
    tick itself masks lanes that have emitted eos, so a finished slot
    can never leak a post-eos token to the host even when retirement
    lags a pipelined tick behind.
    """

    def __init__(self, model: TransformerLM, params, num_slots: int,
                 *, mesh=None, eos_id: Optional[int] = None,
                 spec_draft=None, spec_k: int = 0):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.model = model
        self.dec_model = slot_decode_model(model)
        self.params = params
        self.num_slots = num_slots
        self.mesh = mesh
        self.eos_id = eos_id
        self._eos = jnp.int32(-1 if eos_id is None else eos_id)
        self._cache = init_slot_cache(model, num_slots)
        # Speculative decoding (docs/serving.md "Decode fast path"):
        # ``spec_draft`` = (draft_model, draft_params) arms the
        # draft-verify round — the tick is then `spec_round`, retiring
        # 1..k+1 tokens per lane per round, greedy-only. The draft
        # rides its own linear slot cache, prefilled chunk-for-chunk
        # alongside the target's.
        self.spec_draft = spec_draft
        self.spec_k = int(spec_k) if spec_draft is not None else 0
        self.drf_model = self.drf_params = self._drf_cache = None
        if self.spec_on:
            validate_spec_draft(model, spec_draft, self.spec_k)
            draft_model, draft_params = spec_draft
            self.drf_model = slot_decode_model(draft_model)
            self.drf_params = draft_params
            self._drf_cache = init_slot_cache(draft_model, num_slots)
        self._prefill_pairs = []     # see prefill_chunk / tick_dispatch
        self._toks = jnp.zeros((num_slots,), jnp.int32)
        self._temps = jnp.zeros((num_slots,), jnp.float32)
        self._top_ps = jnp.ones((num_slots,), jnp.float32)
        self._rngs = jnp.stack(
            [jax.random.PRNGKey(i) for i in range(num_slots)])
        # Device occupancy: live gates fill-index advance (FREE and
        # mid-prefill lanes frozen), done is the on-device stop flag.
        self._live = jnp.zeros((num_slots,), bool)
        self._done = jnp.zeros((num_slots,), bool)
        self._free: List[int] = list(range(num_slots))
        # Sharded serving (docs/serving.md "Sharded serving"): commit
        # the KV cache sharded along the heads axis and replicate the
        # per-lane decision vectors across the mesh, so every jitted
        # slot primitive runs GSPMD-partitioned under `use(mesh)` —
        # the PROGRAM is unchanged; the sharding enters through the
        # committed operand layouts. One host decision (slot ids,
        # sampling state) drives all shards.
        if mesh is not None:
            self._cache = shard_slot_cache(self._cache, mesh)
            if self._drf_cache is not None:
                self._drf_cache = shard_slot_cache(self._drf_cache,
                                                   mesh)
            (self._toks, self._temps, self._top_ps, self._rngs,
             self._live, self._done, self._eos) = replicate(
                mesh, (self._toks, self._temps, self._top_ps,
                       self._rngs, self._live, self._done, self._eos))
        # Compile awareness for the engine watchdog: True while a
        # device call whose shape this pool has not executed before is
        # in flight — a first-time XLA compile can take arbitrarily
        # long and must not read as a stuck tick (stuck detection is
        # suppressed while set). Shapes already seen are jit-cache
        # hits, so the flag clears in microseconds for warm calls.
        self.maybe_compiling = False
        self._seen_shapes: set = set()
        # First-time-shape count for this pool (warmup + hot path);
        # the engine subtracts its post-warmup baseline to report
        # hot-path compiles (the "no compile in the timed window"
        # guarantee ci.sh asserts).
        self.compiles = 0
        # Brownout rung >= 2 (docs/serving.md "Overload control"):
        # caps the speculative k mid-stream — greedy spec decode is
        # bitwise for ANY k, so the cap sheds draft compute without
        # touching token streams (one extra compile per new k).
        self.spec_cap = None

    @property
    def spec_on(self) -> bool:
        return self.spec_draft is not None and self.spec_k > 0

    def _ctx(self):
        return use(self.mesh) if self.mesh is not None \
            else contextlib.nullcontext()

    def kernel_plans(self, chunk: int = 1) -> dict:
        """Which program this pool's ticks, and its prompt chunks of
        ``chunk`` tokens, step each kind of layer with and why:
        `models.transformer.kernel_plans` under the pool's mesh."""
        with self._ctx():
            return kernel_plans(self.model, self.num_slots, chunk)

    def _note_shape(self, key):
        if key not in self._seen_shapes:
            self.compiles += 1
            self._seen_shapes.add(key)
            # Observability: compiles are discrete operator-visible
            # events (a compile inside a warmed serving window is a
            # bug ci.sh asserts against) — count them process-wide
            # and log which program shape triggered.
            from horovod_tpu.obs import catalog as _obs_catalog
            from horovod_tpu.obs import events as _events
            _obs_catalog.serving_metrics()["compiles"].inc()
            _events.emit("serving.compile", shape=repr(key))

    def clone_fresh(self) -> "SlotPool":
        """A brand-new pool over the same model/params/mesh — the
        engine watchdog's restart primitive (docs/resilience.md). The
        old pool may be mid-tick in a hung dispatch thread, so its
        cache and free-list are untrusted; a clone starts from zeroed
        slots. Compiled tick/prefill programs are keyed by the model
        config and shapes, both unchanged, so the clone recompiles
        nothing."""
        fresh = SlotPool(self.model, self.params, self.num_slots,
                         mesh=self.mesh, eos_id=self.eos_id,
                         spec_draft=self.spec_draft,
                         spec_k=self.spec_k)
        # The jit cache is process-global: shapes this pool compiled
        # are warm for the clone too (and the compile count carries,
        # so hot-path-compile accounting survives a restart).
        fresh._seen_shapes = set(self._seen_shapes)
        fresh.spec_cap = self.spec_cap
        fresh.compiles = self.compiles
        return fresh

    def fill_indices(self) -> np.ndarray:
        """Per-slot cache fill index, maxed across layers (and the
        pos_index at learned-position models) — introspection for
        tests and debugging (e.g. asserting idle lanes stay at 0)."""
        from jax.tree_util import tree_flatten_with_path
        flat, _ = tree_flatten_with_path(self._cache)
        idx = [np.asarray(leaf) for path, leaf in flat
               if "index" in str(path)]
        assert idx, "slot cache has no index leaves"
        return np.max(np.stack(idx), axis=0)

    def cache_bytes(self) -> dict:
        """Device bytes of the pool's cache by kind: `kv` (keys and
        values of the full-attention layers, appended to: `max_len`
        rows a lane), `kv_window` (keys and values of the
        sliding-window layers, a ring of `window` rows a lane that
        later positions overwrite in place; 0 for a model without
        one), `state` (a recurrent layer's state and convolution
        tail - `models.transformer.RECURRENT_KINDS` - overwritten each
        step; 0 likewise) and - for a model
        with latent-attention layers only - `latent` (their rows,
        appended to like K/V but without a head axis, as stored:
        `LatentSpec.stored` numbers a position). The fill indices are
        not counted."""
        from jax.tree_util import tree_flatten_with_path
        out = {"kv": 0, "kv_window": 0, "state": 0}
        if self.model.has_latent_cache:
            out["latent"] = 0
        kinds = self.model.kinds
        rolls = {f"block_{i}" for i, kind in enumerate(kinds)
                 if kind in self.model.softmax_kinds
                 and self.model.attn_spec(kind).window is not None}
        for path, leaf in tree_flatten_with_path(self._cache)[0]:
            if "index" in str(path):
                continue
            if recurrent_leaf(path):
                kind = "state"
            elif getattr(path[-1], "key", None) == "cached_latent":
                kind = "latent"
            elif getattr(path[0], "key", None) in rolls:
                kind = "kv_window"
            else:
                kind = "kv"
            out[kind] += int(leaf.nbytes)
        return out

    # -- occupancy ----------------------------------------------------

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def busy_slots(self) -> int:
        return self.num_slots - len(self._free)

    def has_free(self) -> bool:
        return bool(self._free)

    # -- lifecycle ----------------------------------------------------

    def alloc(self) -> Optional[int]:
        """Claim a free slot; None when the pool is full. The slot's
        device rows are NOT assumed clean — `begin_prefill` re-zeroes
        them at use time."""
        if not self._free:
            return None
        return self._free.pop()

    def can_admit(self, prompt, max_new: int) -> bool:
        """Scheduler admission gate (shared protocol with the paged
        pool): the fixed pool's only capacity axis is free slots —
        every slot already reserves max_len KV rows, so prompt/budget
        never constrain further."""
        del prompt, max_new
        return self.has_free()

    def admit(self, prompt, max_new: int) -> Optional[Admission]:
        """Claim a slot for one request (shared protocol with
        `serving.paging.PagedSlotPool`, where this is also where
        blocks are reserved and the prompt's prefix is matched). The
        fixed pool never skips prefix tokens."""
        del prompt, max_new
        slot = self.alloc()
        return None if slot is None else Admission(slot=slot)

    def begin_prefill(self, slot: int):
        """Zero ``slot``'s rows and clear its live/done flags — the
        mandatory preamble before streaming a prompt in. The reset
        makes admission self-contained (a slot is correct to prefill
        whatever its history: clone restarts, crashed predecessors,
        direct pool use)."""
        self.maybe_compiling = ("reset",) not in self._seen_shapes
        try:
            with self._ctx():
                self._cache = slot_reset(self.dec_model, self._cache,
                                         jnp.int32(slot))
                if self.spec_on:
                    self._drf_cache = slot_reset(
                        self.drf_model, self._drf_cache,
                        jnp.int32(slot))
                self._live = self._live.at[slot].set(False)
                self._done = self._done.at[slot].set(False)
            self._note_shape(("reset",))
        finally:
            self.maybe_compiling = False

    def chunk_width(self, max_chunk: Optional[int]) -> Optional[int]:
        """The positions of this pool's chunk programs under a budget
        of ``max_chunk`` prompt tokens a step
        (`models.transformer.chunk_width`, within the cache's rows);
        None without a budget."""
        return chunk_width(max_chunk, self.model.max_len)

    def prefill_schedule(self, length: int,
                         max_chunk: Optional[int] = None) -> List[int]:
        """THE chunk schedule of a prompt of ``length`` tokens in this
        pool: the real tokens of each chunk (`prefill_chunks` at
        `chunk_width`) - whole chunks and at most one tail under a
        budget, a binary decomposition without one. The scheduler, a
        restart's replay, `prefill` and `warmup` all read it here, so
        the warm-up compiles exactly what the scheduler can emit and a
        replay streams the chunks the first run did."""
        return prefill_chunks(length, self.chunk_width(max_chunk))

    def prefill_chunk(self, slot: int, chunk,
                      width: Optional[int] = None):
        """Append one prompt chunk (1-D int tokens, a length of
        `prefill_schedule`) into ``slot``'s cache; returns the
        logits of the chunk's last token (a DEVICE array — no host
        sync). A chunk shorter than ``width`` (`chunk_width`: the tail
        of a prompt under a budget) is padded to it and runs as the
        tail program, its true length a traced operand, so every tail
        length is one compiled program; the pads leave the cache as
        they found it. The slot stays non-live, so interleaved decode
        ticks freeze its fill index and the next chunk lands exactly
        where this one stopped."""
        # hvd: disable=HVD001(chunk is host-side prompt tokens from the admission queue, never a device array — no sync)
        chunk = np.asarray(chunk, np.int32)
        c = int(chunk.shape[0])
        args, shape = (), ("prefill", c)
        if width is not None and c < width:
            chunk = np.pad(chunk, (0, width - c))
            # (a numpy scalar rides the call's own argument handling:
            # no device put of its own on the dispatch thread)
            args, shape = (np.int32(c),), ("prefill", width, "tail")
        self.maybe_compiling = shape not in self._seen_shapes
        try:
            with self._ctx():
                self._cache, logits, pairs = slot_prefill_chunk(
                    self.dec_model, self.params, self._cache,
                    jnp.int32(slot), jnp.asarray(chunk), *args)
                if pairs.size:
                    # on the device until the next tick's copy takes it
                    self._prefill_pairs.append(pairs)
                if self.spec_on:
                    # The draft's cache must hold the SAME prompt as
                    # the target's before any round — same chunk
                    # schedule, advance-only (no logits: the first
                    # token is always the target's).
                    self._drf_cache = slot_prefill_advance(
                        self.drf_model, self.drf_params,
                        self._drf_cache, jnp.int32(slot),
                        jnp.asarray(chunk), *args)
            self._note_shape(shape)
            return logits
        finally:
            self.maybe_compiling = False

    def finish_prefill(self, slot: int, logits, temperature: float,
                       top_p: Optional[float], seed: int, *,
                       rng_skip: int = 0) -> int:
        """Close a prefill: sample the request's FIRST token from the
        final chunk's ``logits``, install the slot's tick-side
        sampling state, and mark the lane live. The int() readback is
        the one per-request host sync (TTFT wants the token now).
        ``rng_skip`` (default 0) resumes the request's sample stream
        ``rng_skip`` tokens in — the forced-prefix continuation used
        by token-exact request migration (`_first_token`)."""
        self.maybe_compiling = (
            ("first_token",) not in self._seen_shapes)
        try:
            with self._ctx():
                temp = jnp.float32(temperature)
                tp = jnp.float32(1.0 if top_p is None else top_p)
                tok, rng = _first_token(logits, temp, tp,
                                        jax.random.PRNGKey(seed),
                                        jnp.int32(rng_skip))
                self._note_shape(("first_token",))
                self._toks = self._toks.at[slot].set(tok)
                self._temps = self._temps.at[slot].set(temp)
                self._top_ps = self._top_ps.at[slot].set(tp)
                self._rngs = self._rngs.at[slot].set(rng)
                self._live = self._live.at[slot].set(True)
                # Mirror generate's done0: a first token that IS eos
                # arms the on-device stop immediately, so even the
                # first tick can only re-emit eos for this lane.
                self._done = self._done.at[slot].set(tok == self._eos)
                # hvd: disable=HVD001(the ONE designed per-request sync — TTFT wants the first token now; docs/serving.md)
                return int(tok)
        finally:
            self.maybe_compiling = False

    def prefill(self, slot: int, prompt, temperature: float,
                top_p: Optional[float], seed: int, *,
                max_chunk: Optional[int] = None) -> int:
        """Stream ``prompt`` (1-D int tokens) into ``slot`` in one
        call and return the request's FIRST generated token — the
        begin/chunks/finish composition for callers that do not
        interleave (tests, simple drivers). Chunks follow
        `prefill_schedule`: under ``max_chunk`` whole chunks and one
        padded tail (two compiled programs), without it the binary
        decomposition (at most log2(max_len) programs) — never one
        program per prompt length."""
        prompt = np.asarray(prompt)
        self.begin_prefill(slot)
        logits = None
        off = 0
        width = self.chunk_width(max_chunk)
        for c in self.prefill_schedule(int(prompt.shape[0]), max_chunk):
            logits = self.prefill_chunk(slot, prompt[off:off + c], width)
            off += c
        return self.finish_prefill(slot, logits, temperature, top_p,
                                   seed)

    # -- the tick (split for pipelining) ------------------------------

    @hot_path
    def tick_dispatch(self) -> TickHandle:
        """Enqueue one vmapped decode tick over every slot and start
        the async device->host copy of its token buffer; returns
        immediately (jax async dispatch). Pair with `tick_sync` —
        ideally AFTER dispatching the next tick, so the transfer and
        the host bookkeeping hide behind device compute."""
        self.maybe_compiling = ("tick",) not in self._seen_shapes
        try:
            with self._ctx():
                (self._cache, self._toks, self._rngs,
                 self._done, pairs) = slot_decode_tick(
                    self.dec_model, self.params, self._cache,
                    self._toks, self._temps, self._top_ps, self._rngs,
                    self._live, self._done, self._eos)
            self._note_shape(("tick",))
        finally:
            self.maybe_compiling = False
        toks = self._toks
        toks.copy_to_host_async()
        if not pairs.size:
            return TickHandle(toks)
        # The expert layers' pair counts ride the same asynchronous
        # copy as the tokens: this tick's, and those of the prefill
        # chunks since the last tick.
        prefill, self._prefill_pairs = self._prefill_pairs, []
        for a in (pairs, *prefill):
            a.copy_to_host_async()
        return TickHandle(toks, pairs, prefill,
                          moe_stat_columns(self.model))

    @staticmethod
    @hot_path
    def tick_sync(handle: TickHandle) -> np.ndarray:
        """Block for one dispatched tick's [num_slots] token vector."""
        # The pipelined ring's DESIGNED sync point: the scheduler calls
        # this only after dispatching the next tick, so the read hides
        # behind device compute (metrics: ticks_overlapped).
        return np.asarray(handle.toks)  # hvd: disable=HVD001(the one designed sync of the tick ring)

    @staticmethod
    def tick_stats(handle: TickHandle) -> Optional[dict]:
        """The expert layers' record of one SYNCED tick (its copies
        have landed with the tokens): pairs on the held experts summed
        over layers, the busiest expert's pairs summed over layers,
        the experts that got any, the layers - and the pairs of the
        prefill chunks since the tick before. None for a model
        without a dropless expert layer."""
        if handle.moe_pairs is None:
            return None
        pairs = np.asarray(handle.moe_pairs)  # hvd: disable=HVD001(rides the tick's designed sync - the copy started with the tokens')
        prefill = [np.asarray(a)  # hvd: disable=HVD001(copies started with the tick's, as above)
                   for a in handle.moe_prefill_pairs]
        extra = {}
        if handle.routed_columns:
            # a model with identity experts: the rows' last columns
            # are counts of their own (`_moe_pairs`), not experts
            n = len(handle.routed_columns)
            extra = dict(zip(handle.routed_columns,
                             map(int, pairs[:, -n:].sum(axis=0))))
            pairs = pairs[:, :-n]
            prefill = [a[:, :-n] for a in prefill]
        return {"moe_pairs": int(pairs.sum()),
                "moe_expert_load_max": int(pairs.max(axis=1).sum()),
                "moe_experts_hit": int((pairs > 0).sum()),
                "moe_layers": int(pairs.shape[0]),
                "moe_prefill_pairs": sum(int(a.sum()) for a in prefill),
                **extra}

    def tick(self) -> np.ndarray:
        """Synchronous tick (dispatch + immediate sync) — the
        non-pipelined flavor tests and simple drivers use; the
        scheduler's hot path uses the split pair."""
        return self.tick_sync(self.tick_dispatch())

    # -- speculative rounds (docs/serving.md "Decode fast path") ------

    @hot_path
    def spec_round(self):
        """One batched draft-verify round over every lane: the draft
        proposes ``spec_k`` tokens per live lane, the target verifies
        each lane's block in one chunked append, and 1..k+1 tokens
        retire per lane — bitwise the target's greedy stream. Returns
        ``(emitted [L, k+1], n_emit [L], proposed [L])`` numpy; the
        read is the round's ONE host sync (acceptance is
        data-dependent — the scheduler must see the tokens to retire
        and truncate), amortized over every retired token."""
        assert self.spec_on, "spec_round on a pool without spec_draft"
        k = self.spec_k if self.spec_cap is None \
            else max(1, min(self.spec_k, int(self.spec_cap)))
        self.maybe_compiling = ("spec_round", k) not in self._seen_shapes
        try:
            with self._ctx():
                (self._cache, self._drf_cache, emitted, n_emit,
                 self._done, self._toks, proposed) = slot_spec_round(
                    self.dec_model, self.drf_model, self.params,
                    self.drf_params, self._cache, self._drf_cache,
                    self._toks, self._live, self._done, self._eos,
                    k)
            self._note_shape(("spec_round", k))
        finally:
            self.maybe_compiling = False
        emitted = np.asarray(emitted)  # hvd: disable=HVD001(the spec round's ONE designed sync — acceptance counts are data-dependent and every retired token rides this read; docs/serving.md)
        n_emit = np.asarray(n_emit)  # hvd: disable=HVD001(rides the same designed spec-round sync — the device work is already complete)
        proposed = np.asarray(proposed)  # hvd: disable=HVD001(rides the same designed spec-round sync)
        return emitted, n_emit, proposed

    # -- warmup -------------------------------------------------------

    def warmup(self, max_chunk: Optional[int] = None) -> dict:
        """Precompile the serving hot path before the first request:
        slot reset, every chunk program `prefill_schedule` can emit,
        the first-token sample, and the vmapped decode tick. Under a
        budget (``max_chunk``, as the scheduler has it) the chunk
        programs are TWO - the whole chunk and the padded tail, warmed
        once at one count since the count is traced (through
        `prefill_chunk`, the scheduler's own call: the same jit entry)
        - where every program costs its trace and lowering in every
        process, compile cache or not; without one, every power of
        two up to ``max_len``. All programs land in the compile-keyed
        cache this pool already consults (`_seen_shapes`), so the first
        request of any prompt length is a jit-cache hit — no XLA
        compile in the hot path, nothing for the watchdog's
        `maybe_compiling` exemption to special-case. Runs on the
        caller's thread; lane 0 is used as scratch and re-zeroed
        after. Returns ``compiles``, ``seconds`` and ``prefill_sizes``:
        the chunk programs warmed, a whole chunk as its tokens and the
        tail as ``"1..<width - 1>"``."""
        t0 = time.time()
        before = self.compiles
        width = self.chunk_width(max_chunk)
        if width is None:
            cap = self.model.max_len
            sizes = [1 << b for b in range(cap.bit_length())]
            said = sizes
        else:
            # a whole chunk, and one tail: any count below the width
            sizes = [width] + [width - 1] * (width > 1)
            said = [width] + [f"1..{width - 1}"] * (width > 1)
        logits = None
        for c in sizes:
            self.begin_prefill(0)
            logits = self.prefill_chunk(0, np.zeros((c,), np.int32),
                                        width)
        self.finish_prefill(0, logits, 0.0, None, 0)
        if self.spec_on:
            # Spec mode replaces the S=1 tick with the round (the
            # scheduler never dispatches a plain tick), so warm the
            # round INSTEAD of paying a dead full-model tick compile;
            # its program shape is occupancy-independent (live/done
            # are traced).
            self.spec_round()
        else:
            self.tick_sync(self.tick_dispatch())
        # Lane 0 back to pristine FREE state (reset clears live/done).
        self.begin_prefill(0)
        with self._ctx():
            self._toks = self._toks.at[0].set(0)
            self._temps = self._temps.at[0].set(0.0)
            self._top_ps = self._top_ps.at[0].set(1.0)
        return {"compiles": self.compiles - before,
                "seconds": time.time() - t0,
                "prefill_sizes": said}

    def free(self, slot: int):
        """Retire a slot: zero its rows (cost hygiene + trivially
        inspectable state), clear its live/done flags (the tick stops
        advancing it), and return it to the free list."""
        if slot in self._free:
            raise ValueError(f"slot {slot} is already free")
        with self._ctx():
            self._cache = slot_reset(self.dec_model, self._cache,
                                     jnp.int32(slot))
            if self.spec_on:
                self._drf_cache = slot_reset(
                    self.drf_model, self._drf_cache, jnp.int32(slot))
            self._live = self._live.at[slot].set(False)
            self._done = self._done.at[slot].set(False)
            # Neutral sampling state so the freed lane's masked decode
            # stays cheap and deterministic.
            self._toks = self._toks.at[slot].set(0)
            self._temps = self._temps.at[slot].set(0.0)
            self._top_ps = self._top_ps.at[slot].set(1.0)
        self._free.append(slot)
