"""Paged KV-cache block allocator + shared-prefix caching.

The slot pool (PR 1) reserves a private ``max_len`` KV region per slot,
so device KV capacity is ``num_slots x max_len`` no matter how long
requests actually run, and identical system prompts are re-prefilled
from scratch on every request — exactly the waste the
millions-of-users traffic shape (mixed lengths, shared system prompts)
maximizes. This module is the vLLM-style fix, in two halves:

* **`BlockPool`** — the HOST allocator. The device KV cache is carved
  into fixed-size blocks (``HVD_KV_BLOCK_SIZE`` tokens each, default
  16); each sequence owns a block table. Blocks are refcounted (shared
  prefix blocks carry one ref per pinning sequence), allocation is a
  free list, and freeing a hash-registered block parks it in an LRU of
  RESIDENT refcount-0 blocks instead of the free list — the prefix
  cache. Appending into a block whose refcount > 1 (a forked sequence
  sharing its tail) is copy-on-write: the allocator hands the writer a
  private copy first.
* **`PagedSlotPool`** — the SlotPool-compatible device pool. Decode
  lanes (``num_slots``) are now just program width: KV bytes are
  ``num_blocks x block_size``, decoupled from lane count, so more
  concurrent sequences fit the same device bytes whenever actual
  lengths run short of ``max_len`` (the capacity half of the win).
  Prefill/decode run the PAGED primitives (`models.transformer.
  paged_prefill_chunk` / `paged_decode_tick`): the lane's cache view
  is gathered through its block table INSIDE the jitted program —
  tables are traced operands, one compiled program for every layout —
  and outputs are bitwise-equal to the linear slot pool (pinned by
  tests/test_paging.py).

Shared-prefix caching (the TTFT half): admission hashes the prompt's
block-aligned prefix chain (`BlockPool.match`) against resident
blocks, PINS the hits, and the scheduler skips prefill for the matched
span — a cache-hit system prompt's TTFT collapses to the unmatched
tail. A sequence's full prompt blocks are published to the hash index
when its prefill completes (`publish`), stay resident after it
retires (LRU), and are evicted oldest-first only when allocation
needs the space. Matching is capped at the prompt's LAST token (at
least one tail token always re-prefills — the final chunk's logits
seed the first sampled token).

Restart semantics (docs/resilience.md): `clone_fresh` rebuilds an
EMPTY pool — the old device state is mid-unknown-tick and untrusted —
so watchdog-restart replay re-prefills from the prompt (token-exact as
ever) and re-pins prefixes as the replayed requests re-publish them.
"""

from __future__ import annotations

import contextlib
import hashlib
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from horovod_tpu.annotations import hot_path
from horovod_tpu.models.transformer import (
    TransformerLM, init_paged_pools, init_slot_cache, kernel_plans,
    paged_cache_spec, paged_copy_block, paged_decode_tick,
    paged_prefill_chunk, paged_spec_round, prefill_chunks,
    shard_paged_pools, shard_slot_cache, slot_decode_model,
    slot_prefill_advance, slot_reset,
)
from horovod_tpu.ops.flash_attention import DecodePlan
from horovod_tpu.parallel.mesh import replicate, use
from horovod_tpu.serving.slots import (
    Admission, TickHandle, _first_token, validate_spec_draft,
)


def _paged_attention_way(model: TransformerLM,
                         block_size: int) -> Tuple[str, str]:
    """How a paged pool's programs attend through the block tables
    (docs/serving.md "Decode fast path"), chosen from the geometry
    alone: ("lax", why) - the block-table walk over the FILLED blocks,
    bitwise the gathered-view program - where the walk's granularity
    ``decode_prefix_block`` is a multiple of the KV block size and
    divides max_len (the same divisibility `_prefix_attention` requires
    of the view); ("off", why) - the full-span gather
    (`models.transformer._paged_view`), the reference the walk is held
    to - where it is not."""
    blk = model.decode_prefix_block
    wb = min(int(blk), model.max_len) if blk else 0
    if not wb:
        unfit = "decode_prefix_block is off"
    elif wb % block_size:
        unfit = (f"decode_prefix_block {blk} is no multiple of the "
                 f"block size {block_size}")
    elif model.max_len % wb:
        unfit = (f"decode_prefix_block {blk} does not divide max_len "
                 f"{model.max_len}")
    else:
        return "lax", f"walks filled blocks, {wb} tokens a step"
    return "off", f"gathers the lane's span: {unfit}"


class BlockPool:
    """Host-side refcounted block allocator with hash-based prefix
    reuse and LRU eviction.

    Block ids are ``1 .. num_blocks-1``; block 0 is the reserved NULL
    block (masked device lanes dump dead writes there — never
    allocated, never attended). Every allocatable block is in exactly
    ONE of three states (`check_invariants` pins this under churn):

    * **free** — on the free list, content meaningless;
    * **active** — refcount >= 1, owned by >= 1 live sequence;
    * **cached** — refcount 0 but hash-registered: content is a valid
      block-aligned prompt prefix, kept RESIDENT in the LRU so a later
      admission can pin it instead of re-prefilling; evicted
      oldest-first when allocation outruns the free list.

    Single-threaded by contract (the engine's dispatch thread), like
    every other pool structure.
    """

    def __init__(self, num_blocks: int, block_size: int, *,
                 prefix_cache: bool = True,
                 max_seq_tokens: Optional[int] = None,
                 on_evict: Optional[Callable[[], None]] = None):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is the null "
                f"block), got {num_blocks}")
        if block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.prefix_cache = prefix_cache
        # Cap on positions one sequence can ever WRITE (the paged pool
        # passes the model's max_len): a request the engine accepts at
        # the boundary (P + max_new - 1 == max_len) would otherwise
        # reserve ceil((P+max_new)/bs) = blocks_per_seq + 1 blocks —
        # one more than its block-table row can hold. The device never
        # stores past max_len: the one pipelined boundary tick's
        # table lookup indexes past the row, take_along_axis's fill
        # mode yields an out-of-range block id, and the scatter DROPS
        # the write (verified; see paged_decode_tick) — so the
        # reservation clamps too.
        self.max_seq_tokens = max_seq_tokens
        self._on_evict = on_evict
        # Overload control (docs/serving.md "Overload control"): when
        # set, admission reserves blocks for only min(max_new,
        # watermark) decode tokens instead of the worst case — the
        # pool admits deeper at the same bytes, chains GROW on demand
        # (`extend`, driven by `PagedSlotPool.grow_for_tick`), and a
        # growth failure is resolved by preempting a victim instead of
        # deadlocking. None (the default) keeps the original
        # worst-case reservation: running sequences can never hit
        # allocation failure mid-decode. Only the engine's preemption
        # wiring may set this — optimistic admission WITHOUT a
        # preemption path reintroduces the mid-decode failure mode.
        self.watermark: Optional[int] = None
        # Descending so pop() hands out ascending ids (debuggability).
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}          # active blocks only
        self._hash_of: Dict[int, bytes] = {}    # registered blocks
        self._cache: Dict[bytes, int] = {}      # digest -> block id
        self._lru: "OrderedDict[int, bytes]" = OrderedDict()
        self._seqs: Dict[int, List[int]] = {}   # key (lane) -> chain
        # Residency epoch + memo for `match`: the scheduler's
        # peek-side gate (`can_admit`) and the admit that follows hash
        # the SAME prompt back-to-back, and a head request blocked on
        # block availability re-checks every dispatch loop — the memo
        # collapses those to one chain hash per (prompt, residency
        # state). Any pin/alloc/evict/free/publish bumps the epoch.
        self._epoch = 0
        self._match_memo: Optional[Tuple[bytes, int,
                                         List[int], int]] = None
        self.hits = 0          # prefix blocks served from the cache
        self.misses = 0        # queried prefix blocks not resident
        self.evictions = 0     # cached blocks reclaimed by allocation
        self.cows = 0          # copy-on-write splits

    # -- accounting ---------------------------------------------------

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return len(self._ref)

    @property
    def cached_blocks(self) -> int:
        return len(self._lru)

    @property
    def available_blocks(self) -> int:
        """What an allocation can still claim: free + evictable."""
        return len(self._free) + len(self._lru)

    def blocks_of(self, key: int) -> List[int]:
        return list(self._seqs.get(key, ()))

    def resident(self, digest: bytes) -> bool:
        """Whether a full block with this content digest is resident
        in the prefix cache — the scheduler's swap-restore check (a
        shelved transfer whose blocks are all still resident needs no
        re-graft)."""
        return digest in self._cache

    def _needed(self, prompt_len: int, max_new: int) -> int:
        """Worst-case blocks for one request: the prompt plus every
        generated token's KV row (the pipelined tick writes at most
        position prompt+max_new-1; see the scheduler's retire lag),
        clamped to ``max_seq_tokens`` — positions past it are never
        written."""
        tokens = prompt_len + max_new
        if self.max_seq_tokens is not None:
            tokens = min(tokens, self.max_seq_tokens)
        return -(-tokens // self.block_size)

    def fits(self, prompt_len: int, max_new: int) -> bool:
        """Could this request EVER be admitted (worst-case need vs the
        whole pool, ignoring current residency)? The engine's submit
        validation: a request too big for the pool must shed at the
        front door, not park at the queue head starving everything
        behind it (the degrade-by-shedding contract)."""
        return self._needed(prompt_len, max_new) <= self.num_blocks - 1

    # -- the prefix hash chain ----------------------------------------

    def _chain(self, tokens, nblocks: int) -> List[bytes]:
        """Digests of the first ``nblocks`` block-aligned prefixes:
        h_i = H(h_{i-1} || tokens[i*bs:(i+1)*bs]) — a chain, so a
        block's digest commits to the ENTIRE prefix behind it, never
        just its own 16 tokens."""
        # hvd: disable=HVD001(tokens are host-side prompt ids from the admission queue, never a device array — no sync)
        toks = np.ascontiguousarray(np.asarray(tokens, np.int64))
        out, h = [], b""
        for i in range(nblocks):
            blk = toks[i * self.block_size:(i + 1) * self.block_size]
            h = hashlib.blake2b(h + blk.tobytes(),
                                digest_size=16).digest()
            out.append(h)
        return out

    def match(self, prompt) -> Tuple[List[int], int]:
        """Longest resident block-aligned prefix of ``prompt``:
        returns (block ids, blocks queried). Capped at the LAST prompt
        token — at least one tail token must re-prefill so the final
        chunk yields the logits the first sampled token comes from.
        Pure lookup: nothing is pinned. Memoized per (prompt,
        residency epoch) so the can_admit/admit pair — and a head
        request re-checked every dispatch loop — hash the chain
        once."""
        if not self.prefix_cache:
            return [], 0
        # hvd: disable=HVD001(prompt is host-side admission-queue tokens, never a device array — no sync)
        key = np.ascontiguousarray(np.asarray(prompt, np.int64)).tobytes()
        memo = self._match_memo
        if memo is not None and memo[0] == key \
                and memo[1] == self._epoch:
            return list(memo[2]), memo[3]
        limit = (len(prompt) - 1) // self.block_size
        ids = []
        for h in self._chain(prompt, limit):
            bid = self._cache.get(h)
            if bid is None:
                break
            ids.append(bid)
        self._match_memo = (key, self._epoch, list(ids), limit)
        return ids, limit

    # -- allocation ---------------------------------------------------

    def _evict_one(self) -> int:
        bid, digest = self._lru.popitem(last=False)   # oldest first
        del self._cache[digest]
        del self._hash_of[bid]
        self._epoch += 1
        self.evictions += 1
        if self._on_evict is not None:
            self._on_evict()
        return bid

    def _alloc_one(self) -> int:
        bid = self._free.pop() if self._free else self._evict_one()
        self._ref[bid] = 1
        self._epoch += 1
        return bid

    def _pin(self, bid: int):
        if bid in self._lru:           # resurrect a cached block
            del self._lru[bid]
        self._ref[bid] = self._ref.get(bid, 0) + 1
        self._epoch += 1

    def _headroom(self, matched: List[int]) -> int:
        """Blocks an allocation can still claim AFTER pinning
        ``matched``: the free list plus the LRU minus matched blocks
        that currently sit IN the LRU — pinning resurrects those, so
        they stop being evictable (counting them double let a tight
        admission pass its capacity check and then die evicting from
        an empty LRU)."""
        in_lru = sum(1 for bid in matched if bid in self._lru)
        return len(self._free) + len(self._lru) - in_lru

    def _reserve_new(self, max_new: int) -> int:
        """Decode tokens RESERVED at admission: the worst case, or the
        optimistic watermark when one is set (preemption armed)."""
        if self.watermark is None:
            return max_new
        return min(max_new, max(1, int(self.watermark)))

    def can_admit(self, prompt, max_new: int) -> bool:
        """Would `admit` succeed right now? Pure check (nothing
        allocated or pinned) — the scheduler's peek-side gate, so a
        request that doesn't fit stays at the queue head instead of
        churning pop/requeue."""
        matched, _ = self.match(prompt)
        need = self._needed(
            len(prompt), self._reserve_new(max_new)) - len(matched)
        return need <= self._headroom(matched)

    def admit(self, key: int, prompt, max_new: int) -> Optional[
            "Admission"]:
        """Reserve the request's whole worst-case block chain for lane
        ``key``: pin the matched prefix blocks, allocate the rest
        (evicting LRU-cached blocks as needed). Reserving up front
        (rather than growing on demand) means a running sequence can
        NEVER hit allocation failure mid-decode — admission is the one
        gate, and blocks still free at ACTUAL lengths on retire.
        Returns None when the pool cannot hold it (``slot`` is filled
        in by the caller — the allocator doesn't own lanes)."""
        if key in self._seqs:
            raise ValueError(f"sequence key {key} already admitted")
        matched, queried = self.match(prompt)
        total = self._needed(len(prompt), self._reserve_new(max_new))
        need = total - len(matched)
        if need > self._headroom(matched):
            return None
        for bid in matched:
            self._pin(bid)
        chain = matched + [self._alloc_one() for _ in range(need)]
        self._seqs[key] = chain
        self.hits += len(matched)
        self.misses += queried - len(matched)
        return Admission(slot=-1,
                         skipped=len(matched) * self.block_size,
                         matched_blocks=len(matched),
                         queried_blocks=queried)

    def extend(self, key: int, total_tokens: int) -> bool:
        """Grow lane ``key``'s chain to cover ``total_tokens``
        positions (clamped to ``max_seq_tokens`` — the device drops
        writes past the row anyway). The on-demand half of
        watermark-based optimistic admission: True when the chain
        already covers it or new blocks were allocated, False when
        the pool is out of blocks (the lane is STRANDED — the caller
        must preempt someone before dispatching its next write, else
        the write lands in the null block and corrupts the stream)."""
        chain = self._seqs.get(key)
        if chain is None:
            raise ValueError(f"sequence key {key} not admitted")
        tokens = total_tokens
        if self.max_seq_tokens is not None:
            tokens = min(tokens, self.max_seq_tokens)
        need = -(-tokens // self.block_size) - len(chain)
        if need <= 0:
            return True
        if need > len(self._free) + len(self._lru):
            return False
        for _ in range(need):
            chain.append(self._alloc_one())
        return True

    def publish(self, key: int, prompt):
        """Register lane ``key``'s full prompt blocks in the prefix
        index (called when its prefill completes — from here on, an
        identical block-aligned prefix chain is a cache hit). First
        writer wins on a digest collision between two concurrent cold
        prefills of the same prompt; the loser's private block simply
        stays unregistered."""
        if not self.prefix_cache:
            return
        ids = self._seqs.get(key, [])
        full = min(len(prompt) // self.block_size, len(ids))
        for h, bid in zip(self._chain(prompt, full), ids[:full]):
            if h not in self._cache and bid not in self._hash_of:
                self._cache[h] = bid
                self._hash_of[bid] = h
                self._epoch += 1

    def chain_digests(self, tokens, nblocks: int) -> List[bytes]:
        """Public chain-digest accessor (the transfer layer's
        manifest identity; see serving/transfer.py)."""
        return self._chain(tokens, nblocks)

    def adopt(self, digest: bytes) -> Optional[int]:
        """Register a FOREIGN block under ``digest`` as a refcount-0
        LRU-resident cached block — the ingest half of a KV-block
        transfer (serving/transfer.py). The caller scatters the
        block's device bytes into the returned id; from then on it is
        indistinguishable from a locally published prefix block: an
        admission `match` pins it, eviction reclaims it oldest-first.
        Returns None when the digest is already resident (idempotent
        ingest) or when no block can be claimed without eviction
        pressure the caller should not pay (full pool, empty LRU)."""
        if not self.prefix_cache or digest in self._cache:
            return None
        if not self._free and not self._lru:
            return None
        bid = self._free.pop() if self._free else self._evict_one()
        self._cache[digest] = bid
        self._hash_of[bid] = digest
        self._lru[bid] = digest
        self._epoch += 1
        return bid

    def fork(self, src: int, dst: int):
        """Share ``src``'s whole chain with a new sequence ``dst``
        (n-best sampling / speculative branches): every block gains a
        ref. Appends by either sequence hit copy-on-write at the
        shared tail (`ensure_writable`)."""
        if dst in self._seqs:
            raise ValueError(f"sequence key {dst} already admitted")
        chain = self._seqs[src]
        for bid in chain:
            self._pin(bid)
        self._seqs[dst] = list(chain)

    def ensure_writable(self, key: int,
                        block_index: int) -> Optional[Tuple[int, int]]:
        """Copy-on-write gate: lane ``key`` is about to APPEND into
        chain position ``block_index``. A block shared with anyone
        else (refcount > 1 — a fork tail, or a pinned published
        prefix) must not be mutated in place: allocate a private
        block, swap it into the chain, and return ``(src, dst)`` so
        the caller materializes the copy on device
        (`paged_copy_block`). None = already exclusively owned.
        Raises RuntimeError when no block can be claimed — forking
        needs headroom beyond the per-sequence reservations."""
        chain = self._seqs[key]
        bid = chain[block_index]
        if self._ref[bid] == 1 and bid not in self._hash_of:
            return None
        if self._ref[bid] == 1:
            # Sole owner but PUBLISHED: future matchers would pin a
            # block whose tail this append is about to overwrite.
            # Unregister instead of copying — content up to the hash's
            # span is still the registered prefix, but the simple,
            # provably safe rule is: a written block leaves the index.
            h = self._hash_of.pop(bid)
            del self._cache[h]
            self._epoch += 1
            return None
        if self.available_blocks < 1:
            raise RuntimeError(
                "copy-on-write needs a free block; fork headroom "
                "exhausted")
        nid = self._alloc_one()
        self._ref[bid] -= 1
        chain[block_index] = nid
        self.cows += 1
        return bid, nid

    def free_seq(self, key: int) -> List[int]:
        """Release lane ``key``'s chain: every block drops a ref;
        refcount-0 blocks go to the LRU if hash-registered (resident
        prefix cache) or the free list otherwise. Idempotent per key.
        Returns the released chain (tests)."""
        chain = self._seqs.pop(key, [])
        for bid in chain:
            self._ref[bid] -= 1
            if self._ref[bid] == 0:
                del self._ref[bid]
                self._epoch += 1
                if bid in self._hash_of and self.prefix_cache:
                    self._lru[bid] = self._hash_of[bid]
                else:
                    self._hash_of.pop(bid, None)
                    self._free.append(bid)
        return chain

    def check_invariants(self):
        """Every allocatable block in exactly one of free/active/
        cached; maps mutually consistent; live chains hold refs that
        sum up exactly. Raises AssertionError — the churn tests call
        this after every operation."""
        free, active, cached = (set(self._free), set(self._ref),
                                set(self._lru))
        assert 0 not in free | active | cached, "null block leaked"
        assert not (free & active), (free, active)
        assert not (free & cached), (free, cached)
        assert not (active & cached), (active, cached)
        assert free | active | cached == set(
            range(1, self.num_blocks)), "block lost or duplicated"
        assert all(r >= 1 for r in self._ref.values()), self._ref
        # Refcounts are EXACTLY the per-chain memberships.
        counts: Dict[int, int] = {}
        for chain in self._seqs.values():
            for bid in chain:
                counts[bid] = counts.get(bid, 0) + 1
        assert counts == self._ref, (counts, self._ref)
        # Hash index <-> block registry agree both ways; LRU subset.
        assert {v: k for k, v in self._cache.items()} == self._hash_of
        for bid, h in self._lru.items():
            assert self._hash_of.get(bid) == h, (bid, h)

    def stats(self) -> Dict[str, int]:
        return {"blocks_free": self.free_blocks,
                "blocks_used": self.used_blocks,
                "blocks_cached": self.cached_blocks,
                "prefix_hits": self.hits,
                "prefix_misses": self.misses,
                "prefix_evictions": self.evictions,
                "cows": self.cows}


class PagedSlotPool:
    """The paged twin of `serving.slots.SlotPool`: same lifecycle
    protocol (the scheduler/engine drive both through `can_admit` /
    `admit` / `begin_prefill` / `prefill_chunk` / `finish_prefill` /
    `tick_dispatch` / `tick_sync` / `free` / `warmup` /
    `clone_fresh`), but the device KV lives in one shared block pool
    and each lane indexes it through a block table.

    ``num_blocks`` sets device KV bytes (``num_blocks x block_size``
    token rows per leaf; block 0 is the null block). The default —
    ``num_slots x max_len / block_size + 1`` — matches the fixed slot
    pool's bytes exactly, which is the honest A/B configuration: same
    device KV, strictly more admissible concurrency whenever requests
    run shorter than ``max_len``. All device work on the dispatch
    thread, as ever.
    """

    def __init__(self, model: TransformerLM, params, num_slots: int,
                 *, num_blocks: Optional[int] = None,
                 block_size: Optional[int] = None, mesh=None,
                 eos_id: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 on_evict: Optional[Callable[[], None]] = None,
                 spec_draft=None, spec_k: int = 0):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        from horovod_tpu.runtime.config import config as _cfg
        if block_size is None:
            block_size = _cfg.kv_block_size
        if prefix_cache is None:
            prefix_cache = _cfg.prefix_cache
        self.model = model
        self.dec_model = slot_decode_model(model)
        self.params = params
        self.num_slots = num_slots
        self.mesh = mesh
        self.eos_id = eos_id
        self._eos = jnp.int32(-1 if eos_id is None else eos_id)
        self.spec = paged_cache_spec(model, block_size)
        self.block_size = self.spec.block_size
        # Paged-attention dispatch (docs/serving.md "Decode fast
        # path"): "lax" walks only the FILLED blocks of each lane's
        # table, "off" gathers the whole span (the reference, and the
        # fallback of a geometry that cannot walk).
        self.kernel_mode, self._kernel_why = _paged_attention_way(
            model, self.block_size)
        self._fused = self.kernel_mode != "off"
        # Speculative decoding: the draft rides a LINEAR slot cache
        # (it is small — the paging win is the target's); prefix
        # caching is disabled in spec mode so ONE chunk schedule
        # drives both caches (a matched prefix would skip the
        # target's prefill but the draft still needs those tokens).
        self.spec_draft = spec_draft
        self.spec_k = int(spec_k) if spec_draft is not None else 0
        self.drf_model = self.drf_params = self._drf_cache = None
        if self.spec_on:
            validate_spec_draft(model, spec_draft, self.spec_k)
            draft_model, draft_params = spec_draft
            self.drf_model = slot_decode_model(draft_model)
            self.drf_params = draft_params
            self._drf_cache = init_slot_cache(draft_model, num_slots)
            prefix_cache = False
        if num_blocks is None:
            num_blocks = num_slots * self.spec.blocks_per_seq + 1
        self.num_blocks = int(num_blocks)
        self.blocks = BlockPool(self.num_blocks, self.block_size,
                                prefix_cache=prefix_cache,
                                max_seq_tokens=model.max_len,
                                on_evict=on_evict)
        self._on_evict = on_evict
        self._pools = init_paged_pools(model, self.spec,
                                       self.num_blocks)
        self._tables = jnp.zeros(
            (num_slots, self.spec.blocks_per_seq), jnp.int32)
        self._fills = jnp.zeros((num_slots,), jnp.int32)
        self._toks = jnp.zeros((num_slots,), jnp.int32)
        self._temps = jnp.zeros((num_slots,), jnp.float32)
        self._top_ps = jnp.ones((num_slots,), jnp.float32)
        self._rngs = jnp.stack(
            [jax.random.PRNGKey(i) for i in range(num_slots)])
        self._live = jnp.zeros((num_slots,), bool)
        self._done = jnp.zeros((num_slots,), bool)
        self._free_lanes: List[int] = list(range(num_slots))
        # Sharded serving (docs/serving.md "Sharded serving"): block
        # pools commit sharded along the heads axis — each device
        # holds its head slice of EVERY block, so a host block id
        # names a mesh-wide block SHARD set and the allocator
        # (admission math, prefix digests, COW, eviction) runs
        # unchanged. Block tables and fills stay host-replicated
        # int32 metadata; one host decision drives all shards.
        if mesh is not None:
            self._pools = shard_paged_pools(self._pools, mesh)
            if self._drf_cache is not None:
                self._drf_cache = shard_slot_cache(self._drf_cache,
                                                   mesh)
            (self._tables, self._fills, self._toks, self._temps,
             self._top_ps, self._rngs, self._live, self._done,
             self._eos) = replicate(
                mesh, (self._tables, self._fills, self._toks,
                       self._temps, self._top_ps, self._rngs,
                       self._live, self._done, self._eos))
        # Host-side admission state: what admit() granted, consumed by
        # begin_prefill/finish_prefill; plus a CONSERVATIVE per-lane
        # fill estimate driving the copy-on-write gate (over-estimating
        # only copies early — never corrupts).
        self._admit_info: Dict[int, Tuple[np.ndarray, int]] = {}
        self._est_fill = np.zeros((num_slots,), np.int64)
        self._ticking: set = set()     # lanes live on the host's view
        # Compile awareness (same contract as SlotPool: the watchdog
        # suppresses stuck detection while a first-time shape is in
        # flight).
        self.maybe_compiling = False
        self._seen_shapes: set = set()
        self.compiles = 0
        # Brownout rung >= 2 (docs/serving.md "Overload control"):
        # caps the speculative k mid-stream. Greedy spec decode is
        # bitwise-identical for ANY k, so the cap sheds draft compute
        # without touching token streams; a new effective k compiles
        # one extra program (the shape key includes it).
        self.spec_cap: Optional[int] = None

    # -- shared plumbing (mirrors SlotPool) ---------------------------

    @property
    def spec_on(self) -> bool:
        return self.spec_draft is not None and self.spec_k > 0

    def _ctx(self):
        return use(self.mesh) if self.mesh is not None \
            else contextlib.nullcontext()

    def kernel_plans(self, chunk: int = 1) -> dict:
        """`SlotPool.kernel_plans`' twin. This pool's programs attend
        through the block tables, never the linear cache (and it takes
        no model with a second kind of softmax layer): the one
        ``"decode_attn"`` plan says which way and why. A recurrent
        state lives in the fixed pool alone (this pool refuses it)."""
        with self._ctx():
            plans = kernel_plans(self.model, self.num_slots, chunk)
        return {**plans,
                "decode_attn": {
                    "attn": DecodePlan("paged", self._kernel_why)},
                "state_step": {}}

    def _note_shape(self, key):
        if key not in self._seen_shapes:
            self.compiles += 1
            self._seen_shapes.add(key)
            from horovod_tpu.obs import catalog as _obs_catalog
            from horovod_tpu.obs import events as _events
            _obs_catalog.serving_metrics()["compiles"].inc()
            _events.emit("serving.compile", shape=repr(key))

    def clone_fresh(self) -> "PagedSlotPool":
        """The watchdog's restart primitive: a brand-new pool — fresh
        block allocator, EMPTY prefix cache — over the same model/
        params/geometry. The old device state is mid-unknown-tick and
        untrusted, and a hash index over untrusted bytes would serve
        corrupt prefixes, so the cache restarts cold: requeued
        requests replay token-exact from their prompts and re-publish
        their prefixes as they complete (re-pinning is then automatic
        for every later replay — pinned by tests)."""
        fresh = PagedSlotPool(
            self.model, self.params, self.num_slots,
            num_blocks=self.num_blocks, block_size=self.block_size,
            mesh=self.mesh, eos_id=self.eos_id,
            prefix_cache=self.blocks.prefix_cache,
            on_evict=self._on_evict,
            spec_draft=self.spec_draft, spec_k=self.spec_k)
        fresh._seen_shapes = set(self._seen_shapes)
        fresh.compiles = self.compiles
        # Overload-control knobs survive a watchdog restart: the
        # engine armed them once at construction, and a fresh pool
        # silently back on worst-case reservation would shrink
        # admission depth mid-flight.
        fresh.blocks.watermark = self.blocks.watermark
        fresh.spec_cap = self.spec_cap
        return fresh

    def fill_indices(self) -> np.ndarray:
        """Per-lane device fill index (introspection/tests)."""
        return np.asarray(self._fills)

    # -- occupancy ----------------------------------------------------

    @property
    def free_slots(self) -> int:
        return len(self._free_lanes)

    @property
    def busy_slots(self) -> int:
        return self.num_slots - len(self._free_lanes)

    def has_free(self) -> bool:
        return bool(self._free_lanes)

    def kv_stats(self) -> Dict[str, int]:
        return self.blocks.stats()

    # -- admission ----------------------------------------------------

    def can_admit(self, prompt, max_new: int) -> bool:
        """Free lane AND enough blocks (after prefix credit) — the
        scheduler's peek-side gate. Admission now blocks on BLOCK
        availability, not just lanes: lanes are cheap program width,
        blocks are the real KV bytes."""
        return bool(self._free_lanes) and self.blocks.can_admit(
            prompt, max_new)

    def fits(self, prompt_len: int, max_new: int) -> bool:
        """Could the request EVER be admitted (worst-case need vs the
        whole pool)? The engine's submit-time shed gate — a request
        bigger than the pool must fail at the front door, never park
        at the queue head forever."""
        return self.blocks.fits(prompt_len, max_new)

    def admit(self, prompt, max_new: int) -> Optional[Admission]:
        """Claim a lane + the request's block chain; None when either
        is short. The matched prefix span (``skipped``) is already
        resident — `begin_prefill` starts the lane's fill there and
        the scheduler streams only the tail."""
        if not self._free_lanes:
            return None
        # hvd: disable=HVD001(prompt is host-side admission-queue tokens, never a device array — no sync)
        prompt = np.asarray(prompt)
        slot = self._free_lanes[-1]
        adm = self.blocks.admit(slot, prompt, max_new)
        if adm is None:
            return None
        self._free_lanes.pop()
        self._admit_info[slot] = (prompt, adm.skipped)
        return Admission(slot=slot, skipped=adm.skipped,
                         matched_blocks=adm.matched_blocks,
                         queried_blocks=adm.queried_blocks)

    def alloc(self) -> Optional[int]:
        """SlotPool-compat lane claim for direct pool drivers (tests,
        warmup): a full-length reservation with no prompt to match.
        Prefer `admit` — this books max_len worth of blocks."""
        adm = self.admit(np.zeros((1,), np.int64),
                         self.model.max_len - 1)
        return None if adm is None else adm.slot

    # -- prefill ------------------------------------------------------

    def begin_prefill(self, slot: int):
        """Install the lane's device state for its admitted request:
        fill starts AT the matched-prefix span (the skip), the block
        table row is the admitted chain, live/done clear. No device
        zeroing — block content beyond the fill is masked by every
        decode path, and recycled blocks are fully overwritten before
        the fill reaches them."""
        prompt, skipped = self._admit_info.get(slot, (None, 0))
        chain = self.blocks.blocks_of(slot)
        row = np.zeros((self.spec.blocks_per_seq,), np.int32)
        row[:len(chain)] = chain
        self.maybe_compiling = ("paged_begin",) not in self._seen_shapes
        try:
            with self._ctx():
                self._tables = self._tables.at[slot].set(
                    jnp.asarray(row))
                self._fills = self._fills.at[slot].set(
                    jnp.int32(skipped))
                if self.spec_on:
                    self._drf_cache = slot_reset(
                        self.drf_model, self._drf_cache,
                        jnp.int32(slot))
                self._live = self._live.at[slot].set(False)
                self._done = self._done.at[slot].set(False)
            self._note_shape(("paged_begin",))
        finally:
            self.maybe_compiling = False
        self._est_fill[slot] = skipped
        self._ticking.discard(slot)

    def _cow_span(self, slot: int, start: int, end: int):
        """Copy-on-write gate for writes covering positions
        [start, end): any chain block in that span shared with another
        sequence is split to a private copy first (device bytes via
        `paged_copy_block`, table row updated). With prefix caching
        alone this never fires — matched blocks are always FULL and
        writes land past them — but forked sequences (and a re-append
        into a published block) make it load-bearing."""
        chain = self.blocks.blocks_of(slot)
        lo, hi = start // self.block_size, (end - 1) // self.block_size
        for idx in range(lo, min(hi, len(chain) - 1) + 1):
            swap = self.blocks.ensure_writable(slot, idx)
            if swap is None:
                continue
            src, dst = swap
            with self._ctx():
                self._pools = paged_copy_block(
                    self._pools, jnp.int32(src), jnp.int32(dst))
                self._tables = self._tables.at[slot, idx].set(dst)

    @staticmethod
    def chunk_width(max_chunk: Optional[int]) -> Optional[int]:
        """None under any budget (shared protocol with `SlotPool`):
        `paged_prefill_chunk` takes no count, so no tail is padded and
        a chunk's width is its length."""
        del max_chunk
        return None

    @staticmethod
    def prefill_schedule(length: int,
                         max_chunk: Optional[int] = None) -> List[int]:
        """THE chunk schedule of a prompt in this pool (shared protocol
        with `SlotPool`): the binary decomposition, capped at the
        budget - the fixed pool's padded tail is not taken here."""
        return prefill_chunks(length, max_chunk, pad_tail=False)

    def prefill_chunk(self, slot: int, chunk,
                      width: Optional[int] = None):
        """Append one prompt chunk into lane ``slot``'s paged cache;
        returns the chunk's last-position logits (device array). The
        binary-decomposition chunk schedule (`prefill_schedule`), so
        the compiled-program set stays log2-bounded; ``slot`` and the
        block table are traced, so every lane and layout shares each
        size's program. ``width`` is `chunk_width`'s None."""
        del width
        # hvd: disable=HVD001(chunk is host-side prompt tokens from the admission queue, never a device array — no sync)
        chunk = np.asarray(chunk)
        c = int(chunk.shape[0])
        fill = int(self._est_fill[slot])
        self._cow_span(slot, fill, fill + c)
        self.maybe_compiling = (
            ("paged_prefill", c) not in self._seen_shapes)
        try:
            with self._ctx():
                self._pools, self._fills, logits = paged_prefill_chunk(
                    self.dec_model, self.spec, self._pools,
                    self.params, self._tables, self._fills,
                    jnp.int32(slot), jnp.asarray(chunk, jnp.int32),
                    fused=self._fused)
                if self.spec_on:
                    # Mirror the target's chunk schedule into the
                    # draft cache (advance-only; see SlotPool).
                    self._drf_cache = slot_prefill_advance(
                        self.drf_model, self.drf_params,
                        self._drf_cache, jnp.int32(slot),
                        jnp.asarray(chunk, jnp.int32))
            self._note_shape(("paged_prefill", c))
            self._est_fill[slot] = fill + c
            return logits
        finally:
            self.maybe_compiling = False

    def finish_prefill(self, slot: int, logits, temperature: float,
                       top_p: Optional[float], seed: int, *,
                       rng_skip: int = 0) -> int:
        """Close a prefill exactly as the slot pool does (same
        `_first_token` split discipline — request streams are
        reproducible wherever they land, and ``rng_skip`` resumes a
        forced-prefix continuation's stream mid-way), then PUBLISH the
        prompt's full blocks to the prefix index: from this moment an
        identical block-aligned prefix is a cache hit, even while this
        request is still decoding."""
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
                self._done = self._done.at[slot].set(tok == self._eos)
                info = self._admit_info.pop(slot, None)
                if info is not None:
                    self.blocks.publish(slot, info[0])
                self._ticking.add(slot)
                # hvd: disable=HVD001(the ONE designed per-request sync — TTFT wants the first token now; docs/serving.md)
                return int(tok)
        finally:
            self.maybe_compiling = False

    def prefill(self, slot: int, prompt, temperature: float,
                top_p: Optional[float], seed: int, *,
                max_chunk: Optional[int] = None) -> int:
        """begin/chunks/finish in one call (tests, simple drivers) —
        starts at the admitted skip, streams only the tail."""
        prompt = np.asarray(prompt)
        _, skipped = self._admit_info.get(slot, (None, 0))
        self.begin_prefill(slot)
        logits = None
        off = skipped
        for c in self.prefill_schedule(int(prompt.shape[0]) - skipped,
                                       max_chunk):
            logits = self.prefill_chunk(slot, prompt[off:off + c])
            off += c
        return self.finish_prefill(slot, logits, temperature, top_p,
                                   seed)

    def graft(self, transfer) -> int:
        """Ingest a `BlockTransfer` into this pool's prefix cache
        (serving/transfer.py `ingest_blocks`): verify digests, adopt
        the blocks under fresh ids, scatter the rows. Dispatch-thread
        only, like every other pool mutation. Returns blocks newly
        adopted; raises `TransferError` on any verification failure
        (the pool is left untouched — callers fall back to
        token-level recompute)."""
        from horovod_tpu.serving.transfer import ingest_blocks
        return ingest_blocks(self, transfer)

    def fork(self, slot: int) -> Optional[int]:
        """Clone lane ``slot`` into a fresh lane sharing its ENTIRE
        block chain (refcounted — zero KV bytes copied up front):
        sampling state, fill and done flag are duplicated, so both
        lanes continue from the identical sequence state. The first
        append by either lane into the shared tail block triggers
        copy-on-write. None when no lane is free."""
        if not self._free_lanes:
            return None
        dst = self._free_lanes.pop()
        self.blocks.fork(slot, dst)
        with self._ctx():
            self._tables = self._tables.at[dst].set(self._tables[slot])
            self._fills = self._fills.at[dst].set(self._fills[slot])
            self._toks = self._toks.at[dst].set(self._toks[slot])
            self._temps = self._temps.at[dst].set(self._temps[slot])
            self._top_ps = self._top_ps.at[dst].set(
                self._top_ps[slot])
            self._rngs = self._rngs.at[dst].set(self._rngs[slot])
            self._live = self._live.at[dst].set(self._live[slot])
            self._done = self._done.at[dst].set(self._done[slot])
        self._est_fill[dst] = self._est_fill[slot]
        if slot in self._ticking:
            self._ticking.add(dst)
        return dst

    # -- watermark growth (docs/serving.md "Overload control") --------

    def _spec_k_eff(self) -> int:
        """The speculative k actually dispatched: ``spec_k`` unless a
        brownout cap shrinks it (floor 1 — a zero-k round is a plain
        tick the spec scheduling path never dispatches)."""
        if self.spec_cap is None:
            return self.spec_k
        return max(1, min(self.spec_k, int(self.spec_cap)))

    def grow_for_tick(self) -> List[int]:
        """Under watermark admission, grow every ticking lane's chain
        to cover the positions its NEXT dispatch writes (one for a
        plain tick, up to k+1 for a spec round) and mirror any new
        blocks into the device block-table row. Returns the lanes
        that could NOT be grown (pool dry) — STRANDED: the scheduler
        must preempt before dispatching, because a write past the
        chain lands in null block 0 and corrupts the stream (the
        write is misplaced AND later attention reads of the position
        read null garbage). No-op (fast) when watermark is unset:
        worst-case admission already covered every position."""
        if self.blocks.watermark is None or not self._ticking:
            return []
        bs = self.block_size
        span = self._spec_k_eff() + 1 if self.spec_on else 1
        cap = self.spec.blocks_per_seq * bs
        stranded: List[int] = []
        updates: List[Tuple[int, int, int]] = []
        for slot in sorted(self._ticking):
            est = int(self._est_fill[slot])
            top = min(est + span, cap)
            if top <= est:
                continue
            before = len(self.blocks.blocks_of(slot))
            if not self.blocks.extend(slot, top):
                stranded.append(slot)
                continue
            chain = self.blocks.blocks_of(slot)
            for idx in range(before, len(chain)):
                updates.append((slot, idx, chain[idx]))
        if updates:
            with self._ctx():
                tbl = self._tables
                for slot, idx, bid in updates:
                    tbl = tbl.at[slot, idx].set(bid)
                self._tables = tbl
        return stranded

    # -- the tick (split for pipelining) ------------------------------

    @hot_path
    def tick_dispatch(self) -> TickHandle:
        """Enqueue one paged decode tick over every lane + the async
        token copy; same pipelining contract as the slot pool. Before
        dispatch, the copy-on-write gate runs for each host-live
        lane's next write position — with prefix caching alone it is a
        handful of dict lookups (shared blocks are full, writes land
        past them); forked lanes split here."""
        for slot in list(self._ticking):
            est = int(self._est_fill[slot])
            if est // self.block_size < self.spec.blocks_per_seq:
                self._cow_span(slot, est, est + 1)
        self.maybe_compiling = ("paged_tick",) not in self._seen_shapes
        try:
            with self._ctx():
                (self._pools, self._toks, self._rngs, self._done,
                 self._fills) = paged_decode_tick(
                    self.dec_model, self.spec, self._pools,
                    self.params, self._tables, self._fills, self._toks,
                    self._temps, self._top_ps, self._rngs, self._live,
                    self._done, self._eos, fused=self._fused)
            self._note_shape(("paged_tick",))
        finally:
            self.maybe_compiling = False
        for slot in self._ticking:
            # Conservative host fill advance (device freezes done
            # lanes — over-estimating only triggers an early COW
            # check, clamped to the allocated chain).
            self._est_fill[slot] += 1
        toks = self._toks
        toks.copy_to_host_async()
        return TickHandle(toks)

    @staticmethod
    @hot_path
    def tick_sync(handle: TickHandle) -> np.ndarray:
        """Block for one dispatched tick's [num_slots] token vector."""
        # The pipelined ring's DESIGNED sync point (same as SlotPool).
        return np.asarray(handle.toks)  # hvd: disable=HVD001(the one designed sync of the tick ring)

    def tick(self) -> np.ndarray:
        return self.tick_sync(self.tick_dispatch())

    # -- speculative rounds (docs/serving.md "Decode fast path") ------

    @hot_path
    def spec_round(self):
        """One batched draft-verify round over every paged lane (see
        `SlotPool.spec_round` — same contract, paged target): returns
        ``(emitted [L, k+1], n_emit [L], proposed [L])`` numpy."""
        assert self.spec_on, "spec_round on a pool without spec_draft"
        k = self._spec_k_eff()
        for slot in list(self._ticking):
            est = int(self._est_fill[slot])
            top = min(est + k + 1,
                      self.spec.blocks_per_seq * self.block_size)
            if est < top:
                self._cow_span(slot, est, top)
        self.maybe_compiling = (
            ("paged_spec_round", k) not in self._seen_shapes)
        try:
            with self._ctx():
                (self._pools, self._fills, self._drf_cache, emitted,
                 n_emit, self._done, self._toks,
                 proposed) = paged_spec_round(
                    self.dec_model, self.drf_model, self.spec,
                    self.params, self.drf_params, self._pools,
                    self._drf_cache, self._tables, self._fills,
                    self._toks, self._live, self._done, self._eos,
                    k, fused=self._fused)
            self._note_shape(("paged_spec_round", k))
        finally:
            self.maybe_compiling = False
        emitted = np.asarray(emitted)  # hvd: disable=HVD001(the spec round's ONE designed sync — acceptance counts are data-dependent and every retired token rides this read; docs/serving.md)
        n_emit = np.asarray(n_emit)  # hvd: disable=HVD001(rides the same designed spec-round sync — the device work is already complete)
        proposed = np.asarray(proposed)  # hvd: disable=HVD001(rides the same designed spec-round sync)
        for slot in self._ticking:
            # Conservative host fill advance for the COW gate, same
            # contract as the tick's +1 (over-estimating only copies
            # early, clamped to the chain).
            self._est_fill[slot] += int(n_emit[slot])  # hvd: disable=HVD001(n_emit is already a host numpy array — no device read)
        return emitted, n_emit, proposed

    # -- warmup -------------------------------------------------------

    def warmup(self, max_chunk: Optional[int] = None) -> dict:
        """Precompile the paged hot path (begin, every pow2 prefill
        chunk, first token, the paged tick) on lane 0 against the null
        table — the writes land in the null block, which is never
        attended, so no allocation is needed and the pool ends
        pristine."""
        t0 = time.time()
        before = self.compiles
        cap = self.model.max_len
        if max_chunk is not None and max_chunk >= 1:
            cap = min(cap, int(max_chunk))
        cap = 1 << (max(1, cap).bit_length() - 1)   # pow2 floor
        sizes = [1 << b for b in range(cap.bit_length())]
        logits = None
        for c in sizes:
            self.begin_prefill(0)
            logits = self.prefill_chunk(0, np.zeros((c,), np.int32))
        self.finish_prefill(0, logits, 0.0, None, 0)
        if self.spec_on:
            # Warm the round INSTEAD of the plain tick spec-mode
            # scheduling never dispatches (see SlotPool.warmup).
            self.spec_round()
        else:
            self.tick_sync(self.tick_dispatch())
        # Lane 0 back to pristine FREE state.
        self.begin_prefill(0)
        self._ticking.discard(0)
        self._est_fill[0] = 0
        with self._ctx():
            self._fills = self._fills.at[0].set(0)
            self._toks = self._toks.at[0].set(0)
            self._temps = self._temps.at[0].set(0.0)
            self._top_ps = self._top_ps.at[0].set(1.0)
        return {"compiles": self.compiles - before,
                "seconds": time.time() - t0,
                "prefill_sizes": sizes}

    def free(self, slot: int):
        """Retire a lane: release its block chain to the allocator
        (hash-registered blocks stay RESIDENT in the LRU — the prefix
        cache outliving the request is the whole point), stop the
        lane on device, neutralize its sampling state. Blocks return
        at the request's ACTUAL footprint, never max_len."""
        if slot in self._free_lanes:
            raise ValueError(f"slot {slot} is already free")
        self.blocks.free_seq(slot)
        self._admit_info.pop(slot, None)
        self._ticking.discard(slot)
        self._est_fill[slot] = 0
        if self.spec_on:
            with self._ctx():
                self._drf_cache = slot_reset(
                    self.drf_model, self._drf_cache, jnp.int32(slot))
        with self._ctx():
            # A host row, exactly as begin_prefill sets it: the same
            # (warmed) scatter program. An eager jnp.zeros here would
            # be a first-use XLA compile inside the serving window
            # that the pool's own compile count never sees.
            self._tables = self._tables.at[slot].set(jnp.asarray(
                np.zeros((self.spec.blocks_per_seq,), np.int32)))
            self._fills = self._fills.at[slot].set(0)
            self._live = self._live.at[slot].set(False)
            self._done = self._done.at[slot].set(False)
            self._toks = self._toks.at[slot].set(0)
            self._temps = self._temps.at[slot].set(0.0)
            self._top_ps = self._top_ps.at[slot].set(1.0)
        self._free_lanes.append(slot)
