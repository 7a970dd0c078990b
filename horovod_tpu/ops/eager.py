"""Eager (outside-jit) collective API.

The reference's op-by-op surface: a TF-graph op per tensor
(`horovod/tensorflow/mpi_ops.py:132-190`) executed via the background
MPI thread. The TPU equivalent dispatches a tiny cached pjit'd program per
(op, name, shape, dtype) over the framework mesh — XLA's compile cache
plays the role of the reference's tensor table.

Input conventions (how Horovod's "each rank passes its local tensor" MPMD
call maps onto single-controller JAX):

* ``hvd.per_rank([t0, .., tN-1])`` / ``PerRank`` — explicit per-rank
  values; the true analogue of N MPI ranks each passing a different
  tensor. Used heavily by the test-suite (mirrors `mpi_ops_test.py`
  generating a different random tensor per rank).
* A plain array — the value every rank holds (replicated). Allreduce of a
  replicated value is `x * size` (sum) / `x` (average), matching what N
  identical MPI ranks would produce.
* In multi-controller mode (``hvdrun``), a plain array is *this process's
  local value* and the collective runs across processes.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.ops import collectives as C
from horovod_tpu.runtime import state as _state


@dataclasses.dataclass
class PerRank:
    """Explicit per-rank inputs for eager collectives (leading index =
    rank). Values may differ in dim 0 (variable allgather)."""
    values: List[Any]

    def __post_init__(self):
        self.values = [np.asarray(v) for v in self.values]


def per_rank(values: Sequence[Any]) -> PerRank:
    return PerRank(list(values))


def _normalize_name(name: str) -> str:
    """Parity with `mpi_ops.py:127-129`."""
    return re.sub(r"[^a-zA-Z0-9_]", "_", name)


def _auto_name(prefix: str, name: Optional[str], tensor,
               skip_dim0: bool = False, content_free: bool = False) -> str:
    """Stable auto-name keyed on op/shape/dtype, mirroring the reference's
    naming by tensor graph name (`mpi_ops.py:143-144`) — stable across
    steps so timeline pids and the stall table don't grow per call.
    skip_dim0: allgather inputs may legitimately differ in dim 0 across
    ranks, and negotiation keys on the name, so dim 0 stays out of it.
    content_free: multi-controller negotiation must produce the SAME name
    on processes that *disagree* on shape/dtype (that disagreement is what
    validation exists to catch), so auto-names there carry no tensor
    metadata — cross-process identity comes from call order, the same
    consistent-op-order contract Horovod itself requires."""
    if name is not None:
        return _normalize_name(name)
    if content_free:
        return prefix
    if isinstance(tensor, PerRank):
        v = tensor.values[0]
        shape, dtype = v.shape, v.dtype
    else:
        v = np.asarray(tensor) if not hasattr(tensor, "shape") else tensor
        shape, dtype = tuple(v.shape), v.dtype
    if skip_dim0:
        shape = ("v",) + tuple(shape[1:])
    dims = "x".join(map(str, shape)) or "scalar"
    return f"{prefix}_{dims}_{dtype}"


def _is_multicontroller(st) -> bool:
    return st.num_processes > 1


def _mc_negotiate(st, opname: str, op: str, arr: np.ndarray,
                  root_rank: Optional[int], allow_dim0: bool,
                  extra: Optional[str] = None,
                  timeout_s: Optional[float] = None):
    """Per-op metadata negotiation over the launcher's rendezvous server.

    The runtime equivalent of the reference's coordinator protocol
    (SURVEY §3.2 right half), with the reference's topology: every
    process posts its request (name/op/dtype/shape/root) once; process
    0 gathers all N, validates them — the checks `ConstructMPIResponse`
    runs on rank 0 (`mpi_ops.cc:266-474`) — and publishes ONE response
    that every other process reads (the coordinator's response
    broadcast, `mpi_ops.cc:1421-1427`). Non-coordinator traffic per op
    is therefore 2 round-trips (1 write + 1 read) independent of world
    size; the earlier all-read-all design cost N reads on each of N
    processes against one TCP server. Validation failures are published
    in the response so every process raises the same error instead of
    hanging. Returns the per-process metas.
    """
    import json
    from horovod_tpu.ops.validation import (CollectiveMismatchError,
                                            validate_requests)
    if st.native is None:
        raise RuntimeError("multi-process eager collectives require the "
                           "native control plane")
    if not st.native.ping():
        raise RuntimeError(
            "multi-process eager collectives require the rendezvous "
            "channel: this process is not connected to a coordinator. "
            "Launch with `hvdrun` (which sets HOROVOD_KV) or set "
            "HOROVOD_KV=host:port of a running rendezvous server.")
    seq = st.op_cache.setdefault("_mc_seq", {})
    cnt = seq.get(opname, 0)
    seq[opname] = cnt + 1
    meta = {"dtype": str(arr.dtype), "shape": list(arr.shape),
            "op": op, "root": root_rank,
            "ndev": len(_mc_local_devices(st))}
    if extra is not None:
        # Caller-supplied descriptor validated for cross-rank equality
        # (e.g. grouped_allreduce's per-tensor boundaries, which the
        # flat payload's shape cannot express).
        meta["extra"] = extra
    # The coordinator consumes its own request from local memory; only
    # non-coordinator requests go over the wire.
    if st.process_rank != 0 and not st.native.kv_set(
            f"req/{opname}/{cnt}/{st.process_rank}",
            json.dumps(meta).encode()):
        raise RuntimeError(
            f"failed to post negotiation request for {opname} — "
            f"rendezvous connection lost")
    resp_key = f"resp/{opname}/{cnt}"

    if st.process_rank != 0:
        # The coordinator's sequential gather may legitimately take up
        # to N sequential per-peer waits when ranks arrive staggered,
        # so the response wait scales with world size.
        v = st.native.kv_get(resp_key,
                             timeout_ms=60000 * st.num_processes)
        if v is None:
            raise RuntimeError(
                f"negotiation timeout for {opname}: no response from "
                f"the coordinator (see stall warnings)")
        resp = json.loads(v.decode())
        if resp["status"] != "ok":
            if resp.get("kind") == "CollectiveMismatchError":
                raise CollectiveMismatchError(resp["error"])
            raise RuntimeError(resp["error"])
        return resp["metas"]

    # Coordinator: gather, validate, publish.
    def publish_error(exc):
        st.native.kv_set(resp_key, json.dumps(
            {"status": "error", "kind": type(exc).__name__,
             "error": str(exc)}).encode())

    import sys
    import time as _time
    from horovod_tpu.runtime.config import config as _config
    stall_s = max(1.0, float(_config.stall_warning_time))
    if timeout_s is None:
        timeout_s = 60.0 * st.num_processes
    deadline = _time.time() + timeout_s
    metas_by_rank = {st.process_rank: meta}  # own request: no round-trip
    pending = [r for r in range(st.num_processes)
               if r != st.process_rank]
    # Fast path: ONE blocking read per peer, preserving the
    # 2-round-trip-per-op negotiation count. The TOTAL fast-path
    # blocking is bounded by the stall threshold (not stall_s per
    # peer), so the first warning below fires on time even when
    # several peers are missing; laggards drop into the poll-and-warn
    # loop.
    t_fast = _time.time()
    for r in list(pending):
        budget = min(stall_s - (_time.time() - t_fast),
                     deadline - _time.time())
        if budget <= 0:
            break
        v = st.native.kv_get(f"req/{opname}/{cnt}/{r}",
                             timeout_ms=int(budget * 1000))
        if v is not None:
            metas_by_rank[r] = json.loads(v.decode())
            pending.remove(r)
    warned = False
    while pending:
        # NON-BLOCKING sweep BEFORE diagnosing (timeout_ms=0: the KV
        # server's wait_for(0) checks the predicate immediately): one
        # slow peer exhausting the shared fast-path budget must not get
        # healthy already-posted peers misreported as missing — and the
        # sweep must not itself delay the warning by 2s per dead peer.
        for r in list(pending):
            v = st.native.kv_get(f"req/{opname}/{cnt}/{r}",
                                 timeout_ms=0)
            if v is not None:
                metas_by_rank[r] = json.loads(v.decode())
                pending.remove(r)
        if not pending:
            break
        if not warned:
            # The reference's ready-ranks diagnostic
            # (CheckForStalledTensors, mpi_ops.cc:1150-1193): name the
            # stuck op AND which processes have/haven't posted its
            # request — the difference between "rank 3 died" and
            # "ranks disagree on op order" is exactly this list.
            sys.stderr.write(
                "WARNING: One or more tensors were submitted to be "
                "reduced, gathered or broadcasted by subset of ranks "
                "and are waiting for remainder of ranks for more than "
                "%d seconds. This may indicate that different ranks "
                "are trying to submit different tensors or that only "
                "subset of ranks is submitting tensors, which will "
                "cause deadlock.\nStalled op: %s "
                "[ready processes: %s, missing processes: %s]\n"
                % (int(stall_s), opname,
                   sorted(metas_by_rank), sorted(pending)))
            warned = True
        if _time.time() > deadline:
            exc = RuntimeError(
                f"negotiation timeout for {opname}: process(es) "
                f"{sorted(pending)} never submitted a request "
                f"(ready: {sorted(metas_by_rank)})")
            publish_error(exc)
            raise exc
        # Paced blocking poll between sweeps (bounded per peer so the
        # deadline check above stays roughly honest).
        for r in list(pending):
            v = st.native.kv_get(f"req/{opname}/{cnt}/{r}",
                                 timeout_ms=2000)
            if v is not None:
                metas_by_rank[r] = json.loads(v.decode())
                pending.remove(r)
    metas = [metas_by_rank[r] for r in range(st.num_processes)]
    # Uniform-ownership check on the *exchanged* counts: uneven device
    # ownership would make the duplication corrections in the mc
    # kernels silently wrong.
    ndevs = [m.get("ndev") for m in metas]
    if None not in ndevs and (
            len(set(ndevs)) > 1
            or ndevs[0] * st.num_processes != st.size):
        exc = RuntimeError(
            f"multi-process collectives require every process to own "
            f"the same number of devices; per-process counts {ndevs} "
            f"over world size {st.size}")
        publish_error(exc)
        raise exc
    try:
        validate_requests(
            name=opname, op=op,
            ops=[m["op"] for m in metas],
            dtypes=[m["dtype"] for m in metas],
            shapes=[tuple(m["shape"]) for m in metas],
            root_ranks=([m["root"] for m in metas]
                        if root_rank is not None else None),
            allow_dim0_mismatch=allow_dim0,
            native=st.native)
        extras = [m.get("extra") for m in metas]
        if any(e != extras[0] for e in extras):
            raise CollectiveMismatchError(
                f"Mismatched collective descriptor for {opname} "
                f"across ranks: {extras}")
    except Exception as exc:
        publish_error(exc)
        raise
    st.native.kv_set(resp_key, json.dumps(
        {"status": "ok", "metas": metas}).encode())
    return metas


def _mc_local_devices(st):
    import jax
    pidx = jax.process_index()
    return [d for d in st.devices if d.process_index == pidx]


def _mc_mesh2(st):
    """(proc, local) two-axis view of the multi-controller device set.

    Lets collectives reduce across PROCESSES on intra-process SHARDS:
    device (p, l) carries only chunk l of process p's block, the
    cross-process psum runs over ``proc`` in k parallel chunk groups,
    and the full result reassembles with an intra-process all_gather
    over ``local`` — so the wire payload per process is its block
    ONCE, not k times. Cached on the state.
    """
    cached = getattr(st, "mc_mesh2", None)
    if cached is not None:
        return cached
    from jax.sharding import Mesh
    procs = sorted({d.process_index for d in st.devices})
    rows = [[d for d in st.devices if d.process_index == p]
            for p in procs]
    k = len(rows[0])
    if any(len(r) != k for r in rows):
        raise RuntimeError(
            f"multi-process collectives require uniform device "
            f"ownership; got {[len(r) for r in rows]}")
    mesh = Mesh(np.array(rows), ("proc", "local"))
    st.mc_mesh2 = mesh
    return mesh


def _mc_chunked_global(st, mesh2, x: np.ndarray):
    """Shard `x` (this process's block) over the ``local`` axis:
    [nproc, k, chunk] global array where device (p, l) holds the l-th
    flat chunk of process p's block — each local device receives 1/k
    of the block instead of a full copy."""
    import jax
    k = mesh2.shape["local"]
    n = x.size
    chunk = -(-n // k)
    flat = np.ravel(x)
    if chunk * k != n:
        flat = np.pad(flat, (0, chunk * k - n))
    blocks = flat.reshape(k, chunk)
    pidx = jax.process_index()
    # This process's row of the (proc, local) mesh — rows are ordered
    # by process index by construction in `_mc_mesh2`.
    procs = [r[0].process_index for r in mesh2.devices]
    row = mesh2.devices[procs.index(pidx)]
    sharding = NamedSharding(mesh2, P("proc", "local"))
    shards = [jax.device_put(jnp.asarray(blocks[l])[None, None], row[l])
              for l in range(k)]
    return jax.make_array_from_single_device_arrays(
        (mesh2.shape["proc"], k, chunk), sharding, shards), chunk


def _mc_global_array(st, local_block: np.ndarray) -> jax.Array:
    """Assemble the [world, ...] global array where every device owned by
    this process holds `local_block` as its shard."""
    local = _mc_local_devices(st)
    if len(local) * st.num_processes != st.size:
        # The k-duplication correction in mc allreduce (and the
        # one-block-per-process selection in mc allgather) assumes a
        # uniform device count per process; uneven ownership would give
        # silently wrong sums.
        raise RuntimeError(
            f"multi-process collectives require every process to own the "
            f"same number of devices; this process owns {len(local)} of "
            f"{st.size} across {st.num_processes} processes")
    sharding = NamedSharding(st.mesh, P(st.axis_name))
    shape = (st.size,) + local_block.shape
    block = jnp.asarray(local_block)[None]
    shards = [jax.device_put(block, d) for d in local]
    return jax.make_array_from_single_device_arrays(shape, sharding, shards)




def _timeline(st, name, phase, activity=None):
    if st.timeline is not None:
        st.timeline.record(name, phase, activity)


def _validate_per_rank(st, name: str, op: str, vals: List[np.ndarray],
                       root_rank: Optional[int] = None,
                       allow_dim0_mismatch: bool = False) -> None:
    """Cross-rank metadata validation — the contract of the reference
    coordinator's `ConstructMPIResponse` (`mpi_ops.cc:266-474`): ranks must
    agree on dtype, shape (allgather: all dims but 0), and root rank.
    Delegates to the native control plane when available; raises the same
    error category (a precondition failure) the reference surfaces as
    `tf.errors.FailedPreconditionError` (`mpi_ops_test.py:284-356`).
    """
    from horovod_tpu.ops.validation import validate_requests
    validate_requests(
        name=name, op=op,
        dtypes=[str(v.dtype) for v in vals],
        shapes=[tuple(v.shape) for v in vals],
        root_ranks=None if root_rank is None else [root_rank] * len(vals),
        allow_dim0_mismatch=allow_dim0_mismatch,
        native=st.native,
    )


def _shard_over_mesh(st, stacked: np.ndarray) -> jax.Array:
    """Place a [world, ...] host array so shard i lives on device i."""
    sharding = NamedSharding(st.mesh, P(st.axis_name))
    return jax.device_put(jnp.asarray(stacked), sharding)


# Cached once: _run_collective runs per collective per step, and
# re-resolving the family through the registry lock every dispatch
# would put avoidable lock traffic on the eager hot path.
_COLLECTIVES_COUNTER = None


def _collectives_counter():
    global _COLLECTIVES_COUNTER
    if _COLLECTIVES_COUNTER is None:
        from horovod_tpu.obs import catalog as _obs_catalog
        _COLLECTIVES_COUNTER = _obs_catalog.collective_metrics()[
            "dispatched"]
    return _COLLECTIVES_COUNTER


# Same caching rule for the straggler tracker's module (the tracker
# itself may be swapped by tests — resolve it per dispatch, cheaply).
_STRAGGLER_MOD = None


def _straggler():
    global _STRAGGLER_MOD
    if _STRAGGLER_MOD is None:
        from horovod_tpu.obs import straggler
        _STRAGGLER_MOD = straggler
    return _STRAGGLER_MOD


def _run_collective(st, key, fn, data, *, mesh=None, in_specs=None,
                    out_specs=None):
    """Dispatch a cached shard_map'd collective over the framework mesh
    (or an explicit `mesh`/`in_specs`, e.g. the chunked mc (proc,
    local) mesh). Default `out_specs=P()` (replicated result);
    reducescatter/alltoall pass `P(axis)` because each device's result
    differs.

    `data` is either a host [world, ...] stack (single-controller) or an
    already-placed global jax.Array (multi-controller).
    """
    import time as _time

    from horovod_tpu.resilience import chaos
    # Straggler attribution (obs/straggler.py): per-dispatch host-side
    # enter/exit timestamps around the WHOLE dispatch — the chaos
    # slow-site delay, compile-cache misses and a blocked rendezvous
    # all land inside the bracket, which is exactly the per-rank skew
    # the fleet view attributes.
    t_enter = _time.time()
    # The slow/hung-collective fault at the eager dispatch boundary
    # (the traced twin in ops/collectives.py fires at trace time): the
    # host thread blocks exactly as it would waiting on a dead peer's
    # rendezvous, so StallMonitor brackets around this call see the op
    # pending.
    chaos.slow_site("collective_slow")
    # Observability: eager dispatches are the only collectives the
    # host can still see at runtime (SPMD in-graph ones compile away)
    # — count them by op so a scrape shows the eager-path volume.
    _collectives_counter().inc(op=key[0])
    jitted = st.op_cache.get(key)
    if jitted is None:
        # check_vma=False: all_gather outputs are replicated by
        # construction but JAX's static replication checker cannot prove
        # it, so the check is disabled for these dispatch wrappers.
        shaped = jax.shard_map(
            fn, mesh=st.mesh if mesh is None else mesh,
            in_specs=P(st.axis_name) if in_specs is None else in_specs,
            out_specs=P() if out_specs is None else out_specs,
            check_vma=False,
        )
        jitted = jax.jit(shaped)
        st.op_cache[key] = jitted
    if not isinstance(data, jax.Array):
        data = _shard_over_mesh(st, data)
    out = jitted(data)
    _straggler().tracker().record(key[0], _time.time() - t_enter)
    return out


def allreduce(tensor, average: bool = True, name: Optional[str] = None,
              _meta_extra: Optional[str] = None):
    """Eager allreduce. Parity: `horovod/tensorflow/__init__.py:43-79`
    (dense path) — sum over ranks, divided by size when `average`.

    Accepts a `PerRank`, a plain (replicated) array, or an
    `IndexedSlices` (sparse path: allgather of values+indices,
    `__init__.py:61-72`). `_meta_extra`: internal — an opaque
    descriptor validated for cross-rank equality during negotiation.
    """
    from horovod_tpu.ops.sparse import IndexedSlices, allreduce_indexed_slices
    st = _state.check_initialized()
    if isinstance(tensor, IndexedSlices):
        return allreduce_indexed_slices(tensor, average=average, name=name)
    opname = _auto_name("HorovodAllreduce", name, tensor,
                        content_free=_is_multicontroller(st))
    st.stall_monitor and st.stall_monitor.begin(opname)
    _timeline(st, opname, "NEGOTIATING")
    try:
        if isinstance(tensor, PerRank):
            vals = tensor.values
            if len(vals) != st.size:
                raise ValueError(
                    f"per_rank got {len(vals)} values for world size {st.size}")
            _validate_per_rank(st, opname, "allreduce", vals)
            stacked = np.stack(vals)
            _timeline(st, opname, "TOP_LEVEL", "ALLREDUCE")

            def _kernel(x):
                return C.allreduce(x[0], average=average,
                                   axis_name=st.axis_name)
            key = ("allreduce", average, stacked.shape, str(stacked.dtype))
            return _run_collective(st, key, _kernel, stacked)
        if _is_multicontroller(st):
            # True MPMD path: this process's local tensor, reduced
            # across processes after KV negotiation; ranks are
            # processes, matching Horovod's process-rank model. With
            # k > 1 local devices the block is SHARDED over them
            # (``local`` axis of `_mc_mesh2`), the cross-process psum
            # runs over ``proc`` in k parallel chunk groups, and an
            # intra-process all_gather reassembles — wire payload per
            # process is its block once (no k-fold duplication).
            x = np.asarray(tensor)
            _mc_negotiate(st, opname, "allreduce", x, None, False,
                          extra=_meta_extra)
            _timeline(st, opname, "TOP_LEVEL", "ALLREDUCE")
            nproc = st.num_processes
            k = st.size // nproc
            if k == 1 or x.size == 0:
                # One device per process: the plain mesh psum is
                # already payload-optimal.
                def _kernel(g):
                    from jax import lax
                    s = lax.psum(g[0], st.axis_name)
                    if jnp.issubdtype(s.dtype, jnp.integer):
                        return s // nproc if average else s
                    return s / nproc if average else s
                key = ("mc_allreduce", average, x.shape, str(x.dtype))
                return _run_collective(
                    st, key, _kernel, _mc_global_array(st, x))
            mesh2 = _mc_mesh2(st)
            garr, chunk = _mc_chunked_global(st, mesh2, x)
            n, shape = x.size, x.shape  # static in the cached kernel

            def _kernel(g):
                from jax import lax
                s = lax.psum(g, "proc")            # [1, 1, chunk]
                full = lax.all_gather(s, "local", axis=1,
                                      tiled=True)  # [1, k, chunk]
                flat = full.reshape(-1)[:n].reshape(shape)
                if jnp.issubdtype(flat.dtype, jnp.integer):
                    return flat // nproc if average else flat
                return flat / nproc if average else flat
            key = ("mc_allreduce2", average, x.shape, str(x.dtype))
            return _run_collective(st, key, _kernel, garr, mesh=mesh2,
                                   in_specs=P("proc", "local"))
        # Replicated value: every rank contributes the same tensor.
        x = jnp.asarray(tensor)
        _timeline(st, opname, "TOP_LEVEL", "ALLREDUCE")
        return x if average else x * st.size
    finally:
        _timeline(st, opname, "DONE")
        st.stall_monitor and st.stall_monitor.end(opname)


def allgather(tensor, name: Optional[str] = None):
    """Eager allgather, concatenating along dim 0; per-rank dim-0 sizes may
    differ (MPI_Allgatherv semantics, `mpi_ops.cc:732-809`). Under XLA's
    static shapes the variable case pads each rank's block to the max
    dim-0, gathers, then compacts — the size exchange the reference
    coordinator does in negotiation (`mpi_ops.cc:345-405`) is a psum'd
    size vector here.
    """
    st = _state.check_initialized()
    opname = _auto_name("HorovodAllgather", name, tensor, skip_dim0=True,
                        content_free=_is_multicontroller(st))
    st.stall_monitor and st.stall_monitor.begin(opname)
    _timeline(st, opname, "NEGOTIATING")
    try:
        if isinstance(tensor, PerRank):
            vals = tensor.values
            if len(vals) != st.size:
                raise ValueError(
                    f"per_rank got {len(vals)} values for world size {st.size}")
            _validate_per_rank(st, opname, "allgather", vals,
                               allow_dim0_mismatch=True)
            sizes = [v.shape[0] if v.ndim else 1 for v in vals]
            max_len = max(sizes)
            padded = []
            for v in vals:
                v2 = v.reshape((1,)) if v.ndim == 0 else v
                pad = [(0, max_len - v2.shape[0])] + [(0, 0)] * (v2.ndim - 1)
                padded.append(np.pad(v2, pad))
            stacked = np.stack(padded)
            _timeline(st, opname, "TOP_LEVEL", "ALLGATHER")
            if len(set(sizes)) == 1:
                def _kernel(x):
                    return C.allgather(x[0], axis_name=st.axis_name)
                key = ("allgather", stacked.shape, str(stacked.dtype))
                return _run_collective(st, key, _kernel, stacked)

            size_arr = np.asarray(sizes, np.int32)

            def _kernel(x):
                g, _ = C.allgatherv(
                    x[0], jnp.int32(0), max_len=max_len,
                    axis_name=st.axis_name)
                return g
            key = ("allgatherv", stacked.shape, str(stacked.dtype))
            gathered = _run_collective(st, key, _kernel, stacked)
            parts = [gathered[r, :size_arr[r]] for r in range(st.size)]
            return jnp.concatenate(parts, axis=0)
        if _is_multicontroller(st):
            x = np.asarray(tensor)
            x = x.reshape((1,)) if x.ndim == 0 else x
            metas = _mc_negotiate(st, opname, "allgather", x, None, True)
            _timeline(st, opname, "TOP_LEVEL", "ALLGATHER")
            # Variable dim-0: sizes came back in negotiation (the
            # reference's response.tensor_sizes, mpi_ops.cc:345-405).
            proc_sizes = [m["shape"][0] if m["shape"] else 1
                          for m in metas]
            max_len = max(proc_sizes)
            pad = [(0, max_len - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
            padded = np.pad(x, pad)

            def _kernel(g):
                from jax import lax
                return lax.all_gather(g[0], st.axis_name, axis=0,
                                      tiled=False)
            key = ("mc_allgather", padded.shape, str(padded.dtype))
            gathered = _run_collective(
                st, key, _kernel, _mc_global_array(st, padded))
            # gathered: [world, max_len, ...]; devices of one process hold
            # identical copies, so select one representative row per
            # process ON DEVICE before the host transfer (avoids moving
            # the k-fold duplicate payload), then trim to true sizes.
            first_row = {}
            for i, d in enumerate(st.devices):
                first_row.setdefault(d.process_index, i)
            procs = sorted(first_row)
            picked = np.asarray(gathered[jnp.asarray(
                [first_row[p] for p in procs])])
            return jnp.concatenate(
                [picked[j, :proc_sizes[p]] for j, p in enumerate(procs)],
                axis=0)
        # Replicated value: result is size copies concatenated on dim 0.
        x = jnp.asarray(tensor)
        x2 = x.reshape((1,)) if x.ndim == 0 else x
        _timeline(st, opname, "TOP_LEVEL", "ALLGATHER")
        return jnp.concatenate([x2] * st.size, axis=0)
    finally:
        _timeline(st, opname, "DONE")
        st.stall_monitor and st.stall_monitor.end(opname)


def broadcast(tensor, root_rank: int, name: Optional[str] = None):
    """Eager broadcast from `root_rank`. Parity:
    `horovod/tensorflow/mpi_ops.py:173-190` / kernel `mpi_ops.cc:1110-1137`.
    """
    st = _state.check_initialized()
    opname = _auto_name("HorovodBroadcast", name, tensor,
                        content_free=_is_multicontroller(st))
    if not (0 <= root_rank < st.size):
        raise ValueError(
            f"broadcast root_rank {root_rank} out of range for size {st.size}")
    st.stall_monitor and st.stall_monitor.begin(opname)
    _timeline(st, opname, "NEGOTIATING")
    try:
        if isinstance(tensor, PerRank):
            vals = tensor.values
            if len(vals) != st.size:
                raise ValueError(
                    f"per_rank got {len(vals)} values for world size {st.size}")
            _validate_per_rank(st, opname, "broadcast", vals,
                               root_rank=root_rank)
            stacked = np.stack(vals)
            _timeline(st, opname, "TOP_LEVEL", "BCAST")

            def _kernel(x):
                return C.broadcast(x[0], root_rank, axis_name=st.axis_name)
            key = ("broadcast", root_rank, stacked.shape, str(stacked.dtype))
            return _run_collective(st, key, _kernel, stacked)
        if _is_multicontroller(st):
            x = np.asarray(tensor)
            # root_rank is a process rank (Horovod semantics).
            if not (0 <= root_rank < st.num_processes):
                raise ValueError(
                    f"broadcast root_rank {root_rank} out of range for "
                    f"{st.num_processes} processes")
            _mc_negotiate(st, opname, "broadcast", x, root_rank, False)
            _timeline(st, opname, "TOP_LEVEL", "BCAST")
            root_dev = next(i for i, d in enumerate(st.devices)
                            if d.process_index == root_rank)

            def _kernel(g):
                return C.broadcast(g[0], root_dev, axis_name=st.axis_name)
            key = ("mc_broadcast", root_rank, x.shape, str(x.dtype))
            return _run_collective(
                st, key, _kernel, _mc_global_array(st, x))
        _timeline(st, opname, "TOP_LEVEL", "BCAST")
        return jnp.asarray(tensor)
    finally:
        _timeline(st, opname, "DONE")
        st.stall_monitor and st.stall_monitor.end(opname)


def _mc_positions(st):
    """Mesh-axis-position bookkeeping for the mc kernels. The mesh is
    built from `st.devices` in backend order, which is NOT guaranteed
    to group processes contiguously (the same reason `_mc_mesh2` and
    mc allgather map by `process_index` instead of assuming position
    `i` belongs to process `i // k`). Returns `(proc_of_pos,
    positions)`: the process rank owning each axis position, and each
    rank's positions in ascending order — rank being the index in the
    sorted `process_index` list, the convention all mc paths share."""
    procs = sorted({d.process_index for d in st.devices})
    rank_of = {p: i for i, p in enumerate(procs)}
    proc_of_pos = [rank_of[d.process_index] for d in st.devices]
    positions = [[] for _ in procs]
    for i, r in enumerate(proc_of_pos):
        positions[r].append(i)
    return proc_of_pos, positions


def alltoall(tensor, name: Optional[str] = None):
    """Eager all-to-all (TPU-native extension; later-Horovod
    `hvd.alltoall` forward parity): rank r receives the r-th dim-0 slice
    from every rank, concatenated.

    Accepts `PerRank` (returns all ranks' results stacked [world, ...]),
    a plain array in multi-controller mode (this process's block;
    returns THIS process's received tensor), or a plain replicated array
    in single-controller mode (returns the stacked [world, ...] results,
    consistent with `reducescatter`'s replicated convention).
    """
    st = _state.check_initialized()
    opname = _auto_name("HorovodAlltoall", name, tensor,
                        content_free=_is_multicontroller(st))
    st.stall_monitor and st.stall_monitor.begin(opname)
    _timeline(st, opname, "NEGOTIATING")
    try:
        if isinstance(tensor, PerRank):
            vals = tensor.values
            if len(vals) != st.size:
                raise ValueError(
                    f"per_rank got {len(vals)} values for world size {st.size}")
            _validate_per_rank(st, opname, "alltoall", vals)
            stacked = np.stack(vals)  # [world, world*chunk, ...]
            if stacked.shape[1] % st.size:
                raise ValueError(
                    f"alltoall dim 0 ({stacked.shape[1]}) must be "
                    f"divisible by world size {st.size}")
            _timeline(st, opname, "TOP_LEVEL", "ALLTOALL")

            def _kernel(x):
                return C.alltoall(x[0], axis_name=st.axis_name)

            out = _run_collective(
                st, ("alltoall", stacked.shape, str(stacked.dtype)),
                _kernel, stacked, out_specs=P(st.axis_name))
            # out concatenates per-device results on dim 0; re-stack so
            # out[r] is rank r's received tensor.
            return out.reshape((st.size,) + stacked.shape[1:])
        if _is_multicontroller(st):
            # True MPMD path: process p sends its q-th dim-0 slice to
            # process q. With k > 1 local devices (all holding the same
            # block), the exchange runs in k parallel one-device-per-
            # process groups — every device computes its process's full
            # result, no cross-group duplication on the wire per group.
            x = np.asarray(tensor)
            nproc = st.num_processes
            if x.shape[0] % nproc:
                raise ValueError(
                    f"alltoall dim 0 ({x.shape[0]}) must be divisible "
                    f"by the number of processes {nproc}")
            _mc_negotiate(st, opname, "alltoall", x, None, False)
            _timeline(st, opname, "TOP_LEVEL", "ALLTOALL")
            k = st.size // nproc
            # One device per process per group, at the devices' ACTUAL
            # mesh positions (no process-contiguity assumption); group
            # members in rank order, so member p receives slice p.
            _, positions = _mc_positions(st)
            groups = [[positions[p][j] for p in range(nproc)]
                      for j in range(k)]

            def _kernel(g):
                from jax import lax
                return lax.all_to_all(
                    g[0], st.axis_name, split_axis=0, concat_axis=0,
                    tiled=True, axis_index_groups=groups)

            out = _run_collective(
                st, ("mc_alltoall", x.shape, str(x.dtype)),
                _kernel, _mc_global_array(st, x),
                out_specs=P(st.axis_name))
            # Every local device holds this process's full result.
            return jnp.asarray(np.asarray(out.addressable_shards[0].data))
        # Replicated value: rank r receives slice r from every rank —
        # size copies of x's r-th slice; all ranks' results stacked.
        x = jnp.asarray(tensor)
        if x.shape[0] % st.size:
            raise ValueError(
                f"alltoall dim 0 ({x.shape[0]}) must be divisible by "
                f"world size {st.size}")
        _timeline(st, opname, "TOP_LEVEL", "ALLTOALL")
        s0 = x.shape[0] // st.size
        return jnp.stack([
            jnp.concatenate([x[r * s0:(r + 1) * s0]] * st.size, axis=0)
            for r in range(st.size)])
    finally:
        _timeline(st, opname, "DONE")
        st.stall_monitor and st.stall_monitor.end(opname)


def reducescatter(tensor, average: bool = False, name: Optional[str] = None):
    """Eager reduce-scatter (TPU-native extension; later-Horovod
    `hvd.reducescatter` forward parity): dim 0 is split across ranks
    after a sum.

    `PerRank` and single-controller replicated inputs return all ranks'
    shards stacked [world, ...]; a plain array in multi-controller mode
    is this process's local tensor and THIS process's shard of the
    cross-process reduction is returned (true MPMD semantics, matching
    `allreduce`'s plain-array convention).
    """
    st = _state.check_initialized()
    opname = _auto_name("HorovodReducescatter", name, tensor,
                        content_free=_is_multicontroller(st))
    st.stall_monitor and st.stall_monitor.begin(opname)
    _timeline(st, opname, "NEGOTIATING")
    try:
        if isinstance(tensor, PerRank):
            vals = tensor.values
            if len(vals) != st.size:
                raise ValueError(
                    f"per_rank got {len(vals)} values for world size {st.size}")
            _validate_per_rank(st, opname, "reducescatter", vals)
            stacked = np.stack(vals)
            if stacked.shape[1] % st.size:
                raise ValueError(
                    f"reducescatter dim 0 ({stacked.shape[1]}) must be "
                    f"divisible by world size {st.size}")
            _timeline(st, opname, "TOP_LEVEL", "REDUCESCATTER")

            def _kernel(x):
                return C.reducescatter(x[0], average=average,
                                       axis_name=st.axis_name)
            out = _run_collective(
                st, ("reducescatter", average, stacked.shape,
                     str(stacked.dtype)),
                _kernel, stacked, out_specs=P(st.axis_name))
            # out[r] is rank r's shard (dim0/world rows of the sum).
            shard0 = stacked.shape[1] // st.size
            return out.reshape((st.size, shard0) + stacked.shape[2:])
        if _is_multicontroller(st):
            # True MPMD path: processes are the
            # ranks; every local device holds this process's block, so
            # the device-axis reduction counts each process k times and
            # the sum is corrected by /k (exact for integers too: every
            # term is duplicated exactly k-fold).
            x = np.asarray(tensor)
            nproc = st.num_processes
            if x.shape[0] % nproc:
                raise ValueError(
                    f"reducescatter dim 0 ({x.shape[0]}) must be "
                    f"divisible by the number of processes {nproc}")
            _mc_negotiate(st, opname, "reducescatter", x, None, False)
            _timeline(st, opname, "TOP_LEVEL", "REDUCESCATTER")
            k = st.size // nproc
            shard0 = x.shape[0] // nproc
            div = k * (nproc if average else 1)
            scatter_ok = x.shape[0] % st.size == 0
            proc_of_pos, positions = _mc_positions(st)

            if scatter_ok:
                # One psum_scatter over the device axis. With k > 1
                # local devices the block still crosses the wire k
                # times (each duplicate device participates); the
                # `_mc_mesh2` chunked scheme mc allreduce uses would
                # shave that and is the follow-up if eager
                # reducescatter ever becomes hot — eager ops pay a
                # host round-trip anyway.
                # psum_scatter hands chunk i to mesh POSITION i, and
                # positions are not process-contiguous in general, so
                # the summand's chunks are pre-permuted (sum commutes)
                # such that the device at position i receives chunk
                # `rank(i)*k + ordinal-of-i-within-its-rank` — i.e.
                # every process's devices end up holding exactly its
                # dim-0 shard, in ascending-position order.
                chunkrows = x.shape[0] // st.size
                desired = [0] * st.size
                for p, pos in enumerate(positions):
                    for j, i in enumerate(pos):
                        desired[i] = p * k + j
                perm = np.asarray(desired)

                def _kernel(g):
                    from jax import lax
                    xr = g[0].reshape((st.size, chunkrows)
                                      + x.shape[1:])
                    xp = xr[jnp.asarray(perm)].reshape(x.shape)
                    s = lax.psum_scatter(xp, st.axis_name,
                                         scatter_dimension=0, tiled=True)
                    if jnp.issubdtype(s.dtype, jnp.integer):
                        return s // div
                    return s / div
            else:
                # dim0 divides nproc but not nproc*k: full psum, then
                # each device slices its process's shard (rank looked
                # up from the device's actual mesh position).
                proc_arr = np.asarray(proc_of_pos)

                def _kernel(g):
                    from jax import lax
                    s = lax.psum(g[0], st.axis_name)
                    p = jnp.asarray(proc_arr)[
                        lax.axis_index(st.axis_name)]
                    sl = lax.dynamic_slice_in_dim(
                        s, p * shard0, shard0, 0)
                    if jnp.issubdtype(sl.dtype, jnp.integer):
                        return sl // div
                    return sl / div

            out = _run_collective(
                st, ("mc_reducescatter", average, scatter_ok, x.shape,
                     str(x.dtype)),
                _kernel, _mc_global_array(st, x),
                out_specs=P(st.axis_name))
            if scatter_ok:
                # This process's chunks, ascending mesh position =
                # ascending chunk index by the permutation above.
                shards = sorted(
                    out.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
                return jnp.asarray(np.concatenate(
                    [np.asarray(s.data) for s in shards], axis=0))
            # Fallback kernel: every local device holds the full shard.
            return jnp.asarray(np.asarray(
                out.addressable_shards[0].data))
        # Replicated value: consistent with the PerRank path — the
        # reduced tensor is x*size (or x when averaging), scattered
        # along dim 0.
        x = jnp.asarray(tensor)
        if x.shape[0] % st.size:
            raise ValueError(
                f"reducescatter dim 0 ({x.shape[0]}) must be divisible by "
                f"world size {st.size}")
        _timeline(st, opname, "TOP_LEVEL", "REDUCESCATTER")
        reduced = x if average else x * st.size
        return reduced.reshape(
            (st.size, x.shape[0] // st.size) + x.shape[1:])
    finally:
        _timeline(st, opname, "DONE")
        st.stall_monitor and st.stall_monitor.end(opname)
