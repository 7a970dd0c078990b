"""JAX adapter — the primary framework adapter.

The TPU-native analogue of the reference's TF adapter
(`horovod/tensorflow/__init__.py`): wrap an optimizer so gradients are
allreduce-averaged across the data-parallel mesh before being applied
(`DistributedOptimizer`, reference `:127-186`), and broadcast initial
parameters from a root rank so all workers start identically
(`broadcast_global_variables`, reference `:82-124`).

Where the reference intercepts `compute_gradients` on a
`tf.train.Optimizer`, here we wrap an `optax.GradientTransformation`:
its `update()` first performs a *fused* (bucketed) `psum` of the incoming
gradients over the mesh axis — tensor fusion riding ICI — then delegates
to the wrapped transformation. Sparse `IndexedSlices` leaves take the
allgather path (reference `:61-72`).
"""

from __future__ import annotations

import pickle
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import eager
from horovod_tpu.ops.fusion import (exchange_for, fused_allreduce_tree,
                                    step_compiler_options)
from horovod_tpu.ops.sparse import IndexedSlices
from horovod_tpu.runtime import state as _state
from horovod_tpu.runtime.config import config


def _axis_in_scope(axis_name: str) -> bool:
    """True when `axis_name` is bound by an enclosing shard_map/pmap trace."""
    try:
        lax.axis_index(axis_name)
        return True
    except NameError:
        return False


def allreduce_gradients(grads: Any, *, axis_name: Optional[str] = None,
                        average: bool = True,
                        threshold: Optional[int] = None,
                        reduce_dtype: Optional[Any] = None) -> Any:
    """Fused allreduce of a gradient pytree.

    Inside shard_map (axis bound): bucketed `psum` per SURVEY §7 step 3,
    semantics of the reference's per-gradient `hvd.allreduce`
    (`horovod/tensorflow/__init__.py:164-186`) plus tensor fusion
    (`docs/tensor-fusion.md`). Outside any SPMD context it is the
    size()==1 no-op the reference also short-circuits (`:174`).
    Sparse `IndexedSlices` leaves dispatch to the allgather path.
    """
    axis = axis_name or config.mesh_axis_name
    if reduce_dtype is None and config.allreduce_dtype:
        reduce_dtype = jnp.dtype(config.allreduce_dtype)

    sparse_leaves = {}

    def _is_leaf(x):
        return isinstance(x, IndexedSlices)

    leaves, treedef = jax.tree.flatten(grads, is_leaf=_is_leaf)
    dense_idx = [i for i, l in enumerate(leaves)
                 if not isinstance(l, IndexedSlices)]

    if not _axis_in_scope(axis):
        return grads  # single-program / size-1 path

    dense = [leaves[i] for i in dense_idx]
    reduced_dense = fused_allreduce_tree(
        dense, axis_name=axis, average=average,
        threshold=threshold, reduce_dtype=reduce_dtype)
    out = list(leaves)
    for i, r in zip(dense_idx, reduced_dense):
        out[i] = r
    for i, l in enumerate(leaves):
        if isinstance(l, IndexedSlices):
            vals = lax.all_gather(l.values, axis, axis=0, tiled=True)
            idxs = lax.all_gather(l.indices, axis, axis=0, tiled=True)
            if average:
                vals = vals / lax.psum(jnp.ones((), vals.dtype), axis)
            out[i] = IndexedSlices(vals, idxs, l.dense_shape)
    return jax.tree.unflatten(treedef, out)


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         *, average: bool = True,
                         axis_name: Optional[str] = None,
                         fusion_threshold: Optional[int] = None,
                         reduce_dtype: Optional[Any] = None,
                         backward_passes_per_step: int = 1,
                         compression: Optional[str] = None,
                         compression_rank: int = 4
                         ) -> optax.GradientTransformation:
    """Wrap an optax transformation with gradient allreduce.

    Parity: `hvd.DistributedOptimizer` (`horovod/tensorflow/__init__.py:
    127-186`) — same contract (allreduce-average gradients, then delegate
    every other behavior to the wrapped optimizer), SPMD mechanics.

    ``backward_passes_per_step=k`` (later Horovod's gradient
    accumulation): local gradients accumulate for k microbatch steps
    (`optax.MultiSteps`) and the allreduce runs ONCE per k, on the
    accumulated mean — the bandwidth contract the name promises. The
    returned transformation is marked distributed either way, so
    `make_train_step` never adds a second allreduce on top.

    ``compression``: "fp16" = the reference's wire-dtype compression
    (`horovod/tensorflow/__init__.py:119-124` Compression.fp16 —
    sugar for ``reduce_dtype="float16"``); "powersgd" = rank-r
    factorized allreduce with error feedback
    (`ops.compression.powersgd_allreduce`, ``compression_rank``) —
    matrix gradients ship r·(n+m) floats instead of n·m.
    """
    if compression not in (None, "fp16", "powersgd"):
        raise ValueError(
            f"compression must be None|'fp16'|'powersgd', "
            f"got {compression!r}")
    if compression == "fp16" and reduce_dtype is None:
        reduce_dtype = jnp.float16

    if compression == "powersgd":
        if not average:
            raise ValueError(
                "compression='powersgd' averages by construction "
                "(the factor allreduces are means); average=False is "
                "not supported")
        from horovod_tpu.ops.compression import powersgd_allreduce
        compressor = powersgd_allreduce(
            rank=compression_rank, axis_name=axis_name,
            threshold=fusion_threshold, reduce_dtype=reduce_dtype)

        def init_fn(params):
            return (compressor.init(params), optimizer.init(params))

        def update_fn(updates, opt_state, params=None, **extra):
            c_state, in_state = opt_state
            updates, c_state = compressor.update(updates, c_state,
                                                 params)
            updates, in_state = optimizer.update(updates, in_state,
                                                 params, **extra)
            return updates, (c_state, in_state)
    else:
        def init_fn(params):
            return optimizer.init(params)

        def update_fn(updates, opt_state, params=None, **extra):
            updates = allreduce_gradients(
                updates, axis_name=axis_name, average=average,
                threshold=fusion_threshold, reduce_dtype=reduce_dtype)
            return optimizer.update(updates, opt_state, params, **extra)

    inner = _DistributedTransformation(init_fn, update_fn)
    if backward_passes_per_step > 1:
        ms = optax.MultiSteps(
            inner, every_k_schedule=backward_passes_per_step)

        def ms_update(updates, opt_state, params=None, **extra):
            # MultiSteps accumulates into dense zeros_like buffers;
            # an IndexedSlices leaf would hit an opaque tree-arith
            # error deep inside optax — refuse clearly instead.
            from horovod_tpu.ops.sparse import IndexedSlices
            leaves = jax.tree.leaves(
                updates,
                is_leaf=lambda x: isinstance(x, IndexedSlices))
            if any(isinstance(l, IndexedSlices) for l in leaves):
                raise NotImplementedError(
                    "backward_passes_per_step > 1 does not support "
                    "sparse IndexedSlices gradients (densify them or "
                    "accumulate at k=1)")
            return ms.update(updates, opt_state, params, **extra)

        return _DistributedTransformation(ms.init, ms_update)
    return inner


class _DistributedTransformation(optax.GradientTransformation):
    """Typed marker so make_train_step can tell an already-distributed
    transformation apart and not allreduce twice."""


class DistributedGradientTape:
    """Convenience value-and-grad wrapper (API familiarity with later
    Horovod's `hvd.DistributedGradientTape`): computes grads and
    allreduces them in one call."""

    def __init__(self, loss_fn: Callable, *, axis_name: Optional[str] = None,
                 average: bool = True):
        self._vg = jax.value_and_grad(loss_fn)
        self._axis = axis_name
        self._avg = average

    def __call__(self, params, *args, **kwargs):
        loss, grads = self._vg(params, *args, **kwargs)
        grads = allreduce_gradients(
            grads, axis_name=self._axis, average=self._avg)
        return loss, grads


def broadcast_global_variables(params: Any, root_rank: int = 0) -> Any:
    """Broadcast a parameter pytree from `root_rank` to all ranks.

    Parity: `broadcast_global_variables` (`horovod/tensorflow/__init__.py:
    82-90`). Single-controller: parameters are already globally consistent
    (one copy), so this replicates them over the mesh; multi-controller:
    a true cross-process broadcast so restored/initialized rank-0 weights
    win (the checkpoint/restore contract, SURVEY §5.4).
    """
    return jax.tree.map(
        lambda x: eager.broadcast(x, root_rank), params)


# Aliases matching later-Horovod naming (broadcast_parameters /
# broadcast_optimizer_state are the torch-API names for the same contract).
def broadcast_parameters(params: Any, root_rank: int = 0) -> Any:
    return broadcast_global_variables(params, root_rank)


def broadcast_optimizer_state(opt_state: Any, root_rank: int = 0) -> Any:
    return broadcast_global_variables(opt_state, root_rank)


def broadcast_object(obj: Any, root_rank: int = 0) -> Any:
    """Broadcast an arbitrary picklable object from root_rank (parity with
    later Horovod's `hvd.broadcast_object`; used for epoch counters etc.).
    """
    st = _state.check_initialized()
    if st.num_processes <= 1:
        return obj
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    # Length exchange first (scalars agree in shape on every rank), then
    # the padded payload — broadcast requires identical shapes across
    # ranks, like the reference (`mpi_ops.cc:409-430`).
    n = int(np.asarray(eager.broadcast(
        np.int64(payload.size), root_rank, name="bcast_object_len")))
    buf = np.zeros(n, np.uint8)
    if st.process_rank == root_rank:
        buf[:] = payload[:n]
    out = np.asarray(eager.broadcast(buf, root_rank,
                                     name="bcast_object_payload"))
    return pickle.loads(out.tobytes())


def allgather_object(obj: Any) -> list:
    """Gather one picklable object per rank into a list ordered by rank
    (parity with later Horovod's `hvd.allgather_object`; pairs with
    `broadcast_object` for metric/metadata collection).

    Rides the variable-dim-0 allgather (`MPI_Allgatherv` semantics,
    reference `mpi_ops.cc:732-809`): each process contributes its
    pickled payload as a [len, 1] uint8 block plus a length row, so
    payloads of different sizes need no padding negotiation beyond the
    size exchange the allgather already does.
    """
    st = _state.check_initialized()
    world = st.num_processes if st.num_processes > 1 else st.size
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    if world <= 1:
        return [obj]
    if st.num_processes <= 1:
        # Single-controller SPMD: every rank holds the same object;
        # fresh copies, no gathered blob.
        data = payload.tobytes()
        return [pickle.loads(data) for _ in range(world)]
    sizes = np.asarray(eager.allgather(
        np.asarray([payload.size], np.int64),
        name="agather_object_len"))
    blob = np.asarray(eager.allgather(payload,
                                      name="agather_object_payload"))
    out, off = [], 0
    for n in sizes:
        out.append(pickle.loads(blob[off:off + int(n)].tobytes()))
        off += int(n)
    return out


def grouped_allreduce(tensors: Sequence[Any], average: bool = True,
                      name: Optional[str] = None) -> list:
    """Allreduce a list of tensors as one fused operation (later
    Horovod's `hvd.grouped_allreduce`): same-dtype tensors are packed
    into a single flat collective — explicit access to the fusion the
    `DistributedOptimizer` path applies automatically
    (`ops/fusion.py`, docs/tensor-fusion.md).
    """
    if any(isinstance(t, eager.PerRank) for t in tensors):
        raise TypeError(
            "grouped_allreduce takes plain arrays (one per call site), "
            "not per_rank inputs; allreduce each per_rank individually")
    arrs = [np.asarray(t) for t in tensors]
    out: list = [None] * len(arrs)
    # One collective per dtype, order-independent: the caller asked for
    # a grouped op, so all same-dtype tensors pack together even when
    # interleaved with other dtypes.
    by_dtype: dict = {}
    for i, a in enumerate(arrs):
        by_dtype.setdefault(a.dtype, []).append(i)
    # Packing erases per-tensor boundaries from the flat payload's
    # metadata ((2,)+(4,) vs (4,)+(2,): same flat shape!), so the FULL
    # group composition rides the control-plane negotiation of every
    # bucket as an opaque descriptor validated for cross-rank equality
    # — no extra data-plane collectives, and any disagreement (tensor
    # boundaries, dtype composition, ordering) raises crisply on the
    # first bucket. Buckets are named by ordinal, never by dtype, so
    # disagreeing ranks still negotiate under matching keys instead of
    # timing out on keys the peer never posts.
    desc = repr([(tuple(a.shape), str(a.dtype)) for a in arrs])
    for j, bucket in enumerate(by_dtype.values()):
        flat = np.concatenate([arrs[i].ravel() for i in bucket])
        red = np.asarray(eager.allreduce(
            flat, average=average,
            name=name and f"{name}_g{j}",
            _meta_extra=desc))
        off = 0
        for i in bucket:
            n = arrs[i].size
            out[i] = red[off:off + n].reshape(arrs[i].shape)
            off += n
    return out


def make_global_batch(batch: Any, *, axis_name: Optional[str] = None) -> Any:
    """Assemble per-process local batches into global arrays sharded over
    the data axis — how a multi-controller training loop feeds
    `make_train_step` (each process loads its own shard, the reference's
    per-worker data sharding pattern, `examples/keras_mnist_advanced.py:
    113-119`). A no-op returning device arrays in single-controller mode.
    """
    from jax.sharding import NamedSharding
    st = _state.check_initialized()
    if st.num_processes <= 1:
        return jax.tree.map(jnp.asarray, batch)
    sharding = NamedSharding(st.mesh, P(axis_name or st.axis_name))
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(
            sharding, np.asarray(x)), batch)


def commit_step_state(mesh, tree):
    """Commit a train step's carried state (params, optimizer state)
    to ``mesh``, replicated, unless it already is.

    The step returns its state committed to the mesh, and JAX types
    an array by the mesh it is committed to. State fresh from
    `model.init` / `tx.init` is committed to none, so without this the
    step traced and compiled TWICE: once for the first call's
    uncommitted state, once more for its own output — a second full
    XLA compile inside "step 2". After the first call this is one
    sharding comparison per leaf."""
    from jax.sharding import NamedSharding
    if mesh.is_multi_process:
        return tree   # process-local state: jit assembles it as before
    rep = NamedSharding(mesh, P())
    if all(getattr(x, "sharding", None) == rep
           for x in jax.tree.leaves(tree)):
        return tree
    return jax.device_put(tree, rep)


def make_train_step(loss_fn: Callable, tx: optax.GradientTransformation,
                    *, mesh=None, axis_name: Optional[str] = None,
                    fusion_threshold: Optional[int] = None,
                    reduce_dtype: Optional[Any] = None,
                    donate: bool = True) -> Callable:
    """Build the jitted SPMD data-parallel train step — the hot path
    (reference SURVEY §3.2), compiled once.

    loss_fn(params, batch) -> scalar loss over the *per-device* microbatch.
    Returns step(params, opt_state, batch) -> (params, opt_state, loss)
    where `batch` is sharded over the data axis and params/opt_state are
    replicated.

    The gradient exchange is `ops/fusion.py`'s bucketed `psum`, and
    what is compiled follows the mesh (`ops/fusion.overlaps`). On a
    TPU mesh whose data axis is larger than 1 the step is compiled with
    the keys that make its all-reduces ASYNCHRONOUS collectives
    (`step_compiler_options`) and its body is traced under
    `exchange_for(mesh, axis)`, where a leaf of `ALONE_BYTES` or more
    is reduced alone in its own shape and the small leaves' buckets as
    [rows, 128]: the compiler then carries a matrix's all-reduce
    through the weight-gradient matmuls (which it moves behind the
    backward pass for the purpose) and what is ready last - the
    embedding, the small leaves' bucket - through the optimizer's
    update: the latency hiding the reference builds by hand with its
    background thread + fusion buffer (measured on four v5e chips:
    PERF.md §5, `gpt2-medium.train-dp4`). On any other platform, and
    on one chip, the options are the combiner pin alone and the
    program is what it was.
    """
    st = _state.check_initialized()
    mesh = mesh or st.mesh
    axis = axis_name or st.axis_name
    already_distributed = isinstance(tx, _DistributedTransformation)
    if already_distributed and (fusion_threshold is not None
                                or reduce_dtype is not None):
        # Same contract as make_cnn_train_step: the DistributedOptimizer
        # owns the allreduce, so the factory's wire knobs would be
        # silently dead — refuse instead.
        raise ValueError(
            "tx is an hvd.DistributedOptimizer, which owns the "
            "gradient allreduce — pass fusion_threshold/reduce_dtype "
            "to DistributedOptimizer(...) instead of the step factory")

    def step(params, opt_state, batch):
        with exchange_for(mesh, axis):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            if not already_distributed:
                grads = allreduce_gradients(
                    grads, axis_name=axis, threshold=fusion_threshold,
                    reduce_dtype=reduce_dtype)
            loss = lax.pmean(loss, axis)
            updates, new_opt_state = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        return new_params, new_opt_state, loss

    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P(axis)),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    donate_argnums = (0, 1) if donate else ()
    from horovod_tpu.utils.timeline import step_bracket
    jitted = jax.jit(
        sharded, donate_argnums=donate_argnums,
        compiler_options=step_compiler_options(mesh, axis) or None)

    def placed(params, opt_state, batch):
        params, opt_state = commit_step_state(
            mesh, (params, opt_state))
        return jitted(params, opt_state, batch)

    stepped = step_bracket(placed)
    # `__wrapped__` resolves to the innermost JITTED step
    # (`step.__wrapped__.lower(...)`, models/train.py's convention).
    stepped.__wrapped__ = jitted
    return stepped
