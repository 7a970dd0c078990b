"""Tensor fusion — bucketed gradient allreduce.

TPU-native translation of the reference's Tensor Fusion (SURVEY C5;
`docs/tensor-fusion.md:7-28`, fusion buffer `mpi_ops.cc:667-700`,
response merging `mpi_ops.cc:1392-1419`): many small gradients are batched
into one collective to amortize per-collective latency. Where the
reference memcpys into a persistent 64 MB device buffer, here each bucket
is a flat concatenation of raveled leaves — XLA fuses the concat/split
with neighboring ops, so the "fusion buffer" never exists as a separate
copy in HBM — followed by ONE psum per bucket.

Buckets group leaves by dtype (the reference fuses only same-dtype
responses, `mpi_ops.cc:1397-1404`) and close at
`HOROVOD_FUSION_THRESHOLD` bytes (default 64 MB; 0 disables fusion =
one collective per tensor, matching `docs/tensor-fusion.md:18-28`).
`HVD_FUSION_MB` is the megabyte-denominated alias (fractions accepted;
the byte-exact reference variable wins when both are set) — see
`runtime.config.Config.refresh`.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.runtime.config import config


# XLA's backend collective-combiner passes re-merge independent
# all-reduces into one tuple all-reduce AFTER our bucketing (observed
# on the CPU backend: N independent bucket psums compile to a single
# tuple all-reduce scheduled after the whole backward — voiding the
# per-bucket overlap structure docs/scaling.md's model rests on).
# `xla_disable_hlo_passes` is the generic, per-compile escape hatch;
# unknown pass names are ignored, so one list covers every backend
# (verified on CPU: "cpu-all-reduce-combiner" is the pass that
# re-merges; "all-reduce-combiner" is the OSS/GPU/TPU pass name).
_COMBINER_PASSES = "all-reduce-combiner,cpu-all-reduce-combiner"


def combiner_override_options() -> dict:
    """jit `compiler_options` that pin HOROVOD_FUSION_THRESHOLD's
    bucket granularity through XLA's backend passes.

    The reference's fusion threshold controls collective granularity
    end to end (`mpi_ops.cc:1392-1419` merges *up to* the threshold,
    never past it); without this override the XLA backend combiner
    silently re-merges our buckets, so the env var's semantic — and
    the bucket-level backward/collective overlap — would stop at the
    IR. Returns {} when HOROVOD_XLA_COMBINER=xla (opt out: let XLA
    choose granularity). A compiler that rejected the option would
    fail the step's compile loudly; the CPU and the TPU v5e compilers
    of this installation both accept it (chip_smoke.py compiles the
    ResNet and `make_train_step` steps with it).
    """
    if config.xla_combiner == "xla":
        return {}
    return {"xla_disable_hlo_passes": _COMBINER_PASSES}


def _leaf_bytes(leaf) -> int:
    return int(np.prod(leaf.shape)) * leaf.dtype.itemsize if leaf.ndim else leaf.dtype.itemsize


def plan_buckets(leaves: List[Any],
                 threshold: Optional[int] = None) -> List[List[int]]:
    """Greedy same-dtype bucketing up to `threshold` bytes.

    Mirrors the coordinator's greedy merge of consecutive same-dtype
    allreduce responses under the fusion threshold
    (`mpi_ops.cc:1392-1419`). Returns a list of buckets, each a list of
    leaf indices. threshold<=0 disables fusion (singleton buckets).
    """
    if threshold is None:
        threshold = config.fusion_threshold
    if threshold <= 0:
        return [[i] for i in range(len(leaves))]
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    cur_dtype = None
    for i, leaf in enumerate(leaves):
        b = _leaf_bytes(leaf)
        if cur and (leaf.dtype != cur_dtype or cur_bytes + b > threshold):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += b
        cur_dtype = leaf.dtype
    if cur:
        buckets.append(cur)
    return buckets


def fused_allreduce_leaves(leaves: List[Any], *, axis_name: str,
                           average: bool = True,
                           threshold: Optional[int] = None,
                           reduce_dtype: Optional[Any] = None) -> List[Any]:
    """Allreduce a list of arrays with bucket fusion. Jittable; call
    inside shard_map with `axis_name` bound.

    reduce_dtype: optionally reduce in a different dtype (e.g. bf16) and
    cast back — a TPU-native bandwidth optimization (HOROVOD_ALLREDUCE_DTYPE).
    """
    buckets = plan_buckets(leaves, threshold)
    out: List[Any] = [None] * len(leaves)
    for bucket in buckets:
        if len(bucket) == 1:
            i = bucket[0]
            x = leaves[i]
            if reduce_dtype is not None and x.dtype != reduce_dtype:
                red = lax.psum(x.astype(reduce_dtype), axis_name).astype(x.dtype)
            else:
                red = lax.psum(x, axis_name)
            out[i] = red / lax.psum(1, axis_name) if average else red
            continue
        flat = jnp.concatenate([leaves[i].ravel() for i in bucket])
        if reduce_dtype is not None and flat.dtype != reduce_dtype:
            red = lax.psum(flat.astype(reduce_dtype), axis_name).astype(flat.dtype)
        else:
            red = lax.psum(flat, axis_name)
        if average:
            red = red / lax.psum(1, axis_name)
        offset = 0
        for i in bucket:
            n = int(np.prod(leaves[i].shape)) if leaves[i].ndim else 1
            out[i] = red[offset:offset + n].reshape(leaves[i].shape)
            offset += n
    return out


def fused_allreduce_tree(tree: Any, *, axis_name: str, average: bool = True,
                         threshold: Optional[int] = None,
                         reduce_dtype: Optional[Any] = None) -> Any:
    """Pytree version of `fused_allreduce_leaves` (gradients are pytrees)."""
    leaves, treedef = jax.tree.flatten(tree)
    reduced = fused_allreduce_leaves(
        leaves, axis_name=axis_name, average=average,
        threshold=threshold, reduce_dtype=reduce_dtype)
    return jax.tree.unflatten(treedef, reduced)
