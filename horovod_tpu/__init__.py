"""horovod_tpu — a TPU-native distributed training framework.

Capability-parity rebuild of Horovod v0.10 (reference: chenkaiidy/horovod),
re-designed for TPU hardware: the MPI/NCCL data plane becomes XLA collectives
(`psum` / `all_gather` / `ppermute`) over a `jax.sharding.Mesh`; the rank-0
coordinator negotiation (reference `horovod/tensorflow/mpi_ops.cc:1195-1509`)
is replaced by SPMD compile-time collective ordering, with a compact native
C++ control plane for bootstrap, cross-rank metadata validation, timeline
tracing and stall detection.

Top-level API (parity with `horovod/tensorflow/__init__.py` and
`horovod/tensorflow/mpi_ops.py` in the reference):

    import horovod_tpu as hvd
    hvd.init()
    hvd.rank(), hvd.size(), hvd.local_rank()
    hvd.allreduce(x), hvd.allgather(x), hvd.broadcast(x, root_rank)
    hvd.DistributedOptimizer(optax_tx)
    hvd.broadcast_global_variables(params, root_rank)
"""

from horovod_tpu.runtime.bootstrap import (
    init,
    shutdown,
    is_initialized,
    rank,
    size,
    local_rank,
    local_size,
    process_rank,
    num_processes,
    mesh,
)
from horovod_tpu.ops.eager import (
    allreduce,
    allgather,
    broadcast,
    alltoall,
    reducescatter,
    per_rank,
    PerRank,
)
from horovod_tpu.ops import collectives as spmd
from horovod_tpu.jax import (
    DistributedOptimizer,
    DistributedGradientTape,
    allreduce_gradients,
    broadcast_global_variables,
    broadcast_parameters,
    broadcast_optimizer_state,
    broadcast_object,
    allgather_object,
    grouped_allreduce,
    make_train_step,
    make_global_batch,
)
from horovod_tpu.ops.sparse import IndexedSlices
from horovod_tpu.runtime.config import config
from horovod_tpu.utils.timeline import start_timeline, stop_timeline
from horovod_tpu import resilience  # chaos / retry / elastic (docs/resilience.md)

__version__ = "0.10.0"  # mirrors the reference's version (setup.py:348)

__all__ = [
    "init", "shutdown", "is_initialized",
    "rank", "size", "local_rank", "local_size",
    "process_rank", "num_processes", "mesh",
    "allreduce", "allgather", "broadcast", "alltoall", "reducescatter",
    "per_rank", "PerRank", "spmd",
    "DistributedOptimizer", "DistributedGradientTape", "allreduce_gradients",
    "broadcast_global_variables", "broadcast_parameters",
    "broadcast_optimizer_state", "broadcast_object",
    "allgather_object", "grouped_allreduce",
    "make_train_step", "make_global_batch", "IndexedSlices", "config",
    "start_timeline", "stop_timeline", "resilience",
]
