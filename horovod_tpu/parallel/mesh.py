"""Multi-axis device mesh construction and sharding helpers.

Generalizes the framework's 1-D ``data`` mesh (`runtime/bootstrap.py`) to
the full 5-axis TPU layout. Axis order follows the ICI-locality rule from
the scaling playbook: the innermost (fastest-varying, most ICI-local) axes
carry the chattiest collectives — tensor parallel all-reduces every layer,
expert all-to-alls — while data parallel (one gradient all-reduce per
step) rides the outermost axis and, multi-slice, DCN.

There is no reference equivalent: Horovod v0.10 has exactly one implicit
axis, `MPI_COMM_WORLD` (SURVEY §2.3). This module is the TPU-native
extension that makes the other four axes first-class.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.obs import spans as _spans

AXIS_DATA = "data"
AXIS_SEQ = "seq"
AXIS_MODEL = "model"
AXIS_PIPE = "pipe"
AXIS_EXPERT = "expert"

# In a sharding constraint, None means "this dim is NOT sharded"
# (replicated) while UNCONSTRAINED leaves the dim for the partitioner to
# decide from context. Layers that only care about one dim (e.g. the
# feature dim of a column-parallel matmul) must use UNCONSTRAINED for the
# rest, or they force batch/seq replication — a hidden all-gather.
UNCONSTRAINED = P.UNCONSTRAINED

# Outer → inner device-grid order (inner = most ICI-local; see module doc).
_CANONICAL_ORDER = (AXIS_PIPE, AXIS_DATA, AXIS_SEQ, AXIS_EXPERT, AXIS_MODEL)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Requested degree of each parallelism axis.

    ``data=-1`` (default) absorbs all devices not claimed by other axes.
    Axes of degree 1 are still present in the mesh (size-1 axes are free),
    so model code can always reference every canonical axis name.
    """

    data: int = -1
    seq: int = 1
    model: int = 1
    pipe: int = 1
    expert: int = 1

    def resolve(self, n_devices: int) -> "MeshSpec":
        fixed = {f.name: getattr(self, f.name)
                 for f in dataclasses.fields(self)}
        free = [k for k, v in fixed.items() if v == -1]
        if len(free) > 1:
            raise ValueError(f"at most one axis may be -1, got {free}")
        claimed = math.prod(v for v in fixed.values() if v != -1)
        if free:
            if n_devices % claimed:
                raise ValueError(
                    f"{n_devices} devices not divisible by the "
                    f"{claimed} claimed by {fixed}")
            fixed[free[0]] = n_devices // claimed
        elif claimed != n_devices:
            raise ValueError(
                f"mesh axes {fixed} need {claimed} devices, have "
                f"{n_devices}")
        return MeshSpec(**fixed)


def make_mesh(spec: Optional[MeshSpec] = None,
              devices: Optional[Sequence] = None,
              **axis_sizes: int) -> Mesh:
    """Build a 5-axis `jax.sharding.Mesh`.

    Either pass a `MeshSpec` or axis sizes as keywords::

        mesh = make_mesh(data=2, model=2, seq=2)   # 8 devices

    The device grid is laid out in canonical outer→inner order
    (pipe, data, seq, expert, model) so the chatty axes map to adjacent
    devices (contiguous ICI neighborhoods on a real slice).
    """
    if spec is None:
        spec = MeshSpec(**axis_sizes)
    elif axis_sizes:
        raise ValueError("pass either spec or keyword axis sizes, not both")
    devs = list(devices) if devices is not None else list(jax.devices())
    spec = spec.resolve(len(devs))
    shape = tuple(getattr(spec, name) for name in _CANONICAL_ORDER)
    grid = np.asarray(devs).reshape(shape)
    return Mesh(grid, _CANONICAL_ORDER)


def mesh_axis_names(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def axis_size(axis_name: str) -> int:
    """Static size of a bound mesh axis."""
    return jax.lax.axis_size(axis_name)


def ring_perms(axis_name: str):
    """(forward, backward) `ppermute` permutations for the axis ring —
    the neighbor-exchange pattern every ring schedule here uses (ring
    attention K/V rotation, pipeline stage hand-off, collective
    matmuls). Single site so a topology-aware neighbor order only ever
    needs to land once."""
    n = axis_size(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    return fwd, bwd


def use(mesh: Mesh):
    """Context manager installing `mesh` as the ambient mesh for
    P(...)-spec sharding constraints."""
    return jax.set_mesh(mesh)


def abstract_mesh():
    """The ambient mesh installed by `use()` (empty when off-mesh)."""
    return jax.sharding.get_abstract_mesh()


def auto_axis_names(mesh) -> set:
    """The mesh axes GSPMD may still shard over (type Auto) — the only
    ones a sharding constraint is allowed to mention; axes Manual
    inside an enclosing shard_map region are out of its hands."""
    return {n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t == jax.sharding.AxisType.Auto}


def sharding(mesh: Mesh, *spec) -> NamedSharding:
    """`NamedSharding(mesh, P(*spec))` shorthand."""
    return NamedSharding(mesh, P(*spec))


def safe_spec(mesh: Mesh, spec, shape) -> P:
    """Degrade a P(...) spec to what ``mesh`` can actually shard on a
    CONCRETE array: axes absent from the mesh are dropped, and so are
    axes whose size doesn't divide the dimension — the placement-time
    twin of `constrain`'s rule, used where arrays are committed with
    `device_put` rather than constrained inside a program. This is
    what makes KV-cache sharding GQA-aware: a heads dimension the
    model axis doesn't divide stays replicated instead of erroring."""
    sizes = dict(mesh.shape)
    # A PartitionSpec is a sequence of entries but not a tuple
    # subclass; a bare axis name (or None) is a one-entry spec.
    spec = (tuple(spec) if isinstance(spec, (P, tuple, list))
            else (spec,))
    assert len(spec) <= len(shape), (
        f"spec {spec} has more entries than array rank {len(shape)} "
        f"(shape {shape})")

    def keep(entry, dim):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept, degree = [], 1
            for e in entry:
                if e in sizes and dim % (degree * sizes[e]) == 0:
                    kept.append(e)
                    degree *= sizes[e]
            return tuple(kept) if kept else None
        if entry in sizes and dim % sizes[entry] == 0:
            return entry
        return None

    return P(*(keep(s, d) for s, d in zip(spec, shape)))


def place_with_specs(mesh: Mesh, tree, specs):
    """Commit a plain-array pytree onto ``mesh`` per a matching
    P(...)-spec pytree (e.g. from `parallel.tensor.param_specs`),
    degrading each spec through `safe_spec` first. The sharded-serving
    analogue of `shard_params` for trees whose `nn.Partitioned` boxes
    were already stripped (pools and engines hold unboxed params)."""
    return jax.tree.map(
        lambda x, s: _place(x, NamedSharding(
            mesh, safe_spec(mesh, s, x.shape))),
        tree, specs)


def _place(x, sh: NamedSharding):
    """device_put that also works inside a `use()` mesh context, where
    jax requires the source to be host-resident or already mesh-committed
    (single-device jax Arrays are rejected) — round-trip through numpy."""
    if isinstance(x, jax.Array) and not isinstance(
            x.sharding, NamedSharding):
        # hvd: disable=HVD001(one-shot committed placement at pool/engine CONSTRUCTION (and clone_fresh restart) — never per tick; the coarse call graph reaches it through the pool __init__ chain)
        x = np.asarray(x)
    return jax.device_put(x, sh)


def put_like(x, ref):
    """Commit ``x`` onto ``ref``'s sharding (cross-pool KV-block
    transfer ingest: a block row exported from one engine's pool —
    possibly a different mesh, possibly host-resident — re-enters
    under the DESTINATION pool's committed layout). NamedSharding
    applies shape-agnostically as long as the sharded dims divide, so
    the same helper covers host-bounce and device-to-device rows."""
    sh = getattr(ref, "sharding", None)
    if not isinstance(sh, NamedSharding):
        return jax.device_put(x)
    return _place(x, sh)


def shard_batch(mesh: Mesh, batch,
                axes: Sequence[str] = (AXIS_DATA,)):
    """Place a host batch onto the mesh, dim 0 split over `axes`.

    The TPU analogue of the reference's per-worker dataset sharding
    (`examples/keras_mnist_advanced.py:113-119` divides steps per epoch by
    `hvd.size()`): here one global batch is laid out across the data axis.
    """
    # Single-axis: pass the bare name, the form spec introspection
    # everywhere else compares against.
    sh = sharding(mesh, axes[0] if len(axes) == 1 else tuple(axes))
    with _spans.loop_span("train.shard_batch"):
        return jax.tree.map(lambda x: _place(x, sh), batch)


def replicate(mesh: Mesh, tree):
    """Fully replicate a pytree over the mesh (e.g. initial params before
    tensor-parallel sharding, mirroring `broadcast_global_variables`)."""
    sh = sharding(mesh)
    return jax.tree.map(lambda x: _place(x, sh), tree)


def constrain(x, *spec):
    """`with_sharding_constraint` with a plain P(...) spec — the GSPMD
    escape hatch for pinning an intermediate's layout inside pjit.

    No-op when no mesh is in context (e.g. single-device init or the
    unsharded reference path in tests), so annotated modules run
    unchanged off-mesh. Axes absent from the context mesh are dropped
    from the spec (a mesh built without ``model`` simply doesn't shard
    that dim), and so are axes whose sizes don't divide the dimension
    (GSPMD cannot shard it — e.g. a batch-1 decode on a data-parallel
    mesh keeps its activations replicated instead of erroring).
    """
    mesh = abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    # Only Auto axes may appear in a sharding constraint; axes already
    # Manual (inside an enclosing shard_map, e.g. the pipeline loop) are
    # out of GSPMD's hands and must be dropped from the spec.
    names = auto_axis_names(mesh)
    if not names:
        return x
    sizes = dict(mesh.shape)
    assert len(spec) <= x.ndim, (
        f"constrain spec {spec} has more entries than array rank "
        f"{x.ndim} (shape {x.shape})")

    def keep(entry, dim):
        if entry is None or entry is P.UNCONSTRAINED:
            return entry
        if isinstance(entry, (tuple, list)):
            kept, degree = [], 1
            for e in entry:
                if e in names and dim % (degree * sizes[e]) == 0:
                    kept.append(e)
                    degree *= sizes[e]
            return tuple(kept) if kept else None
        if entry in names and dim % sizes[entry] == 0:
            return entry
        return None

    return jax.lax.with_sharding_constraint(
        x, P(*(keep(s, d) for s, d in zip(spec, x.shape))))
