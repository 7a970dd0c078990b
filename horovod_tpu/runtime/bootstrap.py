"""init()/shutdown() and membership queries.

TPU-native equivalent of the reference's `hvd.init()` call stack
(SURVEY §3.1, `horovod/tensorflow/mpi_ops.cc:1513-1563`): where the
reference spawns a background MPI thread and calls `MPI_Init`, the TPU
build attaches to the JAX runtime — `jax.distributed.initialize` when
launched multi-process (by `hvdrun` or a TPU pod runtime) — and builds a
1-D ``data`` mesh over every participating device. There is no background
thread because under SPMD the collective schedule is decided at compile
time, not negotiated at runtime (SURVEY §7).

Launcher contract (set by ``hvdrun``, horovod_tpu/runner):
  HOROVOD_RANK / HOROVOD_SIZE          process rank / world process count
  HOROVOD_LOCAL_RANK / HOROVOD_LOCAL_SIZE   within-host process placement
  HOROVOD_COORDINATOR                  host:port of the rank-0 coordinator
Standard OMPI/PMI vars are honored as fallbacks so `mpirun`-style launches
also work (parity with `mpi_ops_test.py:31-63`).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

from horovod_tpu.runtime import config as _config
from horovod_tpu.runtime import state as _state
from horovod_tpu.runtime.config import config


def _detect_process_env():
    """Read launcher-provided rank/size env vars.

    Returns (process_rank, num_processes, local_rank, local_size,
    coordinator) or None when not launched multi-process.
    """
    env = os.environ
    # The HOROVOD_* pair reads through the registry accessors like
    # every other knob; the OMPI/PMI names are foreign launcher
    # fallbacks outside the registry's HVD_*/HOROVOD_* namespace and
    # stay raw.
    prank_s = _config.env_raw("HOROVOD_RANK")
    psize_s = _config.env_raw("HOROVOD_SIZE")
    if prank_s is None or psize_s is None:
        for rank_var, size_var in (
            ("OMPI_COMM_WORLD_RANK", "OMPI_COMM_WORLD_SIZE"),
            ("PMI_RANK", "PMI_SIZE"),
        ):
            if rank_var in env and size_var in env:
                prank_s, psize_s = env[rank_var], env[size_var]
                break
        else:
            return None
    prank = int(prank_s)
    psize = int(psize_s)
    lrank = int(_config.env_str(
        "HOROVOD_LOCAL_RANK",
        env.get("OMPI_COMM_WORLD_LOCAL_RANK", str(prank))))
    lsize = int(_config.env_str(
        "HOROVOD_LOCAL_SIZE",
        env.get("OMPI_COMM_WORLD_LOCAL_SIZE", str(psize))))
    coord = _config.env_str("HOROVOD_COORDINATOR")
    return prank, psize, lrank, lsize, coord


def init(devices: Optional[Sequence] = None,
         axis_name: Optional[str] = None) -> int:
    """Initialize horovod_tpu.

    Idempotent, like the reference's atomic-flag-guarded
    `InitializeHorovodOnce` (`mpi_ops.cc:1513-1524`).

    Args:
      devices: optional explicit device list for the mesh (defaults to
        `jax.devices()`).
      axis_name: name of the data-parallel mesh axis (default "data",
        overridable via HOROVOD_MESH_AXIS).

    Returns:
      0 on success (parity with the C `horovod_tensorflow_init`).
    """
    st = _state.global_state()
    with st.lock:
        if st.initialized:
            return 0
        config.refresh()

        import jax

        # hvdrun may force the platform (e.g. several cpu workers on a
        # TPU host, whose chips belong to one process); must happen
        # before the backend initializes.
        forced_platform = _config.env_str("HOROVOD_PLATFORM")
        if forced_platform and forced_platform != "auto":
            jax.config.update("jax_platforms", forced_platform)

        proc_env = _detect_process_env()
        if proc_env is not None:
            already = jax.distributed.is_initialized()
            prank, psize, lrank, lsize, coord = proc_env
            if psize > 1 and coord and not already:
                jax.distributed.initialize(
                    coordinator_address=coord,
                    num_processes=psize,
                    process_id=prank,
                )

        devs = list(devices) if devices is not None else list(jax.devices())
        axis = axis_name or config.mesh_axis_name

        from jax.sharding import Mesh
        import numpy as np
        st.mesh = Mesh(np.asarray(devs), (axis,))
        st.axis_name = axis
        st.devices = devs
        st.size = len(devs)

        if proc_env is not None:
            prank, psize, lrank, lsize, _ = proc_env
            st.process_rank = prank
            st.num_processes = psize
            st.local_rank = lrank
            st.local_size = lsize
        else:
            st.process_rank = jax.process_index()
            st.num_processes = jax.process_count()
            st.local_rank = 0
            st.local_size = 1

        # rank == global index of this process's first addressable device:
        # equals the process rank in the launcher's one-device-per-process
        # mode, matching the reference's MPI rank semantics.
        local_set = set(jax.local_devices())
        local_devs = [d for d in devs if d in local_set]
        if local_devs:
            st.rank = devs.index(local_devs[0])
        else:
            st.rank = st.process_rank

        # Native control plane (timeline, stall detection, validation).
        if config.use_native:
            try:
                from horovod_tpu.native import load_native
                st.native = load_native()
                st.native.init(st.rank, st.size, st.local_rank,
                               st.local_size)
            # hvd: disable=HVD006(native build/load can fail a dozen ways — g++ missing, bad toolchain, sandbox; all degrade to pure Python)
            except Exception:
                st.native = None  # graceful pure-Python degradation

        # Multi-controller: connect to the launcher's rendezvous server
        # (the control-message channel replacing MPI TAG_NOTIFY,
        # mpi_ops.cc:225) and synchronize startup.
        kv_addr = _config.env_str("HOROVOD_KV")
        if kv_addr and st.num_processes > 1:
            if st.native is None:
                raise RuntimeError(
                    "multi-process launch requires the native control "
                    "plane (set HOROVOD_NO_NATIVE='' and ensure g++)")
            host, port = kv_addr.rsplit(":", 1)
            if not st.native.connect(host, int(port), timeout_s=60.0):
                raise RuntimeError(
                    f"could not reach rendezvous server at {kv_addr}")
            if not st.native.barrier("hvd_init", 120000):
                raise RuntimeError("init barrier timed out")

        if config.timeline_path:
            from horovod_tpu.utils.timeline import Timeline
            st.timeline = Timeline(config.timeline_path, native=st.native)

        from horovod_tpu.utils.stall import StallMonitor
        st.stall_monitor = StallMonitor(config.stall_warning_time,
                                        native=st.native)

        # Observability exporter (docs/observability.md): env-gated —
        # with HVD_METRICS_PORT unset this is a no-op, so the knob
        # alone turns the HTTP endpoint on for any init()'d process.
        from horovod_tpu.obs.exporter import start_exporter
        start_exporter()
        from horovod_tpu.runtime.compile_cache import (
            configure_compile_cache)
        configure_compile_cache()

        st.initialized = True
        # Clean teardown even when user scripts never call shutdown()
        # (the reference finalizes from its global destructor,
        # mpi_ops.cc:207-215).
        import atexit
        atexit.register(shutdown)
        return 0


def shutdown() -> None:
    """Graceful shutdown (parity with `mpi_ops.cc:207-215`, SURVEY §5.3)."""
    import sys
    ckpt_mod = sys.modules.get("horovod_tpu.utils.checkpoint")
    if ckpt_mod is not None:
        # Fence any in-flight async checkpoint while the interpreter is
        # still fully alive (atexit is too late for Orbax finalization);
        # swallowing variant — teardown must proceed past a failed save.
        ckpt_mod._fence_swallowing()
    st = _state.global_state()
    with st.lock:
        if not st.initialized:
            return
        if st.timeline is not None:
            st.timeline.close()
        if st.stall_monitor is not None:
            st.stall_monitor.stop()
        if st.native is not None:
            st.native.shutdown()
            st.native = None
        st.reset()
        st.shut_down = True  # observable until the next init()


def is_initialized() -> bool:
    return _state.global_state().initialized


def rank() -> int:
    return _state.check_initialized().rank


def size() -> int:
    return _state.check_initialized().size


def local_rank() -> int:
    return _state.check_initialized().local_rank


def local_size() -> int:
    return _state.check_initialized().local_size


def process_rank() -> int:
    return _state.check_initialized().process_rank


def num_processes() -> int:
    return _state.check_initialized().num_processes


def mesh():
    """The framework-owned `jax.sharding.Mesh` (1-D `data` axis)."""
    return _state.check_initialized().mesh


def connect_kv(addr: Optional[str] = None, *, timeout_s: float = 60.0):
    """Attach this process to the launcher's rendezvous KV plane
    WITHOUT full `init()` — no jax backend, no device mesh, no init
    barrier. Returns the connected native control-plane client.

    This is the multi-controller elastic drill's bootstrap
    (`resilience/drill.py`): worker processes coordinate membership,
    heartbeats and lockstep training entirely through the KV
    (``membership.install_kv(BootstrapKV(connect_kv()))``), so the
    drill runs on any box — including one whose jaxlib lacks
    cross-process CPU collectives. ``addr`` defaults to the
    launcher-set ``HOROVOD_KV``."""
    if addr is None:
        addr = _config.env_str("HOROVOD_KV")
    if not addr or ":" not in addr:
        raise RuntimeError(
            "connect_kv needs a rendezvous address (host:port); "
            "launch under hvdrun or pass addr= explicitly")
    from horovod_tpu.native import load_native
    native = load_native()
    host, port = addr.rsplit(":", 1)
    if not native.connect(host, int(port), timeout_s=timeout_s):
        raise RuntimeError(
            f"could not reach rendezvous server at {addr}")
    return native


def world_generation() -> int:
    """Monotonic elastic-world generation: 0 at launch, +1 per
    committed resize (resilience/membership.py). Readable before
    init() — an uninitialized runtime is generation 0."""
    return _state.global_state().world_generation


def apply_resize(new_rank: int, new_world: int, generation: int, *,
                 rekey_runtime: bool = True) -> None:
    """Re-key the runtime's membership after a committed elastic
    resize (docs/resilience.md "Elastic membership").

    Updates rank/size and the monotonic world generation in place —
    the process survives the resize, so the runtime is re-keyed, not
    re-initialized. Safe on an uninitialized runtime: only the
    bookkeeping fields and the `hvd_elastic_generation` gauge move.
    A real multi-controller deployment additionally rebuilds its mesh
    from the surviving devices before the next compiled step — that
    device-plane re-key is the caller's hook (the mesh cannot be
    rebuilt here for ranks whose devices are gone).

    ``rekey_runtime=False`` records the generation WITHOUT touching
    the membership fields — the in-process simulated worlds
    (`resilience.membership.SimulatedWorld`), where many fake ranks
    share one process, must never rewrite the real runtime's
    rank/size out from under coexisting code."""
    st = _state.global_state()
    with st.lock:
        if generation < st.world_generation:
            raise ValueError(
                f"resize generation {generation} is not monotonic "
                f"(current {st.world_generation})")
        st.world_generation = int(generation)
        if rekey_runtime and st.initialized:
            st.rank = int(new_rank)
            st.size = int(new_world)
            # Compiled collectives are keyed on the old mesh; drop the
            # eager-op cache so nothing re-dispatches against a world
            # that no longer exists.
            st.op_cache = {}
            st.mc_mesh2 = None
    from horovod_tpu.obs import catalog as _obs_catalog
    _obs_catalog.elastic_metrics()["generation"].set(
        float(generation))
