"""Test harness configuration.

The reference tests run as `mpirun -np 2 python mpi_ops_test.py` — N real
MPI processes on one host (SURVEY §4). The TPU-native analogue
(SURVEY §4, "Implication for the TPU build"): a virtual 8-device CPU mesh
via `--xla_force_host_platform_device_count`, with per-rank inputs
expressed as `hvd.per_rank(...)`. Multi-process (hvdrun) tests live in
`tests/test_runner.py` and spawn real subprocesses.
"""

import os

# Must run before the JAX backend initializes: the tests run on the
# virtual 8-device CPU mesh whatever the ambient platform is, so force
# it through the env var and jax.config both.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# The reference sweeps float64 (mpi_ops_test.py:90); enable x64 support.
os.environ.setdefault("JAX_ENABLE_X64", "1")
# hvd.init() / ServingEngine place the persistent compile cache
# (runtime/compile_cache.py); the suite keeps it OFF — here and in
# every subprocess a test starts — so no test's outcome depends on
# what an earlier run left on disk.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

assert jax.device_count() == 8, (
    f"test harness expected the virtual 8-device CPU mesh, got "
    f"{jax.devices()}")


@pytest.fixture(scope="session")
def hvd():
    import horovod_tpu as hvd
    hvd.init()
    return hvd
