"""What keeps the program honest about the machine it runs on: the
compile cache's placement, the native build's source-hash key, the
single compile of a train step, and the entry points that must REFUSE
to run without the chip (docs: README "Tests & the benchmark")."""

import math
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile cache ------------------------------------------------------

@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_set_is_left_alone(monkeypatch,
                                             cache_dir_config):
    """Where JAX_COMPILATION_CACHE_DIR is set, code sets no other."""
    from horovod_tpu.runtime import compile_cache
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/placed/from/outside")
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.configure_compile_cache() == (
        "/placed/from/outside")
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_unset_is_the_fixed_path(monkeypatch,
                                               cache_dir_config):
    """Unset: <checkout>/.jax_cache, next to the package — the same
    path every time (the directory is part of every cache key)."""
    from horovod_tpu.runtime import compile_cache
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.configure_compile_cache() == want


def test_compile_cache_is_placed_by_init_and_engine(monkeypatch,
                                                    cache_dir_config,
                                                    hvd):
    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.parallel.tensor import unbox
    from horovod_tpu.runtime import compile_cache
    from horovod_tpu.serving import ServingEngine
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    model = TransformerLM(vocab_size=32, num_layers=1, num_heads=2,
                          head_dim=8, max_len=16, dtype=jnp.float32)
    params = unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    ServingEngine(model, params, num_slots=1).shutdown()
    assert jax.config.jax_compilation_cache_dir == (
        compile_cache.DEFAULT_CACHE_DIR)


# -- native build keyed on the source ------------------------------------

SRC = 'extern "C" int answer() { return %d; }\n'


def test_native_rebuild_follows_source_hash_not_mtime(tmp_path):
    """A copied tree has fresh mtimes on everything, so a stale binary
    must lose to a changed .cc by CONTENT."""
    import ctypes

    from horovod_tpu.native import build
    src, out = tmp_path / "lib.cc", tmp_path / "libanswer.so"
    src.write_text(SRC % 1)
    build.build_library(str(src), str(out))
    assert build.BUILD_ACTIONS["libanswer.so"] == "built"
    build.build_library(str(src), str(out))
    assert build.BUILD_ACTIONS["libanswer.so"] == "reused"

    # The source changes while the binary stays NEWER than it: the
    # mtime rule would have kept the stale binary.
    src.write_text(SRC % 2)
    os.utime(src, (1, 1))
    build.build_library(str(src), str(out))
    assert build.BUILD_ACTIONS["libanswer.so"] == "built"
    # Load through a fresh name: dlopen caches by path.
    fresh = tmp_path / "check.so"
    fresh.write_bytes(out.read_bytes())
    assert ctypes.CDLL(str(fresh)).answer() == 2

    # A binary with no recorded key is not trusted either.
    os.unlink(str(out) + ".srchash")
    build.build_library(str(src), str(out))
    assert build.BUILD_ACTIONS["libanswer.so"] == "built"


# -- one compile per train step ------------------------------------------

_COMPILES = []    # backend-compile events since the last reset
_LISTENING = []   # truthy once the process-wide listener is in


def _xla_compiles():
    """The (emptied) list XLA backend compiles are appended to."""
    import jax.monitoring
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _s, **_kw: _COMPILES.append(event) if event
            == "/jax/core/compile/backend_compile_duration" else None)
        _LISTENING.append(True)
    _COMPILES.clear()
    return _COMPILES


@pytest.mark.parametrize("route", ["make_train_step",
                                   "make_cnn_train_step"])
def test_train_step_compiles_once(hvd, route):
    """State fresh from init is committed to no mesh; the step's own
    output is. JAX types arrays by that, so the step used to trace and
    compile a SECOND time on its second call."""
    import optax

    from horovod_tpu import models
    from horovod_tpu.models import make_cnn_train_step
    from horovod_tpu.models.train import init_cnn_state

    n = hvd.size()
    tx = hvd.DistributedOptimizer(optax.adamw(1e-2))
    if route == "make_train_step":
        params = {"w": jnp.ones((4, 3)), "b": jnp.zeros((3,))}
        state = (params, tx.init(params))
        step = hvd.make_train_step(
            lambda p, x: ((x @ p["w"] + p["b"]) ** 2).mean(), tx)
        batch = jnp.ones((2 * n, 4))

        def call(state):
            params, opt_state, loss = step(*state, batch)
            return (params, opt_state), loss
    else:
        model = models.MnistConvNet(dtype=jnp.float32)
        state = init_cnn_state(model, tx, jax.random.PRNGKey(0),
                               jnp.zeros((1, 28, 28, 1), jnp.float32))
        step = make_cnn_train_step(model, tx)
        batch = (jnp.zeros((n, 28, 28, 1)), jnp.zeros((n,), jnp.int32))
        rng = jax.random.PRNGKey(1)

        def call(state):
            return step(state, batch, rng)

    state, loss = call(state)
    float(loss)
    seen = _xla_compiles()
    for _ in range(2):
        state, loss = call(state)
        assert math.isfinite(float(loss))
    assert not seen, f"{len(seen)} XLA compile(s) after the first call"


# -- entry points that refuse to run without the chip --------------------

def _run(cmd, **env):
    full = dict(os.environ, **env)
    full.pop("HOROVOD_PLATFORM", None)
    return subprocess.run([sys.executable] + cmd, cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_the_cpu():
    """On a machine without a TPU: non-zero exit and no result line —
    never `ok: true`."""
    r = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout, r.stdout
    assert "no accelerator" in r.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is no program to smoke: non-zero exit, no result."""
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=dict(env, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout, r.stdout


def test_launcher_refuses_several_tpu_workers_on_one_host():
    """A host's chips belong to one process: `-np 2 --platform tpu`
    would start two workers that both take every chip."""
    r = _run(["-m", "horovod_tpu.runner", "-np", "2", "--platform",
              "tpu", sys.executable, "-c", "pass"])
    assert r.returncode != 0
    assert "ONE process" in r.stderr
