"""A throw-away copy of the benchmark with the `tiny` cells added AS
FILES - the way a later PR brings its own cell - and a child process
that runs one cell of it on the CPU.

The tiny configurations live here, with the tests; they are no
configurations of `BENCHMARK.json`.
"""

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

TINY_CELLS = {
    "tiny-gpt2.train": ("tiny-gpt2", "tiny-train", "gpt2-medium.train-1chip"),
    "tiny-qwen2.serve": ("tiny-qwen2", "tiny-serve",
                         "qwen2.5-1.5b.serve-closed32"),
}


def add_cell(root, name, config, traffic, like, chips=1):
    """One more `workloads` entry (and its configuration's entry) in the
    copy's BENCHMARK.json, reporting whatever the cell `like` reports.
    Only BENCHMARK.json gains entries; every file it names is new."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    if config not in [c["name"] for c in b["configs"]]:
        b["configs"].append({
            "name": config, "source": "tests/benchmark/tiny",
            "file": f"benchmarks/configs/{config}.json", "reduced": [],
            "why": "rehearsal"})
    b["workloads"].append({"name": name, "config": config,
                           "traffic": traffic, "chips": chips,
                           "why": "rehearsal"})
    for m in b["end_to_end"] + b["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(name)
    with open(path, "w") as f:
        json.dump(b, f)


def bare_copy(dst):
    """BENCHMARK.json and benchmarks/ copied to `dst`, as they stand."""
    dst = str(dst)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def make_copy(dst):
    """BENCHMARK.json and benchmarks/ copied to `dst`, tiny cells added."""
    dst = bare_copy(dst)
    bench = os.path.join(dst, "benchmarks")
    for name, (config, traffic, like) in TINY_CELLS.items():
        src_traffic = os.path.join(HERE, "tiny", traffic + ".json")
        if not os.path.exists(src_traffic):
            continue
        shutil.copy(os.path.join(HERE, "tiny", config + ".json"),
                    os.path.join(bench, "configs"))
        shutil.copy(src_traffic, os.path.join(bench, "traffic"))
        shutil.copy(os.path.join(bench, "limits", like + ".json"),
                    os.path.join(bench, "limits", name + ".json"))
        add_cell(dst, name, config, traffic, like)
    return dst


BOOT = """
import sys, time
t0 = time.time()
sys.path.insert(0, {root!r})
import jax
jax.config.update("jax_platforms", "cpu")
{patch}
from benchmarks import run
run.main({argv!r}, accept_platform=("cpu",), peaks_kind="TPU v5 lite",
         t_start=t0)
"""


def run_cell(root, workload, *, seed=7, seconds=1.0, trace=0, chips=1,
             patch="", override=True, timeout=600):
    """One run of one cell of the copy at `root` in a child process on
    `chips` virtual CPU devices. Returns (exit code, stdout lines,
    stderr). `patch` is Python the child runs before the harness (the
    broken-path tests break the timed path there). Without `override`
    the child runs the command as the driver does."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_X64="0",
               JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""))
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    if override:
        cmd = [sys.executable, "-c",
               BOOT.format(root=root, patch=patch, argv=argv)]
    else:
        cmd = [sys.executable, os.path.join(root, "benchmarks", "run.py"),
               *argv]
    p = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                       text=True, timeout=timeout)
    return p.returncode, p.stdout.strip().splitlines(), p.stderr
