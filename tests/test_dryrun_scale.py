"""Virtual-mesh scale: the full multi-axis dryrun beyond 8 devices.

The 8-device meshes the suite (and the driver)
exercise can hide factorization/divisibility bugs in `_split`, the
interleaved pipeline placement, and eager negotiation that only appear
at larger N. These tests run the SAME `dryrun_multichip` the driver
uses — every parallelism composition (dp CNN, dp/sp/tp ring LM,
dp/ep/tp MoE+FSDP+GQA LM, GPipe + interleaved pp), one real train step
each — at 16 and 32 virtual CPU devices in a subprocess (the dryrun
commandeers the process's backend, so it cannot share this one).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_entry(expr, ok_marker, timeout=540):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the dryrun sets its own device count
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-c", f"import __graft_entry__ as g; {expr}"],
        capture_output=True, text=True, cwd=REPO, env=env,
        timeout=timeout)
    assert res.returncode == 0, res.stdout + res.stderr
    assert ok_marker in res.stderr + res.stdout, (
        res.stdout + res.stderr)


# 64 reaches axis degrees (e.g. model=4) the 8/16/32 meshes can't —
# it found the kv_heads-vs-tp-degree divisibility bug on first run
# (be an order of magnitude past the reference's
# 2-rank CI scale).
@pytest.mark.parametrize("n", [16, 32, 64])
def test_dryrun_multichip_at_scale(n):
    _run_entry(f"g.dryrun_multichip({n})",
               f"dryrun_multichip({n}): OK")


def test_dryrun_long_context_ring_flash():
    """The flagship ring_flash config at S=256: per-shard sequences
    span multiple Pallas kernel blocks, exercising banded-grid edge
    cases (band across block boundaries, empty-band rotations) that
    the tiny dryrun shapes cannot reach."""
    _run_entry("g.dryrun_long_context(16, 256)",
               "dryrun_long_context(16, 256): OK")
