"""Rehearsals of the benchmark on the CPU at a tiny size: each traffic
kind end to end through `benchmarks/run.py`'s own code path (Pallas in
interpret mode), in a throw-away copy to which the tiny cells are added
as files. Nothing here is a measurement: a CPU's numbers are never
written under a device metric's name.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return tiny.make_copy(tmp_path_factory.mktemp("bench"))


def result_line(out):
    assert out, "the run printed nothing"
    return json.loads(out[-1])


def e2e_names(root, cell):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    return {m["name"] for m in b["end_to_end"]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell,chips", [
    ("tiny-gpt2.train", 1), ("tiny-qwen2.serve", 1)])
def test_kind_end_to_end(copy, cell, chips):
    rc, out, err = tiny.run_cell(copy, cell, seconds=1.5, chips=chips)
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True, "\n".join(out[-20:])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == e2e_names(copy, cell)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == chips
    # every number compared is printed beside its limit
    assert sum("correct: " in x and "(limit " in x for x in out) >= 4


@pytest.mark.parametrize("cell", ["tiny-gpt2.train", "tiny-qwen2.serve"])
def test_traced_run(copy, cell):
    rc, out, err = tiny.run_cell(copy, cell, seconds=1.0, trace=1)
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert set(line) == RESULT_KEYS | {"breakdown"}
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # a CPU trace has no device plane: the readers that need one find
    # nothing and are left out; those that read counters report
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        per_layer = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(line["metrics"]) <= per_layer
    assert line["metrics"], "\n".join(out[-20:])
    assert not any(n.startswith("device_idle_share")
                   for n in line["metrics"])


def test_four_chips_data_parallel(copy):
    """The path across chips on four virtual devices: the same job at
    hvd.size() 4, against the one-device reference."""
    tiny.add_cell(copy, "tiny-gpt2.train-dp4", "tiny-gpt2", "tiny-train",
                  "gpt2-medium.train-dp4", chips=4)
    lim = os.path.join(copy, "benchmarks", "limits")
    with open(os.path.join(lim, "tiny-gpt2.train.json")) as f:
        limits = f.read()
    with open(os.path.join(lim, "tiny-gpt2.train-dp4.json"), "w") as f:
        f.write(limits)
    rc, out, err = tiny.run_cell(copy, "tiny-gpt2.train-dp4", seconds=1.0,
                                 chips=4)
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert line["correct"] is True, "\n".join(out[-20:])
    assert line["device"]["count"] == 4


def test_without_a_tpu_no_result_line(copy):
    """The command as the driver runs it, on a machine without a chip,
    in a directory that holds only BENCHMARK.json and the benchmark:
    non-zero, and no result line."""
    rc, out, err = tiny.run_cell(copy, "gpt2-medium.train-1chip",
                                 override=False)
    assert rc != 0
    assert not any(x.startswith("{") for x in out)
    assert "no accelerator" in err


def _files(root):
    """{relative path: bytes} of every file under `root`."""
    held = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                held[os.path.relpath(p, root)] = fh.read()
    return held


def test_a_later_pr_brings_its_own_cell(tmp_path):
    """A configuration, a traffic mix, a per-layer metric with a reader
    of its own and a `workloads` entry, all as NEW files: no file of
    the benchmark is edited, and the run reports the new metric."""
    root = tiny.make_copy(tmp_path)
    bench = os.path.join(root, "benchmarks")
    before = _files(bench)

    with open(os.path.join(bench, "configs", "tiny-gpt2.json")) as f:
        config = json.load(f)
    config["arch"]["num_layers"] = 1
    with open(os.path.join(bench, "configs", "later.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "tiny-train.json")) as f:
        mix = json.load(f)
    mix["per_chip_batch"] = 1
    with open(os.path.join(bench, "traffic", "later-mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "limits", "tiny-gpt2.train.json")) as f:
        limits = f.read()
    with open(os.path.join(bench, "limits", "later.cell.json"), "w") as f:
        f.write(limits)
    lm = os.path.join(bench, "layer_metrics")
    with open(os.path.join(lm, "later_steps.json"), "w") as f:
        json.dump({"unit": "steps", "layer": "train step programs",
                   "moves": "train_tokens_per_s_per_chip",
                   "source": "program_counter", "args": {"scale": 2}}, f)
    with open(os.path.join(lm, "later_steps.py"), "w") as f:
        f.write("def read(ctx, scale):\n"
                "    return ctx['steps'] * scale\n")
    tiny.add_cell(root, "later.cell", "later", "later-mix",
                  "gpt2-medium.train-1chip")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["per_layer"].append({
        "name": "later_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step programs",
        "moves": "train_tokens_per_s_per_chip",
        "workloads": ["later.cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    rc, out, err = tiny.run_cell(root, "later.cell", seconds=0.5, trace=1)
    assert rc == 0, err[-3000:]
    line = result_line(out)
    assert line["correct"] is True, "\n".join(out[-20:])
    assert line["metrics"]["later_steps"]["value"] == 2 * line["attempted"]
    after = _files(bench)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "configs/later.json", "traffic/later-mix.json",
        "limits/later.cell.json", "layer_metrics/later_steps.json",
        "layer_metrics/later_steps.py"}


BROKEN_TRAIN = """
import horovod_tpu as hvd
_make = hvd.make_train_step
def broken(loss_fn, tx, **kw):
    real = _make(loss_fn, tx, donate=False, **kw)
    def step(params, opt_state, batch):
        _, opt_state, loss = real(params, opt_state, batch)
        return params, opt_state, loss       # the state comes back unchanged
    step.__wrapped__ = real.__wrapped__
    return step
hvd.make_train_step = broken
"""

BROKEN_SERVE = """
import dataclasses
import numpy as np
from horovod_tpu.serving import engine as E
_result = E.RequestHandle.result
def result(self, timeout=None):
    res = _result(self, timeout)
    toks = np.array(res.tokens)
    toks[len(toks) // 2] = (toks[len(toks) // 2] + 1) % 384   # one token altered
    return dataclasses.replace(res, tokens=toks)
E.RequestHandle.result = result
"""


@pytest.mark.parametrize("cell,patch,fails", [
    ("tiny-gpt2.train", BROKEN_TRAIN, "parameter change"),
    ("tiny-qwen2.serve", BROKEN_SERVE, "widest gap")])
def test_broken_timed_path_is_not_correct(copy, cell, patch, fails):
    """The rest of a run, with the timed path broken underneath:
    `correct` comes out false, on the number that is there to catch
    the fault."""
    rc, out, err = tiny.run_cell(copy, cell, seconds=1.0, patch=patch)
    assert rc == 0, err[-3000:]
    assert result_line(out)["correct"] is False
    failed = [x for x in out if "correct: " in x and "FAILED" in x]
    assert any(fails in x for x in failed), "\n".join(out[-20:])
