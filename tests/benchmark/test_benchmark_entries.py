"""What must hold of `BENCHMARK.json` whatever is appended to it later:
every cell, configuration and per-layer entry is a case of its own,
found by the name the file gives it, so a PR that appends one brings
its case with it and edits no test. Nothing here names an entry or
counts a list; a cell's own rehearsal test says what that cell must
report.

The last test appends a configuration, a cell and a per-layer entry
to a copy (their files copied from an existing cell's under new
names) and holds the copy to the same checks: the lists take an
addition.
"""

import glob
import json
import os
import re
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)
import tiny  # noqa: E402

from benchmarks.harness.cells import Cell  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
MAX_CELLS = 24


def benchmark(root=REPO):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def names(key, bench=benchmark()):
    return [x["name"] for x in bench[key]]


# ---- the checks, on the tree at `root` ---------------------------------
def check_cell(root, name):
    cell = Cell(name, root=root)        # configuration, traffic, limits
    entry = cell.entry
    for key in ("name", "config", "traffic"):
        assert NAME.match(entry[key]), (key, entry[key])
    assert entry["chips"] in (1, 4)
    assert os.path.exists(os.path.join(
        cell.bench_dir, "kinds", cell.kind + ".py")), cell.kind
    assert cell.limits, "limits/<cell>.json holds no limit"
    own = [m["name"] for m in cell.end_to_end() if "workloads" in m]
    assert own, "the cell reports no end-to-end metric but setup_s"
    assert cell.per_layer(), "no per-layer metric lists the cell"


def check_config(root, name):
    bench = benchmark(root)
    entry = {c["name"]: c for c in bench["configs"]}[name]
    assert NAME.match(name)
    assert 1 <= len(entry["source"]) <= 200
    assert any(entry["file"].startswith(p + "/") for p in bench["paths"])
    with open(os.path.join(root, entry["file"])) as f:
        held = json.load(f)
    # a reduced key stands in the file beside its published value
    for key in entry["reduced"]:
        assert key in held, key
        assert key in held["published"], key
        assert held[key] != held["published"][key], key
    assert any(w["config"] == name for w in bench["workloads"])


def check_per_layer(root, name):
    bench = benchmark(root)
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert NAME.match(name)
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    for key in ("unit", "layer", "moves", "source"):
        assert entry[key] == spec[key], key
    assert entry["better"] in ("lower", "higher")
    every = [w["name"] for w in bench["workloads"]]
    moved = {m["name"]: m for m in bench["end_to_end"]}[entry["moves"]]
    cells = entry["workloads"]
    assert isinstance(cells, list) and cells
    for cell in cells:
        assert cell in every, cell
        assert cell in moved.get("workloads", every), cell
    # the harness finds the reader, and on nothing it reads nothing
    read = Cell(cells[0], root=root).reader(name)
    assert read({"trace": None}) is None


def check_whole(root):
    bench = benchmark(root)
    cells = bench["workloads"]
    assert 1 <= len(cells) <= MAX_CELLS
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        listed = [x["name"] for x in bench[key]]
        assert sorted(set(listed)) == sorted(listed), key     # no twins
    pairs = [w["config"] + "\n" + w["traffic"] for w in cells]
    assert sorted(set(pairs)) == sorted(pairs)
    # no orphan: every file pair under layer_metrics/ is entered
    lm = os.path.join(root, "benchmarks", "layer_metrics")
    held = {os.path.basename(p)[:-len(".json")]
            for p in glob.glob(os.path.join(lm, "*.json"))}
    assert held == {m["name"] for m in bench["per_layer"]}
    readers = {os.path.basename(p)[:-len(".py")]
               for p in glob.glob(os.path.join(lm, "*.py"))}
    assert readers <= held


# ---- the tree as it stands: one case an entry ---------------------------
@pytest.mark.parametrize("name", names("workloads"))
def test_a_cell_is_found_by_its_names(name):
    check_cell(REPO, name)


@pytest.mark.parametrize("name", names("configs"))
def test_a_configuration_holds_what_its_entry_says(name):
    check_config(REPO, name)


@pytest.mark.parametrize("name", names("per_layer"))
def test_a_per_layer_entry_agrees_with_its_file_pair(name):
    check_per_layer(REPO, name)


def test_the_lists_as_a_whole():
    check_whole(REPO)


# ---- and with an addition ----------------------------------------------
def test_the_lists_take_an_addition(tmp_path):
    """One configuration, one cell and one per-layer entry appended in
    a copy, as a `model_config` PR appends them - files of their own,
    copied from the last cell's under new names - and every check
    above holds of the copy, old entries and new."""
    root = tiny.bare_copy(tmp_path)
    bench = benchmark(root)
    like = bench["workloads"][-1]
    like_config = {c["name"]: c for c in bench["configs"]}[like["config"]]
    metric = [m for m in bench["per_layer"]
              if like["name"] in m["workloads"]][-1]
    b = os.path.join(root, "benchmarks")
    for src, dst in (
            (like_config["file"], "benchmarks/configs/added.json"),
            (f"benchmarks/traffic/{like['traffic']}.json",
             "benchmarks/traffic/added-mix.json"),
            (f"benchmarks/limits/{like['name']}.json",
             "benchmarks/limits/added.added-mix.json")):
        shutil.copy(os.path.join(root, src), os.path.join(root, dst))
    for ext in (".json", ".py"):
        src = os.path.join(b, "layer_metrics", metric["name"] + ext)
        if os.path.exists(src):
            shutil.copy(src, os.path.join(b, "layer_metrics",
                                          "added_metric" + ext))
    tiny.add_cell(root, "added.added-mix", "added", "added-mix",
                  like["name"])
    bench = benchmark(root)
    bench["configs"][-1].update(source=like_config["source"],
                                reduced=like_config["reduced"])
    bench["per_layer"].append(dict(metric, name="added_metric",
                                   workloads=["added.added-mix"]))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    assert [w["name"] for w in bench["workloads"]] == (
        names("workloads") + ["added.added-mix"])
    for cell in bench["workloads"]:
        check_cell(root, cell["name"])
    for config in bench["configs"]:
        check_config(root, config["name"])
    for m in bench["per_layer"]:
        check_per_layer(root, m["name"])
    check_whole(root)
    # the added entries are read from the copy, not from the tree
    added = Cell("added.added-mix", root=root)
    assert added.config_entry["file"] == "benchmarks/configs/added.json"
    assert "added_metric" in [m["name"] for m in added.per_layer()]
    with pytest.raises(SystemExit):
        Cell("added.added-mix")
