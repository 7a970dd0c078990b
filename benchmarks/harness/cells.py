"""Cells as data: everything a run needs is found by the names in
`BENCHMARK.json`.

A `workloads` entry is `{name, config, traffic, chips, why}`. From those
names alone the harness finds `configs/<config>.json`,
`traffic/<traffic>.json` (whose `kind` names `kinds/<kind>.py`, the
driver) and, for each `per_layer` entry of `BENCHMARK.json` that lists
the cell, `layer_metrics/<metric>.json` with its reader. A later PR adds
a cell, a configuration, a traffic mix, a kind or a per-layer metric by
adding files and entries; nothing here names one of them.
"""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    """A Python file under the benchmark's directories, by path."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reports_in(metric, cell_name, cells_reporting_moved):
    """Does `metric` (an entry of BENCHMARK.json) belong to this cell?
    With a `workloads` key: the cells it lists. Without: every cell
    that reports the end-to-end metric it moves (or, for an
    end-to-end metric, every cell)."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return cell_name in cells_reporting_moved


class Cell:
    """One entry of `workloads` with the files it names."""

    def __init__(self, name, root=ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmarks")
        self.benchmark = _json(os.path.join(root, "BENCHMARK.json"))
        entries = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in entries:
            raise SystemExit(
                f"no workload {name!r} in BENCHMARK.json; it has "
                f"{sorted(entries)}")
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.benchmark["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = _json(os.path.join(root, self.config_entry["file"]))
        self.traffic = _json(os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json"))
        self.kind = self.traffic["kind"]
        # the limits of `correct`, with the readings they were set from
        self.limits = _json(os.path.join(
            self.bench_dir, "limits", name + ".json"))["limits"]

    # -- metrics -------------------------------------------------------
    def end_to_end(self):
        """The cell's end-to-end metric entries."""
        all_cells = [w["name"] for w in self.benchmark["workloads"]]
        return [m for m in self.benchmark["end_to_end"]
                if metric_reports_in(m, self.name, all_cells)]

    def per_layer(self):
        """The cell's per-layer metric entries."""
        e2e = {m["name"]: m for m in self.benchmark["end_to_end"]}
        all_cells = [w["name"] for w in self.benchmark["workloads"]]
        out = []
        for m in self.benchmark["per_layer"]:
            moved = e2e[m["moves"]]
            reporting = [c for c in all_cells
                         if metric_reports_in(moved, c, all_cells)]
            if metric_reports_in(m, self.name, reporting):
                out.append(m)
        return out

    def driver(self):
        """The module `kinds/<kind>.py`: `run(cell, args, hooks)`."""
        path = os.path.join(self.bench_dir, "kinds", self.kind + ".py")
        if not os.path.exists(path):
            raise SystemExit(
                f"traffic {self.entry['traffic']!r} has kind "
                f"{self.kind!r} but there is no {path}")
        return load_module(path, f"benchmarks_kind_{self.kind}")

    def reader(self, metric_name):
        """`read(ctx) -> number | None` for one per-layer metric: the
        `read` of `layer_metrics/<name>.py` where that file exists,
        else the stock reader its `.json` names, bound to its `args`."""
        base = os.path.join(self.bench_dir, "layer_metrics", metric_name)
        spec = _json(base + ".json")
        if os.path.exists(base + ".py"):
            mod = load_module(base + ".py", "benchmarks_metric_"
                              + metric_name.replace(".", "_")
                              .replace("-", "_"))
            return lambda ctx: mod.read(ctx, **spec.get("args", {}))
        from benchmarks.harness import readers
        fn = getattr(readers, spec["reader"])
        return lambda ctx: fn(ctx, **spec.get("args", {}))
