"""The documents name files that exist, and no record of a CPU run
stands beside them as a result.

The rule for paths: every file a document names in inline backticks
whose name ends in `.py`, `.md`, `.json` or `.sh` must exist under the
repository root, `horovod_tpu/`, `benchmarks/` or `docs/` (so
`ops/fusion.py`, `harness/trace.py` and `serving.md` are fine as the
documents write them). A trailing `:line` or `::name` is dropped.
Exempt: citations into the reference (`/root/reference/`, or a span
the document marks with "there" right after it, as in "`docs/gpus.md`
there"), any other absolute path, and patterns (`*`, `<...>`, `{...}`,
`$`). Fenced code blocks are not read: they hold a user's own scripts.
"""

import glob
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOTS = ("", "horovod_tpu", "benchmarks", "docs")
DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

_FENCE = re.compile(r"^```.*?^```", re.S | re.M)
_SPAN = re.compile(r"`([^`\n]+)`(\s+there\b)?")
_PATH = re.compile(r"[\w./-]*\w\.(?:py|md|json|sh)\b")


def named_paths(text):
    """(path, span) for every file an inline backtick span names."""
    for m in _SPAN.finditer(_FENCE.sub("", text)):
        span, there = m.group(1), m.group(2)
        if there or re.search(r"[*<>{}$]", span):
            continue
        for path in _PATH.findall(span):
            if path.startswith("/"):
                continue
            yield path, span


def resolves(path):
    return any(os.path.exists(os.path.join(REPO, root, path))
               for root in ROOTS)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_named_files_exist(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    missing = sorted({f"{path}  (in `{span}`)"
                      for path, span in named_paths(text)
                      if not resolves(path)})
    assert not missing, (
        f"{document} names files that are not in the repository:\n  "
        + "\n  ".join(missing))


def test_no_cpu_record_outside_the_benchmark():
    """A `*.json` that says `"platform": "cpu"` is a record of a CPU
    run kept as a result; the benchmark's and the tests' own fixtures
    are the only places one may live. Directories `.gitignore` lists
    (scratch copies, chip output) and dot-directories are not read."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        skip = {line.strip().rstrip("/") for line in f
                if line.strip().endswith("/")}
    skip |= {"benchmarks", "tests"}
    records = []
    for top, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if d not in skip and not d.startswith(".")]
        for name in files:
            if not name.endswith(".json"):
                continue
            with open(os.path.join(top, name)) as f:
                if re.search(r'"platform"\s*:\s*"cpu"', f.read()):
                    records.append(
                        os.path.relpath(os.path.join(top, name), REPO))
    assert not records, sorted(records)


def test_readme_lists_the_benchmarks_cells():
    """`README.md`'s table of cells is `BENCHMARK.json`'s `workloads`,
    each with its chips and its end-to-end metric, and the reverse; it
    carries no reading beside them (the ledger has the numbers)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metric = {cell: m["name"] for m in bench["end_to_end"]
              for cell in m.get("workloads", ())}
    cells = {(w["name"], str(w["chips"]), metric[w["name"]])
             for w in bench["workloads"]}
    with open(os.path.join(REPO, "README.md")) as f:
        text = f.read()
    rows = set(re.findall(
        r"^\| `([\w.-]+\.[\w-]+)` \| (\d+) \| `(\w+)` \|$", text, re.M))
    assert rows == cells
    # and no span names a cell of a listed configuration that is gone
    configs = {w["config"] for w in bench["workloads"]}
    named = {span for span in re.findall(r"`([^`\n]+)`", text)
             if any(span.startswith(c + ".") for c in configs)}
    assert named == {name for name, _, _ in cells}
