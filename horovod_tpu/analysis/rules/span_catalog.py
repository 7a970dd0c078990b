"""HVD012: span names drifting from the SPAN_CATALOG contract.

`horovod_tpu.obs.spans.SPAN_CATALOG` declares every causal span name
the subsystems may record, with the one-line description an operator
reads in docs/observability.md's span table. Phase attribution hangs
off the same names (`SPAN_PHASE`), so drift is worse than a missing
doc row: an undeclared span is invisible to the critical-path
anatomy. Two drift directions break the contract:

* a ``spans.begin_span("name", ...)`` / ``spans.record_span(...)`` /
  ``spans.loop_span(...)`` call (through any alias of the spans
  module, including function-local imports) with a literal name not
  in the catalog
  records a span no doc, waterfall legend or phase map knows
  (flagged at the call site);
* a catalog entry whose name is never recorded anywhere is a dead
  promise — the runbook describes a span that cannot occur (flagged
  at the catalog line).

Dynamic names (a variable first argument) are out of scope for the
literal scan; keep span names literal at call sites — that is what
makes traces greppable in the first place.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Set, Tuple

from horovod_tpu.analysis.core import Finding, RuleMeta, const_str

RULE = RuleMeta(
    id="HVD012",
    name="span-catalog-drift",
    severity="error",
    doc="spans.begin_span()/record_span()/loop_span() with a literal "
        "name not declared in obs/spans.py SPAN_CATALOG (undocumented span, "
        "invisible to phase anatomy), or a catalog entry whose name "
        "is never recorded (dead promise).")

_SPANS_MODULE = "obs/spans.py"
_SPANS_DOTTED = "horovod_tpu.obs.spans"
_RECORD_FNS = ("begin_span", "record_span", "loop_span")


def _spans_module(project):
    for mi in project.symbols.modules.values():
        if mi.path.endswith(_SPANS_MODULE):
            return mi
    return None


def _catalog_from_tree(tree) -> Dict[str, int]:
    """{name: lineno} from the ``SPAN_CATALOG = {...}`` literal."""
    out: Dict[str, int] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            tgts = [t.id for t in node.targets
                    if isinstance(t, ast.Name)]
        elif (isinstance(node, ast.AnnAssign)
              and isinstance(node.target, ast.Name)):
            tgts = [node.target.id]
        else:
            continue
        if "SPAN_CATALOG" not in tgts:
            continue
        if isinstance(node.value, ast.Dict):
            for key in node.value.keys:
                k = const_str(key) if key is not None else None
                if k:
                    out[k] = key.lineno
    return out


def _live_catalog() -> Dict[str, int]:
    try:
        from horovod_tpu.obs import spans as _sp
        return {k: 0 for k in getattr(_sp, "SPAN_CATALOG", {})}
    except ImportError:    # analyzing a foreign tree — static only
        return {}


def _span_aliases(mi) -> Tuple[Set[str], Set[str]]:
    """(module aliases of obs.spans, direct names bound to its
    ``begin_span``/``record_span``/``loop_span``) — scanned over the
    WHOLE tree,
    because subsystems import the spans module function-locally."""
    mods: Set[str] = set()
    fns: Set[str] = set()
    for node in ast.walk(mi.src.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == _SPANS_DOTTED and alias.asname:
                    mods.add(alias.asname)
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            for alias in node.names:
                local = alias.asname or alias.name
                if (mod.endswith("obs") and alias.name == "spans"):
                    mods.add(local)
                elif (mod.endswith("obs.spans")
                      and alias.name in _RECORD_FNS):
                    fns.add(local)
    return mods, fns


def record_sites(project) -> List[Tuple[str, int, int, str]]:
    """[(path, line, col, name)] — every literal-name begin/record
    through a spans-module alias, outside obs/spans.py itself."""
    out = []
    for mi in project.symbols.modules.values():
        if mi.path.endswith(_SPANS_MODULE):
            continue
        mods, fns = _span_aliases(mi)
        if not mods and not fns:
            continue
        for node in ast.walk(mi.src.tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            fn = node.func
            hit = ((isinstance(fn, ast.Attribute)
                    and fn.attr in _RECORD_FNS
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id in mods)
                   or (isinstance(fn, ast.Name) and fn.id in fns))
            if not hit:
                continue
            name = const_str(node.args[0])
            if name:
                out.append((mi.path, node.lineno, node.col_offset,
                            name))
    return out


def check(project):
    sp_mi = _spans_module(project)
    if sp_mi is not None:
        catalog = _catalog_from_tree(sp_mi.src.tree)
    else:
        catalog = _live_catalog()

    sites = record_sites(project)
    for path, line, col, name in sites:
        if name in catalog:
            continue
        yield Finding(
            RULE.id, RULE.severity, path, line, col,
            f"span name {name!r} recorded but not declared in "
            f"SPAN_CATALOG (horovod_tpu/obs/spans.py) — undeclared "
            f"spans never reach the docs/observability.md span table "
            f"and the phase anatomy cannot attribute them")

    # Dead-promise direction only when the spans module itself is in
    # the analyzed set — a subtree run without the recorders would
    # call every entry dead.
    if sp_mi is None:
        return
    recorded = {name for (_, _, _, name) in sites}
    for name in sorted(catalog):
        if name not in recorded:
            yield Finding(
                RULE.id, RULE.severity, sp_mi.path, catalog[name], 0,
                f"SPAN_CATALOG entry {name!r} is never recorded by "
                f"any subsystem — dead promise in the operator docs; "
                f"record it or delete the entry")
