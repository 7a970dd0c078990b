"""hvdlint (`horovod_tpu.analysis`) — rule fixtures, suppression
syntax, the baseline workflow, the CI gate, and the generated env-knob
table.

Every rule is driven by a fixture under `tests/analysis_fixtures/`
carrying a true positive (lines tagged ``# EXPECT``), a suppressed
positive (suppression reasons tagged ``SUPPRESSED``), and clean
negatives; the test asserts the flagged line set EXACTLY equals the
tagged set — false positives on the negatives fail just as hard as
false negatives on the positives.
"""

import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

from horovod_tpu.analysis import ALL_RULES, BY_ID, analyze
from horovod_tpu.analysis.core import (
    Project, SourceFile, collect_files, run_rules,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "analysis_fixtures")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FIXTURE_CASES = [
    ("hvd001_host_sync.py", "HVD001"),
    ("hvd002_trace_safety.py", "HVD002"),
    ("hvd003_recompile.py", "HVD003"),
    ("hvd004_locks.py", "HVD004"),
    ("hvd005_env_registry.py", "HVD005"),
    ("hvd006_broad_except.py", "HVD006"),
    ("hvd007_lock_order.py", "HVD007"),
    ("hvd008_cross_thread.py", "HVD008"),
    ("hvd009_blocking_lock.py", "HVD009"),
    ("hvd010_metric_catalog.py", "HVD010"),
    ("hvd011_event_docs.py", "HVD011"),
    ("hvd012_span_catalog.py", "HVD012"),
]


def _run_fixture(name, rule_id):
    files = collect_files([os.path.join(FIXTURES, name)], FIXTURES)
    active, muted = run_rules(Project(files), [BY_ID[rule_id]])
    return files[0], active, muted


class TestRuleFixtures:
    @pytest.mark.parametrize("name,rule_id", FIXTURE_CASES,
                             ids=[rid for _, rid in FIXTURE_CASES])
    def test_positives_suppressed_negatives(self, name, rule_id):
        src, active, muted = _run_fixture(name, rule_id)
        expected = {i for i, line in enumerate(src.lines, 1)
                    if "# EXPECT" in line}
        n_suppressed = sum(
            bool(re.search(r"hvd:\s*disable=.*SUPPRESSED", line))
            for line in src.lines)
        assert expected, f"{name} has no EXPECT tags"
        assert n_suppressed >= 1, f"{name} has no suppressed positive"
        flagged = {f.line for f in active}
        # Exact set equality: missing a tagged positive is a false
        # negative; flagging an untagged line is a false positive on
        # the fixture's clean negatives.
        assert flagged == expected, (
            f"{rule_id} flagged {sorted(flagged)}, expected "
            f"{sorted(expected)}:\n"
            + "\n".join(f.render() for f in active))
        assert len(muted) == n_suppressed, (
            f"{rule_id}: {len(muted)} muted finding(s) for "
            f"{n_suppressed} suppression(s):\n"
            + "\n".join(f.render() for f in muted))
        assert all(f.rule == rule_id for f in active + muted)

    def test_rule_catalog(self):
        ids = [mod.RULE.id for mod in ALL_RULES]
        assert ids == ["HVD001", "HVD002", "HVD003", "HVD004",
                       "HVD005", "HVD006", "HVD007", "HVD008",
                       "HVD009", "HVD010", "HVD011", "HVD012"]
        assert all(mod.RULE.severity in ("error", "warning")
                   for mod in ALL_RULES)
        assert len({mod.RULE.name for mod in ALL_RULES}) == 12


class TestRepoIsClean:
    def test_package_has_no_findings(self):
        """The shipped tree is hvdlint-clean with an EMPTY baseline —
        every true positive was fixed or carries a reasoned
        suppression (the acceptance bar of the analysis PR)."""
        (active, muted), nfiles = analyze(None)
        assert nfiles > 50   # the whole package, not a subtree
        assert active == [], "\n".join(f.render() for f in active)
        # The designed sync points etc. are suppressed, not absent.
        assert len(muted) >= 10

    def test_shipped_baseline_is_empty(self):
        with open(os.path.join(REPO, ".hvdlint-baseline.json")) as fh:
            data = json.load(fh)
        assert data == {"version": 1, "findings": []}

    def test_hot_path_entries_annotated(self):
        """The tick ring, the slot-pool tick pair, and the decode
        primitives are @hot_path entry points (the HVD001 universe)."""
        files = collect_files(
            [os.path.join(REPO, "horovod_tpu")], REPO)
        entries = {fi.qname.split(":")[1]
                   for fi in Project(files).symbols.hot_entries()}
        assert {"ContinuousBatchingScheduler.step",
                "SlotPool.tick_dispatch", "SlotPool.tick_sync",
                "slot_decode_tick",
                "slot_prefill_chunk"} <= entries


class TestSuppressionSyntax:
    def _src(self, body):
        return SourceFile("/x/f.py", "f.py", textwrap.dedent(body))

    def test_inline_and_preceding_line(self):
        src = self._src("""\
            x = 1  # hvd: disable=HVD001
            # hvd: disable=HVD002(a reason), HVD003
            y = 2
            z = 3
            """)
        assert src.suppressed("HVD001", 1)
        assert src.suppressed("HVD002", 3)
        assert src.suppressed("HVD003", 3)
        assert not src.suppressed("HVD001", 3)
        assert not src.suppressed("HVD002", 4)

    def test_reasons_are_recorded(self):
        src = self._src("""\
            # hvd: disable=HVD006(recovery code - degrade gracefully)
            y = 2
            """)
        assert src.suppressions[2]["HVD006"] == (
            "recovery code - degrade gracefully")

    def test_parens_and_rule_ids_inside_reason(self):
        """A reason mentioning call syntax and another rule id must
        stay ONE suppression with the FULL reason — a first-')' cut
        would silently mute HVD001 here (regression test)."""
        src = self._src("""\
            # hvd: disable=HVD004(abandon() is benign; HVD001 covers the sync)
            y = 2
            """)
        assert src.suppressions[2] == {
            "HVD004": "abandon() is benign; HVD001 covers the sync"}
        assert not src.suppressed("HVD001", 2)

    def test_prose_after_reason_cannot_mute_rules(self):
        """Rules chain only through a comma: ALL-CAPS words in
        trailing prose must not register as extra suppressions."""
        src = self._src("""\
            x = 1  # hvd: disable=HVD005(ok) but HVD001 style prose
            y = 2  # hvd: disable=HVD005 ALLCAPS prose without parens
            """)
        assert src.suppressions[1] == {"HVD005": "ok"}
        assert not src.suppressed("HVD001", 1)
        assert src.suppressions[2] == {"HVD005": ""}
        assert not src.suppressed("ALLCAPS", 2)

    def test_unbalanced_reason_runs_to_end(self):
        src = self._src("""\
            x = 1  # hvd: disable=HVD001(dangling open ( paren
            """)
        assert src.suppressed("HVD001", 1)
        assert "dangling open ( paren" == src.suppressions[1]["HVD001"]

    def test_blank_line_severs_standalone_suppression(self):
        """Deleting the statement a standalone suppression was written
        for must kill the suppression with it — it must NOT migrate
        across blank lines onto whatever code follows (regression
        test: a stale mute would let a genuine new violation pass the
        gate)."""
        src = self._src("""\
            # hvd: disable=HVD005(reason for a since-deleted read)

            # unrelated comment

            y = 2
            """)
        assert not src.suppressed("HVD005", 5)
        assert src.suppressions == {}

    def test_contiguous_comment_block_reaches_code(self):
        """A disable inside an unbroken comment block directly above
        the statement still applies."""
        src = self._src("""\
            # hvd: disable=HVD005(registry bootstrap reads itself)
            # the registry module cannot call its own accessor
            y = 2
            """)
        assert src.suppressed("HVD005", 3)


class TestBaselineWorkflow:
    def test_write_then_gate(self, tmp_path):
        """Snapshot known debt, pass the gate, then a NEW violation
        still fails — the adopt-then-ratchet workflow."""
        from horovod_tpu.analysis.cli import main
        mod = tmp_path / "legacy.py"
        mod.write_text(textwrap.dedent("""\
            def swallow(fn):
                try:
                    return fn()
                except Exception:
                    return None
            """))
        base = tmp_path / "base.json"
        # Unbaselined: fails.
        assert main([str(mod), "--baseline", str(base)]) == 1
        # Snapshot, then the same tree passes.
        assert main([str(mod), "--baseline", str(base),
                     "--write-baseline"]) == 0
        assert main([str(mod), "--baseline", str(base)]) == 0
        # A NEW finding fails even with the old one baselined.
        mod.write_text(mod.read_text() + textwrap.dedent("""\

            def swallow_harder(fn):
                try:
                    return fn()
                except BaseException:
                    return None
            """))
        assert main([str(mod), "--baseline", str(base)]) == 1

    def test_identical_message_still_fails(self, tmp_path):
        """Baselines match occurrence COUNTS: a second violation whose
        (rule, path, message) key is byte-identical to a baselined one
        must still fail the gate."""
        from horovod_tpu.analysis.cli import main
        mod = tmp_path / "legacy.py"
        clause = textwrap.dedent("""\
            def swallow{n}(fn):
                try:
                    return fn()
                except Exception:
                    return None
            """)
        mod.write_text(clause.format(n=1))
        base = tmp_path / "base.json"
        assert main([str(mod), "--baseline", str(base),
                     "--write-baseline"]) == 0
        assert main([str(mod), "--baseline", str(base)]) == 0
        # Same rule, same file, same message — only the count grows.
        mod.write_text(clause.format(n=1) + "\n" + clause.format(n=2))
        assert main([str(mod), "--baseline", str(base)]) == 1

    def test_default_baseline_is_symmetric(self, tmp_path,
                                           monkeypatch):
        """The documented adopt workflow without flags: plain runs
        READ the same cwd `.hvdlint-baseline.json` that
        `--write-baseline` writes (regression test: the default used
        to be write-only, so the snapshot-then-rerun workflow in
        baseline.py exited 1)."""
        from horovod_tpu.analysis.cli import main
        mod = tmp_path / "legacy.py"
        mod.write_text(textwrap.dedent("""\
            def swallow(fn):
                try:
                    return fn()
                except Exception:
                    return None
            """))
        monkeypatch.chdir(tmp_path)
        assert main([str(mod)]) == 1
        assert main([str(mod), "--write-baseline"]) == 0
        assert (tmp_path / ".hvdlint-baseline.json").exists()
        assert main([str(mod)]) == 0

    def test_malformed_baseline_raises(self, tmp_path):
        from horovod_tpu.analysis import baseline
        bad = tmp_path / "bad.json"
        bad.write_text('{"version": 99, "findings": []}')
        with pytest.raises(ValueError, match="version"):
            baseline.load(str(bad))


class TestCIGate:
    """The ci.sh gate (`python -m horovod_tpu.analysis --baseline
    .hvdlint-baseline.json`) must fail on an injected hot-path
    violation — proven here with a deliberately-violating temp file,
    not by breaking CI."""

    def test_gate_fails_on_injected_hvd001(self, tmp_path):
        bad = tmp_path / "injected_hot_sync.py"
        bad.write_text(textwrap.dedent("""\
            from horovod_tpu.annotations import hot_path


            @hot_path
            def tick(handle):
                return handle.toks.item()
            """))
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.analysis",
             "--baseline",
             os.path.join(REPO, ".hvdlint-baseline.json"),
             "--json", str(bad)],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 1, proc.stderr
        out = json.loads(proc.stdout)
        assert [f["rule"] for f in out["findings"]] == ["HVD001"]
        assert ".item()" in out["findings"][0]["message"]

    def test_json_output_shape(self):
        _, active, muted = _run_fixture("hvd006_broad_except.py",
                                        "HVD006")
        f = active[0].to_json()
        assert set(f) == {"rule", "severity", "path", "line", "col",
                          "message"}


class TestEnvKnobTable:
    def test_doc_table_matches_registry(self):
        """The troubleshooting env-var table is GENERATED from the
        config registry (python -m horovod_tpu.analysis
        --write-env-table) — this pins doc == code so it cannot
        drift."""
        from horovod_tpu.runtime.config import env_table_md
        doc = os.path.join(REPO, "docs", "troubleshooting.md")
        with open(doc) as fh:
            text = fh.read()
        m = re.search(
            r"<!-- hvdlint:env-table:begin -->\n(.*?)"
            r"<!-- hvdlint:env-table:end -->", text, re.S)
        assert m, "troubleshooting.md lost its env-table markers"
        assert m.group(1) == env_table_md(), (
            "docs/troubleshooting.md env table is stale — regenerate "
            "with: python -m horovod_tpu.analysis --write-env-table")

    def test_registry_covers_known_knobs(self):
        from horovod_tpu.runtime.config import KNOBS
        for name in ("HOROVOD_FUSION_THRESHOLD", "HVD_FUSION_MB",
                     "HVD_PREFILL_CHUNK_BUDGET", "HVD_CHAOS",
                     "HVD_CHAOS_SEED", "HVD_IO_RETRIES",
                     "HOROVOD_FLASH_BWD", "HOROVOD_PLATFORM",
                     "HOROVOD_KV"):
            assert name in KNOBS, name

    def test_accessors_enforce_registration(self):
        from horovod_tpu.runtime import config as cfg
        assert cfg.env_int("HVD_IO_RETRIES", 3) == 3
        with pytest.raises(KeyError, match="HVD_NOPE"):
            cfg.env_str("HVD_NOPE")
        with pytest.raises(ValueError, match="conflicting"):
            cfg.register_knob("HVD_CHAOS", "str", "different",
                              "elsewhere.py", "conflicting redecl")

    def test_stray_reads_went_through_registry(self, monkeypatch):
        """The satellite fix: the knobs that used to be raw os.environ
        reads now resolve through the registry accessors."""
        from horovod_tpu.resilience.retry import default_io_policy
        monkeypatch.setenv("HVD_IO_RETRIES", "7")
        assert default_io_policy().max_attempts == 7
        from horovod_tpu.resilience import chaos
        monkeypatch.setenv("HVD_CHAOS_SEED", "41")
        assert chaos._env_seed() == 41


class TestEventTable:
    def test_doc_table_matches_catalog(self):
        """The observability event table is GENERATED from
        EVENT_CATALOG (python -m horovod_tpu.analysis
        --write-event-table) — pinned here so doc and catalog cannot
        drift."""
        from horovod_tpu.obs.events import event_table_md
        doc = os.path.join(REPO, "docs", "observability.md")
        with open(doc) as fh:
            text = fh.read()
        m = re.search(
            r"<!-- hvdlint:event-table:begin -->\n(.*?)"
            r"<!-- hvdlint:event-table:end -->", text, re.S)
        assert m, "observability.md lost its event-table markers"
        assert m.group(1) == event_table_md(), (
            "docs/observability.md event table is stale — regenerate "
            "with: python -m horovod_tpu.analysis --write-event-table")

    def test_catalog_covers_known_kinds(self):
        from horovod_tpu.obs.events import EVENT_CATALOG
        for kind in ("serving.restart", "serving.submit", "stall",
                     "chaos.fire", "membership.resize", "slo.breach",
                     "collective.straggler", "flightrec.dump"):
            assert kind in EVENT_CATALOG, kind


class TestSpanTable:
    def test_doc_table_matches_catalog(self):
        """The request-tracing span table is GENERATED from
        SPAN_CATALOG (python -m horovod_tpu.analysis
        --write-span-table) — pinned here so doc and catalog cannot
        drift (the doc twin of HVD012's record-site pin)."""
        from horovod_tpu.obs.spans import span_table_md
        doc = os.path.join(REPO, "docs", "observability.md")
        with open(doc) as fh:
            text = fh.read()
        m = re.search(
            r"<!-- hvdlint:span-table:begin -->\n(.*?)"
            r"<!-- hvdlint:span-table:end -->", text, re.S)
        assert m, "observability.md lost its span-table markers"
        assert m.group(1) == span_table_md(), (
            "docs/observability.md span table is stale — regenerate "
            "with: python -m horovod_tpu.analysis --write-span-table")

    def test_catalog_covers_known_spans(self):
        from horovod_tpu.obs.spans import SPAN_CATALOG, SPAN_PHASE
        for name in ("serving.request", "serving.queued",
                     "serving.prefill", "serving.decode",
                     "router.request", "router.migration_gap",
                     "disagg.handoff", "transfer.export"):
            assert name in SPAN_CATALOG, name
        assert set(SPAN_PHASE) <= set(SPAN_CATALOG)


class TestDriftSelfProof:
    """The acceptance bar for the contract-drift rules: injecting an
    undeclared metric (or an undocumented event kind) in a temp file
    flips the CLI to exit 1."""

    def _cli(self, path, rules):
        return subprocess.run(
            [sys.executable, "-m", "horovod_tpu.analysis",
             "--baseline",
             os.path.join(REPO, ".hvdlint-baseline.json"),
             "--rules", rules, "--json", str(path)],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})

    def test_undeclared_metric_fails_gate(self, tmp_path):
        bad = tmp_path / "injected_metric.py"
        bad.write_text(textwrap.dedent("""\
            def declare(reg):
                return reg.counter("hvd_totally_new_total", "rogue")
            """))
        proc = self._cli(bad, "HVD010")
        assert proc.returncode == 1, proc.stderr
        out = json.loads(proc.stdout)
        assert [f["rule"] for f in out["findings"]] == ["HVD010"]
        assert "hvd_totally_new_total" in out["findings"][0]["message"]

    def test_undocumented_event_fails_gate(self, tmp_path):
        bad = tmp_path / "injected_event.py"
        bad.write_text(textwrap.dedent("""\
            from horovod_tpu.obs import events


            def fire():
                events.emit("injected.unknown_kind", x=1)
            """))
        proc = self._cli(bad, "HVD011")
        assert proc.returncode == 1, proc.stderr
        out = json.loads(proc.stdout)
        assert [f["rule"] for f in out["findings"]] == ["HVD011"]
        assert "injected.unknown_kind" in out["findings"][0]["message"]

    def test_undeclared_span_fails_gate(self, tmp_path):
        bad = tmp_path / "injected_span.py"
        bad.write_text(textwrap.dedent("""\
            from horovod_tpu.obs import spans


            def trace():
                sid = spans.begin_span("injected.unknown_span",
                                       trace_id="t")
                spans.end_span(sid)
            """))
        proc = self._cli(bad, "HVD012")
        assert proc.returncode == 1, proc.stderr
        out = json.loads(proc.stdout)
        assert [f["rule"] for f in out["findings"]] == ["HVD012"]
        assert "injected.unknown_span" in out["findings"][0]["message"]

    def test_json_by_rule_counts(self, tmp_path):
        proc = self._cli(
            os.path.join(FIXTURES, "hvd009_blocking_lock.py"),
            "HVD009")
        assert proc.returncode == 1, proc.stderr
        out = json.loads(proc.stdout)
        assert out["by_rule"] == {
            "HVD009": {"findings": 4, "suppressed": 1}}


class TestDeadEntryDirections:
    """The reverse drift directions run only when the declaring module
    itself is in the analyzed set — proven on a mini-tree."""

    def test_dead_catalog_entry(self, tmp_path):
        obs = tmp_path / "obs"
        obs.mkdir()
        (obs / "catalog.py").write_text(textwrap.dedent("""\
            def my_metrics(reg):
                return {
                    "used": reg.counter("hvd_mini_used_total", "d"),
                    "dead": reg.counter("hvd_mini_dead_total", "d"),
                }
            """))
        (tmp_path / "consumer.py").write_text(textwrap.dedent("""\
            def touch(m):
                m["used"].inc()
                reg = None
            """))
        files = collect_files([str(tmp_path)], str(tmp_path))
        active, _ = run_rules(Project(files), [BY_ID["HVD010"]])
        assert [f.rule for f in active] == ["HVD010"]
        assert "hvd_mini_dead_total" in active[0].message
        assert active[0].path.endswith("obs/catalog.py")

    def test_dead_event_promise(self, tmp_path):
        obs = tmp_path / "obs"
        obs.mkdir()
        (obs / "events.py").write_text(textwrap.dedent("""\
            EVENT_CATALOG = {
                "mini.emitted": "happens",
                "mini.never": "a dead promise",
            }
            """))
        (tmp_path / "consumer.py").write_text(textwrap.dedent("""\
            from horovod_tpu.obs import events


            def fire():
                events.emit("mini.emitted", ok=1)
            """))
        files = collect_files([str(tmp_path)], str(tmp_path))
        active, _ = run_rules(Project(files), [BY_ID["HVD011"]])
        assert [f.rule for f in active] == ["HVD011"]
        assert "mini.never" in active[0].message
        assert active[0].path.endswith("obs/events.py")

    def test_dead_span_promise(self, tmp_path):
        obs = tmp_path / "obs"
        obs.mkdir()
        (obs / "spans.py").write_text(textwrap.dedent("""\
            SPAN_CATALOG = {
                "mini.recorded": "happens",
                "mini.never": "a dead promise",
            }
            """))
        (tmp_path / "consumer.py").write_text(textwrap.dedent("""\
            from horovod_tpu.obs import spans


            def trace():
                spans.begin_span("mini.recorded", trace_id="t")
            """))
        files = collect_files([str(tmp_path)], str(tmp_path))
        active, _ = run_rules(Project(files), [BY_ID["HVD012"]])
        assert [f.rule for f in active] == ["HVD012"]
        assert "mini.never" in active[0].message
        assert active[0].path.endswith("obs/spans.py")


    def test_loop_span_literals_are_pinned_both_ways(self, tmp_path):
        """`loop_span` is a record verb like the others: its literal
        keeps a catalog entry alive, and an undeclared one is flagged
        at the call site."""
        obs = tmp_path / "obs"
        obs.mkdir()
        (obs / "spans.py").write_text(textwrap.dedent("""\
            SPAN_CATALOG = {
                "mini.loop": "recorded through loop_span",
            }
            """))
        (tmp_path / "consumer.py").write_text(textwrap.dedent("""\
            from horovod_tpu.obs import spans


            def loop():
                with spans.loop_span("mini.loop", tick=1):
                    with spans.loop_span("mini.undeclared"):
                        pass
            """))
        files = collect_files([str(tmp_path)], str(tmp_path))
        active, _ = run_rules(Project(files), [BY_ID["HVD012"]])
        assert len(active) == 1
        assert "mini.undeclared" in active[0].message
        assert active[0].path.endswith("consumer.py")


class TestChangedOnly:
    """--changed-only reporting scope: changed files plus their
    one-level importers; full-parse semantics stay (the CLI flag only
    filters findings)."""

    def _project(self):
        files = collect_files(
            [os.path.join(REPO, "horovod_tpu")], REPO)
        return Project(files)

    def test_scope_is_changed_plus_importers(self, monkeypatch):
        from horovod_tpu.analysis import cli
        monkeypatch.setattr(
            cli, "_git_changed_files",
            lambda root: {"horovod_tpu/serving/metrics.py"})
        scope = cli.changed_scope(self._project(), REPO)
        assert "horovod_tpu/serving/metrics.py" in scope
        # engine.py does `from horovod_tpu.serving.metrics import
        # EngineMetrics` — its contracts ride on the changed module.
        assert "horovod_tpu/serving/engine.py" in scope
        # Unrelated modules stay out of scope.
        assert "horovod_tpu/obs/catalog.py" not in scope

    def test_requires_git(self, monkeypatch):
        from horovod_tpu.analysis import cli
        monkeypatch.setattr(cli, "_git_changed_files",
                            lambda root: None)
        with pytest.raises(SystemExit, match="git"):
            cli.changed_scope(self._project(), REPO)

    def test_cli_flag_filters_findings(self, tmp_path, monkeypatch):
        """End to end: a tree with one dirty file reports only that
        file's findings under --changed-only."""
        from horovod_tpu.analysis import cli
        (tmp_path / "clean.py").write_text("x = 1\n")
        (tmp_path / "dirty.py").write_text(textwrap.dedent("""\
            def f():
                try:
                    return 1
                except Exception:
                    pass
            """))
        monkeypatch.setattr(cli, "_git_changed_files",
                            lambda root: {"dirty.py"})
        (active, muted), _ = cli.analyze(
            [str(tmp_path)], [BY_ID["HVD006"]], root=str(tmp_path),
            changed_only=True)
        assert {f.path for f in active} == {"dirty.py"}
