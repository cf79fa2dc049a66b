"""progen-tpu-lint: fixture corpus per rule, suppression + baseline
mechanics, the CLI exit-code contract, and the self-lint gate (the whole
repo must be clean modulo lint_baseline.json — the same invariant CI
enforces)."""

import json
from pathlib import Path

import pytest

from progen_tpu.analysis import (
    PROJECT_RULES,
    RULE_DOCS,
    RULES,
    BaselineError,
    ProjectContext,
    discover_files,
    lint_file,
    lint_paths,
    load_baseline,
    report_json,
)

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"

# rule id -> expected true-positive finding count in its _tp fixture
EXPECTED_TP = {
    "PGL001": 3,
    "PGL002": 2,
    "PGL003": 2,
    "PGL004": 4,
    "PGL005": 2,
    "PGL006": 51,
    "PGL007": 5,
    "PGL008": 4,
    "PGL009": 3,
    "PGL010": 4,
}


class TestFixtureCorpus:
    @pytest.mark.parametrize("rule_id", sorted(EXPECTED_TP))
    def test_true_positives(self, rule_id):
        path = FIXTURES / f"{rule_id.lower()}_tp.py"
        findings = lint_file(path)
        of_rule = [f for f in findings if f.rule == rule_id]
        assert len(of_rule) == EXPECTED_TP[rule_id], [
            f.render() for f in findings
        ]
        # the TP fixture must not trip OTHER rules either — cross-rule
        # noise in the corpus would mask regressions
        assert len(findings) == len(of_rule), [f.render() for f in findings]

    @pytest.mark.parametrize("rule_id", sorted(EXPECTED_TP))
    def test_true_negatives(self, rule_id):
        path = FIXTURES / f"{rule_id.lower()}_tn.py"
        findings = lint_file(path)
        assert findings == [], [f.render() for f in findings]

    def test_stage_names_are_held_to_the_span_rule(self):
        """PGL006 checks ``stage(`` as it checks ``span(``: a computed
        name fails, a literal or a forwarded parameter passes."""
        findings = lint_file(FIXTURES / "pgl006_stage_tp.py")
        assert [f.rule for f in findings] == ["PGL006", "PGL006"], [
            f.render() for f in findings
        ]
        assert "f-string" in findings[0].message
        assert "non-literal" in findings[1].message
        assert all("stage name" in f.message for f in findings)

    def test_literal_stage_names_pass(self):
        findings = lint_file(FIXTURES / "pgl006_stage_tn.py")
        assert findings == [], [f.render() for f in findings]

    def test_every_rule_has_fixtures(self):
        ids = {r.id for r in RULES} | {r.id for r in PROJECT_RULES}
        assert ids == set(EXPECTED_TP)
        for rule_id in ids:
            assert (FIXTURES / f"{rule_id.lower()}_tp.py").is_file()
            assert (FIXTURES / f"{rule_id.lower()}_tn.py").is_file()

    def test_findings_carry_location_and_func(self):
        findings = lint_file(FIXTURES / "pgl001_tp.py")
        f = findings[0]
        assert f.line > 0 and f.func == "loss_with_sync"
        assert "pgl001_tp.py" in f.render()
        assert f.to_json()["rule"] == "PGL001"


class TestProjectContext:
    """Index correctness for the cross-module pass the project rules
    (PGL009) share: installed sites, KNOWN_TARGETS, chaos references."""

    def _ctx(self, tmp_path, name, src):
        from progen_tpu.analysis.core import ModuleContext

        p = tmp_path / name
        p.write_text(src)
        return ModuleContext(p, src)

    def test_site_index_covers_all_installer_shapes(self, tmp_path):
        ctx = self._ctx(tmp_path, "m.py", (
            "def work(span, _span, retry_call, retryable, maybe_inject):\n"
            "    with span('a/plain'):\n"
            "        pass\n"
            "    with _span('a/aliased'):\n"
            "        pass\n"
            "    retry_call(lambda: 0, label='a/retry')\n"
            "    retryable('a/retryable')\n"
            "    maybe_inject('a/inject')\n"
            "    span(dynamic_name)\n"
        ))
        proj = ProjectContext.build([ctx])
        assert set(proj.sites) == {
            "a/plain", "a/aliased", "a/retry", "a/retryable", "a/inject",
        }
        path, line = proj.sites["a/plain"][0]
        assert path.endswith("m.py") and line == 2

    def test_known_targets_declaration_indexed(self, tmp_path):
        ctx = self._ctx(tmp_path, "chaos.py", (
            "KNOWN_TARGETS = frozenset({'x/one', 'x/two'})\n"
        ))
        proj = ProjectContext.build([ctx])
        assert proj.declaration is not None
        assert set(proj.declared) == {"x/one", "x/two"}

    def test_chaos_refs_from_strings_fstrings_comments(self, tmp_path):
        ctx = self._ctx(tmp_path, "t.py", (
            # progen: ignore[PGL009] - fixture source under test
            "SPEC = 'x/one:kill@2'\n"
            "def env(n):\n"
            "    return f'x/two:fail@{n}'\n"
            "# export PROGEN_CHAOS=x/three:0.5\n"
        ))
        proj = ProjectContext.build([ctx])
        assert [(r.target, r.line) for r in proj.chaos_refs] == [
            ("x/one", 1), ("x/two", 3), ("x/three", 4),
        ]

    def test_chaos_refs_from_text_files(self, tmp_path):
        yml = tmp_path / "ci.yml"
        yml.write_text(
            # progen: ignore[PGL009] - fixture source under test
            "env:\n  PROGEN_CHAOS: 'x/site:kill@1'\n"
        )
        proj = ProjectContext.build([], [yml])
        assert [(r.target, r.line) for r in proj.chaos_refs] == [
            ("x/site", 2),
        ]
        assert proj.chaos_refs[0].ctx is None  # not suppressible, bare loc

    def test_spec_grammar_rejects_lookalikes(self, tmp_path):
        ctx = self._ctx(tmp_path, "t.py", (
            "A = 'path/to/file.py:12'\n"          # line ref, not a spec
            "B = 'https://host/a:8080'\n"          # port, not a spec
            "C = 'a/b:kill@x'\n"                   # malformed count
            "D = 'noslash:kill@1'\n"               # target needs a '/'
        ))
        proj = ProjectContext.build([ctx])
        assert proj.chaos_refs == []

    def test_default_text_files_finds_workflows_and_docs(self):
        from progen_tpu.analysis import default_text_files

        files = {p.name for p in default_text_files([REPO / "progen_tpu"])}
        assert "tier1.yml" in files
        assert "README.md" in files


class TestSuppressions:
    def test_inline_same_line(self, tmp_path):
        p = tmp_path / "m.py"
        p.write_text(
            "import jax\n\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return float(x)  # progen: ignore[PGL001]\n"
        )
        assert lint_file(p) == []

    def test_standalone_comment_above(self, tmp_path):
        p = tmp_path / "m.py"
        p.write_text(
            "import jax\n\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    # progen: ignore[PGL001]\n"
            "    # justification may continue over several lines\n"
            "    return float(x)\n"
        )
        assert lint_file(p) == []

    def test_bare_ignore_suppresses_all(self, tmp_path):
        p = tmp_path / "m.py"
        p.write_text(
            "import jax\n\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    print(float(x))  # progen: ignore\n"
        )
        assert lint_file(p) == []

    def test_wrong_rule_does_not_suppress(self, tmp_path):
        p = tmp_path / "m.py"
        p.write_text(
            "import jax\n\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return float(x)  # progen: ignore[PGL005]\n"
        )
        assert [f.rule for f in lint_file(p)] == ["PGL001"]


class TestBaseline:
    def test_reason_is_mandatory(self, tmp_path):
        p = tmp_path / "b.json"
        p.write_text(json.dumps([{"rule": "PGL001", "path": "x.py"}]))
        with pytest.raises(BaselineError, match="reason"):
            load_baseline(p)

    def test_findings_wrapper_accepted(self, tmp_path):
        p = tmp_path / "b.json"
        p.write_text(json.dumps({"findings": [
            {"rule": "PGL001", "path": "x.py", "reason": "legacy"}
        ]}))
        assert len(load_baseline(p)) == 1

    def test_baseline_splits_new_from_grandfathered(self, tmp_path):
        src = tmp_path / "m.py"
        src.write_text(
            "import jax\n\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return float(x)\n\n"
            "@jax.jit\n"
            "def g(x):\n"
            "    return float(x)\n"
        )
        baseline = [
            {"rule": "PGL001", "path": "m.py", "func": "f",
             "reason": "grandfathered"}
        ]
        new, matched = lint_paths([src], baseline=baseline)
        assert [f.func for f in matched] == ["f"]
        assert [f.func for f in new] == ["g"]

    def test_path_matches_by_suffix(self, tmp_path):
        sub = tmp_path / "deep" / "nested"
        sub.mkdir(parents=True)
        src = sub / "m.py"
        src.write_text(
            "import jax\n\n@jax.jit\ndef f(x):\n    return float(x)\n"
        )
        baseline = [
            {"rule": "PGL001", "path": "nested/m.py", "reason": "ok"}
        ]
        new, matched = lint_paths([src], baseline=baseline)
        assert new == [] and len(matched) == 1

    def test_checked_in_baseline_loads_and_validates(self):
        entries = load_baseline(REPO / "lint_baseline.json")
        assert entries, "repo baseline exists and is non-empty"
        for e in entries:
            assert e["reason"].strip()

    def test_report_json_shape(self):
        findings = lint_file(FIXTURES / "pgl006_tp.py")
        rep = report_json(findings, [])
        assert rep["tool"] == "progen-tpu-lint"
        assert rep["summary"]["new"] == len(findings)
        assert rep["summary"]["by_rule"]["PGL006"] == len(findings)
        assert set(rep["rules"]) == set(RULE_DOCS)


class TestSelfLint:
    """The invariant CI enforces: the repo lints clean modulo baseline."""

    def test_repo_is_clean_modulo_baseline(self):
        baseline = load_baseline(REPO / "lint_baseline.json")
        new, _ = lint_paths(
            [REPO / "progen_tpu", REPO / "tests",
             REPO / "bench.py", REPO / "__graft_entry__.py",
             REPO / "chip_smoke.py"],
            baseline=baseline,
        )
        assert new == [], "\n".join(f.render() for f in new)

    def test_fixture_corpus_excluded_from_discovery(self):
        files = discover_files([REPO / "tests"])
        assert not any("lint_fixtures" in str(f) for f in files)

    def test_no_stale_baseline_entries(self):
        """Every baseline entry still matches a real finding — entries
        whose defect was fixed must be deleted, or the baseline rots."""
        baseline = load_baseline(REPO / "lint_baseline.json")
        _, matched = lint_paths(
            [REPO / "progen_tpu", REPO / "tests",
             REPO / "bench.py", REPO / "__graft_entry__.py",
             REPO / "chip_smoke.py"],
            baseline=baseline,
        )
        from progen_tpu.analysis.runner import _baseline_matches

        stale = [
            e for e in baseline
            if not any(_baseline_matches(e, f) for f in matched)
        ]
        assert stale == [], f"stale baseline entries: {stale}"


class TestCli:
    def _run(self, *args):
        from click.testing import CliRunner

        from progen_tpu.cli.lint import main

        return CliRunner(mix_stderr=True).invoke(main, list(args)) \
            if _mix_stderr_supported() else \
            CliRunner().invoke(main, list(args))

    def test_clean_file_exits_zero(self):
        res = self._run("--no-baseline", str(FIXTURES / "pgl001_tn.py"))
        assert res.exit_code == 0, res.output

    def test_findings_exit_one_and_print(self):
        res = self._run("--no-baseline", str(FIXTURES / "pgl001_tp.py"))
        assert res.exit_code == 1
        assert "PGL001" in res.output

    def test_json_report_written(self, tmp_path):
        out = tmp_path / "report.json"
        res = self._run(
            "--no-baseline", "--json", str(out),
            str(FIXTURES / "pgl004_tp.py"),
        )
        assert res.exit_code == 1
        rep = json.loads(out.read_text())
        assert rep["summary"]["by_rule"]["PGL004"] == 4

    def test_malformed_baseline_exits_two(self, tmp_path):
        bad = tmp_path / "b.json"
        bad.write_text(json.dumps([{"rule": "PGL001", "path": "x.py"}]))
        res = self._run(
            "--baseline", str(bad), str(FIXTURES / "pgl001_tn.py")
        )
        assert res.exit_code == 2

    def test_list_rules(self):
        res = self._run("--list-rules")
        assert res.exit_code == 0
        for rule_id in RULE_DOCS:
            assert rule_id in res.output

    def test_lint_is_jax_free(self):
        """The gate must run in a bare CI step: importing the analysis
        package and CLI must not import jax."""
        import subprocess
        import sys

        code = (
            "import sys; import progen_tpu.analysis, progen_tpu.cli.lint; "
            "sys.exit(1 if 'jax' in sys.modules else 0)"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True
        )
        assert proc.returncode == 0, proc.stderr.decode()


class TestRegistry:
    """The generated README sections: the dump renders both registries
    and the committed copy is drift-locked (same gate CI runs)."""

    def test_dump_contains_both_registries(self):
        from progen_tpu.analysis.registry import render_registry_markdown

        block = render_registry_markdown()
        assert "### Chaos sites" in block
        assert "### Event grammars" in block
        # a site every PR since the chaos harness has kept installed
        assert "`ckpt/save`" in block
        # an event grammar with its enum alphabet
        assert "accept/token/done" in block

    def test_chaos_table_lists_every_declared_target(self):
        from progen_tpu.analysis.registry import (
            build_project,
            render_chaos_sites_markdown,
            repo_root,
        )

        root = repo_root()
        proj = build_project([root / "progen_tpu"], rel_to=root)
        table = render_chaos_sites_markdown(proj)
        assert proj.declared, "KNOWN_TARGETS parsed from chaos.py"
        for target in proj.declared:
            assert f"| `{target}` |" in table

    def test_committed_readme_block_matches_code(self):
        from progen_tpu.analysis.registry import registry_check

        assert registry_check(REPO / "README.md") is None

    def test_check_flags_stale_block(self, tmp_path):
        from progen_tpu.analysis.registry import (
            REGISTRY_BEGIN,
            REGISTRY_END,
            registry_check,
        )

        doc = tmp_path / "doc.md"
        doc.write_text(
            f"{REGISTRY_BEGIN}\nstale hand-edited content\n{REGISTRY_END}\n"
        )
        problem = registry_check(doc)
        assert problem is not None and "stale" in problem
        assert registry_check(tmp_path / "doc.md") is not None

    def test_check_flags_missing_markers(self, tmp_path):
        from progen_tpu.analysis.registry import registry_check

        doc = tmp_path / "doc.md"
        doc.write_text("no markers here\n")
        problem = registry_check(doc)
        assert problem is not None and "markers" in problem

    def test_cli_dump_and_check(self, tmp_path):
        from click.testing import CliRunner

        from progen_tpu.cli.lint import main

        runner = CliRunner()
        dump = runner.invoke(main, ["--registry-dump"])
        assert dump.exit_code == 0 and "### Chaos sites" in dump.output

        check = runner.invoke(main, ["--registry-check",
                                     str(REPO / "README.md")])
        assert check.exit_code == 0, check.output

        stale = tmp_path / "doc.md"
        stale.write_text(
            "<!-- registry:begin -->\nold\n<!-- registry:end -->\n"
        )
        bad = runner.invoke(main, ["--registry-check", str(stale)])
        assert bad.exit_code == 1


def _mix_stderr_supported() -> bool:
    import inspect

    from click.testing import CliRunner

    return "mix_stderr" in inspect.signature(CliRunner.__init__).parameters


class TestRuffConfig:
    def test_pyproject_configures_ruff(self):
        text = (REPO / "pyproject.toml").read_text()
        assert "[tool.ruff]" in text
        assert "[tool.ruff.lint]" in text

    def test_ruff_passes_when_available(self):
        import shutil
        import subprocess

        ruff = shutil.which("ruff")
        if ruff is None:
            pytest.skip("ruff not installed in this environment")
        proc = subprocess.run(
            [ruff, "check", "."], cwd=REPO, capture_output=True
        )
        assert proc.returncode == 0, proc.stdout.decode()


class TestServingDonation:
    """Static proof (via the PGL003 machinery itself) that the serving
    engine's hot-loop jits donate their slot buffers and the train step
    donates its state: the buffer-donation audit, locked as a test so a
    refactor that silently drops donate_argnums fails CI, not a later
    HBM-pressure hunt."""

    def _registry(self, relpath):
        from progen_tpu.analysis.core import ModuleContext
        from progen_tpu.analysis.traced import TracedIndex

        path = REPO / relpath
        ctx = ModuleContext(path, path.read_text())
        return TracedIndex(ctx).jit_registry

    def test_engine_jits_donate_slot_buffers(self):
        registry = self._registry("progen_tpu/serving/engine.py")
        assert set(registry) >= {"_decode_step", "_prefill_chunk",
                                 "_prefill_finish"}
        # every program is handed the state it rewrites and hands it
        # back: the pool to the decode step; the pool and the batch-1
        # cache of the admission in flight (the engine's own tree, never
        # one a prefix cache holds) to the scatter; that cache to the
        # chunk. The weights are read by every later step.
        donated = {
            "_decode_step": {"slots"},
            "_prefill_finish": {"slots", "cache1"},
            "_prefill_chunk": {"cache"},
        }
        for fn, names in donated.items():
            assert set(registry[fn].donated_names) == names, (
                f"{fn} donates {registry[fn].donated_names}, not {names}"
            )

    def test_train_step_compile_donates_state(self):
        # assignment-form jit with explicit shardings: assert on source
        # (the traced registry covers decorated defs)
        src = (REPO / "progen_tpu" / "training" / "step.py").read_text()
        import re

        compile_fn = src.split("def compile_train_step", 1)[1]
        compile_fn = compile_fn.split("\ndef ", 1)[0]
        assert re.search(r"donate_argnums=\(0,\)", compile_fn), (
            "compile_train_step no longer donates the TrainState"
        )
