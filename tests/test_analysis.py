"""repro-lint: the checker's own coverage.

Pins (a) the exact finding set each rule produces on the fixture tree
under ``tests/fixtures/lint/`` (one violation + a clean twin per rule),
(b) the ``--explain`` texts, (c) that the committed allowlist matches
the repo's *actual* baseline — empty for every rule, because every real
violation was fixed or its module removed — and (d) the jaxpr-audit contracts
on a slice of the matrix (the full matrix runs as the ``static_audit``
benchmark and in the CI gate).  The doc-lint layer (D1 snippet
execution, D2 link resolution) is covered on synthetic doc trees; the
repo's own snippets execute in the CI docs gate, not here.
"""
import json
import sys
from pathlib import Path

import pytest

from repro.analysis import (
    ALL_RULES,
    DOC_RULE_EXPLAIN,
    RULE_EXPLAIN,
    apply_allowlist,
    load_allowlist,
    render_allowlist,
    run_doclint,
    run_lint,
)
from repro.analysis.astlint import Finding
from repro.analysis.doclint import python_snippets

REPO_ROOT = Path(__file__).resolve().parent.parent
FIXTURE_ROOT = REPO_ROOT / "tests" / "fixtures" / "lint"
ALLOWLIST = REPO_ROOT / "tools" / "check_allowlist.json"


# ---------------------------------------------------------------------------
# Layer 1 on the fixture tree
# ---------------------------------------------------------------------------

# the full pinned finding set: exactly one violation per rule, and the
# clean twins sitting in the same directories stay silent
EXPECTED_FIXTURE_FINDINGS = {
    ("R1", "src/repro/core/r1_bad.py"),
    ("R2", "src/repro/core/r2_bad.py"),
    ("R3", "src/repro/kernels/fake/ops.py"),
    ("R4", "src/repro/core/r4_bad.py"),
    ("R5", "tests/test_r5_bad.py"),
    ("R6", "benchmarks/r6_bad.py"),
    ("R7", "src/repro/orphan_mod.py"),
    ("R8", "src/repro/core/r8_bad.py"),
}


def test_fixture_finding_set():
    findings = run_lint(FIXTURE_ROOT)
    assert {(f.rule, f.path) for f in findings} == EXPECTED_FIXTURE_FINDINGS
    # one finding per rule — the twins must not double-fire
    assert len(findings) == len(EXPECTED_FIXTURE_FINDINGS)


def test_fixture_clean_twins_are_silent():
    findings = run_lint(FIXTURE_ROOT)
    assert not [f for f in findings if "clean" in f.path]


def test_r7_allowlist_keys_by_module_name():
    (r7,) = run_lint(FIXTURE_ROOT, ["R7"])
    assert r7.key() == "repro.orphan_mod"
    assert r7.path == "src/repro/orphan_mod.py"


@pytest.mark.parametrize("rule", ALL_RULES)
def test_single_rule_selection(rule):
    findings = run_lint(FIXTURE_ROOT, [rule])
    assert {f.rule for f in findings} == {rule}


def test_unknown_rule_rejected():
    with pytest.raises(ValueError, match="unknown rules"):
        run_lint(FIXTURE_ROOT, ["R99"])


# ---------------------------------------------------------------------------
# --explain + CLI surface
# ---------------------------------------------------------------------------


def _tools_check():
    if str(REPO_ROOT) not in sys.path:
        sys.path.insert(0, str(REPO_ROOT))
    from tools import check

    return check


@pytest.mark.parametrize("rule", ALL_RULES)
def test_explain_text_pinned(rule, capsys):
    text = RULE_EXPLAIN[rule]
    assert text.startswith(f"{rule}: ")
    rc = _tools_check().main(["--explain", rule])
    assert rc == 0
    assert capsys.readouterr().out.strip() == text.strip()


def test_explain_first_lines():
    first = {r: RULE_EXPLAIN[r].splitlines()[0] for r in ALL_RULES}
    assert first == {
        "R1": "R1: `shard_map` may only be touched inside repro/distributed/sharding.py.",
        "R2": "R2: `repro.kernels.itp_*` packages are importable only by the plasticity",
        "R3": "R3: no literal `interpret=True/False` defaults in kernel ops wrappers.",
        "R4": "R4: one-argument `jnp.where(mask)` requires a static `size=`.",
        "R5": "R5: test modules import `_hypothesis_compat`, never `hypothesis` directly.",
        "R6": "R6: benchmarks write tracked BENCH_*.json via `bench_io.update_bench_json`.",
        "R7": "R7: every module under src/repro must be statically reachable from an",
        "R8": "R8: rule datapath hooks are called only inside repro/plasticity/.",
    }


def test_cli_fails_with_rule_and_location(capsys):
    argv = ["--lint", "--root", str(FIXTURE_ROOT), "--allowlist", "/dev/null"]
    rc = _tools_check().main(argv)
    out = capsys.readouterr().out
    assert rc == 1
    for rule, path in sorted(EXPECTED_FIXTURE_FINDINGS):
        assert f"{rule} {path}:" in out


def test_cli_clean_on_repo(capsys):
    rc = _tools_check().main(["--lint"])
    assert rc == 0, capsys.readouterr().out


# ---------------------------------------------------------------------------
# Allowlist semantics + committed baseline
# ---------------------------------------------------------------------------


def test_committed_allowlist_matches_repo_baseline():
    """The committed baseline IS the repo's current finding set: nothing
    new, nothing stale, and every rule's baseline empty."""
    findings = run_lint(REPO_ROOT)
    allow = load_allowlist(ALLOWLIST)
    new, stale = apply_allowlist(findings, allow)
    assert new == [], [f.render() for f in new]
    assert stale == []
    for rule in ("R1", "R2", "R3", "R4", "R5", "R6", "R7", "R8"):
        msg = f"{rule} baseline must stay empty — fix the violation instead of allowlisting"
        assert not allow.get(rule), msg


def test_allowlist_requires_justification(tmp_path):
    p = tmp_path / "allow.json"
    p.write_text(json.dumps({"R7": [{"module": "repro.x", "justification": "  "}]}))
    with pytest.raises(ValueError, match="justification"):
        load_allowlist(p)
    p.write_text(json.dumps({"R1": [{"justification": "no file key"}]}))
    with pytest.raises(ValueError, match="missing 'file'"):
        load_allowlist(p)


def test_stale_entries_ratchet_down():
    findings = [Finding("R1", "src/a.py", 3, "msg", "src/a.py")]
    allow = {
        "R1": [
            {"file": "src/a.py", "justification": "known"},
            {"file": "src/gone.py", "justification": "fixed"},
        ],
    }
    new, stale = apply_allowlist(findings, allow)
    assert new == []
    assert stale == [("R1", "src/gone.py")]


def test_render_allowlist_roundtrip_keeps_justifications():
    findings = run_lint(FIXTURE_ROOT)
    prev = {"R7": [{"module": "repro.orphan_mod", "justification": "kept on purpose"}]}
    regen = json.loads(render_allowlist(findings, prev))
    (r7,) = regen["R7"]
    assert r7 == {"module": "repro.orphan_mod", "justification": "kept on purpose"}
    expected_r1 = [{"file": "src/repro/core/r1_bad.py", "justification": "TODO: justify or fix"}]
    assert regen["R1"] == expected_r1
    # regenerated baseline gates clean against the same findings
    new, stale = apply_allowlist(findings, regen)
    assert new == [] and stale == []


# ---------------------------------------------------------------------------
# Doc-lint layer — D1 snippet execution, D2 link resolution
# ---------------------------------------------------------------------------


def test_python_snippets_fences_and_line_numbers():
    text = "\n".join(
        [
            "intro",
            "```python",
            "x = 1",
            "y = 2",
            "```",
            "```bash",
            "ls",
            "```",
            "```python",
            "print(x)",
            "```",
        ]
    )
    assert python_snippets(text) == [(3, "x = 1\ny = 2"), (10, "print(x)")]


def test_doclint_clean_tree(tmp_path):
    docs = tmp_path / "docs"
    docs.mkdir()
    (tmp_path / "README.md").write_text("[arch](docs/a.md)\n```python\nprint(1 + 1)\n```\n")
    (docs / "a.md").write_text("back to the [README](../README.md)\n")
    assert run_doclint(tmp_path) == []


def test_doclint_d1_failing_snippet(tmp_path):
    (tmp_path / "README.md").write_text("# t\n```python\nraise SystemExit(3)\n```\n")
    findings = run_doclint(tmp_path)
    assert [(f.rule, f.path, f.line) for f in findings] == [("D1", "README.md", 3)]
    assert "snippet failed" in findings[0].message


def test_doclint_d1_only_python_fences_execute(tmp_path):
    (tmp_path / "README.md").write_text("```bash\nexit 1\n```\n```text\nnot code\n```\n")
    assert run_doclint(tmp_path) == []


def test_doclint_d2_broken_and_skipped_links(tmp_path):
    (tmp_path / "ok.md").write_text("x")
    (tmp_path / "README.md").write_text(
        "[gone](missing.md) [ok](ok.md) [ext](https://example.com/x.md)\n"
        "[anchor](#section) [anchored](ok.md#part)\n"
    )
    findings = run_doclint(tmp_path, execute=False)
    assert [(f.rule, f.line) for f in findings] == [("D2", 1)]
    assert "missing.md" in findings[0].message


def test_doclint_execute_false_skips_snippets(tmp_path):
    (tmp_path / "README.md").write_text("```python\nraise SystemExit(1)\n```\n")
    assert run_doclint(tmp_path, execute=False) == []


def test_repo_doc_links_resolve():
    """Every intra-repo link in README.md/docs/ points at a real file
    (snippet execution is the CI docs gate's job — too slow for here)."""
    assert run_doclint(REPO_ROOT, execute=False) == []


@pytest.mark.parametrize("rule", sorted(DOC_RULE_EXPLAIN))
def test_explain_covers_doc_rules(rule, capsys):
    rc = _tools_check().main(["--explain", rule])
    assert rc == 0
    assert capsys.readouterr().out.strip() == DOC_RULE_EXPLAIN[rule].strip()


def test_cli_docs_layer_reports_findings(tmp_path, capsys):
    (tmp_path / "README.md").write_text("[gone](missing.md)\n")
    rc = _tools_check().main(["--docs", "--root", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "D2 README.md:1" in out
    assert "docs: 1 finding(s) — FAIL" in out


# ---------------------------------------------------------------------------
# Layer 2 — jaxpr audit
# ---------------------------------------------------------------------------


def test_audit_engine_cells_clean():
    from repro.analysis.jaxpr_audit import run_audit

    r = run_audit(kinds=("engine",))
    assert r["n_cells"] == 21  # 2 history × 4 + 3 counter × 3 + mstdp × 4
    bad = [c for c in r["cells"] if c["violations"]]
    assert not bad, bad
    # packed-register cells really carry uint8 through the graph
    for c in r["cells"]:
        if c["uint8_expected"]:
            assert c["has_uint8"], c
    # the counter reference cells read float magnitudes — no uint8 claim
    ref = [c for c in r["cells"] if c["backend"] == "reference" and c["rule"] == "exact"]
    assert ref and not ref[0]["uint8_expected"]


def test_audit_detects_trace_failure(monkeypatch):
    from repro.analysis import jaxpr_audit

    def boom(*a, **k):
        raise RuntimeError("synthetic trace failure")

    monkeypatch.setattr(jaxpr_audit, "engine_step", boom)
    cell = jaxpr_audit.audit_cell("itp", "reference", "engine")
    assert any("trace failed" in v for v in cell["violations"])


@pytest.mark.slow
def test_audit_full_matrix_clean():
    from repro.analysis.jaxpr_audit import run_audit

    r = run_audit()
    assert r["n_cells"] == 84  # 21 rule×backend cells × 4 kinds
    assert r["n_violating"] == 0, [c for c in r["cells"] if c["violations"]]


def test_bench_static_json_in_sync():
    """The tracked BENCH_static.json holds every valid cell of the matrix
    as traced on this toolchain, contract-clean."""
    from repro.analysis.jaxpr_audit import valid_cells

    path = REPO_ROOT / "BENCH_static.json"
    data = json.loads(path.read_text())["static_audit"]
    cells = {(c["rule"], c["backend"], c["kind"]) for c in data["cells"]}
    assert cells == set(valid_cells())
    assert data["n_violating"] == 0
    for c in data["cells"]:
        assert not c["violations"]
        assert not c.get("has_f64"), c
        if c.get("uint8_expected"):
            assert c.get("has_uint8"), c
