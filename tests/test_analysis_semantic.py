"""Semantic analysis v2 (PR 8): interprocedural effect inference, the
jaxpr kernel auditor, units-of-measure dataflow, stale-suppression
detection, parse-error resilience and the summary cache.

The golden fixture pairs live in tests/fixtures/lint/: the two-file
packages ``transitive_violation``/``transitive_clean`` exercise the
cross-function pass (``decide -> _helper -> ctx.cluster.apply()``), the
``kernel_*``/``unit_*`` modules the two new rules (their pair tests are
parametrized in test_analysis.py).
"""
import json
from pathlib import Path

import pytest

from repro.analysis import Analyzer, LintConfig, RuleSettings
from repro.analysis.callgraph import (
    load_summary_cache,
    save_summary_cache,
    summarize_module,
    summary_cache_stats,
)
from repro.analysis.reporters import render_sarif
from repro.analysis.units import (
    BYTES,
    BYTES_PER_S,
    SECONDS,
    parse_unit,
)

from test_analysis import FIXTURES, REPO, run_rule

VIOLATING_PKG = FIXTURES / "transitive_violation"
CLEAN_PKG = FIXTURES / "transitive_clean"


# -- interprocedural effect inference -----------------------------------------

def test_transitive_purity_reports_full_call_chain():
    """`decide -> _helper -> commit_plan -> ctx.cluster.apply()` — the
    mutation is two hops away from the policy method, and the finding's
    message must spell out the whole chain."""
    report = run_rule("policy-purity", VIOLATING_PKG)
    msgs = [f.message for f in report.findings]
    assert any(
        "decide -> _helper -> commit_plan -> ctx.cluster.apply()" in m
        for m in msgs
    ), msgs
    # the second leak: decide -> _note -> stamp_choice mutates `ctx`
    assert any(
        "decide -> _note -> stamp_choice" in m and "`ctx`" in m
        for m in msgs
    ), msgs
    # findings anchor at the call site inside the entry policy, not the leaf
    assert all(f.path.endswith("policy.py") for f in report.findings)


def test_transitive_rng_reports_full_call_chain():
    report = run_rule("rng-discipline", VIOLATING_PKG,
                      {"time_call_paths": ("",)})
    chains = [f for f in report.findings
              if "decide_batch -> pick_order -> np.random.shuffle()"
              in f.message]
    assert chains, [f.message for f in report.findings]
    assert all(f.path.endswith("policy.py") for f in chains)
    # the intraprocedural fallback still flags the leaf draw itself
    assert any(f.path.endswith("util.py") for f in report.findings)


@pytest.mark.parametrize("rule,options", [
    ("policy-purity", None),
    ("rng-discipline", {"time_call_paths": ("",)}),
])
def test_transitive_clean_twin_is_silent(rule, options):
    report = run_rule(rule, CLEAN_PKG, options)
    assert report.findings == [], [f.format() for f in report.findings]


def test_summary_cache_round_trips(tmp_path):
    src = "def f(x):\n    return x + 1\n"
    import ast
    summarize_module("mod.py", src, ast.parse(src))
    h0, _ = summary_cache_stats()
    summarize_module("mod.py", src, ast.parse(src))   # content-hash hit
    h1, _ = summary_cache_stats()
    assert h1 == h0 + 1
    cache = tmp_path / "summaries.json"
    assert save_summary_cache(str(cache)) >= 1
    assert load_summary_cache(str(cache)) >= 1


# -- jaxpr kernel auditor ------------------------------------------------------

def test_batched_kernels_lower_once_across_fleet_sweep():
    """THE acceptance criterion: every registered core/batched.py kernel
    lowers a bounded number of programs (one per padded wave bucket, not
    one per fleet size) across the D/B sweep — no shape-driven
    recompilation."""
    from repro.analysis.kernel_audit import audit_spec, builtin_targets

    specs = builtin_targets()["src/repro/core/batched.py"]
    assert {s.name for s in specs} == {
        "ibdash_scan_kernel", "lavea_kernel",
        "round_robin_kernel", "tier_escalation_kernel",
    }
    for spec in specs:
        assert audit_spec(spec) == []


def test_auditor_counts_distinct_lowerings(tmp_path):
    """A kernel traced at unpadded sizes B in {8, 9, 10} must be reported
    as 3 distinct programs against an expectation of 1."""
    from repro.analysis.kernel_audit import KernelSpec, audit_spec, f64

    def load():
        import jax.numpy as jnp

        def k(x):
            return jnp.sum(x * 2.0)
        return k

    spec = KernelSpec(
        name="toy", fn=load,
        build=lambda p: (f64(p["B"]),),
        sweep=({"B": 8}, {"B": 9}, {"B": 10}),
        x64=True, expected_lowerings=1,
    )
    msgs = audit_spec(spec)
    assert any("3 distinct programs" in m for m in msgs), msgs


# -- units-of-measure algebra --------------------------------------------------

def test_unit_algebra():
    assert parse_unit("B/s") == BYTES_PER_S
    assert BYTES.div(BYTES_PER_S) == SECONDS          # B / (B/s) -> s
    assert BYTES_PER_S.mul(SECONDS) == BYTES          # (B/s) * s -> B
    assert SECONDS.compatible(SECONDS)
    assert not SECONDS.compatible(BYTES)
    assert str(BYTES.div(SECONDS)) == "B/s"
    assert str(parse_unit("1/s").mul(SECONDS)) == "dimensionless"


# -- stale suppressions --------------------------------------------------------

def test_useless_suppression_is_reported(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text("x = 1  # repro-lint: disable=rng-discipline\n")
    report = run_rule("rng-discipline", f, root=tmp_path)
    assert [fd.rule for fd in report.findings] == ["useless-suppression"]
    assert report.findings[0].severity == "warning"
    assert "matched no finding" in report.findings[0].message
    assert report.exit_code == 0        # warnings never fail the run


def test_useless_suppression_only_judges_rules_that_ran(tmp_path):
    """A disable for a deselected rule might be load-bearing — leave it."""
    f = tmp_path / "mod.py"
    f.write_text("x = 1  # repro-lint: disable=deprecation\n")
    report = run_rule("rng-discipline", f, root=tmp_path)
    assert report.findings == [], [fd.format() for fd in report.findings]


def test_disable_marker_in_string_literal_is_ignored(tmp_path):
    """Only real comment tokens count: a marker inside a string (e.g. test
    code building fixture sources) neither suppresses nor goes stale."""
    f = tmp_path / "mod.py"
    f.write_text(
        'SRC = "x = 1  # repro-lint: disable=rng-discipline"\n'
        "import numpy as np\n"
        "y = np.random.normal()\n"
    )
    report = run_rule("rng-discipline", f, root=tmp_path)
    assert [fd.rule for fd in report.findings] == ["rng-discipline"]
    assert report.suppressed == 0


# -- parse-error resilience ----------------------------------------------------

def test_broken_file_does_not_abort_the_run(tmp_path):
    """One unparseable file yields a parse-error finding; every other
    file in the same run is still fully analyzed."""
    (tmp_path / "broken.py").write_text("def oops(:\n")
    (tmp_path / "good.py").write_text(
        "import numpy as np\nx = np.random.normal()\n"
    )
    report = run_rule("rng-discipline", tmp_path, root=tmp_path)
    by_rule = {f.rule: f for f in report.findings}
    assert set(by_rule) == {"parse-error", "rng-discipline"}
    assert by_rule["parse-error"].path == "broken.py"
    assert by_rule["rng-discipline"].path == "good.py"
    assert report.files_scanned == 2
    assert report.exit_code == 1


def test_broken_fixture_parses_as_finding():
    report = run_rule("rng-discipline", FIXTURES / "broken_syntax.py")
    assert [f.rule for f in report.findings] == ["parse-error"]
    assert "could not parse" in report.findings[0].message


# -- SARIF ---------------------------------------------------------------------

def test_sarif_report_shape():
    report = run_rule("unit-consistency", FIXTURES / "unit_violation.py")
    assert report.findings
    doc = json.loads(render_sarif(report))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "repro-analysis"
    rule_ids = [r["id"] for r in run["tool"]["driver"]["rules"]]
    assert len(run["results"]) == len(report.findings)
    for res in run["results"]:
        assert res["ruleId"] in rule_ids
        region = res["locations"][0]["physicalLocation"]["region"]
        assert region["startLine"] >= 1 and region["startColumn"] >= 1
