"""The orchestration-contract linter (repro.analysis).

Covers: each rule fires on its violating golden fixture and stays silent
on the clean one; inline and file-level suppressions; the default config
excluding the fixture directory; the JSON report shape; the runtime
snapshot-schema twin (FleetSnapshot.validate); and the self-clean gate —
``python -m repro.analysis src tests benchmarks examples`` exits 0 on
this very repo.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import (
    Analyzer,
    LintConfig,
    RuleSettings,
    available_rules,
    report_dict,
)

REPO = Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "fixtures" / "lint"

ALL_RULES = (
    "rng-discipline",
    "policy-purity",
    "snapshot-schema",
    "jit-hygiene",
    "deprecation",
    "registry-parity",
    "kernel-hygiene",
    "unit-consistency",
    "span-parity",
)

def run_rule(rule, path, options=None, root=REPO):
    """Run ONE rule over one file/dir with everywhere-scoping."""
    cfg = LintConfig(
        exclude=(),
        select=(rule,),
        rules={rule: RuleSettings(paths=("",), options=options or {})},
    )
    return Analyzer(cfg, root=str(root)).run([str(path)])


# -- the six golden fixture pairs ---------------------------------------------

FIXTURE_OPTIONS = {
    # the wall-clock check is path-scoped to src/repro by default; point it
    # at everything so the fixture exercises it too
    "rng-discipline": {"time_call_paths": ("",)},
    # inject a registry so the fixture is hermetic: "mystery_scheme" is
    # registered but only the clean fixture ever names it
    "registry-parity": {
        "test_paths": ("",),
        "policies": ("ibdash", "mystery_scheme"),
        "recoveries": ("fail_fast",),
    },
    # hermetic schema; no test files scanned, so only the literal/schema
    # halves of the contract are exercised (the test-pin half has its own
    # two-file test below)
    "span-parity": {
        "src_paths": ("",),
        "test_paths": (),
        "schema": ("exec", "plan"),
        "host_schema": ("plan.wave",),
    },
}

FIXTURE_STEMS = {
    "rng-discipline": "rng",
    "policy-purity": "purity",
    "snapshot-schema": "schema",
    "jit-hygiene": "jit",
    "deprecation": "deprecation",
    "registry-parity": "registry",
    "kernel-hygiene": "kernel",
    "unit-consistency": "unit",
    "span-parity": "span",
}

# every violation the fixture encodes must be reported (count pins the
# rule's sensitivity, not just its existence)
MIN_VIOLATIONS = {
    "rng-discipline": 4,      # import random, global draw, seed(), default_rng()
    "policy-purity": 4,       # apply, ctx store, __setattr__, snapshot store
    "snapshot-schema": 2,     # positional + missing leaves
    "jit-hygiene": 4,         # if-on-tracer, .item(), float(), while/np.asarray
    "deprecation": 4,         # Device(bandwidth=), bandwidths(), 2 latency shims
    "registry-parity": 1,     # mystery_scheme unpinned
    "kernel-hygiene": 4,      # f32 const + callback, 3-vs-1 lowerings, donation
    "unit-consistency": 5,    # s+B, B-vs-s, exp(s), where(s,B), prob-vs-count
    "span-parity": 6,         # 2+1 off-schema names, 2+1 computed names
}


@pytest.mark.parametrize("rule", ALL_RULES)
def test_rule_fires_on_violating_fixture(rule):
    path = FIXTURES / f"{FIXTURE_STEMS[rule]}_violation.py"
    report = run_rule(rule, path, FIXTURE_OPTIONS.get(rule))
    assert len(report.findings) >= MIN_VIOLATIONS[rule], report.findings
    assert all(f.rule == rule for f in report.findings)
    assert all(f.severity == "error" for f in report.findings)


@pytest.mark.parametrize("rule", ALL_RULES)
def test_rule_silent_on_clean_fixture(rule):
    path = FIXTURES / f"{FIXTURE_STEMS[rule]}_clean.py"
    report = run_rule(rule, path, FIXTURE_OPTIONS.get(rule))
    assert report.findings == [], [f.format() for f in report.findings]


def test_all_rules_registered():
    assert set(ALL_RULES) <= set(available_rules())


# -- suppressions --------------------------------------------------------------

def test_inline_suppression(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(
        "import numpy as np\n"
        "x = np.random.normal()  # repro-lint: disable=rng-discipline\n"
        "y = np.random.uniform()\n"
    )
    report = run_rule("rng-discipline", f, root=tmp_path)
    assert len(report.findings) == 1          # only the unsuppressed line
    assert report.findings[0].line == 3
    assert report.suppressed == 1


def test_file_level_suppression(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(
        "# repro-lint: disable-file=rng-discipline\n"
        "import numpy as np\n"
        "x = np.random.normal()\n"
        "y = np.random.uniform()\n"
    )
    report = run_rule("rng-discipline", f, root=tmp_path)
    assert report.findings == []
    assert report.suppressed == 2


def test_suppression_is_rule_scoped(tmp_path):
    """disable=<other-rule> must NOT silence a different rule's finding."""
    f = tmp_path / "mod.py"
    f.write_text(
        "import numpy as np\n"
        "x = np.random.normal()  # repro-lint: disable=deprecation\n"
    )
    report = run_rule("rng-discipline", f, root=tmp_path)
    assert len(report.findings) == 1
    assert report.suppressed == 0


# -- config / scoping ----------------------------------------------------------

def test_default_config_excludes_fixtures():
    report = Analyzer(LintConfig(), root=str(REPO)).run([str(FIXTURES)])
    assert report.files_scanned == 0
    assert report.findings == []


def test_path_scoping(tmp_path):
    """A rule scoped to src/ must ignore violations elsewhere."""
    (tmp_path / "src").mkdir()
    (tmp_path / "other").mkdir()
    (tmp_path / "src" / "a.py").write_text("import random\n")
    (tmp_path / "other" / "b.py").write_text("import random\n")
    cfg = LintConfig(
        exclude=(), select=("rng-discipline",),
        rules={"rng-discipline": RuleSettings(paths=("src/",))},
    )
    report = Analyzer(cfg, root=str(tmp_path)).run([str(tmp_path)])
    assert [f.path for f in report.findings] == ["src/a.py"]


def test_parse_error_is_a_finding(tmp_path):
    f = tmp_path / "broken.py"
    f.write_text("def oops(:\n")
    report = run_rule("rng-discipline", f, root=tmp_path)
    assert [f.rule for f in report.findings] == ["parse-error"]
    assert report.exit_code == 1


@pytest.mark.parametrize("emit, option, name", [
    ('tr.event(tid, "exec", t)', "schema", "exec"),
    ('hostspans.tally("plan.wave", 1, t)', "host_schema", "plan.wave"),
])
def test_span_parity_requires_test_pin(tmp_path, emit, option, name):
    """A kind (or wall-clock name) emitted in src but never named in a
    scanned test file is an unpinned span — and naming it silences the
    finding."""
    (tmp_path / "src").mkdir()
    (tmp_path / "tests").mkdir()
    (tmp_path / "src" / "emit.py").write_text(
        f'def go(tr, hostspans, tid, t):\n    {emit}\n'
    )
    (tmp_path / "tests" / "test_spans.py").write_text("x = 'unrelated'\n")
    opts = {option: (name,)}
    report = run_rule("span-parity", tmp_path, options=opts, root=tmp_path)
    assert len(report.findings) == 1
    assert "no behavioural pin" in report.findings[0].message
    (tmp_path / "tests" / "test_spans.py").write_text(f"kinds = ({name!r},)\n")
    report = run_rule("span-parity", tmp_path, options=opts, root=tmp_path)
    assert report.findings == []


def test_span_parity_silent_without_emissions(tmp_path):
    """Linting only tests (no emitting src files) must not guess."""
    f = tmp_path / "mod.py"
    f.write_text("x = 'exec'\n")
    report = run_rule(
        "span-parity", f,
        options={"test_paths": ("",), "src_paths": ("src",),
                 "schema": ("exec",)},
        root=tmp_path,
    )
    assert report.findings == []


def test_registry_parity_silent_without_test_files(tmp_path):
    """Linting only src must not guess about parity pins."""
    f = tmp_path / "mod.py"
    f.write_text("x = 1\n")
    report = run_rule(
        "registry-parity", f,
        options={"test_paths": ("tests",),
                 "policies": ("ibdash",), "recoveries": ()},
        root=tmp_path,
    )
    assert report.findings == []


# -- reporters -----------------------------------------------------------------

def test_json_report_shape():
    report = run_rule(
        "deprecation", FIXTURES / "deprecation_violation.py"
    )
    d = report_dict(report)
    assert d["version"] == 1
    assert d["errors"] == len(d["findings"]) > 0
    assert d["elapsed_s"] >= 0  # the CI wall-clock budget record
    f = d["findings"][0]
    assert set(f) == {"rule", "severity", "path", "line", "col", "message"}
    json.dumps(d)  # must be serialisable


# -- the runtime snapshot-schema twin ------------------------------------------

def _tiny_cluster():
    from repro.core.cluster import ClusterState, Device
    from repro.core.interference import InterferenceModel

    model = InterferenceModel(base=np.array([[0.1]]),
                              slope=np.full((1, 1, 1), 0.05))
    devices = [Device(did=i, cls=0, mem_total=1e9, lam=1e-3,
                      up_bw=1e8, down_bw=1e8) for i in range(2)]
    return ClusterState(devices=devices, model=model, horizon=10.0, dt=0.05)


def test_snapshot_validate_passes_and_chains():
    snap = _tiny_cluster().snapshot(0.0)
    assert snap.validate() is snap


def test_snapshot_validate_catches_leaf_drift(monkeypatch):
    from repro.core import batched

    snap = _tiny_cluster().snapshot(0.0)
    monkeypatch.setattr(
        batched, "FLEET_SNAPSHOT_SCHEMA", batched.FLEET_SNAPSHOT_SCHEMA[:-1]
    )
    with pytest.raises(TypeError, match="leaf drift"):
        snap.validate()


def test_cluster_snapshot_asserts_schema_under_debug(monkeypatch):
    from repro.core import batched

    cluster = _tiny_cluster()
    monkeypatch.setattr(
        batched, "FLEET_SNAPSHOT_SCHEMA",
        batched.FLEET_SNAPSHOT_SCHEMA + ("ghost_leaf",),
    )
    with pytest.raises(TypeError, match="leaf drift"):
        cluster.snapshot(0.0)


def test_schema_matches_dataclass_fields():
    from dataclasses import fields

    from repro.core.batched import FLEET_SNAPSHOT_SCHEMA, FleetSnapshot

    assert tuple(f.name for f in fields(FleetSnapshot)) == FLEET_SNAPSHOT_SCHEMA
    assert len(FLEET_SNAPSHOT_SCHEMA) == 17


# -- the self-clean gate -------------------------------------------------------

def _run_cli(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=300,
    )


def test_repo_is_self_clean(tmp_path):
    """THE acceptance gate: the analyzer runs clean on the repo itself."""
    out = tmp_path / "lint-report.json"
    proc = _run_cli("src", "tests", "benchmarks", "examples",
                    "--json", str(out))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(out.read_text())
    assert data["errors"] == 0
    assert data["files_scanned"] > 100
    assert set(ALL_RULES) <= set(data["rules_run"])


def test_cli_fails_on_violations():
    proc = _run_cli(str(FIXTURES / "rng_violation.py"), "--all-paths")
    assert proc.returncode == 1
    assert "rng-discipline" in proc.stdout


def test_cli_list_rules():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    for rule in ALL_RULES:
        assert rule in proc.stdout
