"""A configuration brings its own deployment as new files: a deployment
module (set-up, warm-up and the reference ``check.py`` replays against), a
driver, and the schedule ops that driver records.  The default deployment
reads what it read before deployments were modules."""
import itertools
import json
import shutil
import sys
import types
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

from check import compare, readings, verdict  # noqa: E402
from harness import (RunError, deployment_for, load_json,  # noqa: E402
                     load_module, measure)

FIXTURES = Path(__file__).resolve().parent / "fixtures"
TINY_CONFIG = {"n_devices": 40, "horizon_s": 3000.0}
TINY_TRAFFIC = {"instances": 24, "warm_rows": [], "check_instances": 12}


def _checkout(root: Path):
    """A checkout of the benchmark's named files that gains, as new files
    only, the ``counted`` deployment, its configuration, the
    ``counted_burst`` driver and mix, and a cell that runs them."""
    bench = load_json(ROOT / "BENCHMARK.json")
    dst = root / HERE.name
    for d in ("configs", "deployments", "drivers", "traffic"):
        shutil.copytree(HERE / d, dst / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for f in FIXTURES.glob("*/*.py"):
        assert not (dst / f.relative_to(FIXTURES)).exists()
        shutil.copy(f, dst / f.relative_to(FIXTURES))
    config = {**load_json(HERE / "configs" / "paper-mix100.json"),
              "name": "counted-mix100", "deployment_module": "counted"}
    config["limits"] = {**config["limits"], "count_gap": 0}
    (dst / "configs" / "counted-mix100.json").write_text(json.dumps(config))
    traffic = {**load_json(HERE / "traffic" / "burst.json"),
               "driver": "counted_burst"}
    (dst / "traffic" / "counted_burst.json").write_text(json.dumps(traffic))
    bench["configs"].append({
        **bench["configs"][0], "name": "counted-mix100",
        "file": f"{HERE.name}/configs/counted-mix100.json"})
    wl = {"name": "mix100.counted", "config": "counted-mix100",
          "traffic": "counted_burst", "chips": 1,
          "why": "the burst cell with outcome counts replayed after each step"}
    bench["workloads"].append(wl)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench, wl


def _counted_run(root: Path):
    bench, wl = _checkout(root)
    return measure(bench, wl, 7, 0.3, False, root=root,
                   config_overrides=TINY_CONFIG,
                   traffic_overrides=TINY_TRAFFIC, log=lambda m: None,
                   compile_cache=False)


def test_own_deployment_replays_its_op_kind(tmp_path):
    run = _counted_run(tmp_path)
    try:
        checks = compare(run)
    finally:
        run.setup.inst.remove()
    assert Path(run.setup.deployment.__file__).name == "counted.py"
    assert verdict(checks), checks
    assert checks["count_gap"] == {"value": 0, "limit": 0}
    kinds = [op[0] for op in run.schedule]
    assert kinds.count("counts") == kinds.count("step") >= 1


def test_op_kind_without_a_handler_fails_the_run(tmp_path, monkeypatch):
    run = _counted_run(tmp_path)
    monkeypatch.delattr(run.setup.deployment.Reference, "replay_counts")
    try:
        with pytest.raises(RunError, match="'counts'"):
            compare(run)
    finally:
        run.setup.inst.remove()


def test_configuration_without_a_deployment_module_is_paper():
    config = load_json(HERE / "configs" / "paper-mix100.json")
    assert "deployment_module" not in config
    assert Path(deployment_for(config).__file__) == HERE / "deployments" / "paper.py"
    with pytest.raises(RunError, match="'absent'"):
        deployment_for({**config, "deployment_module": "absent"})


# mix100.burst's readings at a tiny size over three waves, with the
# window's clock advancing 0.1 s per reading so that the run is the same
# every time: (plan_gap, unplanned, state_gap, checked_tasks, worst) of
# the program, and the float32 control's plan_gap.  Taken on the CPU from
# the same run of the code before set-up and the reference moved into
# deployments/paper.py (commit 76a5bd5).
PINNED = {
    7: ((0.0, 0, 0.0, 72, ""), 0.01809224549536647),
    11: ((0.0, 0, 0.0, 71, ""), 0.0362327002368326),
}


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_burst_readings_are_pinned(monkeypatch, seed):
    """The default deployment reads what the code before it read."""
    bench = load_json(ROOT / "BENCHMARK.json")
    wl = next(w for w in bench["workloads"] if w["name"] == "mix100.burst")
    driver = load_module(HERE / "drivers" / "burst.py")
    monkeypatch.setattr(driver, "time", types.SimpleNamespace(
        perf_counter_ns=itertools.count(0, 10**8).__next__))
    run = measure(bench, wl, seed, 0.25, False, config_overrides=TINY_CONFIG,
                  traffic_overrides=TINY_TRAFFIC, log=lambda m: None,
                  compile_cache=False)
    try:
        out, ctl, extra = readings(run, np.float32)
    finally:
        run.setup.inst.remove()
    program, control = PINNED[seed]
    assert run.notes["waves"] == 3
    assert (out.plan_gap, out.unplanned, out.state_gap, out.checked_tasks,
            out.worst) == program
    assert ctl.plan_gap == pytest.approx(control, rel=1e-6)
    assert extra == {}
