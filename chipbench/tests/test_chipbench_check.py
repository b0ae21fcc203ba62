"""The comparison that decides ``correct``, at a size a test run holds.

A sound run comes out correct; each fault a cell can have, planted in the
program under the timed path, comes out not correct; and the control (the
reference in float32 put in the program's place) comes out as not correct
while the program comes out correct.  The harness's look for a chip is
skipped by calling it below ``run.py``.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

from check import compare, verdict  # noqa: E402
from faults import FAULTS, planted  # noqa: E402
from harness import load_json, measure  # noqa: E402

TINY = {
    "mix100.burst": ({"n_devices": 40, "horizon_s": 3000.0},
                     {"instances": 24, "warm_rows": [], "check_instances": 12}, 0.3),
}


def tiny_run(name, seed=7):
    bench = load_json(ROOT / "BENCHMARK.json")
    wl = next(w for w in bench["workloads"] if w["name"] == name)
    cfg, trf, secs = TINY[name]
    return measure(bench, wl, seed, secs, False, config_overrides=cfg,
                   traffic_overrides=trf, log=lambda m: None,
                   compile_cache=False)


def decide(run, **kw):
    try:
        checks = compare(run, **kw)
    finally:
        run.setup.inst.remove()
    return verdict(checks), checks


@pytest.mark.parametrize("name", sorted(TINY))
def test_sound_run_is_correct(name):
    ok, checks = decide(tiny_run(name))
    assert ok, checks
    assert checks["state_gap"]["value"] == 0


@pytest.mark.parametrize("name", sorted(TINY))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(monkeypatch, name, fault):
    plant, number = FAULTS[fault]
    plant(monkeypatch.setattr)
    ok, checks = decide(tiny_run(name))
    assert not ok
    assert checks[number]["value"] > checks[number]["limit"], checks


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_put_back(fault):
    """``control.py``'s way of planting a fault on the chip: the run inside
    the block is not correct, and the program is whole again after it."""
    from repro import api
    from repro.core.cluster import ClusterState
    from repro.core.policy import IBDASHPolicy

    before = (api.orchestrate_batch, vars(ClusterState)["apply"],
              vars(IBDASHPolicy)["decide_batch"])
    with planted(fault):
        ok, checks = decide(tiny_run("mix100.burst"))
    assert not ok, checks
    assert (api.orchestrate_batch, vars(ClusterState)["apply"],
            vars(IBDASHPolicy)["decide_batch"]) == before
    ok, checks = decide(tiny_run("mix100.burst"))
    assert ok, checks


@pytest.mark.parametrize("name", sorted(TINY))
def test_control_reads_above_the_limit(name):
    run = tiny_run(name, seed=11)
    try:
        prog = compare(run)
        ctl = compare(run, control_dtype=np.float32)
    finally:
        run.setup.inst.remove()
    assert verdict(prog), prog
    assert not verdict(ctl)
    assert set(ctl) == {"plan_gap"}
    assert ctl["plan_gap"]["value"] > ctl["plan_gap"]["limit"]
