"""The churn deployment (``mix100.churn``) at a size a test run holds: its
reference regenerates the program's departure schedule from the
configuration, sound runs come out correct with replans in them, each
fault a churn cell can have, planted in the program, comes out not correct
by its own number, and the float32 control comes out not correct.  The
window's clock advances 0.1 s per reading, so a run is the same every
time: six waves of 120 instances on 40 devices."""
import itertools
import json
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

from check import compare, verdict  # noqa: E402
from harness import load_json, load_module, measure, per_layer_for, run_cell  # noqa: E402

CELL = "mix100.churn"
# the tiny window covers six 15 s cycles of sim time: churn seed 11 puts
# departures of loaded class-6 devices inside it
TINY_CONFIG = {"n_devices": 40, "horizon_s": 3000.0,
               "churn": {**load_json(HERE / "configs" /
                                     "paper-ped100-churn.json")["churn"],
                         "seed": 11}}
TINY_TRAFFIC = {"instances": 120, "warm_rows": [], "check_instances": 12}
# (replica deaths, replans, replans logged) of each sound run: every
# dead task is replanned once, none is lost
SOUND = {7: (237, 237, 237), 27: (256, 256, 256)}


def _cell():
    bench = load_json(ROOT / "BENCHMARK.json")
    return bench, next(w for w in bench["workloads"] if w["name"] == CELL)


def tiny_run(monkeypatch, seed):
    bench, wl = _cell()
    driver = load_module(HERE / "drivers" / "burst.py")
    monkeypatch.setattr(driver, "time", types.SimpleNamespace(
        perf_counter_ns=itertools.count(0, 10**8).__next__))
    return measure(bench, wl, seed, 0.55, False, config_overrides=TINY_CONFIG,
                   traffic_overrides=TINY_TRAFFIC, log=lambda m: None,
                   compile_cache=False)


def decide(run, monkeypatch=None, **kw):
    """``correct`` and the checks; with ``monkeypatch`` also the reference
    the replay ran (its float32 replan reading)."""
    refs = []
    if monkeypatch is not None:
        dep = run.setup.deployment
        monkeypatch.setattr(dep, "reference",
                            lambda s, real=dep.reference:
                            refs.append(real(s)) or refs[-1])
    try:
        checks = compare(run, **kw)
    finally:
        run.setup.inst.remove()
    return verdict(checks), checks, refs


def test_reference_regenerates_the_program_schedule():
    """The reference's own draw of the departures, from the
    configuration's rates and churn seed, equals the schedule the
    program's churn runtime replays."""
    from reference import Fleet
    from repro.sim import make_cluster, make_profile
    from repro.sim.churn import exponential_churn

    churn = load_module(HERE / "deployments" / "churn.py")
    config = load_json(HERE / "configs" / "paper-ped100-churn.json")["churn"]
    cl = make_cluster(make_profile(seed=0), scenario="churn", n_devices=40,
                      seed=27, horizon=3000.0)
    fleet = Fleet.read(cl.devices, cl.model.base, cl.model.slope,
                       cl.backhaul, cl.model_source, cl.dt, cl.horizon)
    lams = churn.rates(fleet, config)
    assert lams.tolist() == fleet.lams.tolist()
    program = exponential_churn(cl, horizon=3000.0, seed=config["seed"],
                                mean_downtime=20.0, resample_first=True)
    assert [(e.t, e.did, e.kind == "leave", e.until)
            for e in program.events] == churn.departures(
                lams, fleet.join, config["seed"], 3000.0, 20.0)
    assert program.n_events > 100


def test_schedule_does_not_follow_the_run_seed(monkeypatch):
    """Two run seeds replay one departure schedule: the churn seed is the
    configuration's."""
    firsts = []
    for seed in (7, 27):
        run = tiny_run(monkeypatch, seed)
        run.setup.inst.remove()
        firsts.append([d.alive_until for d in run.setup.cluster.devices])
    assert firsts[0] == firsts[1]


@pytest.mark.parametrize("seed", sorted(SOUND))
def test_sound_churn_run_is_correct(monkeypatch, seed):
    run = tiny_run(monkeypatch, seed)
    stats, log = run.setup.orch.stats, run.setup.replans
    ok, checks, refs = decide(run, monkeypatch)
    assert ok, checks
    assert (stats.replica_deaths, stats.replans, len(log)) == SOUND[seed]
    assert checks["churn_gap"]["value"] == 0
    assert checks["replan_plan_gap"]["value"] == 0.0
    assert checks["state_gap"]["value"] == 0
    assert refs[0].replan_out.checked_tasks > 0


def _replan_keeps_remainder(setattr):
    """A replan leaves the doomed remainder's provisional occupancy in
    T_alloc: it prices the fleet with it and never returns it."""
    from repro.core.recovery import ReplanRecovery

    real = ReplanRecovery.recover

    def recover(self, engine, run, tname):
        engine._cancel_provisional = lambda r, tasks=None: None
        try:
            real(self, engine, run, tname)
        finally:
            del engine._cancel_provisional

    setattr(ReplanRecovery, "recover", recover)


def _rates_off(setattr):
    """The program's fleet leaves at half the configuration's rates."""
    from repro.sim import profiles

    setattr(profiles, "SCENARIOS", {**profiles.SCENARIOS,
                                    "churn": profiles.LAMBDA_CHURN / 2})


def _no_rejoin(setattr):
    """A DEVICE_UP is dropped: the device stays away."""
    from repro.sim.engine import Engine

    setattr(Engine, "_device_up", lambda self, did, until: None)


def _keeps_occupancy(setattr):
    """A departure kills its replicas but leaves their remaining occupancy
    in T_alloc."""
    from repro.sim.engine import Engine

    real = Engine._device_down

    def device_down(self, did):
        cancel = self.cluster.cancel_from
        self.cluster.cancel_from = lambda *a, **k: None
        try:
            real(self, did)
        finally:
            self.cluster.cancel_from = cancel

    setattr(Engine, "_device_down", device_down)


CHURN_FAULTS = {
    "replan_keeps_remainder": (_replan_keeps_remainder, "replan_plan_gap"),
    "rates_off": (_rates_off, "churn_gap"),
    "dropped_rejoin": (_no_rejoin, "churn_gap"),
    "departure_keeps_occupancy": (_keeps_occupancy, "state_gap"),
}


@pytest.mark.parametrize("fault", sorted(CHURN_FAULTS))
def test_churn_fault_is_not_correct(monkeypatch, fault):
    plant, number = CHURN_FAULTS[fault]
    plant(monkeypatch.setattr)
    ok, checks, _ = decide(tiny_run(monkeypatch, 7))
    assert not ok
    assert checks[number]["value"] > checks[number]["limit"], checks


def test_churn_control_reads_above_the_limits(monkeypatch):
    """The reference in float32 put in the program's place: above
    ``plan_gap``'s limit on the arrivals, and above ``replan_plan_gap``'s
    on the replans it checked, while the program reads 0."""
    run = tiny_run(monkeypatch, 27)
    ctl = compare(run, control_dtype=np.float32)
    ok, checks, refs = decide(run, monkeypatch)
    assert ok, checks
    assert not verdict(ctl)
    assert ctl["plan_gap"]["value"] > ctl["plan_gap"]["limit"]
    replan = refs[0].replan_control
    assert replan.checked_tasks > 0
    assert replan.plan_gap > checks["replan_plan_gap"]["limit"]


def test_traced_churn_run_reads_the_program_side(monkeypatch):
    """Through ``run_cell`` on the CPU: the readers of the program's spans
    and counters report, the device-trace readers report nothing."""
    bench, wl = _cell()
    driver = load_module(HERE / "drivers" / "burst.py")
    monkeypatch.setattr(driver, "time", types.SimpleNamespace(
        perf_counter_ns=itertools.count(0, 10**8).__next__))
    out = run_cell(bench, wl, 7, 0.55, True, log=lambda m: None,
                   config_overrides=TINY_CONFIG, traffic_overrides=TINY_TRAFFIC,
                   compile_cache=False)
    mine = {m["name"]: m for m in per_layer_for(bench, wl)}
    assert len(mine) == 16 and all(n.endswith(".churn") for n in mine)
    host = {n for n, m in mine.items() if m["source"] != "device_trace"}
    assert set(out["metrics"]) == host
    assert out["metrics"]["replan_ms.churn"]["value"] > 0
    assert out["metrics"]["engine_recover_ms.churn"]["value"] > 0
    assert out["metrics"]["engine_device_down_ms.churn"]["value"] > 0
    assert out["correct"], out["checks"]
    json.dumps(out)
