"""Churn runtime: device join/leave streams, failure detection, recovery.

Pins the PR's contracts:
  * a policy can NEVER select a device that already departed (the alive
    mask threaded ClusterState.snapshot -> FleetSnapshot ->
    BatchedPolicyContext -> feasibility);
  * ``fail_fast`` on churn-free runs is bit-identical to the engine's
    default path for all six policies;
  * under churn, ``failover``/``replan`` never lose more instances than
    ``fail_fast`` (property-tested over random schedules) and strictly
    reduce P_f on the benchmark fleet;
  * the occupancy bookkeeping nets to exactly the executed work after
    ``drain()`` — killed replicas and failed apps leave zero ghost residue;
  * FleetMonitor's online lambda MLE (the shared fit_failure_rate
    estimator) feeds the churn generator end-to-end.
"""
from dataclasses import replace

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.api import Orchestrator, make_policy, make_recovery, orchestrate
from repro.core.cluster import ClusterState, Device
from repro.core.dag import AppDAG, TaskSpec
from repro.core.interference import InterferenceModel
from repro.core.recovery import available_recoveries
from repro.ft.runtime import FleetMonitor
from repro.sim import SimConfig, make_cluster, make_profile, run_one
from repro.sim.churn import (
    ChurnSchedule,
    churn_from_monitor,
    deterministic_churn,
    exponential_churn,
    trace_churn,
)
from repro.sim.engine import Engine
from repro.sim.runner import SCHEME_NAMES, _make_workload, policy_for

GB = 1e9
MB = 1e6


@pytest.fixture(scope="module")
def profile():
    return make_profile(seed=0)


def small_cluster(n=4, lam=1e-6, base=None, alive_until=None, horizon=100.0):
    """n single-type devices, device i is class i (distinct base latency)."""
    base = np.linspace(0.1, 0.4, n) if base is None else np.asarray(base)
    model = InterferenceModel(
        base=base[:, None], slope=np.full((n, 1, 1), 0.05)
    )
    devices = [
        Device(did=i, cls=i, mem_total=8 * GB, lam=lam, up_bw=100e6, down_bw=100e6,
               alive_until=(alive_until[i] if alive_until is not None
                            else float("inf")))
        for i in range(n)
    ]
    return ClusterState(devices=devices, model=model, horizon=horizon, dt=0.05)


def one_task_app(name="app"):
    return AppDAG.from_tasks(name, [TaskSpec("t0", ttype=0)])


def two_par_app(name="app"):
    """One stage, two parallel tasks (the cancel-running-siblings shape)."""
    return AppDAG.from_tasks(name, [
        TaskSpec("a", ttype=0),
        TaskSpec("b", ttype=0),
    ])


# ---------------------------------------------------- dead-device masking --
def _policies(profile, cfg):
    return [policy_for(name, profile, cfg) for name in SCHEME_NAMES]


@pytest.mark.parametrize("scheme", SCHEME_NAMES + ("tier_escalation",))
def test_policy_never_selects_dead_device(profile, scheme):
    """Satellite-1 regression: device 0 is the FASTEST but departed at
    t=1.0; planning at t=2.0 must not place anything on it — for every
    registered policy, scalar and batched paths alike."""
    cfg = SimConfig(seed=0)
    for batched in (True, False):
        cluster = small_cluster(alive_until=[1.0, np.inf, np.inf, np.inf])
        pol = policy_for(scheme, profile, cfg)
        plan = orchestrate(one_task_app(), cluster, 2.0, pol, batched=batched)
        assert plan.feasible
        assert all(
            rep.did != 0 for rep in plan.tasks["t0"].replicas
        ), f"{scheme} placed on a dead device (batched={batched})"


def test_dead_device_masked_only_after_departure(profile):
    """Before its departure the device is a normal candidate (future deaths
    stay silent — only pf prices them); after it, it is infeasible."""
    cluster = small_cluster(alive_until=[1.0, np.inf, np.inf, np.inf])
    pol = make_policy("lavea")
    before = orchestrate(one_task_app(), cluster, 0.5, pol)
    after = orchestrate(one_task_app(), cluster, 2.0, pol)
    assert before.tasks["t0"].replicas[0].did == 0     # fastest, still up
    assert after.tasks["t0"].replicas[0].did != 0


def test_snapshot_alive_mask():
    cluster = small_cluster(alive_until=[1.0, 5.0, np.inf, np.inf])
    assert cluster.snapshot(0.0).alive.tolist() == [True, True, True, True]
    assert cluster.snapshot(2.0).alive.tolist() == [False, True, True, True]
    assert cluster.snapshot(6.0).alive.tolist() == [False, False, True, True]


def test_all_devices_dead_is_infeasible(profile):
    cluster = small_cluster(alive_until=[1.0, 1.0, 1.0, 1.0])
    plan = orchestrate(one_task_app(), cluster, 2.0, make_policy("random"))
    assert not plan.feasible and plan.placement.infeasible_task == "t0"


def test_mark_down_and_up_roundtrip():
    cluster = small_cluster()
    cluster.mark_down(1, 3.0)
    assert not cluster.alive_mask(3.0)[1]
    cluster.mark_up(1, 7.0, alive_until=20.0)
    assert cluster.alive_mask(7.5)[1]
    assert not cluster.alive_mask(25.0)[1]
    assert cluster.devices[1].join_time == 7.0
    assert cluster.devices[1].model_cache == {}        # rejoined cold


# -------------------------------------------------- churn-free bit-parity --
def _result_fingerprint(res):
    return [
        (r.app, r.arrival, r.finished, r.failed, r.service_time,
         r.n_replicas, r.pred_latency, r.pred_fail)
        for r in res.instances
    ]


@pytest.mark.parametrize("scheme", SCHEME_NAMES)
def test_fail_fast_churn_free_bit_identical(profile, scheme):
    """Satellite-3 invariant: recovery="fail_fast" with churn disabled IS
    the default engine, bit-for-bit, for all six policies on a scenario
    where devices do die (the passive failure path runs)."""
    cfg = SimConfig(n_cycles=2, instances_per_cycle=80, scenario="ped", seed=3)
    a = run_one(scheme, cfg, profile)
    b = run_one(scheme, replace(cfg, recovery="fail_fast", churn=False), profile)
    assert _result_fingerprint(a) == _result_fingerprint(b)
    assert (a.load_per_device == b.load_per_device).all()


def test_empty_schedule_bit_identical_to_no_churn(profile):
    """An installed schedule with zero events must not perturb anything on
    an immortal fleet (the event machinery itself is inert)."""
    cfg = SimConfig(seed=0)
    apps = [one_task_app(f"#{i}") for i in range(8)]
    times = [0.3 * i for i in range(8)]
    runs = []
    for churn in (None, deterministic_churn([])):
        cluster = small_cluster()
        eng = Engine(cluster, policy_for("ibdash", profile, cfg), seed=0,
                     churn=churn)
        eng.add_arrivals(apps, times)
        eng.drain()
        runs.append((
            [(r.failed, r.finished, r.service_time) for r in eng.records],
            cluster.alloc.copy(),
        ))
    assert runs[0][0] == runs[1][0]
    assert np.array_equal(runs[0][1], runs[1][1])


# ------------------------------------------------- engine churn semantics --
def test_device_down_kills_inflight_and_returns_capacity():
    """A departing device's in-flight replica dies AT the departure (not at
    its estimated completion), its unfinished occupancy is returned, and
    fail_fast loses the instance at that moment."""
    cluster = small_cluster(base=[0.5, 0.5, 0.5, 0.5], lam=1e-4)
    churn = deterministic_churn([(0.2, 0, "leave")])
    eng = Engine(cluster, make_policy("lavea"), noise_sigma=0.0, churn=churn,
                 recovery="fail_fast", track_intervals=True)
    eng.add_arrivals([one_task_app()], [0.0])
    eng.drain()
    rec = eng.records[0]
    assert rec.failed and rec.finished == pytest.approx(0.2)
    assert eng.stats["device_down"] == 1
    assert eng.stats["replica_deaths"] == 1
    assert eng.stats["lost"] == 1
    # capacity returned: no occupancy anywhere after the kill's bucket
    b = cluster.bucket(0.2) + 1
    assert float(np.abs(cluster.alloc[:, :, b:]).max()) == 0.0


def test_device_rejoins_and_is_readmitted():
    cluster = small_cluster(base=[0.1, 0.4, 0.4, 0.4])
    churn = deterministic_churn([(1.0, 0, "leave"), (2.0, 0, "join")])
    eng = Engine(cluster, make_policy("lavea"), noise_sigma=0.0, churn=churn)
    eng.run(until=1.5)      # the departure fired, the rejoin has not
    down = orchestrate(one_task_app(), cluster, 1.5, make_policy("lavea"))
    assert down.tasks["t0"].replicas[0].did != 0
    eng.run(until=5.0)      # the rejoin fired
    up = orchestrate(one_task_app(), cluster, 4.0, make_policy("lavea"))
    # rejoined empty and idle: the fast device wins again
    assert up.tasks["t0"].replicas[0].did == 0
    assert eng.stats["device_up"] == 1


def test_failed_app_cancels_running_siblings():
    """Satellite-2 regression: when an app fails mid-stage, in-flight
    sibling replicas of its OTHER tasks stop occupying T_alloc from the
    failure instant (their output is discarded anyway)."""
    # device 0: fast but dies mid-task; device 1: slow (long sibling run)
    cluster = small_cluster(n=2, base=[0.2, 5.0],
                            alive_until=[0.05, np.inf])
    eng = Engine(cluster, make_policy("round_robin"), noise_sigma=0.0,
                 track_intervals=True)
    eng.add_arrivals([two_par_app()], [0.0])
    eng.drain()
    rec = eng.records[0]
    assert rec.failed
    t_fail = rec.finished                      # task a's passive death
    assert t_fail == pytest.approx(0.2)
    # sibling b (5 s on device 1) was cancelled at the failure: nothing
    # occupies any device afterwards
    b = cluster.bucket(t_fail) + 1
    assert float(np.abs(cluster.alloc[:, :, b:]).max()) == 0.0
    # and the executed log shows b's run was cut at the failure time
    cuts = [e for e in eng.executed if e[0] == 1 and e[4] < e[3]]
    assert len(cuts) == 1 and cuts[0][4] == pytest.approx(t_fail)


def _rebuild_alloc(cluster_factory, executed):
    """Replay an engine's executed-interval log onto a fresh cluster."""
    c = cluster_factory()
    for did, ttype, t0, t1, t_cut in executed:
        c.add_interval(did, ttype, t0, t1)
        if t_cut < t1:
            c.cancel_from(did, ttype, t0, t1, t_cut)
    return c.alloc


@pytest.mark.parametrize("recovery", ("fail_fast", "failover", "replan"))
def test_occupancy_nets_to_executed_work_after_drain(profile, recovery):
    """Satellite-3 invariant: after drain() the T_alloc tensor equals
    EXACTLY the replay of actual execution spans — every provisional
    interval, killed replica and cancelled sibling netted out to zero."""
    cfg = SimConfig(scenario="churn", n_cycles=2, instances_per_cycle=60,
                    seed=3, n_devices=24, recovery=recovery)
    mk = lambda: make_cluster(profile, scenario="churn", n_devices=24, seed=3,
                              horizon=cfg.horizon + 60.0)
    cluster = mk()
    churn = exponential_churn(cluster, horizon=cfg.horizon + 25.0, seed=104)
    orch = Orchestrator(cluster, policy_for("ibdash", profile, cfg), seed=3,
                        churn=churn, recovery=cfg.recovery,
                        track_intervals=True)
    apps, times = _make_workload(cfg)
    orch.submit_batch(apps, times)
    orch.drain()
    assert orch.pending_events == 0
    rebuilt = _rebuild_alloc(mk, orch.engine.executed)
    assert np.array_equal(np.asarray(cluster.alloc), rebuilt)


# ----------------------------------------------------- recovery semantics --
def test_recovery_registry():
    assert {"fail_fast", "failover", "replan"} <= set(available_recoveries())
    with pytest.raises(ValueError, match="unknown recovery"):
        make_recovery("nope")
    r = make_recovery("failover", detection_delay=0.5, max_retries=3)
    assert (r.detection_delay, r.max_retries) == (0.5, 3)


def _run_recovery(profile, recovery, scheme="random",
                  cfg=None) -> tuple:
    cfg = cfg or SimConfig(scenario="churn", n_cycles=2,
                           instances_per_cycle=120, seed=3)
    cluster = make_cluster(profile, scenario="churn", n_devices=100,
                           seed=3, horizon=cfg.horizon + 30.0)
    churn = exponential_churn(cluster, horizon=cfg.horizon + 25.0, seed=104)
    orch = Orchestrator(cluster, policy_for(scheme, profile, cfg), seed=3,
                        churn=churn, recovery=recovery)
    apps, times = _make_workload(cfg)
    orch.submit_batch(apps, times)
    orch.drain()
    return orch.result("churn", cfg.horizon), orch.stats


def test_failover_and_replan_reduce_failures(profile):
    """The acceptance scenario: same fleet, same churn, same workload —
    failover and replan each strictly reduce P_f vs fail_fast."""
    ff, s_ff = _run_recovery(profile, "fail_fast")
    fo, s_fo = _run_recovery(profile, "failover")
    rp, s_rp = _run_recovery(profile, "replan")
    assert s_ff["lost"] > 0                        # churn actually bites
    assert fo.prob_failure < ff.prob_failure
    assert rp.prob_failure < ff.prob_failure
    assert s_fo["task_failovers"] > 0 and s_fo["recovered"] > 0
    assert s_rp["replans"] > 0 and s_rp["recovered"] > 0


def test_failover_retry_lands_on_live_device(profile):
    """The failover replica goes to a surviving device and completes."""
    cluster = small_cluster(base=[0.3, 0.35, 0.4, 0.45], lam=1e-4)
    churn = deterministic_churn([(0.1, 0, "leave")])
    eng = Engine(cluster, make_policy("lavea"), noise_sigma=0.0, churn=churn,
                 recovery=make_recovery("failover", detection_delay=0.05))
    eng.add_arrivals([one_task_app()], [0.0])
    eng.drain()
    rec = eng.records[0]
    assert not rec.failed
    assert eng.stats["task_failovers"] == 1
    assert eng.stats["recovered"] == 1
    # the task's recorded home moved off the dead device
    assert eng.load[0] == 1 and eng.load[1:].sum() == 1


def test_replan_repaints_downstream_stages(profile):
    """replan re-places the dead task AND the not-yet-started downstream
    stage on the survivors, through the pure pinned-orchestrate path."""
    app = AppDAG.from_tasks("chain", [
        TaskSpec("a", ttype=0, out_bytes=1 * MB),
        TaskSpec("b", ttype=0, deps=("a",)),
    ])
    cluster = small_cluster(base=[0.3, 0.32, 0.34, 0.36], lam=1e-4)
    churn = deterministic_churn([(0.1, 0, "leave")])
    eng = Engine(cluster, make_policy("lavea"), noise_sigma=0.0, churn=churn,
                 recovery=make_recovery("replan", detection_delay=0.05),
                 track_intervals=True)
    eng.add_arrivals([app], [0.0])
    eng.drain()
    rec = eng.records[0]
    assert not rec.failed
    assert eng.stats["replans"] == 1
    assert eng.load[0] == 1                        # only a's first attempt
    # post-run occupancy equals the executed work exactly (no ghost from
    # the replaced provisional intervals)
    mk = lambda: small_cluster(base=[0.3, 0.32, 0.34, 0.36], lam=1e-4)
    assert np.array_equal(
        np.asarray(cluster.alloc), _rebuild_alloc(mk, eng.executed)
    )


def test_no_survivor_means_lost(profile):
    cluster = small_cluster(n=2, base=[0.3, 0.35], lam=1e-4)
    churn = deterministic_churn([(0.1, 0, "leave"), (0.12, 1, "leave")])
    eng = Engine(cluster, make_policy("lavea"), noise_sigma=0.0, churn=churn,
                 recovery=make_recovery("failover", detection_delay=0.05))
    eng.add_arrivals([one_task_app()], [0.0])
    eng.drain()
    assert eng.records[0].failed
    assert eng.stats["lost"] == 1


# ------------------------------------------------------- property testing --
@settings(max_examples=20, deadline=None, derandomize=True)
@given(
    deaths=st.lists(
        st.tuples(
            st.floats(min_value=0.05, max_value=24.0),
            st.integers(min_value=0, max_value=3),
            st.one_of(st.none(), st.floats(min_value=0.3, max_value=4.0)),
        ),
        min_size=1, max_size=6,
    )
)
def test_recovery_never_loses_more_than_fail_fast(deaths):
    """Under ANY churn schedule, failover and replan never lose more
    instances than fail_fast.  Arrivals are spaced wider than any app's
    lifetime so instances are independent — recovery work for one cannot
    perturb another."""
    events = []
    for t, did, rejoin_after in deaths:
        events.append((t, did, "leave"))
        if rejoin_after is not None:
            events.append((t + rejoin_after, did, "join"))
    schedule = deterministic_churn(events)
    app = AppDAG.from_tasks("chain", [
        TaskSpec("a", ttype=0, out_bytes=1 * MB),
        TaskSpec("b", ttype=0, deps=("a",)),
    ])
    apps = [app.relabel(f"#{i}") for i in range(5)]
    times = [5.0 * i for i in range(5)]           # isolation spacing
    lost = {}
    for recovery in ("fail_fast", "failover", "replan"):
        cluster = small_cluster(base=[0.3, 0.32, 0.34, 0.36], lam=1e-4)
        eng = Engine(cluster, make_policy("lavea"), noise_sigma=0.0,
                     churn=ChurnSchedule(schedule.events),
                     recovery=make_recovery(recovery, detection_delay=0.1))
        eng.add_arrivals(apps, times)
        eng.drain()
        lost[recovery] = sum(r.failed for r in eng.records)
        # occupancy sums to zero beyond the final event in every mode
        assert float(np.abs(cluster.alloc[:, :, cluster.bucket(60.0):]).max()) == 0.0
    assert lost["failover"] <= lost["fail_fast"]
    assert lost["replan"] <= lost["fail_fast"]


# -------------------------------------------------- trace / monitor wiring --
def test_trace_churn_replay():
    sched = trace_churn([
        (1.0, 0, False), (2.0, 0, True), (3.0, 0, False),
        (0.5, 1, True), (4.0, 1, False),
    ])
    kinds = [(e.t, e.did, e.kind) for e in sched.events]
    assert kinds == [
        (1.0, 0, "leave"), (2.0, 0, "join"), (3.0, 0, "leave"),
        (4.0, 1, "leave"),
    ]
    # the join is re-armed with the device's next departure
    join = [e for e in sched.events if e.kind == "join"][0]
    assert join.until == 3.0
    assert sched.first_leave(0) == 1.0 and sched.first_leave(2) == np.inf


def test_install_makes_schedule_own_lifetimes():
    cluster = small_cluster(alive_until=[50.0, 50.0, 50.0, 50.0])
    deterministic_churn([(7.0, 2, "leave")]).install(cluster)
    au = [d.alive_until for d in cluster.devices]
    assert au == [np.inf, np.inf, 7.0, np.inf]
    assert cluster.alive_mask(8.0).tolist() == [True, True, False, True]


def test_monitor_lam_is_the_shared_mle():
    """FleetMonitor's online estimate == fit_failure_rate on the same
    exposure/death ledger."""
    from repro.core.availability import fit_failure_rate

    mon = FleetMonitor(timeout=2.0)
    for pid in ("p0", "p1", "p2", "p3"):
        mon.join(pid, cls="spot", now=0.0)
    for t in range(1, 11):
        for pid in ("p0", "p1"):
            mon.heartbeat(pid, now=float(t))
    # p2/p3 never heartbeat again -> dead on sweep
    mon.sweep(now=10.0)
    assert mon.lam("spot") == pytest.approx(
        fit_failure_rate([20.0, 0.0, 0.0], [True, False, False])
    )
    assert mon.lam("spot") == pytest.approx(2 / 20.0)


def test_churn_from_monitor_end_to_end(profile):
    """Satellite-6: the monitor's fitted rates drive the churn generator —
    a flaky-observed fleet produces a dense schedule, a solid-observed one
    produces none."""
    flaky, solid = FleetMonitor(timeout=2.0), FleetMonitor(timeout=2.0)
    for mon, keep in ((flaky, 1), (solid, 40)):
        for i in range(40):
            mon.join(f"p{i}", cls="0", now=0.0)
        for t in range(1, 6):
            for i in range(keep):
                mon.heartbeat(f"p{i}", now=float(t))
        mon.sweep(now=5.0)
    cluster = small_cluster(n=4, lam=1e-6)
    for d in cluster.devices:
        d.cls = 0                                   # one monitor class
    cluster.refresh_topology()
    dense = churn_from_monitor(flaky, cluster, horizon=100.0, seed=1)
    sparse = churn_from_monitor(solid, cluster, horizon=100.0, seed=1)
    assert flaky.lam("0") > solid.lam("0")
    assert dense.n_events > sparse.n_events
    # and the schedule slots straight into the engine
    eng = Engine(cluster, make_policy("lavea"), churn=dense,
                 recovery="failover")
    eng.add_arrivals([one_task_app()], [0.0])
    eng.drain()
    assert len(eng.records) == 1


# ------------------------------------------------------------ end-to-end --
def test_simconfig_churn_replan_end_to_end(profile):
    """The acceptance smoke: SimConfig(scenario="churn", recovery="replan")
    runs through run_one unmodified."""
    cfg = SimConfig(scenario="churn", recovery="replan", n_cycles=2,
                    instances_per_cycle=60, seed=3, n_devices=32)
    res = run_one("ibdash", cfg, profile)
    assert res.n == 120
    assert all(r.failed or np.isfinite(r.service_time) for r in res.instances)


def test_serving_fleet_churn_replan(profile):
    """Replica preemption in the serving fleet: replan re-shards in-flight
    requests onto surviving replicas and loses no more than fail_fast."""
    from repro.serve.scheduler import ServingFleet, serving_interference_model

    interference = serving_interference_model()
    results = {}
    for recovery in ("fail_fast", "replan"):
        fleet = ServingFleet(
            interference, n_replicas=8, seed=0, horizon=60.0,
            lams=(1e-5, 2e-2),                     # very flaky spot pool
            churn=True, recovery=recovery, detection_delay=0.05,
        )
        res = fleet.run(n_requests=120, arrival_window=30.0, seed=1)
        results[recovery] = (res.prob_failure, fleet.orchestrator.stats)
    pf_ff, stats_ff = results["fail_fast"]
    pf_rp, stats_rp = results["replan"]
    assert stats_ff["device_down"] > 0
    assert pf_rp <= pf_ff
    if stats_ff["lost"] > 0:
        assert stats_rp["replans"] > 0



# ------------------------------------------- a recovery owns its placement --
def _fused_one_task_plans(cluster, n):
    """``n`` one-task apps planned as one fused wave at t = 0 (all on the
    fastest device: one snapshot)."""
    from repro.api import orchestrate_batch

    plans = orchestrate_batch([one_task_app(f"app{i}") for i in range(n)],
                              cluster, make_policy("lavea"), times=[0.0] * n)
    assert {p.tasks["t0"].replicas[0].did for p in plans} == {0}
    return plans


@pytest.mark.parametrize("recovery", ("failover", "replan"))
def test_recovery_leaves_the_arrival_plan_as_planned(recovery):
    """A recovery rewrites the run's own copy of its task map: the plans
    a fused wave handed to the engine still say what was planned (the
    caller may read them after the window), while the runs move to a
    live device and complete."""
    cluster = small_cluster(base=[0.3, 0.32, 0.34, 0.36], lam=1e-4)
    churn = deterministic_churn([(0.1, 0, "leave")])
    eng = Engine(cluster, make_policy("lavea"), noise_sigma=0.0, churn=churn,
                 recovery=make_recovery(recovery, detection_delay=0.05))
    plans = _fused_one_task_plans(cluster, 3)
    planned = [p.tasks["t0"] for p in plans]
    runs, real = [], eng.recovery.recover

    def recover(engine, run, tname):
        runs.append(run)
        return real(engine, run, tname)

    eng.recovery.recover = recover
    eng.add_arrivals([p.app for p in plans], [0.0] * 3, plans=plans)
    eng.run(until=5.0)
    assert eng.stats["replica_deaths"] == 3 and len(runs) == 3
    assert [p.tasks["t0"] for p in plans] == planned
    assert all(tp.replicas[0].did == 0 for tp in planned)
    assert all(r.placement.tasks["t0"].replicas[0].did != 0 for r in runs)
    assert all(r.done["t0"] and not r.failed for r in runs)
