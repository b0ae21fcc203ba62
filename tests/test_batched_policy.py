"""Batched placement API: FleetSnapshot, decide_batch parity with the
scalar path for all six policies, the fused orchestrate_batch wave planner,
the baseline empty-feasible guards, and the T_alloc horizon clip."""
import warnings

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.api import (
    BatchedDecision,
    BatchedPolicyContext,
    FleetSnapshot,
    Orchestrator,
    make_policy,
    orchestrate,
    orchestrate_batch,
)
from repro.core.batched import BATCH_KERNEL_MIN_ROWS
from repro.core.cluster import ClusterState, Device
from repro.core.dag import AppDAG, TaskSpec
from repro.core.interference import InterferenceModel
from repro.core.policy import LaTSModel, Policy, PolicyContext, TaskDecision
from repro.sim import SimConfig, make_cluster, make_profile
from repro.sim.runner import SCHEME_NAMES, _make_workload, policy_for

GB = 1e9
MB = 1e6


@pytest.fixture(scope="module")
def profile():
    return make_profile(seed=0)


def small_cluster(n=6, n_types=2, lam=5e-2, mem=8 * GB, bw=100e6, seed=0):
    rng = np.random.default_rng(seed)
    model = InterferenceModel(
        base=rng.uniform(0.05, 0.5, (n, n_types)),
        slope=rng.uniform(0.01, 0.08, (n, n_types, n_types)),
    )
    devices = [
        Device(did=i, cls=i, mem_total=mem, lam=lam, up_bw=bw, down_bw=bw)
        for i in range(n)
    ]
    return ClusterState(devices=devices, model=model, horizon=120.0, dt=0.05)


def small_lats(n_classes=16, n_types=2, seed=0):
    rng = np.random.default_rng(seed)
    return LaTSModel(
        base=rng.uniform(0.05, 0.5, (n_classes, n_types)),
        b=rng.uniform(0.1, 0.6, n_classes),
        cpu_usage=rng.uniform(0.1, 0.6, (n_classes, n_types)),
    )


def random_apps(rng, n_apps, n_types=2):
    apps = []
    for i in range(n_apps):
        n_tasks = int(rng.integers(1, 6))
        tasks = []
        for j in range(n_tasks):
            deps = tuple(
                f"t{k}#{i}" for k in range(j) if rng.random() < 0.4
            )
            tasks.append(TaskSpec(
                f"t{j}#{i}",
                ttype=int(rng.integers(n_types)),
                deps=deps,
                out_bytes=float(rng.uniform(0, 20e6)),
                model_id=f"m{int(rng.integers(2))}" if rng.random() < 0.4 else None,
                model_bytes=float(rng.uniform(10e6, 200e6)),
                mem_bytes=float(rng.uniform(0, 1 * GB)),
            ))
        apps.append(AppDAG.from_tasks(f"app{i}", tasks))
    return apps


def fresh_policies(name, seed=0):
    """Two identically-constructed instances (same rng stream / cursor)."""
    kw = dict(seed=seed, alpha=0.4, beta=0.08, gamma=3,
              lats_model=small_lats())
    return make_policy(name, **kw), make_policy(name, **kw)


def same_placement(a, b):
    assert a.feasible == b.feasible
    assert a.infeasible_task == b.infeasible_task
    assert a.est_latency == b.est_latency
    assert set(a.tasks) == set(b.tasks)
    for k in a.tasks:
        ta, tb = a.tasks[k], b.tasks[k]
        assert [r.did for r in ta.replicas] == [r.did for r in tb.replicas]
        assert ta.est_start == tb.est_start
        assert ta.est_latency == tb.est_latency
        for ra, rb in zip(ta.replicas, tb.replicas):
            assert ra.est_exec == rb.est_exec
            assert ra.est_upload == rb.est_upload
            assert ra.est_transfer == rb.est_transfer
            assert ra.pred_fail == rb.pred_fail


# the paper's six plus the forecast-aware IBDASH variant: with no forecast
# installed (every fixture here) churn_aware must ride every parity rail
# bit-identically, and its batched/scalar twins must agree like the rest
ALL_SCHEMES = SCHEME_NAMES + ("churn_aware",)


# ---------------------------------------------------------- fleet snapshot --
def test_fleet_snapshot_shapes_and_values():
    cluster = small_cluster(n=5, n_types=2)
    cluster.add_interval(2, 1, 0.0, 10.0, w=3)
    snap = cluster.snapshot(1.0)
    assert isinstance(snap, FleetSnapshot)
    assert snap.n_devices == 5 and snap.n_types == 2
    assert snap.counts.shape == (5, 2)
    assert snap.counts[2, 1] == 3.0
    assert snap.queue_len[2] == 3.0
    assert np.array_equal(snap.classes, cluster.classes())
    assert np.array_equal(snap.base, cluster.model.base)


def test_fleet_snapshot_is_a_pytree():
    import jax

    from repro.core.batched import _jax

    _jax()  # registers the pytree nodes
    snap = small_cluster(n=3).snapshot(0.0)
    leaves, treedef = jax.tree_util.tree_flatten(snap)
    # + tiers (PR 3), alive (PR 4), surv_grid + survival (PR 5); PR 10
    # factorized the dense link_bw leaf into up_bw + down_bw + backhaul
    assert len(leaves) == 17
    again = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(again, FleetSnapshot)
    assert np.array_equal(again.lams, snap.lams)
    # with no forecast installed the survival leaves are the uniform tensor
    assert snap.surv_grid.shape == (1,)
    assert snap.survival.shape == (3, 1) and (snap.survival == 1.0).all()


# ------------------------------------------------- decide_batch == decide --
@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_decide_batch_matches_looped_decide_on_wave(scheme):
    """decide_batch over a multi-app wave == decide over the same rows in
    order, for every registered policy (exact, including rng streams)."""
    rng = np.random.default_rng(3)
    cluster = small_cluster(n=8, seed=3)
    apps = random_apps(rng, 12)
    pol_b, pol_s = fresh_policies(scheme, seed=7)
    plans_b = orchestrate_batch(apps, cluster, pol_b, batched=True)
    plans_s = orchestrate_batch(apps, cluster, pol_s, batched=False)
    for a, b in zip(plans_b, plans_s):
        same_placement(a.placement, b.placement)


@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_sequential_orchestrate_batched_vs_scalar(scheme, profile):
    """orchestrate(batched=True) == orchestrate(batched=False) arrival by
    arrival on the seeded (miniaturised) Fig. 8/9 grid, with applies in
    between — T_alloc evolution included."""
    cfg = SimConfig(n_cycles=1, instances_per_cycle=50, scenario="mix",
                    seed=0, n_devices=24)
    apps, times = _make_workload(cfg)
    mk = lambda: make_cluster(profile, scenario=cfg.scenario,
                              n_devices=cfg.n_devices, seed=cfg.seed,
                              horizon=cfg.horizon + 30.0)
    c_b, c_s = mk(), mk()
    pol_b = policy_for(scheme, profile, cfg)
    pol_s = policy_for(scheme, profile, cfg)
    for app, t in zip(apps, times):
        pb = orchestrate(app, c_b, t, pol_b, batched=True)
        ps = orchestrate(app, c_s, t, pol_s, batched=False)
        same_placement(pb.placement, ps.placement)
        c_b.apply(pb)
        c_s.apply(ps)
    assert np.array_equal(c_b.alloc, c_s.alloc)


@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_wave_parity_on_seeded_grid(scheme, profile):
    """The fused orchestrate_batch wave == the scalar row loop on the
    seeded Fig. 8/9 grid workload (one shared snapshot, B ~ hundreds of
    rows, so the jitted kernels are exercised)."""
    cfg = SimConfig(n_cycles=1, instances_per_cycle=60, scenario="mix",
                    seed=0, n_devices=24)
    apps, times = _make_workload(cfg)
    cluster = make_cluster(profile, scenario=cfg.scenario,
                           n_devices=cfg.n_devices, seed=cfg.seed,
                           horizon=cfg.horizon + 30.0)
    pol_b = policy_for(scheme, profile, cfg)
    pol_s = policy_for(scheme, profile, cfg)
    plans_b = orchestrate_batch(apps, cluster, pol_b, times=times)
    plans_s = orchestrate_batch(apps, cluster, pol_s, times=times,
                                batched=False)
    for a, b in zip(plans_b, plans_s):
        same_placement(a.placement, b.placement)


@pytest.mark.parametrize("scheme", ("ibdash", "lavea"))
def test_wave_equals_looped_orchestrate_for_stateless(scheme, profile):
    """For stateless policies a fused wave also equals looping pure
    orchestrate per app (no intermediate applies)."""
    cfg = SimConfig(n_cycles=1, instances_per_cycle=40, scenario="ped",
                    seed=1, n_devices=16)
    apps, times = _make_workload(cfg)
    cluster = make_cluster(profile, scenario=cfg.scenario,
                           n_devices=cfg.n_devices, seed=cfg.seed,
                           horizon=cfg.horizon + 30.0)
    pol = policy_for(scheme, profile, cfg)
    plans_b = orchestrate_batch(apps, cluster, pol, times=times)
    plans_l = [orchestrate(app, cluster, t, pol)
               for app, t in zip(apps, times)]
    for a, b in zip(plans_b, plans_l):
        same_placement(a.placement, b.placement)


@pytest.mark.parametrize("uniform_forecast", (False, True))
def test_churn_aware_seed_parity_with_ibdash(profile, uniform_forecast):
    """Satellite-1 seed parity: with no forecast installed — or the uniform
    all-ones forecast — churn_aware's placements equal registry ibdash
    BIT-identically on the seeded Fig. 8/9 grid (the PR-4 placements), with
    applies in between so the T_alloc evolution is pinned too."""
    from repro.core.availability import SurvivalForecast

    cfg = SimConfig(n_cycles=1, instances_per_cycle=50, scenario="mix",
                    seed=0, n_devices=24)
    apps, times = _make_workload(cfg)
    mk = lambda: make_cluster(profile, scenario=cfg.scenario,
                              n_devices=cfg.n_devices, seed=cfg.seed,
                              horizon=cfg.horizon + 30.0)
    c_ib, c_ca = mk(), mk()
    if uniform_forecast:
        # all-ones survival: zero stochastic hazard, nothing scripted
        c_ca.install_forecast(SurvivalForecast.from_rates([0.0] * 24))
    pol_ib = policy_for("ibdash", profile, cfg)
    pol_ca = policy_for("churn_aware", profile, cfg)
    for app, t in zip(apps, times):
        p_ib = orchestrate(app, c_ib, t, pol_ib)
        p_ca = orchestrate(app, c_ca, t, pol_ca)
        same_placement(p_ib.placement, p_ca.placement)
        c_ib.apply(p_ib)
        c_ca.apply(p_ca)
    assert np.array_equal(c_ib.alloc, c_ca.alloc)


def test_round_robin_batch_continues_cursor():
    """The batched cursor picks up exactly where scalar decides left off,
    and advances once per non-empty row."""
    cluster = small_cluster(n=4, n_types=1)
    app = AppDAG.from_tasks("a", [TaskSpec(f"t{i}", ttype=0)
                                  for i in range(6)])
    rr_b, rr_s = fresh_policies("round_robin")
    # advance both cursors by 3 via the scalar path
    warm = AppDAG.from_tasks("w", [TaskSpec("w0", ttype=0),
                                   TaskSpec("w1", ttype=0),
                                   TaskSpec("w2", ttype=0)])
    orchestrate(warm, cluster, 0.0, rr_b, batched=False)
    orchestrate(warm, cluster, 0.0, rr_s, batched=False)
    pb = orchestrate(app, cluster, 0.0, rr_b, batched=True)
    ps = orchestrate(app, cluster, 0.0, rr_s, batched=False)
    same_placement(pb.placement, ps.placement)
    dids = [pb.tasks[f"t{i}"].replicas[0].did for i in range(6)]
    assert dids == [3, 0, 1, 2, 3, 0]                  # cursor started at 3


def test_custom_policy_default_decide_batch_fallback():
    """A user policy with only decide() rides the batched orchestrate path
    through the row() bridge unchanged."""
    class Second(Policy):
        name = "second"

        def decide(self, ctx: PolicyContext) -> TaskDecision:
            ids = ctx.feasible_ids
            order = ids[np.argsort(ctx.total[ids], kind="stable")]
            return TaskDecision(devices=(int(order[min(1, order.size - 1)]),))

    cluster = small_cluster(n=5, n_types=1)
    apps = random_apps(np.random.default_rng(0), 6, n_types=1)
    plans_b = orchestrate_batch(apps, cluster, Second(), batched=True)
    plans_s = orchestrate_batch(apps, cluster, Second(), batched=False)
    for a, b in zip(plans_b, plans_s):
        same_placement(a.placement, b.placement)


def test_batch_kernel_path_used_for_big_pools(monkeypatch):
    """Sanity: pools >= BATCH_KERNEL_MIN_ROWS reach the fused jax kernel
    (guard against silently always taking the scalar fallback)."""
    from repro.core import batched as bt

    calls = []
    orig = bt.ibdash_decide_batch

    def spy(*a, **kw):
        calls.append(a[0].shape)
        return orig(*a, **kw)

    monkeypatch.setattr("repro.core.policy.ibdash_decide_batch", spy)
    rng = np.random.default_rng(5)
    cluster = small_cluster(n=8, seed=5)
    # many single-task apps with distinct mem footprints -> distinct pool rows
    apps = [AppDAG.from_tasks(f"a{i}", [TaskSpec(
        f"t#{i}", ttype=0, mem_bytes=float(i) * MB)])
        for i in range(BATCH_KERNEL_MIN_ROWS + 4)]
    orchestrate_batch(apps, cluster, make_policy("ibdash"))
    assert calls and calls[0][0] >= BATCH_KERNEL_MIN_ROWS


# ------------------------------------------------------ property (random) --
@st.composite
def parity_cases(draw):
    return dict(
        seed=draw(st.integers(min_value=0, max_value=10_000)),
        n_devices=draw(st.integers(min_value=1, max_value=10)),
        n_apps=draw(st.integers(min_value=1, max_value=10)),
        scheme=draw(st.sampled_from(ALL_SCHEMES)),
    )


@given(parity_cases())
@settings(max_examples=60, deadline=None)
def test_property_decide_batch_parity_random_fleets(case):
    """Property: batched == scalar over random fleets / DAGs / seeds for
    every registered policy, including the stateful round_robin cursor and
    the seeded random/petrel/lats draws."""
    rng = np.random.default_rng(case["seed"])
    cluster = small_cluster(n=case["n_devices"], seed=case["seed"],
                            lam=float(rng.uniform(1e-4, 0.5)))
    apps = random_apps(rng, case["n_apps"])
    pol_b, pol_s = fresh_policies(case["scheme"], seed=case["seed"])
    times = list(rng.uniform(0.0, 2.0, len(apps)))
    plans_b = orchestrate_batch(apps, cluster, pol_b, times=times)
    plans_s = orchestrate_batch(apps, cluster, pol_s, times=times,
                                batched=False)
    for a, b in zip(plans_b, plans_s):
        same_placement(a.placement, b.placement)


# ------------------------------------------- baseline empty-feasible guard --
def empty_feasible_ctx(n=4):
    z = np.zeros(n)
    return PolicyContext(
        task="t", ttype=0, t_start=0.0, stage_offset=0.0,
        exec_lat=z + 0.1, upload=z, transfer=z, total=z + 0.1,
        feasible=np.zeros(n, dtype=bool), feasible_ids=np.array([], dtype=int),
        pf=z + 0.5, lams=z + 1e-3, join_times=z, queue_len=z,
        counts=np.zeros((n, 1)), classes=np.arange(n),
    )


@pytest.mark.parametrize("scheme", ALL_SCHEMES)
def test_policies_return_empty_decision_on_empty_feasible(scheme):
    pol, _ = fresh_policies(scheme)
    decision = pol.decide(empty_feasible_ctx())
    assert decision.devices == ()


def test_orchestrator_marks_plan_infeasible_not_crash():
    """End to end: a task too big for every device yields an infeasible
    plan under every scheme (the seed crashed inside the baselines)."""
    cluster = small_cluster(n=3, mem=1 * GB)
    app = AppDAG.from_tasks("big", [
        TaskSpec("ok", ttype=0),
        TaskSpec("huge", ttype=0, mem_bytes=5 * GB),
    ])
    for scheme in ALL_SCHEMES:
        pol, _ = fresh_policies(scheme)
        plan = orchestrate(app, cluster, 0.0, pol)
        assert not plan.feasible
        assert plan.placement.infeasible_task == "huge"
        assert "ok" in plan.placement.tasks      # earlier task still placed


def test_shared_model_id_with_different_sizes_not_conflated():
    """Two tasks sharing a model_id but disagreeing on its size must get
    their own upload latencies (the wave builder caches upload vectors per
    (model, size), not per model)."""
    cluster = small_cluster(n=2, n_types=1, bw=100 * MB)
    app = AppDAG.from_tasks("a", [
        TaskSpec("small", ttype=0, model_id="m", model_bytes=100 * MB),
        TaskSpec("big", ttype=0, model_id="m", model_bytes=400 * MB),
    ])
    plan = orchestrate(app, cluster, 0.0, make_policy("lavea"))
    assert plan.tasks["small"].replicas[0].est_upload == pytest.approx(1.0)
    assert plan.tasks["big"].replicas[0].est_upload == pytest.approx(4.0)


# ------------------------------------------------------ horizon clip fix --
def test_add_interval_clips_at_horizon_and_warns_once():
    cluster = small_cluster(n=2, n_types=1)
    h = cluster.horizon
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        cluster.add_interval(0, 0, h - 1.0, h + 50.0)     # clipped
        cluster.add_interval(0, 0, h + 10.0, h + 20.0)    # fully past: no-op
    assert len(caught) == 1                               # warned exactly once
    assert issubclass(caught[0].category, RuntimeWarning)
    # occupancy exists inside the horizon...
    assert cluster.counts_at(h - 0.5)[0, 0] == 1
    # ...but did NOT pile up in the final bucket beyond the single task
    assert cluster.alloc[0, 0, -1] <= 1
    # and the fully-past-horizon interval left no trace anywhere
    assert cluster.alloc[1].sum() == 0
    assert cluster.alloc[0, 0].sum() <= (1.0 / cluster.dt) + 2


def test_add_interval_clip_is_undo_symmetric():
    cluster = small_cluster(n=2, n_types=1)
    h = cluster.horizon
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        cluster.add_interval(1, 0, h - 2.0, h + 30.0, w=1.0)
        cluster.add_interval(1, 0, h - 2.0, h + 30.0, w=-1.0)
        cluster.add_interval(1, 0, h + 5.0, h + 9.0, w=1.0)
        cluster.add_interval(1, 0, h + 5.0, h + 9.0, w=-1.0)
    assert (cluster.alloc == 0).all()


def test_late_horizon_estimates_not_corrupted():
    """Occupancy far past the horizon must not inflate Eq. (1) estimates at
    the horizon edge (the seed piled every late interval into the last
    bucket)."""
    cluster = small_cluster(n=2, n_types=1)
    h = cluster.horizon
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for _ in range(50):
            cluster.add_interval(0, 0, h + 1.0, h + 2.0)
    assert cluster.counts_at(h)[0, 0] == 0


# ----------------------------------------------------------- fused submit --
def test_fused_submit_batch_end_to_end(profile):
    cfg = SimConfig(n_cycles=1, instances_per_cycle=40, scenario="mix",
                    seed=2, n_devices=16)
    apps, times = _make_workload(cfg)
    cluster = make_cluster(profile, scenario=cfg.scenario,
                           n_devices=cfg.n_devices, seed=cfg.seed,
                           horizon=cfg.horizon + 30.0)
    orch = Orchestrator(cluster, "ibdash", seed=cfg.seed)
    orch.submit_batch(apps, times, fused=True)
    orch.drain()
    res = orch.result("mix", horizon=cfg.horizon)
    assert res.n == len(apps)
    assert all(np.isfinite(r.finished) for r in res.instances)
    assert res.prob_failure < 1.0


def test_fused_run_one_matches_instance_count(profile):
    """fused_burst plans one wave per cycle (cycle-start snapshot), and
    every instance across multiple cycles still resolves."""
    from repro.sim import run_one

    cfg = SimConfig(n_cycles=2, instances_per_cycle=30, scenario="ped",
                    seed=4, n_devices=16, fused_burst=True)
    res = run_one("ibdash", cfg, profile)
    assert res.n == 60
    assert all(r.failed or np.isfinite(r.service_time) for r in res.instances)
    assert all(np.isfinite(r.finished) for r in res.instances)


def test_fused_plans_share_snapshot(profile):
    """Fused plans are computed against one snapshot: identical app
    instances arriving at the same instant get identical placements under a
    stateless policy."""
    cfg = SimConfig(n_devices=12, seed=0)
    cluster = make_cluster(profile, scenario="mix", n_devices=12, seed=0)
    from repro.sim.apps import lightgbm_app

    apps = [lightgbm_app().relabel(f"#{i}") for i in range(5)]
    plans = orchestrate_batch(apps, cluster, policy_for("ibdash", profile, cfg))
    first = [(r.did for r in tp.replicas) for tp in plans[0].tasks.values()]
    for plan in plans[1:]:
        for (k0, tp0), (k1, tp1) in zip(plans[0].tasks.items(),
                                        plan.tasks.items()):
            assert [r.did for r in tp0.replicas] == [r.did for r in tp1.replicas]
