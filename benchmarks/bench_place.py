"""Burst-placement throughput: scalar per-task loop vs one fused batched
call (the PR-2 batched placement API).

Plans B application instances arriving simultaneously on the paper's
100-device mix fleet with IBDASH, through both paths:

  * scalar  — ``orchestrate(app, ..., batched=False)`` per instance: the
    PR-1 per-task ``decide(ctx)`` loop.
  * batched — ``orchestrate_batch(apps, ...)``: one deduplicated
    ``BatchedPolicyContext`` + one fused ``decide_batch`` call per
    wave-stage.

Both paths are pure planning against the same snapshot and are bit-identical
(asserted here on every run).  A second section runs the asymmetric 3-tier
``multi_tier`` fleet with the ``tier_escalation`` policy, so the report also
records placement throughput under the tier-aware bottleneck-link cost
model.  A third section sweeps FLEET SIZE (1k / 10k / 100k devices) over
the factorized snapshot path with the dense ``(D, D)`` accessor tripwired —
reintroducing the dense matrix anywhere in wave planning fails the bench
outright rather than just slowing it.  Writes ``BENCH_place.json`` with
placements/sec at B ∈ {1, 64, 1000} plus the fleet-sweep columns;
``--check BASELINE.json`` exits non-zero on a >2x regression of the
batched-vs-scalar speedup ratio, a missing/failed fleet-sweep point, or a
>3x regression of the sweep's 1k/100k throughput-scaling ratio against the
committed baseline (used by CI; ratios are gated rather than absolute
throughput so the check is portable across runner hardware).

    PYTHONPATH=src python -m benchmarks.bench_place \
        [--out BENCH_place.json] [--check benchmarks/BENCH_place.baseline.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.compile_cache import enable_compile_cache  # noqa: E402

BATCH_SIZES = (1, 64, 1000)
REGRESSION_FACTOR = 2.0
FLEET_SIZES = (1_000, 10_000, 100_000)
# the fleet sweep gates the SHAPE of the scaling curve (pps@1k / pps@100k),
# which is hardware-portable but noisier than the single-fleet speedup ratio
SWEEP_REGRESSION_FACTOR = 3.0


def _workload(B: int, seed: int = 1):
    from repro.sim.apps import APP_BUILDERS

    builders = list(APP_BUILDERS.values())
    rng = np.random.default_rng(seed)
    return [
        builders[int(rng.integers(len(builders)))]().relabel(f"#{i}")
        for i in range(B)
    ]


def first_plan_difference(plans_a, plans_b):
    """``None`` when the two plan lists place every task on the same
    replicas with the same feasibility and estimated latency; otherwise a
    description of the first task (or plan) that differs, with each side's
    replicas as ``(device, est_total, pf)``."""
    if len(plans_a) != len(plans_b):
        return f"{len(plans_a)} plans vs {len(plans_b)}"
    for i, (a, b) in enumerate(zip(plans_a, plans_b)):
        pa, pb = a.placement, b.placement
        for k, tp in pa.tasks.items():
            other = pb.tasks.get(k)
            reps_a = [(r.did, r.est_total, r.pred_fail) for r in tp.replicas]
            reps_b = (None if other is None else
                      [(r.did, r.est_total, r.pred_fail) for r in other.replicas])
            if reps_a != reps_b:
                return f"plan {i} ({pa.app_name}) task {k}: {reps_a} vs {reps_b}"
        if (pa.feasible, pa.est_latency, len(pa.tasks)) != (
                pb.feasible, pb.est_latency, len(pb.tasks)):
            return (f"plan {i} ({pa.app_name}): feasible/est_latency/tasks "
                    f"{pa.feasible}/{pa.est_latency}/{len(pa.tasks)} vs "
                    f"{pb.feasible}/{pb.est_latency}/{len(pb.tasks)}")
    return None


def _same_plans(plans_a, plans_b) -> None:
    diff = first_plan_difference(plans_a, plans_b)
    assert diff is None, diff


def measure(
    scheme: str = "ibdash",
    n_devices: int = 100,
    seed: int = 0,
    scenario: str = "mix",
    latency_budget: float = float("inf"),
):
    from repro.api import orchestrate, orchestrate_batch
    from repro.sim import SimConfig, make_cluster, make_profile
    from repro.sim.runner import policy_for

    cfg = SimConfig(seed=seed, latency_budget=latency_budget)
    profile = make_profile(seed=seed)
    cluster = make_cluster(
        profile, scenario=scenario, n_devices=n_devices, seed=seed,
        horizon=400.0,
    )
    results = {}
    for B in BATCH_SIZES:
        apps = _workload(B)
        # warm up the jitted kernels at this wave shape, and assert parity
        pol = policy_for(scheme, profile, cfg)
        plans_b = orchestrate_batch(apps, cluster, pol)
        pol = policy_for(scheme, profile, cfg)
        _same_plans(
            plans_b,
            [orchestrate(app, cluster, 0.0, pol, batched=False) for app in apps],
        )

        reps = max(1, 2000 // B)
        pol = policy_for(scheme, profile, cfg)
        t0 = time.perf_counter()
        for _ in range(reps):
            orchestrate_batch(apps, cluster, pol)
        batched_s = (time.perf_counter() - t0) / reps

        pol = policy_for(scheme, profile, cfg)
        t0 = time.perf_counter()
        for _ in range(reps):
            for app in apps:
                orchestrate(app, cluster, 0.0, pol, batched=False)
        scalar_s = (time.perf_counter() - t0) / reps

        results[str(B)] = {
            "scalar_pps": B / scalar_s,
            "batched_pps": B / batched_s,
            "speedup": scalar_s / batched_s,
        }
    return {
        "scheme": scheme,
        "scenario": scenario,
        "n_devices": n_devices,
        "n_tasks_per_instance": float(np.mean([a.n_tasks for a in _workload(64)])),
        "results": results,
    }


def _forbid_dense(*_a, **_k):
    raise AssertionError(
        "dense (D, D) link matrix materialized during the fleet sweep — "
        "the factorized snapshot path must never build it"
    )


def sweep_cluster(profile, n_devices: int, seed: int = 0):
    """The fleet-sweep cluster: multi-tier, coarse T_alloc buckets (dt=0.5,
    horizon=20) so the occupancy tensor stays a few hundred MB at 100k
    devices, and the dense ``link_bw`` accessor tripwired."""
    from repro.sim import make_cluster

    cluster = make_cluster(
        profile, scenario="multi_tier", n_devices=n_devices, seed=seed,
        horizon=20.0, dt=0.5,
    )
    cluster.link_bw = _forbid_dense
    return cluster


def fleet_sweep(
    scheme: str = "ibdash",
    B: int = 16,
    sizes=FLEET_SIZES,
    seed: int = 0,
) -> dict:
    """Batched placement throughput vs fleet size on the factorized
    snapshot path (multi-tier fleets, so the backhaul factor is live).

    Every cluster's dense ``link_bw`` accessor is replaced with a tripwire:
    the sweep COMPLETING is the proof that no ``(D, D)`` array was
    materialized anywhere in wave planning, at 100k devices included.
    T_alloc uses coarse buckets (:func:`sweep_cluster`) so the occupancy
    tensor — the one intentionally O(D x N x buckets) structure — stays a
    few hundred MB at 100k devices."""
    from repro.api import orchestrate_batch
    from repro.sim import SimConfig, make_profile
    from repro.sim.runner import policy_for

    profile = make_profile(seed=seed)
    cfg = SimConfig(seed=seed)
    apps = _workload(B)
    results = {}
    for D in sizes:
        cluster = sweep_cluster(profile, D, seed)
        pol = policy_for(scheme, profile, cfg)
        orchestrate_batch(apps, cluster, pol)     # warm the jitted kernels
        reps = 5 if D <= 10_000 else 2
        pol = policy_for(scheme, profile, cfg)
        t0 = time.perf_counter()
        for _ in range(reps):
            orchestrate_batch(apps, cluster, pol)
        wave_s = (time.perf_counter() - t0) / reps
        results[str(D)] = {"pps": B / wave_s, "wave_s": wave_s}
    return {"scheme": scheme, "B": B, "results": results}


def full_report() -> dict:
    """The paper's mix fleet with IBDASH, plus the multi-tier fleet (the
    tier-aware bottleneck-link cost path) with tier_escalation, plus the
    factorized fleet-size sweep (1k / 10k / 100k devices)."""
    report = measure()
    report["multi_tier"] = measure(
        scheme="tier_escalation", scenario="multi_tier", latency_budget=4.0
    )
    report["fleet_sweep"] = fleet_sweep()
    return report


def _check_section(results: dict, base_results: dict, label: str) -> list:
    failures = []
    for B, row in base_results.items():
        got = results.get(B)
        if got is None:
            failures.append(f"{label} B={B}: missing from report")
            continue
        floor = row["speedup"] / REGRESSION_FACTOR
        if got["speedup"] < floor:
            failures.append(
                f"{label} B={B}: batched/scalar speedup {got['speedup']:.2f}x "
                f"< {floor:.2f}x (baseline {row['speedup']:.2f}x / "
                f"{REGRESSION_FACTOR})"
            )
    return failures


def _check_sweep(report: dict, baseline: dict) -> list:
    """Gate the fleet-size sweep: every baseline fleet size must be present
    (the sweep itself raises if a dense (D, D) matrix is materialized, so a
    point existing means the factorized path carried it), and the
    throughput-scaling ratio pps@smallest / pps@largest must not blow up
    more than SWEEP_REGRESSION_FACTOR vs the committed baseline."""
    failures = []
    base_fs = baseline["fleet_sweep"]["results"]
    got_fs = report.get("fleet_sweep", {}).get("results", {})
    for D in base_fs:
        if D not in got_fs or got_fs[D]["pps"] <= 0:
            failures.append(f"fleet_sweep D={D}: missing from report")
    if failures:
        return failures
    lo, hi = min(base_fs, key=int), max(base_fs, key=int)
    base_ratio = base_fs[lo]["pps"] / base_fs[hi]["pps"]
    got_ratio = got_fs[lo]["pps"] / got_fs[hi]["pps"]
    if got_ratio > base_ratio * SWEEP_REGRESSION_FACTOR:
        failures.append(
            f"fleet_sweep: pps@{lo}/pps@{hi} scaling ratio {got_ratio:.1f} "
            f"> {base_ratio:.1f} (baseline) x {SWEEP_REGRESSION_FACTOR} — "
            "placement cost is growing with raw fleet size again"
        )
    return failures


def check(report: dict, baseline_path: str) -> int:
    """Fail on a >2x regression of the batched-vs-scalar SPEEDUP ratio (mix
    fleet and, when the baseline records it, the multi-tier fleet) or a
    fleet-sweep failure (see :func:`_check_sweep`).

    The gates compare ratios, not absolute placements/sec: everything runs
    on the same machine in the same job, so ratios are portable across
    runner hardware while absolute throughput is not.
    """
    with open(baseline_path) as f:
        baseline = json.load(f)
    failures = _check_section(report["results"], baseline["results"], "mix")
    if "multi_tier" in baseline:
        failures += _check_section(
            report.get("multi_tier", {}).get("results", {}),
            baseline["multi_tier"]["results"],
            "multi_tier",
        )
    if "fleet_sweep" in baseline:
        failures += _check_sweep(report, baseline)
    for msg in failures:
        print(f"REGRESSION {msg}", file=sys.stderr)
    return 1 if failures else 0


def run(ctx) -> None:
    """benchmarks.run entry point: emit CSV rows + write BENCH_place.json."""
    report = full_report()
    for B, row in report["results"].items():
        ctx.emit(f"place_scalar_pps_B{B}", row["scalar_pps"])
        ctx.emit(f"place_batched_pps_B{B}", row["batched_pps"])
        ctx.emit(f"place_speedup_B{B}", row["speedup"])
    for B, row in report["multi_tier"]["results"].items():
        ctx.emit(f"place_mt_batched_pps_B{B}", row["batched_pps"])
        ctx.emit(f"place_mt_speedup_B{B}", row["speedup"])
    for D, row in report["fleet_sweep"]["results"].items():
        ctx.emit(f"place_fleet_pps_D{D}", row["pps"])
    from .common import write_current_run

    write_current_run("place", report)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_place.json")
    ap.add_argument("--check", default=None,
                    help="baseline json; exit 1 on >2x throughput regression")
    args = ap.parse_args()
    enable_compile_cache()
    report = full_report()
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    for label, section in (("mix/ibdash", report),
                           ("multi_tier/tier_escalation", report["multi_tier"])):
        for B, row in section["results"].items():
            print(f"{label:26s} B={B:>5s}  "
                  f"scalar {row['scalar_pps']:10.1f} pl/s  "
                  f"batched {row['batched_pps']:10.1f} pl/s  "
                  f"speedup {row['speedup']:6.2f}x")
    for D, row in report["fleet_sweep"]["results"].items():
        print(f"{'fleet_sweep/ibdash':26s} D={D:>6s}  "
              f"batched {row['pps']:10.1f} pl/s  "
              f"wave {row['wave_s'] * 1e3:8.1f} ms")
    if args.check:
        sys.exit(check(report, args.check))


if __name__ == "__main__":
    main()
