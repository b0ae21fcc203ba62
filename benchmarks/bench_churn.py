"""Churn recovery + churn-aware planning: recovered/lost/salvaged instances
and the forecast-aware-vs-memoryless placement race.

Two scenario columns:

  * ``churn`` (exponential leave/rejoin streams) for each recovery strategy
    and two schemes:
      - ``lavea`` — no proactive replication, so every device departure that
        catches a task in flight is a potential instance loss: the cleanest
        view of what detection + recovery buys.  ``failover`` and ``replan``
        must strictly reduce P_f vs ``fail_fast`` (the PR-4 gate).
      - ``ibdash`` — Algorithm 1's pf-aware placement + replication absorbs
        this churn level on its own (the paper's core claim).
  * ``correlated`` (per-group shared shocks + rotating scripted maintenance
    windows, ``repro.sim.churn.correlated_churn``) racing registry
    ``ibdash`` against the forecast-aware ``churn_aware`` under
    ``fail_fast`` (raw P_f), ``fail_fast`` + partial-result salvage
    (salvaged-instance counts), and ``replan`` + salvage (everything on —
    both recover every instance, so its service time is the fair E2E
    latency comparison with no survivorship bias).  Gates: ``churn_aware``
    strictly beats ``ibdash`` on P_f, is no worse on E2E latency, and
    salvage strictly reduces ``ibdash``'s losses.

Writes ``BENCH_churn.json``; ``--check BASELINE.json`` exits non-zero when
any gate fails, the recovered-instance rate drops below the committed
baseline (the sim is seeded, so the counts are deterministic — the
tolerance only covers library drift) or replan throughput regresses more
than 3x (wall-clock, so the factor is generous for runner-hardware
variance).

    PYTHONPATH=src python -m benchmarks.bench_churn \
        [--out BENCH_churn.json] [--check benchmarks/BENCH_churn.baseline.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

SCHEMES = ("lavea", "ibdash")
RECOVERIES = ("fail_fast", "failover", "replan")
GATED_SCHEME = "lavea"
RATE_TOLERANCE = 0.05          # recovered-rate slack vs baseline
THROUGHPUT_FACTOR = 3.0        # replan/s regression factor (hw-portable-ish)

# correlated column: scheme x (recovery, salvage attempts)
CORR_SCHEMES = ("ibdash", "churn_aware")
CORR_MODES = (
    ("fail_fast", 0),          # raw forecast win (P_f gate)
    ("fail_fast_salvage", 1),  # salvage alone (salvaged-count gate)
    ("replan", 1),             # everything on (E2E latency gate)
)
LATENCY_TOLERANCE = 1.02       # churn_aware svc <= ibdash svc * this


def _config(scenario: str = "churn"):
    from repro.sim import SimConfig

    return SimConfig(
        scenario=scenario, n_cycles=4, instances_per_cycle=400,
        n_devices=100, seed=0,
    )


def measure(scheme: str, recovery: str, profile, cfg, salvage: int = 0) -> dict:
    from repro.api import Orchestrator
    from repro.sim import make_cluster
    from repro.sim.runner import _make_workload, make_churn, policy_for

    cluster = make_cluster(
        profile, scenario=cfg.scenario, n_devices=cfg.n_devices,
        seed=cfg.seed, horizon=cfg.horizon + 30.0,
    )
    churn = make_churn(cfg, cluster)
    orch = Orchestrator(
        cluster, policy_for(scheme, profile, cfg), seed=cfg.seed,
        noise_sigma=cfg.noise_sigma, churn=churn, recovery=recovery,
        salvage=salvage, detection_delay=cfg.detection_delay,
        max_retries=cfg.max_retries,
    )
    apps, times = _make_workload(cfg)
    orch.submit_batch(apps, times)
    orch.drain()
    res = orch.result(cfg.scenario, cfg.horizon)
    stats = dict(orch.stats)
    eng = orch.engine
    touched = stats["recovered"] + stats["lost"]
    row = {
        "prob_failure": res.prob_failure,
        "avg_service_time": res.avg_service_time,
        "recovered": stats["recovered"],
        "lost": stats["lost"],
        "recovered_rate": stats["recovered"] / touched if touched else 1.0,
        "replica_deaths": stats["replica_deaths"],
        "device_down": stats["device_down"],
        "device_up": stats["device_up"],
        "task_failovers": stats["task_failovers"],
        "replans": stats["replans"],
        "salvages": stats["salvages"],
        "salvaged": stats["salvaged"],
        "replan_time_s": eng.replan_time,
        "replans_per_sec": (
            stats["replans"] / eng.replan_time if eng.replan_time > 0 else 0.0
        ),
    }
    return row


def full_report() -> dict:
    from repro.sim import make_profile

    cfg = _config()
    corr_cfg = _config("correlated_churn")
    profile = make_profile(seed=cfg.seed)
    report = {
        "config": {
            "scenario": cfg.scenario, "n_cycles": cfg.n_cycles,
            "instances_per_cycle": cfg.instances_per_cycle,
            "n_devices": cfg.n_devices, "seed": cfg.seed,
            "mean_downtime": cfg.mean_downtime,
            "detection_delay": cfg.detection_delay,
            "max_retries": cfg.max_retries,
            "correlated": {
                "churn_groups": corr_cfg.churn_groups,
                "shock_rate": corr_cfg.shock_rate,
                "maintenance_period": corr_cfg.maintenance_period,
                "maintenance_duration": corr_cfg.maintenance_duration,
            },
        },
        "results": {
            scheme: {
                recovery: measure(scheme, recovery, profile, cfg)
                for recovery in RECOVERIES
            }
            for scheme in SCHEMES
        },
        "correlated": {
            scheme: {
                mode: measure(
                    scheme, mode.replace("_salvage", ""), profile, corr_cfg,
                    salvage=salvage,
                )
                for mode, salvage in CORR_MODES
            }
            for scheme in CORR_SCHEMES
        },
    }
    return report


def check(report: dict, baseline_path: str) -> int:
    """Gate the PR's acceptance properties against the committed baseline:

    * churn must actually bite the gated scheme under ``fail_fast``;
    * ``failover`` and ``replan`` must strictly reduce P_f vs ``fail_fast``
      and keep their recovered-instance rate within RATE_TOLERANCE of the
      baseline (counts are deterministic given the seed);
    * replan throughput must stay within THROUGHPUT_FACTOR of baseline;
    * on the correlated scenario, ``churn_aware`` must strictly beat
      registry ``ibdash`` on P_f (fail_fast rows), be no worse on E2E
      latency (replan rows, where both recover everything), and salvage
      must strictly reduce ``ibdash``'s instance losses while actually
      salvaging instances.
    """
    with open(baseline_path) as f:
        baseline = json.load(f)
    failures = []
    rows = report["results"][GATED_SCHEME]
    base_rows = baseline["results"][GATED_SCHEME]
    if rows["fail_fast"]["lost"] == 0:
        failures.append(
            f"{GATED_SCHEME}/fail_fast: no instances lost — churn scenario "
            "no longer exercises recovery"
        )
    for recovery in ("failover", "replan"):
        got, base = rows[recovery], base_rows[recovery]
        if got["prob_failure"] >= rows["fail_fast"]["prob_failure"]:
            failures.append(
                f"{GATED_SCHEME}/{recovery}: P_f {got['prob_failure']:.4f} "
                f">= fail_fast {rows['fail_fast']['prob_failure']:.4f}"
            )
        floor = base["recovered_rate"] - RATE_TOLERANCE
        if got["recovered_rate"] < floor:
            failures.append(
                f"{GATED_SCHEME}/{recovery}: recovered rate "
                f"{got['recovered_rate']:.3f} < {floor:.3f} "
                f"(baseline {base['recovered_rate']:.3f} - {RATE_TOLERANCE})"
            )
    got_tp = rows["replan"]["replans_per_sec"]
    base_tp = base_rows["replan"]["replans_per_sec"]
    if base_tp > 0 and got_tp < base_tp / THROUGHPUT_FACTOR:
        failures.append(
            f"{GATED_SCHEME}/replan: {got_tp:.1f} replans/s < "
            f"{base_tp / THROUGHPUT_FACTOR:.1f} "
            f"(baseline {base_tp:.1f} / {THROUGHPUT_FACTOR})"
        )

    # -- correlated scenario: the churn-aware acceptance gates ----------------
    corr = report["correlated"]
    ib, ca = corr["ibdash"], corr["churn_aware"]
    if ib["fail_fast"]["lost"] == 0:
        failures.append(
            "correlated/ibdash/fail_fast: no instances lost — the "
            "correlated scenario no longer stresses placement"
        )
    if ca["fail_fast"]["prob_failure"] >= ib["fail_fast"]["prob_failure"]:
        failures.append(
            "correlated: churn_aware P_f "
            f"{ca['fail_fast']['prob_failure']:.4f} >= ibdash "
            f"{ib['fail_fast']['prob_failure']:.4f} — the forecast no "
            "longer beats memoryless pricing"
        )
    if ca["replan"]["prob_failure"] > ib["replan"]["prob_failure"]:
        failures.append(
            "correlated/replan: churn_aware P_f "
            f"{ca['replan']['prob_failure']:.4f} > ibdash "
            f"{ib['replan']['prob_failure']:.4f}"
        )
    lat_ca = ca["replan"]["avg_service_time"]
    lat_ib = ib["replan"]["avg_service_time"]
    if lat_ca > lat_ib * LATENCY_TOLERANCE:
        failures.append(
            f"correlated/replan: churn_aware E2E latency {lat_ca:.3f}s > "
            f"ibdash {lat_ib:.3f}s * {LATENCY_TOLERANCE}"
        )
    salv = ib["fail_fast_salvage"]
    if salv["salvaged"] == 0:
        failures.append(
            "correlated/ibdash/fail_fast_salvage: no instance was salvaged"
        )
    if salv["lost"] >= ib["fail_fast"]["lost"]:
        failures.append(
            f"correlated/ibdash: salvage did not reduce losses "
            f"({salv['lost']} >= {ib['fail_fast']['lost']})"
        )
    for msg in failures:
        print(f"REGRESSION {msg}", file=sys.stderr)
    return 1 if failures else 0


def run(ctx) -> None:
    """benchmarks.run entry point: emit CSV rows + write BENCH_churn.json."""
    report = full_report()
    for scheme, rows in report["results"].items():
        for recovery, row in rows.items():
            key = f"churn_{scheme}_{recovery}"
            ctx.emit(f"{key}_pf", row["prob_failure"])
            ctx.emit(f"{key}_recovered", row["recovered"])
            ctx.emit(f"{key}_lost", row["lost"])
    for scheme, rows in report["correlated"].items():
        for mode, row in rows.items():
            key = f"corr_{scheme}_{mode}"
            ctx.emit(f"{key}_pf", row["prob_failure"])
            ctx.emit(f"{key}_svc", row["avg_service_time"])
            ctx.emit(f"{key}_salvaged", row["salvaged"])
    ctx.emit(
        "churn_replan_per_sec",
        report["results"][GATED_SCHEME]["replan"]["replans_per_sec"],
    )
    from .common import write_current_run

    write_current_run("churn", report)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_churn.json")
    ap.add_argument("--check", default=None,
                    help="baseline json; exit 1 on recovery regression")
    args = ap.parse_args()
    enable_compile_cache()
    report = full_report()
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    for scheme, rows in report["results"].items():
        for recovery, row in rows.items():
            print(
                f"{scheme:8s} {recovery:10s}  P_f {row['prob_failure']:.4f}  "
                f"recovered {row['recovered']:4d}  lost {row['lost']:4d}  "
                f"deaths {row['replica_deaths']:4d}  "
                f"replans {row['replans']:3d} "
                f"({row['replans_per_sec']:7.1f}/s)"
            )
    print("-- correlated (shared shocks + maintenance windows) --")
    for scheme, rows in report["correlated"].items():
        for mode, row in rows.items():
            print(
                f"{scheme:12s} {mode:18s}  P_f {row['prob_failure']:.4f}  "
                f"svc {row['avg_service_time']:.3f}s  "
                f"lost {row['lost']:4d}  salvaged {row['salvaged']:3d}"
            )
    if args.check:
        sys.exit(check(report, args.check))


if __name__ == "__main__":
    main()
