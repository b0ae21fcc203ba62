"""Observability overhead + trace-validity gate (repro.obs).

Three sections:

  * ``placement`` — fused batched placement throughput (B = 64, the
    bench_place protocol) with NO tracer anywhere in sight: the number the
    PR-8 era gated.  ``check()`` holds it within the standard wall-clock
    regression factor of this bench's own baseline AND of the committed
    ``BENCH_place.baseline.json`` batched_pps, so threading the tracer
    through the engine cannot tax the tracing-off pipeline unnoticed.
  * ``overhead`` — the same seeded churn run end-to-end with tracing off
    and tracing on.  Tracing-off instances/sec is gated like any other
    throughput column; tracing-on overhead is RECORDED (``overhead_pct``)
    so the trajectory is visible across PRs, and the two runs are asserted
    bit-identical (the observer effect is a correctness failure, not a
    perf number).
  * ``validation`` — the acceptance scenario: a correlated-churn + salvage
    run with tracing on must export a structurally valid Chrome
    ``trace_event`` JSON whose instance events alone reproduce the
    engine's conservation ledger ``admitted == completed + lost + shed``
    exactly, and an attribution report carrying per-stage critical-path
    aggregates and per-policy latency / P_f calibration.  These gates are
    exact and hardware-independent.

Writes ``BENCH_obs.json``; ``--check BASELINE.json`` exits non-zero on
any validity failure or throughput regression.

    PYTHONPATH=src python -m benchmarks.bench_obs \\
        [--out BENCH_obs.json] [--check benchmarks/BENCH_obs.baseline.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

PLACE_B = 64                   # bench_place's middle batch size
THROUGHPUT_FACTOR = 3.0        # wall-clock regression factor (CI standard)
OVERHEAD_REPS = 3              # timed repetitions per tracing mode


def _overhead_cfg(trace: bool):
    from repro.api import SimConfig

    return SimConfig(scenario="churn", n_cycles=2, instances_per_cycle=200,
                     seed=5, n_devices=50, recovery="failover", trace=trace)


def _validation_cfg():
    """Correlated churn hot enough to kill instances outright, replan +
    salvage on — the whole span vocabulary fires (mirrors tests/test_obs)."""
    from repro.api import SimConfig

    return SimConfig(scenario="correlated_churn", n_cycles=2,
                     instances_per_cycle=60, seed=3, n_devices=12,
                     recovery="replan", salvage=2, shock_rate=0.2,
                     mean_downtime=30.0, gamma=1, max_retries=1, trace=True)


def measure_placement(profile) -> dict:
    """Pure planning throughput, bench_place protocol at B=64 — the PR-8
    number the tracing work must leave untouched."""
    from repro.api import orchestrate_batch
    from repro.sim import SimConfig, make_cluster
    from repro.sim.apps import APP_BUILDERS
    from repro.sim.runner import policy_for

    import numpy as np

    rng = np.random.default_rng(1)
    builders = list(APP_BUILDERS.values())
    apps = [builders[int(rng.integers(len(builders)))]().relabel(f"#{i}")
            for i in range(PLACE_B)]
    cluster = make_cluster(profile, scenario="mix", n_devices=100, seed=0,
                           horizon=400.0)
    pol = policy_for("ibdash", profile, SimConfig(seed=0))
    orchestrate_batch(apps, cluster, pol)          # warm the jitted kernels
    reps = max(1, 2000 // PLACE_B)
    t0 = time.perf_counter()
    for _ in range(reps):
        orchestrate_batch(apps, cluster, pol)
    dt = (time.perf_counter() - t0) / reps
    return {"B": PLACE_B, "batched_pps": PLACE_B / dt}


def measure_overhead(profile) -> dict:
    from repro.sim import run_one

    def timed(trace: bool):
        best, res = float("inf"), None
        for _ in range(OVERHEAD_REPS):
            t0 = time.perf_counter()
            res = run_one("ibdash", _overhead_cfg(trace), profile)
            best = min(best, time.perf_counter() - t0)
        return best, res

    wall_off, res_off = timed(False)
    wall_on, res_on = timed(True)
    # identical seeded runs: tracing must not perturb a single outcome
    same = (
        [(r.app, r.finished, r.failed) for r in res_off.instances]
        == [(r.app, r.finished, r.failed) for r in res_on.instances]
    )
    n = len(res_off.instances)
    return {
        "n_instances": n,
        "wall_off_s": wall_off,
        "wall_on_s": wall_on,
        "instances_per_sec_off": n / wall_off,
        "instances_per_sec_on": n / wall_on,
        "overhead_pct": 100.0 * (wall_on - wall_off) / wall_off,
        "n_spans": len(res_on.trace.spans),
        "bit_identical": same,
    }


def measure_validation(profile) -> dict:
    from repro.obs import (
        attribution_report,
        ledger_from_trace,
        to_chrome_trace,
        validate_chrome_trace,
    )
    from repro.sim import run_one

    res = run_one("ibdash", _validation_cfg(), profile)
    tr = res.trace
    doc = to_chrome_trace(tr)
    n_events = validate_chrome_trace(doc)
    led = ledger_from_trace(doc)
    counts = tr.outcome_counts()
    rep = attribution_report(tr, top_k=3)
    pol = rep["calibration"]["policy"].get("ibdash", {})
    return {
        "n_instances": tr.n_instances,
        "n_spans": len(tr.spans),
        "n_trace_events": n_events,
        "ledger": led,
        "ledger_round_trip": (
            led["admitted"] == led["completed"] + led["lost"] + led["shed"]
            and led["completed"] == counts.get("completed", 0)
            and led["lost"] == counts.get("lost", 0)
        ),
        "lost": led["lost"],
        "salvage_events": len(tr.by_kind("salvage")),
        "replan_events": len(tr.by_kind("replan")),
        "critical_path_n": rep["critical_path"]["n"],
        "latency_bias_s": pol.get("latency", {}).get("bias"),
        "pred_p_fail": pol.get("p_fail", {}).get("pred_mean"),
        "empirical_p_fail": pol.get("p_fail", {}).get("empirical"),
    }


def full_report() -> dict:
    from repro.api import make_profile

    profile = make_profile(seed=0)
    return {
        "config": {
            "place_B": PLACE_B,
            "overhead": {"scenario": "churn", "n_instances": 400},
            "validation": {"scenario": "correlated_churn", "salvage": 2},
        },
        "placement": measure_placement(profile),
        "overhead": measure_overhead(profile),
        "validation": measure_validation(profile),
    }


def check(report: dict, baseline_path: str) -> int:
    """Exact validity gates + wall-clock throughput gates.

    Tracing-off throughput is held within THROUGHPUT_FACTOR of this
    bench's own baseline; placement throughput additionally within the
    same factor of the committed PR-8 ``BENCH_place.baseline.json``."""
    with open(baseline_path) as f:
        baseline = json.load(f)
    failures = []

    val = report["validation"]
    if not val["ledger_round_trip"]:
        failures.append(
            f"trace ledger does not round-trip the engine counters: "
            f"{val['ledger']}"
        )
    if val["lost"] <= 0 or val["salvage_events"] <= 0:
        failures.append(
            "validation scenario no longer exercises loss + salvage "
            f"(lost={val['lost']}, salvages={val['salvage_events']})"
        )
    if val["critical_path_n"] <= 0:
        failures.append("attribution report covers no completed instances")
    if val["latency_bias_s"] is None or val["pred_p_fail"] is None:
        failures.append("per-policy calibration rows missing from report")

    ov = report["overhead"]
    if not ov["bit_identical"]:
        failures.append("tracing perturbed the seeded run (observer effect)")
    base_ips = baseline["overhead"]["instances_per_sec_off"]
    if ov["instances_per_sec_off"] < base_ips / THROUGHPUT_FACTOR:
        failures.append(
            f"tracing-off engine throughput "
            f"{ov['instances_per_sec_off']:.0f} inst/s < "
            f"{base_ips / THROUGHPUT_FACTOR:.0f} "
            f"(baseline {base_ips:.0f} / {THROUGHPUT_FACTOR})"
        )

    got_pps = report["placement"]["batched_pps"]
    base_pps = baseline["placement"]["batched_pps"]
    if got_pps < base_pps / THROUGHPUT_FACTOR:
        failures.append(
            f"placement throughput {got_pps:.0f} pl/s < "
            f"{base_pps / THROUGHPUT_FACTOR:.0f} "
            f"(baseline {base_pps:.0f} / {THROUGHPUT_FACTOR})"
        )
    place_base = os.path.join(
        os.path.dirname(baseline_path), "BENCH_place.baseline.json"
    )
    if os.path.exists(place_base):
        with open(place_base) as f:
            pr8 = json.load(f)
        pr8_pps = pr8["results"][str(PLACE_B)]["batched_pps"]
        if got_pps < pr8_pps / THROUGHPUT_FACTOR:
            failures.append(
                f"placement throughput {got_pps:.0f} pl/s < "
                f"{pr8_pps / THROUGHPUT_FACTOR:.0f} (PR-8 place baseline "
                f"{pr8_pps:.0f} / {THROUGHPUT_FACTOR})"
            )

    for msg in failures:
        print(f"REGRESSION {msg}", file=sys.stderr)
    return 1 if failures else 0


def run(ctx) -> None:
    """benchmarks.run entry point: emit CSV rows + write BENCH_obs.json."""
    report = full_report()
    ctx.emit("obs_batched_pps", report["placement"]["batched_pps"])
    ctx.emit("obs_instances_per_sec_off",
             report["overhead"]["instances_per_sec_off"])
    ctx.emit("obs_overhead_pct", report["overhead"]["overhead_pct"])
    ctx.emit("obs_trace_events", report["validation"]["n_trace_events"])
    from .common import write_current_run

    write_current_run("obs", report)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_obs.json")
    ap.add_argument("--check", default=None,
                    help="baseline json; exit 1 on a validity failure or "
                         "throughput regression")
    args = ap.parse_args()
    enable_compile_cache()
    report = full_report()
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    pl, ov, val = report["placement"], report["overhead"], report["validation"]
    print(f"placement  B={pl['B']}  {pl['batched_pps']:10.1f} pl/s (no tracer)")
    print(f"overhead   off {ov['instances_per_sec_off']:8.1f} inst/s  "
          f"on {ov['instances_per_sec_on']:8.1f} inst/s  "
          f"overhead {ov['overhead_pct']:+5.1f}%  "
          f"({ov['n_spans']} spans, identical={ov['bit_identical']})")
    print(f"validation {val['n_instances']} instances -> "
          f"{val['n_trace_events']} trace events  ledger {val['ledger']}  "
          f"round-trip={val['ledger_round_trip']}  "
          f"salvages={val['salvage_events']} replans={val['replan_events']}")
    if val["latency_bias_s"] is not None:
        print(f"calibration ibdash latency bias {val['latency_bias_s']:+.3f}s  "
              f"P_f pred {val['pred_p_fail']:.3f} "
              f"emp {val['empirical_p_fail']:.3f}")
    if args.check:
        sys.exit(check(report, args.check))


if __name__ == "__main__":
    main()
