"""Always-on streaming service: offered load vs shed rate and tail latency.

Sweeps the open-loop Poisson stream over the mixed 100-device fleet at two
offered-load points (the service's admission queue + wave cap throttle
dispatch to roughly the fleet's sustainable rate):

  * ``moderate`` — comfortably inside fleet capacity: nothing is shed and
    the ``latency_critical`` p99 sits far under its SLO;
  * ``overload`` — well past capacity (>= 10k instances), run twice:
      - with admission: deadline-aware shedding + best_effort backpressure
        keep the critical p99 INSIDE its SLO;
      - the no-admission baseline (unbounded queue, shedding off): every
        instance executes and the critical p99 blows past the SLO — the
        run that motivates the subsystem.

Also gates arrival generation throughput (>= 10k instances/sec: the
generators are vectorised and lazy about DAG construction) and fused
placement throughput (wall-clock, generous factor).

Writes ``BENCH_stream.json``; ``--check BASELINE.json`` exits non-zero when
any acceptance gate fails or shed-rate / tail-latency columns drift from
the committed baseline (the run is seeded, so shed counts are
deterministic — the tolerance only covers library drift).

    PYTHONPATH=src python -m benchmarks.bench_stream \\
        [--out BENCH_stream.json] [--check benchmarks/BENCH_stream.baseline.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

N_DEVICES = 100
HORIZON = 45.0
MODERATE_RATE = 60.0
OVERLOAD_RATE = 240.0
QUEUE_CAP = 256
WAVE_CAP = 30                  # per 0.25 s tick -> ~120 dispatches/sec
TICK = 0.25
SLO_CRITICAL = 6.0
SLO_BEST_EFFORT = 30.0

GEN_FLOOR = 10_000             # arrival-generation instances/sec
SHED_TOLERANCE = 0.05          # |shed_rate - baseline| slack
P99_FACTOR = 1.5               # per-column p99 drift factor vs baseline
THROUGHPUT_FACTOR = 3.0        # placements/sec wall-clock regression factor


def _streams():
    from repro.stream import default_streams

    return default_streams(
        slo_critical=SLO_CRITICAL, slo_best_effort=SLO_BEST_EFFORT
    )


def measure_generation() -> dict:
    """Arrival-process throughput: vectorised generation, lazy DAGs."""
    from repro.stream import diurnal_arrivals, poisson_arrivals

    streams = _streams()
    t0 = time.perf_counter()
    arr = poisson_arrivals(streams, 2000.0, 100.0, seed=3)
    arr += diurnal_arrivals(streams, 500.0, 3000.0, 100.0, seed=4)
    dt = time.perf_counter() - t0
    return {"n": len(arr), "gen_per_sec": len(arr) / dt}


def measure(profile, rate: float, admission: bool,
            n_devices: int = N_DEVICES, horizon: float = HORIZON) -> dict:
    from repro.api import Orchestrator, make_cluster, make_policy
    from repro.stream import AdmissionConfig, StreamingOrchestrator
    from repro.stream import poisson_arrivals

    cluster = make_cluster(
        profile, scenario="stream", n_devices=n_devices, seed=0,
        horizon=horizon * 6.0 + 120.0,      # baseline backlog drains late
    )
    orch = Orchestrator(
        cluster,
        make_policy("ibdash", alpha=0.5, beta=0.1, gamma=3,
                    lats_model=profile.lats_model),
    )
    arrivals = poisson_arrivals(_streams(), rate, horizon, seed=7)
    service = StreamingOrchestrator(
        orch,
        admission=AdmissionConfig(queue_cap=QUEUE_CAP) if admission else None,
        wave_cap=WAVE_CAP if admission else None,
        tick=TICK,
    )
    t0 = time.perf_counter()
    res = service.run(arrivals)
    wall = time.perf_counter() - t0
    c = res.metrics["counters"]
    return {
        "rate": rate,
        "admission": admission,
        "n_arrivals": res.n_arrivals,
        "shed_rate": res.shed_rate,
        "shed": res.stats["shed"],
        "completed": res.stats["completed"],
        "lost": res.stats["lost"],
        "deadline_missed": c.get("deadline_missed", 0),
        "deadline_missed_critical": c.get("deadline_missed_latency_critical", 0),
        "p50_critical": res.p("p50", "latency_critical"),
        "p99_critical": res.p("p99", "latency_critical"),
        "p999_critical": res.p("p999", "latency_critical"),
        "p99_best_effort": res.p("p99", "best_effort"),
        "placements_per_sec": res.metrics["gauges"]["placements_per_sec"],
        "wall_s": wall,
    }


def full_report() -> dict:
    from repro.api import make_profile

    profile = make_profile(seed=0)
    return {
        "config": {
            "n_devices": N_DEVICES, "horizon": HORIZON,
            "moderate_rate": MODERATE_RATE, "overload_rate": OVERLOAD_RATE,
            "queue_cap": QUEUE_CAP, "wave_cap": WAVE_CAP, "tick": TICK,
            "slo_critical": SLO_CRITICAL, "slo_best_effort": SLO_BEST_EFFORT,
        },
        "generation": measure_generation(),
        "results": {
            "moderate": measure(profile, MODERATE_RATE, admission=True),
            "overload": measure(profile, OVERLOAD_RATE, admission=True),
            "overload_baseline": measure(
                profile, OVERLOAD_RATE, admission=False
            ),
        },
    }


def check(report: dict, baseline_path: str) -> int:
    """Gate the PR's acceptance properties against the committed baseline:

    * the overload point offers >= 10k instances and the moderate point is
      a genuinely distinct load level;
    * with admission, the latency_critical p99 stays inside its SLO at an
      offered load where the no-admission baseline violates it;
    * moderate load sheds (almost) nothing and also meets the SLO;
    * arrival generation sustains >= GEN_FLOOR instances/sec;
    * shed-rate and p99 columns stay within tolerance of the committed
      baseline, and fused placement throughput within THROUGHPUT_FACTOR.
    """
    with open(baseline_path) as f:
        baseline = json.load(f)
    failures = []
    res = report["results"]
    mod, over, base_run = (
        res["moderate"], res["overload"], res["overload_baseline"]
    )

    if over["n_arrivals"] < 10_000:
        failures.append(
            f"overload offered only {over['n_arrivals']} instances (< 10k)"
        )
    if over["rate"] <= mod["rate"]:
        failures.append("load points are not distinct")
    if over["p99_critical"] > SLO_CRITICAL:
        failures.append(
            f"overload+admission critical p99 {over['p99_critical']:.2f}s "
            f"> SLO {SLO_CRITICAL}s — shedding no longer protects criticals"
        )
    if base_run["p99_critical"] <= SLO_CRITICAL:
        failures.append(
            f"no-admission baseline critical p99 "
            f"{base_run['p99_critical']:.2f}s <= SLO {SLO_CRITICAL}s — the "
            "overload point no longer stresses the fleet"
        )
    if over["shed_rate"] <= 0.0:
        failures.append("overload+admission shed nothing")
    if base_run["shed_rate"] != 0.0:
        failures.append("the no-admission baseline shed instances")
    if mod["p99_critical"] > SLO_CRITICAL:
        failures.append(
            f"moderate critical p99 {mod['p99_critical']:.2f}s > SLO"
        )
    if mod["shed_rate"] > 0.02:
        failures.append(
            f"moderate load shed {100 * mod['shed_rate']:.1f}% (> 2%)"
        )
    gen = report["generation"]["gen_per_sec"]
    if gen < GEN_FLOOR:
        failures.append(
            f"arrival generation {gen:.0f}/s < {GEN_FLOOR}/s"
        )

    for key in ("moderate", "overload", "overload_baseline"):
        got, ref = res[key], baseline["results"][key]
        if abs(got["shed_rate"] - ref["shed_rate"]) > SHED_TOLERANCE:
            failures.append(
                f"{key}: shed rate {got['shed_rate']:.3f} drifted from "
                f"baseline {ref['shed_rate']:.3f} (> {SHED_TOLERANCE})"
            )
        if got["p99_critical"] > ref["p99_critical"] * P99_FACTOR:
            failures.append(
                f"{key}: critical p99 {got['p99_critical']:.2f}s > "
                f"baseline {ref['p99_critical']:.2f}s * {P99_FACTOR}"
            )
        base_tp = ref["placements_per_sec"]
        if base_tp > 0 and got["placements_per_sec"] < base_tp / THROUGHPUT_FACTOR:
            failures.append(
                f"{key}: {got['placements_per_sec']:.0f} placements/s < "
                f"{base_tp / THROUGHPUT_FACTOR:.0f} "
                f"(baseline {base_tp:.0f} / {THROUGHPUT_FACTOR})"
            )

    for msg in failures:
        print(f"REGRESSION {msg}", file=sys.stderr)
    return 1 if failures else 0


def run(ctx) -> None:
    """benchmarks.run entry point: emit CSV rows + write BENCH_stream.json."""
    report = full_report()
    for key, row in report["results"].items():
        ctx.emit(f"stream_{key}_shed_rate", row["shed_rate"])
        ctx.emit(f"stream_{key}_p99_critical", row["p99_critical"])
        ctx.emit(f"stream_{key}_p99_best_effort", row["p99_best_effort"])
    ctx.emit("stream_gen_per_sec", report["generation"]["gen_per_sec"])
    from .common import write_current_run

    write_current_run("stream", report)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_stream.json")
    ap.add_argument("--check", default=None,
                    help="baseline json; exit 1 on an SLO/shed regression")
    args = ap.parse_args()
    enable_compile_cache()
    report = full_report()
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    gen = report["generation"]
    print(f"generation {gen['gen_per_sec']:,.0f} arrivals/s ({gen['n']:,d})")
    for key, row in report["results"].items():
        print(
            f"{key:18s} rate {row['rate']:5.0f}/s  n {row['n_arrivals']:6d}  "
            f"shed {100 * row['shed_rate']:5.1f}%  "
            f"p99crit {row['p99_critical']:6.2f}s  "
            f"p99best {row['p99_best_effort']:6.2f}s  "
            f"missed {row['deadline_missed']:4d}  "
            f"{row['placements_per_sec']:7.0f} placements/s  "
            f"wall {row['wall_s']:.1f}s"
        )
    if args.check:
        sys.exit(check(report, args.check))


if __name__ == "__main__":
    main()
