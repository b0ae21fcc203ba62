"""Smoke run of the fused placement path on one TPU.

    python chip_smoke.py

Drives the orchestrator through its normal entry points and fails unless
the jitted placement kernels ran on the device and placed every task
exactly where the scalar reference (``orchestrate_batch(batched=False)``)
places it:

  A  1000 instances with staggered arrivals on a 10,000-device multi-tier
     fleet (T_alloc over 60 s at dt = 0.05), planned fused and scalar under
     ibdash, churn_aware, tier_escalation, lavea and round_robin; then
     ``run_one`` with two fused 1000-instance cycles on that fleet size;
  B  one fused 1000-instance wave on a 100,000-device fleet (the
     ``bench_place`` fleet-sweep cluster) under ibdash and tier_escalation,
     with scalar parity on the first 128 instances;
  C  the streaming service (``bench_stream.measure``) on 1,000 devices at
     Poisson 60 instances/s for 10 s.  Its dispatch count is reported, not
     checked: the served path plans its small waves on the host.

Phases A and B fail when a policy's kernel never dispatched or a fused
placement differs from the scalar one.  Each phase prints one JSON line:
wall and compile seconds, dispatches and padded shapes per kernel, and the
device's peak bytes in use.  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits 1 before any phase.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from collections import Counter, defaultdict

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

import jax  # noqa: E402

from benchmarks.bench_place import first_plan_difference, sweep_cluster  # noqa: E402
from benchmarks.bench_stream import measure as measure_stream  # noqa: E402
from repro.api import orchestrate_batch, run_one  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import batched  # noqa: E402
from repro.sim import SimConfig, make_cluster, make_profile  # noqa: E402
from repro.sim.runner import _make_workload, policy_for  # noqa: E402

KERNELS = (
    "ibdash_scan_kernel",
    "lavea_kernel",
    "round_robin_kernel",
    "tier_escalation_kernel",
)
# the kernel each policy's decide_batch dispatches for a wave of >= 8 rows
POLICY_KERNEL = {
    "ibdash": "ibdash_scan_kernel",
    "churn_aware": "ibdash_scan_kernel",
    "tier_escalation": "tier_escalation_kernel",
    "lavea": "lavea_kernel",
    "round_robin": "round_robin_kernel",
}
LATENCY_BUDGET = 4.0
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    """A kernel did not reach the device, or a fused placement differs."""


class Probe:
    """Counts placement-kernel dispatches, with the padded shape of each
    call's first argument, and XLA programs compiled while the ``with``
    block runs (``cache_hits`` of them were loaded from the persistent
    cache instead).  Wraps the entries of ``batched._jax()``'s kernel
    table, which ``decide_batch`` looks up on every call; probes nest."""

    def __init__(self):
        self.dispatches: Counter = Counter()
        self.shapes = defaultdict(set)
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.wall_s = 0.0

    def _counted(self, name, fn):
        def call(*args):
            self.dispatches[name] += 1
            self.shapes[name].add(tuple(args[0].shape))
            return fn(*args)

        return call

    def _on_duration(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self) -> "Probe":
        table = batched._jax()
        self._saved = {k: table[k] for k in KERNELS}
        for k in KERNELS:
            table[k] = self._counted(k, table[k])
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.wall_s = time.perf_counter() - self._t0
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        batched._jax().update(self._saved)

    def report(self) -> dict:
        return {
            "wall_s": self.wall_s,
            "compile_s": self.compile_s,
            "compiles": self.compiles,
            "cache_hits": self.cache_hits,
            "dispatches": dict(sorted(self.dispatches.items())),
            "shapes": {k: sorted(v) for k, v in sorted(self.shapes.items())},
        }


def _peak_bytes():
    """The device's peak bytes in use so far in this process (None where
    the backend keeps no memory statistics)."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _wave_config(n_devices: int, n_instances: int, seed: int) -> SimConfig:
    return SimConfig(
        scenario="multi_tier", n_devices=n_devices, n_cycles=1,
        instances_per_cycle=n_instances, seed=seed,
        latency_budget=LATENCY_BUDGET,
    )


def plan_and_compare(scheme, apps, times, cluster, profile, cfg,
                     n_parity=None) -> dict:
    """Plan the wave fused, then the first ``n_parity`` instances (all by
    default) through the scalar ``decide`` loop; raise
    :class:`SmokeFailure` on zero dispatches of the scheme's kernel or on
    the first placement that differs."""
    with Probe() as fused:
        plans = orchestrate_batch(
            apps, cluster, policy_for(scheme, profile, cfg), times=times
        )
    kernel = POLICY_KERNEL[scheme]
    if fused.dispatches[kernel] == 0:
        raise SmokeFailure(f"{scheme}: `{kernel}` was never dispatched")
    k = len(apps) if n_parity is None else n_parity
    t0 = time.perf_counter()
    ref = orchestrate_batch(
        apps[:k], cluster, policy_for(scheme, profile, cfg), times=times[:k],
        batched=False,
    )
    scalar_s = time.perf_counter() - t0
    diff = first_plan_difference(plans[:k], ref)
    if diff is not None:
        raise SmokeFailure(
            f"{scheme} (`{kernel}`): fused vs scalar placement differs at "
            f"{diff}  [replicas as (device, est_total, pf)]"
        )
    return {
        "fused_s": fused.wall_s,
        "scalar_s": scalar_s,
        "compiles": fused.compiles,
        "dispatches": fused.dispatches[kernel],
        "parity_instances": k,
    }


def fused_wave(n_devices: int = 10_000, n_instances: int = 1000,
               n_cycles: int = 2, seed: int = 0) -> dict:
    """Phase A (see the module docstring)."""
    profile = make_profile(seed=seed)
    cfg = _wave_config(n_devices, n_instances, seed)
    apps, times = _make_workload(cfg)
    cluster = make_cluster(
        profile, scenario="multi_tier", n_devices=n_devices, seed=seed,
        horizon=60.0,
    )
    with Probe() as phase:
        policies = {
            scheme: plan_and_compare(scheme, apps, times, cluster, profile, cfg)
            for scheme in POLICY_KERNEL
        }
        with Probe() as sim:
            res = run_one("ibdash", SimConfig(
                scenario="multi_tier", n_devices=n_devices, n_cycles=n_cycles,
                instances_per_cycle=n_instances, fused_burst=True, seed=seed,
            ), profile)
    if sim.dispatches["ibdash_scan_kernel"] == 0:
        raise SmokeFailure("run_one(fused_burst): `ibdash_scan_kernel` was "
                           "never dispatched")
    unresolved = sum(
        1 for r in res.instances if not (r.failed or math.isfinite(r.service_time))
    )
    if res.n != n_cycles * n_instances or unresolved:
        raise SmokeFailure(f"run_one: {res.n} instances, {unresolved} "
                           "neither finished nor failed")
    return {
        "phase": "A", "n_devices": n_devices, "n_instances": n_instances,
        **phase.report(), "policies": policies,
        "run_one": {
            "wall_s": sim.wall_s, "dispatches": sim.dispatches["ibdash_scan_kernel"],
            "instances": res.n, "failed": sum(r.failed for r in res.instances),
            "avg_service_time": res.avg_service_time,
        },
        "peak_bytes": _peak_bytes(),
    }


def large_fleet(n_devices: int = 100_000, n_instances: int = 1000,
                n_parity: int = 128, seed: int = 0) -> dict:
    """Phase B (see the module docstring)."""
    profile = make_profile(seed=seed)
    cfg = _wave_config(n_devices, n_instances, seed)
    apps, times = _make_workload(cfg)
    cluster = sweep_cluster(profile, n_devices, seed)
    with Probe() as phase:
        policies = {
            scheme: plan_and_compare(scheme, apps, times, cluster, profile,
                                     cfg, n_parity)
            for scheme in ("ibdash", "tier_escalation")
        }
    return {
        "phase": "B", "n_devices": n_devices, "n_instances": n_instances,
        **phase.report(), "policies": policies, "peak_bytes": _peak_bytes(),
    }


def served(n_devices: int = 1000, rate: float = 60.0,
           duration: float = 10.0) -> dict:
    """Phase C (see the module docstring)."""
    profile = make_profile(seed=0)
    with Probe() as phase:
        m = measure_stream(profile, rate, admission=True,
                           n_devices=n_devices, horizon=duration)
    return {
        "phase": "C", "n_devices": n_devices, "rate": rate,
        "duration_s": duration, **phase.report(),
        "n_arrivals": m["n_arrivals"], "completed": m["completed"],
        "shed": m["shed"], "lost": m["lost"], "peak_bytes": _peak_bytes(),
    }


def main(argv=None) -> int:
    argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    ).parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing was run",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    print(json.dumps({"compile_cache": cache_dir}), flush=True)
    for phase in (fused_wave, large_fleet, served):
        print(json.dumps(phase()), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
