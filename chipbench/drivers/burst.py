"""Closed loop of the paper's bursts through ``Orchestrator.submit_batch``.

Wave ``k`` is cycle ``k``'s burst (``traffic.burst``), planned as one fused
wave at the cycle's start against the state the earlier waves left; the
engine is then stepped to the next cycle's start.  Nothing is reset inside
the window.  The window times the program alone: the clock stops while
the benchmark instantiates the next burst's apps (its load generator, which
in a deployment runs on the clients), and the window ends with the first
wave after which the program's time reaches ``seconds``.  The generator
runs with Python's garbage collector paused, so every collection that the
window's allocations call for runs on the program's clock: where a
collection fell would otherwise move its cost in or out of the window.
``plans_per_s`` is every instance planned into a feasible plan over the
program's time.
"""
from __future__ import annotations

import gc
import time

from harness import RunError
from traffic import Builder, burst


class Driver:
    def __init__(self, setup, run):
        self.s, self.run = setup, run
        self.t = setup.traffic
        self.cycle = float(self.t["cycle_s"])
        self.builder = None

    def prepare(self) -> None:
        self.s.warm_kernels(self.t["warm_rows"])
        self.s.warm_fleet(0.0)
        self.builder = Builder()

    def window(self) -> None:
        s, run, inst, orch = self.s, self.run, self.s.inst, self.s.orch
        horizon = float(s.config["horizon_s"])
        tail = float(s.config["tail_s"])
        planned = attempted = 0
        busy = gen = 0                     # program / generator time, ns
        run.first_span = inst.mark()
        full0 = gc.get_stats()[2]["collections"]
        t0 = time.perf_counter_ns()
        k = 0
        while True:
            g0 = time.perf_counter_ns()
            dues = burst(s.seed, k, self.t)
            if dues[-1].t + tail > horizon:
                raise RunError(
                    f"wave {k} would run past T_alloc's horizon "
                    f"({dues[-1].t:.1f} s + {tail:.0f} s tail > {horizon:.0f} s)")
            paused = gc.isenabled()
            gc.disable()
            try:
                apps = [self.builder.app(d) for d in dues]
            finally:
                if paused:
                    gc.enable()
            times = [d.t for d in dues]
            w0 = time.perf_counter_ns()
            gen += w0 - g0
            orch.submit_batch(apps, times, fused=True)
            plans = inst.last_plans
            run.waves.append(inst.last["orchestrate_batch"])
            until = (k + 1) * self.cycle
            orch.step(until)
            busy += time.perf_counter_ns() - w0
            run.steps.append(inst.last["step"])
            run.schedule.append(("submit", apps, times, plans))
            run.schedule.append(("step", until))
            attempted += len(apps)
            planned += sum(1 for p in plans if p.feasible)
            k += 1
            if busy >= run.seconds * 1e9:
                break
        run.window_ns = (t0, time.perf_counter_ns())
        run.notes["full collections in the window"] = (
            gc.get_stats()[2]["collections"] - full0)
        run.attempted = attempted
        run.failed = attempted - planned
        run.e2e["plans_per_s"] = planned / (busy / 1e9)
        run.notes["waves"] = k
        run.notes["generator s (clock stopped)"] = gen / 1e9
