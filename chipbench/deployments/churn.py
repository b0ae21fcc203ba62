"""The paper's personal-device churn (arXiv:2301.09278 §V-F): the
``paper`` deployment's fleet under exponential leave/rejoin cycles
(``repro.sim.churn.exponential_churn`` over the configuration's horizon,
from the configuration's fixed churn seed, every device's first lifetime
drawn from it too), and an ``Orchestrator`` whose engine replays them with
the configuration's recovery.

The reference regenerates the departure schedule from the configuration:
Table IV's ``lambda_3`` of each device's class times ``lambda_scale``, the
churn seed and the mean downtime.  It replays the engine's DEVICE_DOWN
(kill, return of the remaining occupancy, recovery hook), DEVICE_UP (fresh
join time, cold caches) and RECOVER events, each RECOVER one replan
against the state every earlier one left.  Like the arrivals' plans, the
replans the program made are followed (so that one difference cannot
cascade) and a sample of them is checked: ``build`` wraps the engine's
recovery so that each replan leaves what it planned in ``setup.replans``.
Two numbers beside the paper's three:

* ``churn_gap``       — the summed absolute difference of the counts of
  departures, rejoins, replica deaths, replans, recovered and lost
  instances (exact);
* ``replan_plan_gap`` — ``plan_gap``'s rule over up to 256 replans, drawn
  from the seed: each replanned task's choice and estimates against the
  reference's at the replan's instant, its pinned tasks pricing the
  transfers; a replan that is not the one the reference makes next reads
  infinite.
"""
from __future__ import annotations

import heapq
import math
from collections import OrderedDict
from dataclasses import dataclass, field, replace
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

from harness import load_module
from reference import Choice, Fleet, Readings, _Run, ibdash_select
from traffic import rng

paper = load_module(Path(__file__).with_name("paper.py"))

warm_fleet = paper.warm_fleet
warm_kernels = paper.warm_kernels
COUNTS = ("device_down", "device_up", "replica_deaths", "replans",
          "recovered", "lost")


def build(setup) -> None:
    """The paper's fleet, policy and orchestrator, with the churn schedule
    and the recovery the configuration states."""
    from repro.api import Orchestrator, make_policy
    from repro.sim import make_cluster, make_profile
    from repro.sim.churn import exponential_churn

    c, ch = setup.config, setup.config["churn"]
    profile = make_profile(seed=int(c["profile_seed"]))
    cl = setup.cluster = make_cluster(
        profile, scenario=c["scenario"], n_devices=int(c["n_devices"]),
        seed=setup.seed, horizon=float(c["horizon_s"]), dt=float(c["dt"]),
    )
    p = c["policy"]
    setup.policy = make_policy(p["name"], alpha=p["alpha"], beta=p["beta"],
                               gamma=p["gamma"], seed=setup.seed)
    setup.fleet = Fleet.read(cl.devices, cl.model.base, cl.model.slope,
                             cl.backhaul, cl.model_source, cl.dt, cl.horizon)
    schedule = exponential_churn(
        cl, horizon=float(c["horizon_s"]), seed=int(ch["seed"]),
        mean_downtime=float(ch["mean_downtime_s"]), resample_first=True,
    )
    setup.orch = Orchestrator(
        cl, setup.policy, seed=setup.seed,
        noise_sigma=float(c["noise_sigma"]), churn=schedule,
        recovery=ch["recovery"],
        detection_delay=float(ch["detection_delay_s"]),
        max_retries=int(ch["max_retries"]), salvage=int(ch["salvage"]),
    )
    setup.replans = _record_replans(setup.orch.engine)


def _record_replans(engine) -> list:
    """Wrap the engine's recovery so that each replan leaves, after it,
    ``(sim time, arrival, task, lost, the instance's task placements)``."""
    log: list = []
    real = engine.recovery.recover

    def recover(eng, run, tname):
        due = not (run.failed or run.done.get(tname, False))
        real(eng, run, tname)
        if due:
            log.append((eng.now, run.rec.arrival, tname, run.failed,
                        dict(run.placement.tasks)))

    engine.recovery.recover = recover
    return log


def rates(fleet: Fleet, churn: dict) -> np.ndarray:
    """Each device's departure rate: Table IV's ``lambda_3`` of its class
    times ``lambda_scale``."""
    return np.asarray(churn["lambda_3"], np.float64)[fleet.classes] \
        * float(churn["lambda_scale"])


def departures(lams, join, seed: int, horizon: float,
               mean_downtime: float) -> List[Tuple[float, int, bool, float]]:
    """The schedule as ``(t, device, leaves, until)`` sorted by time then
    device, one stream per device keyed by ``(seed, device)``: an
    Exp(lambda) lifetime from the device's join time, then
    Exp(``mean_downtime``) away and another Exp(lambda) lifetime, over
    ``horizon``; a join carries the device's next departure (``until``), a
    downtime past the horizon ends its events."""
    events = []
    for did, lam in enumerate(lams):
        r = np.random.default_rng(np.random.SeedSequence((int(seed), did)))
        lam = float(lam)

        def life() -> float:
            return float(r.exponential(1.0 / lam)) if lam > 0 else math.inf

        t_leave = float(join[did]) + life()
        while t_leave <= horizon:
            events.append((t_leave, did, True, math.inf))
            t_join = t_leave + float(r.exponential(mean_downtime))
            if t_join > horizon:
                break
            t_leave = t_join + life()
            events.append((t_join, did, False,
                           t_leave if t_leave <= horizon else math.inf))
    return sorted(events, key=lambda e: (e[0], e[1]))


@dataclass(eq=False)
class _ChurnRun(_Run):
    arrival: float = 0.0
    origins: Dict[str, float] = field(default_factory=dict)
    retries: Dict[str, int] = field(default_factory=dict)
    touched: bool = False


class Reference(paper.Reference):
    """The paper's replay with the churn runtime's events and the replan
    recovery (module docstring)."""

    DEVICE_DOWN, DEVICE_UP, RECOVER = 2, 3, 4

    @classmethod
    def for_setup(cls, setup) -> "Reference":
        ref = super().for_setup(setup)
        c, ch = setup.config, setup.config["churn"]
        stats = setup.orch.engine.stats
        ref.arm(ch, horizon=float(c["horizon_s"]), log=setup.replans,
                program={k: int(getattr(stats, k)) for k in COUNTS},
                sample=rng(setup.seed, 5))
        return ref

    def arm(self, churn: dict, *, horizon, log, program, sample) -> None:
        """Price with the configuration's rates, take the fleet's lifetimes
        over from the schedule (pushed before any arrival, as the engine
        does) and keep what the checks need."""
        f = self.f = replace(self.f, lams=rates(self.f, churn),
                             join=self.f.join.copy(),
                             alive_until=self.f.alive_until.copy())
        events = departures(f.lams, f.join, int(churn["seed"]), horizon,
                            float(churn["mean_downtime_s"]))
        f.alive_until[:] = math.inf
        for t, did, leaves, until in events:
            if leaves and not math.isfinite(f.alive_until[did]):
                f.alive_until[did] = t
            self._push(t, self.DEVICE_DOWN if leaves else self.DEVICE_UP,
                       (did, until))
        self.dev_active = [set() for _ in range(f.n_devices)]
        self.delay = float(churn["detection_delay_s"])
        self.max_retries = int(churn["max_retries"])
        self.log, self.cursor, self.program = log, 0, program
        self.counts = dict.fromkeys(COUNTS[:-1], 0)
        self.picked = set(sample.choice(len(log), size=min(256, len(log)),
                                        replace=False).tolist())
        self.replan_out = Readings()
        self.replan_control = Readings()     # the float32 reference's

    # -- the engine's protocol, with churn ---------------------------------
    def step(self, until: float) -> None:
        while self.events and self.events[0][0] <= until:
            t, _, kind, payload = heapq.heappop(self.events)
            self.now = t
            if kind == self.ARRIVAL:
                self._arrive(*payload)
            elif kind == self.TASK_END:
                self._task_end(*payload)
            elif kind == self.DEVICE_DOWN:
                self._down(payload[0])
            elif kind == self.DEVICE_UP:
                self._up(*payload)
            else:
                run, name = payload
                if not (run.failed or run.done.get(name, False)):
                    self._replan(run, name)
        self.now = until

    def _apply(self, run: _ChurnRun, names, choices, origin: float) -> None:
        """``apply``: each replica's provisional interval and its model."""
        for n in names:
            task, ch = run.app.tasks[n], choices[n]
            start = origin + ch.est_start
            for did, est in zip(ch.devices, ch.estimates):
                self.add(did, task.ttype, start, start + sum(est[:3]))
                if task.model_id is not None:
                    self.admit(did, task.model_id, task.model_bytes)

    def _arrive(self, app, plan) -> None:
        if not plan.feasible:
            self.lost += 1
            return
        run = _ChurnRun(app=app, plan=plan, arrival=self.now)
        self._apply(run, self._ordered(app, plan), plan.tasks, plan.now)
        self._start_stage(run)

    def _start_task(self, run: _ChurnRun, name: str) -> None:
        task, ch = run.app.tasks[name], run.plan.tasks[name]
        run.inflight[name] = 0
        run.started.add(name)
        start = run.origins.get(name, run.plan.now) + ch.est_start
        for did, est in zip(ch.devices, ch.estimates):
            self.add(did, task.ttype, start, start + sum(est[:3]), w=-1.0)
            self._launch(run, name, did, est)

    def _launch(self, run: _ChurnRun, name: str, did: int, est) -> None:
        task = run.app.tasks[name]
        counts = np.maximum(self.alloc[did, :, self.bucket(self.now)], 0.0)
        cls = self.f.classes[did]
        exec_t = float(self.f.base[cls, task.ttype]
                       + self.f.slope[cls, task.ttype] @ counts)
        if self.noise_sigma > 0:
            exec_t *= float(self.noise.lognormal(0.0, self.noise_sigma))
        end = self.now + (exec_t + est[1] + est[2])
        self.add(did, task.ttype, self.now, end)
        run.inflight[name] = run.inflight.get(name, 0) + 1
        rid = next(self._rid)
        self.active[rid] = (run, name, did, task.ttype, self.now, end)
        self.dev_active[did].add(rid)
        run.live.add(rid)
        self._push(end, self.TASK_END,
                   (run, name, rid, end <= self.f.alive_until[did]))

    def _retire(self, rid: int):
        info = self.active.pop(rid, None)
        if info is not None:
            self.dev_active[info[2]].discard(rid)
            info[0].live.discard(rid)
        return info

    def _task_end(self, run: _ChurnRun, name: str, rid: int, ok: bool) -> None:
        if self._retire(rid) is None:
            return
        if run.failed or run.done.get(name, False):
            return
        run.inflight[name] -= 1
        if ok:
            run.done[name] = True
            run.pending -= 1
            if run.pending == 0:
                run.stage_idx += 1
                self._start_stage(run)
            return
        run.touched = True
        self.counts["replica_deaths"] += 1
        if run.inflight[name] == 0:
            self._dead(run, name)

    def _dead(self, run: _ChurnRun, name: str) -> None:
        """The replan strategy's ``on_task_dead``."""
        n = run.retries.get(name, 0)
        if n >= self.max_retries:
            self._finish(run, failed=True)
            return
        run.retries[name] = n + 1
        self._push(self.now + self.delay, self.RECOVER, (run, name))

    def _cancel_provisional(self, run: _ChurnRun, names) -> None:
        for n in names:
            if n in run.started:
                continue
            ch, ttype = run.plan.tasks[n], run.app.tasks[n].ttype
            start = run.origins.get(n, run.plan.now) + ch.est_start
            for did, est in zip(ch.devices, ch.estimates):
                self.add(did, ttype, start, start + sum(est[:3]), w=-1.0)

    def _finish(self, run: _ChurnRun, failed: bool) -> None:
        if not math.isnan(run.finished):
            return
        if failed:
            for rid in sorted(run.live):
                info = self._retire(rid)
                if info is not None:
                    _, _, did, ttype, t0, t1 = info
                    self.cancel_from(did, ttype, t0, t1, self.now)
            run.live.clear()
            self._cancel_provisional(run, list(run.plan.tasks))
            self.lost += 1
        else:
            self.completed += 1
            if run.touched:
                self.counts["recovered"] += 1
        run.failed = failed
        run.finished = self.now

    def _down(self, did: int) -> None:
        self.counts["device_down"] += 1
        self.f.alive_until[did] = min(self.f.alive_until[did], self.now)
        dead = [self._retire(rid) for rid in sorted(self.dev_active[did])]
        for run, name, _, ttype, t0, t1 in dead:
            self.cancel_from(did, ttype, t0, t1, self.now)
            if run.failed or run.done.get(name, False):
                continue
            run.touched = True
            self.counts["replica_deaths"] += 1
            run.inflight[name] -= 1
            if run.inflight[name] == 0:
                self._dead(run, name)

    def _up(self, did: int, until: float) -> None:
        self.counts["device_up"] += 1
        self.f.join[did] = self.now
        self.f.alive_until[did] = until
        self.cache[did] = OrderedDict()
        self.mem_free[did] = self.f.mem_total[did]

    # -- replans -------------------------------------------------------------
    def _logged(self, t: float, run: _ChurnRun, name: str):
        """The program's next replan as ``(lost, placements)`` if it is
        this one, ``name`` of ``run`` at ``t``; any other reads infinite
        and gives None."""
        if self.cursor < len(self.log):
            lt, arrival, tname, lost, tasks = self.log[self.cursor]
            if (lt, arrival, tname.rsplit("#", 1)[0]) == (t, run.arrival,
                                                          name):
                self.cursor += 1
                return lost, _choices(tasks)
        self.replan_out.gap(math.inf, f"replan of {name} at t={t!r}: the "
                            "program's next replan is another")
        return None

    def _replan(self, run: _ChurnRun, name: str) -> None:
        """``ReplanRecovery.recover``: cancel the instance's unstarted
        remainder, plan the dead task and that remainder against the state
        every earlier replan left (the program's plan followed, a sample
        checked), apply it and start the dead task, or lose the
        instance."""
        t, idx = self.now, self.cursor
        got = self._logged(t, run, name)
        unstarted = [n for n in run.plan.tasks if n not in run.started]
        self._cancel_provisional(run, unstarted)
        for n in unstarted:
            del run.plan.tasks[n]
        pinned = {n: ch for n, ch in run.plan.tasks.items() if n != name}
        planned = [n for stage in run.app.stages for n in stage
                   if n not in pinned]
        if got is not None and idx in self.picked:
            self._check_replan(run, t, pinned, planned, got)
        self.counts["replans"] += 1
        if got is None or got[0] or any(n not in got[1] for n in planned):
            if got is not None and not got[0]:
                self.replan_out.gap(math.inf, f"replan at t={t!r}: tasks "
                                    "left unplanned")
            self._finish(run, failed=True)
            return
        self._apply(run, planned, got[1], t)
        for n in planned:
            run.plan.tasks[n] = got[1][n]
            run.origins[n] = t
        self._start_task(run, name)

    def _check_replan(self, run: _ChurnRun, t: float, pinned, planned,
                      got) -> None:
        """``check_app``'s rule for one replan at its instant: the
        instance's pinned tasks price the transfers, its planned tasks are
        walked stage by stage from ``t``.  The float32 reference is read
        into ``replan_control`` on the same tasks."""
        out, ctl = self.replan_out, self.replan_control
        alive = t < self.f.alive_until
        tasks = run.app.tasks
        if got[0]:
            need = [tasks[n].mem_bytes + tasks[n].model_bytes for n in planned]
            if all((self.f.mem_total >= m)[alive].any() for m in need):
                out.gap(math.inf, f"replan at t={t!r} lost a placeable "
                                  "instance")
            return
        choices = got[1]
        out.checked_apps += 1
        where = {n: ch.devices[0] for n, ch in pinned.items()}
        offset = 0.0
        for stage in run.app.stages:
            rows = [n for n in stage if n not in pinned]
            if not rows:
                continue
            t_start = t + offset
            stage_lat = 0.0
            for name in rows:
                task, ch = tasks[name], choices.get(name)
                if ch is None or not ch.devices:
                    out.gap(math.inf, f"replan {name}: no devices")
                    return
                parents = [(tasks[d].out_bytes, where[d])
                           for d in task.deps if d in where]
                exe, up, tr, total, pf, feasible = self.price(
                    task, t_start, parents, alive, np.float64)
                out.checked_tasks += 1
                ref = ibdash_select(total, pf, feasible, self.alpha,
                                    self.beta, self.gamma)
                if not ref:
                    out.gap(math.inf, f"replan {name}: no feasible device")
                    return
                self._read(f"replan {name}", ch, ref, offset, exe, up, tr,
                           total, pf, feasible, out)
                c = self.price(task, t_start, parents, alive, np.float32)
                cdev = ibdash_select(c[3], c[4], c[5], self.alpha, self.beta,
                                     self.gamma)
                if cdev:
                    ctl.checked_tasks += 1
                    self._read(f"replan {name}", Choice(
                        devices=cdev,
                        estimates=tuple((float(c[0][d]), float(c[1][d]),
                                         float(c[2][d]), float(c[4][d]))
                                        for d in cdev),
                        est_start=ch.est_start), ref, offset, exe, up, tr,
                        total, pf, feasible, ctl)
                stage_lat = max(stage_lat, float(total[ch.devices[0]]))
            for name in rows:
                where[name] = choices[name].devices[0]
            offset += stage_lat

    def checks(self) -> Dict[str, float]:
        if self.cursor < len(self.log):
            self.replan_out.gap(math.inf, f"the program replanned at t="
                                f"{self.log[self.cursor][0]!r}, the replay "
                                "did not")
        mine = {**self.counts, "lost": self.lost}
        return {
            "churn_gap": float(sum(abs(mine[k] - self.program[k])
                                   for k in COUNTS)),
            "replan_plan_gap": self.replan_out.plan_gap,
        }


def _choices(placements) -> Dict[str, Choice]:
    """A program's task placements as the reference reads a plan, under
    the app's own task names."""
    return {
        n.rsplit("#", 1)[0]: Choice(
            devices=tuple(int(r.did) for r in tp.replicas),
            estimates=tuple((float(r.est_exec), float(r.est_upload),
                             float(r.est_transfer), float(r.pred_fail))
                            for r in tp.replicas),
            est_start=float(tp.est_start),
        )
        for n, tp in placements.items()
    }


def reference(setup) -> Reference:
    """The reference ``check.py`` replays the window against."""
    return Reference.for_setup(setup)
