"""Plain reference of what a cell's timed path produces.

Written from the paper's Algorithm 1 and the engine's documented protocol,
and importing nothing of the program.  It takes the fleet as data (the
device table and the profiled ``ED_mc`` interference table, which the
configuration generates from the seed) and the schedule the window drove
(which waves were submitted, with which arrivals, and when the engine was
stepped).  From those it keeps its own fleet state and checks three layers:

* pricing (Eq. 1 execution latency under the T_alloc counts, Eq. 2 model
  upload and parent-output transfer over the bottleneck link, and the
  failure probability ``F(T_i) = 1 - exp(-lambda * (t - join + L))``);
* selection (IBDASH lines 16-41: ascending stable order over the feasible
  devices, then replicate while the weighted joint score keeps falling);
* the bookkeeping of applied plans and executed replicas in T_alloc and the
  model caches, replayed event by event with the engine's noise stream.

Like a served model's reference that reads the served tokens, the replay
follows the program's decisions (its chosen devices and the estimates its
plans carry) so that one early difference cannot cascade; every sampled
decision and estimate is compared with the reference's own.
"""
from __future__ import annotations

import heapq
import itertools
import math
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclass
class Fleet:
    """The deployment as data: one entry per device plus the ED_mc table."""

    classes: np.ndarray      # (D,) device class ids
    mem_total: np.ndarray    # (D,) bytes
    lams: np.ndarray         # (D,) failure rates
    up: np.ndarray           # (D,) uplink bytes/s
    down: np.ndarray         # (D,) downlink bytes/s
    tiers: np.ndarray        # (D,) tier ids
    join: np.ndarray         # (D,) join times
    alive_until: np.ndarray  # (D,) ground-truth departure times
    backhaul: np.ndarray     # (T, T) inter-tier rates (all inf = none)
    model_source: Optional[int]
    base: np.ndarray         # (P, N) unloaded latency c[p, i]
    slope: np.ndarray        # (P, N, N) interference slopes m[p, i, j]
    dt: float
    horizon: float

    @property
    def n_devices(self) -> int:
        return int(self.classes.shape[0])

    @property
    def n_types(self) -> int:
        return int(self.base.shape[1])

    @property
    def n_buckets(self) -> int:
        return int(math.ceil(self.horizon / self.dt)) + 1

    @classmethod
    def read(cls, devices, base, slope, backhaul, model_source, dt, horizon):
        """Copy the raw device attributes into plain arrays."""
        tiers = np.array([d.tier for d in devices], np.int64)
        n_tiers = int(tiers.max()) + 1
        bh = (np.full((n_tiers, n_tiers), np.inf) if backhaul is None
              else np.array(backhaul, np.float64))
        return cls(
            classes=np.array([d.cls for d in devices], np.int64),
            mem_total=np.array([d.mem_total for d in devices], np.float64),
            lams=np.array([d.lam for d in devices], np.float64),
            up=np.array([d.up_bw for d in devices], np.float64),
            down=np.array([d.down_bw for d in devices], np.float64),
            tiers=tiers,
            join=np.array([d.join_time for d in devices], np.float64),
            alive_until=np.array([d.alive_until for d in devices], np.float64),
            backhaul=bh,
            model_source=model_source,
            base=np.array(base, np.float64),
            slope=np.array(slope, np.float64),
            dt=float(dt),
            horizon=float(horizon),
        )

    def link_from(self, s: int) -> np.ndarray:
        """(D,) bottleneck rate of the link s -> d: the sender's uplink, the
        receiver's downlink and the tier backhaul; +inf to itself."""
        row = np.minimum(self.up[s], self.down)
        row = np.minimum(row, self.backhaul[self.tiers[s], self.tiers])
        row[s] = np.inf
        return row

    def upload_rate(self) -> np.ndarray:
        """(D,) rate at which a model artifact reaches each device."""
        if self.model_source is None:
            return self.down
        return self.link_from(self.model_source)


@dataclass(frozen=True)
class Task:
    """One task of an application, as the reference reads it."""

    name: str
    ttype: int
    deps: Tuple[str, ...]
    out_bytes: float
    model_id: Optional[str]
    model_bytes: float
    mem_bytes: float


@dataclass
class App:
    """An application instance: its tasks and its stages (longest-path
    levels, tasks in topological order inside each)."""

    tasks: Dict[str, Task]
    stages: List[List[str]]

    @classmethod
    def read(cls, dag) -> "App":
        tasks = {
            n: Task(n, int(t.ttype), tuple(t.deps), float(t.out_bytes),
                    t.model_id, float(t.model_bytes), float(t.mem_bytes))
            for n, t in dag.tasks.items()
        }
        return cls(tasks=tasks, stages=_stages(tasks))


def _stages(tasks: Dict[str, Task]) -> List[List[str]]:
    """Kahn's order with a FIFO frontier in insertion order, then level =
    1 + deepest parent."""
    indeg = {n: len(t.deps) for n, t in tasks.items()}
    children: Dict[str, List[str]] = {n: [] for n in tasks}
    for t in tasks.values():
        for d in t.deps:
            children[d].append(t.name)
    frontier = [n for n in tasks if indeg[n] == 0]
    order: List[str] = []
    while frontier:
        n = frontier.pop(0)
        order.append(n)
        for c in children[n]:
            indeg[c] -= 1
            if indeg[c] == 0:
                frontier.append(c)
    level: Dict[str, int] = {}
    for n in order:
        deps = tasks[n].deps
        level[n] = 0 if not deps else 1 + max(level[d] for d in deps)
    out: List[List[str]] = [[] for _ in range(max(level.values()) + 1)]
    for n in order:
        out[level[n]].append(n)
    return out


@dataclass
class Choice:
    """A decision as a plan states it: devices (primary first), the
    estimates each replica carries (exec, upload, transfer, pf), and the
    task's offset from the arrival."""

    devices: Tuple[int, ...]
    estimates: Tuple[Tuple[float, float, float, float], ...]
    est_start: float


@dataclass
class PlanView:
    """What a plan says about one application instance."""

    now: float
    feasible: bool
    tasks: Dict[str, Choice]

    @classmethod
    def read(cls, plan, suffix: str = "") -> Optional["PlanView"]:
        """The plan with its tasks under their app's own names: each name
        has to end in the instance's ``suffix`` (None when one does not:
        the plan belongs to another instance)."""
        k = len(suffix)
        if any(not n.endswith(suffix) for n in plan.placement.tasks):
            return None
        return cls(
            now=float(plan.now),
            feasible=bool(plan.placement.feasible),
            tasks={
                (n[:-k] if k else n): Choice(
                    devices=tuple(int(r.did) for r in tp.replicas),
                    estimates=tuple(
                        (float(r.est_exec), float(r.est_upload),
                         float(r.est_transfer), float(r.pred_fail))
                        for r in tp.replicas
                    ),
                    est_start=float(tp.est_start),
                )
                for n, tp in plan.placement.tasks.items()
            },
        )


def ibdash_select(total, pf, feasible, alpha, beta, gamma) -> Tuple[int, ...]:
    """Algorithm 1 lines 16-41 for one task."""
    cand = np.flatnonzero(feasible)
    if cand.size == 0:
        return ()
    order = cand[np.argsort(total[cand], kind="stable")]
    best = total[order[0]]
    l_ref = max(best, 1e-9)
    devices = [int(order[0])]
    comb = pf[order[0]]
    w_s = alpha * (best / l_ref) + (1 - alpha) * comb
    reps = 0
    qi = 1
    while comb >= beta and reps < gamma and qi < order.size:
        d = order[qi]
        qi += 1
        new_fail = comb * pf[d]
        w_new = alpha * (total[d] / l_ref) + (1 - alpha) * new_fail
        if w_new > w_s:
            break
        devices.append(int(d))
        comb, w_s = new_fail, w_new
        reps += 1
    return tuple(devices)


def weighted_score(devices, total, pf, l_ref, alpha) -> float:
    """Line 29's WeightS after accepting ``devices`` in order."""
    comb = 1.0
    for d in devices:
        comb *= pf[d]
    return alpha * (total[devices[-1]] / l_ref) + (1 - alpha) * comb


@dataclass
class Readings:
    """The numbers compared, accumulated over a run."""

    plan_gap: float = 0.0        # widest relative gap of a sampled plan
    unplanned: int = 0           # instances without a sound plan
    state_gap: float = 0.0       # T_alloc / outcome gap after the replay
    checked_tasks: int = 0
    checked_apps: int = 0
    worst: str = ""

    def gap(self, value: float, where: str) -> None:
        if not value <= self.plan_gap:          # NaN counts as worst
            self.plan_gap = value if value == value else math.inf
            self.worst = where


@dataclass
class _Run:
    app: App
    plan: PlanView
    stage_idx: int = 0
    pending: int = 0
    inflight: Dict[str, int] = field(default_factory=dict)
    done: Dict[str, bool] = field(default_factory=dict)
    started: set = field(default_factory=set)
    live: set = field(default_factory=set)
    failed: bool = False
    finished: float = math.nan


class ReferenceSim:
    """Fleet state under the replayed schedule, and the per-wave check.

    ``dtype`` is the precision of the reference's pricing and selection:
    float64 is what the configuration states, float32 is the control."""

    ARRIVAL, TASK_END = 0, 1

    def __init__(self, fleet: Fleet, *, seed: int, noise_sigma: float,
                 alpha: float, beta: float, gamma: int):
        self.f = fleet
        self.alpha, self.beta, self.gamma = alpha, beta, gamma
        self.alloc = np.zeros(
            (fleet.n_devices, fleet.n_types, fleet.n_buckets), np.float64
        )
        self.cache: List[OrderedDict] = [OrderedDict() for _ in range(fleet.n_devices)]
        self.mem_free = fleet.mem_total.copy()
        self.noise = np.random.default_rng(seed + 17)
        self.noise_sigma = noise_sigma
        self.events: list = []
        self._seq = itertools.count()
        self._rid = itertools.count()
        self.active: Dict[int, tuple] = {}
        self.now = 0.0
        self.completed = 0
        self.lost = 0
        self.upload_rate = fleet.upload_rate()
        self._links: Dict[int, np.ndarray] = {}
        self._dt = fleet.dt
        self._last = fleet.n_buckets - 1
        self.top = 0                 # one past the last bucket ever written

    # -- T_alloc -----------------------------------------------------------
    def bucket(self, t: float) -> int:
        return min(max(int(t / self._dt), 0), self._last)

    def _span(self, t0: float, t1: float):
        h = self.f.horizon
        if t1 > h:
            t1 = h
        if t0 >= h:
            return None
        b0 = self.bucket(t0)
        return b0, max(self.bucket(t1), b0 + 1)

    def add(self, did, ttype, t0, t1, w=1.0) -> None:
        span = self._span(t0, t1)
        if span is not None:
            self.alloc[did, ttype, span[0]:span[1]] += w
            self.top = max(self.top, span[1])

    def cancel_from(self, did, ttype, t0, t1, cut) -> None:
        span = self._span(t0, t1)
        if span is None or cut >= min(t1, self.f.horizon):
            return
        b0, b1 = span
        bc = min(max(self.bucket(cut), b0), b1)
        self.alloc[did, ttype, bc:b1] -= 1.0

    # -- model caches (LRU, evict least recently used first) ----------------
    def admit(self, did: int, model_id: str, size: float) -> bool:
        cache = self.cache[did]
        if model_id in cache:
            cache.move_to_end(model_id)
            return True
        if size > self.f.mem_total[did]:
            return False
        while self.mem_free[did] < size and cache:
            _, evicted = cache.popitem(last=False)
            self.mem_free[did] += evicted
        if self.mem_free[did] < size:
            return False
        cache[model_id] = size
        self.mem_free[did] -= size
        return True

    # -- the engine's protocol ---------------------------------------------
    def _push(self, t, kind, payload) -> None:
        heapq.heappush(self.events, (t, next(self._seq), kind, payload))

    def submit(self, apps: Sequence[App], times: Sequence[float],
               plans: Sequence[PlanView]) -> None:
        for app, t, plan in zip(apps, times, plans):
            self._push(t, self.ARRIVAL, (app, plan))

    def step(self, until: float) -> None:
        while self.events and self.events[0][0] <= until:
            t, _, kind, payload = heapq.heappop(self.events)
            self.now = t
            if kind == self.ARRIVAL:
                self._arrive(*payload)
            else:
                self._task_end(*payload)
        self.now = until

    def _ordered(self, app: App, plan: PlanView) -> List[str]:
        return [n for stage in app.stages for n in stage if n in plan.tasks]

    def _arrive(self, app: App, plan: PlanView) -> None:
        if not plan.feasible:
            self.lost += 1
            return
        for n in self._ordered(app, plan):
            task, ch = app.tasks[n], plan.tasks[n]
            start = plan.now + ch.est_start
            for did, est in zip(ch.devices, ch.estimates):
                self.add(did, task.ttype, start, start + sum(est[:3]))
                if task.model_id is not None:
                    # models fit every device of these fleets after eviction
                    self.admit(did, task.model_id, task.model_bytes)
        self._start_stage(_Run(app=app, plan=plan))

    def _start_stage(self, run: _Run) -> None:
        app, plan = run.app, run.plan
        while run.stage_idx < len(app.stages):
            todo = [n for n in app.stages[run.stage_idx]
                    if n in plan.tasks and not run.done.get(n, False)]
            if todo:
                run.pending = len(todo)
                for n in todo:
                    self._start_task(run, n)
                return
            run.stage_idx += 1
        self._finish(run, failed=False)

    def _start_task(self, run: _Run, name: str) -> None:
        task, ch = run.app.tasks[name], run.plan.tasks[name]
        run.inflight[name] = 0
        run.started.add(name)
        start = run.plan.now + ch.est_start
        for did, est in zip(ch.devices, ch.estimates):
            self.add(did, task.ttype, start, start + sum(est[:3]), w=-1.0)
            self._launch(run, name, did, est)

    def _launch(self, run: _Run, name: str, did: int, est) -> None:
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
        self.active[rid] = (run, did, task.ttype, self.now, end)
        run.live.add(rid)
        self._push(end, self.TASK_END,
                   (run, name, rid, end <= self.f.alive_until[did]))

    def _task_end(self, run: _Run, name: str, rid: int, ok: bool) -> None:
        if self.active.pop(rid, None) is None:
            return
        run.live.discard(rid)
        if run.failed or run.done.get(name, False):
            return
        run.inflight[name] -= 1
        if ok:
            run.done[name] = True
            run.pending -= 1
            if run.pending == 0:
                run.stage_idx += 1
                self._start_stage(run)
        elif run.inflight[name] == 0:
            self._finish(run, failed=True)

    def _finish(self, run: _Run, failed: bool) -> None:
        if not math.isnan(run.finished):
            return
        if failed:
            for rid in sorted(run.live):
                info = self.active.pop(rid, None)
                if info is not None:
                    _, did, ttype, t0, t1 = info
                    self.cancel_from(did, ttype, t0, t1, self.now)
            run.live.clear()
            for n, ch in run.plan.tasks.items():
                if n in run.started:
                    continue
                ttype = run.app.tasks[n].ttype
                start = run.plan.now + ch.est_start
                for est, did in zip(ch.estimates, ch.devices):
                    self.add(did, ttype, start, start + sum(est[:3]), w=-1.0)
            self.lost += 1
        else:
            self.completed += 1
        run.failed = failed
        run.finished = self.now

    # -- pricing and selection -----------------------------------------------
    def _link(self, s: int) -> np.ndarray:
        row = self._links.get(s)
        if row is None:
            row = self._links[s] = self.f.link_from(s)
        return row

    def price(self, task: Task, t_start: float, parents, alive, dtype):
        """(exec, upload, transfer, total, pf, feasible) over the fleet for
        ``task`` starting at ``t_start`` with parents placed on ``parents``
        (a list of (out_bytes, device))."""
        f = self.f
        counts = np.maximum(self.alloc[:, :, self.bucket(t_start)], 0.0)
        tt = task.ttype
        cls = f.classes
        exe = (f.base[cls, tt].astype(dtype)
               + np.einsum("dj,dj->d", f.slope[cls, tt, :].astype(dtype),
                           counts.astype(dtype)))
        if task.model_id is None:
            up = np.zeros(f.n_devices, dtype)
        else:
            missing = np.array([task.model_id not in c for c in self.cache])
            up = np.where(missing, dtype(task.model_bytes)
                          / self.upload_rate.astype(dtype), dtype(0.0))
        tr = np.zeros(f.n_devices, dtype)
        for ob, src in parents:
            tr = tr + dtype(ob) / self._link(src).astype(dtype)
        total = exe + up + tr
        window = (dtype(t_start) - f.join.astype(dtype)) + total
        pf = dtype(1.0) - np.exp(-f.lams.astype(dtype) * window)
        feasible = (f.mem_total >= task.mem_bytes + task.model_bytes) & alive
        return exe, up, tr, total, pf, feasible

    def check_app(self, app: App, arrival: float, wave_now: float,
                  plan: Optional[PlanView], out: Readings, *,
                  dtype=np.float64, control: Optional[Readings] = None) -> None:
        """Compare one planned instance with the reference at the state the
        wave was planned against.  With ``control`` set, the reference
        computed in ``dtype`` is put in the program's place and read into
        ``control`` (the control reading) beside the program's ``out``."""
        alive = wave_now < self.f.alive_until
        if plan is None:
            out.unplanned += 1
            return
        if not plan.feasible:
            # sound only where some task has no memory-feasible live device
            need = [t.mem_bytes + t.model_bytes for t in app.tasks.values()]
            if all((self.f.mem_total >= m)[alive].any() for m in need):
                out.unplanned += 1
            return
        out.checked_apps += 1
        offset = 0.0
        for stage in app.stages:
            t_start = arrival + offset
            stage_lat = 0.0
            for name in stage:
                task = app.tasks[name]
                ch = plan.tasks.get(name)
                if ch is None or not ch.devices:
                    out.unplanned += 1
                    return
                parents = [(app.tasks[d].out_bytes, plan.tasks[d].devices[0])
                           for d in task.deps if d in plan.tasks]
                exe, up, tr, total, pf, feasible = self.price(
                    task, t_start, parents, alive, np.float64)
                out.checked_tasks += 1
                ref = ibdash_select(total, pf, feasible, self.alpha,
                                    self.beta, self.gamma)
                if not ref:
                    out.unplanned += 1
                    return
                self._read(name, ch, ref, offset, exe, up, tr, total, pf,
                           feasible, out)
                if control is not None:
                    c = self.price(task, t_start, parents, alive, dtype)
                    cdev = ibdash_select(c[3], c[4], c[5], self.alpha,
                                         self.beta, self.gamma)
                    cch = Choice(
                        devices=cdev,
                        estimates=tuple(
                            (float(c[0][d]), float(c[1][d]), float(c[2][d]),
                             float(c[4][d])) for d in cdev),
                        est_start=ch.est_start,
                    )
                    self._read(name, cch, ref, offset, exe, up, tr, total,
                               pf, feasible, control)
                stage_lat = max(stage_lat, float(total[ch.devices[0]]))
            offset += stage_lat

    def _read(self, name, ch: Choice, ref, offset, exe, up, tr, total, pf,
              feasible, out: Readings) -> None:
        where = name
        for did in ch.devices:
            if not (0 <= did < feasible.size) or not feasible[did]:
                out.gap(math.inf, f"{where}: infeasible device {did}")
                return
        feas_total = total[feasible]
        best = float(feas_total.min())
        l_ref = max(best, 1e-9)
        out.gap((float(total[ch.devices[0]]) - best) / l_ref,
                f"{where}: primary {ch.devices[0]} vs {ref[0]}")
        w_ref = weighted_score(ref, total, pf, l_ref, self.alpha)
        w_got = weighted_score(ch.devices, total, pf, l_ref, self.alpha)
        out.gap(abs(w_got - w_ref) / max(abs(w_ref), 1e-12),
                f"{where}: replicas {ch.devices} vs {ref}")
        for did, est in zip(ch.devices, ch.estimates):
            want = (exe[did], up[did], tr[did], pf[did])
            for got, w in zip(est, want):
                w = float(w)
                out.gap(abs(got - w) / max(abs(w), 1e-300) if w else abs(got),
                        f"{where}: estimate on {did}")
        out.gap(abs(ch.est_start - offset) / max(offset, 1e-9),
                f"{where}: stage offset")

    def state_gap(self, alloc: np.ndarray, completed: int, lost: int) -> float:
        """Largest difference from the program's T_alloc and outcome
        counts (both integer-valued, so any difference is a fault).  Past
        the last bucket the replay wrote, the program's T_alloc must be
        all zero."""
        if alloc.shape != self.alloc.shape:
            return math.inf
        top = self.top
        gap = 0.0
        for d0 in range(0, alloc.shape[0], 256):
            a = alloc[d0:d0 + 256, :, :top]
            b = self.alloc[d0:d0 + 256, :, :top]
            if a.size:
                gap = max(gap, float(np.max(np.abs(a - b))))
        rest = alloc[:, :, top:]
        if rest.size and rest.any():
            gap = max(gap, float(np.max(np.abs(rest))))
        return gap + abs(completed - self.completed) + abs(lost - self.lost)
