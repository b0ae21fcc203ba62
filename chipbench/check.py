"""How ``correct`` is decided: the window's outputs against the reference.

After the window (and after the device's peak memory was read) the
recorded schedule is replayed through the reference of the
configuration's deployment module (``deployments/<name>.py``; the default
``paper`` replays through :class:`reference.ReferenceSim`).  The replay
takes ``submit`` and ``step`` ops here; an op of any other kind goes to
the reference's ``replay_<kind>``, and a kind it has no handler for fails
the run.  A sample of the planned instances, drawn from the seed, has
every task's pricing and selection recomputed at the state its wave was
planned against; the whole replay's T_alloc and outcome counts are
compared with the program's at the end.  Three numbers, each with its
limit from the configuration file:

* ``plan_gap``  — the widest relative gap of a sampled plan from the
  reference: a chosen device's Eq. 2 latency above the best feasible one,
  its weighted joint score (line 29) against the reference selection's,
  and each estimate the plan carries (execution, upload, transfer,
  ``F(T_i)``, stage offset) against the reference's;
* ``unplanned`` — instances submitted without a plan, or planned
  infeasible while the reference finds devices for every task (exact);
* ``state_gap`` — the largest difference of the program's T_alloc and its
  completed / lost counts from the replay's (integer-valued, exact);

and whatever further numbers the deployment's reference returns from
``checks()``, each with its limit from the same file.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from harness import RunError
from reference import App, PlanView, Readings
from traffic import Builder, rng


def _sample(run) -> set:
    """(schedule index, position) of the instances whose plans are checked:
    ``check_instances`` drawn from the seed, with a longest app (lightgbm,
    four stages) always among them."""
    slots = [(i, j) for i, op in enumerate(run.schedule) if op[0] == "submit"
             for j in range(len(op[1]))]
    k = min(int(run.setup.traffic["check_instances"]), len(slots))
    r = rng(run.setup.seed, 3)
    picked = {slots[j] for j in r.choice(len(slots), size=k, replace=False)}
    if not any(run.schedule[i][1][j].name == "lightgbm" for i, j in picked):
        for i, j in slots:
            if run.schedule[i][1][j].name == "lightgbm":
                picked.add((i, j))
                break
    return picked


def readings(run, control_dtype=None
             ) -> Tuple[Readings, Optional[Readings], Dict[str, float]]:
    """Replay the window; return the program's readings, with
    ``control_dtype`` the control's (the reference in that precision put
    in the program's place on the same sampled tasks), and the further
    numbers of the deployment's reference."""
    s = run.setup
    sim = s.deployment.reference(s)
    out = Readings()
    ctl = Readings() if control_dtype is not None else None
    picked = _sample(run)
    templates = {k: App.read(d) for k, d in Builder().base.items()}
    for i, op in enumerate(run.schedule):
        if op[0] == "step":
            sim.step(op[1])
            continue
        if op[0] != "submit":
            replay = getattr(sim, f"replay_{op[0]}", None)
            if replay is None:
                raise RunError(f"schedule op {op[0]!r}: the deployment's "
                               f"reference has no replay_{op[0]}")
            replay(*op[1:])
            continue
        _, apps, times, plans = op
        wave_now = min(times)
        # each instance is its kind's app with every task named "<task>#<uid>"
        ref_apps = [templates[a.name] for a in apps]
        views = [PlanView.read(pl, "#" + next(iter(a.tasks)).rsplit("#", 1)[1])
                 for a, pl in zip(apps, plans)]
        out.unplanned += len(apps) - len(views) + views.count(None)
        for j in range(len(apps)):
            if (i, j) in picked:
                sim.check_app(ref_apps[j], times[j], wave_now,
                              views[j] if j < len(views) else None, out,
                              dtype=control_dtype or np.float64, control=ctl)
        sim.submit(*zip(*[(a, t, v) for a, t, v in zip(ref_apps, times, views)
                          if v is not None]) if any(views) else ([], [], []))
    stats = s.orch.stats
    out.state_gap = sim.state_gap(s.cluster.alloc, stats.completed, stats.lost)
    return out, ctl, sim.checks()


def compare(run, control_dtype=None) -> dict:
    """Each number compared, with its limit.  With ``control_dtype`` the
    control's readings: the reference in that precision put in the
    program's place on the same sampled tasks.  The control plans only
    those tasks, so it has a ``plan_gap`` and no other number."""
    out, ctl, extra = readings(run, control_dtype)
    if out.checked_tasks == 0 and out.unplanned == 0:
        raise RunError("no planned task was compared with the reference")
    lim = run.setup.config["limits"]
    if ctl is not None:
        run.notes["control checked"] = (f"{ctl.checked_tasks} tasks; "
                                        f"widest at {ctl.worst}")
        return {"plan_gap": {"value": ctl.plan_gap, "limit": lim["plan_gap"]}}
    run.notes["checked"] = (f"{out.checked_apps} instances, "
                            f"{out.checked_tasks} tasks; widest at {out.worst}")
    checks = {
        "plan_gap": {"value": out.plan_gap, "limit": lim["plan_gap"]},
        "unplanned": {"value": out.unplanned, "limit": lim["unplanned"]},
        "state_gap": {"value": out.state_gap, "limit": lim["state_gap"]},
    }
    for name, value in extra.items():
        if name in checks or name not in lim:
            raise RunError(f"the reference's number {name!r} has no limit of "
                           "its own in the configuration's limits")
        checks[name] = {"value": value, "limit": lim[name]}
    return checks


def verdict(checks: dict) -> bool:
    """``correct``: every number within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())
