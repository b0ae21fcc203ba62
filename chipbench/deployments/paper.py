"""The paper's deployment, and the default of every configuration file
that names none: a fleet from ``repro.sim.make_cluster`` (the file's
``scenario``, ``n_devices``, ``profile_seed``, ``horizon_s`` and ``dt``),
its ``policy`` from ``make_policy``, and an ``Orchestrator`` with the
program's default recovery and no churn runtime.  Its reference is
:class:`reference.ReferenceSim` with the policy's parameters."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from reference import Fleet, ReferenceSim


def build(setup) -> None:
    """Build the cluster, the policy and the orchestrator from the seed, and
    read the fleet as data for the reference."""
    from repro.api import Orchestrator, make_policy
    from repro.sim import make_cluster, make_profile

    c = setup.config
    profile = make_profile(seed=int(c["profile_seed"]))
    setup.cluster = make_cluster(
        profile, scenario=c["scenario"], n_devices=int(c["n_devices"]),
        seed=setup.seed, horizon=float(c["horizon_s"]), dt=float(c["dt"]),
    )
    p = c["policy"]
    setup.policy = make_policy(p["name"], alpha=p["alpha"], beta=p["beta"],
                               gamma=p["gamma"], seed=setup.seed)
    setup.orch = Orchestrator(setup.cluster, setup.policy, seed=setup.seed,
                              noise_sigma=float(c["noise_sigma"]))
    cl = setup.cluster
    setup.fleet = Fleet.read(cl.devices, cl.model.base, cl.model.slope,
                             cl.backhaul, cl.model_source, cl.dt, cl.horizon)


def warm_fleet(setup, t: float) -> None:
    """Plan one instance of each app at sim time ``t`` and discard the
    plans (planning is pure; nothing is submitted).  The program allocates
    T_alloc lazily, so the first plan of a process touches every device's
    rows for the first time; a long-running service has paid that long
    before, so set-up pays it here."""
    from repro import api

    from traffic import APPS, Builder, Due

    b = Builder()
    apps = [b.app(Due(t, k, -1 - i)) for i, k in enumerate(APPS)]
    api.orchestrate_batch(apps, setup.cluster, setup.policy,
                          times=[t] * len(apps))


def warm_kernels(setup, rows: List[int]) -> None:
    """Compile (or load from the persistent cache) the placement kernel at
    each padded row count the traffic produces, through the same host
    entry ``decide_batch`` calls."""
    from repro.core.batched import ibdash_decide_batch

    p = setup.config["policy"]
    r = np.random.default_rng(0)
    for g in rows:
        total = r.uniform(1.0, 2.0, (g, 16))
        pf = r.uniform(0.0, 0.3, (g, 16))
        ibdash_decide_batch(total, pf, np.ones((g, 16), bool),
                            p["alpha"], p["beta"], p["gamma"])


class Reference(ReferenceSim):
    """The replay ``check.py`` runs: it replays ``submit`` and ``step`` ops
    itself.  A deployment's subclass adds ``replay_<kind>(*args)`` for each
    other op kind its driver records, and returns its further numbers from
    :meth:`checks`."""

    @classmethod
    def for_setup(cls, setup) -> "Reference":
        p = setup.config["policy"]
        return cls(setup.fleet, seed=setup.seed,
                   noise_sigma=float(setup.config["noise_sigma"]),
                   alpha=float(p["alpha"]), beta=float(p["beta"]),
                   gamma=int(p["gamma"]))

    def checks(self) -> Dict[str, float]:
        """Numbers compared beyond ``plan_gap``, ``unplanned`` and
        ``state_gap``, by name; each name needs a limit in the
        configuration's ``limits``.  Read after the whole replay."""
        return {}


def reference(setup) -> Reference:
    """The reference ``check.py`` replays the window against."""
    return Reference.for_setup(setup)
