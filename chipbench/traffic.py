"""The load generator: one general generator read by every traffic file.

The arrival process follows the repository's generator of the paper's
cycle burst (``repro.sim.runner._make_workload``), with a keyed rng per
wave as ``repro.stream.arrivals`` keys one per stream, and one change for
steadiness: every seed gets the same number of instances of each
application, in a different order and at different instants.  A burst
holds exactly ``instances / 4`` of each Fig. 6 app.

Applications are instantiated from the program's Fig. 6 app builders, the
workload's data, with instance-unique task names.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np

APPS = ("lightgbm", "mapreduce", "video", "matrix")


def rng(seed: int, *keys: int) -> np.random.Generator:
    """One generator per (seed, keys): adding a stream or a wave never
    reshuffles another."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *map(int, keys)]))


def kinds(r: np.random.Generator, n: int, apps: Sequence[str] = APPS) -> List[str]:
    """``n`` app names, as equal in count as ``n`` allows, shuffled."""
    base = [apps[i % len(apps)] for i in range(n)]
    return [base[i] for i in r.permutation(n)]


@dataclass
class Due:
    """One arrival: when it is due (seconds from the window's start, the
    sim clock), which app, and a unique id."""

    t: float
    kind: str
    uid: int


def burst(seed: int, wave: int, traffic: dict) -> List[Due]:
    """The paper's cycle burst: ``instances`` arrivals spread uniformly over
    the first ``arrival_window_s`` of cycle ``wave``, sorted by time."""
    r = rng(seed, 1, wave)
    n = int(traffic["instances"])
    t0 = wave * float(traffic["cycle_s"])
    offs = np.sort(r.uniform(0.0, float(traffic["arrival_window_s"]), n))
    names = kinds(r, n)
    return [Due(float(t0 + o), k, wave * n + i)
            for i, (o, k) in enumerate(zip(offs.tolist(), names))]


class Builder:
    """Instantiates apps from the program's Fig. 6 builders, one base DAG
    per kind, relabelled per instance."""

    def __init__(self):
        from repro.sim.apps import APP_BUILDERS

        self.base: Dict[str, object] = {k: APP_BUILDERS[k]() for k in APPS}

    def app(self, d: Due):
        return self.base[d.kind].relabel(f"#{d.uid}")
