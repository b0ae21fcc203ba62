"""Faults planted in the program under the timed path, one for each fault
a burst cell can have, to show that ``correct`` comes out false.

Each ``plant(setattr)`` replaces one callable through ``setattr(owner,
name, value)``: ``pytest``'s ``monkeypatch.setattr`` in the tests, or
:func:`planted` in ``control.py`` on the chip.  The number that each has
to fail is in :data:`FAULTS`.  No cell spans chips, so there is no
exchange between chips to leave out.
"""
from __future__ import annotations

import contextlib

import numpy as np


def state_unchanged(setattr) -> None:
    """Applying a plan returns a token and leaves T_alloc as it was."""
    from repro.core.cluster import ApplyToken, ClusterState

    setattr(ClusterState, "apply", lambda self, plan: ApplyToken())


def half_batch(setattr) -> None:
    """The planner plans the first half of each wave and drops the rest."""
    from repro import api

    real = api.orchestrate_batch

    def half(apps, cluster, policy, *, times, **kw):
        k = max(1, len(apps) // 2)
        return real(apps[:k], cluster, policy, times=times[:k], **kw)

    setattr(api, "orchestrate_batch", half)


def answer_altered(setattr) -> None:
    """Every decision's primary device becomes the worst feasible one."""
    from repro.core.batched import BatchedDecision
    from repro.core.policy import IBDASHPolicy

    real = IBDASHPolicy.decide_batch

    def worst(self, batch):
        dec = real(self, batch)
        out = []
        for b, devs in enumerate(dec.devices):
            g = batch.row_pool[b]
            total = np.where(batch.feasible_pool[g], batch.total_pool[g], -np.inf)
            out.append((int(np.argmax(total)),) + tuple(devs[1:]))
        return BatchedDecision(devices=tuple(out))

    setattr(IBDASHPolicy, "decide_batch", worst)


FAULTS = {
    "state_unchanged": (state_unchanged, "state_gap"),
    "half_batch": (half_batch, "unplanned"),
    "answer_altered": (answer_altered, "plan_gap"),
}


@contextlib.contextmanager
def planted(name: str):
    """Plant fault ``name`` for the block; put the program back after."""
    undo = []

    def setattr_(owner, attr, value):
        undo.append((owner, attr, vars(owner).get(attr), attr in vars(owner)))
        setattr(owner, attr, value)

    FAULTS[name][0](setattr_)
    try:
        yield
    finally:
        for owner, attr, old, had in reversed(undo):
            if had:
                setattr(owner, attr, old)
            else:
                delattr(owner, attr)
