"""The program's own wall-clock spans (``repro.obs.hostspans``), read
inside the benchmark's spans of the window.

The program keeps them while a profiler session is active, which in a
traced run is exactly the window, on ``time.perf_counter_ns`` like the
benchmark's own spans, so a program span belongs to the wave or step whose
benchmark span holds it.  A checkout whose program has no such recorder,
or a run that kept nothing (untraced), gives None.
"""
from __future__ import annotations

import bisect
from typing import Iterable, List, Optional, Sequence


def _kept(names: Iterable[str]) -> Optional[list]:
    try:
        from repro.obs import hostspans
    except ImportError:
        return None
    names = set(names)
    kept = [s for s in hostspans.records() if s.name in names]
    return kept or None


def _per_span(run, idx: Sequence[int], kept: list) -> List[list]:
    """``kept`` shared out among the benchmark spans ``run.spans[i]``,
    ``i`` in ``idx`` (in time order, disjoint)."""
    outer = [run.spans[i] for i in idx]
    starts = [sp.t0 for sp in outer]
    out: List[list] = [[] for _ in outer]
    for s in kept:
        j = bisect.bisect_right(starts, s.t0) - 1
        if j >= 0 and s.t1 <= outer[j].t1:
            out[j].append(s)
    return out


def wave_ms(run, names: Iterable[str]) -> Optional[float]:
    """Mean over the window's waves of the time in program spans ``names``
    inside each wave's ``orchestrate_batch`` span."""
    kept = _kept(names)
    if kept is None or not run.waves:
        return None
    per = _per_span(run, run.waves, kept)
    return sum(sum(s.t1 - s.t0 for s in w) for w in per) / len(per) / 1e6


def step_ms(run, attr: str) -> Optional[float]:
    """The window's total of the engine's timed counter carried as ``attr``
    (nanoseconds) by its ``engine.step`` spans, over the number of steps."""
    kept = _kept(["engine.step"])
    if kept is None or not run.steps:
        return None
    per = _per_span(run, run.steps, kept)
    return sum(s.attrs.get(attr, 0) for w in per for s in w) \
        / len(per) / 1e6
