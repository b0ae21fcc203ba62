"""What the churn cell's readers share: the program's spans and the
engine's per-kind timed counters inside the benchmark's ``step`` spans,
where a departure's replans run.  A program without such a span or
counter, or a run that kept none, gives None.
"""
from __future__ import annotations

from typing import Iterable, Optional

import program_spans  # the readers put this directory on sys.path


def step_counter_ms(run, attr: str) -> Optional[float]:
    """``program_spans.step_ms``, or None where no ``engine.step`` span
    carries ``attr`` (a program without that counter)."""
    kept = program_spans._kept(["engine.step"])
    if kept is None or not any(attr in s.attrs for s in kept):
        return None
    return program_spans.step_ms(run, attr)


def step_span_ms(run, names: Iterable[str]) -> Optional[float]:
    """Mean over the window's steps of the time in program spans ``names``
    inside each ``step`` span."""
    kept = program_spans._kept(names)
    if kept is None or not run.steps:
        return None
    per = program_spans._per_span(run, run.steps, kept)
    return sum(s.t1 - s.t0 for w in per for s in w) / len(per) / 1e6
