"""The garbage collector's passes inside the window's waves and steps: the
program's ``gc.collect`` records (``repro.obs.hostspans``, one per pass of
CPython's cyclic collector while the program's spans are kept), read
inside the benchmark's ``orchestrate_batch`` and ``step`` spans.

A pass is charged to whatever span is open when it fires, so this is the
share of the program-span metrics that is the collector.  A checkout whose
program keeps no such record, or a run that kept none, gives None.
"""
from __future__ import annotations

from typing import Iterable, Optional

import program_spans  # the readers put this directory on sys.path


def pause_ms(run, generations: Iterable[int] = (0, 1, 2)) -> Optional[float]:
    """Mean over the window's waves of the collector's time, in passes of
    ``generations``, inside each wave's ``orchestrate_batch`` span and its
    ``step`` span."""
    kept = program_spans._kept(("gc.collect",))
    if kept is None or not run.waves:
        return None
    gens = set(generations)
    ns = sum(s.t1 - s.t0
             for idx in (run.waves, run.steps)
             for per in program_spans._per_span(run, idx, kept)
             for s in per if s.attrs["generation"] in gens)
    return ns / len(run.waves) / 1e6
