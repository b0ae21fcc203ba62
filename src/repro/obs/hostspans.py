"""Wall-clock spans and timed counters inside the planner, the policy and
the engine, on the profiler's clock.

The sim-clock :class:`~repro.obs.tracing.Tracer` says what happened to each
instance in simulated time; this recorder says where the host's time went
while the program planned and stepped.  Two kinds of site, both named in
:data:`HOST_SPAN_SCHEMA`:

  * **coarse spans** (``with hostspans.span("plan.context", B=...) as sp``)
    at layer boundaries that run a few dozen times per wave.  A recorded
    span keeps its name, start and end on ``time.perf_counter_ns``, its
    parent span, the id of the wave it belongs to (the ``plan.wave`` span
    of one ``orchestrate_batch`` call; every span nested in it shares that
    id) and its counts as attributes.  While a ``jax.profiler`` session is
    on it is also a ``jax.profiler.TraceAnnotation`` of the same name, so
    it lands on the profiler's host plane beside the device's events.
  * **timed counters** (``hostspans.tally("engine.arrival", n, ns)``) for
    per-event work that runs thousands of times per wave: the site sums
    ``(count, ns)`` in locals and hands the totals over once, so nothing is
    kept per call and no profiler event is made.

A third kind, also named there, comes from the runtime, not from a call
site: a ``gc.callbacks`` hook installed on import times every pass of
CPython's cyclic garbage collector and, while spans are kept, keeps one
``gc.collect`` record per pass (attributes ``generation``, ``collected``,
``uncollectable``) under the innermost open span, and tallies the timed
counter ``gc.pause``.  A pass is charged to whichever span is open when it
fires; these records say how much of that span was the collector.  The
hook runs in the middle of arbitrary allocations, so it never calls into
JAX or the profiler and makes no annotation; it cannot ask whether a
profiler session is on, so under a session alone it keeps the passes that
fire inside a recording span, and after :func:`enable` every pass (one
outside every span has no parent).  Otherwise it costs one flag check.

When it records: while a ``jax.profiler`` session is active
(``TraceAnnotation.is_enabled()``), or between :func:`enable` and
:func:`disable`.  Otherwise a coarse span costs one clock pair and a flag
check and keeps nothing; a counted site reads :func:`recording` once per
call of the loop it counts.  Every span measures its own duration either
way (``sp.ns``; :func:`last_ns` for the latest span of a name), which is
what the service's ``wave_plan_s`` and the engine's ``replan_time`` read.

Records go to a bounded buffer (the newest :data:`BUFFER`) that
:func:`clear` empties; timed counters go to a
:class:`~repro.obs.metrics.MetricsRegistry` (``<name>`` counts calls,
``<name>.ns`` their nanoseconds).  The recorder is process-wide and
assumes one planning thread.  Names are string literals from
:data:`HOST_SPAN_SCHEMA` at every call site (the ``span-parity`` lint rule
audits them); a recorded name outside it raises.
"""
from __future__ import annotations

import gc
import itertools
import sys
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

from .metrics import MetricsRegistry

__all__ = [
    "HOST_SPAN_SCHEMA",
    "BUFFER",
    "HostSpan",
    "span",
    "tally",
    "recording",
    "enable",
    "disable",
    "clear",
    "records",
    "timed",
    "last_ns",
]

# name -> one-line contract.  Spans and records first, then timed
# counters.  The span-parity lint rule requires every name passed to
# span()/tally() in src/repro to be a literal found here AND named in the
# test suite; _record() checks its name here at run time.  No name may
# equal one of the benchmark's own span names (window, orchestrate_batch,
# decide_batch, step) or start with "kernel:".
HOST_SPAN_SCHEMA: Dict[str, str] = {
    "plan.wave": "span: the whole of one orchestrate_batch call; its id is "
                 "the wave id of every span inside (attrs: apps, stages, "
                 "planned, infeasible)",
    "plan.snapshot": "span: the wave context builder's construction, the "
                     "fleet vectors at the planning instant (attrs: D)",
    "plan.screen": "span, per wave-stage: row enumeration and memory "
                   "screening (attrs: rows_in, rows_kept)",
    "plan.context": "span, per wave-stage: _WaveContextBuilder.batch, the "
                    "(G, D) pricing tensors (attrs: B, G)",
    "plan.assemble": "span, per wave-stage: decisions to Replica / "
                     "TaskPlacement and the stage fold; once more for the "
                     "final Plan list (attrs: rows)",
    "plan.replan": "span: one recovery replan, orchestrate(pinned=...) in "
                   "Engine._salvage or ReplanRecovery",
    "policy.decide": "span: the body of decide_batch of IBDASH (and its "
                     "subclasses) and of the base class's loop (attrs: B, "
                     "G, kernel)",
    "policy.select": "span: the IBDASH candidate queue, stable sort or "
                     "top-k and the gathers (attrs: G, D, n_scan)",
    "policy.kernel": "span: one placement kernel's pad, cast, jitted call "
                     "and read-back (attrs: G, rows)",
    "engine.step": "span: one Engine.run(until) (attrs: arrival, task_end, "
                   "device_down, device_up, recover, other, launches, "
                   "talloc_writes and the matching *_ns)",
    "gc.collect": "record: one pass of the cyclic garbage collector, from "
                  "the gc.callbacks hook, under the innermost open span "
                  "(attrs: generation, collected, uncollectable)",
    "engine.arrival": "timed counter: ARRIVAL events, heappop to the next "
                      "pop (apply into T_alloc, record, first launches)",
    "engine.task_end": "timed counter: TASK_END events, heappop to the "
                       "next pop (retire, later-stage launches)",
    "engine.other": "timed counter: DEVICE_DOWN / DEVICE_UP / RECOVER "
                    "events, heappop to the next pop (the sum of the next "
                    "three)",
    "engine.device_down": "timed counter: DEVICE_DOWN events, heappop to "
                          "the next pop (kills, returned occupancy, "
                          "recovery hooks)",
    "engine.device_up": "timed counter: DEVICE_UP events, heappop to the "
                        "next pop (rejoin, cold caches)",
    "engine.recover": "timed counter: RECOVER events, heappop to the next "
                      "pop (the recovery: a replan, its apply and the "
                      "relaunch)",
    "talloc.write": "timed counter: ClusterState.add_interval calls made "
                    "inside Engine.run, over all its callers",
    "gc.pause": "timed counter: the passes of the garbage collector kept "
                "as gc.collect records",
}

BUFFER = 1 << 16            # records kept (about 35 spans and 55-90
                            # collector passes per wave)

_clock = time.perf_counter_ns
_ids = itertools.count(1)
_forced = False
_stack: List["HostSpan"] = []
_records: Deque["HostSpan"] = deque(maxlen=BUFFER)
_counters = MetricsRegistry()
_last: Dict[str, int] = {}
_gc_t0 = 0                  # start of the collector pass being kept


def _profiling() -> bool:
    """Whether a ``jax.profiler`` session is active.  Without jax imported
    there can be none, and jax is not imported for the asking."""
    jax = sys.modules.get("jax")
    return jax is not None and jax.profiler.TraceAnnotation.is_enabled()


def recording() -> bool:
    """True while spans and counters are kept."""
    return _forced or _profiling()


def enable() -> None:
    """Record with no profiler session (tests, operators)."""
    global _forced, _gc_t0
    _forced, _gc_t0 = True, 0


def disable() -> None:
    """Stop recording outside a profiler session."""
    global _forced, _gc_t0
    _forced, _gc_t0 = False, 0


def clear() -> None:
    """Drop every kept span and zero the timed counters."""
    global _counters
    _records.clear()
    _counters = MetricsRegistry()


class HostSpan:
    """One coarse span; a context manager.  ``recording`` says whether it is
    kept; ``ns`` is its duration after exit, kept or not."""

    __slots__ = ("name", "attrs", "t0", "t1", "id", "parent", "wave",
                 "recording", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.t0 = self.t1 = 0
        self.id = self.parent = self.wave = None
        self.recording = False
        self._ann = None

    def __enter__(self) -> "HostSpan":
        profiling = _profiling()
        if profiling or _forced:
            if self.name not in HOST_SPAN_SCHEMA:
                raise ValueError(
                    f"unknown host span {self.name!r}; add it to "
                    "HOST_SPAN_SCHEMA (and obs/README.md) first")
            self.recording = True
            self.id = next(_ids)
            if _stack:
                top = _stack[-1]
                self.parent, self.wave = top.id, top.wave
            if self.name == "plan.wave":
                self.wave = self.id
            _stack.append(self)
            _records.append(self)
            if profiling:
                ann = sys.modules["jax"].profiler.TraceAnnotation(self.name)
                ann.__enter__()
                self._ann = ann
        self.t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = _clock()
        _last[self.name] = self.t1 - self.t0
        if self.recording:
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None
            _stack.pop()

    @property
    def ns(self) -> int:
        return self.t1 - self.t0

    def set(self, **attrs: Any) -> None:
        """Add counts known only inside the span (kept when recording)."""
        if self.recording:
            self.attrs.update(attrs)


def span(name: str, **attrs: Any) -> HostSpan:
    """A coarse span over a ``with`` block (see the module docstring)."""
    return HostSpan(name, attrs)


def tally(name: str, n: int, ns: int) -> None:
    """Add ``n`` calls taking ``ns`` nanoseconds to timed counter ``name``
    (the caller checks :func:`recording` once for the whole loop)."""
    if name not in HOST_SPAN_SCHEMA:
        raise ValueError(f"unknown timed counter {name!r}; add it to "
                         "HOST_SPAN_SCHEMA (and obs/README.md) first")
    _counters.counter(name).inc(n)
    _counters.counter(name + ".ns").inc(ns)


def _record(name: str, t0: int, t1: int, **attrs: Any) -> None:
    """Keep a closed record of work timed elsewhere, under the innermost
    open span."""
    if name not in HOST_SPAN_SCHEMA:
        raise ValueError(f"unknown host record {name!r}; add it to "
                         "HOST_SPAN_SCHEMA (and obs/README.md) first")
    rec = HostSpan(name, attrs)
    rec.t0, rec.t1, rec.id, rec.recording = t0, t1, next(_ids), True
    if _stack:
        top = _stack[-1]
        rec.parent, rec.wave = top.id, top.wave
    _records.append(rec)


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """The ``gc.callbacks`` hook: one ``gc.collect`` record and a
    ``gc.pause`` tally per pass while spans are kept (module docstring)."""
    global _gc_t0
    if not (_forced or _stack):
        return
    if phase == "start":
        _gc_t0 = _clock()
    elif _gc_t0:
        t0, t1, _gc_t0 = _gc_t0, _clock(), 0
        _record("gc.collect", t0, t1, generation=info["generation"],
                collected=info["collected"],
                uncollectable=info["uncollectable"])
        tally("gc.pause", 1, t1 - t0)


gc.callbacks.append(_on_gc)


def records(name: Optional[str] = None) -> List[HostSpan]:
    """Kept spans that have closed, in order of their start."""
    return [s for s in _records
            if s.t1 and (name is None or s.name == name)]


def timed(name: str) -> Tuple[int, int]:
    """``(count, ns)`` of timed counter ``name`` since the last clear."""
    c, t = _counters.counters.get(name), _counters.counters.get(name + ".ns")
    return (c.value if c else 0, t.value if t else 0)


def last_ns(name: str) -> int:
    """Duration of the latest span of ``name`` to close, recorded or not."""
    return _last.get(name, 0)
