"""repro.obs — end-to-end observability for the orchestration pipeline.

  * :mod:`repro.obs.tracing` — per-instance traces of structured,
    sim-clock-timestamped spans (:data:`SPAN_SCHEMA`), emitted by the
    engine / stream service / recovery strategies through a
    zero-overhead-when-disabled :class:`Tracer`;
  * :mod:`repro.obs.metrics` — the unified counters / gauges /
    exact-quantile histograms registry (:mod:`repro.stream.metrics`
    re-exports from here) and :class:`EngineStats`, the engine's typed
    counter ledger with the conservation identity checked in one place;
  * :mod:`repro.obs.attribution` — predicted-vs-actual cost attribution:
    critical-path breakdowns, Eq. (2) / P_f calibration per policy /
    tier / device, slow- and lost-instance reports;
  * :mod:`repro.obs.export` — Chrome/Perfetto ``trace_event`` JSON
    (device rows + instance flows) and summary exports, with the
    instance ledger recomputable from the exported trace alone;
  * :mod:`repro.obs.hostspans` — the wall-clock side: spans and timed
    counters inside the planner, the policy and the engine
    (:data:`HOST_SPAN_SCHEMA`) on ``time.perf_counter_ns``, kept only
    while a ``jax.profiler`` session is active or after
    ``hostspans.enable()``.

Enable via ``Orchestrator(cluster, policy, trace=Tracer())`` or
``SimConfig(trace=True)``; see ``src/repro/obs/README.md`` for the span
schema and a worked example.
"""
from .attribution import (
    attribution_report,
    calibration,
    format_report,
    instance_breakdown,
    lost_instances,
    slow_instances,
)
from .export import (
    json_summary,
    ledger_from_trace,
    to_chrome_trace,
    validate_chrome_trace,
)
from .metrics import (
    ENGINE_COUNTERS,
    Counter,
    EngineStats,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from . import hostspans
from .hostspans import HOST_SPAN_SCHEMA
from .tracing import FLEET_TID, SPAN_SCHEMA, Span, Tracer

__all__ = [
    "Span",
    "Tracer",
    "SPAN_SCHEMA",
    "FLEET_TID",
    "hostspans",
    "HOST_SPAN_SCHEMA",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "ENGINE_COUNTERS",
    "EngineStats",
    "instance_breakdown",
    "calibration",
    "slow_instances",
    "lost_instances",
    "attribution_report",
    "format_report",
    "to_chrome_trace",
    "ledger_from_trace",
    "validate_chrome_trace",
    "json_summary",
]
