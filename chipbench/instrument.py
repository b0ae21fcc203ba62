"""Host spans and counters taken from the benchmark's side.

The benchmark wraps public callables of the program — the fused planner
``orchestrate_batch`` as :class:`repro.api.Orchestrator` calls it, the
policy's ``decide_batch``, the entries of the placement-kernel table that
``decide_batch`` looks up on every call, and ``Orchestrator.step`` — and
records one span per call on the host clock.  With tracing on, each span is
also a ``jax.profiler.TraceAnnotation``, so host spans and device events
share the profiler's clock.  It counts kernel dispatches with their padded
shapes, and XLA compilations from ``jax.monitoring`` events (a copy of the
repository's bring-up probe).
"""
from __future__ import annotations

import contextlib
import time
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional

KERNELS = (
    "ibdash_scan_kernel",
    "lavea_kernel",
    "round_robin_kernel",
    "tier_escalation_kernel",
)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


@dataclass
class Span:
    name: str
    t0: int                 # perf_counter_ns at entry
    t1: int = 0             # perf_counter_ns at exit
    info: dict = field(default_factory=dict)

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) / 1e6


class Instrument:
    """Installs the wrappers; ``spans`` holds every call in order."""

    def __init__(self, trace: bool = False):
        self.trace = trace
        self.spans: List[Span] = []
        self.dispatches: Counter = Counter()
        self.shapes: Dict[str, set] = defaultdict(set)
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.last: Dict[str, int] = {}     # span name -> index of the latest
        self.last_plans: list = []
        self._undo: list = []

    # -- spans -------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, **info):
        self.last[name] = len(self.spans)
        sp = Span(name, time.perf_counter_ns(), info=info)
        self.spans.append(sp)
        ann = None
        if self.trace:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        try:
            yield sp
        finally:
            if ann is not None:
                ann.__exit__(None, None, None)
            sp.t1 = time.perf_counter_ns()

    def mark(self) -> int:
        """Index of the next span (so a caller can slice a window)."""
        return len(self.spans)

    # -- wrappers ------------------------------------------------------------
    def _patch(self, owner, attr, wrapper) -> None:
        """Wrap ``owner.attr`` (a module function or an instance's bound
        method); :meth:`remove` puts back what was there."""
        self._undo.append((owner, attr, vars(owner).get(attr)))
        setattr(owner, attr, wrapper(getattr(owner, attr)))

    def install(self, orch, policy) -> "Instrument":
        import jax
        from repro import api
        from repro.core import batched

        def planner(fn):
            def orchestrate_batch(apps, *args, **kw):
                with self.span("orchestrate_batch", rows=len(apps)) as sp:
                    plans = fn(apps, *args, **kw)
                    sp.info["plans"] = len(plans)
                    self.last_plans = plans
                    return plans
            return orchestrate_batch

        def decide(fn):
            def decide_batch(batch):
                with self.span("decide_batch", G=batch.n_distinct,
                               D=batch.n_devices, B=batch.n_rows):
                    return fn(batch)
            return decide_batch

        def stepper(fn):
            def step(until):
                with self.span("step", until=until):
                    return fn(until)
            return step

        def kernel(name):
            def wrap(fn):
                def call(*args):
                    shape = tuple(args[0].shape)
                    self.dispatches[name] += 1
                    self.shapes[name].add(shape)
                    with self.span("kernel:" + name, shape=shape):
                        # the caller reads the result back at once; waiting
                        # here puts that wait inside the kernel's span
                        return jax.block_until_ready(fn(*args))
                return call
            return wrap

        self._patch(api, "orchestrate_batch", planner)
        self._patch(policy, "decide_batch", decide)
        self._patch(orch, "step", stepper)
        table = batched._jax()
        for k in KERNELS:
            self._undo.append((table, k, table[k]))
            table[k] = kernel(k)(table[k])
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def remove(self) -> None:
        import jax

        for owner, attr, old in reversed(self._undo):
            if isinstance(owner, dict):
                owner[attr] = old
            elif old is None:
                delattr(owner, attr)      # the class's method shows again
            else:
                setattr(owner, attr, old)
        self._undo.clear()
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)

    def _on_duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    # -- reading -------------------------------------------------------------
    def children(self, idx: int, name: Optional[str] = None) -> List[Span]:
        """Direct and nested spans under span ``idx`` (optionally by name)."""
        out = []
        for j in range(idx + 1, len(self.spans)):
            sp = self.spans[j]
            if sp.t0 >= self.spans[idx].t1:
                break
            if name is None or sp.name == name:
                out.append(sp)
        return out
