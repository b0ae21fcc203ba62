"""From a profiler trace of the window to device metrics.

The window runs under ``jax.profiler`` with the Python tracer off, so the
host plane holds the benchmark's own ``TraceAnnotation`` spans (``window``,
``orchestrate_batch``, ``decide_batch``, ``kernel:<name>``, ``step``) and
the device planes hold what ran on each chip, on the same clock.  The
reduction:

* device ops: the events of the ``XLA Ops`` line of each device plane
  that has one (every op of every program; a plane without it, such as a
  runtime's own trace plane, is no chip); ``XLA Modules`` events name the
  program they ran in;
* busy: the union of the op intervals inside the window, averaged over
  the chips; idle share is one minus busy over the window;
* device time inside a host span: the busy time (the union, since a
  ``while`` op's event covers the ops of its body) that overlaps it;
* idle gaps: the window minus busy, each instant attributed to the
  innermost benchmark span open on the host then, or to ``driver`` (the
  benchmark's own loop and load generator) outside them.

A trace without a device plane (a CPU run) yields no device metric.
"""
from __future__ import annotations

import glob
import os
import shutil
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]          # [start_ns, end_ns)

HOST_SPANS = ("window", "orchestrate_batch", "decide_batch", "step")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Sequence[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def overlap(intervals: Sequence[Interval], lo: int, hi: int) -> int:
    """Total length of ``intervals`` (possibly overlapping) inside [lo, hi)."""
    return sum(max(0, min(b, hi) - max(a, lo)) for a, b in intervals)


def complement(cover: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """[lo, hi) minus a sorted disjoint cover."""
    out, t = [], lo
    for a, b in cover:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def innermost(spans: Sequence[Tuple[str, int, int]]) -> List[Tuple[int, int, str]]:
    """Flatten properly nested host spans into segments labelled with the
    innermost open span."""
    bounds = []
    for name, a, b in spans:
        bounds.append((a, 1, -b, name))
        bounds.append((b, 0, 0, name))
    bounds.sort()
    stack: List[str] = []
    segs: List[Tuple[int, int, str]] = []
    last = None
    for t, kind, _, name in bounds:
        if last is not None and stack and t > last:
            segs.append((last, t, stack[-1]))
        if kind == 1:
            stack.append(name)
        elif name in stack:
            # remove the most recent open span of that name
            del stack[len(stack) - 1 - stack[::-1].index(name)]
        last = t
    return segs


def attribute(gaps: Sequence[Interval], segs: Sequence[Tuple[int, int, str]],
              outside: str = "driver") -> Dict[str, int]:
    """Idle nanoseconds per host activity (both inputs sorted, segments
    disjoint)."""
    out: Dict[str, int] = defaultdict(int)
    j = 0
    for a, b in gaps:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        covered = 0
        k = j
        while k < len(segs) and segs[k][0] < b:
            s0, s1, name = segs[k]
            ov = min(b, s1) - max(a, s0)
            if ov > 0:
                out[name] += ov
                covered += ov
            k += 1
        out[outside] += (b - a) - covered
    return dict(out)


@dataclass
class Trace:
    """The reduced trace of one window."""

    window: Interval
    ops: Dict[int, List[Tuple[str, int, int]]]        # chip -> (op, start, end)
    modules: Dict[int, List[Tuple[str, int, int]]]    # chip -> (program, ...)
    host: Dict[str, List[Interval]] = field(default_factory=dict)

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        import jax

        pd = jax.profiler.ProfileData.from_file(path)
        ops: Dict[int, list] = {}
        modules: Dict[int, list] = {}
        host: Dict[str, List[Interval]] = defaultdict(list)
        for plane in pd.planes:
            if plane.name.startswith("/device:") and any(
                    line.name == OPS_LINE for line in plane.lines):
                chip = len(ops)
                ops[chip], modules[chip] = [], []
                for line in plane.lines:
                    dest = (ops[chip] if line.name == OPS_LINE else
                            modules[chip] if line.name == MODULES_LINE else None)
                    if dest is None:
                        continue
                    for e in line.events:
                        s = int(e.start_ns)
                        dest.append((e.name, s, s + int(e.duration_ns)))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        name = e.name
                        if name in HOST_SPANS or name.startswith("kernel:"):
                            s = int(e.start_ns)
                            host[name].append((s, s + int(e.duration_ns)))
        win = host.get("window")
        if not win:
            raise ValueError(f"{path}: no `window` span in the trace")
        return cls(window=win[0], ops=ops, modules=modules, host=dict(host))

    # -- device ------------------------------------------------------------
    @property
    def has_device(self) -> bool:
        return any(self.ops.values())

    def busy(self, chip: int) -> List[Interval]:
        lo, hi = self.window
        return clip(union([(a, b) for _, a, b in self.ops[chip]]), lo, hi)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        if not self.ops:
            return 0.0
        return sum(length(self.busy(c)) for c in self.ops) / len(self.ops) / 1e9

    def idle_pct(self) -> Optional[float]:
        if not self.has_device:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def device_ns_in(self, lo: int, hi: int) -> int:
        """Busy time (the union of every program's ops: a ``while`` op's
        event covers the ops of its body) inside [lo, hi), summed over
        chips."""
        return sum(length(clip(self.busy(c), lo, hi)) for c in self.ops)

    def module_ns(self, needle: str) -> int:
        """Device time of the programs whose name contains ``needle``,
        inside the window, summed over chips."""
        lo, hi = self.window
        return sum(overlap([(a, b) for n, a, b in self.modules[c] if needle in n],
                           lo, hi) for c in self.modules)

    # -- host --------------------------------------------------------------
    def spans(self, name: str) -> List[Interval]:
        lo, hi = self.window
        return [(a, b) for a, b in self.host.get(name, []) if a >= lo and b <= hi]

    def idle_by_activity(self, chip: int = 0) -> Dict[str, int]:
        lo, hi = self.window
        gaps = complement(self.busy(chip), lo, hi)
        named = [(n, a, b) for n, iv in self.host.items() if n != "window"
                 for a, b in iv if b > lo and a < hi]
        return attribute(gaps, innermost(named))

    def breakdown(self) -> Dict[str, list]:
        lo, hi = self.window
        per_op: Dict[str, int] = defaultdict(int)
        for c in self.ops:
            for n, a, b in self.ops[c]:
                # an op's event name is its whole HLO instruction; keep the name
                per_op[n.split(" = ", 1)[0]] += max(0, min(b, hi) - max(a, lo))
        top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
        idle = (sorted(self.idle_by_activity().items(), key=lambda kv: -kv[1])[:10]
                if self.ops else [])
        return {"device_ops": [[n, v / 1e9] for n, v in top if v > 0],
                "idle_gaps": [[n, v / 1e9] for n, v in idle if v > 0]}


class Tracer:
    """Runs the profiler around the window, into a scratch directory under
    ``$TMPDIR`` that :meth:`cleanup` removes."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def path(self) -> str:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        return files[0]

    def read(self, run=None) -> Trace:
        return Trace.from_file(self.path())

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
