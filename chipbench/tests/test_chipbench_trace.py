"""The reduction from a profiler trace to device metrics."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE / "metrics"))

import trace_reduce as tr  # noqa: E402


def test_union_complement_partition_the_window():
    ops = [(5, 9), (1, 3), (2, 4), (8, 12), (20, 25), (30, 30)]
    cover = tr.union(ops)
    assert cover == [(1, 4), (5, 12), (20, 25)]
    busy = tr.clip(cover, 0, 22)
    gaps = tr.complement(busy, 0, 22)
    assert gaps == [(0, 1), (4, 5), (12, 20)]
    assert tr.length(busy) + tr.length(gaps) == 22


def test_innermost_spans_and_gap_attribution():
    host = [("orchestrate_batch", 0, 100), ("decide_batch", 40, 80),
            ("kernel:k", 50, 60), ("step", 120, 200)]
    segs = tr.innermost(host)
    assert segs == [(0, 40, "orchestrate_batch"), (40, 50, "decide_batch"),
                    (50, 60, "kernel:k"), (60, 80, "decide_batch"),
                    (80, 100, "orchestrate_batch"), (120, 200, "step")]
    idle = tr.attribute([(0, 55), (58, 150)], segs)
    assert idle == {"orchestrate_batch": 60, "decide_batch": 30,
                    "kernel:k": 7, "step": 30, "driver": 20}
    assert sum(idle.values()) == 55 + 92


def _synthetic():
    return tr.Trace(
        window=(0, 1000),
        ops={0: [("fusion", 100, 150), ("copy", 140, 160), ("while", 500, 600),
                 ("late", 990, 1100)]},
        modules={0: [("jit_ibdash_scan_kernel(1)", 100, 160),
                     ("jit_other", 500, 600)]},
        host={"window": [(0, 1000)], "orchestrate_batch": [(50, 300)],
              "decide_batch": [(90, 170)], "step": [(400, 700)]},
    )


def test_busy_idle_and_device_time_in_spans():
    t = _synthetic()
    assert t.busy_s == pytest.approx((60 + 100 + 10) / 1e9)
    assert t.idle_pct() == pytest.approx(100 * (1 - 170 / 1000))
    assert t.device_ns_in(50, 300) == 60
    assert t.module_ns("ibdash_scan_kernel") == 60
    idle = t.idle_by_activity()
    assert sum(idle.values()) == 1000 - 170
    assert idle["step"] == 300 - 100
    bd = t.breakdown()
    assert bd["device_ops"][0] == ["while", 100 / 1e9]
    assert len(bd["idle_gaps"]) <= 10


def test_no_device_plane_gives_no_device_metric():
    t = tr.Trace(window=(0, 10), ops={}, modules={}, host={"window": [(0, 10)]})
    assert t.idle_pct() is None
    assert t.busy_s == 0.0


RECORDED = HERE / "testdata" / "mix100_burst_1wave.xplane.pb"


def test_recorded_chip_trace():
    """One wave of ``mix100.burst`` traced on a TPU v5e: four IBDASH scan
    programs; the busy union is smaller than the summed op time because a
    ``while`` op's event covers its body's ops."""
    t = tr.Trace.from_file(str(RECORDED))
    assert list(t.ops) == [0]          # the runtime's own plane is no chip
    assert len(t.ops[0]) == 312 and len(t.modules[0]) == 4
    assert t.window == (46350453, 390144574)
    busy = tr.union([(a, b) for _, a, b in t.ops[0]])
    assert t.busy_s == pytest.approx(tr.length(busy) / 1e9)
    assert tr.length(busy) == 86355
    assert sum(b - a for _, a, b in t.ops[0]) > tr.length(busy)
    (lo, hi), = t.spans("orchestrate_batch")
    assert t.device_ns_in(lo, hi) == tr.length(tr.clip(busy, lo, hi))
    assert t.module_ns("ibdash_scan_kernel") == 95297
    assert {k: len(v) for k, v in t.host.items()} == {
        "window": 1, "orchestrate_batch": 1, "decide_batch": 4,
        "kernel:ibdash_scan_kernel": 4, "step": 1}
    idle = t.idle_by_activity()
    assert sum(idle.values()) == (t.window[1] - t.window[0]) - tr.length(busy)
    bd = t.breakdown()
    assert bd["device_ops"][0][0] == "%while.7"
    assert [n for n, _ in bd["idle_gaps"]][:2] == ["orchestrate_batch", "step"]
