"""The per-layer metrics that read the garbage collector's passes
(``gc.collect`` records of ``repro.obs.hostspans``) inside the benchmark's
wave and step spans: ``gc_pause_ms.burst`` and ``gc_full_ms.burst``."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
for p in (str(ROOT / "src"), str(HERE), str(HERE / "metrics"),
          str(Path(__file__).resolve().parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import load_module  # noqa: E402
from repro.obs import hostspans  # noqa: E402
# the tiny traced run through the harness, shared with the readers of the
# program's other spans
from test_chipbench_program_spans import traced  # noqa: E402,F401


def test_gc_readers_on_the_traced_run(traced):  # noqa: F811
    """The collector ran inside the tiny run's waves and steps; its full
    collections are part of all its passes, and the whole is the sum of
    the records the two benchmark spans hold."""
    out, run, _ = traced
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["gc_pause_ms.burst"] > 0
    assert 0 <= m["gc_full_ms.burst"] <= m["gc_pause_ms.burst"]
    outer = [run.spans[i] for i in run.waves + run.steps]
    inside = sum(r.ns for r in hostspans.records("gc.collect")
                 if any(o.t0 <= r.t0 and r.t1 <= o.t1 for o in outer))
    assert m["gc_pause_ms.burst"] == pytest.approx(
        inside / len(run.waves) / 1e6, rel=1e-12)


def _collected(gen, t0, t1):
    rec = hostspans.HostSpan("gc.collect", {
        "generation": gen, "collected": 0, "uncollectable": 0})
    rec.t0, rec.t1 = t0, t1
    return rec


def _synthetic_run():
    """Two waves and their steps on a made-up clock (ns), and the
    collector's passes in them, between them and across an edge."""
    from instrument import Span

    spans = [Span("window", 0, 1000),
             Span("orchestrate_batch", 100, 200),
             Span("decide_batch", 120, 150),
             Span("step", 200, 300),
             Span("orchestrate_batch", 500, 600), Span("step", 600, 700)]
    run = type("Run", (), {"spans": spans, "waves": [1, 4], "steps": [3, 5]})()
    records = [
        _collected(2, 110, 130),    # wave 1, full        20
        _collected(0, 130, 135),    # wave 1, in decide    5
        _collected(0, 210, 217),    # step 1               7
        _collected(2, 350, 390),    # between the waves: not counted
        _collected(1, 590, 610),    # across wave 2's end: not counted
        _collected(2, 640, 680),    # step 2, full        40
        _collected(0, 20, 30),      # before the first wave: not counted
    ]
    return run, records


@pytest.mark.parametrize("name, per_wave_ns", [
    ("gc_pause_ms.burst", (20 + 5 + 7 + 40) / 2),
    ("gc_full_ms.burst", (20 + 40) / 2),
])
def test_gc_readers_sum_only_collections_in_waves_and_steps(
        name, per_wave_ns, monkeypatch):
    import repro.obs

    run, records = _synthetic_run()
    reader = load_module(HERE / "metrics" / f"{name}.py")
    spans = [hostspans.HostSpan("plan.wave", {})]
    spans[0].t0, spans[0].t1 = 100, 200
    with monkeypatch.context() as m:
        m.setattr(hostspans, "_records",
                  type(hostspans._records)(spans + records))
        assert reader.read(run) == pytest.approx(per_wave_ns / 1e6)
        only_young = [r for r in records if r.attrs["generation"] != 2]
        m.setattr(hostspans, "_records",
                  type(hostspans._records)(spans + only_young))
        assert reader.read(run) == pytest.approx(
            0.0 if name == "gc_full_ms.burst" else 12 / 2 / 1e6)
        # a program that kept spans but no collector record (the parent
        # checkout), or kept nothing at all (untraced)
        m.setattr(hostspans, "_records", type(hostspans._records)(spans))
        assert reader.read(run) is None
        m.setattr(hostspans, "_records", type(hostspans._records)())
        assert reader.read(run) is None
    with monkeypatch.context() as m:
        m.delattr(repro.obs, "hostspans")
        m.setitem(sys.modules, "repro.obs.hostspans", None)
        assert reader.read(run) is None
