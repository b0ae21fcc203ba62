"""The per-layer metrics that read the program's own wall-clock spans and
counters (``repro.obs.hostspans``), and the clock they share with the
benchmark's spans and the profiler's trace."""
import math
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
for p in (str(ROOT / "src"), str(HERE), str(HERE / "metrics")):
    if p not in sys.path:
        sys.path.insert(0, p)

import trace_reduce as tr  # noqa: E402
from harness import _report, load_json, load_module, measure  # noqa: E402
from repro.obs import HOST_SPAN_SCHEMA, hostspans  # noqa: E402

NEW = ("context_build_ms.burst", "plan_assembly_ms.burst",
       "engine_arrival_ms.burst", "engine_task_end_ms.burst",
       "talloc_write_ms.burst")
RECORDED = HERE / "testdata" / "mix100_burst_1wave_spans.xplane.pb"


def _host_events(path, names):
    """(name, start, end) of the host plane's events named in ``names``."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        s = int(e.start_ns)
                        out.append((e.name, s, s + int(e.duration_ns)))
    return out


@pytest.fixture(scope="module")
def traced():
    """A tiny traced run on the CPU, through the harness as ``run_cell``
    runs it, keeping the profiler's trace file for the test."""
    bench = load_json(ROOT / "BENCHMARK.json")
    wl = next(w for w in bench["workloads"] if w["name"] == "mix100.burst")
    hostspans.clear()
    run = measure(bench, wl, 11, 0.3, True, log=lambda m: None,
                  config_overrides={"n_devices": 40, "horizon_s": 3000.0},
                  traffic_overrides={"instances": 24, "warm_rows": [],
                                     "check_instances": 8},
                  compile_cache=False)
    try:
        out = _report(bench, wl, run, lambda m: None)
        events = _host_events(run.tracer.path(), {
            "orchestrate_batch", "plan.context", "plan.assemble",
            "policy.kernel"})
        return out, run, events
    finally:
        run.setup.inst.remove()
        run.tracer.cleanup()


def test_new_metrics_are_reported_and_positive(traced):
    out, _, _ = traced
    for name in NEW:
        v = out["metrics"][name]["value"]
        assert math.isfinite(v) and v > 0, name
    assert out["correct"]


def test_new_metrics_lie_inside_the_layers_timed_from_outside(traced):
    m = {k: v["value"] for k, v in traced[0]["metrics"].items()}
    assert (m["context_build_ms.burst"] + m["plan_assembly_ms.burst"]
            <= 1.01 * m["orchestrate_self_ms.burst"])
    assert (m["engine_arrival_ms.burst"] + m["engine_task_end_ms.burst"]
            <= 1.01 * m["engine_ms.burst"])
    assert m["talloc_write_ms.burst"] < (m["engine_arrival_ms.burst"]
                                         + m["engine_task_end_ms.burst"])


def test_program_spans_nest_in_the_benchmark_spans_on_the_trace(traced):
    """On the profiler's host plane the program's annotations sit inside
    the benchmark's ``orchestrate_batch`` annotations."""
    events = traced[2]
    waves = [(a, b) for n, a, b in events if n == "orchestrate_batch"]
    inner = [(n, a, b) for n, a, b in events if n != "orchestrate_batch"]
    assert {n for n, _, _ in inner} == {"plan.context", "plan.assemble",
                                       "policy.kernel"}
    for n, a, b in inner:
        assert any(lo <= a and b <= hi for lo, hi in waves), n


def test_program_spans_nest_in_the_benchmark_spans_on_the_host_clock(traced):
    """The recorder's spans share ``time.perf_counter_ns`` with the
    benchmark's: each ``plan.wave`` lies inside one ``orchestrate_batch``
    span of the window, each ``engine.step`` inside one ``step``."""
    _, run, _ = traced
    for inner, outer in (("plan.wave", run.waves), ("engine.step", run.steps)):
        spans = [run.spans[i] for i in outer]
        got = [s for s in hostspans.records(inner)
               if any(o.t0 <= s.t0 and s.t1 <= o.t1 for o in spans)]
        assert len(got) == len(spans), inner


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_without_program_spans(traced, name,
                                                    monkeypatch):
    """An untraced run keeps nothing, and a program without the recorder
    (an older checkout) has none: either way the reader returns None."""
    import repro.obs

    _, run, _ = traced
    reader = load_module(HERE / "metrics" / f"{name}.py")
    assert reader.read(run) is not None
    with monkeypatch.context() as m:
        m.setattr(hostspans, "_records", type(hostspans._records)())
        assert reader.read(run) is None
    with monkeypatch.context() as m:
        m.delattr(repro.obs, "hostspans")
        m.setitem(sys.modules, "repro.obs.hostspans", None)
        assert reader.read(run) is None
    assert reader.read(run) is not None


def test_program_span_names_leave_the_benchmark_readers_alone():
    assert not set(HOST_SPAN_SCHEMA) & set(tr.HOST_SPANS)
    assert not any(n.startswith("kernel:") for n in HOST_SPAN_SCHEMA)


def test_recorded_chip_trace_with_program_spans():
    """One wave of ``mix100.burst`` traced on a TPU v5e with the program's
    spans: each IBDASH scan program runs inside its call's ``policy.decide``
    span, and inside its ``policy.kernel`` span up to the profiler's
    alignment of the device's clock to the host's — here the last scan
    starts 122,406 ns before the host entered its ``policy.kernel``."""
    t = tr.Trace.from_file(str(RECORDED))
    assert list(t.ops) == [0] and len(t.ops[0]) == 312
    assert t.window == (59358348, 354121662)
    assert t.module_ns("ibdash_scan_kernel") == 96487
    events = _host_events(RECORDED, set(HOST_SPAN_SCHEMA) | {
        "orchestrate_batch", "decide_batch", "kernel:ibdash_scan_kernel"})
    counts = {}
    for n, _, _ in events:
        counts[n] = counts.get(n, 0) + 1
    assert counts == {
        "orchestrate_batch": 1, "plan.wave": 1, "plan.snapshot": 1,
        "plan.screen": 4, "plan.context": 4, "decide_batch": 4,
        "policy.decide": 4, "policy.select": 4, "policy.kernel": 4,
        "kernel:ibdash_scan_kernel": 4, "plan.assemble": 5,
        "engine.step": 1}

    def spans(name):
        return sorted((a, b) for n, a, b in events if n == name)

    scans = sorted((a, b) for n, a, b in t.modules[0]
                   if "ibdash_scan_kernel" in n)
    decides, kernels = spans("policy.decide"), spans("policy.kernel")
    assert len(scans) == len(decides) == len(kernels) == 4
    for (a, b), (d0, d1) in zip(scans, decides):
        assert d0 <= a and b <= d1
    early = [k0 - a for (a, b), (k0, k1) in zip(scans, kernels)
             if not (k0 <= a and b <= k1)]
    assert early == [122406]
    ops = [(a, b) for _, a, b in t.ops[0]
           if any(s0 <= a and b <= s1 for s0, s1 in scans)]
    assert len(ops) == 312
    # the benchmark's kernel spans and its wave lie around the program's
    for a, b in spans("kernel:ibdash_scan_kernel"):
        assert any(k0 <= a and b <= k1 for k0, k1 in kernels)
    (w0, w1), = spans("orchestrate_batch")
    (p0, p1), = spans("plan.wave")
    assert w0 <= p0 and p1 <= w1
