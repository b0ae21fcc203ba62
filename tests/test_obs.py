"""Observability layer (repro.obs): spans & traces, the typed engine
ledger, predicted-vs-actual attribution, and the exporters.

Pins the PR's contracts:

  * the span vocabulary is FROZEN — the literal tuple below must equal
    ``SPAN_SCHEMA`` exactly (this is also the span-parity lint rule's
    behavioural pin: every kind emitted in src appears here as a string
    literal);
  * tracing is zero-cost when disabled and bit-identical: the same seeded
    run with ``trace=`` on and off produces the same records and ledger;
  * the exported Chrome trace round-trips the conservation identity
    ``admitted == completed + lost + shed`` from the JSON alone, equal to
    the live :class:`EngineStats`;
  * exec spans are a lossless replay log: they reconstruct
    ``Engine(track_intervals=True).executed`` tuple-for-tuple, and
    replaying them onto a fresh cluster reproduces the occupancy tensor
    (property-tested over random churn schedules);
  * :class:`EngineStats` turns a misspelled counter into an immediate
    ``AttributeError`` (satellite-1 regression) and checks conservation
    in exactly one place;
  * the wall-clock recorder (:mod:`repro.obs.hostspans`) keeps nothing
    when off, nests its spans under their parents with one wave id per
    ``orchestrate_batch`` call, counts exactly and repeatably, and leaves
    plans and T_alloc bit-identical whether it records or not;
  * its garbage-collector hook keeps one ``gc.collect`` record per pass
    under the innermost open span while spans are kept, nothing otherwise,
    never calls JAX, and its ``gc.pause`` counter sums the records.
"""
import gc
import json
import math

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.api import Orchestrator, make_policy, make_recovery, orchestrate_batch
from repro.core.cluster import ClusterState, Device
from repro.core.dag import AppDAG, TaskSpec
from repro.core.interference import InterferenceModel
from repro.obs import (
    ENGINE_COUNTERS,
    EngineStats,
    HOST_SPAN_SCHEMA,
    SPAN_SCHEMA,
    Tracer,
    attribution_report,
    format_report,
    instance_breakdown,
    hostspans,
    json_summary,
    ledger_from_trace,
    to_chrome_trace,
    validate_chrome_trace,
)
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.tracing import FLEET_TID
from repro.sim import SimConfig, make_cluster, make_profile, run_one
from repro.sim.churn import ChurnSchedule, deterministic_churn
from repro.sim.engine import Engine
from repro.sim.runner import _make_workload, make_churn, policy_for

GB = 1e9
MB = 1e6


@pytest.fixture(scope="module")
def profile():
    return make_profile(seed=0)


def small_cluster(n=4, lam=1e-6, base=None, horizon=100.0):
    base = np.linspace(0.1, 0.4, n) if base is None else np.asarray(base)
    model = InterferenceModel(
        base=base[:, None], slope=np.full((n, 1, 1), 0.05)
    )
    devices = [
        Device(did=i, cls=i, mem_total=8 * GB, lam=lam,
               up_bw=100e6, down_bw=100e6)
        for i in range(n)
    ]
    return ClusterState(devices=devices, model=model, horizon=horizon, dt=0.05)


def chain_app(name="chain"):
    return AppDAG.from_tasks(name, [
        TaskSpec("a", ttype=0, out_bytes=1 * MB),
        TaskSpec("b", ttype=0, deps=("a",)),
    ])


# ------------------------------------------------------- the span schema --
# The frozen span vocabulary.  This literal tuple is load-bearing twice:
# it pins the schema against accidental edits, AND it is the test-suite
# string-literal pin the span-parity lint rule requires for every kind
# emitted in src (add a kind here + SPAN_SCHEMA + obs/README.md together).
SPAN_KINDS = (
    "instance",
    "admission_queue",
    "plan",
    "model_upload",
    "parent_transfer",
    "exec",
    "recovery_wait",
    "failover",
    "replan",
    "salvage",
    "shed",
    "device_down",
    "device_up",
)


def test_span_schema_is_frozen():
    assert tuple(SPAN_SCHEMA) == SPAN_KINDS
    assert all(isinstance(doc, str) and doc for doc in SPAN_SCHEMA.values())


# The frozen wall-clock vocabulary of repro.obs.hostspans, spans and
# records first and timed counters after: the span-parity rule's test pin
# for every name passed to hostspans.span()/tally() in src (extend
# HOST_SPAN_SCHEMA, obs/README.md and this tuple together).
HOST_SPAN_NAMES = (
    "plan.wave",
    "plan.snapshot",
    "plan.screen",
    "plan.context",
    "plan.assemble",
    "plan.replan",
    "policy.decide",
    "policy.select",
    "policy.kernel",
    "engine.step",
    "gc.collect",
    "engine.arrival",
    "engine.task_end",
    "engine.other",
    "engine.device_down",
    "engine.device_up",
    "engine.recover",
    "talloc.write",
    "gc.pause",
)


def test_host_span_schema_is_frozen():
    assert tuple(HOST_SPAN_SCHEMA) == HOST_SPAN_NAMES
    assert all(isinstance(doc, str) and doc
               for doc in HOST_SPAN_SCHEMA.values())
    # dotted names: none can be one of the chip benchmark's own span names
    assert all("." in n and not n.startswith("kernel:")
               for n in HOST_SPAN_NAMES)


# ------------------------------------------------------------ tracer unit --
def test_tracer_basic_lifecycle():
    tr = Tracer()
    tid = tr.begin_instance("app#0", 1.0, n_tasks=2)
    assert tid == 0 and tr.n_instances == 1
    sid = tr.open_span(tid, "exec", 1.5, name="a", device=3)
    tr.event(tid, "plan", 1.0, policy="ibdash")
    tr.close_span(sid, 2.5, outcome="ok")
    tr.end_instance(tid, 3.0, outcome="completed")
    tr.check_closed()                       # nothing dangling
    inst = tr.instance(tid)
    assert inst.closed and inst.dur == pytest.approx(2.0)
    assert inst.attrs["outcome"] == "completed"
    # spans_of excludes the envelope; by_kind finds the exec window
    assert [s.kind for s in tr.spans_of(tid)] == ["exec", "plan"]
    (ex,) = tr.by_kind("exec")
    assert (ex.t0, ex.t1, ex.attrs["outcome"]) == (1.5, 2.5, "ok")
    assert tr.outcome_counts() == {"completed": 1}


def test_tracer_rejects_unknown_kind_and_double_close():
    tr = Tracer()
    tid = tr.begin_instance("x", 0.0)
    with pytest.raises(ValueError, match="unknown span kind"):
        tr.event(tid, "not_a_kind", 0.0)
    sid = tr.open_span(tid, "exec", 0.0)
    tr.close_span(sid, 1.0)
    with pytest.raises(RuntimeError, match="closed twice"):
        tr.close_span(sid, 2.0)
    tr.end_instance(tid, 1.0, outcome="completed")
    with pytest.raises(RuntimeError, match="ended twice"):
        tr.end_instance(tid, 2.0, outcome="lost")


def test_check_closed_flags_dangling_spans():
    tr = Tracer()
    tid = tr.begin_instance("x", 0.0)
    tr.open_span(tid, "exec", 0.5)
    with pytest.raises(RuntimeError, match="still open"):
        tr.check_closed()


# -------------------------------------------- EngineStats (satellite-1) --
def test_engine_stats_typo_raises():
    """The regression this class exists for: a misspelled counter is an
    immediate AttributeError, not a silently minted dict key."""
    s = EngineStats()
    with pytest.raises(AttributeError):
        s.completd += 1                     # write typo
    with pytest.raises(AttributeError):
        _ = s.task_failover                 # read typo (singular)
    with pytest.raises(AttributeError):
        EngineStats(admited=3)              # constructor typo
    with pytest.raises(AttributeError):
        s["shedd"] = 1                      # mapping-style typo


def test_engine_stats_mapping_compat():
    s = EngineStats(admitted=3, completed=2, lost=1)
    assert s["admitted"] == 3 and "lost" in s and "nope" not in s
    assert len(s) == len(ENGINE_COUNTERS)
    assert tuple(s.keys()) == ENGINE_COUNTERS
    d = dict(s.items())
    assert d["completed"] == 2 and sum(d.values()) == 6
    assert s == d and s == EngineStats(**d)
    assert dict(s) == {k: s[k] for k in s}  # keys()/__getitem__ protocol
    assert "admitted=3" in repr(s)


def test_engine_stats_conservation():
    EngineStats(admitted=3, completed=1, lost=1, shed=1).check_conservation()
    with pytest.raises(RuntimeError, match="instance-counter drift"):
        EngineStats(admitted=3, completed=1).check_conservation()


def test_engine_stats_to_registry():
    s = EngineStats(admitted=5, completed=4, lost=1)
    reg = MetricsRegistry()
    s.to_registry(reg)
    assert reg.counter("engine_admitted").value == 5
    assert reg.counter("engine_lost").value == 1
    snap = reg.snapshot()
    assert set(snap["counters"]) == {"engine_" + k for k in ENGINE_COUNTERS}


def test_stream_metrics_shim_reexports():
    """repro.stream.metrics stays importable and IS the obs implementation."""
    from repro.stream import metrics as sm

    assert sm.MetricsRegistry is MetricsRegistry
    assert sm.Histogram is Histogram


# ---------------------------------------- histogram edges (satellite-3) --
def test_histogram_empty():
    h = Histogram("h")
    assert h.count == 0
    assert math.isnan(h.quantile(0.5))
    assert h.summary() == {"count": 0}


def test_histogram_single_sample():
    h = Histogram("h")
    h.observe(2.5)
    s = h.summary()
    assert s["count"] == 1
    # every quantile of a single observation is that observation
    assert s["p50"] == s["p99"] == s["p999"] == s["max"] == s["mean"] == 2.5


def test_histogram_all_duplicates():
    h = Histogram("h")
    for _ in range(100):
        h.observe(7.0)
    assert h.quantile(0.01) == h.quantile(0.999) == 7.0
    assert h.summary()["mean"] == 7.0


def test_histogram_p999_under_1000_samples():
    """With fewer than 1000 observations p999 interpolates toward the max
    — it must stay finite and inside [p99, max], never index out of
    range."""
    h = Histogram("h")
    for v in range(10):
        h.observe(float(v))
    s = h.summary()
    assert math.isfinite(s["p999"])
    assert s["p99"] <= s["p999"] <= s["max"] == 9.0


# --------------------------------------------- tracing the churn runtime --
def _traced_orchestrator(profile, scheme="ibdash"):
    """The acceptance scenario: correlated churn hot enough to lose
    instances + replan + salvage, intervals tracked, tracing on."""
    cfg = SimConfig(scenario="correlated_churn", n_cycles=2,
                    instances_per_cycle=60, seed=3, n_devices=12,
                    recovery="replan", salvage=2, shock_rate=0.2,
                    mean_downtime=30.0, gamma=1, max_retries=1)
    mk = lambda: make_cluster(profile, scenario="correlated_churn",
                              n_devices=12, seed=3,
                              horizon=cfg.horizon + 60.0)
    cluster = mk()
    churn = make_churn(cfg, cluster)
    orch = Orchestrator(cluster, policy_for(scheme, profile, cfg), seed=3,
                        churn=churn, recovery=cfg.recovery,
                        salvage=cfg.salvage,
                        detection_delay=cfg.detection_delay,
                        max_retries=cfg.max_retries,
                        track_intervals=True, trace=True)
    apps, times = _make_workload(cfg)
    orch.submit_batch(apps, times)
    orch.drain()
    return orch, cluster, mk


@pytest.fixture(scope="module")
def traced(profile):
    return _traced_orchestrator(profile)


def test_traced_run_covers_the_pipeline(traced):
    """The acceptance trace actually exercises the vocabulary: exec and
    plan everywhere, churn kills, recovery and salvage activity."""
    orch, _, _ = traced
    tr = orch.trace
    tr.check_closed()
    assert tr.n_instances == orch.stats["admitted"]
    kinds = {s.kind for s in tr.spans}
    assert {"instance", "plan", "exec", "model_upload", "parent_transfer",
            "device_down", "device_up", "recovery_wait", "replan",
            "salvage"} <= kinds
    # churn bites and the trace agrees with the counters about how hard
    assert orch.stats["lost"] > 0 and orch.stats["replans"] > 0
    assert orch.stats["salvages"] > 0
    assert len(tr.by_kind("replan")) == orch.stats["replans"]
    assert len(tr.by_kind("salvage")) == orch.stats["salvages"]
    assert len(tr.by_kind("device_down")) == orch.stats["device_down"]
    killed = [s for s in tr.by_kind("exec")
              if s.attrs["outcome"] == "killed"]
    assert killed and all(s.tid != FLEET_TID for s in killed)
    # fleet events belong to no instance
    assert all(s.tid == FLEET_TID for s in tr.by_kind("device_down"))


def test_trace_ledger_matches_engine_stats(traced):
    orch, _, _ = traced
    counts = orch.trace.outcome_counts()
    assert counts.get("completed", 0) == orch.stats["completed"]
    assert counts.get("lost", 0) == orch.stats["lost"]
    assert "open" not in counts


def test_exec_spans_carry_predicted_next_to_realized(traced):
    orch, _, _ = traced
    for s in orch.trace.by_kind("exec"):
        for key in ("pred_exec", "pred_upload", "pred_transfer",
                    "pred_fail", "real_exec", "sched_end", "device",
                    "tier", "ttype", "stage", "outcome"):
            assert key in s.attrs, f"exec span missing {key}"
        assert 0.0 <= s.attrs["pred_fail"] <= 1.0
        if s.attrs["outcome"] == "ok":
            # an ok replica ran exactly to its scheduled end
            assert s.t1 == pytest.approx(s.attrs["sched_end"])


def test_tracing_does_not_perturb_the_run(profile):
    """Bit-identical results with the tracer on and off — the observer
    effect the 'zero overhead when disabled' design rules out."""
    cfg = SimConfig(scenario="churn", n_cycles=1, instances_per_cycle=40,
                    seed=5, n_devices=16, recovery="failover")
    base = run_one("ibdash", cfg, profile)
    traced_res = run_one("ibdash", SimConfig(**{**cfg.__dict__, "trace": True}),
                         profile)
    assert traced_res.trace is not None
    assert base.trace is None
    assert [(r.app, r.finished, r.failed) for r in base.instances] == \
           [(r.app, r.finished, r.failed) for r in traced_res.instances]


def test_disabled_tracing_leaves_no_residue():
    """trace=None (the default): no tracer object, records keep the
    sentinel tid, and no span bookkeeping exists on the engine."""
    cluster = small_cluster()
    eng = Engine(cluster, make_policy("lavea"), noise_sigma=0.0)
    eng.add_arrivals([chain_app()], [0.0])
    eng.drain()
    assert eng.trace is None
    assert all(r.tid == -1 for r in eng.records)
    assert eng._span_of == {}


def test_infeasible_admission_is_traced_as_lost():
    """An instance rejected at planning still opens and closes a trace —
    the ledger must count it."""
    tr = Tracer()
    cluster = small_cluster(n=1)
    churn = deterministic_churn([(0.1, 0, "leave")])
    eng = Engine(cluster, make_policy("lavea"), noise_sigma=0.0,
                 churn=churn, trace=tr)
    eng.add_arrivals([chain_app()], [1.0])   # plans after the only device died
    eng.drain()
    assert eng.stats["lost"] == 1
    (inst,) = list(tr.instances())
    assert inst.attrs["outcome"] == "lost"
    assert inst.attrs["reason"] == "infeasible"
    assert tr.outcome_counts() == {"lost": 1}


# ------------------------------------------------- exec spans == executed --
def _executed_from_trace(tracer):
    """Rebuild the engine's executed-interval log from exec spans alone."""
    return sorted(
        (int(s.attrs["device"]), int(s.attrs["ttype"]), s.t0,
         float(s.attrs["sched_end"]), s.t1)
        for s in tracer.by_kind("exec")
    )


def _rebuild_alloc(cluster_factory, executed):
    c = cluster_factory()
    for did, ttype, t0, t1, t_cut in executed:
        c.add_interval(did, ttype, t0, t1)
        if t_cut < t1:
            c.cancel_from(did, ttype, t0, t1, t_cut)
    return c.alloc


def test_exec_spans_reconstruct_executed_log(traced):
    """Satellite-6 (acceptance half): under correlated churn + salvage the
    exec spans ARE the executed-interval log — tuple for tuple — and
    replaying them onto a fresh cluster reproduces the occupancy tensor
    that ``track_intervals=True`` accumulated."""
    orch, cluster, mk = traced
    eng = orch.engine
    recon = _executed_from_trace(orch.trace)
    assert recon == sorted(eng.executed)
    assert np.array_equal(np.asarray(cluster.alloc),
                          _rebuild_alloc(mk, recon))


@settings(max_examples=15, deadline=None, derandomize=True)
@given(
    deaths=st.lists(
        st.tuples(
            st.floats(min_value=0.05, max_value=20.0),
            st.integers(min_value=0, max_value=3),
            st.one_of(st.none(), st.floats(min_value=0.3, max_value=4.0)),
        ),
        min_size=1, max_size=5,
    ),
    recovery=st.sampled_from(["fail_fast", "failover", "replan"]),
)
def test_exec_spans_replay_property(deaths, recovery):
    """Satellite-6 (property half): for ANY churn schedule and recovery
    mode, exec spans reproduce ``engine.executed`` exactly."""
    events = []
    for t, did, rejoin_after in deaths:
        events.append((t, did, "leave"))
        if rejoin_after is not None:
            events.append((t + rejoin_after, did, "join"))
    schedule = deterministic_churn(events)
    apps = [chain_app(f"#{i}") for i in range(4)]
    times = [5.0 * i for i in range(4)]
    tr = Tracer()
    mk = lambda: small_cluster(base=[0.3, 0.32, 0.34, 0.36], lam=1e-4)
    cluster = mk()
    eng = Engine(cluster, make_policy("lavea"), noise_sigma=0.0,
                 churn=ChurnSchedule(schedule.events),
                 recovery=make_recovery(recovery, detection_delay=0.1),
                 track_intervals=True, trace=tr)
    eng.add_arrivals(apps, times)
    eng.drain()
    tr.check_closed()
    recon = _executed_from_trace(tr)
    assert recon == sorted(eng.executed)
    assert np.array_equal(np.asarray(cluster.alloc),
                          _rebuild_alloc(mk, recon))


# -------------------------------------------------------------- exporters --
def test_chrome_trace_round_trips_the_ledger(traced, tmp_path):
    """The acceptance check: the exported trace_event JSON is structurally
    valid AND the conservation ledger recomputed from the file alone
    equals the live engine counters."""
    orch, _, _ = traced
    path = tmp_path / "trace.json"
    doc = to_chrome_trace(orch.trace, path=str(path))
    n = validate_chrome_trace(doc)
    assert n == len(doc["traceEvents"]) > 0
    # byte round-trip through disk, strict JSON (no NaN/Infinity tokens)
    text = path.read_text()
    assert "NaN" not in text and "Infinity" not in text
    led = ledger_from_trace(json.loads(text))
    assert led["admitted"] == orch.stats["admitted"]
    assert led["completed"] == orch.stats["completed"]
    assert led["lost"] == orch.stats["lost"]
    assert led["shed"] == orch.stats["shed"]
    assert led["admitted"] == led["completed"] + led["lost"] + led["shed"]


def test_chrome_trace_structure(traced):
    orch, _, _ = traced
    ev = to_chrome_trace(orch.trace)["traceEvents"]
    pids = {e["pid"] for e in ev}
    assert pids == {0, 1}                    # instances + devices
    process_names = {e["args"]["name"] for e in ev
                     if e["ph"] == "M" and e["name"] == "process_name"}
    assert process_names == {"instances", "devices"}
    # every exec window sits on its device's row with a flow stitch back
    execs = [e for e in ev if e.get("cat") == "exec" and e["ph"] == "X"]
    assert execs and all(e["pid"] == 1 for e in execs)
    flows = {(e["ph"], e["pid"]) for e in ev if e.get("cat") == "flow"}
    assert ("s", 0) in flows and ("t", 1) in flows
    # churn instants land on device rows
    churn_ev = [e for e in ev if e.get("cat") == "churn"]
    assert churn_ev and all(e["pid"] == 1 and e["ph"] == "i"
                            for e in churn_ev)


def test_export_refuses_open_spans():
    tr = Tracer()
    tid = tr.begin_instance("x", 0.0)
    tr.open_span(tid, "exec", 0.5)
    with pytest.raises(ValueError, match="drain the engine"):
        to_chrome_trace(tr)


def test_ledger_from_trace_rejects_missing_outcome():
    doc = {"traceEvents": [{"name": "i0", "cat": "instance", "ph": "X",
                            "pid": 0, "tid": 0, "ts": 0, "dur": 1,
                            "args": {}}]}
    with pytest.raises(ValueError, match="no terminal outcome"):
        ledger_from_trace(doc)


def test_json_summary(traced, tmp_path):
    orch, _, _ = traced
    reg = MetricsRegistry()
    orch.stats.to_registry(reg)
    path = tmp_path / "summary.json"
    out = json_summary(orch.trace, registry=reg, path=str(path))
    assert out["n_instances"] == orch.stats["admitted"]
    assert out["spans_by_kind"]["exec"] == len(orch.trace.by_kind("exec"))
    on_disk = json.loads(path.read_text())
    assert on_disk["ledger"] == out["ledger"]
    assert on_disk["metrics"]["counters"]["engine_lost"] == orch.stats["lost"]


# ------------------------------------------------------------ attribution --
def _hand_trace():
    """A trace with known arithmetic: 1 s queue, two overlapping execs
    (union 3 s), a recovery wait, 1 s unexplained stall."""
    tr = Tracer()
    tid = tr.begin_instance("app", 1.0)
    tr.add_span(tid, "admission_queue", 0.0, 1.0, slo="best_effort")
    tr.event(tid, "plan", 1.0, policy="p", pred_latency=4.0, pred_fail=0.1)
    tr.add_span(tid, "exec", 1.0, 3.0, name="a", device=0, tier=0, stage=0,
                pred_exec=1.8, pred_upload=0.0, pred_transfer=0.0,
                pred_fail=0.05, sched_end=3.0, outcome="ok")
    tr.add_span(tid, "exec", 2.0, 4.0, name="a", device=1, tier=1, stage=0,
                pred_exec=2.1, pred_upload=0.0, pred_transfer=0.0,
                pred_fail=0.20, sched_end=4.0, outcome="dead")
    tr.add_span(tid, "recovery_wait", 4.0, 4.5, name="a")
    tr.add_span(tid, "exec", 4.5, 5.0, name="b", device=0, tier=0, stage=1,
                pred_exec=0.6, pred_upload=0.0, pred_transfer=0.0,
                pred_fail=0.05, sched_end=5.0, outcome="ok")
    tr.end_instance(tid, 6.0, outcome="completed")
    return tr, tid


def test_instance_breakdown_arithmetic():
    tr, tid = _hand_trace()
    b = instance_breakdown(tr, tid)
    assert b["arrival"] == 0.0                # true arrival = queue start
    assert b["e2e"] == pytest.approx(6.0)
    assert b["queue_wait"] == pytest.approx(1.0)
    assert b["exec_busy"] == pytest.approx(3.5)   # [1,4] u [4.5,5]
    assert b["recovery_wait"] == pytest.approx(0.5)
    assert b["stall"] == pytest.approx(1.0)       # 6 - 1 - 3.5 - 0.5
    assert set(b["stages"]) == {0, 1}
    s0 = b["stages"][0]
    assert s0["n_replicas"] == 2 and s0["critical_device"] == 1
    assert s0["wall"] == pytest.approx(3.0)


def test_calibration_rows():
    from repro.obs.attribution import calibration

    tr, _ = _hand_trace()
    cal = calibration(tr)
    pol = cal["policy"]["p"]
    assert pol["latency"]["n"] == 1
    # e2e from engine arrival (1.0) to end (6.0) = 5.0 vs predicted 4.0
    assert pol["latency"]["real_mean"] == pytest.approx(5.0)
    assert pol["latency"]["bias"] == pytest.approx(1.0)
    assert pol["p_fail"]["empirical"] == 0.0
    # device 1's only replica died -> empirical death rate 1.0
    assert cal["device"]["1"]["p_fail"]["empirical"] == pytest.approx(1.0)
    assert cal["device"]["0"]["p_fail"]["empirical"] == pytest.approx(0.0)
    # duration rows compare pred sum vs realized window
    assert cal["tier"]["0"]["duration"]["n"] == 2
    assert cal["tier"]["0"]["duration"]["pred_mean"] == pytest.approx(1.2)
    assert cal["tier"]["0"]["duration"]["real_mean"] == pytest.approx(1.25)


def test_attribution_report_on_traced_run(traced):
    orch, _, _ = traced
    rep = attribution_report(orch.trace, top_k=3)
    assert rep["ledger"].get("completed", 0) == orch.stats["completed"]
    cp = rep["critical_path"]
    assert cp["n"] == orch.stats["completed"]
    for f in ("e2e", "queue_wait", "exec_busy", "upload_total",
              "transfer_total", "recovery_wait", "stall"):
        assert math.isfinite(cp[f + "_mean"]) and cp[f + "_mean"] >= 0.0
    # the per-stage decomposition never exceeds e2e on any slow offender
    for b in rep["slow"]:
        assert b["queue_wait"] + b["exec_busy"] + b["recovery_wait"] + \
               b["stall"] <= b["e2e"] + 1e-9
    # lost report names the devices whose deaths sank the instance
    assert rep["lost"] and all(b["replica_deaths"] >= 0 for b in rep["lost"])
    assert "ibdash" in rep["calibration"]["policy"]
    text = format_report(rep)
    assert "instance ledger" in text and "calibration: policy" in text
    assert "ibdash" in text


# ------------------------------------------------------- stream tracing --
def test_stream_run_traces_admission(profile):
    """The stream scenario end-to-end with tracing: admission-queue spans
    on dispatched instances, shed instances traced and counted, and the
    exported ledger equal to the engine's, shed included."""
    cfg = SimConfig(scenario="stream", n_cycles=1, cycle_len=6.0,
                    seed=2, n_devices=8, stream_rate=80.0,
                    stream_queue_cap=24, trace=True)
    res = run_one("ibdash", cfg, profile)
    tr = res.trace
    assert tr is not None
    counts = tr.outcome_counts()
    shed = counts.get("shed", 0)
    assert shed > 0, "queue cap chosen to force shedding"
    assert shed == sum(1 for s in tr.by_kind("shed"))
    queue_spans = tr.by_kind("admission_queue")
    assert queue_spans, "dispatched instances carry queue spans"
    assert all(s.dur >= 0.0 for s in queue_spans)
    doc = to_chrome_trace(tr)
    validate_chrome_trace(doc)
    led = ledger_from_trace(doc)
    assert led["shed"] == shed
    assert led["admitted"] == led["completed"] + led["lost"] + led["shed"]
    # the unified registry carries the engine ledger next to service series
    snap = res.stream.metrics
    assert snap["counters"]["engine_admitted"] == led["admitted"]
    assert snap["counters"]["engine_shed"] == led["shed"]


# ------------------------------------------------ the wall-clock recorder --
def _bursts(profile, *, record, seed=7, n_cycles=2, per_cycle=120):
    """Fused bursts planned at each cycle's start and stepped to the next,
    as the chip benchmark's loop does, at a tiny size; the recorder on or
    off.  Wrappers of the test's own count what the recorder should."""
    cfg = SimConfig(n_cycles=n_cycles, instances_per_cycle=per_cycle,
                    seed=seed, n_devices=24)
    cluster = make_cluster(profile, scenario="mix", n_devices=24, seed=seed,
                           horizon=cfg.horizon + 120.0)
    policy = make_policy("ibdash", seed=seed)
    orch = Orchestrator(cluster, policy, seed=seed)
    apps, times = _make_workload(cfg)
    seen = {"G": [], "rows": [], "writes": 0}
    decide = policy.decide_batch

    def decide_batch(batch):
        seen["G"].append(batch.n_distinct)
        seen["rows"].append(batch.n_rows)
        return decide(batch)

    add = ClusterState.add_interval

    def add_interval(self, *args, **kw):
        seen["writes"] += 1
        return add(self, *args, **kw)

    policy.decide_batch = decide_batch
    ClusterState.add_interval = add_interval
    plans = []
    hostspans.clear()
    if record:
        hostspans.enable()
    try:
        for c in range(n_cycles):
            lo, hi = c * cfg.cycle_len, (c + 1) * cfg.cycle_len
            idx = [i for i, t in enumerate(times) if lo <= t < hi]
            wave = orchestrate_batch([apps[i] for i in idx], cluster, policy,
                                     times=[times[i] for i in idx])
            orch.engine.add_arrivals([apps[i] for i in idx],
                                     [times[i] for i in idx], plans=wave)
            plans += wave
            orch.step(hi)
    finally:
        hostspans.disable()
        ClusterState.add_interval = add
    out = {
        "submitted": len(apps),
        "seen": seen,
        "plans": [
            (p.feasible, p.est_latency, [
                (k, tp.ttype, tp.est_start,
                 [(r.did, r.est_exec, r.est_upload, r.est_transfer,
                   r.pred_fail) for r in tp.replicas])
                for k, tp in p.tasks.items()])
            for p in plans
        ],
        "alloc": cluster.alloc.copy(),
        # the collector's passes fire wherever allocation calls for them,
        # so they are kept apart from the program's repeatable spans
        "records": [r for r in hostspans.records() if r.name != "gc.collect"],
        "gc": hostspans.records("gc.collect"),
        "timed": {n: hostspans.timed(n) for n in HOST_SPAN_NAMES},
        "wave_ns": hostspans.last_ns("plan.wave"),
    }
    hostspans.clear()
    return out


@pytest.fixture(scope="module")
def recorded(profile):
    return _bursts(profile, record=True)


def _shape(run):
    """Everything a recorded run says except its times and ids, and the
    collector's passes (which follow the process's allocations)."""
    by_id = {r.id: r for r in run["records"]}
    return (
        [(r.name, by_id[r.parent].name if r.parent else None,
          {k: v for k, v in r.attrs.items() if not k.endswith("_ns")})
         for r in run["records"]],
        {n: c for n, (c, _) in run["timed"].items() if n != "gc.pause"},
    )


def test_host_recorder_off_keeps_nothing(profile, monkeypatch):
    """No profiler session and no enable(): no record, no counter and no
    profiler annotation — yet every span still timed itself."""
    import jax

    made = []

    class Annotation:
        is_enabled = staticmethod(lambda: False)

        def __init__(self, name, **kw):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    assert not hostspans.recording()
    run = _bursts(profile, record=False, n_cycles=1, per_cycle=40)
    assert run["records"] == [] and run["gc"] == [] and made == []
    assert all(v == (0, 0) for v in run["timed"].values())
    assert run["wave_ns"] > 0


def test_host_spans_nest_under_their_parents(recorded):
    """Parent links follow the call tree, every nested span lies inside its
    parent, and all spans of one orchestrate_batch call share its wave id."""
    recs = recorded["records"]
    by_id = {r.id: r for r in recs}
    parent_of = {
        "plan.snapshot": "plan.wave", "plan.screen": "plan.wave",
        "plan.context": "plan.wave", "plan.assemble": "plan.wave",
        "policy.decide": "plan.wave", "policy.select": "policy.decide",
        "policy.kernel": "policy.decide",
    }
    names = {r.name for r in recs}
    assert names == set(parent_of) | {"plan.wave", "engine.step"}
    assert sum(r.name == "plan.wave" for r in recs) == 2
    assert sum(r.name == "engine.step" for r in recs) == 2
    for r in recs:
        assert r.t0 <= r.t1
        if r.name in ("plan.wave", "engine.step"):
            assert r.parent is None
            assert r.wave == (r.id if r.name == "plan.wave" else None)
            continue
        up = by_id[r.parent]
        assert up.name == parent_of[r.name]
        assert up.t0 <= r.t0 and r.t1 <= up.t1
        assert r.wave == up.wave and by_id[r.wave].name == "plan.wave"


@pytest.mark.parametrize("count", [
    "arrivals", "talloc_writes", "distinct_rows", "rows_kept", "launches"])
def test_host_counts_are_exact(recorded, count):
    recs, seen = recorded["records"], recorded["seen"]
    steps = [r for r in recs if r.name == "engine.step"]
    if count == "arrivals":
        assert recorded["timed"]["engine.arrival"][0] == recorded["submitted"]
        assert sum(r.attrs["arrival"] for r in steps) == recorded["submitted"]
    elif count == "talloc_writes":
        # planning is pure, so every write of the run was the engine's
        assert recorded["timed"]["talloc.write"][0] == seen["writes"] > 0
        assert sum(r.attrs["talloc_writes"] for r in steps) == seen["writes"]
    elif count == "distinct_rows":
        got = [r.attrs["G"] for r in recs if r.name == "plan.context"]
        assert got == seen["G"]
        kernel = [r.attrs["G"] for r in recs if r.name == "policy.kernel"]
        assert kernel and kernel == [g for g in seen["G"] if g >= 8]
    elif count == "rows_kept":
        got = [r.attrs["rows_kept"] for r in recs
               if r.name == "plan.screen" and r.attrs["rows_kept"]]
        assert got == seen["rows"]
        assert [r.attrs["B"] for r in recs if r.name == "policy.decide"] \
            == seen["rows"]
    else:
        # every replica launch is a T_alloc write at its start, and the
        # engine's three timed counters split its events between them
        launches = sum(r.attrs["launches"] for r in steps)
        assert 0 < launches <= seen["writes"]
        events = sum(r.attrs["arrival"] + r.attrs["task_end"]
                     + r.attrs["other"] for r in steps)
        assert recorded["timed"]["engine.task_end"][0] == \
            sum(r.attrs["task_end"] for r in steps) == launches
        assert events == recorded["submitted"] + launches


def test_host_counts_repeat_on_the_same_seed(profile, recorded):
    again = _bursts(profile, record=True)
    assert _shape(again) == _shape(recorded)


def test_recording_leaves_plans_and_talloc_bit_identical(profile, recorded):
    off = _bursts(profile, record=False)
    assert off["records"] == []
    assert off["plans"] == recorded["plans"]
    assert off["alloc"].tobytes() == recorded["alloc"].tobytes()
    assert off["seen"] == recorded["seen"]


def test_engine_timed_counters_partition_the_step(recorded):
    """Each event is charged from its pop to the next pop, so the three
    event counters add up to nearly all of engine.step."""
    for r in (r for r in recorded["records"] if r.name == "engine.step"):
        charged = (r.attrs["arrival_ns"] + r.attrs["task_end_ns"]
                   + r.attrs["other_ns"])
        assert r.attrs["talloc_ns"] < charged <= r.ns


def test_host_recorder_follows_the_profiler(profile, tmp_path):
    """A jax.profiler session turns recording on with no enable(), and
    the coarse spans then come with profiler annotations."""
    import jax

    cluster = make_cluster(profile, scenario="mix", n_devices=12, seed=4,
                           horizon=200.0)
    apps, times = _make_workload(SimConfig(n_cycles=1, instances_per_cycle=30,
                                           seed=4, n_devices=12))
    hostspans.clear()
    with jax.profiler.trace(str(tmp_path)):
        assert hostspans.recording()
        orchestrate_batch(apps, cluster, "ibdash", times=times)
    assert not hostspans.recording()
    names = {r.name for r in hostspans.records()}
    hostspans.clear()
    assert {"plan.wave", "plan.snapshot", "plan.screen", "plan.context",
            "plan.assemble", "policy.decide"} <= names


def test_replan_time_reads_the_replan_spans(profile, traced):
    """Engine.replan_time keeps its meaning: the wall time of the recovery
    replans, read from their plan.replan spans, recorded or not."""
    assert traced[0].engine.replan_time > 0.0
    hostspans.clear()
    hostspans.enable()
    try:
        orch, _, _ = _traced_orchestrator(profile)
    finally:
        hostspans.disable()
    replans = hostspans.records("plan.replan")
    other = hostspans.timed("engine.other")
    down, up, recover = (hostspans.timed(n) for n in (
        "engine.device_down", "engine.device_up", "engine.recover"))
    hostspans.clear()
    assert len(replans) == orch.stats["replans"] + orch.stats["salvages"]
    assert orch.engine.replan_time == pytest.approx(
        sum(r.ns for r in replans) / 1e9, rel=1e-9)
    # engine.other is the sum of the three event kinds it covers
    assert (down[0] + up[0] + recover[0], down[1] + up[1] + recover[1]) \
        == other
    assert down[0] == orch.stats["device_down"]
    assert up[0] == orch.stats["device_up"]
    assert recover[0] >= orch.stats["replans"]


def test_wave_plan_metrics_read_the_wave_spans(profile, monkeypatch):
    """The service's wave_plan_s histogram and placements_per_sec gauge
    keep their names and meaning: the plan.wave span of each wave the
    service plans (the admission estimator plans probes of its own)."""
    from repro.stream import service

    plan = service.orchestrate_batch
    dispatched = []

    def orchestrate_batch(apps, *args, **kw):
        plans = plan(apps, *args, **kw)
        dispatched.append(hostspans.records("plan.wave")[-1])
        return plans

    monkeypatch.setattr(service, "orchestrate_batch", orchestrate_batch)
    cfg = SimConfig(scenario="stream", n_cycles=1, cycle_len=6.0,
                    seed=2, n_devices=8, stream_rate=80.0)
    hostspans.clear()
    hostspans.enable()
    try:
        res = run_one("ibdash", cfg, profile)
    finally:
        hostspans.disable()
        hostspans.clear()
    snap = res.stream.metrics
    h = snap["histograms"]["wave_plan_s"]
    total = sum(w.ns for w in dispatched) / 1e9
    assert h["count"] == len(dispatched) > 0
    assert h["mean"] * h["count"] == pytest.approx(total, rel=1e-9)
    planned = sum(w.attrs["apps"] for w in dispatched)
    assert snap["gauges"]["placements_per_sec"] == pytest.approx(
        planned / total, rel=1e-9)


# ------------------------------------------- the garbage-collector hook --
GC_INFO = {"generation": 0, "collected": 0, "uncollectable": 0}


@pytest.fixture
def collector_quiet():
    """No automatic collection while the test runs (explicit passes still
    run the callbacks), and the recorder left off and empty after it."""
    was = gc.isenabled()
    gc.disable()
    hostspans.clear()
    try:
        yield
    finally:
        hostspans.disable()
        hostspans.clear()
        if was:
            gc.enable()


def test_forced_collection_in_a_recorded_span_is_one_record(collector_quiet):
    hostspans.enable()
    with hostspans.span("plan.wave"):
        with hostspans.span("plan.assemble") as sp:
            gc.collect()
    hostspans.disable()
    rec, = hostspans.records("gc.collect")
    assert rec.attrs["generation"] == 2
    assert set(rec.attrs) == {"generation", "collected", "uncollectable"}
    assert rec.parent == sp.id and rec.wave == sp.wave == sp.parent
    assert sp.t0 <= rec.t0 < rec.t1 <= sp.t1
    assert hostspans.timed("gc.pause") == (1, rec.ns)
    # a pass outside every span under enable() is kept without a parent
    hostspans.enable()
    gc.collect(0)
    rec = hostspans.records("gc.collect")[-1]
    assert rec.parent is None and rec.wave is None
    assert rec.attrs["generation"] == 0


def test_collector_hook_keeps_nothing_while_off(collector_quiet,
                                                monkeypatch):
    """Off, a pass is only a flag check: no record, no counter.  In no
    state does the hook ask JAX's profiler anything."""
    import jax

    asked = []

    class Annotation:
        @staticmethod
        def is_enabled():
            asked.append("is_enabled")
            return False

        def __init__(self, name, **kw):
            asked.append(name)

    assert not hostspans.recording()
    with hostspans.span("plan.wave"):
        gc.collect()
    assert hostspans.records("gc.collect") == []
    assert hostspans.timed("gc.pause") == (0, 0)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    hostspans._on_gc("start", GC_INFO)
    hostspans._on_gc("stop", GC_INFO)
    hostspans.enable()
    hostspans._on_gc("start", GC_INFO)
    hostspans._on_gc("stop", GC_INFO)
    assert asked == []
    assert len(hostspans.records("gc.collect")) == 1


def test_clear_and_disable_leave_the_collector_hook_harmless(
        collector_quiet):
    assert gc.callbacks.count(hostspans._on_gc) == 1
    hostspans.enable()
    gc.collect()
    hostspans.clear()
    assert hostspans.records() == [] and hostspans.timed("gc.pause") == (0, 0)
    gc.collect()
    assert len(hostspans.records("gc.collect")) == 1
    assert hostspans.timed("gc.pause")[0] == 1
    # a pass that began while recording and ended after disable() is
    # dropped, and its start is not carried over to a later pass's end
    hostspans._on_gc("start", GC_INFO)
    hostspans.disable()
    hostspans._on_gc("stop", GC_INFO)
    hostspans.enable()
    hostspans._on_gc("stop", GC_INFO)
    hostspans.disable()
    gc.collect()
    assert len(hostspans.records("gc.collect")) == 1
    assert hostspans.timed("gc.pause")[0] == 1


def test_record_outside_the_schema_raises(collector_quiet):
    hostspans.enable()
    with pytest.raises(ValueError, match="gc.rogue"):
        hostspans._record("gc.rogue", 1, 2)
    hostspans._record("gc.collect", 1, 2, generation=0)
    assert [r.name for r in hostspans.records()] == ["gc.collect"]


def test_gc_pause_is_the_sum_of_the_records(recorded):
    """Over the recorded bursts the collector ran on its own; its counter
    is the records' count and time, and each record lies in its parent."""
    recs = recorded["gc"]
    assert recs and all(r.name == "gc.collect" for r in recs)
    assert recorded["timed"]["gc.pause"] == (len(recs),
                                             sum(r.ns for r in recs))
    by_id = {r.id: r for r in recorded["records"]}
    for r in recs:
        assert 0 < r.ns and r.attrs["generation"] in (0, 1, 2)
        if r.parent is not None:
            up = by_id[r.parent]
            assert up.t0 <= r.t0 and r.t1 <= up.t1 and r.wave == up.wave
