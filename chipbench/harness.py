"""One run of one cell: set-up, the measured window, the per-layer
reduction and the comparison with the reference.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own under ``chipbench/`` and is found by the
name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``  — the deployment's sizes (fleet, policy,
  T_alloc window, the limits of the comparison); ``deployment_module``
  names ``deployments/<name>.py``, by default ``paper``;
* ``deployments/<name>.py``  — what set-up builds from the configuration,
  its warm-up, and the reference ``check.py`` replays against
  (``deployments/README.md``);
* ``traffic/<traffic>.json`` — the mix; ``driver`` names
  ``drivers/<driver>.py``, the rest are that driver's parameters;
* ``metrics/<metric>.py``    — ``read(run)`` returns the metric or None.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
import warnings
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class RunError(RuntimeError):
    """The run cannot produce a result (it prints none)."""


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: Path):
    """Import a file by path (metric files have dots in their names), once
    per file: a driver and a deployment may share a name."""
    name = "chipbench_" + "".join(c if c.isalnum() else "_"
                                  for c in str(Path(path).resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cache_dir(root: Path) -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``
    (a fixed path: the directory is part of what a later run must find)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")


def deployment_for(config: dict, root: Path = ROOT):
    """The deployment module a configuration names (``deployment_module``),
    ``paper`` where it names none."""
    name = config.get("deployment_module", "paper")
    path = root / HERE.name / "deployments" / f"{name}.py"
    if not path.is_file():
        raise RunError(f"no deployment module {name!r} at {path}")
    return load_module(path)


@dataclass
class Setup:
    """What set-up builds from the seed through the configuration's
    deployment module: the fleet, the policy, the orchestrator with its
    instrumentation, and the fleet as data for the reference."""

    config: dict
    traffic: dict
    seed: int
    trace: bool
    deployment: object = None
    cluster: object = None
    orch: object = None
    policy: object = None
    inst: object = None
    fleet: object = None

    def build(self) -> "Setup":
        from instrument import Instrument

        self.deployment.build(self)
        self.inst = Instrument(trace=self.trace).install(self.orch, self.policy)
        return self

    def warm_fleet(self, t: float) -> None:
        """The deployment's warm-up of fleet state at sim time ``t``."""
        self.deployment.warm_fleet(self, t)

    def warm_kernels(self, rows: List[int]) -> None:
        """The deployment's warm-up of its kernels at each padded row count
        the traffic produces."""
        self.deployment.warm_kernels(self, rows)


@dataclass
class RunRecord:
    """What the window leaves for the per-layer readers."""

    setup: Setup
    seconds: float
    first_span: int = 0
    window_ns: tuple = (0, 0)
    waves: List[int] = field(default_factory=list)   # orchestrate span ids
    steps: List[int] = field(default_factory=list)   # step span ids
    schedule: list = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    e2e: Dict[str, float] = field(default_factory=dict)
    trace: object = None        # metrics.trace_reduce.Trace in traced runs
    tracer: object = None       # metrics.trace_reduce.Tracer in traced runs
    peaks: dict = field(default_factory=dict)
    notes: Dict[str, object] = field(default_factory=dict)

    @property
    def spans(self):
        return self.setup.inst.spans

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    def within(self, idx: int, name: Optional[str] = None):
        """The spans nested inside span ``idx`` (optionally by name)."""
        return self.setup.inst.children(idx, name)


def per_layer_for(bench: dict, wl: dict) -> List[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list that move an end-to-end metric the cell reports."""
    mine = {m["name"] for m in e2e_for(bench, wl)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if wl["name"] in m["workloads"]:
                out.append(m)
        elif m["moves"] in mine:
            out.append(m)
    return out


def e2e_for(bench: dict, wl: dict) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or wl["name"] in m["workloads"]]


def measure(bench: dict, wl: dict, seed: int, seconds: float, trace: bool,
            *, root: Path = ROOT, config_overrides: Optional[dict] = None,
            traffic_overrides: Optional[dict] = None,
            log: Callable[[str], None] = print,
            t_start: Optional[float] = None,
            compile_cache: bool = True) -> RunRecord:
    """Set up the cell from the seed and run its window.  ``t_start`` is
    the process's start on ``time.perf_counter``: set-up counts from there.
    ``compile_cache=False`` leaves JAX's configuration alone (tests).  The
    caller removes ``run.setup.inst`` when it is done with the run."""
    import jax

    if t_start is None:
        t_start = time.perf_counter()
    if compile_cache:
        jax.config.update("jax_compilation_cache_dir", cache_dir(root))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = {**load_json(root / cfg_entry["file"]), **(config_overrides or {})}
    bench_dir = root / HERE.name
    traffic = {**load_json(bench_dir / "traffic" / f"{wl['traffic']}.json"),
               **(traffic_overrides or {})}
    driver = load_module(bench_dir / "drivers" / f"{traffic['driver']}.py")
    deployment = deployment_for(config, root)

    t_enter = time.perf_counter()
    setup = Setup(config, traffic, seed, trace, deployment).build()
    t_built = time.perf_counter()
    run = RunRecord(setup=setup, seconds=float(seconds))
    try:
        drv = driver.Driver(setup, run)
        drv.prepare()
        t_ready = time.perf_counter()
        run.e2e["setup_s"] = t_ready - t_start
        run.notes["set-up s (start to harness, fleet build, warm-up)"] = (
            t_enter - t_start, t_built - t_enter, t_ready - t_built)
        compiles0 = setup.inst.compiles
        if trace:
            run.tracer = load_module(HERE / "metrics" / "trace_reduce.py").Tracer()
            run.tracer.start()
        try:
            with warnings.catch_warnings(), setup.inst.span("window"):
                # an interval past T_alloc's horizon fails the run, never clips
                warnings.filterwarnings("error", message="T_alloc interval extends")
                drv.window()
        finally:
            if run.tracer is not None:
                run.tracer.stop()
    except BaseException:
        setup.inst.remove()
        raise
    run.notes["compiles inside the window"] = setup.inst.compiles - compiles0
    run.notes["persistent-cache hits in the run"] = setup.inst.cache_hits
    run.notes["kernel rows in the window"] = sorted({
        sp.info["shape"][0] for sp in run.spans[run.first_span:]
        if sp.name.startswith("kernel:")})
    return run


def run_cell(bench: dict, wl: dict, seed: int, seconds: float, trace: bool,
             *, log: Callable[[str], None] = print, **kw) -> dict:
    """Run one cell and return the result object (see ``run.py``)."""
    run = measure(bench, wl, seed, seconds, trace, log=log, **kw)
    try:
        return _report(bench, wl, run, log)
    finally:
        run.setup.inst.remove()
        if run.tracer is not None:
            run.tracer.cleanup()


def _report(bench: dict, wl: dict, run: RunRecord, log) -> dict:
    import jax

    from check import compare, verdict

    trace = run.tracer is not None
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        run.peaks = load_json(HERE / "peaks.json")[dev.device_kind] \
            if dev.platform == "tpu" else {}
        run.trace = run.tracer.read(run)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
        for m in per_layer_for(bench, wl):
            value = load_module(HERE / "metrics" / f"{m['name']}.py").read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e_for(bench, wl):
            metrics[m["name"]] = {"value": run.e2e[m["name"]], "unit": m["unit"]}

    t_ref = time.perf_counter()
    checks = compare(run)
    run.notes["reference check s"] = time.perf_counter() - t_ref
    for k, v in run.notes.items():
        log(f"{k}: {v}")
    correct = verdict(checks)
    out = {"correct": correct, "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
