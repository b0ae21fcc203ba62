"""The chip benchmark of the DAG orchestrator.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on, which
must hold a TPU with at least as many chips as the cell asks for; without
one it exits with code 2 and prints no result.  Set-up builds the fleet and
the traffic from ``--seed`` and warms the placement kernels; the window then
measures for ``--seconds``.  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from host spans and a profiler trace of the window.  Either way the window's
plans are then compared with the plain reference (``check.py``), and the
last line of standard output is one JSON object::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

``checks`` holds each number compared with its limit; the same lines end
standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _finite(x):
    """JSON has no infinity: a gap that is infinite is reported as the
    largest float."""
    if isinstance(x, float) and not math.isfinite(x):
        return sys.float_info.max
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(f"[chipbench] {msg}", file=sys.stderr, flush=True)

    bench_path = ROOT / "BENCHMARK.json"
    try:
        bench = json.loads(bench_path.read_text())
        wl = next(w for w in bench["workloads"] if w["name"] == args.workload)
    except (OSError, ValueError, StopIteration) as exc:
        log(f"no workload {args.workload!r} in {bench_path}: {exc!r}")
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    try:
        import repro.api  # noqa: F401  the system under test
    except ImportError as exc:
        log(f"the program is not in this checkout: {exc!r}")
        return 2

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < int(wl["chips"]):
        log(f"needs {wl['chips']} TPU chip(s); JAX found {len(devices)} "
            f"{dev.platform} device(s): nothing was measured")
        return 2
    peaks = json.loads((HERE / "peaks.json").read_text())
    if dev.device_kind not in peaks:
        log(f"no peaks for device kind {dev.device_kind!r} in peaks.json")
        return 2
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")

    from harness import RunError, run_cell

    try:
        out = run_cell(bench, wl, args.seed, args.seconds, bool(args.trace),
                       log=log, root=ROOT, t_start=T_START)
    except RunError as exc:
        log(f"run failed: {exc}")
        return 3
    for c in out["checks"].values():
        c["value"] = _finite(c["value"])
    for name, c in out["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
