"""The readings behind each limit of ``correct``: sound runs, the control
and the planted faults, at the cell's own size.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]
        [--fault state_unchanged|half_batch|answer_altered]

For each seed, in one process: set up the cell, run its window at the
cell's own size and load, and decide ``correct`` with the same
``check.compare`` and ``check.verdict`` as a run of ``run.py``.  Without
``--fault`` it decides twice over the same sampled tasks: once for the
program (the lower reading: what sound runs give) and once for the control,
the reference computed in float32 put in the program's place (the next
precision below the float64 the configuration states), which has to come
out as not correct.  With ``--fault`` the fault is planted in the program
under the timed path (``faults.py``) and the run has to come out as not
correct.  Prints one JSON line per seed.  Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    from faults import FAULTS, planted

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from check import compare, verdict
    from harness import load_json, measure

    if jax.devices()[0].platform != "tpu":
        print("control.py needs a TPU", file=sys.stderr)
        return 2
    bench = load_json(ROOT / "BENCHMARK.json")
    wl = next(w for w in bench["workloads"] if w["name"] == args.workload)
    for seed in args.seeds:
        fault = planted(args.fault) if args.fault else contextlib.nullcontext()
        with fault:
            run = measure(bench, wl, seed, args.seconds, False)
            try:
                line = {"workload": wl["name"], "seed": seed,
                        "fault": args.fault, "waves": run.notes.get("waves")}
                checks = compare(run)
                line["program"] = {"correct": verdict(checks), "checks": checks,
                                   "checked": run.notes["checked"]}
                if not args.fault:
                    ctl = compare(run, control_dtype=np.float32)
                    line["control_float32"] = {
                        "correct": verdict(ctl), "checks": ctl,
                        "checked": run.notes["control checked"]}
            finally:
                run.setup.inst.remove()
        print(json.dumps(line), flush=True)
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
