"""Benchmark harness: one module per paper table/figure (+ beyond-paper).

Prints ``name,value,derived`` CSV rows.  Set REPRO_BENCH_FULL=1 for the
paper-scale protocol (20 cycles x 1000 instances, fine-grained sweeps).

    PYTHONPATH=src python -m benchmarks.run [bench ...]
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from . import (
    bench_alpha_gamma,
    bench_availability,
    bench_churn,
    bench_failure,
    bench_interference,
    bench_load,
    bench_microscopic,
    bench_obs,
    bench_place,
    bench_profiles,
    bench_roofline,
    bench_service_time,
    bench_serving,
    bench_serving_shard,
    bench_stream,
)
from .common import Ctx
from repro.compile_cache import enable_compile_cache

BENCHES = {
    "interference": bench_interference,   # Fig. 2 / Fig. 4
    "profiles": bench_profiles,           # Table III / Fig. 5
    "availability": bench_availability,   # Fig. 7 / Table IV
    "service_time": bench_service_time,   # Fig. 8
    "failure": bench_failure,             # Fig. 9
    "load": bench_load,                   # Fig. 10
    "microscopic": bench_microscopic,     # Fig. 11
    "alpha_gamma": bench_alpha_gamma,     # Fig. 12
    "place": bench_place,                 # beyond-paper burst placement
    "churn": bench_churn,                 # beyond-paper churn recovery
    "serving": bench_serving,             # beyond-paper fleet policies
    "roofline": bench_roofline,           # §Roofline (dry-run grid)
    "serving_shard": bench_serving_shard, # beyond-paper TP serving sharding
    "stream": bench_stream,               # beyond-paper always-on service
    "obs": bench_obs,                     # observability overhead + validity
}


def main() -> None:
    names = sys.argv[1:] or list(BENCHES)
    enable_compile_cache()
    ctx = Ctx()
    print("name,value,derived")
    for name in names:
        mod = BENCHES[name]
        t0 = time.time()
        print(f"# === {name} ===", file=sys.stderr)
        mod.run(ctx)
        print(f"# {name} took {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
