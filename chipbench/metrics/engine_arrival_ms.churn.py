"""Engine ARRIVAL events per wave on the churn cell, as
``engine_arrival_ms.burst`` reads it (the program's timed counter
``engine.arrival``)."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import load_module  # noqa: E402

read = load_module(Path(__file__).with_name("engine_arrival_ms.burst.py")).read
