"""Engine time per wave on the churn cell, as ``engine_ms.burst`` reads it:
``Orchestrator.step`` to the next cycle, which here also runs the
departures, rejoins and replans."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import load_module  # noqa: E402

read = load_module(Path(__file__).with_name("engine_ms.burst.py")).read
