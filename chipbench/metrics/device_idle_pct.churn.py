"""Device idle share of the churn cell's window, as ``device_idle_pct.burst``
reads it (profiler trace)."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import load_module  # noqa: E402

read = load_module(Path(__file__).with_name("device_idle_pct.burst.py")).read
