"""The garbage collector's time per wave on the churn cell, as
``gc_pause_ms.burst`` reads it: every pass inside each wave's
``orchestrate_batch`` and ``step`` spans, over the number of waves."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import load_module  # noqa: E402

read = load_module(Path(__file__).with_name("gc_pause_ms.burst.py")).read
