"""Device time per wave on the churn cell, as ``kernel_device_ms.burst``
reads it: every device op inside a wave's ``orchestrate_batch`` span (the
replans, inside ``step``, dispatch none)."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import load_module  # noqa: E402

read = load_module(Path(__file__).with_name("kernel_device_ms.burst.py")).read
