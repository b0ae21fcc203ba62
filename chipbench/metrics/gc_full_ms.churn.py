"""The garbage collector's full collections per wave on the churn cell, as
``gc_full_ms.burst`` reads them (generation-2 passes)."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import load_module  # noqa: E402

read = load_module(Path(__file__).with_name("gc_full_ms.burst.py")).read
