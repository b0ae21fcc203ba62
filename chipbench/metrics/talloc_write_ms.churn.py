"""T_alloc writes per wave on the churn cell, as ``talloc_write_ms.burst``
reads it (the program's timed counter ``talloc.write``): here also the
occupancy a departure returns and the replans apply."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import load_module  # noqa: E402

read = load_module(Path(__file__).with_name("talloc_write_ms.burst.py")).read
