"""Wave context build per wave on the churn cell, as ``context_build_ms.burst``
reads it: the program's ``plan.snapshot`` and ``plan.context`` spans inside
each wave's ``orchestrate_batch`` span, here with the churn schedule's
survival forecast installed on the fleet.  The replans' own spans run
inside ``step`` and are not counted."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import load_module  # noqa: E402

read = load_module(Path(__file__).with_name("context_build_ms.burst.py")).read
