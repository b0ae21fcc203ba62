"""Plan assembly per wave on the churn cell, as ``plan_assembly_ms.burst``
reads it: the program's ``plan.assemble`` spans inside each wave's
``orchestrate_batch`` span.  The replans' own spans run inside ``step``
and are not counted."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import load_module  # noqa: E402

read = load_module(Path(__file__).with_name("plan_assembly_ms.burst.py")).read
