"""Wave context build per wave: the program's ``plan.snapshot`` (fleet
vectors at the planning instant) and ``plan.context`` (each wave-stage's
pricing tensors) spans inside each wave's ``orchestrate_batch`` span."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import program_spans  # noqa: E402


def read(run):
    return program_spans.wave_ms(run, ("plan.snapshot", "plan.context"))
