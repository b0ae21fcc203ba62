"""Plan assembly per wave: the program's ``plan.assemble`` spans (each
wave-stage's decisions to replicas and task placements, the stage fold,
the final plan list) inside each wave's ``orchestrate_batch`` span."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import program_spans  # noqa: E402


def read(run):
    return program_spans.wave_ms(run, ("plan.assemble",))
