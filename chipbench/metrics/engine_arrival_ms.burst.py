"""Engine ARRIVAL events per wave: the program's timed counter
``engine.arrival`` (each arrival from its pop to the next event's: the
apply into T_alloc, the record, the first stage's launches), summed over
the window's steps, over the number of steps."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import program_spans  # noqa: E402


def read(run):
    return program_spans.step_ms(run, "arrival_ns")
