"""Engine TASK_END events per wave: the program's timed counter
``engine.task_end`` (each task end from its pop to the next event's: the
retire, the later stages' launches), summed over the window's steps, over
the number of steps."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import program_spans  # noqa: E402


def read(run):
    return program_spans.step_ms(run, "task_end_ns")
