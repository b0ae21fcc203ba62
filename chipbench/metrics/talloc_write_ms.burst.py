"""T_alloc writes per wave: the program's timed counter ``talloc.write``
(every ``ClusterState.add_interval`` call inside the engine, whatever
event made it), summed over the window's steps, over the number of
steps.  Part of the two engine event counters, not beside them."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import program_spans  # noqa: E402


def read(run):
    return program_spans.step_ms(run, "talloc_ns")
