"""Engine DEVICE_DOWN events per wave: the program's timed counter
``engine.device_down`` (each departure from its pop to the next event's:
the kills, the occupancy returned to T_alloc, the recovery hooks), summed
over the window's steps, over the number of steps."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import churn_spans  # noqa: E402


def read(run):
    return churn_spans.step_counter_ms(run, "device_down_ns")
