"""Engine RECOVER events per wave: the program's timed counter
``engine.recover`` (each RECOVER from its pop to the next event's: the
replan, its apply and the relaunch), summed over the window's steps, over
the number of steps."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import churn_spans  # noqa: E402


def read(run):
    return churn_spans.step_counter_ms(run, "recover_ns")
