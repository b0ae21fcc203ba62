"""The garbage collector's time per wave: every pass (the program's
``gc.collect`` records) inside each wave's ``orchestrate_batch`` and
``step`` spans, over the number of waves."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import gc_spans  # noqa: E402


def read(run):
    return gc_spans.pause_ms(run)
