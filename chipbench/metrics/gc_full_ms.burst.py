"""The garbage collector's full collections per wave: the generation-2
passes of ``gc_pause_ms.burst``, the part that grows with every object the
process keeps alive."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import gc_spans  # noqa: E402


def read(run):
    return gc_spans.pause_ms(run, generations=(2,))
