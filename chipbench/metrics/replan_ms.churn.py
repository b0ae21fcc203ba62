"""Replanning per wave: the program's ``plan.replan`` spans (one per
replan: the pinned ``orchestrate`` of a dead task and the rest of its
instance) inside each wave's ``step`` span, where the departures'
recoveries run."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import churn_spans  # noqa: E402


def read(run):
    return churn_spans.step_span_ms(run, ("plan.replan",))
