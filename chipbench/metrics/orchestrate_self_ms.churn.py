"""Orchestration self time per wave on the churn cell, as
``orchestrate_self_ms.burst`` reads it: ``orchestrate_batch`` minus the
``decide_batch`` calls inside it.  The replans' ``decide_batch`` calls
run inside ``step`` and are not counted."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import load_module  # noqa: E402

read = load_module(
    Path(__file__).with_name("orchestrate_self_ms.burst.py")).read
