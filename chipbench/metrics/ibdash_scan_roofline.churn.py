"""Share of its roofline that the IBDASH scan reaches on the churn cell, as
``ibdash_scan_roofline.burst`` reads it: the arrivals' waves are the only
dispatches (a replan plans one instance, whose widest stage has 6 tasks,
under the scan's 8-row threshold), so the scan's device time in the window
is theirs."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import load_module  # noqa: E402

read = load_module(
    Path(__file__).with_name("ibdash_scan_roofline.burst.py")).read
