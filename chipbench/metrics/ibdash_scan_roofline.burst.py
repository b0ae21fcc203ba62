"""Share of its roofline that the IBDASH scan reaches: the least time of
the scan's work at the chip's peaks (``work.py``, from each wave-stage's
distinct rows G and fleet size D) over the device time of the scan's
programs in the window (profiler trace)."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import work  # noqa: E402


def read(run):
    tr = run.trace
    if tr is None or not tr.has_device or not run.peaks:
        return None
    device_s = tr.module_ns("ibdash_scan_kernel") / 1e9
    calls = []
    for i in run.waves:
        scans = run.within(i, "kernel:ibdash_scan_kernel")
        calls += [(sp.info["G"], sp.info["D"]) for sp in run.within(i, "decide_batch")
                  if any(sp.t0 <= k.t0 and k.t1 <= sp.t1 for k in scans)]
    if device_s <= 0 or not calls:
        return None
    gamma = int(run.setup.config["policy"]["gamma"])
    return 100.0 * work.wave_least_seconds(calls, gamma, run.peaks) / device_s
