"""The work behind ``ibdash_scan_roofline``: bytes and operations of the
IBDASH score-and-replicate scan (Algorithm 1 lines 29-41) for one
wave-stage, from the wave's distinct context rows ``G``, the fleet size
``D`` and the policy's replication cap ``gamma`` — never from the padded
shapes a kernel happens to be called with.

Derivation.  Each distinct row is one task whose candidates are already in
ascending order of Eq. 2 latency (lines 16-18).  Every scan step either
accepts a replica (at most ``gamma`` times) or ends the row, so a row can
reach at most ``n = min(gamma + 1, D - 1)`` steps over ``K = n + 1``
candidates.  The least a device must move per row is its inputs once and
its output once:

* reads:  ``K`` latencies and ``K`` failure probabilities in float64
  (``16 K`` bytes) and the count of feasible devices (8 bytes);
* writes: one accept flag per step (``n`` bytes).

Its floating-point operations per row: the latency reference
``max(best, 1e-9)`` and the first weighted score
``alpha * best / l_ref + (1 - alpha) * pf`` (1 + 4), then per step the
combined failure ``comb * pf`` and the candidate's weighted score
(1 + 4).  Comparisons and selects are not counted.

So for ``G`` rows: ``bytes = G (16 K + 8 + n)`` and
``flops = G (5 + 5 n)``.  A wave of ``S`` stages sums its stages.  The
least time at the chip's peaks is ``max(flops / peak_flops,
bytes / peak_bytes_per_s)``; the metric is that least time over the
device time of the scan's programs.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def ibdash_scan(G: int, D: int, gamma: int) -> Tuple[int, int]:
    """(flops, bytes) of the scan over ``G`` distinct rows on ``D`` devices."""
    if G <= 0 or D <= 1:
        return 0, 0
    n = min(int(gamma) + 1, int(D) - 1)
    k = n + 1
    return G * (5 + 5 * n), G * (16 * k + 8 + n)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / float(peaks["flops_per_s"]),
               nbytes / float(peaks["hbm_bytes_per_s"]))


def wave_least_seconds(calls: Iterable[Tuple[int, int]], gamma: int,
                       peaks: dict) -> float:
    """Least time of a set of scan calls, each ``(G, D)``."""
    total = 0.0
    for G, D in calls:
        f, b = ibdash_scan(G, D, gamma)
        total += least_seconds(f, b, peaks)
    return total
