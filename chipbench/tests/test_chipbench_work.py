"""The work count behind ``ibdash_scan_roofline``."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import work  # noqa: E402

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("G, D, gamma, flops, nbytes", [
    # gamma 3 on a big fleet: 4 steps over 5 candidates per row
    (10, 10_000, 3, 10 * (5 + 5 * 4), 10 * (16 * 5 + 8 + 4)),
    # a 3-device fleet caps the steps at D - 1 = 2
    (10, 3, 3, 10 * (5 + 5 * 2), 10 * (16 * 3 + 8 + 2)),
    # no replication: one step decides the row
    (7, 100, 0, 7 * (5 + 5 * 1), 7 * (16 * 2 + 8 + 1)),
    (0, 100, 3, 0, 0),
    (5, 1, 3, 0, 0),
])
def test_scan_work(G, D, gamma, flops, nbytes):
    assert work.ibdash_scan(G, D, gamma) == (flops, nbytes)


def test_least_time_is_memory_bound_for_the_scan():
    f, b = work.ibdash_scan(2048, 10_000, 3)
    assert work.least_seconds(f, b, PEAKS) == pytest.approx(b / 819e9)
    calls = [(1250, 10_000), (1000, 10_000), (750, 10_000), (250, 10_000)]
    total_b = sum(work.ibdash_scan(g, d, 3)[1] for g, d in calls)
    assert work.wave_least_seconds(calls, 3, PEAKS) == pytest.approx(total_b / 819e9)
