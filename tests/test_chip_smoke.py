"""``chip_smoke.py``'s phases on the CPU at a tiny size.

The chip run drives phases A and B at 10k and 100k devices; here the same
functions run on ~200-device fleets, so a wrong path, a kernel that stops
being dispatched, or a fused placement that leaves the scalar reference
fails in the suite before it costs chip time.  The TPU device check in
``main`` is what keeps the script itself off the CPU.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.core import batched  # noqa: E402


def test_fused_wave_matches_scalar_and_dispatches_every_kernel():
    rep = chip_smoke.fused_wave(n_devices=200, n_instances=64)
    assert set(rep["dispatches"]) == set(chip_smoke.KERNELS)
    assert all(n > 0 for n in rep["dispatches"].values())
    for scheme, row in rep["policies"].items():
        assert row["dispatches"] > 0, scheme
        assert row["parity_instances"] == 64
    assert rep["run_one"]["instances"] == 2 * 64
    assert rep["run_one"]["dispatches"] > 0
    # every padded row count is one of _padded's bucket sizes
    for shapes in rep["shapes"].values():
        assert all(batched._padded(s[0]) == s[0] for s in shapes)


def test_large_fleet_matches_scalar_on_parity_subset():
    rep = chip_smoke.large_fleet(n_devices=300, n_instances=64, n_parity=32)
    assert set(rep["policies"]) == {"ibdash", "tier_escalation"}
    assert rep["dispatches"]["ibdash_scan_kernel"] > 0
    assert rep["dispatches"]["tier_escalation_kernel"] > 0
    assert all(row["parity_instances"] == 32 for row in rep["policies"].values())


def test_served_path_reports_its_dispatches():
    rep = chip_smoke.served(n_devices=100, duration=3.0)
    assert rep["n_arrivals"] > 0
    assert rep["completed"] + rep["shed"] + rep["lost"] == rep["n_arrivals"]
    assert isinstance(rep["dispatches"], dict)


def test_probe_counts_and_restores_the_kernel_table():
    table = batched._jax()
    before = {k: table[k] for k in chip_smoke.KERNELS}
    queue = np.tile(np.arange(5.0) % 3, (8, 1))
    with chip_smoke.Probe() as outer, chip_smoke.Probe() as inner:
        batched.lavea_decide_batch(queue, np.ones((8, 5), bool))
    assert outer.dispatches == inner.dispatches == {"lavea_kernel": 1}
    assert inner.shapes["lavea_kernel"] == {(8, 5)}
    assert {k: table[k] for k in chip_smoke.KERNELS} == before


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err


def test_compile_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    from repro import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.compile_cache_dir() == str(ROOT / ".jax_cache")
