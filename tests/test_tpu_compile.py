"""Compile-only checks of the four placement kernels for a TPU v5e.

Each test lowers one jitted kernel of ``repro.core.batched`` under x64 at the
row and fleet widths the chip smoke run (``chip_smoke.py``) dispatches, and
compiles it with the TPU compiler for a described ``v5e:2x2`` topology: no
chip is attached and nothing runs.  The compiler refuses what the chip
would refuse (an unsupported f64/s64 lowering, a program over the device's
memory), so these tests guard the device path at no chip time.
"""
from __future__ import annotations

import os

import numpy as np
import pytest

from repro.core import batched

V5E_HBM_BYTES = 16 * 10**9

# (rows, devices) per kernel: rows are `_padded` wave sizes, devices the
# 10k-device fleet of smoke phase A and the 100k-device fleet of phase B
IBDASH_SHAPES = [(2048, 5)]
WIDE_SHAPES = [(2048, 10_000), (1024, 100_000)]
TIER_SHAPES = [(2048, 10_000), (2048, 100_000)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(name, sharding, *args):
    """Lower and compile ``batched._jax()[name]`` under x64.  ``args`` are
    (shape, dtype) pairs for array arguments and plain Python values for
    scalars; returns the compiled executable."""
    import jax

    kernel = batched._jax()[name]
    with jax.enable_x64(True):
        specs = [
            jax.ShapeDtypeStruct(a[0], a[1], sharding=sharding)
            if isinstance(a, tuple) else a
            for a in args
        ]
        return kernel.lower(*specs).compile()


def _assert_fits(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, mem


@pytest.mark.parametrize("rows,k", IBDASH_SHAPES)
def test_ibdash_scan_kernel_compiles_for_v5e(one_chip, rows, k):
    compiled = _compile(
        "ibdash_scan_kernel", one_chip,
        ((rows, k), np.float64), ((rows, k), np.float64), ((rows,), np.int64),
        0.5, 0.1, 3,
    )
    _assert_fits(compiled)


@pytest.mark.parametrize("rows,d", WIDE_SHAPES)
def test_lavea_kernel_compiles_for_v5e(one_chip, rows, d):
    compiled = _compile(
        "lavea_kernel", one_chip,
        ((rows, d), np.float64), ((rows, d), np.bool_),
    )
    _assert_fits(compiled)


@pytest.mark.parametrize("rows,d", WIDE_SHAPES)
def test_round_robin_kernel_compiles_for_v5e(one_chip, rows, d):
    compiled = _compile(
        "round_robin_kernel", one_chip,
        ((rows, d), np.bool_), ((rows,), np.int64),
    )
    _assert_fits(compiled)


@pytest.mark.parametrize("rows,d", TIER_SHAPES)
def test_tier_escalation_kernel_compiles_for_v5e(one_chip, rows, d):
    compiled = _compile(
        "tier_escalation_kernel", one_chip,
        ((rows, d), np.float64), ((rows, d), np.bool_), ((d,), np.int64),
        4.0, 3,
    )
    _assert_fits(compiled)
