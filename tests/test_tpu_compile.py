"""Compile the device path's Pallas programs for a TPU v5e chip that is
described, not attached.

The TPU compiler refuses what interpret mode accepts: tiles that break
the (8, 128) layout rules, unsigned reductions, kernels that need more
scoped VMEM than a core has.  These tests compile the two kernels at the
8x8 and 16x16 fabric sizes (|V_C| 2144 for C4K8@8x8, 10464 for the
16x16 loop kernel, K = 1024 device trajectories) and the device SBTS
chunk program at 8x8 size, and check that each kernel lowers to a
Mosaic custom call — so no interpret-mode fallback hides on that path.
Nothing runs: results are covered by the interpret-mode oracles in
test_kernels.py and test_mis_device.py.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

K = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to an enabled
    # persistent cache but cannot be read back without the chip.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_pad", [2176, 10496])
def test_selection_counts_compiles(one_chip, n_pad):
    from repro.kernels.sbts_step.kernel import selection_counts_pallas
    w = n_pad // 32
    compiled = selection_counts_pallas.lower(
        _shape(one_chip, (n_pad, w), jnp.uint32),
        _shape(one_chip, (K, w), jnp.uint32)).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("n", [2144, 10464])
def test_conflict_matrix_packed_compiles(one_chip, n):
    from repro.kernels.conflict_matrix.kernel import \
        conflict_matrix_packed_pallas
    compiled = conflict_matrix_packed_pallas.lower(
        _shape(one_chip, (n, 8), jnp.int32)).compile()
    _assert_mosaic(compiled)


def test_device_sbts_chunk_compiles_at_8x8(one_chip):
    """The jitted chunk `DeviceSBTS` runs for C4K8@8x8 (n_pad 2176)."""
    from repro.core.mis_device import _build_chunk
    n_pad = 2176
    chunk = _build_chunk(n_pad, K, 7, 1024, 8, False)
    state = (_shape(one_chip, (K, n_pad), jnp.bool_),
             _shape(one_chip, (K, n_pad), jnp.int32),
             _shape(one_chip, (K,), jnp.int32),
             _shape(one_chip, (K,), jnp.int32),
             _shape(one_chip, (K, n_pad), jnp.bool_),
             _shape(one_chip, (K,), jnp.int32))
    scalar = _shape(one_chip, (), jnp.int32)
    compiled = chunk.lower(
        _shape(one_chip, (n_pad, n_pad // 32), jnp.uint32), state,
        _shape(one_chip, (2,), jnp.uint32), scalar, scalar,
        scalar).compile()
    _assert_mosaic(compiled)
