"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

jax ships the TPU compiler, which compiles for a described ``v5e:2x2``
topology with no chip attached: nothing runs, but the compiler refuses
what the chip would refuse (VMEM overflow, unaligned blocks). The topology
is described inside a module fixture, never at import time: only one
process may load the TPU library at once, and every test worker imports
this file. The persistent compilation cache is off around these tests (a
TPU executable written here could not be read back without a chip).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bocd_step import (
    MAX_SLOT_STREAMS,
    bocd_step,
    padded_slot_streams,
)
from repro.kernels.cell_reduce import cell_reduce


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this jax install
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _bocd_args(k, b, sharding):
    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    return (
        sds((b,)), sds((k, b)), sds((k, b)), sds((k, b)), sds((k, 1)),
        sds((k, 1)), sds((k, 1), jnp.int32), sds((b,)), sds(()),
    )


def _compile_bocd(k, b, sharding):
    return jax.jit(
        lambda *a: bocd_step(*a, interpret=False)
    ).lower(*_bocd_args(k, b, sharding)).compile()


@pytest.mark.parametrize(
    "k,b",
    # K=64 is DEFAULT_SLOTS; K=66 (padded to 72) is the widest frontier
    # adaptive retunes reach in the mixed_fleet campaign, here at the most
    # streams the bound lets it hold.
    [(32, 8), (32, 8192), (64, 4096), (66, 3584)],
)
def test_bocd_step_compiles_for_v5e(one_chip, k, b):
    compiled = _compile_bocd(k, b, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert padded_slot_streams(k, b) <= MAX_SLOT_STREAMS


def test_bocd_step_vmem_bound_is_measured(one_chip):
    """At K=32, 12,288 streams (above the bound PallasBOCD enforces)
    overflow VMEM."""
    b = 12288
    assert padded_slot_streams(32, b) > MAX_SLOT_STREAMS
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        _compile_bocd(32, b, one_chip)


@pytest.mark.parametrize("pp,dp,tp", [(2, 2, 2), (2, 4, 2), (8, 160, 8)])
def test_cell_reduce_compiles_for_v5e(one_chip, pp, dp, tp):
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(
        lambda *a: cell_reduce(*a, interpret=False)
    ).lower(
        sds((pp, dp)), sds((pp, dp, tp)), sds((pp, dp, tp)),
        sds((pp - 1, dp)), sds((dp,)), *(sds(()),) * 5,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
