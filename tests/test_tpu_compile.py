"""Compile-only checks of the main-path ``cache_sim`` kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: each kernel
is compiled for one chip of a described ``v5e:2x2`` topology at the control
plane's real sizes (a full-grid window tape of 16 tenants x 16,384
accesses, segment widths of 16,384 and 4,096 accesses, and the sub-tile
segments of the serving path's page-event windows).  Interpret mode never
checks the block-shape and layout rules these compiles enforce.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cache_sim.kernel import (cache_sim_levels_scan,
                                            cache_sim_scan,
                                            cache_sim_segments_scan,
                                            live_count_scan)

TAPE = 16 * 16_384          # one control-plane window of the MSR host


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, n_args: int, n: int, sharding) -> str:
    arg = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)
    return jax.jit(fn).lower(*(arg,) * n_args).compile().as_text()


@pytest.mark.parametrize("name,fn,n_args", [
    ("cache_sim_scan", cache_sim_scan, 3),
    ("cache_sim_levels_scan", cache_sim_levels_scan, 5),
    ("live_count_scan", live_count_scan, 2),
])
def test_full_grid_kernel_compiles_for_v5e(one_chip, name, fn, n_args):
    assert "tpu_custom_call" in _compiled_text(fn, n_args, TAPE, one_chip)


@pytest.mark.parametrize("seg_width,n", [
    (16_384, TAPE),          # the MSR host's tenant-windows
    (4_096, TAPE),           # fleet tenants of 4,096 accesses
    (16, 48),                # page-event windows below one kernel tile
])
def test_segments_kernel_compiles_for_v5e(one_chip, seg_width, n):
    def fn(prev, nxt, occ):
        return cache_sim_segments_scan(prev, nxt, occ, seg_width=seg_width)
    assert "tpu_custom_call" in _compiled_text(fn, 3, n, one_chip)
