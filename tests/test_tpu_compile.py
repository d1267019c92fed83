"""The cluster path's Pallas kernels compile for a TPU v5e.

Interpret mode (tests/test_cluster_step.py, tests/test_arepas.py) checks
what the kernels compute; it cannot see what the TPU compiler refuses
(block tiling, scalar stores to vector memory, primitives Mosaic does not
lower). These tests compile each kernel at the sizes the fused-cluster
benchmark runs, for a described (not attached) v5e chip, and check that
the program really contains the Mosaic kernel.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so a test worker that is
not given this file must never touch it.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.allocator import AllocationPolicy
from repro.kernels.cluster_step import epoch_step_pallas, resize_step_pallas
from repro.kernels.skyline import skyline_runtimes

# bench_fused_cluster's epoch table and resize sizes
K, L, Q = 4, 8192, 4096
C, SMAX = 512, 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_epoch_step_compiles_for_v5e(one_chip):
    # traced under x64 with 64-bit tables, as FusedReplay launches it
    f64, i64 = jnp.float64, jnp.int64
    with jax.enable_x64(True):
        hlo = _compile(epoch_step_pallas, one_chip, ((K, L), f64),
                       ((K, L), i64), ((K,), i64), ((K, Q), i64),
                       ((K, Q), f64), ((), f64))
    assert "tpu_custom_call" in hlo


def test_resize_step_compiles_for_v5e(one_chip):
    policy = AllocationPolicy(max_slowdown=0.05)

    def fn(*args):
        return resize_step_pallas(*args, 8.0, policy=policy, cap=65536)

    vec = ((C,), jnp.float32)
    hlo = _compile(fn, one_chip, *[vec] * 8, ((C, SMAX), jnp.float32),
                   ((C,), jnp.int32), ((), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_skyline_runtimes_compiles_for_v5e(one_chip):
    hlo = _compile(skyline_runtimes, one_chip, ((C, SMAX), jnp.float32),
                   ((C,), jnp.int32), ((C, 4), jnp.float32))
    assert "tpu_custom_call" in hlo
