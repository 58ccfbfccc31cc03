"""Ahead-of-time compiles of the served path's Pallas kernels for a TPU v5e.

Interpret mode, which the rest of the suite runs the kernels in, executes
a kernel body without the TPU's lowering rules, so it cannot see a block
shape the chip refuses, a gather Mosaic does not lower, or a block that
overflows a core's VMEM.  These tests hand every kernel the shapes it
meets on a 1M-row table (bitmaps of n/32 words) at the widths of the
paper's datasets, and compile it with `interpret=False` for a described
v5e chip.  Nothing runs, so no chip is needed: the TPU compiler comes
with the installed libtpu.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.distance import distance_matrix_pallas
from repro.kernels.frontier_scan import (frontier_scan_excl_pallas,
                                         frontier_scan_excl_sq8_pallas,
                                         frontier_scan_pallas,
                                         frontier_scan_sq8_pallas)
from repro.kernels.leaf_scan import leaf_scan_batched_pallas, leaf_scan_pallas

N_ROWS = 1_000_000
WORDS = N_ROWS // 32
Q = 64              # in-flight queries of one batch
CHUNK = 64          # frontier candidates scored per query and superstep
LEAVES = 32         # leaves one ScaNN query opens
UNION = 256         # leaves in a batch's union scan
LEAF_ROWS = 1000    # rows per leaf (about n / sqrt(n))
CENTROIDS = 1000
DIMS = (128, 200, 768, 1536)   # sift, text2image, cohere, openai

f32, i32, u32, i8 = jnp.float32, jnp.int32, jnp.uint32, jnp.int8


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with the persistent
    compilation cache off: a compile for a described chip is written to
    the cache but cannot be read back without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _cases(d):
    """kernel name -> (fn, argument (shape, dtype) list) at width d."""
    frontier = [((Q, d), f32), ((Q, CHUNK, d), f32), ((Q, CHUNK), f32),
                ((Q, CHUNK), i32), ((Q, WORDS), u32)]
    frontier_sq8 = [((Q, d), f32), ((Q, CHUNK, d), i8), ((d,), f32),
                    ((d,), f32), ((Q, CHUNK), f32), ((Q, CHUNK), i32),
                    ((Q, WORDS), u32)]
    excl = [((Q, CHUNK), f32), ((Q, 1), f32)]
    return {
        "frontier_scan": (frontier_scan_pallas, frontier),
        "frontier_scan_sq8": (frontier_scan_sq8_pallas, frontier_sq8),
        "frontier_scan_excl": (frontier_scan_excl_pallas, frontier + excl),
        "frontier_scan_excl_sq8": (frontier_scan_excl_sq8_pallas,
                                   frontier_sq8 + excl),
        "leaf_scan": (leaf_scan_pallas,
                      [((d,), f32), ((LEAVES, LEAF_ROWS, d), i8),
                       ((LEAVES, LEAF_ROWS), i32), ((d,), f32), ((d,), f32),
                       ((WORDS,), u32)]),
        "leaf_scan_batched": (leaf_scan_batched_pallas,
                              [((Q, d), f32), ((UNION, LEAF_ROWS, d), i8),
                               ((UNION, LEAF_ROWS), i32), ((d,), f32),
                               ((d,), f32), ((Q, WORDS), u32),
                               ((UNION, LEAF_ROWS), f32)]),
        "distance_matrix": (distance_matrix_pallas,
                            [((Q, d), f32), ((CENTROIDS, d), f32)]),
    }


KERNELS = tuple(_cases(128))


@pytest.mark.parametrize("d", DIMS)
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel, d):
    fn, specs = _cases(d)[kernel]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in specs]
    compiled = jax.jit(
        lambda *a: fn(*a, metric="l2", interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
