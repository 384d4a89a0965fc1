"""Compile the served path's Pallas kernels for a TPU v5e chip.

Interpret mode (every other kernel test) accepts programs the chip's
Mosaic compiler refuses: in-kernel gathers, blocks that break the
(8, 128) tiling rule, tiles that overflow VMEM.  These tests compile each
main-path kernel at deployment widths for a *described* v5e chip — the
TPU compiler is installed even where no chip is attached — so such a
regression fails here instead of on the chip.  Nothing runs; the tests
check only that Mosaic accepts the kernel and that the compiled program
holds it as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import, so under
pytest-xdist only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ann_match import cell_rescore_pallas, centroid_topc_pallas
from repro.kernels.gallery_match import (gallery_match_pallas,
                                         gallery_match_quant_pallas)

N_DEPLOY = 1 << 20          # 10^6-class watchlist rows
N_TENANT = 333_334          # one fleet tenant's scoped view of 10^6 rows


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype,N,D,Q,k", [
    ("float32", N_DEPLOY, 128, 8, 1),
    ("float32", N_DEPLOY, 512, 1024, 1),
    ("bfloat16", N_DEPLOY, 128, 8, 5),
    ("bfloat16", N_DEPLOY, 512, 1024, 1),
    ("int8", N_DEPLOY, 512, 8, 5),
    ("int8", N_DEPLOY, 128, 1024, 1),
    ("int8", N_DEPLOY, 512, 1024, 5),     # the largest int8 VMEM footprint
    ("int8", N_TENANT, 128, 13, 1),       # a served micro-batch's shape
])
def test_gallery_match_compiles(one_chip, dtype, N, D, Q, k):
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    if dtype == "int8":
        c = _compile(lambda q, g, s: gallery_match_quant_pallas(
            q, g, s, k=k, bq=256, fuse_norm=True),
            S((Q, D), jnp.float32), S((N, D), jnp.int8),
            S((N,), jnp.float32))
    else:
        dt = jnp.dtype(dtype)
        c = _compile(lambda q, g: gallery_match_pallas(
            q, g, k=k, bq=256, fuse_norm=True), S((Q, D), dt), S((N, D), dt))
    _assert_kernel(c)


def test_centroid_topc_int8_compiles(one_chip):
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    c = _compile(lambda q, cq, cs: centroid_topc_pallas(q, cq, cs, c=8,
                                                        bq=256),
                 S((8, 128), jnp.float32), S((1024, 128), jnp.int8),
                 S((1024,), jnp.float32))
    _assert_kernel(c)


# L: cell pad width = the largest cell; 1000 is a multiple of 8 but not
# of 128, as ``build_cell_layout`` produces it
@pytest.mark.parametrize("dtype,L", [("float32", 2048), ("int8", 1000)])
def test_cell_rescore_compiles(one_chip, dtype, L):
    K, D, Q, c, k = 1024, 128, 8, 8, 5

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    ids, lens = S((Q, c), jnp.int32), S((K,), jnp.int32)
    if dtype == "int8":
        comp = _compile(lambda q, cells, sc, i, n: cell_rescore_pallas(
            q, cells, i, n, sc, k=k, L=L), S((Q, D), jnp.float32),
            S((K * L, D), jnp.int8), S((K * L,), jnp.float32), ids, lens)
    else:
        comp = _compile(lambda q, cells, i, n: cell_rescore_pallas(
            q, cells, i, n, k=k, L=L), S((Q, D), jnp.float32),
            S((K * L, D), jnp.float32), ids, lens)
    _assert_kernel(comp)
