"""Compile the counting kernels for a described TPU v5e (no chip needed).

Interpret mode cannot see what Mosaic refuses (scatters, scalar reads of
VMEM at a dynamic lane, boolean sublane shuffles, tiling, VMEM), so these
tests compile every kernel of the main path at the widths the dispatch
admits, for a ``v5e:2x2`` topology described by the installed TPU
compiler: the carried kernels at M = 1,024 episodes and several event
chunks and at the streaming counters' fixed launch shape, the segmented
kernels at the ``MAX_SEG_BRICK_LW`` corner, the batcher's fused
(vmapped) segmented launch, and the mesh-sharded launch over all four
chips. A compile that passes is not a chip run.

The topology is described inside module fixtures (never at import): only
one process may hold the TPU library, and under several test workers the
file is collected everywhere but runs in one.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.a1_count import a1_count_state_kernel, a1_mapconcat_kernel
from repro.kernels.a2_count import (DEFAULT_BLOCK_E, LANES, SEG_ROWS, SUBLANES,
                                    a2_count_state_kernel, a2_mapconcat_kernel)

M = 1024  # episodes: 8 lane tiles
EP = 4 * DEFAULT_BLOCK_E  # events: 4 chunks on the arbitrary grid axis
LCAP = 4
SEGMENTS = 8


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices), ("data",))


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _shape(sharding, *shape):
    return _i32(shape, sharding)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    print(compiled.memory_analysis())
    return compiled


def _np(n_levels):
    return -(-n_levels // SUBLANES) * SUBLANES


@pytest.mark.parametrize("n_levels", [2, 5])
def test_a1_state_kernel_compiles(one_chip, n_levels):
    npad = _np(n_levels)
    s = functools.partial(_shape, one_chip)
    _compile(functools.partial(a1_count_state_kernel, n_levels=n_levels,
                               lcap=LCAP),
             s(npad, M), s(npad, M), s(npad, M), s(3, EP),
             s(npad, LCAP, M), s(npad, LCAP, M), s(SUBLANES, M),
             s(SUBLANES, M))


@pytest.mark.parametrize("n_levels", [2, 5])
def test_a2_state_kernel_compiles(one_chip, n_levels):
    npad = _np(n_levels)
    s = functools.partial(_shape, one_chip)
    _compile(functools.partial(a2_count_state_kernel, n_levels=n_levels),
             s(npad, M), s(npad, M), s(npad, M), s(2, EP), s(npad, M),
             s(SUBLANES, M))


@pytest.mark.parametrize("n_levels", [2, 3, 4])
def test_stream_block_state_kernels_compile(one_chip, n_levels):
    # the streaming counters' one launch shape per level
    bm, be = ops.stream_block(False)
    s = functools.partial(_shape, one_chip)
    npad = _np(n_levels)
    _compile(functools.partial(a1_count_state_kernel, n_levels=n_levels,
                               lcap=LCAP),
             s(npad, bm), s(npad, bm), s(npad, bm), s(3, be),
             s(npad, LCAP, bm), s(npad, LCAP, bm), s(SUBLANES, bm),
             s(SUBLANES, bm))
    _compile(functools.partial(a2_count_state_kernel, n_levels=n_levels),
             s(npad, bm), s(npad, bm), s(npad, bm), s(2, be), s(npad, bm),
             s(SUBLANES, bm))


@pytest.mark.parametrize("kernel", ["a1", "a2"])
def test_mapconcat_kernel_compiles_at_brick_corner(one_chip, kernel):
    s = functools.partial(_shape, one_chip)
    n_levels = 5
    npad = _np(n_levels)
    fn = (functools.partial(a1_mapconcat_kernel, n_levels=n_levels, lcap=LCAP)
          if kernel == "a1"
          else functools.partial(a2_mapconcat_kernel, n_levels=n_levels))
    _compile(fn, s(npad, M), s(npad, M), s(npad, M), s(npad, M),
             s(SUBLANES, M), s(SEGMENTS, SEG_ROWS, ops.MAX_SEG_BRICK_LW))


def test_fused_mapconcat_kernel_compiles(one_chip):
    s = functools.partial(_shape, one_chip, 2)
    npad = _np(3)
    _compile(ops.a1_mapc_vmapped(3, LCAP, False),
             s(npad, LANES), s(npad, LANES), s(npad, LANES),
             s(npad, LANES), s(SUBLANES, LANES),
             s(SEGMENTS, SEG_ROWS, 2 * DEFAULT_BLOCK_E))


@pytest.mark.parametrize("lanes", [False, True])
def test_sharded_mapconcat_compiles_on_four_chips(mesh4, lanes):
    fn = ops.mapc_sharded_launch(mesh4, "a1", 3, LCAP, False, lanes)
    lead = (2,) if lanes else ()
    rep = NamedSharding(mesh4, P())
    seg = NamedSharding(mesh4, P(None, "data") if lanes else P("data"))
    npad = _np(3)
    shapes = [_i32(lead + (npad, M), rep) for _ in range(4)]
    shapes += [_i32(lead + (SUBLANES, M), rep),
               _i32(lead + (SEGMENTS, SEG_ROWS, 2 * DEFAULT_BLOCK_E), seg)]
    compiled = _compile(fn, *shapes)
    # the per-device launches' tuples are gathered across all four chips
    assert "all-gather" in compiled.as_text()
    assert all(len(sh.device_set) == 4 for sh in compiled.output_shardings)
