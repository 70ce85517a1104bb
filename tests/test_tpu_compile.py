"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret-mode tests run the kernel bodies on the CPU but cannot see
what Mosaic refuses: a block shape off the (8, 128) tiling, or more
scoped VMEM than the kernel may use. These tests compile each kernel at
the geometry the production code picks for it, for a v5e that is
described, not attached, so a refused geometry fails here instead of on
the chip. Nothing runs; about two seconds per compile.

The topology is described only inside the module fixture: only one
process may load the TPU library, and the xdist workers all import this
file.
"""

import functools

import pytest

import jax
import jax.numpy as jnp

from racon_tpu.ops import budget, ovl_align
from racon_tpu.ops.device_poa import round_band_width
from racon_tpu.ops.pallas.band_kernel import (TB, fw_dirs_band,
                                              fw_dirs_band_tile)

SCORES = dict(match=5, mismatch=-4, gap=-8)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_named(fn, args, kernel):
    """Compile ``fn``; its lowered module must call the Pallas kernel by
    the pinned name ``kernel``, which profiler traces show and the
    benchmark's ``kernel.band_*`` readers match."""
    lowered = jax.jit(fn).lower(*args)
    assert f'kernel_name = "{kernel}"' in lowered.as_text()
    return lowered.compile()


def _compile_band(sharding, B, Lq, W, nxt_k, **tiles):
    fn = functools.partial(fw_dirs_band, W=W, nxt_k=nxt_k, **tiles,
                           **SCORES)
    args = (_shape(sharding, (B, W + Lq), jnp.uint8),
            _shape(sharding, (Lq, B), jnp.uint8),
            _shape(sharding, (B,), jnp.int32),
            _shape(sharding, (B,), jnp.int32))
    return _compile_named(fn, args, "fw_dirs_band")


def _compile_tile(sharding, plan):
    B, T, W = plan.lanes, plan.T, plan.W
    fn = functools.partial(fw_dirs_band_tile, W=W, tb=B, ch=plan.ch,
                           nxt_k=plan.nxt_k, match=0, mismatch=-1, gap=-1)
    row = _shape(sharding, (B,), jnp.int32)
    front = _shape(sharding, (B, W), jnp.int32)
    args = (_shape(sharding, (B, W + T), jnp.uint8),
            _shape(sharding, (T, B), jnp.uint8),
            row, row, row, front, front, front)
    return _compile_named(fn, args, "fw_dirs_band_tile")


@pytest.mark.parametrize("nxt_k", [2, 4])
def test_consensus_band_kernel_compiles(one_chip, nxt_k):
    # w=500 windows at 30x: run_caps pads layers to Lq=640; round 0
    # bands at W=256 and later rounds at round_band_width's 192.
    W = 256
    assert round_band_width(W, 1) == 192
    _compile_band(one_chip, 2048, 640, W, nxt_k)


@pytest.mark.parametrize("read_len,nxt_k", [(8000, 2), (4000, 4)])
def test_overlap_bucket_compiles(one_chip, read_len, nxt_k):
    # The geometry device_breaking_points builds for reads of this
    # length; ch=4 at (Lq=8192, W=1536) was refused before the picker
    # floored ch at 8.
    Lq = ovl_align._round_up(read_len, 2048)
    W = ovl_align._round_up(
        ovl_align.band_width_for_read(read_len, read_len + 100), 512)
    assert TB * Lq * W <= ovl_align.MAX_DIR_ELEMS
    assert budget.walk_k_for(TB * Lq * W) >= nxt_k
    tb, ch = ovl_align._pick_tiles(W, Lq, nxt_k)
    assert ch >= budget.MIN_CH
    _compile_band(one_chip, TB, Lq, W, nxt_k, tb=tb, ch=ch)


def test_tiled_tier_kernel_compiles(one_chip):
    plan = budget.tile_plan(12_000, 12_300)
    assert plan is not None and plan.ch >= budget.MIN_CH
    _compile_tile(one_chip, plan)


def _compile_tiled_program(sharding, plan, nxt_k):
    from racon_tpu.ops.ovl_align import _tiled_chunk_breaking_points
    B, Lq = plan.lanes, plan.Lq
    fn = functools.partial(
        _tiled_chunk_breaking_points, match=0, mismatch=-1, gap=-1,
        W=plan.W, w_len=500, NW=Lq // 500 + 2, Lq=Lq, LA=Lq, T=plan.T,
        tb=B, ch=plan.ch, pallas=True, nxt_k=nxt_k)
    row = _shape(sharding, (B,), jnp.int32)
    return jax.jit(fn).lower(
        _shape(sharding, (B, Lq), jnp.uint8),
        _shape(sharding, (B, Lq), jnp.uint8), row, row, row).compile()


@pytest.mark.parametrize("nxt_k", [2, 4])
def test_tiled_program_hbm_verdict(one_chip, nxt_k):
    # The whole stitched program at the largest geometry the tiers
    # admit on a v5e: the 16-lane tier at 20 kb. Its planes pad the
    # lane axis to 128 in HBM. At the admitted k=2 it needs 12.0 GiB;
    # at k=4 the compiler refuses it ("Used 22.00G of 15.75G hbm"), as
    # the chip did in PR 21's first run, so tile_plan must keep k=2.
    plan = budget.tile_plan(20_000, 20_300)
    assert plan is not None and plan.Lq == 20_480 and plan.nxt_k == 2
    if nxt_k == plan.nxt_k:
        compiled = _compile_tiled_program(one_chip, plan, nxt_k)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp <= budget.V5E_HBM_BYTES
    else:
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED.*hbm"):
            _compile_tiled_program(one_chip, plan, nxt_k)
