"""Compile rehearsal: the served path's kernels for a described TPU v5e.

Interpret mode cannot see what Mosaic refuses (block shapes off the
(8, 128) tiling, scalar stores to VMEM, unsigned reductions, in-kernel
gathers, SMEM overflow), so each main-path kernel is lowered and
compiled here with ``interpret=False`` for a v5e that is described, not
attached.  Shapes are the largest level launch of ``chip_smoke.py`` at
its default N: ``SMOKE_TILES`` 1024-word tiles at the 32-bit pack width
its dictionaries need, and the 16-bit width next to it.

The topology is described inside a module fixture (never at import),
so pytest-xdist workers all collect the same tests and only the worker
running this file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import agg_scan, fused_scan, merge_remap

SMOKE_TILES = 4096      # >= the smoke's largest level launch
N_SCTS = 64             # SCTs sharing one level launch (range-table rows)
FILTER_PREDS = 20       # one ScanServer batch: 16 prefix + 4 range
AGG_PREDS = 6
HIST_BINS = 16
REMAP_ROWS = 2048       # one compaction output file at 32-bit codes
REMAP_TABLE = 1 << 19   # pow2-padded flat <src, ev> -> ev' table


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _level(shape, meta_cols):
    rows = SMOKE_TILES * fused_scan.DEFAULT_BLOCK_ROWS
    return (shape((rows, fused_scan.LANES), jnp.uint32),
            shape((SMOKE_TILES, meta_cols), jnp.uint32))


@pytest.mark.parametrize("width", [16, 32])
def test_fused_filter_compiles(shape, width):
    words, meta = _level(shape, fused_scan.META_COLS)
    _compile(fused_scan.fused_zone_filter_2d, words, meta,
             shape((N_SCTS * FILTER_PREDS, 2), jnp.uint32),
             width=width, n_preds=FILTER_PREDS)


@pytest.mark.parametrize("with_sum", [False, True])
def test_agg_compiles(shape, with_sum):
    words, meta = _level(shape, agg_scan.AGG_META_COLS)
    _compile(agg_scan.fused_zone_agg_2d, words, meta,
             shape((N_SCTS * AGG_PREDS, 2), jnp.uint32),
             shape((REMAP_TABLE // agg_scan.LANES, agg_scan.LANES),
                   jnp.int32),
             width=32, n_preds=AGG_PREDS, with_sum=with_sum)


def test_histogram_compiles(shape):
    words, meta = _level(shape, agg_scan.AGG_META_COLS)
    _compile(agg_scan.zone_histogram_2d, words, meta,
             shape((N_SCTS, HIST_BINS + 1), jnp.uint32),
             width=32, n_bins=HIST_BINS)


@pytest.mark.parametrize("width", [8, 16, 32])
def test_remap_pack_compiles(shape, width):
    codes = shape((REMAP_ROWS, 32 // width, fused_scan.LANES), jnp.int32)
    _compile(merge_remap.remap_pack_codes_3d, codes, codes,
             shape((REMAP_TABLE,), jnp.int32), shape((8,), jnp.int32),
             width=width)


@pytest.mark.parametrize("fn,kernel", [
    (fused_scan.fused_zone_filter_2d, "fused_zone_filter"),
    (agg_scan.fused_zone_agg_2d, "fused_zone_agg"),
    (agg_scan.zone_histogram_2d, "zone_histogram")])
def test_served_kernels_carry_their_names(shape, fn, kernel):
    """Each served-path ``pallas_call`` is named, inside the program of
    its jitted wrapper (whose name the rooflines read)."""
    rows, tiles = 2 * fused_scan.DEFAULT_BLOCK_ROWS, 2
    words = shape((rows, fused_scan.LANES), jnp.uint32)
    if fn is fused_scan.fused_zone_filter_2d:
        args = (shape((tiles, fused_scan.META_COLS), jnp.uint32),
                shape((2, 2), jnp.uint32))
    elif fn is agg_scan.fused_zone_agg_2d:
        args = (shape((tiles, agg_scan.AGG_META_COLS), jnp.uint32),
                shape((2, 2), jnp.uint32),
                shape((1, agg_scan.LANES), jnp.int32))
    else:
        args = (shape((tiles, agg_scan.AGG_META_COLS), jnp.uint32),
                shape((1, 9), jnp.uint32))
    text = fn.lower(words, *args, width=32, interpret=False).as_text()
    assert f"module @jit_{fn.__name__} " in text
    assert f'kernel_name = "{kernel}"' in text
