"""Pallas TPU megakernel: the fused, zone-mapped scan read path.

One launch evaluates K range predicates over EVERY SCT of an LSM level
(ROADMAP item 2): the per-SCT bit-packed word columns are concatenated
tile-aligned, each tile carries a meta row ``(zone_lo, zone_hi,
range_base)``, and the per-(SCT, predicate) code ranges sit in one
table indexed by ``range_base + k`` — so SCTs with *different
dictionaries* (different planned ranges) share a single grid.  This
replaces the staged host pipeline (read -> unpack -> filter -> bitmap
per SCT) with one fused pass: packed-word field extraction, K-predicate
compare, and bitmap emission never leave the kernel.

Zone-map pruning: a tile is evaluated only if at least one of its K
planned ranges can intersect the tile's packed-code zone ``[zone_lo,
zone_hi]`` (aggregated from the per-4KB-block zone maps in
``core.blocks.BlockIndex``).  The per-tile verdict depends on the meta
row and the range table alone, so the jitted wrapper computes it for
every tile at once in XLA and hands the kernel one scalar-prefetched
word per tile: the tile's range base, or -1 for a pruned tile.  A pruned
tile's bitmap block is zeroed without extracting a single field, and
``tile_hits`` records the verdict so the executor can report pruning
rates.  An empty range is encoded as ``lo > hi`` (no uint32 satisfies
it), and a padding tile as the empty zone ``(0xFFFFFFFF, 0)`` (no
planned range reaches 2**32 - 1, so padding is always skipped).

TPU layout: the per-tile words and the flat ``[R * 2]`` range table are
1-D scalar-prefetch operands in SMEM (a 2-D SMEM table pads its minor
dim to 128 words, and a per-tile ``(1, 4)`` SMEM block breaks the (8,
128) block rule); the words and bitmaps move through VMEM in
``(block_rows, 128)`` tiles.

The default tile (``block_rows=8`` -> 1024 words) is deliberately small:
zone pruning works at tile granularity, and a fine grid keeps the
prunable fraction close to the block-granular verdict.  The trade is
pruning resolution vs. grid overhead, not correctness.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_ROWS = 8
LANES = 128
META_COLS = 4          # (zone_lo, zone_hi, range_base, reserved)
EMPTY_ZONE = (0xFFFFFFFF, 0)   # zone no non-degenerate range intersects


def tile_ranges(meta: jax.Array, ranges: jax.Array, n_preds: int):
    """Per-tile view of the range table plus the zone verdict, in XLA.

    ``meta`` uint32 [T, >=3] (zone_lo, zone_hi, range_base, ...);
    ``ranges`` uint32 [R, 2].  Returns ``(lo, hi, inter)``, each
    [T, n_preds]: tile t's planned range k is ``ranges[base_t + k]`` and
    ``inter`` says whether it can intersect the tile's zone (an empty
    range, ``lo > hi``, never does)."""
    base = meta[:, 2].astype(jnp.int32)
    rows = base[:, None] + jnp.arange(n_preds, dtype=jnp.int32)[None, :]
    lo, hi = ranges[rows, 0], ranges[rows, 1]
    z_lo, z_hi = meta[:, 0:1], meta[:, 1:2]
    inter = (lo <= hi) & (lo <= z_hi) & (hi >= z_lo)
    return lo, hi, inter


def _make_kernel(width: int, n_preds: int):
    per = 32 // width

    def kernel(tile_base_ref, ranges_ref, w_ref, bitmap_ref):
        base = tile_base_ref[pl.program_id(0)]   # -1: zone-pruned tile

        @pl.when(base >= 0)
        def _evaluate():
            fmask = jnp.uint32((1 << width) - 1)
            w = w_ref[...]                               # [rows, 128]
            accs = [jnp.zeros_like(w) for _ in range(n_preds)]
            for f in range(per):  # static unroll: per in {1,2,4,8,16,32}
                v = (w >> jnp.uint32(f * width)) & fmask  # extracted ONCE
                for k in range(n_preds):                  # reused K times
                    lo = ranges_ref[2 * (base + k)]
                    hi = ranges_ref[2 * (base + k) + 1]
                    p = jnp.logical_and(v >= lo, v <= hi)
                    accs[k] = accs[k] | (p.astype(jnp.uint32)
                                         << jnp.uint32(f))
            for k in range(n_preds):
                bitmap_ref[k] = accs[k]

        @pl.when(base < 0)
        def _skip():
            # whole tile pruned: fields never extracted
            bitmap_ref[...] = jnp.zeros_like(bitmap_ref)

    return kernel


@functools.partial(jax.jit, static_argnames=("width", "n_preds",
                                             "block_rows", "interpret"))
def fused_zone_filter_2d(
    words: jax.Array,       # uint32 [rows, 128], rows == n_tiles*block_rows
    meta: jax.Array,        # uint32 [n_tiles, 4]: zone_lo, zone_hi, base, 0
    ranges: jax.Array,      # uint32 [R, 2] inclusive [lo, hi]; lo > hi empty
    width: int = 8,
    n_preds: int = 1,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = True,
):
    """Returns ``(bitmaps u32 [n_preds, rows, 128], hits i32 [n_tiles, 1])``."""
    rows = words.shape[0]
    n_tiles = meta.shape[0]
    assert words.shape[1] == LANES and rows == n_tiles * block_rows, \
        (words.shape, meta.shape, block_rows)
    assert meta.shape[1] == META_COLS and ranges.shape[1] == 2
    meta = jnp.asarray(meta, jnp.uint32)
    ranges = jnp.asarray(ranges, jnp.uint32)
    _lo, _hi, inter = tile_ranges(meta, ranges, n_preds)
    hit = inter.any(axis=1)
    tile_base = jnp.where(hit, meta[:, 2].astype(jnp.int32), -1)
    bitmaps = pl.pallas_call(
        _make_kernel(width, n_preds),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((block_rows, LANES),
                                   lambda i, tb, rg: (i, 0))],
            out_specs=pl.BlockSpec((n_preds, block_rows, LANES),
                                   lambda i, tb, rg: (0, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_preds, rows, LANES), jnp.uint32),
        interpret=interpret,
        name="fused_zone_filter",
    )(tile_base, ranges.reshape(-1), words)
    return bitmaps, hit.astype(jnp.int32).reshape(-1, 1)
