"""Pallas TPU kernels: zone-gated aggregation directly on packed codes.

Two kernels extend ``fused_scan.py``'s tile loop from predicate bitmaps
to *partial aggregates* (ROADMAP item 1 — the analytics tier):

``fused_zone_agg_2d``
  One launch evaluates K (range, aggregate) pairs over the concatenated
  tile-aligned packed columns of a level.  Per tile and per range k it
  emits ``(count, min_code, max_code, sum)`` — matches are never
  materialized; min/max stay in the packed-code domain (the OPD is
  order-preserving, so code order IS value order within a dictionary)
  and SUM gathers an int32 weight per matching code from a per-SCT
  weight table (``numeric(dict[code])``, the "decode" that never touches
  strings).

``zone_histogram_2d``
  Per-code-bucket histogram for GROUP BY: bin edges are per-SCT code
  values (SMEM table), and each bin count is a difference of two rank
  counts ``#(v >= e_b) - #(v >= e_{b+1})`` — no scatter needed.

Zone short-circuiting (the closed-form contribution the paper's zone
maps enable): a tile whose code zone ``[z_lo, z_hi]`` is CONTAINED by a
range contributes ``n_valid`` (its real-entry count) without reading a
single word; for the histogram, a zone crossed by no bin edge drops its
whole tile into one bin.  ``z_lo >= 1`` is required so tombstones
(packed as code 0) cannot hide inside a short-circuited tile.

Exactness of the min/max fold (why superset tile zones are safe): tile
zones aggregate the 4 KB-block zones the tile overlaps, so ``z_lo`` may
undercut the tile's true minimum — but ``z_lo`` is always *attained* by
some entry of an overlapping block of the SAME run, and containment
(``lo <= z_lo <= z_hi <= hi``) makes that entry a match.  Folding
``min`` over per-tile contributions of one run therefore returns a
value that (a) is attained by a matching entry of the run and (b) lower-
bounds every matching entry (the true-min entry's tile contributes at
most its value).  The fold is exact per run; cross-run combination must
happen in value space after one dictionary decode per run.

Division of work on the TPU: every per-tile verdict (skip / evaluate /
short-circuit) and every closed-form contribution depends only on the
tile's meta row and the small range / edge tables, so the jitted
wrappers compute them for all tiles at once in XLA.  The Pallas kernels
run only the data-dependent part — field extraction, compare and
reduction over the tile's words — for tiles marked for evaluation, which
they learn from a scalar-prefetched per-tile word (range base or edge
row, -1 otherwise).  Per-tile results leave the kernel as one small
lane vector per tile (``(1, 4, K)`` / ``(1, 1, B)`` blocks), never as
scalar stores.  Mosaic has no unsigned reductions, so min/max fold over
the order-preserving signed key ``bitcast(v, int32) ^ INT32_MIN``; SUM
reads a per-entry weight column that the wrapper gathers in XLA
(``weights[weight_base + code]``), since Mosaic cannot gather from a
VMEM table.

Layout notes shared with ``fused_scan``: little-endian fields in uint32
words (word j holds codes ``j*per .. j*per+per-1``, ``per = 32//width``),
padding words are 0xFFFFFFFF, a padding tile carries the empty zone
``(0xFFFFFFFF, 0)``.  Padding fields can alias real codes (field value
``2**width - 1``), so evaluated tiles mask entries by their linear index
against the tile's ``n_valid`` meta column.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_scan import tile_ranges

DEFAULT_BLOCK_ROWS = 8
LANES = 128
# (zone_lo, zone_hi, range_base, n_valid, weight_base, tile_weight_sum)
AGG_META_COLS = 6
EMPTY_ZONE = (0xFFFFFFFF, 0)
MIN_SENTINEL = 0xFFFFFFFF   # per-tile min when no entry matched
WSUM_COL = 5                # meta column: exact tile weight total
WSUM_SENTINEL = 0xFFFFFFFF  # unknown/overflowing total: no SUM closed form
MAX_BINS = 64       # histogram kernel cap (static unroll is O(bins * per))

# tile flag values (per-tile provenance for StageStats)
FLAG_SKIPPED = 0        # zone intersects no range: words never read
FLAG_EVALUATED = 1      # fields extracted and compared
FLAG_SHORTCIRCUIT = 2   # closed-form contribution from the zone alone

_INT32_MIN = -2**31
_INT32_MAX = 2**31 - 1


def _entry_index(rows: int):
    """Linear entry-number-per-word grid [rows, 128] (times ``per`` plus
    the field number gives the entry index; 2D iota keeps TPU happy)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    l = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    return r * LANES + l


def _fields(words: jax.Array, width: int):
    fmask = jnp.uint32((1 << width) - 1)
    return [(words >> jnp.uint32(f * width)) & fmask
            for f in range(32 // width)]


def _lane_vector(values, n: int):
    """Assemble ``(1, 1)`` values into one ``(1, n)`` lane vector (TPU
    stores whole vectors, not scalars)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    out = jnp.zeros((1, n), jnp.int32)
    for j, v in enumerate(values):
        out = jnp.where(lane == j, v, out)
    return out


def _sum11(x):
    return jnp.sum(x, axis=(0, 1), keepdims=True)


def _make_agg_kernel(width: int, n_preds: int, with_sum: bool):
    per = 32 // width

    def kernel(tile_base_ref, n_valid_ref, ranges_ref, w_ref, *refs):
        wt_ref, out_ref = refs if with_sum else (None, refs[0])
        i = pl.program_id(0)
        base = tile_base_ref[i]              # -1: not evaluated here

        @pl.when(base >= 0)
        def _evaluate():
            w = w_ref[...]                                # [rows, 128]
            widx = _entry_index(w.shape[0]) * per         # entry of field 0
            n_valid = n_valid_ref[i]
            cnt = [jnp.zeros(w.shape, jnp.int32) for _ in range(n_preds)]
            mn = [jnp.full(w.shape, _INT32_MAX, jnp.int32)
                  for _ in range(n_preds)]
            mx = [jnp.full(w.shape, _INT32_MIN, jnp.int32)
                  for _ in range(n_preds)]
            sm = [jnp.zeros(w.shape, jnp.int32) for _ in range(n_preds)]
            for f, v in enumerate(_fields(w, width)):     # extracted ONCE
                valid = (widx + f) < n_valid              # padding guard
                key = jax.lax.bitcast_convert_type(v, jnp.int32) ^ _INT32_MIN
                for k in range(n_preds):                  # reused K times
                    lo = ranges_ref[2 * (base + k)]
                    hi = ranges_ref[2 * (base + k) + 1]
                    p = valid & (v >= lo) & (v <= hi)
                    cnt[k] = cnt[k] + p.astype(jnp.int32)
                    mn[k] = jnp.minimum(mn[k], jnp.where(p, key, _INT32_MAX))
                    mx[k] = jnp.maximum(mx[k], jnp.where(p, key, _INT32_MIN))
                    if with_sum:
                        sm[k] = sm[k] + jnp.where(p, wt_ref[f], 0)
            # rows (count, min, max, sum); min/max back to raw uint32 bits
            rows = [
                _lane_vector([_sum11(c) for c in cnt], n_preds),
                _lane_vector([jnp.min(m, axis=(0, 1), keepdims=True)
                              ^ _INT32_MIN for m in mn], n_preds),
                _lane_vector([jnp.max(m, axis=(0, 1), keepdims=True)
                              ^ _INT32_MIN for m in mx], n_preds),
                _lane_vector([_sum11(m) for m in sm], n_preds),
            ]
            out_ref[0] = jnp.concatenate(rows, axis=0)

        @pl.when(base < 0)
        def _idle():
            out_ref[...] = jnp.zeros_like(out_ref)

    return kernel


@functools.partial(jax.jit, static_argnames=("width", "n_preds", "with_sum",
                                             "block_rows", "interpret"))
def fused_zone_agg_2d(
    words: jax.Array,     # uint32 [rows, 128], rows == n_tiles*block_rows
    meta: jax.Array,      # uint32 [n_tiles, 6]
    ranges: jax.Array,    # uint32 [R, 2] inclusive [lo, hi]; lo > hi empty
    weights: jax.Array,   # int32 [t_rows, 128] flat per-SCT weight tables
    width: int = 8,
    n_preds: int = 1,
    with_sum: bool = False,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = True,
):
    """Per-tile partial aggregates for K code ranges in one launch.

    Returns ``(counts i32 [n_tiles, K], mins u32, maxs u32, sums i32,
    flags i32 [n_tiles, 1])``.  ``mins == MIN_SENTINEL`` / ``counts == 0``
    mark tiles with no match for that range; ``flags`` records skip /
    evaluate / short-circuit per tile for pruning telemetry.
    """
    rows = words.shape[0]
    n_tiles = meta.shape[0]
    assert words.shape[1] == LANES and rows == n_tiles * block_rows, \
        (words.shape, meta.shape, block_rows)
    assert meta.shape[1] == AGG_META_COLS and ranges.shape[1] == 2
    assert weights.shape[1] == LANES
    meta = jnp.asarray(meta, jnp.uint32)
    ranges = jnp.asarray(ranges, jnp.uint32)
    z_lo, z_hi = meta[:, 0:1], meta[:, 1:2]
    n_valid = meta[:, 3].astype(jnp.int32)
    wsum = meta[:, WSUM_COL:WSUM_COL + 1]

    # per-tile verdicts: the closed form needs z_lo >= 1 (tombstones
    # pack as 0 and would be counted) and every intersecting range to
    # CONTAIN the zone; SUM's closed form is the tile's exact weight
    # total (meta col WSUM_COL), absent when it holds the sentinel.
    lo, hi, inter = tile_ranges(meta, ranges, n_preds)
    contained = inter & (lo <= z_lo) & (z_hi <= hi)
    any_hit = inter.any(axis=1)
    all_closed = (z_lo[:, 0] >= 1) & (contained | ~inter).all(axis=1)
    if with_sum:
        all_closed &= wsum[:, 0] != jnp.uint32(WSUM_SENTINEL)
    shortcut = any_hit & all_closed
    evaluate = any_hit & ~shortcut
    tile_base = jnp.where(evaluate, meta[:, 2].astype(jnp.int32), -1)

    operands = [tile_base, n_valid, ranges.reshape(-1), words]
    in_specs = [pl.BlockSpec((block_rows, LANES), lambda i, *_: (i, 0))]
    if with_sum:
        # dictionary gather per entry: weight of its code (planned ranges
        # never exceed the dictionary, so matching entries index inside
        # their SCT's slice; the rest are clipped and masked in-kernel)
        w_base = jnp.repeat(meta[:, 4].astype(jnp.int32), block_rows)
        idx = jnp.stack([w_base[:, None] + v.astype(jnp.int32)
                         for v in _fields(words, width)])
        operands.append(jnp.take(jnp.asarray(weights, jnp.int32).reshape(-1),
                                 idx, mode="clip"))
        in_specs.append(pl.BlockSpec((32 // width, block_rows, LANES),
                                     lambda i, *_: (0, i, 0)))
    part = pl.pallas_call(
        _make_agg_kernel(width, n_preds, with_sum),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 4, n_preds), lambda i, *_: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 4, n_preds), jnp.int32),
        interpret=interpret,
        name="fused_zone_agg",
    )(*operands)

    # every real entry of a short-circuited tile matches each
    # intersecting range; z_lo / z_hi are attained within this run (see
    # module docstring), so they are valid min/max partials — and the
    # tile weight total IS the SUM contribution.
    sc = shortcut[:, None]
    ev = evaluate[:, None]
    as_u32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)
    closed_sum = wsum.astype(jnp.int32) if with_sum else 0
    counts = jnp.where(ev, part[:, 0], jnp.where(sc & inter, n_valid[:, None], 0))
    mins = jnp.where(ev, as_u32(part[:, 1]),
                     jnp.where(sc & inter, z_lo, jnp.uint32(MIN_SENTINEL)))
    maxs = jnp.where(ev, as_u32(part[:, 2]),
                     jnp.where(sc & inter, z_hi, jnp.uint32(0)))
    sums = jnp.where(ev, part[:, 3], jnp.where(sc & inter, closed_sum, 0))
    flags = jnp.where(shortcut, FLAG_SHORTCIRCUIT, any_hit.astype(jnp.int32))
    return counts, mins, maxs, sums, flags.reshape(-1, 1)


def _make_hist_kernel(width: int, n_bins: int):
    per = 32 // width
    n_edges = n_bins + 1

    def kernel(tile_seg_ref, n_valid_ref, edges_ref, w_ref, hist_ref):
        i = pl.program_id(0)
        seg = tile_seg_ref[i]               # -1: closed form / empty tile

        @pl.when(seg >= 0)
        def _evaluate():
            # rank counting: ge[e] = #(valid entries >= edges[e]);
            # hist[b] = ge[b] - ge[b+1] (no scatter required)
            w = w_ref[...]
            widx = _entry_index(w.shape[0]) * per
            n_valid = n_valid_ref[i]
            ge = [jnp.zeros(w.shape, jnp.int32) for _ in range(n_edges)]
            for f, v in enumerate(_fields(w, width)):
                valid = (widx + f) < n_valid
                for e in range(n_edges):
                    edge = edges_ref[seg * n_edges + e]
                    ge[e] = ge[e] + (valid & (v >= edge)).astype(jnp.int32)
            ge = [_sum11(g) for g in ge]
            hist_ref[0] = _lane_vector(
                [ge[b] - ge[b + 1] for b in range(n_bins)], n_bins)

        @pl.when(seg < 0)
        def _idle():
            hist_ref[...] = jnp.zeros_like(hist_ref)

    return kernel


@functools.partial(jax.jit, static_argnames=("width", "n_bins",
                                             "block_rows", "interpret"))
def zone_histogram_2d(
    words: jax.Array,   # uint32 [rows, 128], rows == n_tiles*block_rows
    meta: jax.Array,    # uint32 [n_tiles, 6]: (z_lo, z_hi, seg, n_valid, 0, 0)
    edges: jax.Array,   # uint32 [S, n_bins+1] per-SCT bin edges, ascending
    width: int = 8,
    n_bins: int = 8,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = True,
):
    """Per-tile code histogram: bin b counts codes in [e_b, e_{b+1}).

    Returns ``(hist i32 [n_tiles, n_bins], flags i32 [n_tiles, 1])``.
    Each tile reads its own SCT's edge row (``seg`` meta column) so SCTs
    with different dictionaries share the launch; trailing duplicated
    edges make short rows safe (their bins are empty by construction).
    """
    rows = words.shape[0]
    n_tiles = meta.shape[0]
    assert words.shape[1] == LANES and rows == n_tiles * block_rows, \
        (words.shape, meta.shape, block_rows)
    assert meta.shape[1] == AGG_META_COLS
    assert edges.shape[1] == n_bins + 1 and n_bins <= MAX_BINS, edges.shape
    meta = jnp.asarray(meta, jnp.uint32)
    edges = jnp.asarray(edges, jnp.uint32)
    z_lo, z_hi = meta[:, 0:1], meta[:, 1:2]
    seg = meta[:, 2].astype(jnp.int32)
    n_valid = meta[:, 3].astype(jnp.int32)

    # how many edges sit at or below each zone bound: equal counts mean
    # no edge crosses the zone, so every real entry falls in the SAME
    # bin (tombstone-free guaranteed by z_lo >= 1)
    e = edges[seg]                                        # [T, B+1]
    n_le_lo = (e <= z_lo).sum(axis=1)
    n_le_hi = (e <= z_hi).sum(axis=1)
    # zone entirely outside [e_0, e_B): nothing to count
    outside = (z_hi[:, 0] < e[:, 0]) | (z_lo[:, 0] >= e[:, n_bins])
    empty = outside | (n_valid == 0)
    closed = empty | ((n_le_lo == n_le_hi) & (z_lo[:, 0] >= 1))
    tile_seg = jnp.where(closed, -1, seg)

    part = pl.pallas_call(
        _make_hist_kernel(width, n_bins),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((block_rows, LANES),
                                   lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec((1, 1, n_bins), lambda i, *_: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, n_bins), jnp.int32),
        interpret=interpret,
        name="zone_histogram",
    )(tile_seg, n_valid, edges.reshape(-1), words)

    # closed form: all n_valid entries land in the bin holding z_lo
    bins = jnp.arange(n_bins, dtype=jnp.int32)[None, :]
    take = ~empty[:, None] & (bins == (n_le_lo - 1)[:, None])
    hist = jnp.where(closed[:, None], jnp.where(take, n_valid[:, None], 0),
                     part[:, 0])
    flags = jnp.where(closed, jnp.where(empty, FLAG_SKIPPED,
                                        FLAG_SHORTCIRCUIT), FLAG_EVALUATED)
    return hist, flags.astype(jnp.int32).reshape(-1, 1)
