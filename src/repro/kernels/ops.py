"""Public jit'd wrappers around the Pallas kernels.

Handles shape padding to tile boundaries, 1D<->2D lane reshaping, and
interpret-mode dispatch.  On a TPU backend every kernel compiles to
Mosaic.  On the ``cpu`` backend (``JAX_PLATFORMS=cpu``, how the tests
and examples run) every kernel runs with ``interpret=True``: the kernel
body executes through the Pallas interpreter, which validates results
but says nothing about speed.  Any other backend is refused at import,
so a run that lost its TPU cannot slip into interpret mode unnoticed.
"""

from __future__ import annotations

import contextlib
import functools
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import agg_scan as _agg
from repro.kernels import bitpack as _bitpack
from repro.kernels import bloom_probe as _bloom
from repro.kernels import fused_scan as _fused
from repro.kernels import merge_remap as _merge_remap
from repro.kernels import multi_filter as _multi_filter
from repro.kernels import opd_filter as _opd_filter
from repro.kernels import packed_filter as _packed_filter
from repro.kernels import ssm_scan as _ssm

_BACKEND = jax.default_backend()
if _BACKEND not in ("tpu", "cpu"):
    raise RuntimeError(
        f"Pallas kernels compile for 'tpu' and interpret on 'cpu'; "
        f"backend {_BACKEND!r} is neither")
INTERPRET = _BACKEND == "cpu"
LANES = 128


def _pad_rows(x: jax.Array, mult: int, fill) -> jax.Array:
    rows = x.shape[0]
    want = ((rows + mult - 1) // mult) * mult
    if want == rows:
        return x
    pad = [(0, want - rows)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=fill)


# --------------------------------------------------------------------------- #
# opd_filter
# --------------------------------------------------------------------------- #
def range_filter_codes(codes, lo: int, hi: int, block_rows: int = 256) -> np.ndarray:
    """bool mask over a 1D int32 code column: lo <= code <= hi (inclusive)."""
    codes = jnp.asarray(codes, jnp.int32)
    n = codes.shape[0]
    flat = _pad_rows(codes.reshape(-1), LANES * block_rows, -1).reshape(-1, LANES)
    mask, _ = _opd_filter.range_filter_codes_2d(
        flat, jnp.int32(lo), jnp.int32(hi),
        block_rows=block_rows, interpret=INTERPRET)
    return np.asarray(mask).reshape(-1)[:n].astype(bool)


def range_filter_count(codes, lo: int, hi: int, block_rows: int = 256) -> int:
    codes = jnp.asarray(codes, jnp.int32)
    flat = _pad_rows(codes.reshape(-1), LANES * block_rows, -1).reshape(-1, LANES)
    _, counts = _opd_filter.range_filter_codes_2d(
        flat, jnp.int32(lo), jnp.int32(hi),
        block_rows=block_rows, interpret=INTERPRET)
    return int(np.asarray(counts).sum())


# --------------------------------------------------------------------------- #
# packed_filter (direct on compressed words)
# --------------------------------------------------------------------------- #
def range_filter_packed(words, width: int, lo: int, hi: int,
                        block_rows: int = 256) -> np.ndarray:
    """uint32 bitmap aligned with `words`; bit k of bitmap[i] = predicate of
    the code packed in field k of words[i]."""
    words = jnp.asarray(words, jnp.uint32)
    m = words.shape[0]
    # pad with all-ones words: field value (2^width - 1) only matches if
    # hi == 2^width - 1; we slice the bitmap back to m words so padding
    # never leaks into results.
    flat = _pad_rows(words.reshape(-1), LANES * block_rows, np.uint32(0xFFFFFFFF))
    flat = flat.reshape(-1, LANES)
    bitmap, _ = _packed_filter.range_filter_packed_2d(
        flat, jnp.uint32(lo), jnp.uint32(hi),
        width=width, block_rows=block_rows, interpret=INTERPRET)
    return np.asarray(bitmap).reshape(-1)[:m]


def multi_range_filter_packed(words, width: int, ranges,
                              block_rows: int = 256) -> np.ndarray:
    """K predicates, one pass: uint32 bitmaps [K, len(words)].

    ``ranges`` is (K, 2) inclusive [lo, hi] code ranges; lo > hi encodes
    the empty range.  Row k is bit-identical to
    ``range_filter_packed(words, width, lo_k, hi_k)`` — the batched
    kernel only amortizes the word read + field extraction over K.
    """
    words = jnp.asarray(words, jnp.uint32)
    ranges = jnp.asarray(np.asarray(ranges, np.uint32).reshape(-1, 2))
    m = words.shape[0]
    flat = _pad_rows(words.reshape(-1), LANES * block_rows, np.uint32(0xFFFFFFFF))
    flat = flat.reshape(-1, LANES)
    bitmaps, _ = _multi_filter.multi_range_filter_packed_2d(
        flat, ranges, width=width, block_rows=block_rows, interpret=INTERPRET)
    return np.asarray(bitmaps).reshape(ranges.shape[0], -1)[:, :m]


# --------------------------------------------------------------------------- #
# level-wide launches (fused_scan, agg_scan): upload, run, read back
# --------------------------------------------------------------------------- #
class _Untimed:
    """The ``stats`` of a caller that passes none: stages untimed,
    counters dropped."""

    def __init__(self) -> None:
        self.counts = defaultdict(int)

    def time(self, stage: str):
        return contextlib.nullcontext()


def _launch(st, kernel, inputs, **static) -> list:
    """Run one level-wide kernel in three timed stages of ``st``: upload
    ``inputs`` (``ops.h2d``), run ``kernel`` until its outputs are ready
    (``ops.device``) and copy them to the host (``ops.d2h``).  Each stage
    waits for the one before, so the spans do not overlap.  Counts the
    bytes each way in ``h2d_bytes`` / ``d2h_bytes``."""
    with st.time("ops.h2d"):
        dev = jax.block_until_ready(jax.device_put(inputs))
    with st.time("ops.device"):
        outs = jax.block_until_ready(
            kernel(*dev, interpret=INTERPRET, **static))
    with st.time("ops.d2h"):
        host = [np.asarray(o) for o in outs]
    st.counts["h2d_bytes"] += sum(x.nbytes for x in inputs)
    st.counts["d2h_bytes"] += sum(x.nbytes for x in host)
    return host


# --------------------------------------------------------------------------- #
# level operands: per-SCT tile meta and the padded word column
# --------------------------------------------------------------------------- #
def tile_meta(packed, n: int, zones, width: int, block_rows: int,
              wtab=None) -> np.ndarray:
    """The query-independent meta rows of one SCT's tiles (``block_rows``
    x 128 packed words each), uint32 ``[n_tiles, AGG_META_COLS]`` in the
    agg kernels' layout:

      col 0, 1  zone: min / max of the 4 KB block zones the tile covers;
                ``EMPTY_ZONE`` on a padding-only tile; ``(0, 0xFFFFFFFF)``
                when ``zones`` is None (forced evaluation; z_lo = 0 also
                blocks the closed form, so tombstones stay safe)
      col 3     n_valid: the real entries inside the tile
      col 5     the tile's EXACT weight total, or ``WSUM_SENTINEL`` (no
                SUM closed form) when ``wtab`` or the block weight sums
                ``zones[3]`` are absent, or the total is outside
                [0, 2**31), the kernel's int32 accumulator
      col 2, 4  range base and weight base: 0 here, set per launch

    ``zones`` is (code_lo, code_hi, entries_per_block[, weight_sums]).
    Tiles and blocks do not align: a ``reduceat`` over each tile's first
    block, folded with the block the tile shares with the next one, gives
    the zones; the weight totals are cumulative block sums plus one
    gather of the edge-block entries before every tile boundary.  The
    edge entries read tombstones as code 0 and charge ``wtab[0]``, which
    the (tombstone-zeroed) block sums do not; that only touches blocks
    whose zone starts at 0, which force ``z_lo = 0`` on every tile
    covering them, so the kernel never uses those tiles' totals.

    The result depends only on the SCT and ``(width, block_rows)``; SCTs
    are immutable, so the engine builds it once per SCT
    (``query.planner.run_tile_meta``).
    """
    per = 32 // width
    tile_entries = block_rows * LANES * per
    words = np.asarray(packed, np.uint32).reshape(-1)
    n_tiles = max(1, -(-words.shape[0] // (block_rows * LANES)))
    # entry bounds: tile t holds entries [bounds[t], bounds[t + 1])
    bounds = np.minimum(int(n), np.arange(n_tiles + 1, dtype=np.int64)
                        * tile_entries)
    n_valid = np.diff(bounds)
    n_live = int(np.count_nonzero(n_valid))  # live tiles are a prefix
    meta = np.zeros((n_tiles, _agg.AGG_META_COLS), np.uint32)
    meta[:, 3] = n_valid
    meta[n_live:, 0], meta[n_live:, 1] = _agg.EMPTY_ZONE
    meta[:, _agg.WSUM_COL] = _agg.WSUM_SENTINEL
    if zones is None:
        meta[:n_live, 1] = 0xFFFFFFFF
        return meta
    code_lo, code_hi, epb = zones[0], zones[1], int(zones[2])
    if n_live:
        b0 = bounds[:n_live] // epb
        b1 = (bounds[1:n_live + 1] - 1) // epb
        last = int(b1[-1]) + 1
        meta[:n_live, 0] = np.minimum(
            np.minimum.reduceat(code_lo[:last], b0), code_lo[b1])
        meta[:n_live, 1] = np.maximum(
            np.maximum.reduceat(code_hi[:last], b0), code_hi[b1])
    ws = zones[3] if len(zones) > 3 else None
    wtab = None if wtab is None else np.asarray(wtab, np.int64).reshape(-1)
    if ws is None or wtab is None or wtab.shape[0] == 0:
        return meta
    # weight total of entries [0, e): whole blocks, then entries
    # [b * epb, e) of the block b = e // epb that e falls in
    blk = bounds // epb
    k = bounds - blk * epb
    off = np.cumsum(k) - k
    idx = np.repeat(blk * epb - off, k) + np.arange(int(k.sum()))
    fields = (words[idx // per] >> (idx % per * width).astype(np.uint32)) \
        & np.uint32((1 << width) - 1)
    cw = np.concatenate([[0], np.cumsum(wtab[fields])])
    cum = np.concatenate([[0], np.cumsum(np.asarray(ws, np.int64))])
    tot = np.diff(cum[blk] + cw[off + k] - cw[off])
    fits = (tot >= 0) & (tot < 2**31)
    meta[fits, _agg.WSUM_COL] = tot[fits]
    return meta


def _level_tiles(packed_list, n_list, zones_list, width: int,
                 block_rows: int, metas_list=None, weights_list=None):
    """The operands every level-wide launch shares.  Each SCT's packed
    words are padded to whole tiles with 0xFFFFFFFF and concatenated
    ([rows, 128]); the SCTs' tile meta (``metas_list``, the callers'
    cached ``tile_meta``, or built here with ``weights_list`` as the
    weight tables) is concatenated into a fresh array whose column 2
    holds each tile's SCT index.

    Returns (words_all, meta_all, seg_words, seg_tiles)."""
    tile_words = block_rows * LANES
    words = [np.asarray(p, np.uint32).reshape(-1) for p in packed_list]
    seg_words = [w.shape[0] for w in words]
    seg_tiles = [max(1, -(-m // tile_words)) for m in seg_words]
    words_all = np.full(sum(seg_tiles) * tile_words, 0xFFFFFFFF, np.uint32)
    off = 0
    for w, n_tiles in zip(words, seg_tiles):
        words_all[off:off + w.shape[0]] = w
        off += n_tiles * tile_words
    if metas_list is None:
        wtabs = weights_list or [None] * len(words)
        metas_list = [tile_meta(w, n, z, width, block_rows, wt)
                      for w, n, z, wt in zip(words, n_list, zones_list, wtabs)]
    meta_all = np.concatenate(metas_list)
    meta_all[:, 2] = np.repeat(np.arange(len(seg_tiles)), seg_tiles)
    return words_all.reshape(-1, LANES), meta_all, seg_words, seg_tiles


# --------------------------------------------------------------------------- #
# fused_scan: one zone-gated launch over every SCT of a level
# --------------------------------------------------------------------------- #
def fused_level_filter(
    packed_list, n_list, ranges_list, zones_list, width: int,
    block_rows: int = _fused.DEFAULT_BLOCK_ROWS, stats=None, metas_list=None,
):
    """ONE kernel launch evaluating K code ranges over S packed columns.

    Per-SCT word columns are padded to tile boundaries (``block_rows`` x
    128 words) with 0xFFFFFFFF and concatenated; each tile carries an
    SMEM meta row ``(zone_lo, zone_hi, range_base)`` where the zone is
    the min/max packed code over the 4 KB blocks the tile covers and
    ``range_base = s_idx * K`` indexes the concatenated [S*K, 2] range
    table — so SCTs with different dictionaries (different planned
    ranges) share the single grid.  The kernel skips whole tiles whose
    zone no range intersects.

      packed_list: per-SCT uint32 packed words (s.packed)
      n_list:      per-SCT entry counts
      ranges_list: per-SCT uint32 [K, 2] inclusive [lo, hi]; lo > hi empty
      zones_list:  per-SCT (code_lo, code_hi, entries_per_block[, ...])
                   or None (no zones -> tiles marked always-hit, never
                   pruned)
      metas_list:  per-SCT ``tile_meta`` for this ``(width, block_rows)``,
                   as the engine caches it; None builds it here

    Returns (bitmaps, info): bitmaps[s] is uint32 [K, n_words_s] aligned
    with packed_list[s] (bit-identical to ``multi_range_filter_packed``
    per SCT); info counts tiles/blocks skipped for StageStats.  The
    caller's ``stats`` (a ``core.stats.StageStats``) gets the launch's
    ``ops.*`` stages and byte counters (``_launch``).
    """
    st = stats if stats is not None else _Untimed()
    per = 32 // width
    tile_words = block_rows * LANES
    tile_entries = tile_words * per
    n_preds = int(np.asarray(ranges_list[0], np.uint32).reshape(-1, 2).shape[0])
    with st.time("ops.prep"):
        words_all, tiles, seg_words, seg_tiles = _level_tiles(
            packed_list, n_list, zones_list, width, block_rows, metas_list)
        # (zone_lo, zone_hi, range_base, reserved)
        meta_all = np.zeros((tiles.shape[0], _fused.META_COLS), np.uint32)
        meta_all[:, :3] = tiles[:, :3]
        meta_all[:, 2] *= n_preds
        t_off = 0
        for zones, m, n_tiles in zip(zones_list, seg_words, seg_tiles):
            if zones is None or m == 0:
                # no zone map: every tile, padding too, is a forced hit
                meta_all[t_off:t_off + n_tiles, :2] = (0, 0xFFFFFFFF)
            t_off += n_tiles
        ranges_all = np.concatenate(
            [np.asarray(r, np.uint32).reshape(-1, 2) for r in ranges_list])
    bitmaps2, hits2 = _launch(
        st, _fused.fused_zone_filter_2d, [words_all, meta_all, ranges_all],
        width=width, n_preds=n_preds, block_rows=block_rows)
    flat = bitmaps2.reshape(n_preds, -1)
    hit = hits2.reshape(-1).astype(bool)

    bitmaps, info = [], {
        "tiles_total": int(hit.shape[0]),
        "tiles_skipped": int((~hit).sum()),
        "blocks_total": 0, "blocks_skipped": 0, "blocks_prunable": 0,
    }
    w_off = t_off = 0
    for s_idx, (m, n_tiles) in enumerate(zip(seg_words, seg_tiles)):
        bitmaps.append(flat[:, w_off:w_off + m])
        zones = zones_list[s_idx]
        if zones is not None:
            code_lo, code_hi, epb = zones[0], zones[1], zones[2]
            nb = int(code_lo.shape[0])
            info["blocks_total"] += nb
            # a block is skipped iff EVERY tile overlapping it was
            skipped_t = ~hit[t_off:t_off + n_tiles]
            b = np.arange(nb, dtype=np.int64)
            t0 = (b * epb) // tile_entries
            t1 = np.minimum(((b + 1) * epb - 1) // tile_entries, n_tiles - 1)
            cs = np.concatenate([[0], np.cumsum(skipped_t)])
            info["blocks_skipped"] += int(
                ((cs[t1 + 1] - cs[t0]) == (t1 - t0 + 1)).sum())
            # block-granular verdict (upper bound on achievable skips)
            rng = np.asarray(ranges_list[s_idx], np.uint32).reshape(-1, 2)
            lo = rng[:, 0].astype(np.uint64)[:, None]
            hi = rng[:, 1].astype(np.uint64)[:, None]
            hit_b = ((lo <= hi) & (lo <= code_hi[None, :].astype(np.uint64))
                     & (hi >= code_lo[None, :].astype(np.uint64)))
            info["blocks_prunable"] += int((~hit_b.any(axis=0)).sum())
        w_off += n_tiles * tile_words
        t_off += n_tiles
    return bitmaps, info


def bitmap_to_mask(bitmap: np.ndarray, width: int, n: int) -> np.ndarray:
    """Expand a packed-filter bitmap to a per-code bool mask of length n."""
    per = 32 // width
    bits = np.arange(per, dtype=np.uint32)
    m = ((bitmap[:, None] >> bits[None, :]) & 1).astype(bool)
    return m.reshape(-1)[:n]


# --------------------------------------------------------------------------- #
# agg_scan: zone-gated aggregation directly on packed codes
# --------------------------------------------------------------------------- #
def _tile_info(flags: np.ndarray) -> dict:
    return {
        "tiles_total": int(flags.shape[0]),
        "tiles_skipped": int((flags == _agg.FLAG_SKIPPED).sum()),
        "tiles_evaluated": int((flags == _agg.FLAG_EVALUATED).sum()),
        "tiles_shortcircuit": int((flags == _agg.FLAG_SHORTCIRCUIT).sum()),
    }


def fused_level_agg(
    packed_list, n_list, ranges_list, zones_list, width: int,
    weights_list=None, block_rows: int = _fused.DEFAULT_BLOCK_ROWS,
    stats=None, metas_list=None,
):
    """ONE launch computing K (count, min, max[, sum]) partials over every
    packed column of a level, folded per SCT on the host.

      packed_list:  per-SCT uint32 packed words (s.packed)
      n_list:       per-SCT entry counts
      ranges_list:  per-SCT uint32 [K, 2] inclusive [lo, hi]; lo > hi empty
      zones_list:   per-SCT (code_lo, code_hi, entries_per_block[,
                    weight_sums]) or None
      weights_list: per-SCT int32 numeric weight per code (enables SUM;
                    ranges must then lie inside each dictionary)
      metas_list:   as in ``fused_level_filter``, its weight totals built
                    from the same weights

    Returns (per_sct, info): per_sct[s] is a dict with int64 arrays
    ``counts``/``sums`` [K] and ``min_code``/``max_code`` [K] (-1 when no
    entry of that SCT matched range k); the min/max fold over tiles is
    exact per SCT (see ``agg_scan`` docstring).  info carries the
    tiles_{total,skipped,evaluated,shortcircuit} telemetry.  ``stats``
    as in ``fused_level_filter``.
    """
    st = stats if stats is not None else _Untimed()
    n_preds = int(np.asarray(ranges_list[0], np.uint32).reshape(-1, 2).shape[0])
    with_sum = weights_list is not None
    with st.time("ops.prep"):
        words_all, meta_all, _seg_words, seg_tiles = _level_tiles(
            packed_list, n_list, zones_list, width, block_rows, metas_list,
            weights_list)
        meta_all[:, 2] *= n_preds
        if with_sum:
            tabs = [np.asarray(w, np.int32).reshape(-1) for w in weights_list]
            sizes = [t.shape[0] for t in tabs]
            meta_all[:, 4] = np.repeat(np.cumsum([0] + sizes[:-1]), seg_tiles)
            flat = np.concatenate(tabs) if tabs else np.zeros(0, np.int32)
            pad = -(-max(1, flat.shape[0]) // LANES) * LANES
            weights = np.zeros(pad, np.int32)
            weights[:flat.shape[0]] = flat
            weights = weights.reshape(-1, LANES)
        else:
            # no weight table: no SUM closed form
            meta_all[:, _agg.WSUM_COL] = _agg.WSUM_SENTINEL
            weights = np.zeros((1, LANES), np.int32)
        ranges_all = np.concatenate(
            [np.asarray(r, np.uint32).reshape(-1, 2) for r in ranges_list])
    cnts, mins, maxs, sums, flags = _launch(
        st, _agg.fused_zone_agg_2d, [words_all, meta_all, ranges_all, weights],
        width=width, n_preds=n_preds, with_sum=with_sum,
        block_rows=block_rows)
    cnts = cnts.astype(np.int64)
    mins = mins.astype(np.int64)
    maxs = maxs.astype(np.int64)
    sums = sums.astype(np.int64)
    flags = flags.reshape(-1)

    per_sct, t_off = [], 0
    for n_tiles in seg_tiles:
        c = cnts[t_off:t_off + n_tiles]
        got = c > 0
        lo = np.where(got, mins[t_off:t_off + n_tiles], np.int64(2**32))
        hi = np.where(got, maxs[t_off:t_off + n_tiles], np.int64(-1))
        per_sct.append({
            "counts": c.sum(axis=0),
            "min_code": np.where(got.any(axis=0), lo.min(axis=0), -1),
            "max_code": np.where(got.any(axis=0), hi.max(axis=0), -1),
            "sums": sums[t_off:t_off + n_tiles].sum(axis=0),
        })
        t_off += n_tiles
    return per_sct, _tile_info(flags)


def level_histogram(
    packed_list, n_list, edges_list, zones_list, width: int,
    block_rows: int = _fused.DEFAULT_BLOCK_ROWS, stats=None, metas_list=None,
):
    """ONE launch computing a per-code-bucket histogram over every packed
    column of a level (the GROUP BY gather).

    ``edges_list[s]`` is an ascending uint32 array of B_s + 1 code-space
    bin edges for SCT s (bin b = [e_b, e_{b+1})).  Rows are padded to the
    level's widest edge table by duplicating the last edge (empty bins),
    so SCTs with different group counts share the launch.

    Returns (hists, info): hists[s] is int64 [B_s]; info carries the tile
    telemetry (a short-circuited tile contributed its whole entry count
    to one bin without reading data).  ``stats`` and ``metas_list`` as
    in ``fused_level_filter``.
    """
    st = stats if stats is not None else _Untimed()
    n_bins = max(len(e) - 1 for e in edges_list)
    assert n_bins <= _agg.MAX_BINS, n_bins
    with st.time("ops.prep"):
        words_all, meta_all, _seg_words, seg_tiles = _level_tiles(
            packed_list, n_list, zones_list, width, block_rows, metas_list)
        meta_all[:, _agg.WSUM_COL] = _agg.WSUM_SENTINEL
        edges = np.zeros((len(edges_list), n_bins + 1), np.uint32)
        for s_idx, e in enumerate(edges_list):
            e = np.asarray(e, np.uint32).reshape(-1)
            edges[s_idx, :e.shape[0]] = e
            edges[s_idx, e.shape[0]:] = e[-1]
    hist2, flags = _launch(
        st, _agg.zone_histogram_2d, [words_all, meta_all, edges],
        width=width, n_bins=n_bins, block_rows=block_rows)
    hist2 = hist2.astype(np.int64)
    flags = flags.reshape(-1)
    hists, t_off = [], 0
    for n_tiles, e in zip(seg_tiles, edges_list):
        hists.append(hist2[t_off:t_off + n_tiles].sum(axis=0)[:len(e) - 1])
        t_off += n_tiles
    return hists, _tile_info(flags)


# --------------------------------------------------------------------------- #
# bitpack
# --------------------------------------------------------------------------- #
def pack_codes(codes, width: int, block_rows: int = 128) -> np.ndarray:
    """int32 codes [n] -> uint32 words [ceil(n / (32/width))].

    Produces the same *linear* word layout as ``core.sct.bitpack`` (word j
    holds codes j*per .. j*per+per-1), so the engine, the numpy reference
    and this kernel are interchangeable.  The kernel itself packs along
    the sublane axis; a host-side permutation maps linear -> tile layout.
    """
    per = 32 // width
    codes = jnp.asarray(codes, jnp.int32)
    n = codes.shape[0]
    group = per * LANES
    flat = _pad_rows(codes, group * block_rows, 0)
    m = flat.shape[0] // group
    # linear code index m*LANES*per + l*per + k -> x3[m, k, l]
    x3 = flat.reshape(m, LANES, per).transpose(0, 2, 1)
    words = _bitpack.pack_codes_3d(x3, width, block_rows=block_rows,
                                   interpret=INTERPRET)
    n_words = (n + per - 1) // per
    return np.asarray(words).reshape(-1)[:n_words]


def unpack_codes(words, width: int, n: int, block_rows: int = 128) -> np.ndarray:
    per = 32 // width
    words = jnp.asarray(words, jnp.uint32)
    flat = _pad_rows(words, LANES * block_rows, 0).reshape(-1, LANES)
    codes3 = _bitpack.unpack_codes_3d(flat, width, block_rows=block_rows,
                                      interpret=INTERPRET)
    # x3[m, k, l] -> linear code index m*LANES*per + l*per + k
    lin = np.asarray(codes3).transpose(0, 2, 1).reshape(-1)
    return lin[:n]


# --------------------------------------------------------------------------- #
# merge_remap (compaction-time code rewrite)
# --------------------------------------------------------------------------- #
def _pad_rows_pow2(x: jax.Array, unit: int, fill) -> jax.Array:
    """Pad 1D x to a power-of-two count of `unit`-sized rows (>= 1 row).

    Compaction calls these kernels once per output chunk, and chunk and
    dictionary sizes vary per merge — padding to power-of-two buckets
    keeps the padded work proportional to the real work (vs a fixed
    full-grid pad) AND bounds the set of traced shapes to O(log n), so
    repeated compactions reuse a handful of compiled kernels instead of
    retracing per distinct (rows, t_rows)."""
    n = x.shape[0]
    rows = max(1, -(-n // unit))
    r = 1
    while r < rows:
        r *= 2
    want = r * unit
    if want == n:
        return x
    return jnp.pad(x, [(0, want - n)], constant_values=fill)


def _remap_operands(table, offsets):
    """Flat remap table zero-padded to a power-of-two multiple of 128
    (>= 1 row, so the dead-entry placeholder gather stays in bounds)
    and the per-source offsets as int32 [n_src]."""
    n_src = len(offsets) - 1
    tbl = _pad_rows_pow2(jnp.asarray(np.asarray(table, np.int32)), LANES, 0)
    offs = jnp.asarray(np.asarray(offsets[:n_src], np.int32))
    return tbl, offs


def remap_codes(evs, srcs, table, offsets) -> np.ndarray:
    """Flattened <src, ev> -> ev' remap (Algorithm 1 line 9) as one
    table gather.  evs int32 [n] (-1 = dead), srcs int32 [n],
    table int32 [sum D_i], offsets [n_src + 1]; returns int32 [n] with
    dead entries preserved as -1."""
    evs = jnp.asarray(evs, jnp.int32)
    n = evs.shape[0]
    if n == 0:
        return np.zeros(0, np.int32)
    tbl, offs = _remap_operands(table, offsets)
    out = _merge_remap.remap_codes(
        _pad_rows_pow2(evs, LANES, -1),
        _pad_rows_pow2(jnp.asarray(srcs, jnp.int32), LANES, 0), tbl, offs)
    return np.asarray(out)[:n]


def remap_pack_codes(evs, srcs, table, offsets, width: int,
                     block_rows: int = 128) -> np.ndarray:
    """Fused remap + k-bit pack ('jax_packed' compaction backend): returns
    uint32 words [ceil(n / (32/width))] in the same linear layout as
    ``core.sct.bitpack`` — word j holds entries j*per .. j*per+per-1, and
    dead entries pack as 0.  Only the packed words leave the device."""
    per = 32 // width
    evs = jnp.asarray(evs, jnp.int32)
    n = evs.shape[0]
    if n == 0:
        return np.zeros(0, np.uint32)
    tbl, offs = _remap_operands(table, offsets)
    group = per * LANES
    ev_flat = _pad_rows_pow2(evs, group, -1)
    src_flat = _pad_rows_pow2(jnp.asarray(srcs, jnp.int32), group, 0)
    m = ev_flat.shape[0] // group
    # linear entry index m*LANES*per + l*per + k -> x3[m, k, l] (bitpack layout)
    ev3 = ev_flat.reshape(m, LANES, per).transpose(0, 2, 1)
    src3 = src_flat.reshape(m, LANES, per).transpose(0, 2, 1)
    words = _merge_remap.remap_pack_codes_3d(ev3, src3, tbl, offs, width=width,
                                             block_rows=min(block_rows, m),
                                             interpret=INTERPRET)
    n_words = (n + per - 1) // per
    return np.asarray(words).reshape(-1)[:n_words]


# --------------------------------------------------------------------------- #
# bloom probe
# --------------------------------------------------------------------------- #
def bloom_probe(bloom_words, nbits: int, keys32, n_hashes: int = 6) -> np.ndarray:
    """hits bool [Q] for uint32 keys against one bloom (uint32 words)."""
    keys32 = jnp.asarray(keys32, jnp.uint32)
    q = keys32.shape[0]
    bw = jnp.asarray(bloom_words, jnp.uint32)
    bw = _pad_rows(bw, LANES, 0).reshape(-1, LANES)
    kq = _pad_rows(keys32, LANES * _bloom.DEFAULT_BLOCK_Q, 0).reshape(-1, LANES)
    hits = _bloom.bloom_probe_2d(bw, kq, nbits, n_hashes,
                                 interpret=INTERPRET)
    return np.asarray(hits).reshape(-1)[:q].astype(bool)


# --------------------------------------------------------------------------- #
# ssm scan
# --------------------------------------------------------------------------- #
def ssm_scan(u, delta, A, B, C, chunk: int = 32):
    """Batched chunked selective scan; see kernels.ssm_scan for layout."""
    return _ssm.ssm_scan_chunked(
        jnp.asarray(u), jnp.asarray(delta), jnp.asarray(A),
        jnp.asarray(B), jnp.asarray(C), chunk=chunk, interpret=INTERPRET)
