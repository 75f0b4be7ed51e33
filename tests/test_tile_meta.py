"""Per-SCT tile meta (``kernels.ops.tile_meta``), built once and cached.

* The operands every level launch uploads (filter, COUNT/MIN/MAX, SUM,
  histogram) are bit-identical to those of the per-tile loops the
  vectorized builder replaced, kept below as the reference, whether the
  level function builds the meta itself or the caller hands in cached
  meta.
* The engine builds each SCT's meta once: a second launch over the same
  SCTs counts a hit and no build; the SCTs a flush and a compaction write
  build their own, and every answer still equals the numpy reference.
"""

import numpy as np
import pytest

from repro.core import LSMConfig, LSMTree, Predicate
from repro.core.sct import bitpack
from repro.kernels import agg_scan, fused_scan, ops
from repro.query import AggSpec, GroupBy, numeric_values
from repro.query.spec import prefix_labels

LANES = 128
BLOCK_ROWS = fused_scan.DEFAULT_BLOCK_ROWS


# --------------------------------------------------------------------------- #
# reference: the per-tile loops, as the launches ran them before
# --------------------------------------------------------------------------- #
def _ref_words(packed_list, block_rows):
    tile_words = block_rows * LANES
    chunks, seg_tiles = [], []
    for packed in packed_list:
        words = np.asarray(packed, np.uint32).reshape(-1)
        n_tiles = max(1, -(-words.shape[0] // tile_words))
        pad = np.full(n_tiles * tile_words, 0xFFFFFFFF, np.uint32)
        pad[:words.shape[0]] = words
        chunks.append(pad)
        seg_tiles.append(n_tiles)
    return np.concatenate(chunks).reshape(-1, LANES), seg_tiles


def _ref_filter_meta(packed_list, n_list, zones_list, width, block_rows,
                     n_preds):
    tile_words = block_rows * LANES
    tile_entries = tile_words * (32 // width)
    metas = []
    for s_idx, (packed, n, zones) in enumerate(
            zip(packed_list, n_list, zones_list)):
        m = np.asarray(packed).reshape(-1).shape[0]
        n_tiles = max(1, -(-m // tile_words))
        meta = np.zeros((n_tiles, fused_scan.META_COLS), np.uint32)
        meta[:, 2] = s_idx * n_preds
        if zones is None or m == 0:
            meta[:, 0], meta[:, 1] = 0, 0xFFFFFFFF
        else:
            code_lo, code_hi, epb = zones[0], zones[1], zones[2]
            for t in range(n_tiles):
                e0 = t * tile_entries
                e1 = min(int(n), (t + 1) * tile_entries)
                if e0 >= e1:
                    meta[t, 0], meta[t, 1] = fused_scan.EMPTY_ZONE
                    continue
                b0, b1 = e0 // epb, (e1 - 1) // epb
                meta[t, 0] = code_lo[b0:b1 + 1].min()
                meta[t, 1] = code_hi[b0:b1 + 1].max()
        metas.append(meta)
    return np.concatenate(metas)


def _ref_agg_metas(packed_list, n_list, zones_list, width, block_rows):
    tile_words = block_rows * LANES
    tile_entries = tile_words * (32 // width)
    metas = []
    for packed, n, zones in zip(packed_list, n_list, zones_list):
        m = np.asarray(packed).reshape(-1).shape[0]
        n_tiles = max(1, -(-m // tile_words))
        meta = np.zeros((n_tiles, agg_scan.AGG_META_COLS), np.uint32)
        meta[:, agg_scan.WSUM_COL] = agg_scan.WSUM_SENTINEL
        for t in range(n_tiles):
            e0 = t * tile_entries
            e1 = min(int(n), (t + 1) * tile_entries)
            meta[t, 3] = max(0, e1 - e0)
            if e0 >= e1:
                meta[t, 0], meta[t, 1] = agg_scan.EMPTY_ZONE
            elif zones is None:
                meta[t, 0], meta[t, 1] = 0, 0xFFFFFFFF
            else:
                code_lo, code_hi, epb = zones[0], zones[1], zones[2]
                b0, b1 = e0 // epb, (e1 - 1) // epb
                meta[t, 0] = code_lo[b0:b1 + 1].min()
                meta[t, 1] = code_hi[b0:b1 + 1].max()
        metas.append(meta)
    return metas


def _ref_tile_weight_sums(meta, packed, n, zones, wtab, width, block_rows):
    ws = zones[3] if zones is not None and len(zones) > 3 else None
    wtab = np.asarray(wtab, np.int64).reshape(-1)
    if ws is None or wtab.shape[0] == 0:
        return
    per = 32 // width
    tile_entries = block_rows * LANES * per
    epb = zones[2]
    words = np.asarray(packed, np.uint32).reshape(-1)
    cum = np.concatenate([[0], np.cumsum(np.asarray(ws, np.int64))])
    fmask = np.uint32((1 << width) - 1)

    def prefix(e):
        b = e // epb
        a = b * epb
        part = 0
        if a < e:
            w0 = a // per
            seg = words[w0: (e - 1) // per + 1]
            fields = np.zeros(seg.shape[0] * per, np.int64)
            for f in range(per):
                fields[f::per] = (seg >> np.uint32(f * width)) & fmask
            part = int(wtab[fields[a - w0 * per: e - w0 * per]].sum())
        return int(cum[b]) + part

    pref = [prefix(min(int(n), t * tile_entries))
            for t in range(meta.shape[0] + 1)]
    for t in range(meta.shape[0]):
        v = pref[t + 1] - pref[t]
        if 0 <= v < 2**31:
            meta[t, agg_scan.WSUM_COL] = np.uint32(v)


def _ref_ranges(ranges_list):
    return np.concatenate(
        [np.asarray(r, np.uint32).reshape(-1, 2) for r in ranges_list])


def _ref_filter_operands(lvl, ranges_list):
    words, _ = _ref_words(lvl["packed"], BLOCK_ROWS)
    meta = _ref_filter_meta(lvl["packed"], lvl["n"], lvl["zones"],
                            lvl["width"], BLOCK_ROWS, ranges_list[0].shape[0])
    return [words, meta, _ref_ranges(ranges_list)]


def _ref_agg_operands(lvl, ranges_list, with_sum):
    words, seg_tiles = _ref_words(lvl["packed"], BLOCK_ROWS)
    metas = _ref_agg_metas(lvl["packed"], lvl["n"], lvl["zones"],
                           lvl["width"], BLOCK_ROWS)
    weights = np.zeros((1, LANES), np.int32)
    if with_sum:
        w_off, tabs = 0, []
        for s, meta in enumerate(metas):
            meta[:, 4] = w_off
            wts = np.asarray(lvl["wtabs"][s], np.int32)
            tabs.append(wts)
            w_off += wts.shape[0]
            _ref_tile_weight_sums(meta, lvl["packed"][s], lvl["n"][s],
                                  lvl["zones"][s], wts, lvl["width"],
                                  BLOCK_ROWS)
        flat = np.concatenate(tabs)
        weights = np.zeros(-(-max(1, flat.shape[0]) // LANES) * LANES,
                           np.int32)
        weights[:flat.shape[0]] = flat
        weights = weights.reshape(-1, LANES)
    meta = np.concatenate(metas)
    meta[:, 2] = np.repeat(np.arange(len(seg_tiles)),
                           seg_tiles) * ranges_list[0].shape[0]
    return [words, meta, _ref_ranges(ranges_list), weights]


def _ref_hist_operands(lvl, edges_list):
    words, seg_tiles = _ref_words(lvl["packed"], BLOCK_ROWS)
    meta = np.concatenate(_ref_agg_metas(lvl["packed"], lvl["n"],
                                         lvl["zones"], lvl["width"],
                                         BLOCK_ROWS))
    meta[:, 2] = np.repeat(np.arange(len(seg_tiles)), seg_tiles)
    n_bins = max(len(e) - 1 for e in edges_list)
    edges = np.zeros((len(edges_list), n_bins + 1), np.uint32)
    for s, e in enumerate(edges_list):
        edges[s, :len(e)] = e
        edges[s, len(e):] = e[-1]
    return [words, meta, edges]


# --------------------------------------------------------------------------- #
# levels: one SCT of each level (the middle one) carries the case
# --------------------------------------------------------------------------- #
CASES = ["epb_below", "epb_equal", "epb_above", "whole_tiles",
         "padding_tiles", "no_zones", "no_weight_sums", "total_over_2_31",
         "tombstones"]


def _sct(case, width, rng, special):
    """(packed, n, zones, wtab) of one SCT as ``core.sct`` builds it:
    zones over the packed field values (tombstones as 0), block weight
    sums with tombstones zeroed."""
    tile_entries = BLOCK_ROWS * LANES * (32 // width)
    n_codes = min(1 << width, 5000)
    epb = 146
    n = 2 * tile_entries + int(rng.integers(1, tile_entries))
    if special and case == "epb_equal":
        epb = tile_entries
    if special and case == "epb_above":
        epb = 2 * tile_entries + 37
    if special and case == "whole_tiles":
        n = 3 * tile_entries
    codes = rng.integers(1, n_codes, n)
    codes[: n // 2].sort()                # clustered half: narrow zones
    tombs = np.zeros(n, bool)
    if special and case == "tombstones":
        tombs = rng.random(n) < 0.1
        codes[tombs] = 0
    wtab = rng.integers(0, 1000, n_codes)
    if special and case == "total_over_2_31":
        # tile 0 fits, tile 1 passes 2**31, tile 2 sums below 0
        q = n_codes // 4
        wtab[2 * q:] = 1 << 22
        wtab[q:2 * q] = -(1 << 20)
        te = tile_entries
        codes[:te] = rng.integers(1, q, te)
        codes[te:2 * te] = rng.integers(2 * q, n_codes, te)
        codes[2 * te:] = rng.integers(q, 2 * q, n - 2 * te)
    entry_w = wtab[codes]
    entry_w[tombs] = 0
    n_packed = n
    if special and case == "padding_tiles":
        n = tile_entries // 3             # words reach two tiles further
    edges = np.arange(0, max(n, 1), epb)
    if n:
        field = codes[:n].astype(np.uint32)
        lo = np.minimum.reduceat(field, edges)
        hi = np.maximum.reduceat(field, edges)
        ws = np.add.reduceat(entry_w[:n].astype(np.int64), edges)
    else:
        lo = np.full(1, 0xFFFFFFFF, np.uint32)
        hi = np.zeros(1, np.uint32)
        ws = np.zeros(1, np.int64)
    zones = (lo, hi, epb, ws)
    if special and case == "no_zones":
        zones = None
    if special and case == "no_weight_sums":
        zones = zones[:3]
    packed = bitpack(codes[:n_packed].astype(np.int32), width)
    return packed, n, zones, wtab.astype(np.int32)


def _level(case, width, n_scts, seed):
    rng = np.random.default_rng(seed)
    scts = [_sct(case, width, rng, s == n_scts // 2) for s in range(n_scts)]
    if case == "padding_tiles" and n_scts == 3:
        # an SCT with no entries: one tile, padding only
        scts[2] = (np.zeros(0, np.uint32), 0,
                   (np.full(1, 0xFFFFFFFF, np.uint32), np.zeros(1, np.uint32),
                    146, np.zeros(1, np.int64)), scts[2][3])
    packed, n, zones, wtabs = map(list, zip(*scts))
    return {"packed": packed, "n": n, "zones": zones, "wtabs": wtabs,
            "width": width}


class _Captured(Exception):
    pass


@pytest.fixture
def uploads(monkeypatch):
    """The operands each level launch hands to ``ops._launch``; the
    launch itself is cut short."""
    got = []

    def capture(st, kernel, inputs, **static):
        got.append(inputs)
        raise _Captured

    monkeypatch.setattr(ops, "_launch", capture)

    def run(fn, *args, **kw):
        with pytest.raises(_Captured):
            fn(*args, **kw)
        return got.pop()
    return run


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("n_scts", [1, 3])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("width", [8, 16, 32])
def test_tile_meta_matches_per_tile_loops(uploads, width, case, n_scts):
    """Filter, COUNT/MIN/MAX, SUM and histogram launches upload exactly
    the operands of the per-tile loops, from meta built in the launch
    and from meta the caller built once (``tile_meta`` with the weight
    table, as ``planner.run_tile_meta`` caches it)."""
    lvl = _level(case, width, n_scts, seed=width * 100 + n_scts)
    args = (lvl["packed"], lvl["n"])
    rng = np.random.default_rng(7)
    ranges_list = [np.sort(rng.integers(0, 1 << min(width, 12), (3, 2)),
                           axis=1).astype(np.uint32) for _ in range(n_scts)]
    edges_list = [np.asarray([0, 3, 900, 1 << min(width, 12)], np.uint32)
                  for _ in range(n_scts)]
    cached = [ops.tile_meta(p, n, z, width, BLOCK_ROWS, w)
              for p, n, z, w in zip(lvl["packed"], lvl["n"], lvl["zones"],
                                    lvl["wtabs"])]
    if case == "total_over_2_31":
        wsum = np.concatenate(cached)[:, agg_scan.WSUM_COL]
        assert (wsum == agg_scan.WSUM_SENTINEL).any()
        assert (wsum != agg_scan.WSUM_SENTINEL).any()
    for metas in (None, cached):
        _same(uploads(ops.fused_level_filter, *args, ranges_list,
                      lvl["zones"], width, metas_list=metas),
              _ref_filter_operands(lvl, ranges_list))
        _same(uploads(ops.fused_level_agg, *args, ranges_list, lvl["zones"],
                      width, metas_list=metas),
              _ref_agg_operands(lvl, ranges_list, with_sum=False))
        _same(uploads(ops.fused_level_agg, *args, ranges_list, lvl["zones"],
                      width, weights_list=lvl["wtabs"], metas_list=metas),
              _ref_agg_operands(lvl, ranges_list, with_sum=True))
        _same(uploads(ops.level_histogram, *args, edges_list, lvl["zones"],
                      width, metas_list=metas),
              _ref_hist_operands(lvl, edges_list))


# --------------------------------------------------------------------------- #
# engine: built once per SCT, rebuilt for the SCTs a compaction writes
# --------------------------------------------------------------------------- #
VW = 24
PRED = Predicate("prefix", b"cat_000")
SPECS = [AggSpec("count", pred=PRED), AggSpec("sum"),
         AggSpec("sum", pred=PRED), AggSpec("min"), AggSpec("max", pred=PRED),
         AggSpec("group_count", group=GroupBy("prefix", prefix_len=7))]


def _rows(rng, lo, n):
    keys = rng.permutation(np.arange(lo, lo + n).astype(np.uint64))
    vals = np.array([b"cat_%05d_%s" % (int(c), bytes(t))
                     for c, t in zip(rng.integers(0, 300, n),
                                     rng.integers(97, 123, (n, 12)).astype(
                                         np.uint8))], f"S{VW}")
    return keys, vals


def _want(live: dict, spec: AggSpec):
    """Decode-then-aggregate over the live rows, in numpy."""
    v = np.array(sorted(live.values()), f"S{VW}")
    if spec.pred is not None:
        v = v[np.char.startswith(v, spec.pred.a)]
    if spec.op == "count":
        return len(v)
    if spec.op == "sum":
        return int(numeric_values(v).sum())
    if spec.op in ("min", "max"):
        return bytes(v[0] if spec.op == "min" else v[-1]) if len(v) else None
    labs, cnts = np.unique(prefix_labels(v, spec.group.prefix_len),
                           return_counts=True)
    return sorted(((bytes(a), int(c)) for a, c in zip(labs, cnts)),
                  key=lambda kv: (-kv[1], kv[0]))


def _check(tree, live):
    got = tree.filter(PRED)
    want = np.array(sorted(v for v in live.values()
                           if v.startswith(PRED.a)), f"S{VW}")
    np.testing.assert_array_equal(np.sort(got.values), want)
    for spec, res in zip(SPECS, tree.aggregate_many(SPECS)):
        assert res.value == _want(live, spec), (spec.op, spec.pred)


def test_engine_builds_tile_meta_once_per_sct():
    rng = np.random.default_rng(3)
    cfg = LSMConfig(codec="opd", value_width=VW, filter_backend="fused",
                    file_bytes=64 * 1024)
    with LSMTree(cfg) as tree:
        keys, vals = _rows(rng, 1, 6000)
        tree.put_batch(keys, vals)
        tree.flush()
        tree.compact()
        live = dict(zip(keys.tolist(), (bytes(v) for v in vals)))
        fc, ac = tree.filter_stats.counts, tree.agg_stats.counts
        n_runs = sum(len(lvl) for lvl in tree.levels)
        assert n_runs > 1

        tree.filter(PRED)
        assert (fc["tile_meta_builds"], fc["tile_meta_hits"]) == (n_runs, 0)
        tree.filter(PRED)  # the same SCTs: hits only
        assert (fc["tile_meta_builds"], fc["tile_meta_hits"]) == (n_runs,
                                                                  n_runs)
        tree.aggregate_many(SPECS)  # scalar launch + histogram launch
        assert ac["tile_meta_builds"] == 0
        assert ac["tile_meta_hits"] == 2 * n_runs
        _check(tree, live)

        old = {id(s) for lvl in tree.levels for s in lvl}
        more_keys, more_vals = _rows(rng, 3001, 6000)  # half overwrites
        tree.put_batch(more_keys, more_vals)
        live.update(zip(more_keys.tolist(), (bytes(v) for v in more_vals)))
        for k in more_keys[:200].tolist():
            tree.delete(int(k))
            live.pop(k)
        tree.flush()
        tree.compact()
        runs = [s for lvl in tree.levels for s in lvl]
        new = sum(id(s) not in old for s in runs)
        assert new > 0
        builds, hits = fc["tile_meta_builds"], fc["tile_meta_hits"]
        tree.filter(PRED)
        assert fc["tile_meta_builds"] - builds == new
        assert fc["tile_meta_hits"] - hits == len(runs) - new
        _check(tree, live)
