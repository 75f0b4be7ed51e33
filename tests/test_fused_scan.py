"""Zone-mapped fused scan megakernel (ROADMAP item 2).

Three layers of parity plus the pruning/launch-count contracts:

* kernel vs pure-jnp oracle (``ref.fused_zone_filter``) — bitmaps AND
  per-tile hit flags, including skipped and padding tiles;
* ``ops.fused_level_filter`` vs the staged ``multi_range_filter_packed``
  per SCT — zone pruning must be bit-invisible;
* engine: ``filter_backend='fused'`` vs 'numpy' across every codec and
  shard count, with ONE kernel launch per level and >= 50 % of blocks
  skipped for selective predicates over clustered (key-correlated)
  values.

Also here: the block-boundary duplicate-key fixes
(``BlockIndex.locate_block_range`` / ``probe_range`` + snapshot ``get``)
and the empty-result value dtype contract, which both live on the same
read path the megakernel serves.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LSMConfig, LSMTree, Predicate
from repro.core.blocks import BlockIndex
from repro.core.sct import bitpack as np_bitpack
from repro.kernels import fused_scan, ops, ref
from repro.shard import ShardedLSM

RNG = np.random.default_rng(13)
VW = 24


def _pack(codes: np.ndarray, width: int) -> np.ndarray:
    return np_bitpack(codes.astype(np.int32), width)


def _zones(codes: np.ndarray, epb: int):
    edges = np.arange(0, codes.shape[0], epb)
    return (np.minimum.reduceat(codes, edges).astype(np.uint32),
            np.maximum.reduceat(codes, edges).astype(np.uint32), epb)


def _ranges(k: int, width: int, rng) -> np.ndarray:
    maxv = 2 ** min(width, 16)
    out = []
    for i in range(k):
        if i % 4 == 3:
            out.append((1, 0))  # empty
        else:
            a, b = sorted(rng.integers(0, maxv, 2).tolist())
            out.append((a, b))
    return np.asarray(out, np.uint32)


# --------------------------------------------------------------------------- #
# kernel vs oracle
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("width", [1, 2, 4, 8, 16, 32])
def test_fused_kernel_matches_oracle(width):
    """Bitmaps + hit flags identical for hit, skipped and padding tiles."""
    rng = np.random.default_rng(width)
    block_rows = fused_scan.DEFAULT_BLOCK_ROWS
    tile_words = block_rows * fused_scan.LANES
    n_tiles, n_preds = 4, 3
    words = rng.integers(0, 2 ** 32, n_tiles * tile_words,
                         dtype=np.uint64).astype(np.uint32)
    ranges = _ranges(2 * n_preds, width, rng)  # two range_base groups
    meta = np.zeros((n_tiles, fused_scan.META_COLS), np.uint32)
    for t in range(n_tiles):
        if t == 2:  # force one always-skipped (padding-style) tile
            meta[t, 0], meta[t, 1] = fused_scan.EMPTY_ZONE
        else:
            lo, hi = sorted(rng.integers(0, 2 ** min(width, 16), 2).tolist())
            meta[t, 0], meta[t, 1] = lo, hi
        meta[t, 2] = (t % 2) * n_preds
    got_b, got_h = fused_scan.fused_zone_filter_2d(
        jnp.asarray(words.reshape(-1, fused_scan.LANES)), jnp.asarray(meta),
        jnp.asarray(ranges), width=width, n_preds=n_preds,
        block_rows=block_rows, interpret=True)
    exp_b, exp_h = ref.fused_zone_filter(
        jnp.asarray(words.reshape(-1, fused_scan.LANES)), jnp.asarray(meta),
        jnp.asarray(ranges), width, n_preds, block_rows)
    assert np.array_equal(np.asarray(got_b), np.asarray(exp_b))
    assert np.array_equal(np.asarray(got_h), np.asarray(exp_h))
    assert int(np.asarray(got_h)[2, 0]) == 0  # the empty-zone tile skipped


def test_fused_kernel_matches_oracle_many_tiles():
    """Kernel == oracle over 2,304 tiles: above the 1,024-tile wall that a
    whole-array SMEM meta table hit (per-tile range bases now travel as
    a 1-D scalar-prefetch table)."""
    rng = np.random.default_rng(2304)
    width, n_tiles, n_preds = 32, 2304, 3
    block_rows = fused_scan.DEFAULT_BLOCK_ROWS
    words = rng.integers(0, 5000, (n_tiles * block_rows, fused_scan.LANES)
                         ).astype(np.uint32)
    meta = np.zeros((n_tiles, fused_scan.META_COLS), np.uint32)
    meta[:, 0] = rng.integers(0, 2500, n_tiles)
    meta[:, 1] = meta[:, 0] + rng.integers(0, 2500, n_tiles)
    meta[:, 2] = (np.arange(n_tiles) % 2) * n_preds
    meta[7] = (*fused_scan.EMPTY_ZONE, 0, 0)
    ranges = np.asarray([(10, 400), (1, 0), (3000, 4000),
                         (100, 200), (4500, 4600), (1, 0)], np.uint32)
    args = (jnp.asarray(words), jnp.asarray(meta), jnp.asarray(ranges))
    got_b, got_h = fused_scan.fused_zone_filter_2d(
        *args, width=width, n_preds=n_preds, block_rows=block_rows,
        interpret=True)
    exp_b, exp_h = ref.fused_zone_filter(*args, width, n_preds, block_rows)
    assert 0 < int(np.asarray(got_h).sum()) < n_tiles
    assert np.array_equal(np.asarray(got_b), np.asarray(exp_b))
    assert np.array_equal(np.asarray(got_h), np.asarray(exp_h))


# --------------------------------------------------------------------------- #
# ops.fused_level_filter vs the staged multi_filter path
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("width", [2, 4, 8, 16])
@pytest.mark.parametrize("n_scts", [1, 3])
def test_fused_level_filter_matches_staged(width, n_scts):
    """One launch over S SCTs == S independent multi_filter launches."""
    rng = np.random.default_rng(width * 10 + n_scts)
    packed_list, n_list, ranges_list, zones_list = [], [], [], []
    for s in range(n_scts):
        n = int(rng.integers(50, 6000))
        codes = rng.integers(0, 2 ** min(width, 12), n).astype(np.uint32)
        packed_list.append(_pack(codes, width))
        n_list.append(n)
        ranges_list.append(_ranges(4, width, rng))
        # SCT 1 (when present) has no zone map: must never be pruned
        zones_list.append(None if s == 1 else _zones(codes, 64))
    bitmaps, info = ops.fused_level_filter(
        packed_list, n_list, ranges_list, zones_list, width)
    assert info["tiles_total"] >= n_scts
    for s in range(n_scts):
        want = ops.multi_range_filter_packed(
            packed_list[s], width, ranges_list[s])
        n = n_list[s]
        for k in range(4):
            got_m = ops.bitmap_to_mask(bitmaps[s][k], width, n)
            want_m = ops.bitmap_to_mask(want[k], width, n)
            assert np.array_equal(got_m, want_m), (width, s, k)


def test_fused_level_filter_prunes_clustered():
    """Clustered codes + selective ranges: tiles and blocks are skipped,
    and pruning is bit-invisible in the surviving masks."""
    width, per = 8, 4
    n = 60000
    codes = np.sort(RNG.integers(0, 250, n)).astype(np.uint32)
    ranges = np.asarray([(5, 7), (240, 244), (1, 0)], np.uint32)
    bitmaps, info = ops.fused_level_filter(
        [_pack(codes, width)], [n], [ranges], [_zones(codes, 128)], width)
    assert info["tiles_skipped"] > 0
    assert info["blocks_skipped"] > 0
    assert info["blocks_skipped"] <= info["blocks_prunable"] \
        <= info["blocks_total"]
    for k in range(3):
        lo, hi = int(ranges[k, 0]), int(ranges[k, 1])
        want = (codes >= lo) & (codes <= hi) if lo <= hi \
            else np.zeros(n, np.bool_)
        assert np.array_equal(
            ops.bitmap_to_mask(bitmaps[0][k], width, n), want), k


# --------------------------------------------------------------------------- #
# engine: 'fused' backend parity — every codec, shard counts {1, 4}
# --------------------------------------------------------------------------- #
PREDS = [
    Predicate("prefix", b"tag_0"),
    Predicate("eq", b"tag_00037"),
    Predicate("range", b"tag_00020", b"tag_00090"),
    Predicate("ge", b"tag_00150"),
    Predicate("le", b"", b"tag_00012"),
    Predicate("prefix", b"zzz"),
]


def _cfg(codec, backend, **kw):
    base = dict(codec=codec, value_width=VW, file_bytes=16 * 1024,
                l0_limit=2, size_ratio=3)
    base.update(kw)
    return LSMConfig(filter_backend=backend, **base)


def _load(tree, n=2500, seed=5):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        tree.put(int(rng.integers(0, 2000)),
                 b"tag_%05d" % int(rng.integers(0, 200)))
    for k in rng.integers(0, 2000, n // 10).tolist():
        tree.delete(int(k))


@pytest.mark.parametrize("codec", ["opd", "plain", "heavy", "blob"])
def test_fused_backend_engine_parity(codec):
    ta = LSMTree(_cfg(codec, "numpy"))
    tb = LSMTree(_cfg(codec, "fused"))
    _load(ta)
    _load(tb)
    many_a = ta.filter_many(PREDS)
    many_b = tb.filter_many(PREDS)
    for p, ra, rb in zip(PREDS, many_a, many_b):
        assert np.array_equal(ra.keys, rb.keys), (codec, p)
        assert np.array_equal(ra.values, rb.values), (codec, p)
        assert ra.n_matched_raw == rb.n_matched_raw
    if codec == "opd":
        assert tb.filter_stats.counts["fused_launches"] > 0


@pytest.mark.parametrize("n_shards", [1, 4])
def test_fused_backend_sharded_parity(n_shards):
    with ShardedLSM(_cfg("opd", "numpy"), n_shards=n_shards,
                    key_max=2000) as sa, \
         ShardedLSM(_cfg("opd", "fused"), n_shards=n_shards,
                    key_max=2000) as sb:
        _load(sa)
        _load(sb)
        for p, ra, rb in zip(PREDS, sa.filter_many(PREDS),
                             sb.filter_many(PREDS)):
            assert np.array_equal(ra.keys, rb.keys), (n_shards, p)
            assert np.array_equal(ra.values, rb.values), (n_shards, p)
            assert ra.values.dtype == np.dtype(f"S{VW}")


def test_fused_one_launch_per_level():
    """Launch count == number of levels holding live opd runs, not the
    number of runs (the whole point of the level-batched dispatch)."""
    t = LSMTree(_cfg("opd", "fused"))
    _load(t)
    snap = t.snapshot()
    levels_with_runs = {s.level for s in snap.runs if s.n > 0}
    n_runs = sum(1 for s in snap.runs if s.n > 0)
    assert n_runs > len(levels_with_runs), "need a multi-run level"
    t.filter_stats.counts.clear()
    t.filter_many(PREDS, snapshot=snap)
    assert t.filter_stats.counts["fused_launches"] == len(levels_with_runs)
    # an unmatchable batch launches NOTHING
    t.filter_stats.counts.clear()
    t.filter_many([Predicate("prefix", b"zzz")], snapshot=snap)
    assert t.filter_stats.counts["fused_launches"] == 0


def test_fused_zone_pruning_rate_selective():
    """Key-correlated (clustered) values + a < 1 % selectivity predicate:
    zone maps skip >= 50 % of blocks, with results identical to numpy."""
    cfg = _cfg("opd", "fused", file_bytes=256 * 1024)
    t = LSMTree(cfg)
    tn = LSMTree(_cfg("opd", "numpy", file_bytes=256 * 1024))
    for k in range(20000):  # value follows key -> natural clustering
        v = b"ts_%08d" % (k // 4)
        t.put(k, v)
        tn.put(k, v)
    t.flush()
    tn.flush()
    pred = Predicate("range", b"ts_00000100", b"ts_00000120")  # ~0.4 %
    r = t.filter(pred)
    rn = tn.filter(pred)
    assert np.array_equal(r.keys, rn.keys)
    assert np.array_equal(r.values, rn.values)
    c = t.filter_stats.counts
    assert c["zone_blocks_total"] > 0
    assert c["zone_blocks_skipped"] >= 0.5 * c["zone_blocks_total"], dict(c)


# --------------------------------------------------------------------------- #
# block-boundary duplicate keys (locate_block_range / probe_range / get)
# --------------------------------------------------------------------------- #
def test_locate_block_range_boundary_duplicates():
    """A key whose duplicate versions span block boundaries is reported
    in EVERY candidate block, and the bloom verdict ORs across them."""
    # 3 blocks of 4: key 7's versions occupy blocks 0, 1 and 2
    keys = np.asarray([1, 5, 7, 7, 7, 7, 7, 7, 7, 7, 9, 12], np.uint64)
    bi = BlockIndex.build(keys, entries_per_block=4)
    b_lo, b_hi = bi.locate_block_range(np.uint64(7))
    assert (b_lo, b_hi) == (0, 2)
    assert b_hi > b_lo  # the span is visible, not collapsed to one block
    assert bi.locate_block(np.uint64(7)) == b_lo  # legacy API = first
    _, _, maybe = bi.probe_range(np.uint64(7))
    assert maybe
    assert bi.locate_block_range(np.uint64(8)) == (2, 2)   # in block 2's range
    assert bi.locate_block_range(np.uint64(6)) == (0, 0)   # only block 0
    assert bi.locate_block_range(np.uint64(0)) == (-1, -1)
    assert bi.locate_block_range(np.uint64(99)) == (-1, -1)


def test_snapshot_get_across_block_boundary():
    """An old snapshot's version of a heavily-updated key lives past a
    block boundary; the walk finds it and charges each crossed block."""
    t = LSMTree(LSMConfig(codec="opd", value_width=VW))
    t.put(5, b"v_first")
    old_seq = t.snapshot().seqno
    for i in range(200):  # versions of key 5 span > 1 block (epb ~ 146)
        t.put(5, b"v_%03d" % i)
    t.flush()
    s = t.levels[0][0]
    b_lo, b_hi = s.blocks.locate_block_range(np.uint64(5))
    assert b_hi > b_lo, "fixture must span a block boundary"
    # a snapshot pinned at the FIRST write, resolved against the flushed
    # runs: the oldest version sits past the block boundary (versions are
    # stored newest-first within the key)
    snap_old = dataclasses.replace(t.snapshot(), seqno=old_seq)
    reads0 = t.store.stats.read_ios
    assert t.get(5, snapshot=snap_old) == b"v_first"
    assert t.get(5) == b"v_199"
    # the snapshot walk crossed into the next block: that block's fetch
    # is charged too (2 for the walk + 1 for the plain get)
    assert t.store.stats.read_ios - reads0 >= 3


# --------------------------------------------------------------------------- #
# empty-result value dtype (scatter-gather contract)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", ["numpy", "fused"])
def test_empty_filter_result_dtype(backend):
    """Empty results carry the tree's configured width — including the
    no-live-runs and no-memtable corners that used to fall back to 8."""
    t = LSMTree(_cfg("opd", backend))
    r = t.filter(Predicate("prefix", b"zzz"))  # empty tree, no runs
    assert r.values.dtype == np.dtype(f"S{VW}")
    t.put(1, b"tag_00001")
    t.flush()
    r = t.filter(Predicate("prefix", b"zzz"))  # runs, zero matches
    assert r.values.dtype == np.dtype(f"S{VW}")
    assert r.keys.shape == (0,)


def test_sharded_gather_dtype_consistent():
    """Every per-shard result (matching or empty) concatenates under the
    configured dtype; the _gather assert enforces it."""
    with ShardedLSM(_cfg("opd", "fused"), n_shards=4, key_max=2000) as sh:
        rng = np.random.default_rng(3)
        for k in range(0, 500):  # only low shards get data
            sh.put(k, b"tag_%05d" % int(rng.integers(0, 50)))
        sh.flush()
        r = sh.filter(Predicate("prefix", b"tag_0"))
        assert r.values.dtype == np.dtype(f"S{VW}")
        assert r.keys.shape[0] > 0
        r = sh.filter(Predicate("prefix", b"zzz"))  # empty on EVERY shard
        assert r.values.dtype == np.dtype(f"S{VW}")
        assert r.keys.shape == (0,)
