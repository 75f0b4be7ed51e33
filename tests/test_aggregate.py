"""Analytics pushdown (repro.query): aggregates on packed OPD codes.

Four layers of parity:

* agg kernels vs their numpy oracles (``ref.fused_zone_agg`` /
  ``ref.zone_histogram``) — partials AND per-tile flags, including
  short-circuited and padding tiles;
* engine ``aggregate_many`` vs a decode-then-aggregate numpy oracle
  across every codec x shard count x maintenance mode (value
  identity is the subsystem's contract: computing on codes must be
  invisible);
* MVCC: a snapshot pinned before writes + flush + compaction still
  aggregates to the pre-write answer;
* the fast path actually engages on a compacted OPD tree (telemetry:
  fastpath runs, short-circuited tiles) and the ScanServer batches
  ``AggRequest`` next to filters against one snapshot.

Bucket group-by uses EXPLICIT edges wherever results are compared
across configurations: equi-depth resolution depends on the observed
domain, which legitimately changes when compaction drops shadowed
versions.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LSMConfig, LSMTree, Predicate
from repro.kernels import agg_scan, ops, ref
from repro.query import (AggPartial, AggSpec, GroupBy, finalize_partial,
                         merge_partials, numeric_values)
from repro.query.spec import INT32_MAX, bucket_ids, prefix_labels
from repro.serving.scan_server import ScanServer
from repro.shard import ShardedLSM

VW = 24
KEY_SPACE = 1 << 20


# --------------------------------------------------------------------------- #
# workload + decode-then-aggregate oracle
# --------------------------------------------------------------------------- #
def _workload(n=4000, seed=7, n_cats=30):
    rng = np.random.default_rng(seed)
    keys = rng.permutation(np.arange(1, n + 1).astype(np.uint64))
    cats = np.array([b"cat_%05d_" % (i % n_cats) for i in range(n_cats * 5)])
    tails = rng.integers(97, 123, (n, VW - 10)).astype(np.uint8)
    vals = np.array([cats[rng.integers(0, len(cats))] + t.tobytes()
                     for t in tails], f"S{VW}")
    return keys, vals


PRED = Predicate("prefix", b"cat_000")
EDGES = (b"cat_00008", b"cat_00015", b"cat_00022")  # explicit: comparable


def _specs():
    return [
        AggSpec("count"),
        AggSpec("count", pred=PRED),
        AggSpec("sum"),
        AggSpec("sum", pred=PRED),
        AggSpec("min"),
        AggSpec("max"),
        AggSpec("min", pred=PRED),
        AggSpec("max", pred=PRED),
        AggSpec("group_count", group=GroupBy("prefix", prefix_len=9)),
        AggSpec("group_count", pred=PRED,
                group=GroupBy("prefix", prefix_len=9), top_k=3),
        AggSpec("group_count",
                group=GroupBy("bucket", n_buckets=4, edges=EDGES)),
    ]


def _oracle(values: np.ndarray, spec: AggSpec):
    """Aggregate DECODED values with numpy — the answer the packed path
    must reproduce exactly."""
    v = values
    sv = np.sort(v) if len(v) else v  # S-dtype has no min/max ufunc
    if spec.op == "count":
        return len(v)
    if spec.op == "sum":
        return int(numeric_values(v).sum())
    if spec.op == "min":
        return bytes(sv[0]) if len(v) else None
    if spec.op == "max":
        return bytes(sv[-1]) if len(v) else None
    g = spec.group
    if g.kind == "prefix":
        labs, cnts = np.unique(prefix_labels(v, g.prefix_len),
                               return_counts=True)
        items = [(bytes(a), int(c)) for a, c in zip(labs, cnts)]
    else:
        ids, cnts = np.unique(bucket_ids(v, g.edges), return_counts=True)
        items = [(g.bucket_label(int(b)), int(c))
                 for b, c in zip(ids, cnts)]
    items.sort(key=lambda kv: (-kv[1], kv[0]))
    return items[: spec.top_k] if spec.top_k else items


def _check_engine(tree, specs, snapshot=None, tag=""):
    got = tree.aggregate_many(specs, snapshot=snapshot)
    frs = {}  # one decode per distinct predicate
    for spec, res in zip(specs, got):
        key = (spec.pred.kind, spec.pred.a, spec.pred.b) \
            if spec.pred is not None else None
        if key not in frs:
            frs[key] = tree.filter(spec.pred or Predicate("prefix", b""),
                                   snapshot=snapshot)
        vals = frs[key].values
        assert res.value == _oracle(vals, spec), (tag, spec.op, spec.group)


# --------------------------------------------------------------------------- #
# kernel vs oracle (tile level)
# --------------------------------------------------------------------------- #
def _level_inputs(width, rng, n_scts=2):
    """Realistic per-SCT packed columns + zones via the executor's own
    tile builder (sorted-ish codes so zones actually short-circuit)."""
    packed_list, n_list, zones_list, codes_list = [], [], [], []
    epb = 64
    for s in range(n_scts):
        n = int(rng.integers(300, 1200))
        codes = np.sort(rng.integers(1, 2 ** min(width, 12), n)) \
            if s == 0 else rng.integers(0, 2 ** min(width, 12), n)
        codes = codes.astype(np.int32)
        from repro.core.sct import bitpack
        packed_list.append(bitpack(codes, width))
        n_list.append(n)
        codes_list.append(codes)
        edges = np.arange(0, n, epb)
        u = codes.astype(np.uint32)
        zones_list.append((np.minimum.reduceat(u, edges),
                           np.maximum.reduceat(u, edges), epb))
    return packed_list, n_list, zones_list, codes_list


@pytest.mark.parametrize("width", [2, 4, 8, 16])
@pytest.mark.parametrize("with_sum", [False, True])
def test_agg_kernel_matches_ref(width, with_sum):
    """fused_zone_agg_2d == ref.fused_zone_agg: partials and flags."""
    rng = np.random.default_rng(width + 100 * with_sum)
    packed_list, n_list, zones_list, _ = _level_inputs(width, rng)
    block_rows = agg_scan.DEFAULT_BLOCK_ROWS
    maxv = 2 ** min(width, 12)
    ranges = np.asarray([(1, maxv - 1), (1, 0),
                         (maxv // 4, maxv // 2)], np.uint32)
    n_preds = ranges.shape[0]
    words_all, meta, _w, seg_tiles = ops._level_tiles(
        packed_list, n_list, zones_list, width, block_rows)
    meta[:, 2] *= n_preds
    if with_sum:
        meta[:, 4] = np.repeat(np.arange(len(seg_tiles)) * maxv, seg_tiles)
        tabs = [rng.integers(0, 1000, maxv).astype(np.int32)
                for _ in seg_tiles]
        flat = np.concatenate(tabs)
        pad = -(-flat.shape[0] // agg_scan.LANES) * agg_scan.LANES
        weights = np.zeros(pad, np.int32)
        weights[:flat.shape[0]] = flat
        weights = weights.reshape(-1, agg_scan.LANES)
    else:
        weights = np.zeros((1, agg_scan.LANES), np.int32)
    ranges_all = np.concatenate([ranges] * len(seg_tiles))
    got = agg_scan.fused_zone_agg_2d(
        jnp.asarray(words_all), jnp.asarray(meta), jnp.asarray(ranges_all),
        jnp.asarray(weights), width=width, n_preds=n_preds,
        with_sum=with_sum, block_rows=block_rows, interpret=True)
    want = ref.fused_zone_agg(words_all, meta, ranges_all, weights,
                              width=width, n_preds=n_preds,
                              with_sum=with_sum, block_rows=block_rows)
    for g, w, name in zip(got, want,
                          ("counts", "mins", "maxs", "sums", "flags")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)


@pytest.mark.parametrize("width", [2, 4, 8, 16])
def test_hist_kernel_matches_ref(width):
    """zone_histogram_2d == ref.zone_histogram: bins and flags."""
    rng = np.random.default_rng(width)
    packed_list, n_list, zones_list, _ = _level_inputs(width, rng)
    block_rows = agg_scan.DEFAULT_BLOCK_ROWS
    maxv = 2 ** min(width, 12)
    n_bins = 5
    edges_row = np.sort(rng.choice(maxv, n_bins - 1, replace=False))
    edges_row = np.concatenate([[0], edges_row, [maxv]]).astype(np.uint32)
    words_all, meta, _w, seg_tiles = ops._level_tiles(
        packed_list, n_list, zones_list, width, block_rows)
    edges = np.stack([edges_row] * len(seg_tiles))
    got_h, got_f = agg_scan.zone_histogram_2d(
        jnp.asarray(words_all), jnp.asarray(meta), jnp.asarray(edges),
        width=width, n_bins=n_bins, block_rows=block_rows, interpret=True)
    want_h, want_f = ref.zone_histogram(words_all, meta, edges, width=width,
                                        n_bins=n_bins, block_rows=block_rows)
    np.testing.assert_array_equal(np.asarray(got_h), want_h)
    np.testing.assert_array_equal(np.asarray(got_f), want_f)


MANY_TILES = 2304  # above the 1,024-tile wall of whole-array SMEM meta


@pytest.mark.parametrize("kernel", ["agg", "agg_sum", "hist"])
def test_agg_kernels_match_ref_many_tiles(kernel):
    """Kernel == oracle over a level launch of MANY_TILES tiles (per-tile
    meta now travels as 1-D scalar-prefetch tables)."""
    rng = np.random.default_rng(len(kernel))
    width, block_rows, epb = 16, agg_scan.DEFAULT_BLOCK_ROWS, 512
    tile_entries = block_rows * agg_scan.LANES * (32 // width)
    n = MANY_TILES * tile_entries - 777   # partial last tile
    maxv = 1 << 12
    codes = rng.integers(0, maxv, n)
    codes[: n // 2].sort()                # clustered half: short-circuits
    codes = codes.astype(np.int32)
    from repro.core.sct import bitpack
    edges = np.arange(0, n, epb)
    u = codes.astype(np.uint32)
    zones = (np.minimum.reduceat(u, edges), np.maximum.reduceat(u, edges), epb)
    words_all, meta, _w, _t = ops._level_tiles(
        [bitpack(codes, width)], [n], [zones], width, block_rows)
    assert meta.shape[0] == MANY_TILES
    if kernel == "hist":
        edges_tab = np.asarray([[1, 100, 900, 2000, 4000, maxv]], np.uint32)
        got = agg_scan.zone_histogram_2d(
            jnp.asarray(words_all), jnp.asarray(meta), jnp.asarray(edges_tab),
            width=width, n_bins=5, block_rows=block_rows, interpret=True)
        want = ref.zone_histogram(words_all, meta, edges_tab, width=width,
                                  n_bins=5, block_rows=block_rows)
    else:
        with_sum = kernel == "agg_sum"
        ranges = np.asarray([(1, maxv - 1), (1, 0), (300, 2500)], np.uint32)
        weights = rng.integers(-50, 1000, (maxv // agg_scan.LANES,
                                           agg_scan.LANES)).astype(np.int32)
        got = agg_scan.fused_zone_agg_2d(
            jnp.asarray(words_all), jnp.asarray(meta), jnp.asarray(ranges),
            jnp.asarray(weights), width=width, n_preds=3, with_sum=with_sum,
            block_rows=block_rows, interpret=True)
        want = ref.fused_zone_agg(words_all, meta, ranges, weights,
                                  width=width, n_preds=3, with_sum=with_sum,
                                  block_rows=block_rows)
    flags = np.asarray(got[-1])
    assert (flags == agg_scan.FLAG_EVALUATED).any()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_level_agg_matches_direct_numpy():
    """ops.fused_level_agg partials == direct numpy over the raw codes
    (count / exact min / exact max / sum per range, per SCT)."""
    width = 10
    rng = np.random.default_rng(5)
    packed_list, n_list, zones_list, codes_list = _level_inputs(width, rng)
    maxv = 2 ** width
    ranges = np.asarray([(1, maxv - 1), (7, 300), (1, 0)], np.uint32)
    weights = [rng.integers(0, 500, maxv).astype(np.int32)
               for _ in packed_list]
    per_sct, info = ops.fused_level_agg(
        packed_list, n_list, [ranges] * len(packed_list), zones_list,
        width, weights_list=weights)
    assert info["tiles_total"] > 0
    for s, codes in enumerate(codes_list):
        for k, (lo, hi) in enumerate(ranges):
            m = (codes >= lo) & (codes <= hi)
            assert per_sct[s]["counts"][k] == m.sum()
            assert per_sct[s]["sums"][k] == weights[s][codes[m]].sum()
            want_min = codes[m].min() if m.any() else -1
            want_max = codes[m].max() if m.any() else -1
            assert per_sct[s]["min_code"][k] == want_min
            assert per_sct[s]["max_code"][k] == want_max


def test_level_histogram_matches_direct_numpy():
    width = 10
    rng = np.random.default_rng(6)
    packed_list, n_list, zones_list, codes_list = _level_inputs(width, rng)
    # different bin counts per SCT exercises the pad-to-widest path
    edges_list = [np.asarray([0, 100, 400, 2 ** width], np.uint32),
                  np.asarray([0, 50, 2 ** width], np.uint32)]
    hists, info = ops.level_histogram(packed_list, n_list, edges_list,
                                      zones_list, width)
    for s, codes in enumerate(codes_list):
        e = edges_list[s].astype(np.int64)
        want = np.histogram(codes, bins=e)[0]
        # np.histogram's last bin is closed; ours is half-open
        want[-1] -= (codes == e[-1]).sum()
        np.testing.assert_array_equal(hists[s], want)


# --------------------------------------------------------------------------- #
# engine: aggregate == decode-then-aggregate, every codec/shard/maintenance
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("codec", ["opd", "plain", "heavy", "blob"])
@pytest.mark.parametrize("maintenance", ["sync", "background"])
def test_tree_aggregate_parity(codec, maintenance):
    backend = "fused" if codec == "opd" else "numpy"
    cfg = LSMConfig(codec=codec, value_width=VW, filter_backend=backend,
                    maintenance=maintenance)
    keys, vals = _workload()
    specs = _specs()
    with LSMTree(cfg) as tree:
        for i in range(0, len(keys), 500):
            tree.put_batch(keys[i:i + 500], vals[i:i + 500])
        tree.put_batch(keys[:100], vals[100:200])     # overwrites
        for k in keys[200:220]:
            tree.delete(int(k))                        # tombstones
        _check_engine(tree, specs, tag=f"{codec}/{maintenance}/pre")
        tree.drain()
        tree.compact()
        _check_engine(tree, specs, tag=f"{codec}/{maintenance}/compacted")


@pytest.mark.parametrize("codec", ["opd", "plain"])
@pytest.mark.parametrize("n_shards", [1, 4])
def test_sharded_aggregate_parity(codec, n_shards):
    backend = "fused" if codec == "opd" else "numpy"
    cfg = LSMConfig(codec=codec, value_width=VW, filter_backend=backend)
    keys, vals = _workload()
    specs = _specs()
    with ShardedLSM(cfg, n_shards=n_shards, key_max=KEY_SPACE) as sharded:
        sharded.put_batch(keys, vals)
        sharded.put_batch(keys[:100], vals[100:200])
        for k in keys[200:220]:
            sharded.delete(int(k))
        _check_engine(sharded, specs, tag=f"{codec}/x{n_shards}/pre")
        sharded.flush()
        sharded.compact_all()
        _check_engine(sharded, specs, tag=f"{codec}/x{n_shards}/compacted")


def test_sharded_equals_single_tree():
    """Cross-shard scatter-gather merge == one tree, same data."""
    keys, vals = _workload()
    specs = _specs()
    cfg = LSMConfig(codec="opd", value_width=VW, filter_backend="numpy")
    with LSMTree(cfg) as tree, \
            ShardedLSM(cfg, n_shards=3, key_max=KEY_SPACE) as sharded:
        tree.put_batch(keys, vals)
        sharded.put_batch(keys, vals)
        tree.flush()
        tree.compact()
        sharded.flush()
        sharded.compact_all()
        for a, b, spec in zip(tree.aggregate_many(specs),
                              sharded.aggregate_many(specs), specs):
            assert a.value == b.value, spec


def test_equidepth_bucket_resolution_is_snapshot_consistent():
    """Unresolved bucket specs resolve against the queried snapshot's
    domain; pinning the RESOLVED specs keeps results stable across
    maintenance even though re-resolution would move the edges."""
    keys, vals = _workload()
    cfg = LSMConfig(codec="opd", value_width=VW)
    specs = [AggSpec("group_count", group=GroupBy("bucket", n_buckets=6))]
    with LSMTree(cfg) as tree:
        tree.put_batch(keys, vals)
        tree.put_batch(keys[:400], vals[600:1000])  # shadowed versions
        rspecs = tree._resolve_agg_specs(specs, tree.snapshot())
        assert rspecs[0].group.resolved()
        before = tree.aggregate_many(rspecs)
        tree.flush()
        tree.compact()  # drops shadowed versions -> domain changes
        after = tree.aggregate_many(rspecs)
        assert before[0].value == after[0].value
        fr = tree.filter(Predicate("prefix", b""))
        assert after[0].value == _oracle(fr.values, rspecs[0])


# --------------------------------------------------------------------------- #
# MVCC: snapshot pinned across writes + flush + compaction
# --------------------------------------------------------------------------- #
def test_snapshot_aggregate_during_maintenance():
    keys, vals = _workload()
    cfg = LSMConfig(codec="opd", value_width=VW, filter_backend="fused")
    specs = _specs()
    with LSMTree(cfg) as tree:
        tree.put_batch(keys, vals)
        snap = tree.snapshot()
        want = {i: _oracle(
            tree.filter(s.pred if s.pred is not None
                        else Predicate("prefix", b""), snapshot=snap).values,
            s) for i, s in enumerate(specs)}
        # mutate heavily after the pin
        tree.put_batch(keys, np.array([b"zzz_" + v[:VW - 4] for v in vals],
                                      f"S{VW}"))
        for k in keys[:50]:
            tree.delete(int(k))
        tree.flush()
        tree.compact()
        got = tree.aggregate_many(specs, snapshot=snap)
        for i, res in enumerate(got):
            assert res.value == want[i], specs[i]


# --------------------------------------------------------------------------- #
# fast path engagement + telemetry
# --------------------------------------------------------------------------- #
def test_fastpath_engages_with_shortcircuit():
    """Compacted OPD tree + clustered values: the fused fast path must
    run (no fallback), short-circuit tiles, and stay value-identical."""
    n = 6000
    keys = np.arange(1, n + 1).astype(np.uint64)
    # key-correlated values -> tight zones -> whole tiles short-circuit
    vals = np.array([b"ts_%012d" % (i // 4) for i in range(n)], f"S{VW}")
    cfg = LSMConfig(codec="opd", value_width=VW, filter_backend="fused")
    specs = [AggSpec("count"), AggSpec("min"), AggSpec("max"),
             AggSpec("count", pred=Predicate("prefix", b"ts_000000000"))]
    with LSMTree(cfg) as tree:
        tree.put_batch(keys, vals)
        tree.flush()
        tree.compact()
        got = tree.aggregate_many(specs)
        c = tree.agg_stats.counts
        assert c.get("agg_fastpath_runs", 0) > 0
        assert c.get("agg_fallback_runs", 0) == 0
        assert c.get("agg_tiles_shortcircuit", 0) > 0
        _check_engine(tree, specs, tag="fastpath")
        assert got[0].value == n


@pytest.mark.parametrize("backend", ["fused", "numpy"])
def test_sum_shortcircuit_via_weight_sums(backend):
    """SUM rides the closed-form tile short-circuit: per-block weight
    sums in the zone map let contained tiles/blocks contribute their
    exact weight total without reading a code word.  Before the weight
    sums existed, any SUM spec forced full evaluation of every
    intersecting tile — this pins the telemetry floor on both the
    kernel path ('fused') and the host block-granular path ('numpy')."""
    n = 6000
    keys = np.arange(1, n + 1).astype(np.uint64)
    # key-correlated numeric values -> tight zones, nonzero weights
    vals = np.array([b"%012d_v" % (1000 + i // 4) for i in range(n)],
                    f"S{VW}")
    cfg = LSMConfig(codec="opd", value_width=VW, filter_backend=backend)
    specs = [AggSpec("sum"),
             AggSpec("sum", pred=Predicate("prefix", b"000000001"))]
    with LSMTree(cfg) as tree:
        tree.put_batch(keys, vals)
        tree.flush()
        tree.compact()
        got = tree.aggregate_many(specs)
        c = tree.agg_stats.counts
        assert c.get("agg_fastpath_runs", 0) > 0
        assert c.get("agg_fallback_runs", 0) == 0
        assert c.get("agg_tiles_shortcircuit", 0) > 0
        _check_engine(tree, specs, tag=f"sum-sc-{backend}")
        assert got[0].value == int(numeric_values(vals).sum())


def test_general_path_with_visible_memtable():
    """Any visible memtable row forces the general path (its tombstones
    shadow run rows) — and the answers still match the oracle."""
    keys, vals = _workload(n=1500)
    cfg = LSMConfig(codec="opd", value_width=VW, filter_backend="fused")
    with LSMTree(cfg) as tree:
        tree.put_batch(keys, vals)
        tree.flush()
        tree.compact()
        tree.put(int(keys[0]), b"freshest")
        tree.delete(int(keys[1]))
        _check_engine(tree, _specs(), tag="memtable")
        assert tree.agg_stats.counts.get("agg_fallback_runs", 0) > 0


# --------------------------------------------------------------------------- #
# ScanServer: AggRequest batched with filters on one snapshot
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("engine", ["tree", "sharded"])
def test_scan_server_mixed_batch(engine):
    keys, vals = _workload(n=2000)
    cfg = LSMConfig(codec="opd", value_width=VW, filter_backend="fused")
    if engine == "tree":
        eng = LSMTree(cfg)
    else:
        eng = ShardedLSM(cfg, n_shards=3, key_max=KEY_SPACE)
    with eng:
        eng.put_batch(keys, vals)
        eng.flush()
        (eng.compact if engine == "tree" else eng.compact_all)()
        srv = ScanServer(eng, max_batch=8)
        rid_f = srv.submit(PRED)
        rid_c = srv.submit_agg(AggSpec("count"))
        rid_g = srv.submit_agg(AggSpec(
            "group_count", group=GroupBy("prefix", prefix_len=9), top_k=4))
        out = srv.drain()
        assert out[rid_c].value == len(keys)
        fr = eng.filter(PRED)
        assert len(out[rid_f].values) == len(fr.values)
        assert out[rid_g].value == _oracle(
            eng.filter(Predicate("prefix", b"")).values,
            AggSpec("group_count", group=GroupBy("prefix", prefix_len=9),
                    top_k=4))
        assert srv.stats.n_batches == 1  # one batch, one snapshot


def test_scan_server_mixed_batch_consistent_snapshot():
    """Writes submitted between submit and step must not leak into the
    batch: filter count == aggregate count (same pinned snapshot)."""
    keys, vals = _workload(n=1000)
    cfg = LSMConfig(codec="opd", value_width=VW)
    with LSMTree(cfg) as tree:
        tree.put_batch(keys, vals)
        srv = ScanServer(tree, max_batch=4)
        rid_f = srv.submit(Predicate("prefix", b""))
        rid_c = srv.submit_agg(AggSpec("count"))
        out = srv.step()
        assert len(out[rid_f].values) == out[rid_c].value == len(keys)


# --------------------------------------------------------------------------- #
# spec-layer units: merge contract, SUM semantics, bucket truncation
# --------------------------------------------------------------------------- #
def test_partial_merge_associative_commutative():
    rng = np.random.default_rng(0)

    def rand_partial():
        p = AggPartial(count=int(rng.integers(0, 50)),
                       total=int(rng.integers(0, 1000)))
        if rng.random() < 0.8:
            p.min_value = bytes(rng.integers(97, 123, 4).astype(np.uint8))
            p.max_value = max(p.min_value,
                              bytes(rng.integers(97, 123, 4).astype(np.uint8)))
        if rng.random() < 0.5:
            p.groups = {b"g%d" % g: int(rng.integers(1, 9))
                        for g in rng.integers(0, 5, 3)}
        return p

    for _ in range(50):
        a, b, c = rand_partial(), rand_partial(), rand_partial()
        ab_c = a.merge(b).merge(c)
        a_bc = a.merge(b.merge(c))
        ba_c = b.merge(a).merge(c)
        for x in (a_bc, ba_c):
            assert ab_c.count == x.count and ab_c.total == x.total
            assert ab_c.min_value == x.min_value
            assert ab_c.max_value == x.max_value
            assert ab_c.groups == x.groups
        ident = merge_partials([a, AggPartial()])
        assert (ident.count, ident.total, ident.min_value,
                ident.max_value) == (a.count, a.total, a.min_value,
                                     a.max_value)


def test_finalize_topk_tiebreak_deterministic():
    spec = AggSpec("group_count",
                   group=GroupBy("prefix", prefix_len=2), top_k=2)
    part = AggPartial(groups={b"bb": 5, b"aa": 5, b"cc": 9})
    part.count = 19
    res = finalize_partial(spec, part)
    assert res.groups == [(b"cc", 9), (b"aa", 5)]  # (-count, label)


def test_numeric_values_semantics():
    vals = np.asarray([b"abc", b"a1b2", b"007x", b"", b"99999999999",
                       b"x" + str(INT32_MAX).encode()], "S16")
    out = numeric_values(vals)
    assert out.tolist() == [0, 1, 7, 0, INT32_MAX, INT32_MAX]


def test_bucket_ids_overlong_edge_truncation():
    """An edge longer than the value width compares exclusively after
    truncation (mirrors filter_exec._lower_mask)."""
    vals = np.asarray([b"aaaa", b"aaab"], "S4")
    # b"aaaa" == the truncation -> excluded; b"aaab" > it -> included
    assert bucket_ids(vals, (b"aaaa_longer",)).tolist() == [0, 1]
    assert bucket_ids(vals, (b"aaab",)).tolist() == [0, 1]
