"""Tracing of the served scan path: ``StageStats`` stages as profiler
spans, the kernel dispatch split (``ops.prep`` / ``ops.h2d`` /
``ops.device`` / ``ops.d2h``) with its byte counters, the executor's
stages nested inside ``"filter"``, the shard layer's result gather and
the server's step span."""

import glob

import jax
import numpy as np
import pytest

from repro.core import LSMConfig, LSMTree, Predicate, StageStats
from repro.core.sct import bitpack
from repro.kernels import agg_scan, fused_scan, ops
from repro.query import AggSpec, GroupBy
from repro.serving.scan_server import ScanServer
from repro.shard import ShardedLSM

VW = 16
OPS_STAGES = ("ops.prep", "ops.h2d", "ops.device", "ops.d2h")
FILTER_SUBSTAGES = ("plan", "expand", "gather", "memtable") + OPS_STAGES
PREDS = [Predicate("prefix", b"cat_01"), Predicate("range", b"cat_03", b"cat_07"),
         Predicate("eq", b"cat_099")]


def _records(n=3000, seed=3):
    rng = np.random.default_rng(seed)
    keys = rng.permutation(n).astype(np.uint64) * 3
    vals = np.asarray([b"cat_%03d" % int(x) for x in rng.integers(0, 120, n)],
                      f"S{VW}")
    return keys, vals


def _compacted(cfg, n_shards=None):
    keys, vals = _records()
    if n_shards is None:
        eng = LSMTree(cfg)
        eng.put_batch(keys, vals)
        eng.flush()
        eng.compact()
    else:
        eng = ShardedLSM(cfg, n_shards=n_shards, key_max=1 << 20)
        eng.put_batch(keys, vals)
        eng.compact_all()
    return eng


def _host_spans(tmp_path, fn):
    """Run ``fn`` under a ``jax.profiler`` trace; its host events as
    (name, start_ns, end_ns, event)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, e)
                        for e in line.events]
    return out


def test_stage_opens_span_named_after_stats_and_stage(tmp_path):
    named, unnamed = StageStats("filter"), StageStats()

    def work():
        with named.time("plan"):
            pass
        with unnamed.time("merge"):
            pass
    names = [n for n, *_ in _host_spans(tmp_path, work)]
    assert names.count("filter.plan") == 1 and names.count("merge") == 1
    assert named.counts["plan"] == 1 and unnamed.counts["merge"] == 1
    assert StageStats.merge_all([named, unnamed]).name is None


def _level(width=16, n=(5000, 2600)):
    rng = np.random.default_rng(11)
    codes = [rng.integers(1, 900, k).astype(np.int32) for k in n]
    return codes, [bitpack(c, width) for c in codes], width


def _tiles(packed, block_rows):
    tile_words = block_rows * fused_scan.LANES
    return sum(-(-p.shape[0] // tile_words) for p in packed)


@pytest.mark.parametrize("kind", ["filter", "agg", "histogram"])
def test_launch_counts_bytes_both_ways(kind):
    codes, packed, width = _level()
    n = [c.shape[0] for c in codes]
    ranges = [np.asarray([[10, 200], [300, 301], [5, 4]], np.uint32)] * 2
    zones = [None, None]
    br = fused_scan.DEFAULT_BLOCK_ROWS
    tiles, k, lanes = _tiles(packed, br), 3, fused_scan.LANES
    words = tiles * br * lanes * 4
    st = StageStats("filter")
    if kind == "filter":
        got = ops.fused_level_filter(packed, n, ranges, zones, width, stats=st)
        want = ops.fused_level_filter(packed, n, ranges, zones, width)
        up = words + tiles * fused_scan.META_COLS * 4 + 2 * k * 2 * 4
        down = k * words + tiles * 4           # bitmaps + per-tile hits
        for a, b in zip(got[0], want[0]):
            np.testing.assert_array_equal(a, b)
    elif kind == "agg":
        wts = [np.arange(900, dtype=np.int32)] * 2
        got = ops.fused_level_agg(packed, n, ranges, zones, width,
                                  weights_list=wts, stats=st)
        want = ops.fused_level_agg(packed, n, ranges, zones, width,
                                   weights_list=wts)
        up = (words + tiles * agg_scan.AGG_META_COLS * 4 + 2 * k * 2 * 4
              + 1800 * 4 + (-1800 % lanes) * 4)
        down = 4 * tiles * k * 4 + tiles * 4   # count/min/max/sum + flags
        for a, b in zip(got[0], want[0]):
            for key in a:
                np.testing.assert_array_equal(a[key], b[key])
    else:
        edges = [np.asarray([1, 100, 400, 900], np.uint32)] * 2
        got = ops.level_histogram(packed, n, edges, zones, width, stats=st)
        want = ops.level_histogram(packed, n, edges, zones, width)
        up = words + tiles * agg_scan.AGG_META_COLS * 4 + 2 * 4 * 4
        down = tiles * 3 * 4 + tiles * 4       # per-tile bins + flags
        for a, b in zip(got[0], want[0]):
            np.testing.assert_array_equal(a, b)
    assert got[1] == want[1]
    assert st.counts["h2d_bytes"] == up
    assert st.counts["d2h_bytes"] == down
    assert all(st.counts[s] == 1 for s in OPS_STAGES)


def test_filter_and_merge_cover_nested_stages(tmp_path):
    tree = _compacted(LSMConfig(codec="opd", value_width=VW,
                                filter_backend="fused"))
    keys, vals = _records(200, seed=9)
    tree.put_batch(keys + 1, vals)            # memtable rows to scan too
    spans = _host_spans(tmp_path, lambda: tree.filter_many(PREDS))
    st = tree.filter_stats
    assert st.counts["filter"] == 1 and st.counts["merge"] == 1
    assert all(st.counts[s] >= 1 for s in FILTER_SUBSTAGES)
    nested = sum(st.seconds[s] for s in FILTER_SUBSTAGES)
    assert nested <= st.seconds["filter"]
    (f0, f1), = [(s, e) for n, s, e, _ in spans if n == "filter.filter"]
    (m0, m1), = [(s, e) for n, s, e, _ in spans if n == "filter.merge"]
    inner = [(s, e) for n, s, e, _ in spans
             if n in {f"filter.{x}" for x in FILTER_SUBSTAGES}]
    assert len(inner) >= len(FILTER_SUBSTAGES)
    assert all(f0 <= s and e <= f1 for s, e in inner)
    assert all(e <= m0 or s >= m1 for s, e in inner)
    tree.close()


def test_gathered_rows_are_the_run_candidates():
    tree = _compacted(LSMConfig(codec="opd", value_width=VW,
                                filter_backend="fused"))
    res = tree.filter_many(PREDS)
    c = tree.filter_stats.counts
    assert c["gathered_rows"] == sum(r.n_matched_raw for r in res) > 0
    assert c["h2d_bytes"] > 0 and c["d2h_bytes"] > 0
    tree.close()


def test_general_aggregate_times_filter_substages():
    cfg = LSMConfig(codec="opd", value_width=VW, filter_backend="fused")
    tree = _compacted(cfg)
    keys, vals = _records(100, seed=5)
    tree.put_batch(keys, vals)                # visible memtable: general path
    tree.aggregate_many([AggSpec("count", pred=PREDS[0]), AggSpec("min")])
    c = tree.agg_stats.counts
    assert c["agg_fallback_runs"] > 0 and c["gathered_rows"] > 0
    assert all(c[s] >= 1 for s in ("expand", "gather", "memtable",
                                   "ops.device"))
    assert "agg_rows_scanned" not in c
    tree.close()


def test_shard_stages_kept_on_the_engine():
    eng = _compacted(LSMConfig(codec="opd", value_width=VW,
                               filter_backend="fused"), n_shards=3)
    specs = [AggSpec("group_count", group=GroupBy("bucket", n_buckets=4)),
             AggSpec("count")]
    eng.aggregate_many(specs)
    eng.filter_many(PREDS)
    st = eng.shard_stats
    assert st.name == "shard"
    # one span per call, not per predicate or per shard
    assert dict(st.counts) == {"gather": 1} and st.seconds["gather"] > 0
    # the aggregate launches time their dispatch into the trees' agg_stats
    agg = eng.agg_stats.counts
    assert agg["ops.d2h"] == agg["agg_launches"] >= 2
    assert agg["h2d_bytes"] > 0
    eng.close()


def test_server_step_is_a_numbered_step_span(tmp_path):
    tree = _compacted(LSMConfig(codec="opd", value_width=VW,
                                filter_backend="fused"))
    srv = ScanServer(tree, max_batch=2)

    def serve():
        srv.submit_many(PREDS)
        srv.drain()
    steps = [dict(e.stats)["step_num"]
             for n, _, _, e in _host_spans(tmp_path, serve)
             if n == "scan_server.step"]
    assert sorted(steps) == [0, 1]
    assert srv.stats.n_served == len(PREDS)
    tree.close()
