"""Chip smoke: the served scan path, end to end, on one TPU chip.

    python chip_smoke.py                 # the chip run: N = 16,000,000
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 200000   # CPU rehearsal

One process drives the engine the way a deployment does: a
``ScanServer`` over ``ShardedLSM(n_shards=4)`` with OPD-coded 128-byte
values in 64 MiB files, the zone-mapped ``'fused'`` filter, the
``'jax_packed'`` compaction backend, background maintenance and a
group-commit WAL on a spill directory.  Data comes from ``--seed`` through
``benchmarks/_harness.py`` (``gen_keys`` uniform over 4N, ``gen_values``
at 1 % NDV).  N is a quarter of the paper's 6.4e7 pairs, cut only for
host ingest time.

Phases, each timed on its own line: set-up (data generation), ingest
in chunks (then ``drain`` and ``raise_maintenance_errors``), compact
(``compact_all``, so aggregates take the per-run fast path), the numpy
oracle, two identical ``ScanServer`` rounds against one pinned snapshot
(16 prefix and 4 range filters plus COUNT, SUM, MIN/MAX and a
group-count; the difference between the rounds is compile time), and
1,000 point gets of acknowledged keys.  Every result is checked against
a plain numpy oracle built from the generated arrays (last write wins
per key); any mismatch exits nonzero.  Timings are bring-up
observations, not benchmark numbers.

No fallback hides the device: without a TPU the script stops before
its first phase, unless ``--rows`` asks for a rehearsal, which then
runs every phase and still exits nonzero.  The kernels run compiled
(``kernels.ops.INTERPRET`` is False on a TPU backend), and a failed
background flush or compaction is raised, never caught.  The last line
of a passing chip run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

There is deliberately no four-chip phase: the engine has no path across
chips.  ``ShardedLSM`` shards are host threads over one device, and
replicas live in one process; one shard per chip is unbuilt work.

The compile cache follows ``repro.runtime.compile_cache``: JAX's own
``JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
PAPER_PAIRS = 64_000_000
DEFAULT_ROWS = 16_000_000
N_SHARDS = 4
VALUE_WIDTH = 128
N_GETS = 1000
CHUNK = 1_000_000       # records per put_batch call
# The paper's SST size (32-64 MB), not the CI-scaled 4 MiB default.  With
# 4 MiB files a compacted 16M-record shard spans L1 and L2, whose runs
# overlap, so aggregates take the general path and no agg kernel runs.
FILE_BYTES = 64 * 2**20


def _phase(name: str, t0: float, **extra) -> None:
    tail = "".join(f" {k}={v}" for k, v in extra.items())
    print(f"phase {name}: {time.perf_counter() - t0:.3f} s{tail}", flush=True)


def _requests():
    from repro.core import Predicate
    from repro.query import AggSpec, GroupBy

    prefixes = [Predicate("prefix", b"cat_00%02d" % j) for j in range(0, 96, 6)]
    ranges = [Predicate("range", b"cat_%05d" % lo, b"cat_%05d" % (lo + w))
              for lo, w in ((100, 5), (333, 40), (700, 1), (950, 49))]
    aggs = [
        AggSpec("count"),
        AggSpec("count", pred=prefixes[3]),
        AggSpec("sum", pred=ranges[1]),
        AggSpec("min"),
        AggSpec("max"),
        AggSpec("min", pred=ranges[2]),
        AggSpec("max", pred=ranges[2]),
        AggSpec("group_count", group=GroupBy("prefix", prefix_len=7)),
    ]
    return prefixes + ranges, aggs


class Oracle:
    """Last write wins per key, then byte-string predicates and
    aggregates over the surviving values, in plain numpy."""

    def __init__(self, keys, values):
        import numpy as np

        n = keys.shape[0]
        uniq, first_of_rev = np.unique(keys[::-1], return_index=True)
        self.keys = uniq                          # ascending, like results
        self.values = values[n - 1 - first_of_rev]
        self.bytes = self.values.view(np.uint8).reshape(-1, VALUE_WIDTH)

    def mask(self, pred):
        import numpy as np

        if pred is None:
            return np.ones(self.keys.shape[0], bool)
        if pred.kind == "prefix":
            want = np.frombuffer(pred.a, np.uint8)
            return (self.bytes[:, :want.shape[0]] == want).all(axis=1)
        assert pred.kind == "range", pred
        return (self.values >= pred.a) & (self.values <= pred.b)

    def extreme(self, m, pick):
        """Lexicographic min/max over the masked values, 8 bytes at a
        time (numpy has no min/max for byte strings)."""
        import numpy as np

        rows = np.nonzero(m)[0]
        for c in range(0, VALUE_WIDTH, 8):
            chunk = self.bytes[rows, c:c + 8].copy().view(">u8").reshape(-1)
            rows = rows[chunk == pick(chunk)]
        return bytes(self.values[rows[0]]) if rows.shape[0] else None

    def numeric(self, m):
        """SUM weight: the first digit run, which ``gen_values`` puts at
        bytes 4..8 (``cat_%05d_`` then letters) — asserted, not assumed."""
        import numpy as np

        b = self.bytes[m]
        assert (b[:, :4] == np.frombuffer(b"cat_", np.uint8)).all()
        assert ((b[:, 4:9] >= 48) & (b[:, 4:9] <= 57)).all()
        assert (b[:, 9] == ord("_")).all() and (b[:, 10:] >= 97).all()
        digits = b[:, 4:9].astype(np.int64) - 48
        return int((digits @ np.asarray([10**4, 10**3, 100, 10, 1])).sum())

    def aggregate(self, spec):
        import numpy as np

        m = self.mask(spec.pred)
        if spec.op == "count":
            return int(m.sum())
        if spec.op == "sum":
            return self.numeric(m)
        if spec.op in ("min", "max"):
            return self.extreme(m, np.min if spec.op == "min" else np.max)
        assert spec.op == "group_count" and spec.group.kind == "prefix"
        plen = spec.group.prefix_len
        labels = np.zeros((int(m.sum()), 8), np.uint8)
        labels[:, :plen] = self.bytes[m, :plen]
        ids, counts = np.unique(labels.view(">u8").reshape(-1),
                                return_counts=True)
        items = [(int(i).to_bytes(8, "big")[:plen].rstrip(b"\0"), int(c))
                 for i, c in zip(ids, counts)]
        return sorted(items, key=lambda kv: (-kv[1], kv[0]))


def _serve_round(engine, oracle, preds, aggs, expected) -> list:
    """One ScanServer batch against one pinned snapshot; returns the
    names of results that differ from the oracle."""
    import numpy as np

    from repro.serving.scan_server import ScanServer

    server = ScanServer(engine, max_batch=len(preds) + len(aggs))
    rids = server.submit_many(preds) + server.submit_aggs(aggs)
    out = server.step(engine.snapshot())
    assert not server.queue and len(out) == len(rids)
    bad = []
    for q, (rid, pred) in enumerate(zip(rids, preds)):
        if ("filter", q) not in expected:
            expected["filter", q] = oracle.mask(pred)
        m = expected["filter", q]
        res = out[rid]
        if not (np.array_equal(res.keys, oracle.keys[m])
                and np.array_equal(res.values, oracle.values[m])):
            bad.append(f"filter {pred}")
    for q, (rid, spec) in enumerate(zip(rids[len(preds):], aggs)):
        if ("agg", q) not in expected:
            expected["agg", q] = oracle.aggregate(spec)
        want = expected["agg", q]
        if out[rid].value != want:
            bad.append(f"aggregate {spec.op} {spec.pred} {spec.group}: "
                       f"got {out[rid].value!r} want {want!r}")
    return bad


def _largest_launch(engine, tile_words: int):
    """Tiles and padded packed-word bytes of the largest level launch:
    per shard, one launch per (level, pack width) over its opd runs."""
    best = (0, 0)
    for snap in engine.snapshot().snaps:
        groups: dict = {}
        for s in snap.runs:
            if s.n and s.codec == "opd" and s.packed is not None:
                tiles = max(1, -(-s.packed.shape[0] // tile_words))
                key = (s.level, s.code_bits)
                groups[key] = groups.get(key, 0) + tiles
        for tiles in groups.values():
            best = max(best, (tiles, tiles * tile_words * 4))
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=None,
                    help=f"records to ingest (default {DEFAULT_ROWS:,}); "
                         "on a non-TPU backend runs a rehearsal that still "
                         "exits nonzero")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    rows = args.rows or DEFAULT_ROWS
    t_start = time.perf_counter()

    if not (ROOT / "src" / "repro").is_dir() or \
            not (ROOT / "benchmarks" / "_harness.py").is_file():
        print(f"chip_smoke: {ROOT} holds no checkout of the repo "
              "(src/repro, benchmarks/_harness.py)", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from repro.runtime.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()   # before the first compile
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}", flush=True)
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.rows is None:
        print(f"chip_smoke: no TPU (jax.devices()[0].platform == "
              f"{dev.platform!r}); stopping before the first phase",
              file=sys.stderr)
        return 1

    import numpy as np

    from benchmarks._harness import gen_keys, gen_values
    from repro.core import LSMConfig
    from repro.kernels import fused_scan, ops as kops
    from repro.shard import ShardedLSM

    if kops.INTERPRET == on_tpu:
        raise RuntimeError(f"kernels.ops.INTERPRET={kops.INTERPRET} on "
                           f"{dev.platform!r}")
    print(f"compile_cache: {cache_dir}", flush=True)
    print(f"interpret: {kops.INTERPRET}", flush=True)
    print(f"rows: {rows:,} ({rows / PAPER_PAIRS:.4g} of the paper's "
          f"{PAPER_PAIRS:.1e} pairs; cut for host ingest time)", flush=True)

    t0 = time.perf_counter()
    keys = gen_keys(rows, seed=args.seed)
    values = gen_values(rows, VALUE_WIDTH, ndv_ratio=0.01, seed=args.seed + 1)
    _phase("setup", t0, rows=rows)

    cfg = LSMConfig(codec="opd", value_width=VALUE_WIDTH, file_bytes=FILE_BYTES,
                    filter_backend="fused", compaction_backend="jax_packed",
                    maintenance="background", wal_sync="group")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_spill_") as spill, \
            ShardedLSM(cfg, n_shards=N_SHARDS, key_max=4 * rows,
                       spill_dir=spill) as engine:
        t0 = time.perf_counter()
        for i in range(0, rows, CHUNK):
            engine.put_batch(keys[i:i + CHUNK], values[i:i + CHUNK])
        engine.drain()
        engine.raise_maintenance_errors()
        _phase("ingest", t0, rows_per_s=f"{rows / (time.perf_counter() - t0):.0f}")

        t0 = time.perf_counter()
        engine.compact_all()
        engine.raise_maintenance_errors()
        _phase("compact", t0)

        t0 = time.perf_counter()
        oracle = Oracle(keys, values)
        del keys, values
        _phase("oracle", t0, live_keys=oracle.keys.shape[0])

        preds, aggs = _requests()
        expected: dict = {}
        bad = []
        for name in ("round1", "round2"):
            t0 = time.perf_counter()
            bad += _serve_round(engine, oracle, preds, aggs, expected)
            _phase(name, t0, filters=len(preds), aggregates=len(aggs))

        t0 = time.perf_counter()
        rng = np.random.default_rng(args.seed + 2)
        for k in rng.choice(oracle.keys, N_GETS):
            want = bytes(oracle.values[np.searchsorted(oracle.keys, k)])
            if engine.get(int(k)) != want:
                bad.append(f"get {int(k)}")
        _phase("gets", t0, gets=N_GETS)
        engine.raise_maintenance_errors()

        fc, ac = engine.filter_stats.counts, engine.agg_stats.counts
        for name in ("fused_launches", "zone_tiles_total",
                     "zone_tiles_skipped", "zone_blocks_total",
                     "zone_blocks_skipped"):
            print(f"{name}: {fc[name]}")
        for name in ("agg_launches", "agg_fastpath_runs", "agg_tiles_total",
                     "agg_tiles_skipped", "agg_tiles_evaluated",
                     "agg_tiles_shortcircuit", "agg_histograms_gathered"):
            print(f"{name}: {ac[name]}")
        shape = engine.shape_report()
        print(f"n_compactions: {shape['n_compactions']}")
        print(f"n_flushes: {shape['n_flushes']}")
        print(f"levels: {[s['levels'] for s in shape['per_shard']]}")
        tiles, nbytes = _largest_launch(
            engine, fused_scan.DEFAULT_BLOCK_ROWS * fused_scan.LANES)
        print(f"largest_launch: tiles={tiles} packed_word_bytes={nbytes}")
    mem = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use', 'not reported')}")
    print(f"wall_total: {time.perf_counter() - t_start:.3f} s", flush=True)

    for line in bad:
        print(f"MISMATCH {line}", flush=True)
    checks = {"fused_launches > 0": fc["fused_launches"] > 0,
              "agg_launches > 0": ac["agg_launches"] > 0,
              "n_compactions > 0": shape["n_compactions"] > 0,
              "results match the oracle": not bad}
    failed = [name for name, ok in checks.items() if not ok]
    if failed:
        print(f"chip_smoke: failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    if not on_tpu:
        print(f"chip_smoke: rehearsal on {dev.platform!r} passed; "
              "only a TPU run reports ok", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
