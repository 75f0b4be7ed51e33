"""Scan-based filter evaluation — paper §4.2.2, single- and multi-query.

``filtering(Value_{conditions})`` scans every run in every level, finds
entries whose *value* satisfies the predicate, discards stale versions,
and returns the qualifying (key, value) pairs.

The OPD fast path (Figure 5):
  1. predicate -> code range [lo, hi) via two dictionary binary searches
     (O(log D) string comparisons — the only place strings are touched);
  2. vectorized compare directly on the encoded column (numpy here; the
     TPU kernels in ``repro.kernels`` do the same over VMEM tiles, and
     ``packed_filter`` does it without even unpacking the bit-packed
     words);
  3. O(1) decode of the (few) matches: code == offset into the dict;
  4. cross-level merge discarding stale versions.

``evaluate_filter_many`` is the batched executor behind the serving
path: K predicates are planned together (K binary searches per SCT
dictionary) and evaluated in ONE pass over each run's value column —
the per-run read/decode cost and, on the ``jax_packed`` backend, the
packed-word field extraction (``kernels.multi_filter``) are amortized
over all K queries.  ``evaluate_filter`` is the K=1 special case, so
batched and single results are bit-identical by construction.

Competitor codecs pay what the paper says they pay: 'plain' compares
S_V-byte strings for every entry; 'heavy' first zlib-decompresses every
block (C_D x F); 'blob' performs random value addressing in blob files.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.memtable import MemTable, MemTables, as_mems
from repro.core.opd import OPD, Predicate
from repro.core.sct import SCT, BlobManager
from repro.core.stats import StageStats
from repro.storage.io import FileStore


def string_mask(values: np.ndarray, pred: Predicate) -> np.ndarray:
    """Vectorized predicate over raw fixed-width strings (C_S * S_V * N).

    Operands longer than the value width need care: the ``S{w}`` cast
    silently truncates, and a truncated operand compares equal to values
    it should NOT match.  'eq'/'prefix' with an over-long operand match
    nothing; an over-long *lower* bound must exclude its own truncation
    (v == a[:w] < a because a is longer); an over-long *upper* bound is
    truncation-safe (v == b[:w] < b, so v <= b still holds).  Mirrors
    ``OPD.code_range`` so every codec plans identically.
    """
    w = values.dtype.itemsize
    if pred.kind == "eq":
        if len(pred.a) > w:
            return np.zeros(values.shape[0], np.bool_)
        return values == np.asarray([pred.a], f"S{w}")[0]
    if pred.kind == "prefix":
        if len(pred.a) > w:
            # b"\xff" * (w - len(pred.a)) goes negative -> b"", and the
            # truncated cast used to match values equal to the truncated
            # prefix; no w-byte value has a longer-than-w prefix
            return np.zeros(values.shape[0], np.bool_)
        lo = np.asarray([pred.a], f"S{w}")[0]
        hi = np.asarray([pred.a + b"\xff" * (w - len(pred.a))], f"S{w}")[0]
        return (values >= lo) & (values <= hi)
    if pred.kind == "range":
        return _lower_mask(values, pred.a) & \
            (values <= np.asarray([pred.b], f"S{w}")[0])
    if pred.kind == "ge":
        return _lower_mask(values, pred.a)
    if pred.kind == "le":
        return values <= np.asarray([pred.b], f"S{w}")[0]
    raise ValueError(pred.kind)


def _lower_mask(values: np.ndarray, a: bytes) -> np.ndarray:
    """``value >= a`` (truncation-aware: an over-long bound excludes
    values equal to its truncation)."""
    w = values.dtype.itemsize
    bound = np.asarray([a], f"S{w}")[0]
    return values > bound if len(a) > w else values >= bound


@dataclasses.dataclass
class FilterResult:
    keys: np.ndarray     # uint64 [k]
    values: np.ndarray   # S<w>  [k]
    n_scanned: int
    n_matched_raw: int   # before stale-version discard


def evaluate_filter(
    runs: List[SCT],
    memtable: MemTables,
    pred: Predicate,
    *,
    stats: StageStats,
    store: FileStore,
    blob_mgr: Optional[BlobManager] = None,
    snapshot_seqno: Optional[int] = None,
    backend: str = "numpy",  # 'numpy' | 'jax' | 'jax_packed' | 'fused'
    value_width: Optional[int] = None,
) -> FilterResult:
    """Single-predicate filter — the K=1 case of ``evaluate_filter_many``."""
    return evaluate_filter_many(
        runs, memtable, [pred],
        stats=stats, store=store, blob_mgr=blob_mgr,
        snapshot_seqno=snapshot_seqno, backend=backend,
        value_width=value_width,
    )[0]


def evaluate_filter_many(
    runs: List[SCT],
    memtable: MemTables,
    preds: Sequence[Predicate],
    *,
    stats: StageStats,
    store: FileStore,
    blob_mgr: Optional[BlobManager] = None,
    snapshot_seqno: Optional[int] = None,
    backend: str = "numpy",  # 'numpy' | 'jax' | 'jax_packed' | 'fused'
    value_width: Optional[int] = None,
) -> List[FilterResult]:
    """Evaluate K predicates with one pass over every run's value column.

    Returns one ``FilterResult`` per predicate, bit-identical to K
    independent ``evaluate_filter`` calls; only the run-level costs
    (file read, 'heavy' decompression, 'blob' addressing, packed-word
    field extraction) are paid once instead of K times.

    The 'fused' backend additionally batches ACROSS runs: every 'opd'
    run of a level goes through ONE ``kernels.ops.fused_level_filter``
    launch (zone-gated; see ``_fused_level_bitmaps``), so launch count is
    per level, not per run.

    ``value_width`` pins the dtype of empty results.  Without it an
    empty ``FilterResult`` falls back to the width of the first live run
    (or 8 when no runs survive), which drifts from the tree's configured
    width and breaks concatenation in scatter-gather merges — callers
    that know the tree config (``LSMTree.filter*``) always pass it.
    """
    preds = list(preds)
    n_preds = len(preds)
    if n_preds == 0:
        return []
    mems = as_mems(memtable)
    snap = np.uint64(snapshot_seqno) if snapshot_seqno is not None else None

    # ---- stage: retrieval (locate candidate files across all levels) ----- #
    with stats.time("retrieval"):
        live_runs = [s for s in runs if s.n > 0]

    # ---- stage: read (bulk full-file reads, ONCE for the whole batch) ---- #
    with stats.time("read"):
        for s in live_runs:
            store.stats.add_read(s.disk_bytes, 1)

    # ---- stage: decode (only competitors pay here; once per batch) ------- #
    decoded: List[Optional[np.ndarray]] = [None] * len(live_runs)
    with stats.time("decode"):
        for i, s in enumerate(live_runs):
            if s.codec == "heavy":
                decoded[i] = s._decompress_all()[2]
            elif s.codec == "blob":
                decoded[i] = _read_blob_values(s, blob_mgr)

    # ---- stage: filter (one vectorized pass, K masks per run) ------------ #
    cand_keys = [[] for _ in range(n_preds)]
    cand_seqs = [[] for _ in range(n_preds)]
    cand_vals = [[] for _ in range(n_preds)]
    n_scanned = 0
    with stats.time("filter"):
        for i, s, masks in _run_masks(live_runs, preds, decoded, stats,
                                      backend, snap):
            n_scanned += s.n
            with stats.time("gather"):
                for q in range(n_preds):
                    idx = np.nonzero(masks[q])[0]
                    if idx.shape[0] == 0:
                        continue
                    stats.counts["gathered_rows"] += idx.shape[0]
                    cand_keys[q].append(s.keys[idx])
                    cand_seqs[q].append(s.seqnos[idx])
                    if s.codec == "opd":
                        # O(1) decode: code is the offset into the dictionary
                        cand_vals[q].append(s.opd.decode(s.evs[idx]))
                    elif s.codec == "plain":
                        cand_vals[q].append(s.values[idx])
                    else:
                        cand_vals[q].append(decoded[i][idx])
        # memtable stack (newest data) — small, row-oriented scans,
        # walked once per memtable.  Rows shadowed by a newer memtable
        # (or run) are discarded by the seqno merge below, so simply
        # concatenating every memtable's newest-visible rows is correct.
        with stats.time("memtable"):
            mk, ms, mv = _memtable_visible(mems, snap, value_width)
            if mk.shape[0]:
                for q, p in enumerate(preds):
                    m = string_mask(mv, p)
                    if m.any():
                        cand_keys[q].append(mk[m])
                        cand_seqs[q].append(ms[m])
                        cand_vals[q].append(mv[m])

    # ---- stage: merge (discard stale versions, per predicate) ------------ #
    results = []
    with stats.time("merge"):
        # memtable shadow state is computed ONCE per batch (sorted key ->
        # newest visible seqno, tombstones included); the per-predicate
        # shadow check below is then one searchsorted, not a Python probe
        # per candidate.
        mem_newest = _memtable_newest(mems, snap)
        for q in range(n_preds):
            results.append(_merge_candidates(
                cand_keys[q], cand_seqs[q], cand_vals[q],
                live_runs, mem_newest, snap, n_scanned, value_width))
    return results


def _merge_candidates(
    cand_keys: List[np.ndarray],
    cand_seqs: List[np.ndarray],
    cand_vals: List[np.ndarray],
    live_runs: List[SCT],
    mem_newest: Optional[Tuple[np.ndarray, np.ndarray]],
    snap,
    n_scanned: int,
    value_width: Optional[int] = None,
) -> FilterResult:
    """Cross-level merge for one predicate's candidates (paper step 4)."""
    if not cand_keys:
        # empty result still needs the RIGHT dtype: scatter-gather merge
        # concatenates per-shard values, and a width-8 fallback from an
        # empty shard poisons the concatenation
        w = value_width if value_width is not None else (
            live_runs[0].value_width if live_runs else 8)
        return FilterResult(np.zeros(0, np.uint64), np.zeros(0, f"S{w}"), n_scanned, 0)
    keys = np.concatenate(cand_keys)
    seqs = np.concatenate(cand_seqs)
    vals = np.concatenate(cand_vals)
    n_raw = int(keys.shape[0])
    order = np.lexsort((np.uint64(0xFFFFFFFFFFFFFFFF) - seqs, keys))
    keys, seqs, vals = keys[order], seqs[order], vals[order]
    first = np.ones(keys.shape[0], np.bool_)
    first[1:] = keys[1:] != keys[:-1]
    keys, seqs, vals = keys[first], seqs[first], vals[first]
    # shadow check: a candidate only survives if it is the *globally*
    # newest visible version of its key (a newer non-matching version
    # or tombstone shadows it).
    newest = _global_newest(keys, live_runs, mem_newest, snap)
    ok = seqs == newest
    keys, vals = keys[ok], vals[ok]
    return FilterResult(keys, vals, n_scanned, n_raw)


# --------------------------------------------------------------------------- #
def _code_masks_many(
    s: SCT, ranges: Sequence[Tuple[int, int]], backend: str
) -> List[np.ndarray]:
    """K bool masks over one SCT's code column from planned [lo, hi) ranges.

    One pass over the column for the whole batch: numpy broadcasts the
    compare over a (K, n) grid; ``jax_packed`` hands the (K, 2) table to
    ``kernels.multi_filter`` so each packed word is read and
    field-extracted once for all K predicates.
    """
    if backend == "numpy":
        los = np.asarray([lo for lo, _ in ranges], np.int64)
        his = np.asarray([hi for _, hi in ranges], np.int64)
        grid = (s.evs[None, :] >= los[:, None]) & (s.evs[None, :] < his[:, None])
        return [grid[q] for q in range(len(ranges))]
    from repro.kernels import ops as kops

    if backend == "jax":
        out = []
        for lo, hi in ranges:
            if lo >= hi:
                out.append(np.zeros(s.n, np.bool_))
            else:
                out.append(np.asarray(
                    kops.range_filter_codes(s.evs, lo, hi - 1))[: s.n].astype(bool))
        return out
    if backend == "jax_packed":
        if all(lo >= hi for lo, hi in ranges):
            # no predicate can match this SCT: skip the kernel launch
            return [np.zeros(s.n, np.bool_) for _ in ranges]
        # inclusive [lo, hi-1]; lo > hi encodes the empty range in-kernel
        tbl = np.asarray(
            [(lo, hi - 1) if lo < hi else (1, 0) for lo, hi in ranges],
            np.uint32)
        bitmaps = kops.multi_range_filter_packed(s.packed, s.code_bits, tbl)
        # tombstones carry code -1 in the unpacked column (so [lo, hi)
        # with lo >= 0 never matches them) but pack as 0 — the kernel
        # sees a live-looking code, so mask them out of its bitmap here
        live = ~s.tombs
        return [kops.bitmap_to_mask(bitmaps[q], s.code_bits, s.n) & live
                for q in range(len(ranges))]
    raise ValueError(backend)


def _run_masks(
    live_runs: List[SCT], preds: Sequence[Predicate],
    decoded: List[Optional[np.ndarray]], stats: StageStats, backend: str,
    snap,
) -> Iterator[Tuple[int, SCT, List[np.ndarray]]]:
    """Per live run ``(index, run, K bool masks)``: the entries that match
    each predicate, are live and are visible at ``snap``.

    Timed inside the caller's ``"filter"`` stage: ``plan`` (code ranges
    per run and predicate) and ``expand`` (fused bitmaps to per-entry
    masks, the tombstone and snapshot masks; one block per run).  The
    'fused' backend evaluates every 'opd' run up front, one launch per
    level (``_fused_level_bitmaps``)."""
    fused = (_fused_level_bitmaps(live_runs, preds, stats)
             if backend == "fused" else {})
    for i, s in enumerate(live_runs):
        if s.codec != "opd":
            vals = s.values if s.codec == "plain" else decoded[i]
            base = ~s.tombs
            masks = [string_mask(vals, p) & base for p in preds]
        elif i not in fused:
            # K x O(log D) planning on the dictionary, then ONE column
            # pass evaluating every planned code range
            with stats.time("plan"):
                ranges = [s.opd.code_range(p) for p in preds]
            masks = _code_masks_many(s, ranges, backend)
        with stats.time("expand"):
            if i in fused:
                masks = _expand_bitmaps(s, fused[i], len(preds))
            if snap is not None:
                visible = s.seqnos <= snap
                masks = [m & visible for m in masks]
        yield i, s, masks


def _expand_bitmaps(s: SCT, bitmaps: Optional[np.ndarray],
                    n_preds: int) -> List[np.ndarray]:
    """K per-entry masks of one run from its fused-filter bitmaps
    (None: its level had nothing to evaluate).  Tombstones pack as 0,
    so they are masked out of the bitmap here."""
    if bitmaps is None:
        return [np.zeros(s.n, np.bool_) for _ in range(n_preds)]
    from repro.kernels import ops as kops

    live = ~s.tombs
    return [kops.bitmap_to_mask(bitmaps[k], s.code_bits, s.n) & live
            for k in range(n_preds)]


def _fused_level_bitmaps(
    live_runs: List[SCT], preds: Sequence[Predicate], stats: StageStats,
) -> dict:
    """The 'fused' backend: plan + evaluate every 'opd' run through the
    zone-mapped megakernel, ONE launch per level.

    Runs are grouped by ``(level, pack_width)`` — the pack width is a
    static kernel parameter, and within a level it is uniform in
    practice (the level was written by one flush/compaction policy).
    Each run contributes its own K planned [lo, hi] ranges to the
    group's concatenated range table, so runs with *different
    dictionaries* still share the launch.  Per-block code zones from
    ``BlockIndex`` gate each tile in-kernel, through tile meta built
    once per run (``query.planner.run_tile_meta``); pruning telemetry
    lands in ``stats.counts`` (``fused_launches``, ``zone_tiles_*``,
    ``zone_blocks_*``, ``tile_meta_*``) for the bench reports, the
    launch's stages and byte counters in ``stats``
    (``kernels.ops.fused_level_filter``).

    Returns {run index -> uint32 [K, n_words] bitmaps, or None when no
    predicate can match anywhere in its level}; ``_expand_bitmaps``
    turns them into masks bit-identical to the 'jax_packed'/'numpy'
    backends.
    """
    from repro.kernels import ops as kops
    from repro.kernels.fused_scan import DEFAULT_BLOCK_ROWS
    from repro.query import planner

    groups: dict = {}
    for i, s in enumerate(live_runs):
        if s.codec == "opd":
            groups.setdefault((s.level, s.code_bits), []).append(i)
    out: dict = {}
    for (_level, width), idxs in sorted(groups.items()):
        ranges_list = []
        with stats.time("plan"):
            for i in idxs:
                s = live_runs[i]
                rr = [s.opd.code_range(p) for p in preds]
                # inclusive [lo, hi-1]; lo > hi encodes empty in-kernel
                ranges_list.append(np.asarray(
                    [(lo, hi - 1) if lo < hi else (1, 0) for lo, hi in rr],
                    np.uint32))
        if all((r[:, 0] > r[:, 1]).all() for r in ranges_list):
            # no predicate can match anywhere in this level: skip the
            # launch entirely (keeps fused_launches honest)
            out.update((i, None) for i in idxs)
            continue
        runs = [live_runs[i] for i in idxs]
        bitmaps, info = kops.fused_level_filter(
            [s.packed for s in runs], [s.n for s in runs], ranges_list,
            [planner.run_zones(s) for s in runs], width,
            block_rows=DEFAULT_BLOCK_ROWS, stats=stats,
            metas_list=[planner.run_tile_meta(s, DEFAULT_BLOCK_ROWS, stats)
                        for s in runs])
        stats.counts["fused_launches"] += 1
        for k in ("tiles_total", "tiles_skipped", "blocks_total",
                  "blocks_skipped", "blocks_prunable"):
            stats.counts[f"zone_{k}"] += info[k]
        out.update(zip(idxs, bitmaps))
    return out


def _read_blob_values(s: SCT, blob_mgr: BlobManager) -> np.ndarray:
    """BlobDB filter path: random value addressing per entry (paper §5.3)."""
    out = np.zeros(s.n, f"S{s.value_width}")
    live = s.vfids >= 0
    for fid in np.unique(s.vfids[live]):
        sel = live & (s.vfids == fid)
        out[sel] = blob_mgr.read_values(int(fid), s.vptrs[sel], random_io=True)
    return out


def _memtable_visible(mems: List[MemTable], snap,
                      value_width: Optional[int] = None) -> Tuple:
    """Newest visible live (key, seqno, value) triples across the
    memtable stack — one locked columnar pass per memtable, predicates
    mask after.  Rows a newer memtable shadows are included; the seqno
    merge downstream discards them."""
    parts = [m.newest_rows(None if snap is None else int(snap))
             for m in mems if m.n_versions]
    parts = [(k[~t], s[~t], v[~t]) for k, s, t, v in parts]
    parts = [p for p in parts if p[0].shape[0]]
    w = value_width if value_width is not None else (
        mems[0].value_width if mems else 8)
    if not parts:
        return (np.zeros(0, np.uint64), np.zeros(0, np.uint64),
                np.zeros(0, f"S{w}"))
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]),
            np.concatenate([p[2] for p in parts]))


def _memtable_newest(
    mems: List[MemTable], snap
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Newest visible seqno per key across the memtable stack,
    *including tombstones* (a newer tombstone shadows older candidates),
    as key-sorted arrays so the shadow check is one ``searchsorted`` per
    predicate instead of a per-candidate chain probe."""
    max_seq = None if snap is None else int(snap)
    parts = [m.newest_rows(max_seq)[:2] for m in mems if m.n_versions]
    parts = [p for p in parts if p[0].shape[0]]
    if not parts:
        return None
    mk = np.concatenate([p[0] for p in parts])
    ms = np.concatenate([p[1] for p in parts])
    # newest per key across memtables: sort by (key, seqno) and keep the
    # last row of each key group (the max seqno)
    order = np.lexsort((ms, mk))
    mk, ms = mk[order], ms[order]
    last = np.ones(mk.shape[0], np.bool_)
    last[:-1] = mk[1:] != mk[:-1]
    return mk[last], ms[last]


def _global_newest(
    cand_keys: np.ndarray, runs: List[SCT],
    mem_newest: Optional[Tuple[np.ndarray, np.ndarray]], snap
) -> np.ndarray:
    """Newest visible seqno per candidate key across all runs + memtable.

    §Perf engine hillclimb change 2: runs pinned by an engine snapshot
    were flushed *before* the snapshot, so every stored seqno <= snap
    (cached per-SCT ``max_seqno``).  The per-candidate Python correction
    loop is therefore only needed for exotic externally-built snapshots;
    the common path is one vectorized searchsorted per run."""
    newest = np.zeros(cand_keys.shape[0], np.uint64)
    for s in runs:
        pos = np.searchsorted(s.keys, cand_keys, side="left")
        inb = pos < s.n
        hit = inb & (s.keys[np.minimum(pos, s.n - 1)] == cand_keys)
        if snap is None or np.uint64(s.max_seqno) <= snap:
            seq = np.where(hit, s.seqnos[np.minimum(pos, s.n - 1)], 0)
        else:
            seq = np.zeros(cand_keys.shape[0], np.uint64)
            for j in np.nonzero(hit)[0]:
                p = pos[j]
                while p < s.n and s.keys[p] == cand_keys[j] and s.seqnos[p] > snap:
                    p += 1
                if p < s.n and s.keys[p] == cand_keys[j]:
                    seq[j] = s.seqnos[p]
        newest = np.maximum(newest, seq)
    if mem_newest is not None:
        mk, ms = mem_newest
        pos = np.minimum(np.searchsorted(mk, cand_keys), mk.shape[0] - 1)
        hit = mk[pos] == cand_keys
        newest = np.maximum(newest, np.where(hit, ms[pos], 0))
    return newest
