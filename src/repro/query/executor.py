"""Aggregate execution: direct computing on packed codes with an MVCC
fallback — the aggregation analogue of ``filter_exec``.

Two paths, chosen PER SNAPSHOT by ``planner.fastpath_eligible``:

**Fast path** (all runs 'opd', disjoint key ranges, unique keys per
run, nothing visible in the memtable, snapshot covers every stored
seqno — i.e. a compacted, quiescent tree): every stored row is the
newest visible version of its key, so per-run partials simply add up.
Aggregates are computed *in the code domain*, per run:

* backend 'fused' / 'jax_packed' -> ONE ``kernels.ops.fused_level_agg``
  launch per (level, pack-width) group for the scalar specs and one
  ``level_histogram`` launch for each GROUP BY — zone-contained tiles
  contribute closed forms without their words ever being read;
* backend 'numpy' / 'jax' -> the same zone short-circuit evaluated
  host-side at 4 KB-block granularity (a block whose zone a range
  contains contributes its entry count / exact zone bounds closed-form;
  only zone-crossing blocks touch the code column).

MIN/MAX stay codes until the very end: one dictionary decode per run
turns the per-run extreme code into a value, and runs merge in value
space (codes from different dictionaries never compare).  SUM gathers
``numeric_values`` weights per CODE (table built once per dictionary);
GROUP BY folds a per-code histogram through the dictionary's
prefix-label table or the globally resolved bucket edges.  A run with
tombstones is only kernel-eligible when every planned bound keeps code
0 out (tombstones pack as 0); otherwise it drops to the host-masked
evaluation, which sees the -1 sentinels.

**General path** (any codec mix, visible memtable deltas, overlapping
runs, in-flight snapshots): reuses ``filter_exec``'s one-pass candidate
/ visibility machinery — per-run masks for every spec in one column
pass, lexsort dedup, global shadow check — but candidates carry
``(source run, code)`` instead of decoded values; only non-OPD sources
(plain/heavy/blob runs, memtable rows) carry raw values.  Surviving
candidates aggregate per source exactly as above, so the general path
still never decodes a value for an order-preserving aggregate beyond
the <= 2 min/max codes per run.

StageStats contract (counters for the bench / roofline telemetry):
``agg_tiles_{total,skipped,evaluated,shortcircuit}`` (unit: kernel tile
on the fused path, (block x spec) on the host fast path),
``agg_histograms_gathered``, ``agg_codes_decoded``,
``agg_fastpath_runs`` / ``agg_fallback_runs``, ``agg_launches``; on
kernel launches ``h2d_bytes`` / ``d2h_bytes`` and the ``ops.*`` stages
(``kernels.ops``); on the general path ``gathered_rows`` and the
``filter`` sub-stages of ``filter_exec``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.filter_exec import (_global_newest, _memtable_newest,
                                    _memtable_visible, _read_blob_values,
                                    _run_masks, string_mask)
from repro.core.memtable import MemTables, as_mems
from repro.core.opd import Predicate
from repro.core.sct import SCT, BlobManager
from repro.core.stats import StageStats
from repro.query import planner
from repro.query.spec import (AggPartial, AggSpec, bucket_ids,
                              numeric_values, prefix_labels)
from repro.storage.io import FileStore

INT32_MAX = 2**31 - 1


def evaluate_aggregates(
    runs: List[SCT],
    memtable: MemTables,
    specs: Sequence[AggSpec],
    *,
    stats: StageStats,
    store: FileStore,
    blob_mgr: Optional[BlobManager] = None,
    snapshot_seqno: Optional[int] = None,
    backend: str = "numpy",  # 'numpy' | 'jax' | 'jax_packed' | 'fused'
    value_width: Optional[int] = None,
    block_rows: int = 8,
) -> List[AggPartial]:
    """Evaluate K aggregate specs against one snapshot's runs + memtables.

    Returns one mergeable ``AggPartial`` per spec (the caller finalizes
    — across shards, AFTER merging).  'bucket' groups must arrive
    resolved (``planner.resolve_specs``); the engine entry points handle
    that.
    """
    specs = list(specs)
    if not specs:
        return []
    for spec in specs:
        assert spec.group is None or spec.group.resolved(), \
            "bucket GroupBy must be resolved before execution"
    mems = as_mems(memtable)
    snap = np.uint64(snapshot_seqno) if snapshot_seqno is not None else None
    stats.counts["agg_specs"] += len(specs)

    with stats.time("plan"):
        live_runs = [s for s in runs if s.n > 0]
        mem_newest = _memtable_newest(mems, snap)
        fast, _why = planner.fastpath_eligible(live_runs, mem_newest, snap)

    with stats.time("read"):
        for s in live_runs:
            store.stats.add_read(s.disk_bytes, 1)

    if fast:
        stats.counts["agg_fastpath_runs"] += len(live_runs)
        with stats.time("aggregate"):
            return _fastpath_aggregate(live_runs, specs, stats, backend,
                                       block_rows)
    stats.counts["agg_fallback_runs"] += len(live_runs)
    return _general_aggregate(live_runs, mems, mem_newest, specs, stats,
                              blob_mgr, snap, backend, value_width)


# =========================================================================== #
# fast path: per-run partials in the code domain, no visibility merge
# =========================================================================== #
def _fastpath_aggregate(live_runs, specs, stats, backend, block_rows):
    K = len(specs)
    partials = [AggPartial() for _ in range(K)]
    scalar_q = [q for q in range(K) if specs[q].op != "group_count"]
    group_q = [q for q in range(K) if specs[q].op == "group_count"]
    use_kernel = backend in ("fused", "jax_packed")

    # half-open planned window per (run, spec)
    windows = [[s.opd.code_range(spec.plan_pred()) for spec in specs]
               for s in live_runs]

    if scalar_q:
        with_sum = any(specs[q].op == "sum" for q in scalar_q)
        kernel_runs, host_runs = [], []
        for i, s in enumerate(live_runs):
            ok = use_kernel and s.packed is not None
            if ok and planner.run_has_tombs(s):
                # tombstones pack as 0: the kernel may only see this run
                # if every non-empty planned range excludes code 0
                ok = all(lo >= 1 or lo >= hi
                         for q in scalar_q
                         for lo, hi in [windows[i][q]])
            if ok and with_sum:
                # int32 per-tile accumulation guard
                tile_entries = block_rows * 128 * (32 // s.code_bits)
                wmax = int(np.abs(planner.run_weights(s)).max(initial=0))
                ok = wmax * tile_entries < INT32_MAX
            (kernel_runs if ok else host_runs).append(i)
        if kernel_runs:
            _kernel_scalars(live_runs, kernel_runs, windows, specs, scalar_q,
                            with_sum, partials, stats, block_rows)
        for i in host_runs:
            _host_scalars(live_runs[i], windows[i], specs, scalar_q,
                          partials, stats)

    for q in group_q:
        _fastpath_group(live_runs, windows, specs[q], q, partials, stats,
                        use_kernel, block_rows)
    return partials


def _decode_one(s: SCT, code: int, stats) -> bytes:
    stats.counts["agg_codes_decoded"] += 1
    return bytes(s.opd.values[int(code)])


def _fold_scalar(partials, specs, scalar_q, s, counts, min_codes, max_codes,
                 sums, stats):
    """Fold one run's per-spec code-domain partials into the value-domain
    AggPartials (the <= 2 decodes per run happen here)."""
    for k, q in enumerate(scalar_q):
        c = int(counts[k])
        if c == 0:
            continue
        p = partials[q]
        p.count += c
        op = specs[q].op
        if op == "sum":
            p.total += int(sums[k])
        if op in ("min", "max") and min_codes[k] >= 0:
            mn = _decode_one(s, min_codes[k], stats)
            mx = _decode_one(s, max_codes[k], stats)
            if p.min_value is None or mn < p.min_value:
                p.min_value = mn
            if p.max_value is None or mx > p.max_value:
                p.max_value = mx


def _kernel_scalars(live_runs, idxs, windows, specs, scalar_q, with_sum,
                    partials, stats, block_rows):
    """Scalar specs through ``fused_level_agg``, one launch per
    (level, pack-width) group — mirrors ``_fused_level_bitmaps``."""
    from repro.kernels import ops as kops

    groups: Dict[Tuple[int, int], List[int]] = {}
    for i in idxs:
        s = live_runs[i]
        groups.setdefault((s.level, s.code_bits), []).append(i)
    for (_level, width), members in sorted(groups.items()):
        ranges_list = [
            np.asarray([(lo, hi - 1) if lo < hi else (1, 0)
                        for q in scalar_q
                        for lo, hi in [windows[i][q]]], np.uint32)
            for i in members]
        runs = [live_runs[i] for i in members]
        weights_list = ([planner.run_weights(s) for s in runs]
                        if with_sum else None)
        per_sct, info = kops.fused_level_agg(
            [s.packed for s in runs], [s.n for s in runs], ranges_list,
            [planner.run_zones(s) for s in runs], width,
            weights_list=weights_list, block_rows=block_rows, stats=stats,
            metas_list=[planner.run_tile_meta(s, block_rows, stats)
                        for s in runs])
        stats.counts["agg_launches"] += 1
        for key in ("tiles_total", "tiles_skipped", "tiles_evaluated",
                    "tiles_shortcircuit"):
            stats.counts[f"agg_{key}"] += info[key]
        for j, i in enumerate(members):
            r = per_sct[j]
            _fold_scalar(partials, specs, scalar_q, live_runs[i],
                         r["counts"], r["min_code"], r["max_code"],
                         r["sums"], stats)


def _host_scalars(s, windows, specs, scalar_q, partials, stats):
    """Host fast path: the kernel's zone short-circuit at 4 KB-block
    granularity (block zones are EXACT per block, so closed-form min/max
    bounds are attained), falling back to masked evaluation of the
    zone-crossing blocks only."""
    K = len(scalar_q)
    counts = np.zeros(K, np.int64)
    sums = np.zeros(K, np.int64)
    min_codes = np.full(K, -1, np.int64)
    max_codes = np.full(K, -1, np.int64)
    zones = planner.run_zones(s)
    evs = None
    for k, q in enumerate(scalar_q):
        lo, hi = windows[q]
        if lo >= hi:
            continue
        lo_i, hi_i = lo, hi - 1  # inclusive
        need_sum = specs[q].op == "sum"
        if zones is None:
            evs = s.evs if evs is None else evs
            m = (evs >= lo_i) & (evs <= hi_i)
            stats.counts["agg_tiles_total"] += 1
            stats.counts["agg_tiles_evaluated"] += 1
            _host_tally(s, evs, m, k, counts, sums, min_codes, max_codes,
                        need_sum)
            continue
        code_lo, code_hi, epb = zones[0], zones[1], zones[2]
        wsums = zones[3] if len(zones) > 3 else None
        nb = code_lo.shape[0]
        ends = np.minimum((np.arange(nb) + 1) * epb, s.n)
        starts = np.arange(nb) * epb
        inter = (code_lo.astype(np.int64) <= hi_i) & \
            (code_hi.astype(np.int64) >= lo_i)
        closed = inter & (lo_i <= code_lo.astype(np.int64)) & \
            (code_hi.astype(np.int64) <= hi_i) & (code_lo >= 1)
        if need_sum and wsums is None:
            # SUM's closed form needs the per-block weight totals
            closed = np.zeros(nb, bool)
        evaluate = inter & ~closed
        stats.counts["agg_tiles_total"] += nb
        stats.counts["agg_tiles_skipped"] += int((~inter).sum())
        stats.counts["agg_tiles_shortcircuit"] += int(closed.sum())
        stats.counts["agg_tiles_evaluated"] += int(evaluate.sum())
        if closed.any():
            counts[k] += int((ends[closed] - starts[closed]).sum())
            min_codes[k] = int(code_lo[closed].min())
            max_codes[k] = int(code_hi[closed].max())
            if need_sum:
                # containment makes every live entry a match, and
                # code_lo >= 1 rules out tombstones — the block weight
                # total IS the blocks' exact SUM contribution
                sums[k] += int(wsums[closed].sum())
        if evaluate.any():
            evs = s.evs if evs is None else evs
            m = np.zeros(s.n, bool)
            for b in np.nonzero(evaluate)[0]:
                c = evs[starts[b]:ends[b]]
                m[starts[b]:ends[b]] = (c >= lo_i) & (c <= hi_i)
            _host_tally(s, evs, m, k, counts, sums, min_codes, max_codes,
                        need_sum)
    _fold_scalar(partials, specs, scalar_q, s, counts, min_codes, max_codes,
                 sums, stats)


def _host_tally(s, evs, m, k, counts, sums, min_codes, max_codes, need_sum):
    c = int(m.sum())
    if c == 0:
        return
    counts[k] += c
    sel = evs[m]
    mn, mx = int(sel.min()), int(sel.max())
    min_codes[k] = mn if min_codes[k] < 0 else min(min_codes[k], mn)
    max_codes[k] = max(max_codes[k], mx)
    if need_sum:
        sums[k] += int(planner.run_weights(s)[sel].sum(dtype=np.int64))


def _fastpath_group(live_runs, windows, spec, q, partials, stats,
                    use_kernel, block_rows):
    """GROUP BY on the fast path: per-run code histogram folded through
    the dictionary's label table / resolved bucket edges."""
    from repro.kernels import agg_scan as _agg

    partials[q].groups = {}
    plans = []  # (i, edges u32 [B+1], labels)
    for i, s in enumerate(live_runs):
        lo, hi = windows[i][q]
        if lo >= hi:
            continue
        edges, labels = planner.group_code_edges(s, spec.group, lo, hi)
        plans.append((i, edges, labels))
    kernel_ok = use_kernel and plans and \
        max(len(e) - 1 for _, e, _ in plans) <= _agg.MAX_BINS and \
        all(live_runs[i].packed is not None and
            (not planner.run_has_tombs(live_runs[i]) or e[0] >= 1)
            for i, e, _ in plans)
    if kernel_ok:
        from repro.kernels import ops as kops

        groups: Dict[Tuple[int, int], List[int]] = {}
        by_run = {i: (e, lab) for i, e, lab in plans}
        for i, _, _ in plans:
            s = live_runs[i]
            groups.setdefault((s.level, s.code_bits), []).append(i)
        for (_level, width), members in sorted(groups.items()):
            runs = [live_runs[i] for i in members]
            hists, info = kops.level_histogram(
                [s.packed for s in runs], [s.n for s in runs],
                [by_run[i][0] for i in members],
                [planner.run_zones(s) for s in runs], width,
                block_rows=block_rows, stats=stats,
                metas_list=[planner.run_tile_meta(s, block_rows, stats)
                            for s in runs])
            stats.counts["agg_launches"] += 1
            for key in ("tiles_total", "tiles_skipped", "tiles_evaluated",
                        "tiles_shortcircuit"):
                stats.counts[f"agg_{key}"] += info[key]
            for j, i in enumerate(members):
                stats.counts["agg_histograms_gathered"] += 1
                _fold_hist(partials[q], hists[j], by_run[i][1])
        return
    for i, edges, labels in plans:
        s = live_runs[i]
        evs = s.evs
        cnt = np.bincount(evs[evs >= 0], minlength=s.opd.size)
        cum = np.concatenate([[0], np.cumsum(cnt)])
        hist = cum[edges[1:].astype(np.int64)] - cum[edges[:-1].astype(np.int64)]
        stats.counts["agg_histograms_gathered"] += 1
        stats.counts["agg_tiles_total"] += 1
        stats.counts["agg_tiles_evaluated"] += 1
        _fold_hist(partials[q], hist, labels)


def _fold_hist(partial, hist, labels):
    got = np.nonzero(np.asarray(hist) > 0)[0]
    partial.add_group_counts([labels[b] for b in got],
                             [int(hist[b]) for b in got])


# =========================================================================== #
# general path: filter_exec's candidate/visibility machinery, codes carried
# =========================================================================== #
def _general_aggregate(live_runs, mems, mem_newest, specs, stats, blob_mgr,
                       snap, backend, value_width):
    K = len(specs)
    preds = [spec.plan_pred() for spec in specs]

    decoded: List[Optional[np.ndarray]] = [None] * len(live_runs)
    with stats.time("decode"):
        for i, s in enumerate(live_runs):
            if s.codec == "heavy":
                decoded[i] = s._decompress_all()[2]
            elif s.codec == "blob":
                decoded[i] = _read_blob_values(s, blob_mgr)

    # per-spec candidate columns; srcs >= 0 index live_runs and pair with
    # CODES, srcs == -1 pairs with an index into the spec's `others` pool
    cand = [{"keys": [], "seqs": [], "srcs": [], "codes": []}
            for _ in range(K)]
    others: List[List[np.ndarray]] = [[] for _ in range(K)]
    other_n = [0] * K

    def _push(q, keys, seqs, src, codes=None, vals=None):
        cand[q]["keys"].append(keys)
        cand[q]["seqs"].append(seqs)
        if src >= 0:
            cand[q]["srcs"].append(np.full(keys.shape[0], src, np.int64))
            cand[q]["codes"].append(codes.astype(np.int64))
        else:
            cand[q]["srcs"].append(np.full(keys.shape[0], -1, np.int64))
            cand[q]["codes"].append(
                np.arange(other_n[q], other_n[q] + keys.shape[0], dtype=np.int64))
            others[q].append(vals)
            other_n[q] += keys.shape[0]

    with stats.time("filter"):
        for i, s, masks in _run_masks(live_runs, preds, decoded, stats,
                                      backend, snap):
            with stats.time("gather"):
                for q in range(K):
                    idx = np.nonzero(masks[q])[0]
                    if idx.shape[0] == 0:
                        continue
                    stats.counts["gathered_rows"] += idx.shape[0]
                    if s.codec == "opd":
                        _push(q, s.keys[idx], s.seqnos[idx], i,
                              codes=s.evs[idx])
                    else:
                        vals = s.values if s.codec == "plain" else decoded[i]
                        _push(q, s.keys[idx], s.seqnos[idx], -1,
                              vals=vals[idx])
        with stats.time("memtable"):
            mk, ms, mv = _memtable_visible(mems, snap, value_width)
            if mk.shape[0]:
                for q, p in enumerate(preds):
                    m = string_mask(mv, p)
                    if m.any():
                        _push(q, mk[m], ms[m], -1, vals=mv[m])

    partials = []
    for q in range(K):
        with stats.time("merge"):
            srcs, codes, vals = _merge_agg_candidates(
                cand[q], others[q], live_runs, mem_newest, snap, value_width)
        with stats.time("aggregate"):
            partials.append(_aggregate_candidates(
                specs[q], live_runs, srcs, codes, vals, stats))
    return partials


def _merge_agg_candidates(c, others, live_runs, mem_newest, snap,
                          value_width):
    """Newest-visible dedup + global shadow check (same discipline as
    ``filter_exec._merge_candidates``) carrying (src, code) payloads."""
    w = value_width if value_width is not None else (
        live_runs[0].value_width if live_runs else 8)
    if not c["keys"]:
        return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                np.zeros(0, f"S{w}"))
    keys = np.concatenate(c["keys"])
    seqs = np.concatenate(c["seqs"])
    srcs = np.concatenate(c["srcs"])
    codes = np.concatenate(c["codes"])
    order = np.lexsort((np.uint64(0xFFFFFFFFFFFFFFFF) - seqs, keys))
    keys, seqs = keys[order], seqs[order]
    srcs, codes = srcs[order], codes[order]
    first = np.ones(keys.shape[0], np.bool_)
    first[1:] = keys[1:] != keys[:-1]
    keys, seqs = keys[first], seqs[first]
    srcs, codes = srcs[first], codes[first]
    ok = seqs == _global_newest(keys, live_runs, mem_newest, snap)
    srcs, codes = srcs[ok], codes[ok]
    pool = np.concatenate(others) if others else np.zeros(0, f"S{w}")
    is_val = srcs < 0
    vals = pool[codes[is_val]] if is_val.any() else np.zeros(0, pool.dtype)
    return srcs, codes, vals


def _aggregate_candidates(spec, live_runs, srcs, codes, vals, stats):
    """Per-source aggregation of the surviving candidates — codes stay
    codes (order-preserving ops) until the per-run decode of the fold."""
    p = AggPartial()
    if spec.op == "group_count":
        p.groups = {}
    n = srcs.shape[0]
    if n == 0:
        return p
    if spec.op in ("count",):
        p.count = n
        return p
    is_val = srcs < 0
    run_ids = np.unique(srcs[~is_val])
    if spec.op in ("min", "max"):
        p.count = n
        for r in run_ids:
            s = live_runs[int(r)]
            sel = codes[srcs == r]
            mn = _decode_one(s, int(sel.min()), stats)
            mx = _decode_one(s, int(sel.max()), stats)
            if p.min_value is None or mn < p.min_value:
                p.min_value = mn
            if p.max_value is None or mx > p.max_value:
                p.max_value = mx
        if vals.shape[0]:
            sv = np.sort(vals)  # S-dtype has no min/max ufunc
            mn, mx = bytes(sv[0]), bytes(sv[-1])
            if p.min_value is None or mn < p.min_value:
                p.min_value = mn
            if p.max_value is None or mx > p.max_value:
                p.max_value = mx
        return p
    if spec.op == "sum":
        p.count = n
        for r in run_ids:
            s = live_runs[int(r)]
            hist = np.bincount(codes[srcs == r], minlength=s.opd.size)
            stats.counts["agg_histograms_gathered"] += 1
            p.total += int((hist * planner.run_weights(s).astype(np.int64))
                           .sum(dtype=np.int64))
        if vals.shape[0]:
            p.total += int(numeric_values(vals).sum(dtype=np.int64))
        return p
    # group_count
    g = spec.group
    for r in run_ids:
        s = live_runs[int(r)]
        sel = codes[srcs == r]
        hist = np.bincount(sel, minlength=s.opd.size)
        stats.counts["agg_histograms_gathered"] += 1
        if g.kind == "prefix":
            labels_all = planner.run_prefix_table(s, g.prefix_len)
            got = np.nonzero(hist)[0]
            labs, inv = np.unique(labels_all[got], return_inverse=True)
            counts = np.zeros(labs.shape[0], np.int64)
            np.add.at(counts, inv, hist[got])
            p.add_group_counts([bytes(x) for x in labs], counts)
        else:
            edges, labels = planner.group_code_edges(s, g, 0, s.opd.size)
            cum = np.concatenate([[0], np.cumsum(hist)])
            gh = cum[edges[1:].astype(np.int64)] - \
                cum[edges[:-1].astype(np.int64)]
            _fold_hist(p, gh, labels)
    if vals.shape[0]:
        if g.kind == "prefix":
            labs, counts = np.unique(prefix_labels(vals, g.prefix_len),
                                     return_counts=True)
            p.add_group_counts([bytes(x) for x in labs], counts)
        else:
            ids = bucket_ids(vals, g.edges or ())
            got, counts = np.unique(ids, return_counts=True)
            p.add_group_counts([g.bucket_label(int(b)) for b in got], counts)
    return p
