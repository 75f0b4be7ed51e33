"""Aggregate planning: predicate -> code ranges, grouping keys -> code
edges, bucket-edge resolution, and the fast-path eligibility check.

Planning reuses the filter pipeline's contract (``OPD.code_range`` /
``string_mask`` agree on every predicate, including truncation edge
cases), then adds the aggregation-specific pieces:

* ``resolve_specs`` pins 'bucket' group edges to concrete value-domain
  boundaries (equi-depth over the observed sorted-unique domain).  The
  caller controls the collection scope — ``ShardedLSM`` resolves ONCE
  over every shard's domain so per-shard partials share labels and
  merge exactly.
* ``group_code_edges`` maps a resolved grouping onto ONE dictionary's
  code space as B+1 ascending edges (prefix groups are intervals of any
  sorted dictionary; bucket edges are two binary searches each),
  clipped to the spec's planned code window so the histogram kernel
  counts filter+group in one pass.
* ``fastpath_eligible`` decides whether a snapshot can be aggregated
  without the candidate/visibility merge: every live run 'opd',
  pairwise-disjoint key ranges, unique keys per run, no visible
  memtable rows (a memtable tombstone shadows run rows, so ANY visible
  memtable state forces the general path), and no stored seqno above
  the snapshot.  Under those invariants every stored row is the newest
  visible version of its key, so per-run partials add up without dedup.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core.filter_exec import _read_blob_values
from repro.core.opd import OPD
from repro.core.sct import SCT
from repro.query.spec import AggSpec, GroupBy, prefix_labels


# --------------------------------------------------------------------------- #
# per-SCT cached facts (setattr-cached: SCTs are immutable after build)
# --------------------------------------------------------------------------- #
def run_has_tombs(s: SCT) -> bool:
    v = getattr(s, "_q_has_tombs", None)
    if v is None:
        v = bool(s.tombs.any())
        s._q_has_tombs = v
    return v


def run_keys_unique(s: SCT) -> bool:
    v = getattr(s, "_q_keys_unique", None)
    if v is None:
        v = bool(np.all(s.keys[1:] != s.keys[:-1]))
        s._q_keys_unique = v
    return v


def run_weights(s: SCT) -> np.ndarray:
    """int32 numeric weight per dictionary code (SUM's gather table) —
    computed once per dictionary (D_i work), never per row."""
    v = getattr(s, "_q_weights", None)
    if v is None:
        from repro.query.spec import numeric_values

        v = numeric_values(s.opd.values).astype(np.int32)
        s._q_weights = v
    return v


def run_zones(s: SCT):
    """(code_lo, code_hi, entries_per_block, weight_sums) of the run's
    block index, or None without a zone map; the last entry is the
    per-block SUM weight total (None on SCTs built before it existed).
    Tile builders index positionally, so 3-tuples keep working."""
    b = s.blocks
    if b is None or not b.has_zones:
        return None
    return (b.code_lo, b.code_hi, b.entries_per_block,
            getattr(b, "weight_sums", None))


def run_tile_meta(s: SCT, block_rows: int, stats) -> np.ndarray:
    """The run's per-tile kernel meta (``kernels.ops.tile_meta``: zones,
    n_valid, SUM weight totals), built once per (pack width,
    block_rows).  Counts ``tile_meta_builds`` / ``tile_meta_hits`` in
    ``stats.counts``."""
    cache = getattr(s, "_q_tile_meta", None)
    if cache is None:
        cache = {}
        s._q_tile_meta = cache
    key = (s.code_bits, block_rows)
    meta = cache.get(key)
    if meta is None:  # two threads that miss at once build the same meta
        from repro.kernels import ops as kops

        meta = kops.tile_meta(s.packed, s.n, run_zones(s), s.code_bits,
                              block_rows, run_weights(s))
        cache[key] = meta
        stats.counts["tile_meta_builds"] += 1
    else:
        stats.counts["tile_meta_hits"] += 1
    return meta


def run_prefix_table(s: SCT, prefix_len: int) -> np.ndarray:
    """S<prefix_len> label per dictionary code (group labels are one
    gather away from a code histogram)."""
    tabs = getattr(s, "_q_prefix_tables", None)
    if tabs is None:
        tabs = {}
        s._q_prefix_tables = tabs
    if prefix_len not in tabs:
        tabs[prefix_len] = prefix_labels(s.opd.values, prefix_len)
    return tabs[prefix_len]


# --------------------------------------------------------------------------- #
# bucket-edge resolution
# --------------------------------------------------------------------------- #
def source_domain(s: SCT, blob_mgr) -> np.ndarray:
    """Sorted unique live values of one run (the OPD dictionary IS that
    set; competitors compute it the hard way)."""
    if s.codec == "opd":
        return s.opd.values
    if s.codec == "plain":
        vals = s.values
    elif s.codec == "heavy":
        vals = s._decompress_all()[2]
    else:
        vals = _read_blob_values(s, blob_mgr)
    return np.unique(vals[~s.tombs])


def collect_domain(runs: Sequence[SCT], mems, blob_mgr,
                   value_width: int) -> np.ndarray:
    """Observed value domain of a snapshot (runs + memtable stack)."""
    parts = [source_domain(s, blob_mgr) for s in runs if s.n > 0]
    for m in mems or []:
        if m.n_versions:
            k, sq, t, v = m.newest_rows(None)
            if v.shape[0]:
                parts.append(np.unique(v[~t]))
    if not parts:
        return np.zeros(0, f"S{value_width}")
    return np.unique(np.concatenate(parts))


def bucket_edges_from_domain(domain: np.ndarray,
                             n_buckets: int) -> Tuple[bytes, ...]:
    """Equi-depth interior edges: n_buckets-1 cut values from the sorted
    unique domain (deterministic given the domain; duplicate cuts are
    dropped, yielding fewer, still-exact buckets)."""
    d = domain.shape[0]
    if d == 0 or n_buckets <= 1:
        return ()
    idx = np.unique((np.arange(1, n_buckets) * d) // n_buckets)
    idx = idx[(idx > 0) & (idx < d)]
    return tuple(bytes(v) for v in np.unique(domain[idx]))


def resolve_specs(specs: Sequence[AggSpec],
                  domain: np.ndarray) -> List[AggSpec]:
    """Pin every unresolved 'bucket' GroupBy to concrete edges."""
    out = []
    for spec in specs:
        g = spec.group
        if g is not None and not g.resolved():
            g = GroupBy(g.kind, g.prefix_len, g.n_buckets,
                        bucket_edges_from_domain(domain, g.n_buckets))
            spec = AggSpec(spec.op, spec.pred, g, spec.top_k)
        out.append(spec)
    return out


# --------------------------------------------------------------------------- #
# code-space planning against one dictionary
# --------------------------------------------------------------------------- #
def plan_ranges(s: SCT, specs: Sequence[AggSpec]) -> np.ndarray:
    """uint32 [K, 2] inclusive planned code ranges (lo > hi = empty) —
    the same encoding ``filter_exec`` hands the packed kernels."""
    rr = [s.opd.code_range(spec.plan_pred()) for spec in specs]
    return np.asarray([(lo, hi - 1) if lo < hi else (1, 0) for lo, hi in rr],
                      np.uint32)


def group_code_edges(
    s: SCT, group: GroupBy, lo: int, hi: int,
) -> Tuple[np.ndarray, List[bytes]]:
    """B+1 ascending code edges + B labels for one dictionary, clipped
    to the planned half-open code window [lo, hi).

    Clipping folds the filter into the histogram: bins outside the
    window collapse to empty ([e, e)), codes outside it fall below
    edge 0 or at/above the last edge — so the histogram of the clipped
    edges IS the filtered group count.
    """
    opd: OPD = s.opd
    D = opd.size
    if group.kind == "prefix":
        labels_all = run_prefix_table(s, group.prefix_len)
        starts = np.concatenate(
            [[0], np.nonzero(labels_all[1:] != labels_all[:-1])[0] + 1]) \
            if D else np.zeros(0, np.int64)
        edges = np.concatenate([starts, [D]]).astype(np.int64)
        labels = [bytes(v) for v in labels_all[starts.astype(np.int64)]]
    else:
        w = opd.values.dtype.itemsize
        interior = np.asarray(list(group.edges or ()), f"S{w}")
        cuts = np.searchsorted(opd.values, interior, side="left")
        edges = np.concatenate([[0], cuts, [D]]).astype(np.int64)
        labels = [group.bucket_label(b) for b in range(len(edges) - 1)]
    edges = np.clip(edges, lo, hi)
    return edges.astype(np.uint32), labels


# --------------------------------------------------------------------------- #
# fast-path eligibility
# --------------------------------------------------------------------------- #
def fastpath_eligible(live_runs: Sequence[SCT], mem_newest,
                      snap) -> Tuple[bool, str]:
    """Can per-run partials be summed without the visibility merge?"""
    if mem_newest is not None:
        return False, "memtable"
    for s in live_runs:
        if s.codec != "opd" or s.opd is None:
            return False, f"codec:{s.codec}"
        if snap is not None and np.uint64(s.max_seqno) > snap:
            return False, "seqno"
        if not run_keys_unique(s):
            return False, "dup_keys"
    spans = sorted((s.min_key, s.max_key) for s in live_runs)
    for (_, pmax), (nmin, _) in zip(spans, spans[1:]):
        if pmax >= nmin:
            return False, "overlap"
    return True, "ok"
