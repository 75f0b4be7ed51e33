"""OPD-based leveling compaction — paper Algorithm 1 + competitor paths.

The merge itself is codec-agnostic: assemble key columns + per-entry
source ids, merge-sort by (key asc, seqno desc), GC stale versions and
(at the bottom level) tombstones, then cut into output files.

What differs per codec is what happens to the *values*:

  'opd'    values never leave the encoded domain.  Per output SCT the new
           dictionary is rebuilt from the *input dictionaries only*
           (OPD.merge_subset — O(sum D_i log sum D_i) string comparisons)
           and every <ev, src> pair is remapped to its new dense code by
           one O(1) table gather.  This is the paper's central claim: the
           S_V-sized strings contribute only D_i log D_i, not N, to the
           compaction CPU cost.
  'plain'  values are copied (C_C x F per the paper's cost model).
  'heavy'  every input block is really zlib-decompressed and every output
           block re-compressed (the C_D/C_E terms that dominate the
           paper's heavy-compression competitor).
  'blob'   pointers are copied (values untouched — WiscKey's advantage);
           dropped entries mark blob garbage for GC.

The 'opd' encode stage is backend-pluggable (``backend=``, mirroring the
filter path's ``filter_backend``; see docs/DESIGN.md §7):

  'numpy'       host gather + host bitpack (the reference).
  'jax'         the remap runs on the device as one XLA gather
                (``kernels.merge_remap.remap_codes``); packing stays on
                the host.
  'jax_packed'  remap then the Pallas bit-packing kernel in one jitted
                program: output SCT columns come back already packed
                (``SCT.evs`` unpacks lazily if a reader asks).

All three produce bit-identical SCTs (tests/test_compaction_backends.py
is the differential contract).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from repro.core.opd import OPD
from repro.core.sct import SCT, BlobManager, build_sct, pack_width
from repro.core.stats import StageStats
from repro.storage.io import FileStore
from repro.testing.crashpoints import crashpoint

_SEQ_MAX = np.uint64(0xFFFFFFFFFFFFFFFF)


@dataclasses.dataclass
class CompactionResult:
    outputs: List[SCT]
    n_in: int
    n_out: int
    n_dropped: int
    dict_compares: int  # total distinct values sorted (paper's D_i terms)


def merge_scts(
    inputs: List[SCT],
    *,
    out_level: int,
    is_bottom: bool,
    file_entries: int,
    store: FileStore,
    stats: StageStats,
    blob_mgr: Optional[BlobManager] = None,
    block_bytes: int = 4096,
    bloom_bits_per_key: int = 10,
    backend: str = "numpy",  # 'numpy' | 'jax' | 'jax_packed' ('opd' encode)
    key_range: Optional[Tuple[int, int]] = None,  # half-open [lo, hi)
) -> CompactionResult:
    """``key_range`` restricts the output to keys in ``[lo, hi)`` — the
    shard-split path rebuilds each half of a tree with one such merge
    over ALL of the tree's runs.  Entries outside the range are simply
    not ours (they belong to the sibling merge), so they are neither
    counted as dropped nor marked as blob garbage."""
    codec = inputs[0].codec
    n_in = sum(s.n for s in inputs)

    # ---- stage: read (charge full-file I/O for every input) -------------- #
    with stats.time("read"):
        for s in inputs:
            store.read(s.file_id)

    # ---- stage: decode (only non-OPD codecs pay this) -------------------- #
    raw_cols: Optional[List[np.ndarray]] = None
    with stats.time("decode"):
        if codec == "heavy":
            raw_cols = [s._decompress_all()[2] for s in inputs]  # real zlib
        elif codec == "plain":
            raw_cols = [s.values for s in inputs]
        # 'opd': values stay encoded; 'blob': values not touched.

    # ---- stage: merge (keys + GC; the C_K / C_C terms) -------------------- #
    with stats.time("merge"):
        keys = np.concatenate([s.keys for s in inputs])
        seqnos = np.concatenate([s.seqnos for s in inputs])
        tombs = np.concatenate([s.tombs for s in inputs])
        srcs = np.concatenate(
            [np.full(s.n, i, np.int32) for i, s in enumerate(inputs)]
        )
        idxs = np.concatenate([np.arange(s.n, dtype=np.int64) for s in inputs])
        order = np.lexsort((_SEQ_MAX - seqnos, keys))  # key asc, seqno desc
        keys, seqnos, tombs = keys[order], seqnos[order], tombs[order]
        srcs, idxs = srcs[order], idxs[order]
        # newest version per key survives
        keep = np.ones(keys.shape[0], np.bool_)
        keep[1:] = keys[1:] != keys[:-1]
        if is_bottom:
            keep &= ~tombs  # physical delete at the deepest level
        if key_range is not None:
            in_range = _range_mask(keys, key_range)
            n_in = int(in_range.sum())  # only our half's entries count
            keep &= in_range
        keys, seqnos, tombs = keys[keep], seqnos[keep], tombs[keep]
        srcs, idxs = srcs[keep], idxs[keep]
    n_out = int(keys.shape[0])
    n_dropped = n_in - n_out

    # ---- stage: encode + write per output file --------------------------- #
    outputs: List[SCT] = []
    dict_compares = 0
    kwargs = dict(
        level=out_level,
        codec=codec,
        key_bytes=inputs[0].key_bytes,
        value_width=inputs[0].value_width,
        block_bytes=block_bytes,
        bloom_bits_per_key=bloom_bits_per_key,
        store=store,
        blob_mgr=blob_mgr,
    )

    if codec == "blob" and blob_mgr is not None:
        _mark_blob_garbage(inputs, srcs, idxs, blob_mgr, key_range)

    # hoisted once per merge (not per output chunk): old-code columns of
    # the inputs, unpacked transiently for packed-only SCTs
    src_codes: Optional[List[np.ndarray]] = None
    if codec == "opd" and n_out:
        with stats.time("encode"):
            src_codes = [_source_codes(s, backend) for s in inputs]

    for lo in range(0, max(n_out, 1), file_entries):
        hi = min(lo + file_entries, n_out)
        if hi <= lo:
            break
        ck, cs, ct = keys[lo:hi], seqnos[lo:hi], tombs[lo:hi]
        c_src, c_idx = srcs[lo:hi], idxs[lo:hi]
        with stats.time("encode"):
            if codec == "opd":
                encoded, packed_encoded, ncmp = _remap_codes(
                    inputs, src_codes, c_src, c_idx, ct, backend)
                dict_compares += ncmp
                out = build_sct(keys=ck, seqnos=cs, tombs=ct, encoded=encoded,
                                packed_encoded=packed_encoded, **kwargs)
            elif codec in ("plain", "heavy"):
                vals = _gather_raw(raw_cols, c_src, c_idx, inputs[0].value_width)
                out = build_sct(keys=ck, seqnos=cs, tombs=ct, raw_values=vals, **kwargs)
            elif codec == "blob":
                fids = _gather_i64([s.vfids for s in inputs], c_src, c_idx)
                ptrs = _gather_u64([s.vptrs for s in inputs], c_src, c_idx)
                out = build_sct(
                    keys=ck, seqnos=cs, tombs=ct, blob_refs=(fids, ptrs), **kwargs
                )
            else:
                raise ValueError(codec)
        outputs.append(out)
        crashpoint("compact.mid_spill")

    return CompactionResult(outputs, n_in, n_out, n_dropped, dict_compares)


# --------------------------------------------------------------------------- #
# Algorithm 1 lines 4-9: per-output-subsequence dictionary rebuild + remap
# --------------------------------------------------------------------------- #
def _remap_codes(
    inputs: List[SCT],
    src_codes: List[np.ndarray],
    c_src: np.ndarray,
    c_idx: np.ndarray,
    c_tombs: np.ndarray,
    backend: str = "numpy",
) -> Tuple[Optional[Tuple[np.ndarray, OPD]],
           Optional[Tuple[np.ndarray, int, OPD]], int]:
    """Returns (encoded, packed_encoded, dict_compares): exactly one of
    the first two is set — (evs, opd) for 'numpy'/'jax', or the
    'jax_packed' fused result (packed words, pack width, opd).
    ``src_codes`` are the inputs' old-code columns from ``_source_codes``
    (hoisted by the caller so packed-only inputs unpack once per merge)."""
    old_evs = np.full(c_src.shape[0], -1, np.int32)
    used_masks = []
    for i, s in enumerate(inputs):
        sel = c_src == i
        if sel.any():
            old_evs[sel] = src_codes[i][c_idx[sel]]
        m = np.zeros(s.opd.size, np.bool_)
        live = sel & ~c_tombs
        if live.any():
            cs = old_evs[live]
            m[cs[cs >= 0]] = True
        used_masks.append(m)
    # reverse index + new OPD: one fused sorted-array merge of the used
    # dictionary entries (paper's RBTree replaced by branch-free
    # searchsorted — see the docs/DESIGN.md §2 hardware-adaptation table).
    # flat is the index table: flattened <src, ev> -> ev' (O(1) gather).
    new_opd, flat, offsets = OPD.merge_subset_flat(
        [s.opd for s in inputs], used_masks)
    ncmp = sum(int(m.sum()) for m in used_masks)
    if backend == "numpy":
        new_evs = np.full(c_src.shape[0], -1, np.int32)
        live = (old_evs >= 0) & ~c_tombs
        if live.any():
            new_evs[live] = flat[old_evs[live].astype(np.int64)
                                 + offsets[c_src[live]]]
        return (new_evs, new_opd), None, ncmp
    from repro.kernels import ops as kops  # deferred: jax only on demand
    ev_in = np.where(c_tombs, np.int32(-1), old_evs)
    if backend == "jax":
        new_evs = kops.remap_codes(ev_in, c_src, flat, offsets)
        return (new_evs, new_opd), None, ncmp
    if backend == "jax_packed":
        width = pack_width(new_opd.code_bits)
        words = kops.remap_pack_codes(ev_in, c_src, flat, offsets, width)
        return None, (words, width, new_opd), ncmp
    raise ValueError(f"unknown compaction backend {backend!r}")


def _source_codes(s: SCT, backend: str) -> np.ndarray:
    """Old-code column of one input SCT.  Packed-only inputs (written by
    the 'jax_packed' backend) are unpacked *transiently* — on the jax
    backends via the bitpack kernel — instead of through the caching
    ``SCT.evs`` property, so merging a packed SCT does not permanently
    materialize (and double-store) its unpacked column."""
    if s._evs is not None or s.packed is None:
        return s.evs
    if backend == "numpy":
        from repro.core.sct import bitunpack
        codes = bitunpack(s.packed, s.code_bits, s.n)
    else:
        from repro.kernels import ops as kops
        codes = kops.unpack_codes(s.packed, s.code_bits, s.n)
    return np.where(s.tombs, np.int32(-1), codes)


def _range_mask(keys: np.ndarray, key_range: Tuple[int, int]) -> np.ndarray:
    """bool mask for keys in half-open [lo, hi); hi >= 2**64 (the top
    shard's unbounded range) cannot be a uint64 and means no upper cap."""
    lo, hi = key_range
    mask = keys >= np.uint64(lo)
    if hi < 2 ** 64:
        mask &= keys < np.uint64(hi)
    return mask


def _gather_raw(raw_cols, c_src, c_idx, width) -> np.ndarray:
    out = np.zeros(c_src.shape[0], f"S{width}")
    for i, col in enumerate(raw_cols):
        sel = c_src == i
        if sel.any():
            out[sel] = col[c_idx[sel]]
    return out


def _gather_u64(cols, c_src, c_idx) -> np.ndarray:
    out = np.zeros(c_src.shape[0], np.uint64)
    for i, col in enumerate(cols):
        sel = c_src == i
        if sel.any():
            out[sel] = col[c_idx[sel]]
    return out


def _gather_i64(cols, c_src, c_idx) -> np.ndarray:
    out = np.full(c_src.shape[0], -1, np.int64)
    for i, col in enumerate(cols):
        sel = c_src == i
        if sel.any():
            out[sel] = col[c_idx[sel]]
    return out


def _mark_blob_garbage(inputs, srcs, idxs, blob_mgr: BlobManager,
                       key_range=None):
    """Entries dropped by the merge leave garbage in their blob files.
    Under a ``key_range`` restriction only in-range drops are garbage —
    out-of-range entries stay live in the sibling half's output."""
    total = sum(s.n for s in inputs)
    kept = np.zeros(total, np.bool_)
    starts = np.zeros(len(inputs) + 1, np.int64)
    for i, s in enumerate(inputs):
        starts[i + 1] = starts[i] + s.n
    kept[starts[srcs] + idxs] = True
    for i, s in enumerate(inputs):
        k = kept[starts[i] : starts[i + 1]]
        dead = (~k) & (s.vfids >= 0)
        if key_range is not None:
            dead &= _range_mask(s.keys, key_range)
        if dead.any():
            for fid in np.unique(s.vfids[dead]):
                blob_mgr.mark_dead(int(fid), int((s.vfids[dead] == fid).sum()))
