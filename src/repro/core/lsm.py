"""LSM-OPD storage engine (paper §3/§4).

Out-of-place ingestion -> memtable -> flush to SCTs (L0, tiered runs) ->
leveling compaction into single-sorted-run levels with size ratio T.
Codec is pluggable ('opd' | 'plain' | 'heavy' | 'blob') so the paper's
four competitors share one engine and all benchmark comparisons are
like-for-like.

State management is an immutable **version set** (``core.version``): the
tree shape lives in ``VersionSet.current`` (frozen per-level run
tuples), every flush/compaction/GC installs a ``VersionEdit`` atomically
under a light mutex, and each edit is appended to a manifest log in the
store's spill directory so ``LSMTree.restore`` rebuilds the exact tree
shape after a crash (``FileStore.restore`` recovers the bytes, the
manifest recovers the structure).

Maintenance runs in one of two modes (``LSMConfig.maintenance``):

  'sync'        (default) flushes and compactions run inline on the
                writer's thread — deterministic, the mode every
                differential test baselines against.
  'background'  the active memtable rotates into a frozen (immutable but
                still readable) queue at ``mem_bytes``; a background
                flush worker drains the queue and a debt-scored
                compaction worker keeps levels in shape
                (``core.maintenance``).  The old forced write stall is
                replaced by graduated throttling: past ``l0_slowdown``
                runs in L0 the writer is delayed, past ``l0_stop`` (or a
                full frozen queue) it blocks until maintenance catches
                up.

MVCC follows the paper's lightweight file-snapshot scheme: a snapshot
pins (seqno, the memtable stack — active + frozen queue, newest first —
and the current version's runs).  Maintenance installs new versions;
pinned objects stay readable because the snapshot holds direct
references (immutability does the rest).  Blob GC is copy-on-write: a
run whose value pointers move is *rebuilt* and swapped in via an edit,
so concurrent readers never observe a half-rewritten run.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import warnings
import weakref
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from repro.core.compaction import merge_scts
from repro.core.filter_exec import (FilterResult, evaluate_filter,
                                    evaluate_filter_many)
from repro.core.iterator import range_scan
from repro.core.maintenance import (THROTTLE_NONE, THROTTLE_SLOWDOWN,
                                    THROTTLE_STOP, MaintenanceScheduler)
from repro.core.memtable import MemTable
from repro.core.opd import Predicate
from repro.core.policy import (CompactionPolicy, PolicyTuner, make_policy,
                               run_depth)
from repro.core.sct import SCT, BlobManager, build_sct, record_disk_bytes
from repro.core.stats import StageStats
from repro.core.version import Version, VersionEdit, VersionSet
from repro.core.wal import OP_DELETE, OP_PUT, WALWriter, wal_prefix_for
from repro.storage.devices import DeviceModel
from repro.storage.io import FileStore
from repro.testing.crashpoints import crashpoint


@dataclasses.dataclass(frozen=True)
class LSMConfig:
    codec: str = "opd"                 # 'opd' | 'plain' | 'heavy' | 'blob'
    key_bytes: int = 16                # S_K (paper default 16)
    value_width: int = 64              # S_V
    file_bytes: int = 4 * 2**20        # F (paper: 32-64MB; scaled for CI)
    memtable_bytes: Optional[int] = None
    size_ratio: int = 10               # T
    l0_limit: int = 4                  # L0 compaction trigger (footnote 1)
    block_bytes: int = 4096
    bloom_bits_per_key: int = 10
    max_levels: int = 7
    blob_compress: bool = False        # BlobDB + dictionary compression
    blob_gc_threshold: float = 0.5
    filter_backend: str = "numpy"      # 'numpy' | 'jax' | 'jax_packed' | 'fused'
    compaction_backend: str = "numpy"  # 'numpy' | 'jax' | 'jax_packed'
    # --- compaction policy engine (docs/DESIGN.md §12) ---
    compaction_policy: str = "leveled"  # | 'tiered' | 'lazy_leveled' | 'hybrid'
    tier_runs: int = 4                  # K: runs per tiered level
    level_modes: Optional[Tuple[str, ...]] = None  # hybrid 'L'/'T' vector
    policy_autotune: bool = False       # online PolicyTuner per tree
    # --- maintenance pipeline (docs/DESIGN.md §9) ---
    maintenance: str = "sync"          # 'sync' | 'background'
    l0_slowdown: Optional[int] = None  # default: l0_limit + 4
    l0_stop: Optional[int] = None      # default: l0_limit + 8
    slowdown_seconds: float = 0.002    # per-rotation delay in the band
    max_immutables: int = 4            # frozen-memtable queue backpressure
    # --- durability (docs/DESIGN.md §10) ---
    wal_sync: str = "off"              # 'off' | 'group' | 'every'
    wal_group_bytes: int = 64 * 1024   # group-commit fsync threshold

    @property
    def mem_bytes(self) -> int:
        return self.memtable_bytes or self.file_bytes

    @property
    def l0_slowdown_trigger(self) -> int:
        return self.l0_slowdown if self.l0_slowdown is not None \
            else self.l0_limit + 4

    @property
    def l0_stop_trigger(self) -> int:
        return self.l0_stop if self.l0_stop is not None \
            else self.l0_limit + 8


@dataclasses.dataclass
class Snapshot:
    seqno: int
    memtable: MemTable
    runs: List[SCT]
    # active + frozen memtables, newest first (None: pre-version-set
    # callers constructed (seqno, memtable, runs) — fall back to the one)
    memtables: Optional[List[MemTable]] = None
    version: Optional[Version] = None

    @property
    def mems(self) -> List[MemTable]:
        return self.memtables if self.memtables is not None \
            else [self.memtable]


class LSMTree:
    def __init__(self, cfg: LSMConfig, spill_dir: Optional[str] = None,
                 store: Optional[FileStore] = None,
                 blob_mgr: Optional[BlobManager] = None,
                 manifest: Optional[str] = None,
                 scheduler: Optional[MaintenanceScheduler] = None):
        """``store``/``blob_mgr`` injection lets several trees share one
        backing store (the sharded engine: N shard trees over one disk,
        so split-rebuilt shards keep addressing existing blob files and
        I/O accounting stays in one place).  Default: private store.

        ``manifest`` names this tree's manifest log inside the store's
        spill dir (shard trees sharing a dir need distinct names).
        ``scheduler``: with ``cfg.maintenance='background'``, the
        maintenance scheduler to register with; None creates a private
        one (the sharded engine passes a shared instance so one
        scheduler drives all shards)."""
        self.cfg = cfg
        self.store = store if store is not None else FileStore(spill_dir)
        if blob_mgr is not None:
            self.blob_mgr: Optional[BlobManager] = blob_mgr
        else:
            self.blob_mgr = (
                BlobManager(self.store, cfg.value_width, cfg.blob_compress,
                            cfg.blob_gc_threshold)
                if cfg.codec == "blob" else None
            )
        self.memtable = MemTable(cfg.value_width, cfg.key_bytes)
        self.versions = VersionSet(self.store, cfg.max_levels,
                                   manifest=manifest)
        # write-ahead log (docs/DESIGN.md §10): per-tree segments in the
        # spill dir, named after the manifest so shard trees don't collide
        self.wal: Optional[WALWriter] = None
        self.wal_replayed = 0
        if cfg.wal_sync != "off":
            if cfg.wal_sync not in ("group", "every"):
                raise ValueError(f"unknown wal_sync mode {cfg.wal_sync!r}")
            if not self.store.spill_dir:
                raise ValueError(
                    "wal_sync requires a spill_dir-backed store")
            self.wal = WALWriter(
                self.store.spill_dir,
                prefix=wal_prefix_for(self.versions.manifest_name),
                sync=cfg.wal_sync, group_bytes=cfg.wal_group_bytes)
        self._immutables: List[MemTable] = []  # newest first; flush pops tail
        self._lock = threading.RLock()
        self._seqno = 0
        self._cursors: Dict[int, int] = {}  # round-robin compaction cursors
        # compaction policy (docs/DESIGN.md §12): an immutable value the
        # trigger/victim/output hooks consult; ``set_policy`` swaps it
        # and future compactions migrate the tree toward the new shape
        self.policy: CompactionPolicy = make_policy(cfg)
        self.tuner: Optional[PolicyTuner] = (
            PolicyTuner() if cfg.policy_autotune else None)
        # maintenance mode
        self._owns_sched = False
        if cfg.maintenance == "background":
            if scheduler is None:
                scheduler = MaintenanceScheduler()
                self._owns_sched = True
            scheduler.register(self)
            self._sched: Optional[MaintenanceScheduler] = scheduler
        elif cfg.maintenance == "sync":
            self._sched = None
        else:
            raise ValueError(f"unknown maintenance mode {cfg.maintenance!r}")
        # stats
        self.compaction_stats = StageStats("compaction")
        self.filter_stats = StageStats("filter")
        self.flush_stats = StageStats("flush")
        self.lookup_stats = StageStats("lookup")
        self.throttle_stats = StageStats("throttle")  # 'slowdown' / 'stop' stages
        self.agg_stats = StageStats("agg")  # analytics pushdown (repro.query)
        self.n_flushes = 0
        self.n_compactions = 0
        self.write_stalls = 0
        self.stall_seconds = 0.0
        self.write_slowdowns = 0
        self.slowdown_seconds = 0.0
        self.cascade_truncations = 0
        self.compaction_in_bytes = 0
        self.compaction_out_bytes = 0
        self.dict_compares = 0  # cumulative D_i terms across compactions
        self.ingest_bytes = 0   # logical bytes written (rebalance signal)
        self.n_policy_switches = 0  # set_policy calls (tuner migrations)
        # weakrefs to handed-out snapshots: blob GC must not delete value
        # logs a live snapshot can still address (see _gc_blobs)
        self._snapshots: List["weakref.ref[Snapshot]"] = []
        # blob logs replaced by copy-on-write GC: unlinked one pass later
        # so readers that grabbed the pre-replace version finish first
        self._zombie_blobs: List[int] = []

    # ------------------------------------------------------------------ #
    # restart
    # ------------------------------------------------------------------ #
    @classmethod
    def restore(cls, cfg: LSMConfig, spill_dir: str,
                manifest: Optional[str] = None,
                store: Optional[FileStore] = None,
                scheduler: Optional[MaintenanceScheduler] = None,
                gc_orphans: bool = True) -> "LSMTree":
        """Rebuild a tree after a crash/restart: ``FileStore.restore``
        recovers the spilled bytes, the manifest replay recovers the tree
        shape and seqno watermark, and SCT files a crash stranded between
        spill and manifest append are garbage-collected.  With
        ``cfg.wal_sync != 'off'`` the WAL tail is then replayed into the
        fresh memtable — records above the manifest watermark, stopping
        at the first torn record — so every acknowledged write survives.
        With the WAL off, unflushed memtable contents are lost
        (flush/drain before a planned shutdown)."""
        if store is None:
            store = FileStore.restore(spill_dir)
        tree = cls(cfg, store=store, manifest=manifest, scheduler=scheduler)
        tree.versions = VersionSet.recover(store, cfg.max_levels,
                                           manifest=manifest)
        if gc_orphans:
            # sole-tree stores only: a sharded restore GCs against the
            # union of all shard versions instead (other shards' live
            # files are NOT orphans)
            tree.versions.gc_orphans()
        tree._seqno = tree.versions.last_seqno
        if tree.blob_mgr is not None:
            # garbage ratios restart at zero: the manifest records runs,
            # not per-log death counts; future drops re-accrue garbage
            live: Dict[int, int] = {}
            for s in tree.versions.current.all_runs():
                if s.vfids is None or not s.n:
                    continue
                fids, counts = np.unique(s.vfids[s.vfids >= 0],
                                         return_counts=True)
                for f, c in zip(fids, counts):
                    live[int(f)] = live.get(int(f), 0) + int(c)
            tree.blob_mgr.live = dict(live)
            tree.blob_mgr.total = dict(live)
        if cfg.wal_sync != "off":
            # replay the WAL tail: only records the manifest watermark
            # does not already cover (flushed segments are truncated at
            # flush time, but the crash may have raced that)
            wal, records = WALWriter.restore(
                store.spill_dir,
                prefix=wal_prefix_for(tree.versions.manifest_name),
                sync=cfg.wal_sync, group_bytes=cfg.wal_group_bytes)
            tree.wal = wal
            watermark = tree.versions.last_seqno
            replayed = 0
            for rec in records:
                if rec.seqno <= watermark:
                    continue
                if rec.op == OP_PUT:
                    tree.memtable.put(rec.key, rec.value, rec.seqno)
                else:
                    tree.memtable.delete(rec.key, rec.seqno)
                tree._seqno = max(tree._seqno, rec.seqno)
                replayed += 1
            tree.wal_replayed = replayed
        return tree

    def close(self) -> None:
        if self._sched is not None and self._owns_sched:
            self._sched.close()
        if self.wal is not None:
            # planned shutdown: fsync the tail and keep the segments —
            # the next restore replays them
            self.wal.close()

    def __enter__(self) -> "LSMTree":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------ #
    # geometry
    # ------------------------------------------------------------------ #
    @property
    def levels(self) -> List[List[SCT]]:
        """Read-only view of the current version's per-level runs (kept
        for reporting/tests; mutations go through ``VersionEdit``)."""
        return [list(lvl) for lvl in self.versions.current.levels]

    @property
    def file_entries(self) -> int:
        rec = record_disk_bytes(self.cfg.codec, self.cfg.key_bytes, self.cfg.value_width)
        return max(256, int(self.cfg.file_bytes / rec))

    def level_bytes(self, i: int) -> int:
        return self.versions.current.level_bytes(i)

    def level_capacity(self, i: int) -> int:
        # L1 holds T files; each deeper level is T times larger.  T comes
        # from the active policy (the tuner varies it per tree) and
        # defaults to the config's ratio.
        return self.cfg.file_bytes * (self.policy.ratio(self.cfg.size_ratio) ** i)

    # ------------------------------------------------------------------ #
    # compaction policy hooks (docs/DESIGN.md §12)
    # ------------------------------------------------------------------ #
    def set_policy(self, policy: CompactionPolicy) -> None:
        """Swap the compaction policy.  Purely forward-looking: the
        installed version is untouched; future triggers/merges rewrite
        the tree toward the new shape (stacked levels drain through
        full-level merges, leveled layouts start stacking).  Readers are
        unaffected — every read path is seqno-correct under overlapping
        runs at any level."""
        with self._lock:
            self.policy = policy
            self.n_policy_switches += 1

    def _mode(self, level: int) -> str:
        """'L' (single sorted run) or 'T' (stacked runs) for one level."""
        return self.policy.mode(level, self.cfg.max_levels)

    def _l0_trigger(self) -> int:
        return self.policy.l0_trigger(self.cfg.l0_limit)

    def _run_depth(self, i: int) -> int:
        """Max number of overlapping runs a read must consult at level i."""
        return run_depth(self.versions.current.levels[i])

    def _level_pressure(self, i: int) -> float:
        """Compaction urgency of level i under the active policy (0 = in
        shape).  Leveled levels: bytes/capacity overage, plus any excess
        run depth left behind by a tiered->leveled migration.  Tiered
        levels: run depth past K-1 (each point = one extra run every
        read consults), plus a 4x-capacity byte safety valve so a
        mis-sized K cannot balloon a level unboundedly."""
        v = self.versions.current
        if not v.levels[i]:
            return 0.0
        over = self.level_bytes(i) / self.level_capacity(i) - 1.0
        if self._mode(i) == "T":
            pressure = float(max(0, self._run_depth(i)
                                 - (self.policy.tier_runs - 1)))
            if over > 3.0:
                pressure += over - 3.0
            return pressure
        pressure = max(0.0, over)
        depth = self._run_depth(i)
        if depth > 1:
            pressure += float(depth - 1)
        return pressure

    @property
    def dict_bytes(self) -> int:
        """Memory-resident OPD footprint (paper reports <1GB at NDV<=10%)."""
        return sum(s.dict_nbytes for s in self.versions.current.all_runs())

    @property
    def n_files(self) -> int:
        return self.versions.current.n_files

    @property
    def disk_bytes(self) -> int:
        total = sum(s.disk_bytes for s in self.versions.current.all_runs())
        if self.blob_mgr is not None:
            total += sum(self.store.size_of(f)
                         for f in self.blob_mgr.live_fids()
                         if self.store.contains(f))
        return total

    def all_runs(self, newest_first: bool = True) -> List[SCT]:
        """L0 runs (newest->oldest, or oldest->newest when
        ``newest_first=False``), then L1..Ln (sorted, non-overlapping).
        Read paths require the default: first-match-wins point lookups
        depend on newer L0 runs shadowing older ones."""
        return self.versions.current.all_runs(newest_first)

    # ------------------------------------------------------------------ #
    # writes
    # ------------------------------------------------------------------ #
    def put(self, key: int, value: bytes) -> None:
        self._check_maintenance()
        self._seqno += 1
        self.ingest_bytes += self.cfg.key_bytes + 8 + self.cfg.value_width
        if self.wal is not None:
            # log-before-apply: the record is on (or heading to) disk
            # before the memtable can serve it to readers
            self.wal.append(OP_PUT, key, self._seqno, value)
        self.memtable.put(key, value, self._seqno)
        self._after_write()

    def put_batch(self, keys: np.ndarray, values: np.ndarray) -> None:
        """Bulk insertion path for benchmarks (amortizes Python overhead).
        Under ``wal_sync='group'`` the whole batch is acknowledged by ONE
        fsync barrier at return — the group-commit fast path."""
        self._check_maintenance()
        self.ingest_bytes += len(keys) * (self.cfg.key_bytes + 8
                                          + self.cfg.value_width)
        for k, v in zip(keys.tolist(), values):
            self._seqno += 1
            if self.wal is not None:
                self.wal.append(OP_PUT, int(k), self._seqno, bytes(v))
            self.memtable.put(int(k), bytes(v), self._seqno)
            if self.memtable.approx_bytes >= self.cfg.mem_bytes:
                self._handle_full_memtable()
        if self.wal is not None:
            self.wal.sync()

    def delete(self, key: int) -> None:
        self._check_maintenance()
        self._seqno += 1
        self.ingest_bytes += self.cfg.key_bytes + 8
        if self.wal is not None:
            self.wal.append(OP_DELETE, key, self._seqno)
        self.memtable.delete(key, self._seqno)
        self._after_write()

    def _check_maintenance(self) -> None:
        """Surface background-worker failures on the next ingest instead
        of silently accepting writes a dead flush pipeline will never
        persist (tests/test_maintenance.py worker error-path suite)."""
        if self._sched is not None:
            self._sched.raise_if_failed()

    def raise_maintenance_errors(self) -> None:
        """Public form of the ingest-path guard, for read-only callers:
        a ``ScanServer`` that never ingests would otherwise keep serving
        from a tree whose flush pipeline died hours ago."""
        self._check_maintenance()

    # ------------------------------------------------------------------ #
    # replication apply (follower side; repro.replica)
    # ------------------------------------------------------------------ #
    def replicate(self, records) -> int:
        """Follower apply path: install leader-assigned WAL records —
        the shipped ``core.wal`` stream — through this tree's own
        WAL/memtable/flush/compaction pipeline.

        Seqnos come from the LEADER (this tree assigns none of its own
        while it is a follower), so ``_seqno`` doubles as the follower's
        contiguous *applied watermark*.  Records at or below it are
        skipped — a resume after a partition re-ships from the durable
        watermark, and duplicates must be harmless — while a gap above
        it raises: applying past a hole would break the prefix
        consistency every failover differential asserts.  Returns the
        number of records newly applied."""
        applied = 0
        for rec in records:
            if rec.seqno <= self._seqno:
                continue   # duplicate from a resume: already applied
            if rec.seqno != self._seqno + 1:
                raise ValueError(
                    f"replication gap: applied through {self._seqno}, "
                    f"next shipped record is {rec.seqno}")
            self._check_maintenance()
            crashpoint("apply.record")
            if self.wal is not None:
                self.wal.append(rec.op, rec.key, rec.seqno, rec.value)
            if rec.op == OP_PUT:
                self.ingest_bytes += (self.cfg.key_bytes + 8
                                      + self.cfg.value_width)
                self.memtable.put(rec.key, rec.value, rec.seqno)
            elif rec.op == OP_DELETE:
                self.ingest_bytes += self.cfg.key_bytes + 8
                self.memtable.delete(rec.key, rec.seqno)
            else:
                raise ValueError(f"unknown WAL op {rec.op!r}")
            self._seqno = rec.seqno
            applied += 1
            self._after_write()
        if applied and self.wal is not None:
            # one group barrier per shipped batch: the follower's
            # durable watermark (promotion floor) advances with delivery
            self.wal.sync()
        return applied

    def _after_write(self) -> None:
        if self.memtable.approx_bytes >= self.cfg.mem_bytes:
            self._handle_full_memtable()

    def _handle_full_memtable(self) -> None:
        if self._sched is None:
            self._sync_flush()
        else:
            self._rotate_memtable()
            self._sched.throttle(self)

    def _rotate_memtable(self) -> bool:
        """Swap the active memtable into the frozen queue (background
        mode).  The frozen memtable stays readable until its SCTs land
        in an installed version."""
        with self._lock:
            if self.memtable.n_versions == 0:
                return False
            self._immutables.insert(0, self.memtable)
            self.memtable = MemTable(self.cfg.value_width, self.cfg.key_bytes)
            if self.wal is not None:
                # seal under the same lock as the swap: segment k holds
                # exactly memtable k's records (truncation granularity)
                self.wal.rotate()
        if self._sched is not None:
            self._sched.schedule_flush(self)
        return True

    def flush(self) -> None:
        """Sync mode: freeze + OPD-encode + write to L0 inline (compact
        if L0 over limit — the legacy forced stall).  Background mode:
        rotate the active memtable and return immediately; ``drain`` is
        the completion barrier."""
        if self._sched is None:
            self._sync_flush()
        else:
            self._rotate_memtable()

    def _sync_flush(self) -> None:
        if self.memtable.n_versions == 0 and not self._immutables:
            return
        self._rotate_memtable()
        while self._flush_oldest_immutable():
            pass
        if len(self.versions.current.levels[0]) > self._l0_trigger():
            # forced write stall: ingestion waits for L0 compaction
            self.write_stalls += 1
            t0 = time.perf_counter()
            self._compact_l0()
            self._cascade()
            self.stall_seconds += time.perf_counter() - t0

    def _pending_flushes(self) -> int:
        return len(self._immutables)

    def _flush_oldest_immutable(self) -> bool:
        """Encode + install ONE frozen memtable (the oldest — L0 recency
        order depends on oldest-first processing).  Runs inline in sync
        mode and on the flush worker in background mode; the memtable is
        removed from the readable queue only after its version installs,
        so readers never observe a gap (worst case they see the same
        rows twice, which the seqno merges dedup)."""
        with self._lock:
            if not self._immutables:
                return False
            imm = self._immutables[-1]
        frozen = imm.freeze()
        fe = self.file_entries
        new: List[SCT] = []
        try:
            with self.flush_stats.time("encode"):
                for lo in range(0, frozen.n, fe):
                    hi = min(lo + fe, frozen.n)
                    sct = build_sct(
                        keys=frozen.keys[lo:hi], seqnos=frozen.seqnos[lo:hi],
                        tombs=frozen.tombs[lo:hi], raw_values=frozen.values[lo:hi],
                        level=0, codec=self.cfg.codec,
                        key_bytes=self.cfg.key_bytes, value_width=self.cfg.value_width,
                        block_bytes=self.cfg.block_bytes,
                        bloom_bits_per_key=self.cfg.bloom_bits_per_key,
                        store=self.store, blob_mgr=self.blob_mgr,
                    )
                    new.append(sct)
                    crashpoint("flush.mid_spill")
        except Exception:
            # a failed flush must not leak freshly spilled chunks: no
            # version references them yet, so unregister before re-raising
            # (the memtable stays queued — a retry re-encodes it whole).
            # Exception, not BaseException: a SimulatedCrash is a kill
            # and must leave the orphans for restore-time GC.
            for s in new:
                self.store.delete(s.file_id)
            raise
        last = int(frozen.seqnos.max()) if frozen.n else None
        crashpoint("flush.before_manifest")
        # adds listed oldest-chunk-first; Version.with_edit prepends the
        # reversed list, reproducing the legacy ``new[::-1] + L0`` order
        self.versions.apply(VersionEdit(adds=[(0, s) for s in new],
                                        last_seqno=last))
        crashpoint("flush.after_manifest")
        with self._lock:
            self._immutables.pop()
        if self.wal is not None and last is not None:
            # every record <= last is now reachable through the manifest:
            # sealed segments it covers are dead weight
            self.wal.truncate_upto(last)
        self.n_flushes += 1
        return True

    def drain(self) -> None:
        """Barrier: wait for every queued flush and all compaction debt
        (background mode; no-op in sync mode, where nothing is queued)."""
        if self._sched is not None:
            self._sched.drain([self])

    def compact(self) -> None:
        """Force a full maintenance pass: flush the memtable, fold L0
        into L1, and cascade any over-capacity levels.  The shard
        executor drives this across shards on its thread pool."""
        self.flush()
        if self._sched is not None:
            self._sched.drain([self])
        self._force_compact_inline()
        self._maybe_retune()

    def _force_compact_inline(self) -> None:
        """Fold L0 + cascade inline.  Background callers must drain
        first so no worker job is concurrently compacting this tree."""
        if self.versions.current.levels[0]:
            self._compact_l0()
        self._cascade()

    def _maybe_retune(self) -> None:
        """Between-compaction-rounds tuner hook (sync: end of
        ``compact``; background: the compaction worker after debt drains
        to zero)."""
        if self.tuner is not None:
            self.tuner.maybe_retune(self)

    # ------------------------------------------------------------------ #
    # compaction scheduling (policy-driven; paper Figure 2 for leveling)
    # ------------------------------------------------------------------ #
    def _merge_is_bottom(self, inputs: List[SCT], out_level: int) -> bool:
        """Tombstone-drop safety: the merge may physically delete
        tombstones only if no run OUTSIDE its inputs can hold an older
        version of an input key — i.e. every deeper level is empty and
        every surviving run at ``out_level`` does not overlap the input
        key span.  Under pure leveling the surviving runs never overlap
        (the merge consumed all overlaps), so this reduces to the legacy
        deeper-levels-empty check; with stacked (tiered) levels the
        surviving overlapping runs force tombstone retention."""
        v = self.versions.current
        if any(len(v.levels[j])
               for j in range(out_level + 1, self.cfg.max_levels)):
            return False
        live = [s for s in inputs if s.n]
        if not live:
            return True
        lo = min(s.min_key for s in live)
        hi = max(s.max_key for s in live)
        consumed = {s.file_id for s in inputs}
        return all(s.file_id in consumed or not s.n
                   or not s.overlaps(lo, hi)
                   for s in v.levels[out_level])

    def _compaction_debt(self) -> float:
        """Debt score driving the background scheduler: L0 run-count
        overage past the policy's trigger (each point = one whole run
        every read must consult) plus per-level policy pressure
        (``_level_pressure``: bytes overage for leveled levels, run
        depth past K for tiered ones)."""
        v = self.versions.current
        debt = float(max(0, len(v.levels[0]) - self._l0_trigger()))
        for i in range(1, self.cfg.max_levels - 1):
            debt += self._level_pressure(i)
        return debt

    def _compact_one_step(self) -> bool:
        """One highest-debt merge (background compaction worker).  L0
        depth always wins (it taxes every read); otherwise the highest-
        pressure level compacts one step."""
        v = self.versions.current
        if len(v.levels[0]) > self._l0_trigger():
            self._compact_l0()
            return True
        best, best_over = None, 0.0
        for i in range(1, self.cfg.max_levels - 1):
            over = self._level_pressure(i)
            if over > best_over:
                best, best_over = i, over
        if best is None:
            return False
        self._compact_level_step(best)
        return True

    def _throttle_level(self) -> int:
        """Graduated writer backpressure (RocksDB slowdown/stop).  The
        slowdown band opens at HALF the frozen-queue limit so the writer
        is gently delayed well before the stop cliff — per-rotation
        sleeps concede the GIL to the flush/compaction workers, which is
        usually enough to never reach a hard stop.

        Thresholds float with the active policy's L0 trigger: a tiered
        L0 legitimately stacks K runs, so the slowdown/stop gates keep
        their configured *offsets* above the trigger instead of firing
        at the leveled absolute counts (identical to the legacy behavior
        for the leveled policy, where trigger == l0_limit)."""
        if self._sched is None:
            return THROTTLE_NONE
        l0_trig = self._l0_trigger()
        stop_at = l0_trig + (self.cfg.l0_stop_trigger - self.cfg.l0_limit)
        slow_at = l0_trig + (self.cfg.l0_slowdown_trigger
                             - self.cfg.l0_limit)
        n_l0 = len(self.versions.current.levels[0])
        n_imm = len(self._immutables)
        if n_l0 >= stop_at or n_imm > self.cfg.max_immutables:
            return THROTTLE_STOP
        if n_l0 >= slow_at \
                or n_imm >= max(1, self.cfg.max_immutables // 2):
            return THROTTLE_SLOWDOWN
        return THROTTLE_NONE

    def _compact_l0(self) -> None:
        v = self.versions.current
        inputs = list(v.levels[0])
        if not inputs:
            return
        if self._mode(1) == "T":
            # tiering: the merged L0 runs become ONE new run stacked on
            # L1 — nothing at L1 is consumed (that's the write savings)
            self._run_merge(inputs, out_level=1, drop_in=[(0, inputs)],
                            stacked=True)
            return
        lo = min(s.min_key for s in inputs)
        hi = max(s.max_key for s in inputs)
        overlaps = [s for s in v.levels[1] if s.overlaps(lo, hi)]
        self._run_merge(inputs + overlaps, out_level=1,
                        drop_in=[(0, inputs), (1, overlaps)])

    def _compact_level_step(self, i: int) -> None:
        """One compaction step at level i, shaped by the policy:

        leveled level, single sorted run   round-robin victim file +
                                           overlaps below (the legacy
                                           leveling step, bit-identical).
        tiered level, or a leveled level   whole-level K-way merge into
        still holding stacked runs from    one output run below — stacked
        a migration                        if the level below is tiered,
                                           folded into the sorted run if
                                           it is leveled.
        """
        v = self.versions.current
        runs = list(v.levels[i])
        if not runs:
            return
        full_level = self._mode(i) == "T" or run_depth(runs) > 1
        if not full_level:
            victim = self._pick_victim(i)
            if victim is None:
                return
            overlaps = [s for s in v.levels[i + 1]
                        if s.overlaps(victim.min_key, victim.max_key)]
            self._run_merge([victim] + overlaps, out_level=i + 1,
                            drop_in=[(i, [victim]), (i + 1, overlaps)])
            return
        if self._mode(i + 1) == "T" and i + 1 < self.cfg.max_levels - 1:
            self._run_merge(runs, out_level=i + 1, drop_in=[(i, runs)],
                            stacked=True)
            return
        lo = min(s.min_key for s in runs if s.n)
        hi = max(s.max_key for s in runs if s.n)
        overlaps = [s for s in v.levels[i + 1] if s.overlaps(lo, hi)]
        self._run_merge(runs + overlaps, out_level=i + 1,
                        drop_in=[(i, runs), (i + 1, overlaps)])

    def _level_needs_compaction(self, i: int) -> bool:
        return bool(self.versions.current.levels[i]) \
            and self._level_pressure(i) > 0.0

    def _cascade(self) -> None:
        for i in range(1, self.cfg.max_levels - 1):
            guard = 0
            while self._level_needs_compaction(i):
                self._compact_level_step(i)
                guard += 1
                if guard > 64:
                    # previously a silent break: now counted + warned so
                    # benchmark runs can't quietly under-compact
                    self.cascade_truncations += 1
                    warnings.warn(
                        f"cascade truncated at level {i} after {guard} "
                        f"merges (level still {self.level_bytes(i)}B over "
                        f"{self.level_capacity(i)}B capacity); tree may be "
                        "under-compacted", RuntimeWarning, stacklevel=2)
                    break

    def _pick_victim(self, level: int) -> Optional[SCT]:
        runs = self.versions.current.levels[level]
        if not runs:
            return None
        cur = self._cursors.get(level, 0) % len(runs)
        self._cursors[level] = cur + 1
        return runs[cur]

    def _run_merge(self, inputs: List[SCT], out_level: int,
                   drop_in: List[Tuple[int, List[SCT]]],
                   stacked: bool = False) -> None:
        """K-way merge ``inputs`` into ``out_level``.  ``stacked=True``
        emits the output as one new run prepended (newest-first) at a
        tiered level instead of folding into the sorted layout."""
        res = merge_scts(
            inputs,
            out_level=out_level,
            is_bottom=self._merge_is_bottom(inputs, out_level),
            file_entries=self.file_entries,
            store=self.store,
            stats=self.compaction_stats,
            blob_mgr=self.blob_mgr,
            block_bytes=self.cfg.block_bytes,
            bloom_bits_per_key=self.cfg.bloom_bits_per_key,
            backend=self.cfg.compaction_backend,
        )
        self.n_compactions += 1
        self.dict_compares += res.dict_compares
        self.compaction_in_bytes += sum(s.disk_bytes for s in inputs)
        self.compaction_out_bytes += sum(s.disk_bytes for s in res.outputs)
        edit = VersionEdit(
            adds=[(out_level, s) for s in res.outputs],
            drops=[(lvl, s.file_id) for lvl, gone in drop_in for s in gone],
            stacked=[out_level] if stacked else [],
        )
        crashpoint("compact.before_manifest")
        self.versions.apply(edit)
        crashpoint("compact.after_manifest")
        # files leave the store only after the edit is durable: a crash
        # in between leaves orphans (GC'd on restore), never dangling refs
        for _, gone in drop_in:
            for s in gone:
                self.store.delete(s.file_id)
        if self.blob_mgr is not None:
            self._gc_blobs()

    # ------------------------------------------------------------------ #
    # blob GC (copy-on-write)
    # ------------------------------------------------------------------ #
    def _pinned_blob_fids(self) -> Set[int]:
        """Blob files addressable through a live snapshot.  Snapshots pin
        SCT objects directly (immutability), but blob *values* live in the
        store — GC must defer deleting any log a pinned run points into,
        or snapshot reads would dangle.  Dead weakrefs are pruned here, so
        a dropped snapshot releases its files at the next GC pass."""
        pinned: Set[int] = set()
        with self._lock:
            snaps = list(self._snapshots)
        for ref in snaps:
            snap = ref()
            if snap is None:
                continue
            for s in snap.runs:
                if s.vfids is not None and s.n:
                    pinned.update(int(f) for f in np.unique(s.vfids)
                                  if f >= 0)
        with self._lock:
            # prune IN PLACE against the live list: a snapshot registered
            # while we walked the copy above must not be dropped (its
            # blob logs would become deletable while it still reads them)
            self._snapshots = [r for r in self._snapshots
                               if r() is not None]
        return pinned

    def _gc_blobs(self) -> None:
        """Rewrite blob files past the garbage threshold (BlobDB GC),
        copy-on-write: runs whose pointers move are REBUILT and swapped
        into the version via a replace edit — concurrent readers holding
        the previous version keep a fully consistent view.  The replaced
        log itself is unlinked one GC pass later (and only while no live
        snapshot pins it), giving in-flight readers of the old version
        time to finish.  Files pinned by a live snapshot are skipped
        entirely — their garbage is collected once the snapshot goes."""
        pinned = self._pinned_blob_fids()
        with self._lock:
            zombies, self._zombie_blobs = self._zombie_blobs, []
        survivors = []
        for fid in zombies:
            if fid in pinned:
                survivors.append(fid)
            else:
                self.store.delete(fid)
        with self._lock:
            self._zombie_blobs.extend(survivors)
        for fid in self.blob_mgr.gc_candidates():
            if fid in pinned:
                continue
            v = self.versions.current
            refs = []
            for lvl_idx, lvl in enumerate(v.levels):
                for s in lvl:
                    sel = np.nonzero(s.vfids == fid)[0]
                    if sel.shape[0]:
                        refs.append((lvl_idx, s, sel))
            live_n = sum(sel.shape[0] for _, _, sel in refs)
            old_size = self.store.size_of(fid)
            self.store.stats.add_read(old_size, 1)
            if live_n == 0:
                self.store.delete(fid)
                self.blob_mgr.forget(fid)
                continue
            _, payload, values = self.store.payload(fid)
            parts = [values[s.vptrs[sel].astype(np.int64)]
                     for _, s, sel in refs]
            new_vals = np.concatenate(parts)
            new_fid, _ = self.blob_mgr.append(new_vals)
            crashpoint("gc.mid_blob")
            off = 0
            replaces = []
            for lvl_idx, s, sel in refs:
                vfids = s.vfids.copy()
                vptrs = s.vptrs.copy()
                vfids[sel] = new_fid
                vptrs[sel] = np.arange(off, off + sel.shape[0],
                                       dtype=np.uint64)
                off += sel.shape[0]
                ns = dataclasses.replace(s, vfids=vfids, vptrs=vptrs)
                ns.file_id = self.store.alloc_id()
                self.store.write(ns, ns.disk_bytes, fid=ns.file_id)
                replaces.append((lvl_idx, s.file_id, ns))
            self.versions.apply(VersionEdit(replaces=replaces))
            crashpoint("gc.after_replace")
            for _, s, _sel in refs:
                self.store.delete(s.file_id)
            self.blob_mgr.forget(fid)
            with self._lock:
                self._zombie_blobs.append(fid)
            self.blob_mgr.gc_runs += 1
            self.blob_mgr.gc_bytes_rewritten += int(new_vals.nbytes)

    # ------------------------------------------------------------------ #
    # reads
    # ------------------------------------------------------------------ #
    def _read_state(self) -> Tuple[int, List[MemTable], Version]:
        """Consistent (seqno, memtable stack, version) triple.  Memtables
        are captured before the version under the tree lock: a flush
        that lands in between shows its rows in BOTH (deduped by the
        seqno merges), never in neither."""
        with self._lock:
            return (self._seqno,
                    [self.memtable] + list(self._immutables),
                    self.versions.current)

    def snapshot(self) -> Snapshot:
        seqno, mems, version = self._read_state()
        snap = Snapshot(seqno, mems[0], version.all_runs(),
                        memtables=mems, version=version)
        if self.blob_mgr is not None:
            # registry only feeds blob-GC pinning; prune dead refs on the
            # way in so read-heavy workloads never grow it unboundedly
            with self._lock:
                self._snapshots = [r for r in self._snapshots
                                   if r() is not None]
                self._snapshots.append(weakref.ref(snap))
        return snap

    def get(self, key: int, snapshot: Optional[Snapshot] = None) -> Optional[bytes]:
        """point_lookup: memtable stack, then L0 newest->oldest, then L1..Ln."""
        if snapshot is not None:
            snap_seq: Optional[int] = snapshot.seqno
            mems = snapshot.mems
            runs = snapshot.runs
        else:
            snap_seq = None
            _, mems, version = self._read_state()
            runs = version.all_runs()
        with self.lookup_stats.time("lookup"):
            for mem in mems:  # newest first; first hit decides
                got = mem.get(key, snap_seq)
                if got is not None:
                    return got[1]
            k = np.uint64(key)
            # tiered levels hold OVERLAPPING runs, so run enumeration
            # order no longer implies recency: track the max-seqno
            # visible version across every candidate run instead of
            # returning the first match (first-match-wins is only sound
            # for the strictly-newest-first memtable stack above)
            best_seq = -1
            best: Optional[Tuple[SCT, int]] = None
            for s in runs:
                if s.n == 0 or not (s.min_key <= key <= s.max_key):
                    continue
                # duplicate versions of a key can SPAN a block boundary:
                # probe_range blooms every candidate block (not just the
                # first) so an older version stored past the boundary is
                # never pruned away
                _b_lo, _b_hi, maybe = s.blocks.probe_range(k)
                if not maybe:
                    continue
                # the block is fetched to search it: charge the read now,
                # whether or not the key is present (bloom false
                # positives are real I/O, not free)
                self.store.stats.add_read(self.cfg.block_bytes, 1)
                epb = s.blocks.entries_per_block
                pos = int(np.searchsorted(s.keys, k, side="left"))
                cur_blk = pos // epb
                while pos < s.n and s.keys[pos] == k:
                    if pos // epb != cur_blk:
                        # snapshot walk crossed into the next block:
                        # that fetch is real I/O too
                        cur_blk = pos // epb
                        self.store.stats.add_read(self.cfg.block_bytes, 1)
                    if snap_seq is None or s.seqnos[pos] <= snap_seq:
                        # newest visible version within this run (rows
                        # are (key asc, seqno desc))
                        seq = int(s.seqnos[pos])
                        if seq > best_seq:
                            best_seq = seq
                            best = None if s.tombs[pos] else (s, pos)
                        break
                    pos += 1
            if best is None:
                return None
            return self._decode_one(best[0], best[1])

    def _decode_one(self, s: SCT, pos: int) -> bytes:
        if s.codec == "opd":
            return bytes(s.opd.values[s.evs[pos]])          # O(1) dict offset
        if s.codec == "plain":
            return bytes(s.values[pos])
        if s.codec == "heavy":
            epb = s.zblock_entries
            bk, bv = s.decompress_block(pos // epb)          # real zlib
            return bytes(bv[pos % epb])
        if s.codec == "blob":
            v = self.blob_mgr.read_values(int(s.vfids[pos]),
                                          s.vptrs[pos:pos + 1], random_io=True)
            return bytes(v[0])
        raise ValueError(s.codec)

    def range_lookup(self, lo: int, hi: int,
                     snapshot: Optional[Snapshot] = None) -> Tuple[np.ndarray, np.ndarray]:
        snap = snapshot or self.snapshot()
        return range_scan(
            snap.runs, snap.mems, lo, hi,
            stats=self.lookup_stats, store=self.store, blob_mgr=self.blob_mgr,
            snapshot_seqno=snap.seqno, block_bytes=self.cfg.block_bytes,
        )

    def filter(self, pred: Predicate,
               snapshot: Optional[Snapshot] = None) -> FilterResult:
        snap = snapshot or self.snapshot()
        return evaluate_filter(
            snap.runs, snap.mems, pred,
            stats=self.filter_stats, store=self.store, blob_mgr=self.blob_mgr,
            snapshot_seqno=snap.seqno, backend=self.cfg.filter_backend,
            value_width=self.cfg.value_width,
        )

    def filter_many(self, preds: List[Predicate],
                    snapshot: Optional[Snapshot] = None) -> List[FilterResult]:
        """Batched filter: all predicates share one pass over every run
        (on 'jax_packed', one ``multi_filter`` kernel launch per run; on
        'fused', one zone-gated ``fused_level_filter`` launch per LEVEL),
        against a single consistent snapshot."""
        snap = snapshot or self.snapshot()
        return evaluate_filter_many(
            snap.runs, snap.mems, preds,
            stats=self.filter_stats, store=self.store, blob_mgr=self.blob_mgr,
            snapshot_seqno=snap.seqno, backend=self.cfg.filter_backend,
            value_width=self.cfg.value_width,
        )

    # ------------------------------------------------------------------ #
    # analytics pushdown (aggregates on packed codes; repro.query)
    # ------------------------------------------------------------------ #
    def aggregate(self, spec, snapshot: Optional[Snapshot] = None):
        """One aggregate against a consistent snapshot -> ``AggResult``."""
        return self.aggregate_many([spec], snapshot)[0]

    def aggregate_many(self, specs, snapshot: Optional[Snapshot] = None):
        """Batched aggregates: all specs share one pass over every run
        (scalar specs one zone-gated ``fused_level_agg`` launch per level
        on kernel backends), against a single consistent snapshot."""
        from repro.query import finalize_partial

        snap = snapshot or self.snapshot()
        specs = self._resolve_agg_specs(specs, snap)
        parts = self._aggregate_partials(specs, snap)
        return [finalize_partial(spec, part)
                for spec, part in zip(specs, parts)]

    def aggregate_partials(self, specs, snapshot: Optional[Snapshot] = None):
        """Mergeable per-tree partials (the scatter half of the sharded
        scatter-gather).  Specs must arrive RESOLVED (bucket edges fixed
        globally) or per-shard partials would not share labels."""
        snap = snapshot or self.snapshot()
        return self._aggregate_partials(specs, snap)

    def _aggregate_partials(self, specs, snap: Snapshot):
        from repro.query import evaluate_aggregates

        return evaluate_aggregates(
            snap.runs, snap.mems, specs,
            stats=self.agg_stats, store=self.store, blob_mgr=self.blob_mgr,
            snapshot_seqno=snap.seqno, backend=self.cfg.filter_backend,
            value_width=self.cfg.value_width,
        )

    def _resolve_agg_specs(self, specs, snap: Snapshot):
        from repro.query import resolve_specs
        from repro.query.planner import collect_domain

        specs = list(specs)
        if all(spec.group is None or spec.group.resolved()
               for spec in specs):
            return specs
        with self.agg_stats.time("plan"):
            domain = collect_domain(snap.runs, snap.mems, self.blob_mgr,
                                    self.cfg.value_width)
        return resolve_specs(specs, domain)

    # ------------------------------------------------------------------ #
    # reporting
    # ------------------------------------------------------------------ #
    def io_report(self, device: DeviceModel) -> Dict[str, float]:
        st = self.store.stats
        return {
            "read_bytes": st.bytes_read,
            "write_bytes": st.bytes_written,
            "read_ios": st.read_ios,
            "write_ios": st.write_ios,
            "modeled_read_s": device.read_seconds(st.bytes_read, st.read_ios),
            "modeled_write_s": device.write_seconds(st.bytes_written, st.write_ios),
        }

    def shape_report(self) -> Dict[str, object]:
        v = self.versions.current
        return {
            "levels": [len(l) for l in v.levels],
            "level_bytes": [v.level_bytes(i) for i in range(self.cfg.max_levels)],
            "run_depths": [run_depth(l) for l in v.levels],
            "policy": self.policy.describe(),
            "n_policy_switches": self.n_policy_switches,
            "n_retunes": self.tuner.n_retunes if self.tuner else 0,
            "n_files": self.n_files,
            "disk_bytes": self.disk_bytes,
            "dict_bytes": self.dict_bytes,
            "n_flushes": self.n_flushes,
            "n_compactions": self.n_compactions,
            "write_stalls": self.write_stalls,
            "stall_seconds": self.stall_seconds,
            "write_slowdowns": self.write_slowdowns,
            "slowdown_seconds": self.slowdown_seconds,
            "cascade_truncations": self.cascade_truncations,
            "dict_compares": self.dict_compares,
            "version": v.vid,
            "n_immutables": len(self._immutables),
            "maintenance": self.cfg.maintenance,
            "wal_sync": self.cfg.wal_sync,
            "wal_appends": self.wal.appends if self.wal else 0,
            "wal_syncs": self.wal.syncs if self.wal else 0,
            "wal_bytes": self.wal.bytes_written if self.wal else 0,
            "wal_replayed": self.wal_replayed,
        }
