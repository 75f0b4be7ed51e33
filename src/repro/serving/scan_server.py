"""Continuous-batching scan server over the LSM-OPD engine.

The serving-side counterpart of ``serving.engine``: where the token
engine keeps B decode slots busy and refills finished slots from a
request queue, the scan server keeps B *predicate* slots busy and
drains them through ``LSMTree.filter_many`` — every occupied slot rides
the same single pass over each SCT's packed column (one HBM read + one
``kernels.multi_filter`` launch per run, amortized over the batch).

Flow: clients ``submit`` predicates -> requests queue -> each ``step``
fills up to ``max_batch`` slots, pins ONE engine snapshot for the whole
batch (every query in a batch sees the same consistent state), executes
the batched filter, completes all slots, and refills from the queue.
``drain`` steps until the queue is empty — the scan analogue of running
the decode loop until all sequences finish.

Writes may interleave between batches (each batch re-snapshots), which
is exactly the MVCC behavior a per-query snapshot would give, minus the
K-1 redundant column passes.

Sharded mode: the server accepts a ``ShardedLSM`` in place of a plain
tree — both expose the same ``filter_many``/``snapshot`` surface.  Each
batch then pins ONE cross-shard snapshot vector and rides one
``filter_many`` per shard (scatter on the shard executor's thread pool,
one ``multi_filter`` launch per shard per run on 'jax_packed'), so
batching amortization and shard parallelism compose.

Aggregates ride the same batches: ``submit_agg`` enqueues an
``AggSpec`` next to the filter requests, and ``step`` executes the
batch's aggregate slots through ``aggregate_many`` against the SAME
pinned snapshot as its filter slots — an HTAP round's point lookups,
scans, and group-bys all observe one consistent version.  The result
dict then maps rid -> ``FilterResult`` or ``AggResult`` depending on
what was submitted.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Union

from jax.profiler import StepTraceAnnotation

from repro.core.filter_exec import FilterResult
from repro.core.lsm import LSMTree, Snapshot
from repro.core.opd import Predicate
from repro.query import AggResult, AggSpec

try:  # engine surface the server needs: filter_many + snapshot
    from repro.shard.sharded_lsm import ShardedLSM, ShardSnapshot
    ScanEngine = Union[LSMTree, ShardedLSM]
    AnySnapshot = Union[Snapshot, ShardSnapshot]
except ImportError:  # pragma: no cover - shard layer absent
    ScanEngine = LSMTree
    AnySnapshot = Snapshot


@dataclasses.dataclass
class ScanRequest:
    rid: int
    pred: Predicate
    submitted_at: float = 0.0
    result: Optional[FilterResult] = None
    done: bool = False


@dataclasses.dataclass
class AggRequest:
    rid: int
    spec: AggSpec
    submitted_at: float = 0.0
    result: Optional[AggResult] = None
    done: bool = False


QueryResult = Union[FilterResult, AggResult]


@dataclasses.dataclass
class ScanServerStats:
    n_submitted: int = 0
    n_served: int = 0
    n_batches: int = 0
    batch_sizes: List[int] = dataclasses.field(default_factory=list)

    @property
    def mean_batch(self) -> float:
        return (sum(self.batch_sizes) / len(self.batch_sizes)
                if self.batch_sizes else 0.0)


class ScanServer:
    def __init__(self, tree: ScanEngine, max_batch: int = 16,
                 maintenance: str = "background"):
        """``maintenance`` sets how batches relate to engine maintenance:

        'background'  (default) batches pin whatever version is current;
                      flushes/compactions overlap with serving — the
                      steady-state production posture.
        'sync'        every batch first drains pending maintenance
                      (``tree.drain()``), so queries always observe a
                      fully flushed + compacted tree — the
                      deterministic posture differential tests and
                      latency-floor benchmarks want.
        """
        assert max_batch >= 1
        if maintenance not in ("background", "sync"):
            raise ValueError(f"unknown maintenance mode {maintenance!r}")
        self.tree = tree
        self.max_batch = max_batch
        self.maintenance = maintenance
        self.queue: List[Union[ScanRequest, AggRequest]] = []
        self.stats = ScanServerStats()
        self._next_rid = 0

    # ------------------------------------------------------------------ #
    # client side
    # ------------------------------------------------------------------ #
    def submit(self, pred: Predicate) -> int:
        """Enqueue one predicate; returns a request id resolved by drain."""
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(ScanRequest(rid, pred, time.perf_counter()))
        self.stats.n_submitted += 1
        return rid

    def submit_many(self, preds: List[Predicate]) -> List[int]:
        return [self.submit(p) for p in preds]

    def submit_agg(self, spec: AggSpec) -> int:
        """Enqueue one aggregate; batched with filters in ``step``."""
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(AggRequest(rid, spec, time.perf_counter()))
        self.stats.n_submitted += 1
        return rid

    def submit_aggs(self, specs: List[AggSpec]) -> List[int]:
        return [self.submit_agg(s) for s in specs]

    # ------------------------------------------------------------------ #
    # server side
    # ------------------------------------------------------------------ #
    def step(self, snapshot: Optional[AnySnapshot] = None
             ) -> Dict[int, QueryResult]:
        """Fill up to ``max_batch`` slots from the queue and execute them
        as ONE batched filter + ONE batched aggregate, both against a
        single pinned snapshot.  The step is a profiler step span
        (``scan_server.step``, numbered by batch), so the engine's spans
        of one batch share its step number in a trace."""
        with StepTraceAnnotation("scan_server.step",
                                 step_num=self.stats.n_batches):
            return self._step(snapshot)

    def _step(self, snapshot: Optional[AnySnapshot]
              ) -> Dict[int, QueryResult]:
        raiser = getattr(self.tree, "raise_maintenance_errors", None)
        if raiser is not None:
            # a read-only server must not silently serve over a dead
            # flush/compaction worker: surface the failure to the
            # waiting clients instead of swallowing it
            raiser()
        if not self.queue:
            return {}
        if self.maintenance == "sync" and hasattr(self.tree, "drain"):
            self.tree.drain()  # observe a fully maintained tree
        slots = self.queue[: self.max_batch]
        scans = [r for r in slots if isinstance(r, ScanRequest)]
        aggs = [r for r in slots if isinstance(r, AggRequest)]
        if snapshot is None:
            # pin here, not inside the engine calls, so the batch's
            # filters and aggregates observe one consistent version
            snapshot = self.tree.snapshot()
        # dequeue only after the batch succeeds: a failing engine call
        # leaves the requests queued for a retry instead of losing them
        filter_res = self.tree.filter_many(
            [r.pred for r in scans], snapshot=snapshot) if scans else []
        agg_res = self.tree.aggregate_many(
            [r.spec for r in aggs], snapshot=snapshot) if aggs else []
        del self.queue[: len(slots)]
        out: Dict[int, QueryResult] = {}
        for r, res in list(zip(scans, filter_res)) + list(zip(aggs, agg_res)):
            r.result = res
            r.done = True
            out[r.rid] = res
        self.stats.n_batches += 1
        self.stats.n_served += len(slots)
        self.stats.batch_sizes.append(len(slots))
        return out

    def drain(self) -> Dict[int, QueryResult]:
        """Step until the queue is empty (continuous batching: each step
        re-fills from whatever has been submitted since)."""
        out: Dict[int, QueryResult] = {}
        while self.queue:
            out.update(self.step())
        return out

    def run(self, preds: List[Predicate]) -> Dict[int, QueryResult]:
        """Convenience: submit a workload and drain it."""
        self.submit_many(preds)
        return self.drain()
