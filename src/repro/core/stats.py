"""Per-stage timing — mirrors the paper's seven-stage breakdown.

Paper §1: "a compaction operation comprises of seven stages: file
retrieval, reading, decoding, merging, filtering, encoding, and writing,
while a value filtering operation involves the first five stages".

CPU seconds are measured (perf_counter); I/O seconds are *modeled* from
byte/IO counters by ``storage.devices`` at report time (CPU-only box).

Each timed stage is also a profiler span named ``<name>.<stage>``
(``jax.profiler.TraceAnnotation``), so a ``jax.profiler`` trace holds
the same intervals on the clock it gives the device's operations.  With
no trace running a span costs under a microsecond.  ``jax`` is imported
on the first timed stage, not with this module.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, Iterable, Iterator, Optional

COMPACTION_STAGES = (
    "retrieval", "read", "decode", "merge", "filter", "encode", "write",
)


class StageStats:
    """Seconds and entry counts per stage, plus free-form counters in
    ``counts``.  ``name`` prefixes the profiler spans of its stages;
    an unnamed instance (a ``merge_all`` report) names them by stage."""

    def __init__(self, name: Optional[str] = None) -> None:
        self.name = name
        self.seconds: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)

    @contextlib.contextmanager
    def time(self, stage: str) -> Iterator[None]:
        from jax.profiler import TraceAnnotation  # deferred: jax on demand

        span = f"{self.name}.{stage}" if self.name else stage
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(span):
                yield
        finally:
            self.seconds[stage] += time.perf_counter() - t0
            self.counts[stage] += 1

    def add(self, stage: str, seconds: float) -> None:
        self.seconds[stage] += seconds
        self.counts[stage] += 1

    def total(self) -> float:
        return sum(self.seconds.values())

    def merged(self, other: "StageStats") -> "StageStats":
        return StageStats.merge_all((self, other))

    @staticmethod
    def merge_all(many: Iterable["StageStats"]) -> "StageStats":
        """Aggregate per-stage seconds/counts across components — the
        scatter-gather report path (e.g. one row per ShardedLSM stage
        summed over every shard tree)."""
        out = StageStats()
        for st in many:
            for k, v in st.seconds.items():
                out.seconds[k] += v
            for k, v in st.counts.items():
                out.counts[k] += v
        return out

    def as_dict(self) -> Dict[str, float]:
        return dict(self.seconds)

    def __repr__(self) -> str:
        parts = ", ".join(f"{k}={v * 1e3:.2f}ms" for k, v in sorted(self.seconds.items()))
        return f"StageStats({parts})"
