"""Bytes a launch must move, whatever implements it.

Worked out from the snapshot, not from the program's padding or pack
width: a run of ``entries`` codes over a dictionary of ``dict_size``
values needs ceil(log2(dict_size)) bits a code.

- filter launch with K predicates: read every code once, write one
  result bit per entry per predicate (the filters of a step, or its
  aggregates where they take the filter kernel);
- aggregate or histogram launch: read every code once.

The kernel's share of its roofline is these bytes over the chip's HBM
bandwidth, divided by the device time of the kernel's programs.
"""

from __future__ import annotations

import math
from typing import Iterable, Optional, Tuple

from bench.peaks import peaks

FILTER_PROGRAMS = ("fused_zone_filter_2d",)
AGG_PROGRAMS = ("fused_zone_agg_2d", "zone_histogram_2d")


def code_bits(dict_size: int) -> int:
    return max(1, math.ceil(math.log2(max(2, dict_size))))


def read_bytes(runs: Iterable[Tuple[int, int]]) -> float:
    """``runs``: (entries, dictionary size) of the runs one launch covers."""
    return sum(n * code_bits(d) / 8.0 for n, d in runs)


def filter_launch_bytes(runs, k: int) -> float:
    runs = list(runs)
    return read_bytes(runs) + k * sum(n for n, _ in runs) / 8.0


def agg_launch_bytes(runs) -> float:
    return read_bytes(runs)


def _launch_runs(layout) -> Optional[list]:
    """The runs of one launch when every shard launches over the same
    sizes (one level group each); otherwise None."""
    groups = []
    for shard in layout:
        levels = {}
        for r in shard:
            levels.setdefault((r["level"], r["width"]), []).append(
                (r["entries"], r["dict"]))
        if len(levels) != 1:
            return None
        groups.append(next(iter(levels.values())))
    sizes = {tuple((n, code_bits(d)) for n, d in g) for g in groups}
    return groups[0] if len(sizes) == 1 else None


def roofline_pct(ctx, kind: str) -> Optional[float]:
    tr = ctx["trace"]
    runs = _launch_runs(ctx["layout"])
    if tr is None or runs is None:
        return None
    names = FILTER_PROGRAMS if kind == "filter" else AGG_PROGRAMS
    device_s = sum(s for name, s in tr["modules"].items()
                   if any(p in name for p in names))
    t_end = ctx["traced"][1]
    batches = [b for b in ctx["batches"] if b.t1 <= t_end]
    if kind == "filter":
        # aggregates that meet memtable rows leave the aggregate kernels
        # for filter launches of their own, K = the step's aggregates
        need = sum(b.filter_launches * filter_launch_bytes(runs, b.n_filters)
                   + b.agg_filter_launches * filter_launch_bytes(runs, b.n_aggs)
                   for b in batches)
    else:
        need = sum(b.agg_launches for b in batches) * agg_launch_bytes(runs)
    if device_s <= 0 or need <= 0:
        return None
    bw = peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * need / bw / device_s
