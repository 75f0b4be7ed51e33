"""From a JAX profiler trace to the numbers the per-layer metrics read.

``reduce_profile`` takes a ``jax.profiler.ProfileData`` (one ``.xplane.pb``)
and returns:

- ``window_s``: the benchmark's ``bench.window`` span (the served window);
- ``busy_s``: the union of the intervals in which an operation ran on a
  TPU core (its ``XLA Ops`` line; ``XLA Modules`` where a plane has no
  op line), clipped to the window and averaged over the chips;
- ``modules``: device seconds per XLA program (``XLA Modules`` line,
  the run id suffix dropped), summed over chips;
- ``device_ops``: ``[name, seconds]`` of the device operations that took
  most time, longest first;
- ``idle_gaps``: ``[name, seconds]``: the device's idle time inside the
  window, each gap given to the innermost benchmark span on the host
  that covers its middle (``other`` when none does), longest total
  first.

Times in one trace share the profiler's clock, so host spans and device
events compare directly.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPANS = (WINDOW_SPAN, "server.step", "engine.filter_many",
         "engine.aggregate_many", "engine.put_batch", "serve.wait")
_TPU_PLANE = re.compile(r"/device:TPU:\d+$")
_RUN_SUFFIX = re.compile(r"\(\d+\)$")


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns), \
            float(e.duration_ns)


def reduce_profile(pd) -> dict:
    spans: List[Tuple[str, float, float]] = []
    planes = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(n, s, e) for n, s, e, _ in _events(line)
                          if n in SPANS]
        elif _TPU_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            planes.append(lines)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    modules: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    busy_per_plane = []
    for lines in planes:
        op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
        if op_line is None:
            continue
        ivals = []
        for n, s, e, d in _events(op_line):
            ivals.append((s, e))
            ops[n] += d * 1e-9
        busy_per_plane.append(ivals)
        if "XLA Modules" in lines:
            for n, s, e, d in _events(lines["XLA Modules"]):
                modules[_RUN_SUFFIX.sub("", n)] += d * 1e-9
    if windows:
        w0, w1 = windows[0]
    else:
        ends = [x for iv in busy_per_plane for x in iv]
        w0 = min((s for s, _ in ends), default=0.0)
        w1 = max((e for _, e in ends), default=0.0)
    unions = [_union(_clip(iv, w0, w1)) for iv in busy_per_plane]
    busy_ns = (sum(e - s for u in unions for s, e in u) / len(unions)
               if unions else 0.0)
    gaps: Dict[str, float] = defaultdict(float)
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    if unions:
        u = unions[0]
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = 0.5 * (s + e)
            cover = [(e2 - s2, n) for n, s2, e2 in inner if s2 <= mid <= e2]
            gaps[min(cover)[1] if cover else "other"] += (e - s) * 1e-9
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "modules": dict(modules),
        "device_ops": sorted(([n, s] for n, s in ops.items()),
                             key=lambda x: -x[1]),
        "idle_gaps": sorted(([n, s] for n, s in gaps.items()),
                            key=lambda x: -x[1]),
        "n_device_planes": len(busy_per_plane),
    }


def find_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def reduce_trace(trace_dir: str) -> Optional[dict]:
    import jax

    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_profile(jax.profiler.ProfileData.from_file(path))
