"""Record the small TPU trace that ``test_trace_reduce.py`` reads.

    python bench/tests/make_trace_fixture.py --out <dir>

Run on a machine with a TPU.  It launches the served path's three
kernel wrappers (fused filter, scalar aggregate, histogram) a few times
on a synthetic 16-bit packed column inside the benchmark's own
``TraceAnnotation`` spans, with the profiler's Python tracer off, and
writes the ``.xplane.pb`` plus a JSON summary of its planes, lines and
event names next to it.  The test keeps the trace as
``bench/tests/data/fixture.xplane.pb`` and the launch counts in
``bench/tests/data/fixture.json``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--entries", type=int, default=200_000)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("make_trace_fixture: no TPU", file=sys.stderr)
        return 1
    from repro.core.sct import bitpack
    from repro.kernels import ops as kops

    rng = np.random.default_rng(7)
    n, width = args.entries, 16
    codes = rng.integers(0, 30_000, n).astype(np.int32)
    packed = bitpack(codes, width)
    ranges = np.asarray([[100, 400], [5_000, 5_300], [9, 8], [20_000, 29_999]],
                        np.uint32)
    edges = np.linspace(0, 30_000, 11).astype(np.uint32)
    wts = (np.arange(30_000) % 1000).astype(np.int32)

    def once():
        with jax.profiler.TraceAnnotation("engine.filter_many"):
            kops.fused_level_filter([packed], [n], [ranges], [None], width)
        with jax.profiler.TraceAnnotation("engine.aggregate_many"):
            kops.fused_level_agg([packed], [n], [ranges], [None], width,
                                 weights_list=[wts])
            kops.level_histogram([packed], [n], [edges], [None], width)

    once()  # compile outside the trace
    log_dir = Path(args.out) / "raw"
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    t0 = time.perf_counter()
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("server.step"):
                once()
            time.sleep(0.01)
    print(f"traced {time.perf_counter() - t0:.3f} s")
    path = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, Path(args.out) / "fixture.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(path)
    summary = {"launches": {"filter": 3, "agg": 3, "hist": 3},
               "entries": n, "width": width, "planes": []}
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            names = Counter(e.name for e in evs)
            stats = sorted({k for e in evs[:50] for k, _ in e.stats})
            lines.append({"name": line.name, "n": len(evs),
                          "top": names.most_common(12), "stat_keys": stats})
        summary["planes"].append({"name": plane.name,
                                  "stats": [k for k, _ in plane.stats],
                                  "lines": lines})
    (Path(args.out) / "fixture.json").write_text(json.dumps(summary, indent=1))
    print(json.dumps(summary)[:20_000])
    print(f"xplane bytes: {os.path.getsize(path)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
