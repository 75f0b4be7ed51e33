"""Benchmark entry: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, then ``diag`` and, last, ``checks``: each number that
decided ``correct`` with its limit.  The checks are also the last lines
of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.  ``--rehearse`` runs the whole cell at the
configuration's ``rehearsal`` size on the CPU (kernels interpreted);
its line goes to standard error and it exits 3, so a rehearsal never
reads as a result.  ``--control`` also reports how the reference's
8-byte approximation fares against the reference on the same kept
requests (the control of ``correct``; the benchmark's own runs leave it
off).

JAX's persistent compilation cache is kept at ``<checkout>/.jax_cache``
whatever the environment says, so only a checkout's first run of a cell
compiles and two checkouts share nothing.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: {ROOT} holds no program (src/repro)", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness

    bench = harness.load_benchmark()
    cell, cfg, mix = harness.find_cell(bench, args.workload)
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu":
            print("bench: --rehearse runs on the CPU only", file=sys.stderr)
            return 2
        cfg = {**cfg, **cfg["rehearsal"]}
    elif platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX has {len(devices)} {platform!r} device(s)", file=sys.stderr)
        return 2
    result = harness.run_cell(bench, cell, cfg, mix, args.seed, args.seconds,
                              bool(args.trace), T_START, control=args.control)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    line = json.dumps(result)
    if args.rehearse:
        print(line, file=sys.stderr)
        print("bench: rehearsal on the CPU; not a result", file=sys.stderr)
        return 3
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
