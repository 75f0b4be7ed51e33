"""Shared set-up of the harness tests: a cell at its rehearsal size."""

from __future__ import annotations

import time

from bench import harness

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2**31 + 11   # seeds may exceed what 32 signed bits hold


# at the rehearsal size the prefill is a few thousand updates, not the
# hundreds of thousands a half-full 64 MiB write buffer holds
PREFILL_SHARE = 0.002


def rehearsal(workload: str):
    cell, cfg, mix = harness.find_cell(BENCH, workload)
    mix = {**mix, "warm_seconds": 0.5}
    if "writes" in mix:
        mix["writes"] = {**mix["writes"], "prefill_share": PREFILL_SHARE}
    return cell, {**cfg, **cfg["rehearsal"]}, mix


def run(workload: str, seed: int = SEED, seconds: float = 2.0,
        trace: bool = False, **kw) -> dict:
    cell, cfg, mix = rehearsal(workload)
    return harness.run_cell(BENCH, cell, cfg, mix, seed, seconds, trace,
                            time.perf_counter(), **kw)
