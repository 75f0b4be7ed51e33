"""``correct`` comes out false when it should.

- The control: the reference's own 8-byte approximation, put in the
  program's place, fails the exact comparison on every seed.
- Faults planted under the timed path (the look for a chip skipped, the
  rest of a run driven as the benchmark drives it): an answer altered
  where it is produced, half of each batch left out, one shard's part
  left out of the gather, one shard's aggregate partial left out of the
  merge.  Each must turn ``correct`` false.
- In a cell that writes, two more: the scans skip the memtables, and
  the merge ignores what the memtables shadow (stale run values come
  back).
"""

import numpy as np
import pytest

import repro.core.filter_exec as filter_exec_mod
import repro.core.lsm as lsm_mod
import repro.query as query_mod
import repro.query.executor as agg_exec_mod
from bench import harness, traffic
from bench.oracle import Oracle, same_answer
from bench.tests.helpers import CELLS, SEED, rehearsal, run
from bench.tests.test_traffic import WRITE_CELLS
from repro.core.filter_exec import FilterResult
from repro.shard.sharded_lsm import ShardedLSM


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [SEED, 1, 2**32 + 3])
def test_control_fails_the_comparison(workload, seed):
    _, cfg, mix = rehearsal(workload)
    data = harness.generate_data(cfg, seed)
    reqs = traffic.generate(mix, cfg["labels"], seed, 4.0)
    ref = Oracle(data.keys, data.ids, data.vocab)
    ctl = Oracle(data.keys, data.ids, data.vocab, truncate=8)
    wrong = sum(not same_answer(ctl.answer(r), ref.answer(r)) for r in reqs)
    assert wrong > 0
    kept = {j: None for j in range(len(reqs))}
    assert harness._control_wrong(data, reqs, kept) == wrong


def _alter_answer(orig):
    def altered(*a, **kw):
        out = orig(*a, **kw)
        for res in out:
            if res.keys.shape[0]:
                vals = res.values.copy()
                vals[0] = b"x" * vals.dtype.itemsize
                res.values = vals
                break
        return out
    return altered


def _half_batch(orig):
    def half(self, preds, snapshot=None):
        keep = len(preds) // 2
        got = orig(self, preds[:keep], snapshot=snapshot) if keep else []
        empty = FilterResult(np.zeros(0, np.uint64),
                             np.zeros(0, f"S{self.cfg.value_width}"), 0, 0)
        return got + [empty] * (len(preds) - keep)
    return half


def _drop_shard(orig):
    def gather(self, results):
        return orig(self, results[:-1])
    return gather


def _drop_partial(orig):
    def merge(parts):
        return orig(parts[:-1])
    return merge


FAULTS = {
    "answer_altered": (lsm_mod, "evaluate_filter_many", _alter_answer),
    "half_batch": (ShardedLSM, "filter_many", _half_batch),
    "shard_left_out": (ShardedLSM, "_gather", _drop_shard),
    "partial_left_out": (query_mod, "merge_partials", _drop_partial),
}


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_turns_correct_false(workload, fault, monkeypatch):
    owner, name, make = FAULTS[fault]
    monkeypatch.setattr(owner, name, make(getattr(owner, name)))
    monkeypatch.setattr(harness, "warm_up", lambda *a, **kw: 0)
    # a rate the CPU cannot keep up with, so steps carry full batches
    res = run(workload, seconds=1.5, rate_per_s=200.0)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


def _no_memtable_rows(orig):
    def visible(mems, snap, value_width=None):
        return orig([], snap, value_width)
    return visible


def _no_shadowing(orig):
    def newest(mems, snap):
        return None
    return newest


# each planted where the filter and the aggregate executors both call it
WRITE_FAULTS = {
    "memtable_skipped": ("_memtable_visible", _no_memtable_rows),
    "shadowing_ignored": ("_memtable_newest", _no_shadowing),
}


@pytest.mark.parametrize("workload", WRITE_CELLS)
@pytest.mark.parametrize("fault", sorted(WRITE_FAULTS))
def test_write_fault_turns_correct_false(workload, fault, monkeypatch):
    name, make = WRITE_FAULTS[fault]
    for owner in (filter_exec_mod, agg_exec_mod):
        monkeypatch.setattr(owner, name, make(getattr(owner, name)))
    monkeypatch.setattr(harness, "warm_up", lambda *a, **kw: 0)
    res = run(workload, seconds=1.5, rate_per_s=200.0)
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0
    assert res["diag"]["write_batches"] > 0
