"""The reference under writes: the load plus the first m write batches,
applied to the ``Oracle`` in order, agrees with a plain dict replay,
and the harness's replay walks the kept requests through exactly those
states."""

import numpy as np
import pytest

from bench import harness, traffic
from bench.oracle import Oracle, same_answer
from bench.tests.helpers import SEED, rehearsal

WORKLOAD = "ycsb_v128.htap"


def _setup(n_batches: int = 24):
    _, cfg, mix = rehearsal(WORKLOAD)
    data = harness.generate_data(cfg, SEED)
    w = mix["writes"]
    seconds = n_batches * w["batch"] / w["rate_per_s"]
    batches = traffic.write_batches(mix, data.keys.shape[0],
                                    data.vocab.shape[0], SEED, seconds, 4)
    return cfg, mix, data, batches, np.sort(data.keys)


def _dict_replay(data, batches, sorted_keys):
    d = dict(zip(data.keys.tolist(), data.ids.tolist()))
    for b in batches:
        d.update(zip(sorted_keys[b.items].tolist(), b.ids.tolist()))
    keys = np.asarray(sorted(d), np.uint64)
    return keys, np.asarray([d[k] for k in keys.tolist()])


@pytest.mark.parametrize("m", [0, 1, 3, 8, 20])
def test_oracle_after_m_batches_is_a_dict_replay(m):
    _, _, data, batches, sk = _setup()
    assert len(batches) >= 20
    oracle = Oracle(data.keys, data.ids, data.vocab)
    for b in batches[:m]:
        oracle.apply(sk[b.items], b.ids)
    keys, ids = _dict_replay(data, batches[:m], sk)
    assert np.array_equal(oracle.keys, keys)
    assert np.array_equal(oracle.ids, ids)
    # the constructor's last-write-wins over the concatenation agrees
    ks = np.concatenate([data.keys] + [sk[b.items] for b in batches[:m]])
    vs = np.concatenate([data.ids] + [b.ids for b in batches[:m]])
    whole = Oracle(ks, vs, data.vocab)
    assert np.array_equal(whole.keys, keys) and np.array_equal(whole.ids, ids)


def test_apply_keeps_the_last_write_of_a_batch():
    vocab = np.asarray([b"a", b"b", b"c", b"d"], "S4")
    oracle = Oracle(np.asarray([8, 4, 0], np.uint64),
                    np.asarray([0, 1, 2], np.int32), vocab)
    oracle.apply(np.asarray([4, 8, 4], np.uint64),
                 np.asarray([3, 1, 2], np.int32))
    assert oracle.keys.tolist() == [0, 4, 8]
    assert oracle.ids.tolist() == [2, 2, 1]
    keys, values = oracle.filter(("prefix", b"c", b""))
    assert keys.tolist() == [0, 4] and values.tolist() == [b"c", b"c"]
    with pytest.raises(ValueError):
        oracle.apply(np.asarray([6], np.uint64), np.asarray([0], np.int32))


def test_replay_answers_each_request_at_its_state():
    _, mix, data, batches, sk = _setup()
    reqs = traffic.generate(mix, rehearsal(WORKLOAD)[1]["labels"], SEED, 8.0)
    steady = batches[:3]
    window = batches[3:]
    state = {j: (j * 7) % (len(window) + 1) for j in range(12)}
    writes = harness.Writes(steady=steady, window=window, kept=state,
                            sorted_keys=sk)
    # answers as a fresh reference at each request's own state gives them
    kept = {}
    for j, m in state.items():
        keys, ids = _dict_replay(data, steady + window[:m], sk)
        kept[j] = Oracle(keys, ids, data.vocab).answer(reqs[j])
    res = harness.check_answers(data, reqs, kept, [0.0] * len(reqs), writes)
    assert res == {"wrong_answers": 0, "unanswered": 0, "checked": 12}
    # answered one batch early, some of them read stale values
    early = dict(state)
    for j in early:
        early[j] = max(0, early[j] - 1)
    stale = harness.check_answers(
        data, reqs, kept, [0.0] * len(reqs),
        harness.Writes(steady=steady, window=window, kept=early,
                       sorted_keys=sk))
    assert stale["wrong_answers"] > 0


def test_replay_applies_the_prefill_first():
    _, mix, data, batches, sk = _setup()
    pre = traffic.prefill(mix, 2000, data.keys.shape[0], data.vocab.shape[0],
                          SEED)
    reqs = traffic.generate(mix, rehearsal(WORKLOAD)[1]["labels"], SEED, 8.0)
    state = {j: (j * 5) % (len(batches) + 1) for j in range(12)}
    kept = {}
    for j, m in state.items():
        keys, ids = _dict_replay(data, [pre] + batches[:m], sk)
        kept[j] = Oracle(keys, ids, data.vocab).answer(reqs[j])
    lat = [0.0] * len(reqs)
    res = harness.check_answers(
        data, reqs, kept, lat,
        harness.Writes(prefill=pre, window=batches, kept=state,
                       sorted_keys=sk))
    assert res["wrong_answers"] == 0
    # a reference that leaves the prefill out reads stale values
    res = harness.check_answers(
        data, reqs, kept, lat,
        harness.Writes(window=batches, kept=state, sorted_keys=sk))
    assert res["wrong_answers"] > 0


def test_control_fails_under_writes():
    _, mix, data, batches, sk = _setup()
    reqs = traffic.generate(mix, rehearsal(WORKLOAD)[1]["labels"], SEED, 4.0)
    kept = {j: None for j in range(len(reqs))}
    writes = harness.Writes(steady=batches[:2], window=batches[2:],
                            kept={j: j % len(batches[2:]) for j in kept},
                            sorted_keys=sk)
    assert harness._control_wrong(data, reqs, kept, writes) > 0
    # the reference replayed against itself finds nothing
    ref = Oracle(data.keys, data.ids, data.vocab)
    twin = Oracle(data.keys, data.ids, data.vocab)
    assert all(same_answer(twin.answer(reqs[j]), ref.answer(reqs[j]))
               for j in harness.replay([ref, twin], kept, writes))
