"""The traffic generator: the scan requests of a mix do not depend on
whether it writes, and its write batches follow YCSB's scrambled
zipfian at due times that every seed shares."""

import hashlib

import numpy as np
import pytest

from bench import traffic
from bench.tests.helpers import CELLS, SEED, rehearsal

# sha256 of repr([(due, op, pred, prefix_len), ...]) of the first 200
# requests of ycsb_v128.scan_agg at SEED, as the generator gave them
# before mixes could write
SCAN_AGG_200 = "fde0c0352669ff59385c3b4e3cdd460cdc0bfba66558ecdd5a4cac2fcea20b0a"
WRITE_CELLS = [w for w in CELLS if "writes" in rehearsal(w)[2]]


def _digest(reqs) -> str:
    rows = [(r.due, r.op, r.pred, r.prefix_len) for r in reqs]
    return hashlib.sha256(repr(rows).encode()).hexdigest()


def test_scan_agg_requests_are_pinned():
    _, cfg, mix = rehearsal("ycsb_v128.scan_agg")
    reqs = traffic.generate(mix, cfg["labels"], SEED, 40.0)[:200]
    assert len(reqs) == 200 and _digest(reqs) == SCAN_AGG_200


@pytest.mark.parametrize("workload", WRITE_CELLS)
def test_writes_leave_the_requests_alone(workload):
    _, cfg, mix = rehearsal(workload)
    bare = {k: v for k, v in mix.items() if k != "writes"}
    for seed in (SEED, 7):
        assert (traffic.generate(mix, cfg["labels"], seed, 12.0)
                == traffic.generate(bare, cfg["labels"], seed, 12.0))
    assert traffic.write_batches(bare, 1000, 50, SEED, 12.0, 4) == []


@pytest.mark.parametrize("workload", WRITE_CELLS)
def test_write_batches_same_times_for_every_seed(workload):
    _, cfg, mix = rehearsal(workload)
    n_items, ndv = 800_000, 8_000
    a = traffic.write_batches(mix, n_items, ndv, SEED, 10.0, 4)
    b = traffic.write_batches(mix, n_items, ndv, 12345, 10.0, 4)
    w = mix["writes"]
    period = w["batch"] / w["rate_per_s"]
    assert len(a) == len(b) == int(np.ceil(10.0 / period))
    assert [x.due for x in a] == [x.due for x in b]
    assert all(x.due < 10.0 for x in a)
    assert {x.items.shape[0] for x in a + b} == {w["batch"]}
    items_a = np.concatenate([x.items for x in a])
    items_b = np.concatenate([x.items for x in b])
    assert not np.array_equal(items_a, items_b)
    assert items_a.min() >= 0 and items_a.max() < n_items
    ids = np.concatenate([x.ids for x in a])
    assert ids.min() >= 0 and ids.max() < ndv
    # another stream of the same seed is another draw
    c = traffic.write_batches(mix, n_items, ndv, SEED, 10.0, 5)
    assert not np.array_equal(items_a, np.concatenate([x.items for x in c]))


@pytest.mark.parametrize("workload", WRITE_CELLS)
def test_prefill_same_size_for_every_seed(workload):
    _, cfg, mix = rehearsal(workload)
    n_items, ndv = 800_000, 8_000
    a = traffic.prefill(mix, 5000, n_items, ndv, SEED)
    b = traffic.prefill(mix, 5000, n_items, ndv, 12345)
    assert a.items.shape == b.items.shape == a.ids.shape == (5000,)
    assert not np.array_equal(a.items, b.items)
    assert a.items.max() < n_items and a.ids.max() < ndv
    # a stream of its own: not the steady phase's or the window's draws
    for stream in (4, 5):
        w = traffic.write_batches(mix, n_items, ndv, SEED, 10.0, stream)
        assert not np.array_equal(a.items[:w[0].items.shape[0]], w[0].items)
    bare = {k: v for k, v in mix.items() if k != "writes"}
    assert traffic.prefill(bare, 5000, n_items, ndv, SEED) is None
    assert traffic.prefill(mix, 0, n_items, ndv, SEED) is None


@pytest.mark.parametrize("workload", WRITE_CELLS)
def test_hot_keys_take_most_updates(workload):
    _, cfg, mix = rehearsal(workload)
    # many more updates than keys, so that uniform keys would give the
    # hottest 1 % of them little more than 1 % of the updates
    n_items = 10_000
    seconds = 12 * n_items / mix["writes"]["rate_per_s"]
    items = np.concatenate([x.items for x in traffic.write_batches(
        mix, n_items, 8_000, SEED, seconds, 4)])
    assert items.shape[0] >= 12 * n_items
    counts = np.sort(np.bincount(items, minlength=n_items))[::-1]
    hot = counts[: n_items // 100].sum() / items.shape[0]
    assert hot > 0.1, hot
    # the hashing spreads the hottest keys over the key space
    top = np.argsort(np.bincount(items, minlength=n_items))[-100:]
    assert len(set((top * 4 // n_items).tolist())) == 4


def test_zeta_matches_ycsb_constant():
    # ZipfianGenerator's ZETAN for ITEM_COUNT = 10**10 at 0.99
    assert traffic.zeta(10**10, 0.99) == pytest.approx(26.46902820178302,
                                                       rel=1e-10)
    small = float(np.sum(np.arange(1, 3_000_001, dtype=np.float64) ** -0.99))
    assert traffic.zeta(3_000_000, 0.99) == pytest.approx(
        small, rel=1e-9)


def test_fnvhash64_matches_plain_loop():
    def plain(v: int) -> int:
        h = traffic.FNV_OFFSET_64
        for _ in range(8):
            h ^= v & 0xFF
            v >>= 8
            h = (h * traffic.FNV_PRIME_64) % 2**64
        return abs(h - 2**64 if h >= 2**63 else h)

    xs = [0, 1, 2, 255, 256, 12345, 10**10 - 1, 2**40 + 7]
    got = traffic.fnvhash64(np.asarray(xs, np.int64))
    assert got.tolist() == [plain(x) for x in xs]


def test_zipfian_ranks_follow_the_law():
    rng = np.random.default_rng(3)
    ranks = traffic.zipfian_ranks(rng, 200_000, 0.99, 10**10)
    zetan = traffic.zeta(10**10, 0.99)
    for r in (0, 1):
        assert np.mean(ranks == r) == pytest.approx(
            (r + 1) ** -0.99 / zetan, rel=0.05)
    assert ranks.min() >= 0 and ranks.max() < 10**10
