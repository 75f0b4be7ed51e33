"""YCSB load phase with the paper's value extension.

Keys are the fixed set {0, 4, ..., 4(N-1)} (``key_max`` = 4N), inserted
in a seed-permuted ("hashed") order, so each of the store's range
shards holds exactly N / n_shards keys whatever the seed.  Values come
from a vocabulary of NDV = ``ndv_ratio`` x N strings of ``value_width``
bytes: ``cat_%05d_`` with label ``i % n_labels``, then seeded lowercase
filler (the paper's "commodity category_field" values), each record
drawing one uniformly.  Copied from the repository's benchmark helpers
(``make_vocab`` / ``gen_values``) so the yardstick does not move with
them.
"""

from __future__ import annotations

import numpy as np

from bench.data import Dataset


def make_vocab(ndv: int, width: int, n_labels: int, rng) -> np.ndarray:
    cats = [b"cat_%05d_" % (i % n_labels) for i in range(ndv)]
    fill = rng.integers(97, 123, (ndv, width - 10), dtype=np.uint8)
    out = np.zeros((ndv, width), np.uint8)
    out[:, :10] = np.frombuffer(b"".join(cats), np.uint8).reshape(ndv, 10)
    out[:, 10:] = fill
    return out.view(f"S{width}").reshape(ndv)


def generate(cfg: dict, seed: int) -> Dataset:
    n = int(cfg["records"])
    width = int(cfg["value_width"])
    ndv = int(round(n * float(cfg["ndv_ratio"])))
    rng = np.random.default_rng(seed)
    vocab = make_vocab(ndv, width, int(cfg["labels"]["count"]), rng)
    ids = rng.integers(0, ndv, n).astype(np.int32)
    keys = (rng.permutation(n).astype(np.uint64) * np.uint64(4))
    return Dataset(keys=keys, ids=ids, vocab=vocab, key_max=4 * n)
