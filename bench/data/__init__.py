"""Data generators, one module per configuration's ``generator`` name.

Each module has ``generate(cfg, seed) -> Dataset``: the records in
insert order as keys plus an index into a vocabulary of distinct
values, so the store gets ``vocab[ids]`` and the reference never has to
hold a copy of every value.
"""

from __future__ import annotations

import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass
class Dataset:
    keys: np.ndarray    # uint64 [N], insert order
    ids: np.ndarray     # int32 [N], value = vocab[ids]
    vocab: np.ndarray   # S<width> [NDV]
    key_max: int        # exclusive bound of the key space

    def values(self, lo: int, hi: int) -> np.ndarray:
        return self.vocab[self.ids[lo:hi]]


def generate(cfg: dict, seed: int) -> Dataset:
    mod = importlib.import_module(f"bench.data.{cfg['generator']}")
    return mod.generate(cfg, seed)
