"""The plain reference: last write wins per key, then byte-string
predicates and aggregates over the surviving values, in numpy.

It imports nothing of the program and reads only the generated records
and updates (``apply``: later writes to loaded keys, in order).
Values are held as ids into the vocabulary, so a predicate is evaluated
once per distinct value and gathered per row.  Semantics, as the store
states them:

- ``prefix`` a: value starts with a; ``range`` (a, b): a <= value <= b,
  both as byte strings of the value width;
- a filter answers every live key whose newest value matches, in
  ascending key order, with that value;
- COUNT counts the matches; SUM adds each match's first run of ASCII
  digits as an integer clipped to 2**31 - 1; MIN / MAX are the
  lexicographically least / greatest matching value (None if none);
- a group-count by prefix length p counts matches per first p bytes
  (trailing NULs dropped), sorted by count descending, then label.

``Oracle(..., truncate=8)`` is the control: predicates, orderings and
SUM weights see only the first 8 bytes of each value (the approximation
a cheaper fixed-width compare would make), while answers still carry
whole values.  It breaks the exact-answer guarantee and must fail the
comparison.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

INT32_MAX = 2**31 - 1


def _digit_weight(v: bytes) -> int:
    run = b""
    for c in v:
        if 48 <= c <= 57:
            run += bytes([c])
        elif run:
            break
    return min(int(run), INT32_MAX) if run else 0


class Oracle:
    def __init__(self, keys: np.ndarray, ids: np.ndarray, vocab: np.ndarray,
                 truncate: Optional[int] = None):
        n = keys.shape[0]
        uniq, first_rev = np.unique(keys[::-1], return_index=True)
        self.keys = uniq                          # ascending
        self.ids = ids[n - 1 - first_rev]         # newest value per key
        self.vocab = vocab
        self.width = vocab.dtype.itemsize
        # what predicates and aggregates see: the whole value, or (the
        # control) its first ``truncate`` bytes
        self.seen = vocab if truncate is None else \
            vocab.astype(f"S{truncate}").astype(f"S{self.width}")
        self._order = np.argsort(self.seen, kind="stable")
        self._weights = None

    def apply(self, keys: np.ndarray, ids: np.ndarray) -> None:
        """Updates of held keys to ``vocab[ids]``, in order and after
        every record already held: last write wins per key, as in the
        constructor."""
        n = keys.shape[0]
        uniq, first_rev = np.unique(keys[::-1], return_index=True)
        pos = np.minimum(np.searchsorted(self.keys, uniq),
                         self.keys.shape[0] - 1)
        if not np.array_equal(self.keys[pos], uniq):
            raise ValueError("an update to a key the reference does not hold")
        self.ids[pos] = ids[n - 1 - first_rev]

    def _bound(self, b: bytes):
        return np.asarray([b], f"S{self.width}")[0]

    def value_mask(self, pred) -> np.ndarray:
        """Bool per vocabulary entry."""
        if pred is None:
            return np.ones(self.vocab.shape[0], bool)
        kind, a, b = pred
        if kind == "prefix":
            head = np.frombuffer(a, np.uint8)
            raw = self.seen.view(np.uint8).reshape(-1, self.width)
            return (raw[:, :head.shape[0]] == head).all(axis=1)
        if kind == "range":
            return (self.seen >= self._bound(a)) & (self.seen <= self._bound(b))
        raise ValueError(f"unknown predicate kind {kind!r}")

    def filter(self, pred):
        m = self.value_mask(pred)[self.ids]
        return self.keys[m], self.vocab[self.ids[m]]

    def _weights_of(self) -> np.ndarray:
        if self._weights is None:
            self._weights = np.asarray(
                [_digit_weight(bytes(v)) for v in self.seen], np.int64)
        return self._weights

    def aggregate(self, op: str, pred, prefix_len: int = 0):
        vm = self.value_mask(pred)
        counts = np.bincount(self.ids[vm[self.ids]],
                             minlength=self.vocab.shape[0])
        present = counts > 0
        if op == "count":
            return int(counts.sum())
        if op == "sum":
            return int((counts * self._weights_of()).sum())
        if op in ("min", "max"):
            order = self._order[present[self._order]]
            if order.shape[0] == 0:
                return None
            return bytes(self.vocab[order[0] if op == "min" else order[-1]])
        if op == "group_count":
            labels = self.seen[present].astype(f"S{prefix_len}")
            uniq, inv = np.unique(labels, return_inverse=True)
            totals = np.zeros(uniq.shape[0], np.int64)
            np.add.at(totals, inv, counts[present])
            items = [(bytes(u), int(t)) for u, t in zip(uniq, totals)]
            return sorted(items, key=lambda kv: (-kv[1], kv[0]))
        raise ValueError(f"unknown aggregate {op!r}")

    def answer(self, req):
        if req.op == "filter":
            return self.filter(req.pred)
        return self.aggregate(req.op, req.pred, req.prefix_len)


def same_answer(got, want) -> bool:
    """Filters as (keys, values) arrays; aggregates as Python values."""
    if isinstance(want, tuple):
        keys, values = got
        return (np.array_equal(np.asarray(keys, np.uint64), want[0])
                and np.array_equal(np.asarray(values), want[1]))
    return got == want
