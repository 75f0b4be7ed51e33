"""The one traffic generator: a mix file in, a timed request list out.

A mix (``bench/traffic/<name>.json``) is data only:

``arrivals``   ``{"kind": "periodic", "rate_per_s": r}``: one request
               every 1/r seconds.
``max_batch``  slots per ``ScanServer`` step.
``block``      the request slots of one block; every block holds each
               slot once, in a seed-permuted order.  A slot is
               ``{"op": op or [ops...], "count": c, "pred": ...}`` with
               ``pred`` one of ``null`` (whole column), ``"range"``
               (``"widths"``: label counts, cycled per occurrence) or
               ``"prefix"`` (``"drop_digits"``: the label's last digits
               dropped, covering 10**d labels).  ``group_count`` slots
               give ``"prefix_len"``.  A list of ops is cycled per
               occurrence.
``check``      how many served requests ``correct`` compares.
``warm_seconds`` how long set-up serves the same mix (another seed
               stream) before the window, so the window meets a server
               in its steady state.
``writes``     optional: ``{"kind": "ycsb_update", "rate_per_s": r,
               "batch": b, "distribution": "zipfian", "theta": t,
               "prefill_share": f}``.  ``kind`` and ``distribution``
               are format tags: the generator knows only these two and
               refuses any other.  One ``put_batch`` of b updates falls
               due every b / r seconds, the same times for every seed.
               Each update overwrites a loaded key drawn by YCSB's
               scrambled zipfian (``workloads/workloada``: ranks over
               10**10 items with constant t, FNV-1a-hashed onto the
               loaded keys, so the hot keys spread over the key space)
               with a value drawn uniformly from the configuration's
               vocabulary.  Before the steady phase, set-up applies
               the same stream's updates, untimed, until the memtables
               hold f of their flush size (``prefill``), the mean
               occupancy of a store written at a steady rate.  Writes
               draw from seed streams of their own, so a mix without
               them yields exactly the requests it yields with them.

Labels are the configuration's ordered value labels
(``cfg["labels"]``: a ``%``-format and a count); a range over labels
[lo, lo + w) is the value range [fmt % lo, fmt % (lo + w)], which every
value of label lo + w exceeds, being longer than the bare label.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Optional, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
FILTER = "filter"
SCALAR_AGGS = ("count", "sum", "min", "max")


@dataclasses.dataclass(frozen=True)
class Request:
    due: float                                  # seconds after window start
    op: str                                     # filter | count | ... | group_count
    pred: Optional[Tuple[str, bytes, bytes]]    # (kind, a, b); None = all
    prefix_len: int = 0                         # group_count only


def load(name: str, traffic_dir: Path = TRAFFIC_DIR) -> dict:
    return json.loads((traffic_dir / f"{name}.json").read_text())


def _pred(slot: dict, occurrence: int, labels: dict, rng):
    kind = slot.get("pred")
    fmt, n = labels["format"], int(labels["count"])
    if kind is None:
        return None
    if kind == "range":
        widths = slot["widths"]
        w = int(widths[occurrence % len(widths)])
        lo = int(rng.integers(0, n - w + 1))
        return ("range", (fmt % lo).encode(), (fmt % (lo + w)).encode())
    if kind == "prefix":
        d = int(slot["drop_digits"])
        lo = int(rng.integers(0, n // 10**d)) * 10**d
        return ("prefix", (fmt % lo).encode()[:-d], b"")
    raise ValueError(f"unknown pred kind {kind!r}")


def generate(mix: dict, labels: dict, seed: int, seconds: float,
             rate_per_s: Optional[float] = None,
             stream: int = 1) -> List[Request]:
    """Every request due in [0, seconds), in due order; ``stream``
    separates the window's requests from set-up's."""
    arrivals = dict(mix["arrivals"])
    if rate_per_s is not None:
        arrivals["rate_per_s"] = rate_per_s
    if arrivals["kind"] != "periodic":
        raise ValueError(f"unknown arrivals kind {arrivals['kind']!r}")
    rate = float(arrivals["rate_per_s"])
    n_req = int(np.ceil(rate * seconds)) + 1
    rng = np.random.default_rng([seed, stream])
    due = np.arange(n_req) / rate
    slots = [s for s in mix["block"] for _ in range(int(s["count"]))]
    seen: dict = {}
    out: List[Request] = []
    while len(out) < n_req:
        for j in rng.permutation(len(slots)):
            slot = slots[j]
            k = seen.get(id(slot), 0)
            seen[id(slot)] = k + 1
            ops = slot["op"] if isinstance(slot["op"], list) else [slot["op"]]
            i = len(out)
            out.append(Request(float(due[i]), ops[k % len(ops)],
                               _pred(slot, k, labels, rng),
                               int(slot.get("prefix_len", 0))))
            if len(out) == n_req:
                break
    return [r for r in out if r.due < seconds]


@dataclasses.dataclass(frozen=True, eq=False)
class WriteBatch:
    due: float          # seconds after window start
    items: np.ndarray   # int64 [batch]: index into the sorted loaded keys
    ids: np.ndarray     # int32 [batch]: value = vocab[ids]


# YCSB's ScrambledZipfianGenerator: a zipfian over ITEM_COUNT ranks,
# each rank hashed (fnvhash64) onto the items
YCSB_ITEM_COUNT = 10**10
FNV_OFFSET_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


ZETA_EXACT_TERMS = 10**6


def zeta(n: int, theta: float) -> float:
    """sum_{i=1..n} i**-theta: the first ``ZETA_EXACT_TERMS`` terms
    summed, the rest by Euler-Maclaurin (YCSB precomputes
    26.46902820178302 for 10**10 items at 0.99)."""
    m = min(n, ZETA_EXACT_TERMS)
    head = float(np.sum(np.arange(1, m + 1, dtype=np.float64) ** -theta))
    if n == m:
        return head
    a, b = float(m), float(n)
    tail = (b ** (1 - theta) - a ** (1 - theta)) / (1 - theta)
    tail += (b ** -theta - a ** -theta) / 2
    tail += theta * (a ** (-theta - 1) - b ** (-theta - 1)) / 12
    return head + tail


def zipfian_ranks(rng, n: int, theta: float, items: int) -> np.ndarray:
    """``n`` draws of YCSB's ZipfianGenerator over [0, items) (Gray et
    al.'s closed form, as ``ZipfianGenerator.nextLong``)."""
    zetan = zeta(items, theta)
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(n)
    uz = u * zetan
    ranks = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ranks = np.where(uz < 1.0 + 0.5 ** theta, 1, ranks)
    return np.where(uz < 1.0, 0, ranks)


def fnvhash64(x: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the 8 bytes of a long,
    low byte first, then the absolute value as a signed long."""
    val = x.astype(np.uint64)
    h = np.full(val.shape, FNV_OFFSET_64, np.uint64)
    for _ in range(8):
        h ^= val & np.uint64(0xFF)
        val >>= np.uint64(8)
        h *= np.uint64(FNV_PRIME_64)
    return np.abs(h.view(np.int64))


def write_batches(mix: dict, n_items: int, ndv: int, seed: int,
                  seconds: float, stream: int) -> List[WriteBatch]:
    """The mix's write batches due in [0, seconds), in due order; none
    when the mix has no ``writes``."""
    w = mix.get("writes")
    if w is None:
        return []
    size = int(w["batch"])
    period = size / float(w["rate_per_s"])
    n = int(np.ceil(seconds / period))
    due = np.arange(n) * period
    n = int(np.count_nonzero(due < seconds))
    items, ids = _updates(w, n * size, n_items, ndv, seed, stream)
    return [WriteBatch(float(due[i]), items[i * size:(i + 1) * size],
                       ids[i * size:(i + 1) * size]) for i in range(n)]


def prefill(mix: dict, rows: int, n_items: int, ndv: int,
            seed: int) -> Optional[WriteBatch]:
    """``rows`` updates of the mix's write stream (seed stream 6), all
    due before the steady phase; none without ``writes``."""
    w = mix.get("writes")
    if w is None or rows <= 0:
        return None
    items, ids = _updates(w, rows, n_items, ndv, seed, 6)
    return WriteBatch(0.0, items, ids)


def _updates(w: dict, n: int, n_items: int, ndv: int, seed: int,
             stream: int) -> Tuple[np.ndarray, np.ndarray]:
    if w["kind"] != "ycsb_update" or w["distribution"] != "zipfian":
        raise ValueError(f"unknown writes {w['kind']!r} / "
                         f"{w['distribution']!r}")
    rng = np.random.default_rng([seed, stream])
    ranks = zipfian_ranks(rng, n, float(w["theta"]), YCSB_ITEM_COUNT)
    items = fnvhash64(ranks) % n_items
    return items, rng.integers(0, ndv, n).astype(np.int32)


def warmup_batches(mix: dict, labels: dict,
                   n_positions: int) -> List[List[Request]]:
    """Batches that launch every kernel shape the window can launch.

    A step takes at most ``max_batch`` consecutive requests, which span
    at most ceil((max_batch - 1) / block) + 1 blocks, so the most
    filters (scalar aggregates) one launch can carry follows from the
    block alone, the same for every seed.  One batch per filter count
    and per scalar count, with and without a SUM where the mix has one,
    plus each group-count at ``n_positions`` spots over the labels, so
    every shard's dictionary shape is met.  Each predicate covers one
    label, which keeps warm-up cheap."""
    fmt, n = labels["format"], int(labels["count"])
    mb = int(mix["max_batch"])
    slots = [s for s in mix["block"] for _ in range(int(s["count"]))]
    spans = -(-(mb - 1) // len(slots)) + 1
    ops_of = [s["op"] if isinstance(s["op"], list) else [s["op"]]
              for s in slots]

    def most(kinds) -> int:
        return min(mb, spans * sum(1 for ops in ops_of if ops[0] in kinds))

    def narrow(j: int, k: int):
        lo = min((j * n) // k + (n // k) // 2, n - 1)
        return ("range", (fmt % lo).encode(), (fmt % (lo + 1)).encode())

    batches = [[Request(0.0, FILTER, narrow(j, k)) for j in range(k)]
               for k in range(1, most((FILTER,)) + 1)]
    scalar_ops = sorted({op for ops in ops_of for op in ops
                         if op in SCALAR_AGGS})
    sums = [True, False] if "sum" in scalar_ops else [False]
    plain = [op for op in scalar_ops if op != "sum"] or ["count"]
    for k in range(1, most(SCALAR_AGGS) + 1):
        for with_sum in sums:
            ops = (["sum"] if with_sum else []) + plain * k
            batches.append([Request(0.0, ops[j], narrow(j, k))
                            for j in range(k)])
    groups = sorted({(int(s.get("prefix_len", 0)), s.get("pred") is None)
                     for s in slots if s["op"] == "group_count"})
    for plen, whole in groups:
        for j in range(1 if whole else n_positions):
            batches.append([Request(0.0, "group_count",
                                    None if whole else narrow(j, n_positions),
                                    plen)])
    return batches
