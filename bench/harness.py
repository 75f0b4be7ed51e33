"""One run of one cell: build the store, warm its shapes, serve the
window, check the answers, read the metrics.

``run_cell`` does the work and returns the result line as a dict; the
command-line entry (``bench/run.py``) only parses arguments, points
JAX's compilation cache into the checkout, looks for the chip and
prints; it imports this module after the first two.

Served path: ``ScanServer`` over ``ShardedLSM`` as the configuration
states it (``store`` keys are ``LSMConfig`` fields).  The window is an
open loop in one thread: every request is due at a time fixed by the
mix; whenever the queue holds requests the loop calls
``ScanServer.step`` (one batched ``filter_many`` + ``aggregate_many``
against one pinned snapshot), and a request's latency runs from the
time it was due to the end of the step that answered it.  Requests
still queued when the window closes are served after it, up to a
minute, and count with their wait.

Writes (a mix's ``writes``) are served in the same loop: every batch
that has fallen due goes through ``put_batch`` before the next step, so
each step's pinned snapshot holds exactly the batches applied before
it.  A batch's delay runs from its due time to the return of
``put_batch``, its acknowledgement.  Writes are interleaved between
steps, not concurrent with them: a batch due during a step waits for
it.  After warm-up, which writes nothing, set-up fills the memtables to
the mix's ``prefill_share`` of their flush size (untimed, through
``put_batch``); the steady phase before the window then applies a write
stream of its own.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from repro.core import LSMConfig, Predicate
from repro.query import AggSpec, GroupBy
from repro.serving.scan_server import ScanServer
from repro.shard import ShardedLSM

from bench import traffic as traffic_mod
from bench.data import generate as generate_data
from bench.oracle import Oracle, same_answer

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
LATE_LIMIT_S = 60.0
COMPILE_EVENTS = ("/jax/core/compile/", "/jax/compilation_cache/cache_retrieval")


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, workload: str, root: Path = ROOT):
    """The cell, its configuration file and its traffic mix, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    mix = traffic_mod.load(cell["traffic"], root / "bench" / "traffic")
    return cell, cfg, mix


def metric_names(bench: dict, cell: dict, kind: str) -> List[dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]


# --------------------------------------------------------------------------- #
# store
# --------------------------------------------------------------------------- #
def build_store(cfg: dict, data, spill_dir: Optional[str]):
    """Load the records through ``put_batch``, wait for maintenance,
    then ``compact_all`` so every shard is one compacted level."""
    lcfg = LSMConfig(value_width=int(cfg["value_width"]), **cfg["store"])
    eng = ShardedLSM(lcfg, n_shards=int(cfg["shards"]), key_max=data.key_max,
                     spill_dir=spill_dir)
    n, chunk = data.keys.shape[0], int(cfg["load_chunk"])
    for lo in range(0, n, chunk):
        hi = min(n, lo + chunk)
        eng.put_batch(data.keys[lo:hi], data.values(lo, hi))
    eng.drain()
    eng.raise_maintenance_errors()
    eng.compact_all()
    eng.raise_maintenance_errors()
    return eng


def prefill_rows(cfg: dict, mix: dict) -> int:
    """Updates that fill each shard's memtable to the mix's
    ``prefill_share`` of its flush size: the store's write buffer over
    the bytes a version takes in the memtable's count (key, seqno,
    value), times the shards."""
    share = float(mix.get("writes", {}).get("prefill_share", 0.0))
    lcfg = LSMConfig(value_width=int(cfg["value_width"]), **cfg["store"])
    per_row = lcfg.key_bytes + 8 + lcfg.value_width
    return int(round(share * lcfg.mem_bytes / per_row)) * int(cfg["shards"])


def layout(eng) -> List[List[dict]]:
    """Per shard, the runs a scan launches over: level, entries,
    dictionary size, pack width and 1,024-word tiles."""
    out = []
    for snap in eng.snapshot().snaps:
        runs = []
        for s in snap.runs:
            if s.n:
                words = s.packed.shape[0] if s.packed is not None else 0
                runs.append({"level": int(s.level), "entries": int(s.n),
                             "dict": int(s.opd.size) if s.opd is not None else 0,
                             "width": int(s.code_bits),
                             "tiles": -(-words // 1024)})
        out.append(runs)
    return out


# --------------------------------------------------------------------------- #
# requests -> program objects
# --------------------------------------------------------------------------- #
def to_program(req):
    pred = None if req.pred is None else Predicate(*req.pred)
    if req.op == traffic_mod.FILTER:
        return pred
    if req.op == "group_count":
        return AggSpec("group_count", pred=pred,
                       group=GroupBy("prefix", prefix_len=req.prefix_len))
    return AggSpec(req.op, pred=pred)


def submit(server, req) -> int:
    obj = to_program(req)
    if req.op == traffic_mod.FILTER:
        return server.submit(obj)
    return server.submit_agg(obj)


def plain_answer(req, res):
    if req.op == traffic_mod.FILTER:
        return (res.keys, res.values)
    return res.value


@dataclasses.dataclass
class Batch:
    t0: float
    t1: float
    n_filters: int
    n_aggs: int
    cpu_s: float = 0.0      # process CPU time over the step, all threads
    filter_s: float = 0.0
    agg_s: float = 0.0
    filter_launches: int = 0
    agg_launches: int = 0
    agg_filter_launches: int = 0    # aggregates' general path (memtable rows)


class TimedEngine:
    """The engine as ``ScanServer`` sees it, with the benchmark's spans
    around the two calls into the shard layer."""

    def __init__(self, eng):
        self.eng = eng
        self._annotate = jax.profiler.TraceAnnotation
        self.filter_s = 0.0
        self.agg_s = 0.0

    def snapshot(self):
        return self.eng.snapshot()

    def raise_maintenance_errors(self):
        self.eng.raise_maintenance_errors()

    def filter_many(self, preds, snapshot=None):
        t0 = time.perf_counter()
        with self._annotate("engine.filter_many"):
            out = self.eng.filter_many(preds, snapshot=snapshot)
        self.filter_s += time.perf_counter() - t0
        return out

    def aggregate_many(self, specs, snapshot=None):
        t0 = time.perf_counter()
        with self._annotate("engine.aggregate_many"):
            out = self.eng.aggregate_many(specs, snapshot=snapshot)
        self.agg_s += time.perf_counter() - t0
        return out

    def put_batch(self, keys, values):
        with self._annotate("engine.put_batch"):
            self.eng.put_batch(keys, values)


class Writer:
    """A write stream applied through ``put_batch`` as its batches fall
    due: item i of a batch is the i-th smallest loaded key, its value
    ``vocab[id]``.  ``applied`` counts the batches acknowledged."""

    def __init__(self, timed, batches: Sequence[traffic_mod.WriteBatch],
                 sorted_keys: np.ndarray, vocab: np.ndarray):
        self.timed = timed
        self.batches = list(batches)
        self.sorted_keys = sorted_keys
        self.vocab = vocab
        self.applied = 0
        self.put_s: List[float] = []
        self.delay_s: List[float] = []

    def next_due(self) -> float:
        """Seconds after window start of the next batch (inf: none)."""
        if self.applied < len(self.batches):
            return self.batches[self.applied].due
        return float("inf")

    def apply_due(self, t0: float) -> None:
        while t0 + self.next_due() <= time.perf_counter():
            b = self.batches[self.applied]
            keys, values = self.sorted_keys[b.items], self.vocab[b.ids]
            t = time.perf_counter()
            self.timed.put_batch(keys, values)
            t1 = time.perf_counter()
            self.put_s.append(t1 - t)
            self.delay_s.append(t1 - (t0 + b.due))
            self.applied += 1


def _step(server, timed, eng, annotate) -> tuple:
    """One ``ScanServer.step`` with its batch record."""
    fl0 = eng.filter_stats.counts["fused_launches"]
    al0 = eng.agg_stats.counts["agg_launches"]
    afl0 = eng.agg_stats.counts["fused_launches"]
    f0, a0 = timed.filter_s, timed.agg_s
    slots = server.queue[:server.max_batch]
    n_f = sum(1 for r in slots if hasattr(r, "pred"))
    c0 = time.process_time()
    t0 = time.perf_counter()
    with annotate("server.step"):
        out = server.step()
    t1 = time.perf_counter()
    b = Batch(t0, t1, n_f, len(slots) - n_f, time.process_time() - c0,
              timed.filter_s - f0,
              timed.agg_s - a0,
              eng.filter_stats.counts["fused_launches"] - fl0,
              eng.agg_stats.counts["agg_launches"] - al0,
              eng.agg_stats.counts["fused_launches"] - afl0)
    return out, b


def warm_up(server, timed, eng, mix, cfg) -> int:
    """Serve the warm-up batches: every shape the window can launch is
    compiled (or loaded from the cache) before the window opens."""
    batches = traffic_mod.warmup_batches(mix, cfg["labels"],
                                         2 * int(cfg["shards"]))
    for batch in batches:
        for req in batch:
            submit(server, req)
        while server.queue:
            _step(server, timed, eng, jax.profiler.TraceAnnotation)
    return len(batches)


# --------------------------------------------------------------------------- #
# window
# --------------------------------------------------------------------------- #
class CompileClock:
    """Seconds JAX spends tracing, lowering, compiling or loading from
    its cache while ``on`` is set (``jax.monitoring`` listener)."""

    def __init__(self):
        self.on = False
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event: str, duration: float, **_kw) -> None:
        if self.on and event.startswith(COMPILE_EVENTS):
            self.seconds += float(duration)
            self.events += 1


class GcClock:
    """Pauses of Python's garbage collector while ``on`` is set: total
    and longest seconds, and the number of full (generation 2) passes."""

    def __init__(self):
        self.on = False
        self.seconds = self.longest = 0.0
        self.full = 0
        self._t0 = 0.0
        gc.callbacks.append(self._hear)

    def reset(self) -> None:
        self.seconds = self.longest = 0.0
        self.full = 0

    def _hear(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.on:
            dt = time.perf_counter() - self._t0
            self.seconds += dt
            self.longest = max(self.longest, dt)
            self.full += info.get("generation") == 2


def serve_window(server, timed, eng, requests, seconds: float, keep: set,
                 trace_dir: Optional[str], clock: CompileClock,
                 writer: Writer):
    """Serve every request due in [0, seconds) open loop, and apply the
    writer's batches as they fall due.  Returns the per-request latency
    (s, or None if never answered), the kept answers, the number of
    write batches applied before the step that answered each kept
    request, the in-window batch records and the traced window."""
    annotate = jax.profiler.TraceAnnotation
    lat: List[Optional[float]] = [None] * len(requests)
    kept: Dict[int, object] = {}
    kept_writes: Dict[int, int] = {}
    batches: List[Batch] = []
    rid_of: Dict[int, int] = {}
    i = 0
    traced = None
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    window_span = annotate("bench.window")
    window_span.__enter__()
    clock.seconds, clock.events, clock.on = 0.0, 0, True
    t0 = time.perf_counter()
    end = t0 + seconds
    late_end = end + LATE_LIMIT_S
    while True:
        now = time.perf_counter()
        while i < len(requests) and t0 + requests[i].due <= now:
            rid_of[submit(server, requests[i])] = i
            i += 1
        writer.apply_due(t0)
        if now >= end and traced is None:
            window_span.__exit__(None, None, None)
            traced = (t0, now)
            if trace_dir is not None:
                jax.profiler.stop_trace()
        if (now >= end and not server.queue) or now >= late_end:
            break
        if server.queue:
            out, b = _step(server, timed, eng, annotate)
            batches.append(b)
            for rid, res in out.items():
                j = rid_of.pop(rid)
                lat[j] = b.t1 - (t0 + requests[j].due)
                if j in keep:
                    kept[j] = plain_answer(requests[j], res)
                    kept_writes[j] = writer.applied
        elif i < len(requests) or writer.next_due() < seconds:
            due = min(requests[i].due if i < len(requests) else seconds,
                      writer.next_due())
            wait = min(t0 + due, end) - time.perf_counter()
            if wait > 0:
                with annotate("serve.wait"):
                    time.sleep(wait)
        else:
            with annotate("serve.wait"):
                time.sleep(max(0.0, end - time.perf_counter()))
    clock.on = False
    return lat, kept, kept_writes, batches, (t0, end), traced


# --------------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------------- #
def _stage_counts(eng) -> dict:
    fs, ag = eng.filter_stats, eng.agg_stats
    return {"filter_s": fs.seconds.get("filter", 0.0),
            "merge_s": fs.seconds.get("merge", 0.0),
            # the memtable scans of filters and of the aggregates'
            # general path
            "memtable_s": (fs.seconds.get("memtable", 0.0)
                           + ag.seconds.get("memtable", 0.0)),
            "zone_tiles_total": fs.counts.get("zone_tiles_total", 0),
            "zone_tiles_skipped": fs.counts.get("zone_tiles_skipped", 0),
            "fused_launches": fs.counts.get("fused_launches", 0),
            "agg_launches": ag.counts.get("agg_launches", 0)}


@dataclasses.dataclass
class Writes:
    """What a run wrote: the prefill and the steady phase's batches, all
    applied before the window, then the window's, with the number of
    them applied before the step that answered each kept request."""
    prefill: Optional[traffic_mod.WriteBatch] = None
    steady: Sequence[traffic_mod.WriteBatch] = ()
    window: Sequence[traffic_mod.WriteBatch] = ()
    kept: Dict[int, int] = dataclasses.field(default_factory=dict)
    sorted_keys: Optional[np.ndarray] = None


def replay(oracles, kept: dict, writes: Writes):
    """Walk the kept requests in order of the window batches applied
    before their step, bringing the references to that state first:
    the load, the prefill, every steady-phase batch, then the window's
    in order.  Yields each kept request's index once its state is
    reached, so one reference per distinct state is built, each from
    the last."""
    def apply(b):
        for o in oracles:
            o.apply(writes.sorted_keys[b.items], b.ids)

    if writes.prefill is not None:
        apply(writes.prefill)
    for b in writes.steady:
        apply(b)
    done = 0
    for j in sorted(kept, key=lambda j: (writes.kept.get(j, 0), j)):
        while done < writes.kept.get(j, 0):
            apply(writes.window[done])
            done += 1
        yield j


def check_answers(data, requests, kept: dict, lat: list,
                  writes: Optional[Writes] = None) -> dict:
    """Compare the kept answers with the reference over the load plus
    exactly the write batches applied before each answer's step; count
    the due requests that were never answered."""
    oracle = Oracle(data.keys, data.ids, data.vocab)
    wrong = 0
    for j in replay([oracle], kept, writes or Writes()):
        if not same_answer(kept[j], oracle.answer(requests[j])):
            wrong += 1
    unanswered = sum(1 for x in lat if x is None)
    return {"wrong_answers": wrong, "unanswered": unanswered,
            "checked": len(kept)}


def load_reader(name: str, metrics_dir: Path = BENCH / "metrics"):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``."""
    path = metrics_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(names: List[dict], ctx: dict) -> Dict[str, dict]:
    """Each metric its reader finds something for; ``setup_s`` is the
    harness's own."""
    out = {}
    for m in names:
        if m["name"] == "setup_s":
            value = ctx["setup_s"]
        else:
            value = load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(bench: dict, cell: dict, cfg: dict, mix: dict, seed: int,
             seconds: float, trace: bool, t_start: float,
             rate_per_s: Optional[float] = None,
             control: bool = False) -> dict:
    """Everything after the look for a chip; returns the result line."""
    dev = jax.devices()[0]
    clock = CompileClock()
    gc_clock = GcClock()
    clock.on = True
    data = generate_data(cfg, seed)
    requests = traffic_mod.generate(mix, cfg["labels"], seed, seconds,
                                    rate_per_s=rate_per_s)
    rng = np.random.default_rng([seed, 2])
    n_keep = min(int(mix["check"]), len(requests))
    keep = set(rng.choice(len(requests), n_keep, replace=False).tolist())
    writes = Writes()
    if "writes" in mix:
        writes.sorted_keys = np.sort(data.keys)
        ndv = data.vocab.shape[0]
        writes.prefill = traffic_mod.prefill(
            mix, prefill_rows(cfg, mix), data.keys.shape[0], ndv, seed)
        writes.steady = traffic_mod.write_batches(
            mix, data.keys.shape[0], ndv, seed, float(mix["warm_seconds"]), 5)
        writes.window = traffic_mod.write_batches(
            mix, data.keys.shape[0], ndv, seed, seconds, 4)
    spill = tempfile.mkdtemp(prefix="bench_spill_")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    phases = {"data_s": time.perf_counter() - t_start}
    try:
        t = time.perf_counter()
        eng = build_store(cfg, data, spill)
        phases["load_s"] = time.perf_counter() - t
        try:
            timed = TimedEngine(eng)
            server = ScanServer(timed, max_batch=int(mix["max_batch"]))
            t = time.perf_counter()
            n_warm = warm_up(server, timed, eng, mix, cfg)
            phases["warm_s"] = time.perf_counter() - t
            phases["warm_compile_s"] = clock.seconds
            if writes.prefill is not None:
                t = time.perf_counter()
                apply_prefill(eng, writes, data.vocab, int(cfg["load_chunk"]))
                phases["prefill_s"] = time.perf_counter() - t
            t = time.perf_counter()
            steady = traffic_mod.generate(mix, cfg["labels"], seed,
                                          float(mix["warm_seconds"]),
                                          rate_per_s=rate_per_s, stream=3)
            serve_window(server, timed, eng, steady,
                         float(mix["warm_seconds"]), set(), None, clock,
                         Writer(timed, writes.steady, writes.sorted_keys,
                                data.vocab))
            phases["steady_s"] = time.perf_counter() - t
            shape = layout(eng)
            memtables_open = memtable_rows(eng)
            before = _stage_counts(eng)
            setup_s = time.perf_counter() - t_start
            shape_before = eng.shape_report()
            gc_clock.reset()
            gc_clock.on = True
            writer = Writer(timed, writes.window, writes.sorted_keys,
                            data.vocab)
            lat, kept, writes.kept, batches, window, traced = serve_window(
                server, timed, eng, requests, seconds, keep, trace_dir, clock,
                writer)
            gc_clock.on = False
            after = _stage_counts(eng)
            memtables = memtable_rows(eng)
            mem = dev.memory_stats() or {}
            shape_report = eng.shape_report()
        finally:
            eng.close()
        reduced = None
        if trace:
            from bench.trace_reduce import reduce_trace
            reduced = reduce_trace(trace_dir)
    finally:
        shutil.rmtree(spill, ignore_errors=True)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    del eng, server, timed
    gc.callbacks.remove(gc_clock._hear)

    t = time.perf_counter()
    checks = check_answers(data, requests, kept, lat, writes)
    phases["check_s"] = time.perf_counter() - t
    result = {
        "correct": checks["wrong_answers"] == 0 and checks["unanswered"] == 0,
        "attempted": len(requests),
        "failed": checks["wrong_answers"] + checks["unanswered"],
    }
    answered = np.asarray([x for x in lat if x is not None], np.float64)
    ctx = {"cfg": cfg, "mix": mix, "requests": requests, "latency_s": answered,
           "batches": batches, "window": window, "before": before,
           "after": after, "layout": shape, "trace": reduced,
           "compile_s": clock.seconds, "compile_events": clock.events,
           "device_kind": dev.device_kind, "setup_s": setup_s,
           "traced": traced, "writer": writer}
    kind = "per_layer" if trace else "end_to_end"
    metrics = read_metrics(metric_names(bench, cell, kind), ctx)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    result["metrics"] = metrics
    result["device"] = device
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    # where stalls are: the longest step, when in the window it began,
    # the CPU the process spent over it (far below its length: the
    # process waited or was descheduled), and the collector's pauses
    longest = max(batches, key=lambda x: x.t1 - x.t0,
                  default=Batch(window[0], window[0], 0, 0))
    step_ms = [(x.t1 - x.t0) * 1e3 for x in batches] or [0.0]
    result["diag"] = {**phases, "warmup_batches": n_warm,
                      "batches": len(batches),
                      "compile_s": clock.seconds,
                      "compile_events": clock.events,
                      "n_flushes": shape_report["n_flushes"],
                      "n_compactions": shape_report["n_compactions"],
                      "window_flushes": (shape_report["n_flushes"]
                                         - shape_before["n_flushes"]),
                      "window_compactions": (shape_report["n_compactions"]
                                             - shape_before["n_compactions"]),
                      "max_step_ms": (longest.t1 - longest.t0) * 1e3,
                      "max_step_at_s": longest.t0 - window[0],
                      "max_step_cpu_ms": longest.cpu_s * 1e3,
                      "max_step_requests": longest.n_filters + longest.n_aggs,
                      "median_step_ms": float(np.median(step_ms)),
                      "mean_step_ms": float(np.mean(step_ms)),
                      "gc_ms": gc_clock.seconds * 1e3,
                      "gc_max_ms": gc_clock.longest * 1e3,
                      "gc_full": gc_clock.full,
                      "layout": shape}
    if writes.window:
        result["diag"].update(_write_diag(requests, lat, writer, seconds,
                                          memtables_open, memtables))
        result["diag"]["prefill_rows"] = (0 if writes.prefill is None else
                                          int(writes.prefill.items.shape[0]))
    if control:
        # the control's answers in the program's place
        result["control"] = {"wrong_answers": _control_wrong(data, requests,
                                                             kept, writes),
                             "checked": checks["checked"]}
    result["checks"] = {
        "wrong_answers": {"value": checks["wrong_answers"], "limit": 0},
        "unanswered": {"value": checks["unanswered"], "limit": 0},
        "checked": {"value": checks["checked"], "limit": "at least 1"}}
    if checks["checked"] < 1:
        result["correct"] = False
    return result


def apply_prefill(eng, writes: Writes, vocab: np.ndarray,
                  chunk: int) -> None:
    """The prefill's updates through ``put_batch``, in order, ``chunk``
    rows a call."""
    b = writes.prefill
    keys, values = writes.sorted_keys[b.items], vocab[b.ids]
    for lo in range(0, keys.shape[0], chunk):
        eng.put_batch(keys[lo:lo + chunk], values[lo:lo + chunk])
    eng.raise_maintenance_errors()


def memtable_rows(eng) -> List[int]:
    """Versions each shard's memtables hold."""
    return [sum(m.n_versions for m in snap.mems)
            for snap in eng.snapshot().snaps]


def _write_diag(requests, lat, writer: Writer, seconds: float,
                memtables_open, memtables) -> dict:
    """Whether the window sustained its load: the write delays and the
    scan tail in each fifth of it, and what the memtables hold
    (versions, per shard) when it opens and when it closes."""
    due = np.asarray([r.due for r in requests])
    answered = np.asarray([x is not None for x in lat])
    ms = np.asarray([x if x is not None else np.nan for x in lat]) * 1e3
    wdue = np.asarray([b.due for b in writer.batches[:writer.applied]])
    delay = np.asarray(writer.delay_s) * 1e3
    rows = sum(b.items.shape[0] for b in writer.batches[:writer.applied])

    def part(lo, hi):
        sel = answered & (due >= lo) & (due < hi)
        wsel = (wdue >= lo) & (wdue < hi)
        return (float(np.percentile(ms[sel], 95)) if sel.any() else None,
                float(delay[wsel].mean()) if wsel.any() else None)

    edges = np.linspace(0.0, seconds, 6)
    parts = [part(lo, hi) for lo, hi in zip(edges[:-1], edges[1:])]
    return {"write_batches": writer.applied, "write_rows": rows,
            "write_delay_mean_ms": float(delay.mean()) if rows else None,
            "write_delay_max_ms": float(delay.max()) if rows else None,
            "write_delay_fifths_ms": [d for _, d in parts],
            "scan_p95_fifths_ms": [p for p, _ in parts],
            "memtable_rows_open": memtables_open,
            "memtable_rows": memtables}


def _control_wrong(data, requests, kept: dict,
                   writes: Optional[Writes] = None) -> int:
    ref = Oracle(data.keys, data.ids, data.vocab)
    ctl = Oracle(data.keys, data.ids, data.vocab, truncate=8)
    return sum(1 for j in replay([ref, ctl], kept, writes or Writes())
               if not same_answer(ctl.answer(requests[j]),
                                  ref.answer(requests[j])))
