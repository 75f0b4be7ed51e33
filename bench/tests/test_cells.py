"""Every cell runs end to end at its rehearsal size and agrees with the
reference; its line holds the contract's keys."""

import pytest

from bench import harness, traffic
from bench.tests.helpers import BENCH, CELLS, SEED, rehearsal, run

LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_agrees_with_reference(workload):
    res = run(workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["checked"]["value"] > 0
    assert list(res)[:5] == LINE_KEYS and list(res)[-1] == "checks"
    cell = {w["name"]: w for w in BENCH["workloads"]}[workload]
    want = {m["name"] for m in harness.metric_names(BENCH, cell, "end_to_end")}
    assert set(res["metrics"]) == want
    assert res["diag"]["compile_s"] == 0.0, "a shape compiled in the window"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])


def test_traced_line_has_per_layer_metrics():
    res = run(CELLS[0], trace=True)
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELLS[0]]
    names = {m["name"] for m in harness.metric_names(BENCH, cell, "per_layer")}
    assert set(res["metrics"]) <= names
    # host and program readings exist on any backend; device ones need a TPU
    assert {"server.self_ms", "exec.filter_stage_ms",
            "jit.compile_s"} <= set(res["metrics"])
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_layout_same_for_two_seeds(workload, tmp_path):
    _, cfg, mix = rehearsal(workload)
    shapes = []
    for seed in (SEED, 12345):
        spill = tmp_path / str(seed)
        eng = harness.build_store(cfg, harness.generate_data(cfg, seed),
                                  str(spill))
        try:
            shapes.append([[(r["level"], r["entries"], r["width"], r["tiles"])
                            for r in shard] for shard in harness.layout(eng)])
        finally:
            eng.close()
    assert shapes[0] == shapes[1]
    assert len({tuple(s) for s in shapes[0]}) == 1, "shards differ in shape"


@pytest.mark.parametrize("workload", CELLS)
def test_requests_same_sizes_for_every_seed(workload):
    _, cfg, mix = rehearsal(workload)
    block = sum(int(s["count"]) for s in mix["block"])
    # long enough for two whole blocks at the mix's rate
    seconds = 2.5 * block / mix["arrivals"]["rate_per_s"]
    a = traffic.generate(mix, cfg["labels"], SEED, seconds)
    b = traffic.generate(mix, cfg["labels"], 7, seconds)
    assert [r.due for r in a] == [r.due for r in b]
    full = len(a) // block * block  # whole blocks: the same multiset

    def sizes(reqs):
        return sorted((r.op, r.pred[0] if r.pred else None) for r in reqs)
    assert full and sizes(a[:full]) == sizes(b[:full])
    assert a != b


@pytest.mark.parametrize("workload", [w for w in CELLS
                                      if "writes" in rehearsal(w)[2]])
def test_write_cell_reads_its_writes(workload):
    res = run(workload, trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["write.put_batch_ms"]["value"] > 0
    assert res["metrics"]["exec.memtable_ms"]["value"] > 0
    diag = res["diag"]
    assert diag["write_batches"] > 0
    assert diag["window_flushes"] == 0 and diag["window_compactions"] == 0
    # the window opens on memtables that hold the prefill and the
    # steady phase's writes, and closes on more
    _, cfg, mix = rehearsal(workload)
    assert diag["prefill_rows"] == harness.prefill_rows(cfg, mix) > 0
    assert sum(diag["memtable_rows_open"]) > diag["prefill_rows"]
    assert (sum(diag["memtable_rows"]) - sum(diag["memtable_rows_open"])
            == diag["write_rows"])
    assert "write_batches" not in run("ycsb_v128.scan_agg")["diag"]


def test_prefill_fills_half_the_write_buffer():
    _, cfg, mix = harness.find_cell(BENCH, "ycsb_v128.htap")
    share = mix["writes"]["prefill_share"]
    per_row = cfg["store"]["key_bytes"] + 8 + cfg["value_width"]
    want = round(share * cfg["store"]["file_bytes"] / per_row) * cfg["shards"]
    assert share == 0.5 and harness.prefill_rows(cfg, mix) == want
    assert harness.prefill_rows(cfg, {"block": []}) == 0
