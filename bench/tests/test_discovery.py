"""A configuration, traffic mix, data generator or metric added as a
file under its directory is found by its name, with no code edited."""

import json

import pytest

import bench.data
from bench import harness


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    (tmp_path / "gens").mkdir()
    cfg = {"generator": "tiny_gen", "records": 8, "value_width": 16}
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = {"arrivals": {"kind": "periodic", "rate_per_s": 1.0}}
    (tmp_path / "bench" / "traffic" / "trickle.json").write_text(json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "tiny.answer_ms.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx['x'] else None\n")
    (tmp_path / "gens" / "tiny_gen.py").write_text(
        "def generate(cfg, seed):\n    return ('tiny', cfg['records'], seed)\n")
    spec = {"configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
            "workloads": [{"name": "tiny.trickle", "config": "tiny",
                           "traffic": "trickle", "chips": 1}]}
    cell, got_cfg, got_mix = harness.find_cell(spec, "tiny.trickle", tmp_path)
    assert got_cfg == cfg and got_mix == mix and cell["chips"] == 1
    read = harness.load_reader("tiny.answer_ms", tmp_path / "bench" / "metrics")
    assert read({"x": True}) == 42.0 and read({"x": False}) is None
    monkeypatch.setattr(bench.data, "__path__",
                        [*bench.data.__path__, str(tmp_path / "gens")])
    assert bench.data.generate(cfg, 3) == ("tiny", 8, 3)


def test_every_named_metric_has_a_reader():
    spec = harness.load_benchmark()
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] != "setup_s":
            assert callable(harness.load_reader(m["name"])), m["name"]


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        harness.find_cell(harness.load_benchmark(), "no.such_cell")
