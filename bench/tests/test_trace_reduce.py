"""The reduction from a profiler trace to busy time, program time, top
device ops and attributed idle gaps: on a hand-built trace with known
answers, and on a small trace recorded on a TPU v5e."""

import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * 1e6, duration_ns=dur_ms * 1e6,
              stats=[])


def fake_profile():
    host = NS(name="/host:CPU", stats=[], lines=[NS(name="main", events=[
        ev("bench.window", 0, 100),
        ev("server.step", 10, 50),
        ev("engine.filter_many", 12, 30),
        ev("serve.wait", 70, 30),
    ])])
    ops = NS(name="XLA Ops", events=[ev("fusion.1", 20, 5), ev("kernel", 23, 7),
                                     ev("fusion.1", 45, 5), ev("copy", 120, 4)])
    mods = NS(name="XLA Modules", events=[
        ev("jit_fused_zone_filter_2d(7)", 20, 10),
        ev("jit_fused_zone_agg_2d(9)", 45, 5)])
    tpu = NS(name="/device:TPU:0", stats=[], lines=[ops, mods])
    other = NS(name="/device:TPU:0 SparseCore", stats=[], lines=[ops])
    return NS(planes=[host, tpu, other])


def test_reduce_hand_built_trace():
    r = trace_reduce.reduce_profile(fake_profile())
    assert r["window_s"] == pytest.approx(0.100)
    # ops [20, 30) and [45, 50) inside the window; the copy lies outside
    assert r["busy_s"] == pytest.approx(0.015)
    assert r["n_device_planes"] == 1
    assert r["modules"] == {"jit_fused_zone_filter_2d": pytest.approx(0.010),
                            "jit_fused_zone_agg_2d": pytest.approx(0.005)}
    assert r["device_ops"][0][0] in ("fusion.1", "kernel")
    gaps = dict(r["idle_gaps"])
    # [0,20): midpoint 10 on the step's edge; [30,45) inside filter_many
    # until 42 -> midpoint 37.5 in filter_many; [50,100) midpoint 75 in wait
    assert gaps["server.step"] == pytest.approx(0.020)
    assert gaps["engine.filter_many"] == pytest.approx(0.015)
    assert gaps["serve.wait"] == pytest.approx(0.050)
    assert sum(gaps.values()) == pytest.approx(0.085)


def test_union_and_clip():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == \
        [(0, 3), (5, 9)]
    assert trace_reduce._clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_recorded_tpu_trace():
    import jax

    path = DATA / "fixture.xplane.pb"
    meta = json.loads((DATA / "fixture.json").read_text())
    r = trace_reduce.reduce_profile(jax.profiler.ProfileData.from_file(str(path)))
    assert r["n_device_planes"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    names = " ".join(r["modules"])
    for program in ("fused_zone_filter_2d", "fused_zone_agg_2d",
                    "zone_histogram_2d"):
        assert program in names
    assert r["device_ops"] and r["idle_gaps"]
    assert meta["launches"]["filter"] == 3
