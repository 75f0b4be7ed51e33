"""The bytes a launch must move come from the snapshot's entries and
dictionary sizes, never from the program's pack width or tile size."""

import pytest

from bench import peaks, roofline_work as rw


def _layout(width, tiles, dict_size=30_000, entries=750_000, shards=4):
    return [[{"level": 1, "entries": entries, "dict": dict_size,
              "width": width, "tiles": tiles}] for _ in range(shards)]


def test_code_bits():
    assert rw.code_bits(30_000) == 15
    assert rw.code_bits(3_750) == 12
    assert rw.code_bits(2) == 1 and rw.code_bits(1) == 1


@pytest.mark.parametrize("k", [1, 12, 16])
def test_bytes_do_not_depend_on_pack_width_or_tiles(k):
    counts = set()
    for width, tiles in ((16, 367), (32, 733), (16, 92), (8, 184)):
        runs = rw._launch_runs(_layout(width, tiles))
        counts.add((rw.filter_launch_bytes(runs, k), rw.agg_launch_bytes(runs)))
    assert counts == {(750_000 * 15 / 8 + k * 750_000 / 8, 750_000 * 15 / 8)}


def test_shards_of_different_sizes_give_no_per_launch_bytes():
    lay = _layout(16, 367)
    lay[2][0]["entries"] = 700_000
    assert rw._launch_runs(lay) is None


def test_roofline_share_and_unknown_device():
    class B:  # one batch: 4 filter launches of K = 12, 8 agg launches
        t1, filter_launches, agg_launches, n_filters = 1.0, 4, 8, 12
        agg_filter_launches, n_aggs = 0, 4
    need = 4 * rw.filter_launch_bytes([(750_000, 30_000)], 12)
    ctx = {"trace": {"modules": {"jit_fused_zone_filter_2d": need / 819e9 * 2,
                                 "jit_fused_zone_agg_2d": 1.0}},
           "layout": _layout(16, 367), "traced": (0.0, 2.0), "batches": [B()],
           "device_kind": "TPU v5 lite"}
    assert rw.roofline_pct(ctx, "filter") == pytest.approx(50.0)
    ctx["device_kind"] = "TPU v9 imaginary"
    with pytest.raises(KeyError):
        rw.roofline_pct(ctx, "filter")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
