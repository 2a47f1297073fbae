"""The trace reduction on a small synthetic trace."""

import pytest

from harness import trace

MS = 1_000_000  # ns


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_idle_and_gap_attribution():
    device = {"/device:TPU:0": [("fusion.1", 10 * MS, 40 * MS),
                                ("fusion.2", 20 * MS, 10 * MS),   # nested
                                ("copy.3", 60 * MS, 30 * MS),
                                ("outside", 150 * MS, 10 * MS)]}  # clipped
    host = [("bench.window", 0, 100 * MS),
            ("bench.input", 0, 8 * MS),
            ("bench.on_step", 50 * MS, 10 * MS),
            ("bench.dispatch", 10 * MS, 2 * MS),
            ("other", 0, 100 * MS)]
    r = trace.reduce(device, host)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.07)          # 10..50 and 60..90
    assert r["idle_share"] == pytest.approx(0.3)
    assert r["spans"]["bench.input"] == {"seconds": pytest.approx(0.008),
                                         "count": 1}
    assert "other" not in r["spans"] and "bench.window" not in r["spans"]
    assert sorted(n for n, _ in r["idle_gaps"]) == [
        "bench.input", "bench.on_step", "host"]
    assert [g for _, g in r["idle_gaps"]] == pytest.approx([0.01] * 3)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.03)
    assert ops["fusion.2"] == pytest.approx(0.01)
    assert ops["copy.3"] == pytest.approx(0.03)
    assert "outside" not in ops


def test_busy_is_averaged_over_devices():
    device = {"a": [("x", 0, 50 * MS)], "b": [("x", 0, 100 * MS)]}
    r = trace.reduce(device, [("bench.window", 0, 100 * MS)])
    assert r["busy_s"] == pytest.approx(0.075)
    assert r["idle_share"] == pytest.approx(0.25)


def test_nothing_to_read_gives_nothing():
    assert trace.reduce({}, [("bench.window", 0, MS)]) == {}
    assert trace.reduce({"a": [("x", 0, MS)]}, []) == {}


def test_nested_operations_count_once():
    device = {"d": [("%while.1 = (s32[]) while(...)", 0, 100 * MS),
                    ("%fusion.2 = bf16[4] fusion(...)", 10 * MS, 30 * MS),
                    ("%fusion.2 = bf16[4] fusion(...)", 50 * MS, 20 * MS)]}
    r = trace.reduce(device, [("bench.window", 0, 100 * MS)])
    ops = dict(r["device_ops"])
    assert ops["fusion.2"] == pytest.approx(0.05)
    assert ops["while.1"] == pytest.approx(0.05)
    assert r["busy_s"] == pytest.approx(0.1)
