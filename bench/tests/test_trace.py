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


def test_collective_time_by_opcode_averaged_over_devices():
    from harness import cells
    d0 = [("%while.1 = (s32[]) while(...)", 0, 80 * MS),
          ("%all-gather-start.3 = (bf16[4]) all-gather-start(...)",
           5 * MS, 2 * MS),                               # nested in while
          ("%all-gather-done.4 = bf16[16] all-gather-done(...)",
           10 * MS, 6 * MS),
          ("%fusion.7 = bf16[4] fusion(...)", 20 * MS, 30 * MS),
          ("collective-permute-done.2", 60 * MS, 4 * MS),
          ("async-collective-start", 70 * MS, 1 * MS),
          ("%all-reduce.9 = f32[] all-reduce(...)", 85 * MS, 3 * MS),
          ("%reduce-scatter.5 = f32[4] reduce-scatter(...)", 95 * MS, 2 * MS),
          ("%all-to-all.6 = f32[4] all-to-all(...)", 120 * MS, 9 * MS)]
    d1 = [("%all-gather.8 = bf16[16] all-gather(...)", 0, 1 * MS),
          ("%copy-start.1 = (f32[4]) copy-start(...)", 10 * MS, 5 * MS),
          ("%fusion.all-gather.2 = bf16[4] fusion(...)", 30 * MS, 5 * MS)]
    host = [("bench.window", 0, 100 * MS),
            ("bench.dispatch", 0, 1 * MS), ("bench.dispatch", 50 * MS, MS)]
    r = trace.reduce({"a": d0, "b": d1}, host)
    # a: 2 + 6 + 4 + 1 + 3 + 2 (the all-to-all lies outside the window);
    # b: 1 (a copy and a fusion are not collectives)
    assert r["collective_s"] == pytest.approx((0.018 + 0.001) / 2)
    ms = cells.find("hymba-1.5b-32l.train-4chip").metric_reader(
        "collective_device_ms").read({"trace": r})
    assert ms == pytest.approx(1e3 * r["collective_s"] / 2)


def test_collective_reader_without_a_trace_reads_nothing():
    from harness import cells
    reader = cells.find("hymba-1.5b-32l.train-4chip").metric_reader(
        "collective_device_ms")
    assert reader.read({"trace": {}}) is None
    assert reader.read({"trace": trace.reduce(
        {"a": [("x", 0, MS)]}, [("bench.window", 0, MS)])}) is None
