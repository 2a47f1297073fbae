"""Dashboard rendering + specialized views (paper §4.4)."""

import numpy as np

from repro.core.aggregator import MetricStore
from repro.core.daemon import JobManifest
from repro.core.derived import TPU_V5E
from repro.core.dashboards import (JobPoint, job_metric_series,
                                   job_statistical_view, markdown_table,
                                   render_roofline_svg,
                                   render_timeseries_svg, roofline_points,
                                   view_idle_accelerators,
                                   view_low_participation,
                                   view_memory_underuse,
                                   view_top_apps_by_device_hours)
from repro.core.schema import MetricRecord


def build_store():
    store = MetricStore()
    manifests = {}
    for j, (app, g, frac) in enumerate([
            ("gemma2-27b", 900.0, 0.7), ("qwen3-8b", 300.0, 0.6),
            ("idle-app", 50.0, 0.01)]):
        job = f"j{j}"
        manifests[job] = JobManifest(job_id=job, app=app, num_hosts=2,
                                     num_chips=8,
                                     extra={"large_memory": "1"})
        for h in range(2):
            for s in range(10):
                store.insert(MetricRecord(
                    1000.0 + s * 60, f"n{j}{h}", job, "perf",
                    {"gflops": g + s, "gflops_per_chip": (g + s) / 8,
                     "ai": 10.0 + j, "mfu": 0.4, "step_time_s": 1.0}))
                store.insert(MetricRecord(
                    1000.0 + s * 60, f"n{j}{h}", job, "device",
                    {"hbm_frac_used": frac, "local_devices": 4}))
        store.insert(MetricRecord(1000.0, f"n{j}0", job, "meta",
                                  {"app": app}))
    return store, manifests


def test_roofline_points_and_svg():
    store, manifests = build_store()
    pts = roofline_points(store, manifests)
    assert len(pts) == 3
    svg = render_roofline_svg(pts, TPU_V5E)
    assert svg.startswith("<svg") and svg.count("<circle") >= 3
    assert "GFLOP/s per chip" in svg
    # empty store still renders axes
    assert render_roofline_svg([], TPU_V5E).startswith("<svg")


def test_timeseries_svg():
    series = {"n0": [(0.0, 1.0), (60.0, 2.0)], "n1": [(0.0, 1.5)]}
    svg = render_timeseries_svg(series, "t", "gflops")
    assert "<polyline" in svg
    assert render_timeseries_svg({}, "t", "y").count("no data") == 1


def test_job_series_and_statistical_view():
    store, _ = build_store()
    series = job_metric_series(store, "j0", "gflops")
    assert set(series) == {"n00", "n01"} and len(series["n00"]) == 10
    stat = job_statistical_view(store, "j0", "gflops", span_s=60)
    assert set(stat) == {"min", "median", "max"}
    for b_min, b_med, b_max in zip(stat["min"], stat["median"],
                                   stat["max"]):
        assert b_min[1] <= b_med[1] <= b_max[1]


def test_specialized_views():
    store, manifests = build_store()
    top = view_top_apps_by_device_hours(store, manifests)
    assert top and top[0]["device_hours"] >= top[-1]["device_hours"]
    idle = view_idle_accelerators(store)
    assert [r["job"] for r in idle] == ["j2"]
    mem = view_memory_underuse(store, manifests)
    assert [r["job"] for r in mem] == ["j2"]
    # every host reports work -> no low-participation rows
    assert view_low_participation(store, manifests) == []


def test_markdown_table():
    md = markdown_table([{"a": 1, "b": 2.5}, {"a": 3, "b": 4.0}])
    assert md.count("|") > 6 and "2.5" in md
    assert markdown_table([]) == "*(empty)*\n"
