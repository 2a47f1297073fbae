"""Dashboards — the Splunk-dashboard analog (paper §4.4), rendered to SVG.

Three views, exactly as in the paper:

* **Roofline view** (Fig. 2): every finished job in a time window as a
  circle on log-log (arithmetic intensity, GFLOP/s-per-chip) axes, sized
  by device-hours, under the machine roofline.
* **Detailed job view** (Fig. 3): temporal plots per metric per host,
  plus a min/median/max statistical aggregation for large jobs.
* **Specialized views**: top apps by device-hours; accelerators reserved
  but idle; large-memory underuse; low host participation — implemented
  as splunklite queries (staff "custom queries" in the paper).

Every view takes a single :class:`MetricStore` *or* a sharded store
(:class:`~repro.core.shards.ShardedAggregator`, including its
worker-process subclass
:class:`~repro.core.remote.RemoteShardedAggregator`) — ``query``
dispatches fleet queries through the scatter/gather planner and
``scan`` merges per-shard column scans, so dashboards render
identically either way: in-process, sharded, or against a remote
worker fleet (the shard- and remote-parity suites assert it).

For the paper's continuous dashboards, :class:`StreamingView` (and
:func:`streaming_specialized_views`) wrap the query-backed views in
:class:`~repro.core.splunklite.QueryHandle` refresh loops: re-rendering
after each aggregator pump recomputes only the unsealed append buffer —
sealed segments come from the segment-keyed partial-aggregate cache
(docs/incremental.md).

Rendering is dependency-free SVG string building.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.aggregator import MetricStore
from repro.core.daemon import JobManifest
from repro.core.derived import HardwareSpec
from repro.core.shards import ShardedAggregator
from repro.core.splunklite import QueryHandle, query

# RemoteShardedAggregator subclasses ShardedAggregator, so the union
# covers the worker-process fleet too
StoreLike = Union[MetricStore, ShardedAggregator]

# ------------------------------------------------------------ svg helpers ---

_SVG_HEADER = ('<svg xmlns="http://www.w3.org/2000/svg" width="{w}" '
               'height="{h}" viewBox="0 0 {w} {h}" '
               'font-family="Helvetica,Arial,sans-serif">')


def _esc(s: str) -> str:
    return (str(s).replace("&", "&amp;").replace("<", "&lt;")
            .replace(">", "&gt;").replace('"', "&quot;"))


class SvgCanvas:
    def __init__(self, w: int, h: int) -> None:
        self.w, self.h = w, h
        self.parts: List[str] = [_SVG_HEADER.format(w=w, h=h),
                                 f'<rect width="{w}" height="{h}" fill="white"/>']

    def line(self, x1, y1, x2, y2, stroke="#444", width=1.0, dash=""):
        d = f' stroke-dasharray="{dash}"' if dash else ""
        self.parts.append(
            f'<line x1="{x1:.1f}" y1="{y1:.1f}" x2="{x2:.1f}" y2="{y2:.1f}" '
            f'stroke="{stroke}" stroke-width="{width}"{d}/>')

    def circle(self, cx, cy, r, fill="#1f77b4", opacity=0.6, title=""):
        t = f"<title>{_esc(title)}</title>" if title else ""
        self.parts.append(
            f'<circle cx="{cx:.1f}" cy="{cy:.1f}" r="{r:.1f}" fill="{fill}" '
            f'fill-opacity="{opacity}" stroke="#333" stroke-width="0.5">{t}'
            '</circle>')

    def text(self, x, y, s, size=11, anchor="start", fill="#222", rotate=None):
        rot = (f' transform="rotate({rotate} {x:.1f} {y:.1f})"'
               if rotate is not None else "")
        self.parts.append(
            f'<text x="{x:.1f}" y="{y:.1f}" font-size="{size}" fill="{fill}" '
            f'text-anchor="{anchor}"{rot}>{_esc(s)}</text>')

    def polyline(self, pts: Sequence[Tuple[float, float]], stroke="#1f77b4",
                 width=1.5):
        if len(pts) < 2:
            return
        path = " ".join(f"{x:.1f},{y:.1f}" for x, y in pts)
        self.parts.append(
            f'<polyline points="{path}" fill="none" stroke="{stroke}" '
            f'stroke-width="{width}"/>')

    def polyline_xy(self, xs, ys, stroke="#1f77b4", width=1.5):
        """Vectorized variant: pre-scaled coordinate arrays."""
        if len(xs) < 2:
            return
        path = " ".join(map("%.1f,%.1f".__mod__,
                            zip(xs.tolist(), ys.tolist())))
        self.parts.append(
            f'<polyline points="{path}" fill="none" stroke="{stroke}" '
            f'stroke-width="{width}"/>')

    def render(self) -> str:
        return "\n".join(self.parts + ["</svg>"])


_PALETTE = ["#1f77b4", "#ff7f0e", "#2ca02c", "#d62728", "#9467bd",
            "#8c564b", "#e377c2", "#7f7f7f", "#bcbd22", "#17becf"]


# ------------------------------------------------------------ roofline ------

@dataclass
class JobPoint:
    job: str
    app: str
    ai: float                 # FLOP/byte
    gflops_per_chip: float
    device_hours: float
    mfu: float = 0.0


def roofline_points(store: StoreLike,
                    manifests: Optional[Dict[str, JobManifest]] = None
                    ) -> List[JobPoint]:
    """Condense each job into (AI, GFLOP/s-per-chip, device-hours)."""
    manifests = manifests or {}
    rows = query(store, "search kind=perf gflops>0 "
                        "| stats avg(ai) avg(gflops_per_chip) avg(mfu) "
                        "min(ts) max(ts) by job")
    app_by_job = {r["job"]: str(r.get("app", "?")) for r in query(
        store, "search kind=meta | dedup job | fields job app")}
    points = []
    for r in rows:
        job = r["job"]
        man = manifests.get(job)
        chips = man.num_chips if man else 1
        dur_h = max(float(r["max_ts"]) - float(r["min_ts"]), 0.0) / 3600.0
        points.append(JobPoint(
            job=job,
            app=(man.app if man else app_by_job.get(job, "?")),
            ai=float(r["avg_ai"]),
            gflops_per_chip=float(r["avg_gflops_per_chip"]),
            device_hours=max(dur_h * chips, 1e-6),
            mfu=float(r.get("avg_mfu") or 0.0)))
    return points


def render_roofline_svg(points: Sequence[JobPoint], hw: HardwareSpec,
                        width: int = 860, height: int = 560,
                        title: str = "Job roofline overview") -> str:
    """Fig. 2 analog: log-log roofline with one circle per job."""
    c = SvgCanvas(width, height)
    ml, mr, mt, mb = 70, 30, 46, 56
    pw, ph = width - ml - mr, height - mt - mb
    # axis ranges (log10)
    ai_lo, ai_hi = -2.0, 4.0
    peak_g = hw.peak_flops / 1e9
    pf_lo, pf_hi = math.log10(peak_g) - 5.0, math.log10(peak_g) + 0.4

    def X(ai: float) -> float:
        ai = min(max(ai, 10 ** ai_lo), 10 ** ai_hi)
        return ml + (math.log10(ai) - ai_lo) / (ai_hi - ai_lo) * pw

    def Y(gf: float) -> float:
        gf = min(max(gf, 10 ** pf_lo), 10 ** pf_hi)
        return mt + ph - (math.log10(gf) - pf_lo) / (pf_hi - pf_lo) * ph

    c.text(width / 2, 22, title, size=15, anchor="middle")
    # gridlines + ticks
    for e in range(int(ai_lo), int(ai_hi) + 1):
        x = X(10 ** e)
        c.line(x, mt, x, mt + ph, stroke="#eee")
        c.text(x, mt + ph + 16, f"1e{e}", size=10, anchor="middle")
    for e in range(math.ceil(pf_lo), math.floor(pf_hi) + 1):
        y = Y(10 ** e)
        c.line(ml, y, ml + pw, y, stroke="#eee")
        c.text(ml - 6, y + 3, f"1e{e}", size=10, anchor="end")
    c.line(ml, mt + ph, ml + pw, mt + ph)
    c.line(ml, mt, ml, mt + ph)
    c.text(width / 2, height - 14,
           "arithmetic intensity [FLOP/byte]", size=12, anchor="middle")
    c.text(16, mt + ph / 2, "GFLOP/s per chip", size=12, anchor="middle",
           rotate=-90)
    # roofline: bandwidth slope then flat compute roof
    ridge = hw.ridge_ai
    bw_g = hw.hbm_bw / 1e9
    pts = [(X(10 ** ai_lo), Y(bw_g * 10 ** ai_lo)),
           (X(ridge), Y(peak_g)), (X(10 ** ai_hi), Y(peak_g))]
    c.polyline(pts, stroke="#d62728", width=2.0)
    c.text(X(ridge), Y(peak_g) - 8,
           f"{hw.name}: {peak_g / 1e3:.0f} TFLOP/s, "
           f"{bw_g:.0f} GB/s, ridge {ridge:.0f}",
           size=10, anchor="middle", fill="#d62728")
    # jobs
    if points:
        max_h = max(p.device_hours for p in points)
        apps = sorted({p.app for p in points})
        color = {a: _PALETTE[i % len(_PALETTE)] for i, a in enumerate(apps)}
        for p in points:
            r = 4 + 14 * math.sqrt(p.device_hours / max_h)
            c.circle(X(p.ai), Y(max(p.gflops_per_chip, 10 ** pf_lo)), r,
                     fill=color[p.app],
                     title=(f"{p.job} ({p.app}) AI={p.ai:.2f} "
                            f"{p.gflops_per_chip:.1f} GFLOP/s/chip "
                            f"MFU={p.mfu:.1%} {p.device_hours:.2f} dev-h"))
        for i, a in enumerate(apps[:12]):
            c.circle(ml + 10, mt + 12 + 16 * i, 5, fill=color[a])
            c.text(ml + 20, mt + 16 + 16 * i, a, size=10)
    return c.render()


# ------------------------------------------------------- detailed job view --

def render_timeseries_svg(series: Dict[str, List[Tuple[float, float]]],
                          title: str, ylabel: str,
                          width: int = 860, height: int = 300) -> str:
    """Multi-line temporal plot (one line per host/socket), Fig. 3 style."""
    c = SvgCanvas(width, height)
    ml, mr, mt, mb = 64, 120, 34, 40
    pw, ph = width - ml - mr, height - mt - mb
    raw = {name: np.asarray(pts, dtype=np.float64)
           for name, pts in series.items() if pts}
    arrays = {name: a[~np.isnan(a[:, 1])] for name, a in raw.items()}
    c.text(width / 2, 20, title, size=13, anchor="middle")
    if not arrays or not any(a.size for a in arrays.values()):
        c.text(width / 2, height / 2, "(no data)", anchor="middle")
        return c.render()
    x0 = min(float(a[:, 0].min()) for a in raw.values())
    x1 = max(float(a[:, 0].max()) for a in raw.values())
    valid = [a for a in arrays.values() if a.size]
    y0 = min(0.0, min(float(a[:, 1].min()) for a in valid))
    y1 = max(float(a[:, 1].max()) for a in valid)
    if y1 <= y0:
        y1 = y0 + 1.0
    if x1 <= x0:
        x1 = x0 + 1.0
    sx = pw / (x1 - x0)
    sy = ph / (y1 - y0)

    def X(t): return ml + (t - x0) * sx
    def Y(v): return mt + ph - (v - y0) * sy

    for i in range(5):
        yv = y0 + (y1 - y0) * i / 4
        c.line(ml, Y(yv), ml + pw, Y(yv), stroke="#eee")
        c.text(ml - 6, Y(yv) + 3, f"{yv:.3g}", size=9, anchor="end")
    for i in range(5):
        tv = x0 + (x1 - x0) * i / 4
        c.text(X(tv), mt + ph + 14, f"+{tv - x0:.0f}s", size=9,
               anchor="middle")
    c.line(ml, mt + ph, ml + pw, mt + ph)
    c.line(ml, mt, ml, mt + ph)
    c.text(14, mt + ph / 2, ylabel, size=11, anchor="middle", rotate=-90)
    for i, name in enumerate(sorted(series)):
        col = _PALETTE[i % len(_PALETTE)]
        arr = arrays.get(name)
        if arr is not None and arr.size:
            c.polyline_xy(ml + (arr[:, 0] - x0) * sx,
                          mt + ph - (arr[:, 1] - y0) * sy, stroke=col)
        if i < 14:
            c.line(ml + pw + 8, mt + 10 + 14 * i, ml + pw + 24,
                   mt + 10 + 14 * i, stroke=col, width=2)
            c.text(ml + pw + 28, mt + 14 + 14 * i, name[:14], size=9)
    return c.render()


JOB_VIEW_METRICS = ("gflops", "hbm_gbs", "ai", "mfu", "step_time_s",
                    "tokens_per_s", "loss")


def job_metric_series(store: StoreLike, job: str, metric: str,
                      kind: str = "perf"
                      ) -> Dict[str, List[Tuple[float, float]]]:
    """Per-host (ts, value) series straight off the column arrays."""
    sc = store.scan(job=job, kind=kind, fields=(metric,))
    vals, present = sc.field(metric)
    idx = np.nonzero(present)[0]
    series: Dict[str, List[Tuple[float, float]]] = {}
    if idx.size == 0:
        return series
    hc = sc.host_codes[idx]
    ts = sc.ts[idx]
    vs = vals[idx]
    order = np.lexsort((vs, ts, hc))
    hc, ts, vs = hc[order], ts[order], vs[order]
    cuts = np.nonzero(hc[1:] != hc[:-1])[0] + 1
    starts = np.concatenate([[0], cuts])
    stops = np.concatenate([cuts, [len(hc)]])
    for lo, hi in zip(starts, stops):
        host = str(sc.host_vocab[hc[lo]])
        series[host] = list(zip(ts[lo:hi].tolist(), vs[lo:hi].tolist()))
    return series


def job_statistical_view(store: StoreLike, job: str, metric: str,
                         kind: str = "perf", span_s: float = 60.0
                         ) -> Dict[str, List[Tuple[float, float]]]:
    """The paper's second job dashboard: min/median/max curves across all
    hosts per time bucket, computed exactly by a NumPy bucket group-by
    over the columnar store (the streaming ``QuantileSet`` sketch remains
    for relays that cannot hold samples)."""
    sc = store.scan(job=job, kind=kind, fields=(metric,))
    vals, present = sc.field(metric)
    valid = present & ~np.isnan(vals)
    out: Dict[str, List[Tuple[float, float]]] = {
        "min": [], "median": [], "max": []}
    if not valid.any():
        return out
    vs = vals[valid]
    buckets = np.floor(sc.ts[valid] / span_s) * span_s
    order = np.lexsort((vs, buckets))  # value-sorted within each bucket
    buckets, vs = buckets[order], vs[order]
    cuts = np.nonzero(buckets[1:] != buckets[:-1])[0] + 1
    starts = np.concatenate([[0], cuts])
    stops = np.concatenate([cuts, [len(vs)]])
    counts = stops - starts
    mins = vs[starts]
    maxs = vs[stops - 1]
    med_lo = vs[starts + (counts - 1) // 2]
    med_hi = vs[starts + counts // 2]
    medians = 0.5 * (med_lo + med_hi)
    out["min"] = list(zip(buckets[starts].tolist(), mins.tolist()))
    out["median"] = list(zip(buckets[starts].tolist(), medians.tolist()))
    out["max"] = list(zip(buckets[starts].tolist(), maxs.tolist()))
    return out


# ------------------------------------------------------- specialized views --

def view_top_apps_by_device_hours(store: StoreLike,
                                  manifests: Dict[str, JobManifest],
                                  limit: int = 10) -> List[Dict]:
    """Paper: 'most executed applications by core hours'."""
    rows = query(store, "search kind=perf "
                        "| stats min(ts) max(ts) count by job")
    acc: Dict[str, float] = {}
    for r in rows:
        man = manifests.get(r["job"])
        if man is None:
            continue
        dur_h = max(float(r["max_ts"]) - float(r["min_ts"]), 0.0) / 3600.0
        acc[man.app] = acc.get(man.app, 0.0) + dur_h * man.num_chips
    table = [{"app": a, "device_hours": round(h, 4)}
             for a, h in sorted(acc.items(), key=lambda kv: -kv[1])]
    return table[:limit]


_IDLE_ACCEL_Q = ("search kind=device | stats max(hbm_frac_used) count "
                 "by job | where max_hbm_frac_used<{max_frac} "
                 "| sort max_hbm_frac_used")
# same aggregation prefix as the idle view (the threshold lives in the
# idle view's *tail*), so both streaming views share one set of cached
# per-segment partials — the fingerprint excludes tail stages
_MEMORY_PEAK_Q = "search kind=device | stats max(hbm_frac_used) count by job"
_PARTICIPATION_Q = "search kind=perf gflops>0 | stats dc(host) by job"


def view_idle_accelerators(store: StoreLike, max_frac: float = 0.05
                           ) -> List[Dict]:
    """Paper: 'jobs that reserved GPU nodes without using GPUs'."""
    return query(store, _IDLE_ACCEL_Q.format(max_frac=max_frac))


def _memory_underuse_rows(rows: List[Dict],
                          manifests: Dict[str, JobManifest],
                          max_frac: float) -> List[Dict]:
    out = []
    for r in rows:
        man = manifests.get(r["job"])
        if man is None or man.extra.get("large_memory") not in ("1", 1, True):
            continue
        v = r.get("max_hbm_frac_used")
        if isinstance(v, (int, float)) and v < max_frac:
            out.append({"job": r["job"], "peak_frac": v, "app": man.app})
    return out


def view_memory_underuse(store: StoreLike,
                         manifests: Dict[str, JobManifest],
                         max_frac: float = 0.25) -> List[Dict]:
    """Paper: 'jobs that reserved large memory nodes without using much
    memory'."""
    return _memory_underuse_rows(query(store, _MEMORY_PEAK_Q), manifests,
                                 max_frac)


def _low_participation_rows(rows: List[Dict],
                            manifests: Dict[str, JobManifest],
                            min_frac: float) -> List[Dict]:
    out = []
    for r in rows:
        man = manifests.get(r["job"])
        if man is None or man.num_hosts <= 1:
            continue
        active = int(r["dc_host"])
        if active < min_frac * man.num_hosts:
            out.append({"job": r["job"], "active_hosts": active,
                        "allocated_hosts": man.num_hosts, "app": man.app})
    return out


def view_low_participation(store: StoreLike,
                           manifests: Dict[str, JobManifest],
                           min_frac: float = 0.5) -> List[Dict]:
    """Paper: 'jobs that use less than half of the available CPU cores'."""
    return _low_participation_rows(query(store, _PARTICIPATION_Q), manifests,
                                   min_frac)


# ------------------------------------------------------- streaming views ---

class StreamingView:
    """One continuously-refreshed dashboard view (paper §4.4's
    "interactive analysis" loop): a :class:`QueryHandle` plus an
    optional row post-processor and renderer.

    Call :meth:`refresh` after each aggregator pump.  The handle makes
    the refresh incremental — with no new data it returns the previous
    rows untouched, and with new data a mergeable query recomputes only
    the append buffer plus newly sealed segments (the sealed fleet
    comes from the store's segment-keyed partial-aggregate cache; see
    docs/incremental.md).  Post-processing and rendering re-run only
    when the underlying rows actually changed.

    ``service`` routes refreshes through a
    :class:`~repro.core.service.QueryService` (tenant ``"dashboard"``,
    ``shed_ok``): many concurrent views over the same query share one
    execution, and at saturation a refresh returns the previous rows
    instead of joining the backlog — docs/service.md.
    """

    def __init__(self, store: StoreLike, q: str,
                 postprocess: Optional[Callable[[List[Dict]], List[Dict]]]
                 = None,
                 render: Optional[Callable[[List[Dict]], str]] = None,
                 service=None) -> None:
        self.handle = QueryHandle(store, q, service=service,
                                  tenant="dashboard",
                                  shed_ok=service is not None)
        self.postprocess = postprocess
        self.render = render
        self.renders = 0
        self._rows_seen: Optional[List[Dict]] = None
        self._result: List[Dict] = []
        self._rendered: Optional[str] = None

    def refresh(self) -> List[Dict]:
        """Current (post-processed) rows; incremental under the hood.

        ``postprocess`` re-runs on every refresh — it may close over
        mutable state (e.g. a manifests dict that gained a job without
        any new metric records), so only the query itself is memoized
        on the store version; the render invalidates whenever the
        post-processed output actually changed."""
        rows = self.handle.refresh()
        if rows is not self._rows_seen or self.postprocess is not None:
            result = self.postprocess(rows) if self.postprocess else rows
            if result != self._result:
                self._result = result
                self._rendered = None
            self._rows_seen = rows
        return self._result

    def rendered(self) -> str:
        """Rendered form of the current rows (markdown by default);
        re-rendered only when a refresh changed the row *content* —
        new records that leave the aggregate unchanged cost nothing."""
        self.refresh()
        if self._rendered is None:
            self._rendered = (self.render(self._result) if self.render
                              else markdown_table(self._result))
            self.renders += 1
        return self._rendered

    def explain(self) -> Dict:
        return self.handle.explain()


def streaming_specialized_views(store: StoreLike,
                                manifests: Optional[
                                    Dict[str, JobManifest]] = None,
                                idle_max_frac: float = 0.05,
                                memory_max_frac: float = 0.25,
                                participation_min_frac: float = 0.5,
                                service=None
                                ) -> Dict[str, StreamingView]:
    """The paper's specialized views as streaming dashboards.

    Returns named :class:`StreamingView` instances over the same
    queries as the one-shot ``view_*`` functions — refreshing them
    between pumps matches the one-shot results exactly, but repeated
    refreshes cost only buffer work.  The idle-accelerator view's
    threshold lives in a *tail* stage, so it shares cached per-segment
    partials with the memory view's identical aggregation prefix.
    ``service`` is forwarded to every view (see
    :class:`StreamingView`).
    """
    if manifests is None:  # keep the caller's dict: postprocess closes
        manifests = {}     # over it and re-reads it on every refresh
    return {
        "idle_accelerators": StreamingView(
            store, _IDLE_ACCEL_Q.format(max_frac=idle_max_frac),
            service=service),
        "memory_underuse": StreamingView(
            store, _MEMORY_PEAK_Q,
            postprocess=lambda rows: _memory_underuse_rows(
                rows, manifests, memory_max_frac),
            service=service),
        "low_participation": StreamingView(
            store, _PARTICIPATION_Q,
            postprocess=lambda rows: _low_participation_rows(
                rows, manifests, participation_min_frac),
            service=service),
    }


# ------------------------------------------------------ fleet health (ops) --
#
# The monitor monitoring itself (docs/observability.md): these views run
# over the dedicated ``_telemetry`` store that ``telemetry.SelfMonitor``
# pumps ``kind=fleet`` registry snapshots into — not over job metrics.

FLEET_HEALTH_FIELDS = (
    "remote.queries", "remote.degraded_queries", "remote.retries",
    "breaker.open", "breaker.opens", "breaker.rejections",
    "cache.partial.hits", "cache.partial.misses",
    "storage.segments", "storage.quarantined_segments",
    "tracer.spans_started", "tracer.slow_queries",
)


def _fleet_health_rows(rows: List[Dict],
                       fields: Sequence[str] = FLEET_HEALTH_FIELDS
                       ) -> List[Dict]:
    """Latest snapshot row -> one {metric, value} row per listed field
    (fields absent from the snapshot — e.g. breaker.* on a breakerless
    fleet — are simply omitted)."""
    if not rows:
        return []
    latest = max(rows, key=lambda r: float(r.get("ts", 0.0) or 0.0))
    out = []
    for f in fields:
        v = latest.get(f)
        if isinstance(v, (int, float)):
            out.append({"metric": f, "value": float(v)})
    return out


def view_fleet_health(telemetry_store: StoreLike,
                      fields: Sequence[str] = FLEET_HEALTH_FIELDS
                      ) -> List[Dict]:
    """Ops dashboard: the fleet's own vitals from its newest
    self-ingested ``kind=fleet`` snapshot, as {metric, value} rows
    (render with :func:`markdown_table`)."""
    return _fleet_health_rows(query(telemetry_store, "search kind=fleet"),
                              fields)


def streaming_fleet_health(telemetry_store: StoreLike,
                           fields: Sequence[str] = FLEET_HEALTH_FIELDS,
                           service=None) -> StreamingView:
    """:func:`view_fleet_health` as a :class:`StreamingView` — refresh
    after each self-monitor pump; unchanged vitals re-render nothing."""
    return StreamingView(
        telemetry_store, "search kind=fleet",
        postprocess=lambda rows: _fleet_health_rows(rows, fields),
        service=service)


def view_slow_queries(telemetry_store: StoreLike, limit: int = 10
                      ) -> List[Dict]:
    """Slowest recent queries from the self-ingested slow-query events
    (``kind=event event=slow_query``), worst first."""
    rows = query(telemetry_store, "search kind=event")
    slow = [r for r in rows if r.get("event") == "slow_query"]
    slow.sort(key=lambda r: -float(r.get("duration_s", 0.0) or 0.0))
    return [{"trace_id": r.get("trace_id"), "name": r.get("name"),
             "duration_s": float(r.get("duration_s", 0.0) or 0.0),
             "ts": float(r.get("ts", 0.0) or 0.0)}
            for r in slow[:limit]]


def markdown_table(rows: List[Dict], columns: Optional[List[str]] = None
                   ) -> str:
    if not rows:
        return "*(empty)*\n"
    cols = columns or list(rows[0].keys())
    def fmt(v):
        if isinstance(v, float):
            return f"{v:.4g}"
        return str(v)
    lines = ["| " + " | ".join(cols) + " |",
             "|" + "|".join("---" for _ in cols) + "|"]
    for r in rows:
        lines.append("| " + " | ".join(fmt(r.get(c, "")) for c in cols) + " |")
    return "\n".join(lines) + "\n"
