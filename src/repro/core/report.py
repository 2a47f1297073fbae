"""Per-job performance reports — the paper's §4.5 PDF-for-users analog.

Users do not get Splunk access (security/data-protection, per the paper);
they get a static, self-contained report per job.  We render Markdown plus
embedded SVGs, and a single-file HTML (the "PDF" stand-in: printable,
self-contained, no external references).

All store reads go through splunklite queries and the dashboard helpers,
which execute on the columnar engine (``repro.core.columnar``) — report
generation never materializes row objects from the store.
"""

from __future__ import annotations

import html
import math
import os
from pathlib import Path
from typing import Dict, List, Optional

from repro.core.aggregator import MetricStore
from repro.core.daemon import JobManifest
from repro.core.dashboards import (JOB_VIEW_METRICS, JobPoint,
                                   job_metric_series, job_statistical_view,
                                   markdown_table, render_roofline_svg,
                                   render_timeseries_svg, roofline_points)
from repro.core.derived import HardwareSpec, hardware_for
from repro.core.detectors import DetectorBank
from repro.core.splunklite import query


def _fmt(v, nd=3):
    if isinstance(v, float):
        if math.isnan(v):
            return "–"
        return f"{v:.{nd}g}"
    return str(v)


def job_hardware(store: MetricStore, job: str) -> Optional[HardwareSpec]:
    """Peaks of the part ``job`` ran on, from the device its meta record
    names; None where it names none or a CPU."""
    for r in query(store, f"search kind=meta job={job} "
                          "| fields backend device_kind"):
        if r.get("backend") and r.get("device_kind"):
            return hardware_for(str(r["backend"]), str(r["device_kind"]))
    return None


def job_summary(store: MetricStore, job: str,
                manifest: Optional[JobManifest] = None,
                hw: Optional[HardwareSpec] = None) -> Dict[str, object]:
    """Per-job figures; the roofline placement only where ``hw`` (the
    part's peaks) is given."""
    rows = query(store, f"search kind=perf job={job} gflops>0 "
                        "| stats avg(gflops) max(gflops) avg(gflops_per_chip) "
                        "avg(hbm_gbs) avg(ici_gbs) avg(ai) avg(mfu) "
                        "p50(step_time_s) avg(tokens_per_s) "
                        "min(ts) max(ts) count")
    s = rows[0] if rows else {}
    chips = manifest.num_chips if manifest else 1
    dur = max(float(s.get("max_ts", 0) or 0) - float(s.get("min_ts", 0) or 0),
              0.0)
    out = {
        "job": job,
        "app": manifest.app if manifest else "?",
        "user": manifest.user if manifest else "?",
        "hosts": manifest.num_hosts if manifest else len(store.hosts(job)),
        "chips": chips,
        "duration_s": dur,
        "device_hours": dur * chips / 3600.0,
        "samples": int(s.get("count", 0) or 0),
        "avg_gflops": float(s.get("avg_gflops", 0) or 0),
        "max_gflops": float(s.get("max_gflops", 0) or 0),
        "avg_gflops_per_chip": float(s.get("avg_gflops_per_chip", 0) or 0),
        "avg_hbm_gbs": float(s.get("avg_hbm_gbs", 0) or 0),
        "avg_ici_gbs": float(s.get("avg_ici_gbs", 0) or 0),
        "avg_ai": float(s.get("avg_ai", 0) or 0),
        "avg_mfu": float(s.get("avg_mfu", 0) or 0),
        "p50_step_time_s": float(s.get("p50_step_time_s", 0) or 0),
        "avg_tokens_per_s": float(s.get("avg_tokens_per_s", 0) or 0),
    }
    ai = out["avg_ai"]
    if ai > 0 and hw is not None:
        attain = hw.attainable_flops(ai) / 1e9
        out["roofline_attainable_gflops_per_chip"] = attain
        out["roofline_fraction"] = (out["avg_gflops_per_chip"] / attain
                                    if attain else 0.0)
        out["roofline_regime"] = ("memory-bound" if ai < hw.ridge_ai
                                  else "compute-bound")
    return out


def generate_report(store: MetricStore, job: str, out_dir: os.PathLike,
                    manifests: Optional[Dict[str, JobManifest]] = None,
                    hw: Optional[HardwareSpec] = None) -> Path:
    """Write ``report.md``, ``report.html`` and SVGs; returns the md path.

    ``hw`` defaults to the part the job's meta record names
    (:func:`job_hardware`); without one the roofline is left out."""
    manifests = manifests or {}
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    man = manifests.get(job)
    hw = hw if hw is not None else job_hardware(store, job)
    summ = job_summary(store, job, man, hw)

    svgs: List[str] = []
    md: List[str] = [f"# Job performance report — `{job}`", ""]
    md.append(f"*Application*: **{summ['app']}** — *user*: {summ['user']} — "
              f"*hosts*: {summ['hosts']} — *chips*: {summ['chips']} — "
              f"*duration*: {summ['duration_s']:.1f}s — "
              f"*device-hours*: {summ['device_hours']:.3f}")
    md.append("")
    md.append("## Summary")
    md.append(markdown_table([{k: _fmt(v) for k, v in summ.items()
                               if k not in ("job", "app", "user")}]))

    # roofline placement of THIS job among all jobs in the store
    points = roofline_points(store, manifests) if hw is not None else []
    if points:
        svg = render_roofline_svg(
            points, hw, title=f"Roofline placement — {job}")
        (out / "roofline.svg").write_text(svg)
        svgs.append(svg)
        md.append("## Roofline placement\n\n![roofline](roofline.svg)\n")

    # temporal views per metric (per host), Fig. 3 analog
    md.append("## Temporal metrics (per host)")
    for metric in JOB_VIEW_METRICS:
        series = job_metric_series(store, job, metric)
        if not series:
            continue
        svg = render_timeseries_svg(series, f"{metric} — {job}", metric)
        name = f"ts_{metric}.svg"
        (out / name).write_text(svg)
        svgs.append(svg)
        md.append(f"![{metric}]({name})\n")

    # statistical min/median/max view (large-job dashboard)
    stat = job_statistical_view(store, job, "gflops")
    if any(stat.values()):
        svg = render_timeseries_svg(
            stat, f"gflops min/median/max across hosts — {job}", "gflops")
        (out / "stat_gflops.svg").write_text(svg)
        svgs.append(svg)
        md.append("## Statistical view (all hosts)\n\n"
                  "![stat](stat_gflops.svg)\n")

    # detector findings for this job
    bank = DetectorBank()
    events = [e for e in bank.scan(store, manifests) if e.job == job]
    md.append("## Automated findings")
    if events:
        md.append(markdown_table([
            {"severity": e.severity, "detector": e.detector,
             "message": e.message} for e in events]))
    else:
        md.append("No issues detected.\n")

    # environment / meta
    meta = query(store, f"search kind=meta job={job} | head 1")
    if meta:
        md.append("## Job environment")
        md.append(markdown_table([{k: _fmt(v) for k, v in meta[0].items()
                                   if k not in ("ts",)}]))

    md_text = "\n".join(md) + "\n"
    md_path = out / "report.md"
    md_path.write_text(md_text)

    # single-file printable HTML ("PDF" stand-in)
    body = []
    for line in md:
        if line.startswith("# "):
            body.append(f"<h1>{html.escape(line[2:])}</h1>")
        elif line.startswith("## "):
            body.append(f"<h2>{html.escape(line[3:])}</h2>")
        elif line.startswith("!["):
            continue  # svgs are embedded below their section instead
        elif line.startswith("|"):
            body.append(f"<pre>{html.escape(line)}</pre>")
        elif line:
            body.append(f"<p>{html.escape(line)}</p>")
    svg_html = "\n".join(svgs)
    (out / "report.html").write_text(
        "<!doctype html><html><head><meta charset='utf-8'>"
        f"<title>{html.escape(job)}</title></head><body>"
        + "\n".join(body) + svg_html + "</body></html>")
    return md_path
