#!/usr/bin/env python3
"""Profile one training cell and read its trace by the program's names.

    python3 bench/trace_cell.py --workload mamba2-780m.train --seed 7 \
        --seconds 10 --out trace_out/mamba2

From the root of a checkout, on the chip. The cell's job runs its compared
steps, an untraced window of ``--seconds`` and then the traffic's
``traced_steps`` under the profiler, as ``bench/run.py --trace 1`` does. It prints one JSON
object: the existing reduction (``harness.trace``), the additions of
``harness.scopes`` and, per traced step, what each addition reads: device
milliseconds per named scope, the monitor's tick and the pipeline's wait.
It also gives the median untraced step and the median traced step (from
one ``bench.dispatch`` start to the next), so the cost of tracing shows, and with ``--span-cost N`` the cost of one program span with no
profiler session, over N spans. ``--out`` keeps the reduction, the
compiled step's HLO text and, with ``--keep-trace``, the ``.xplane.pb``.
"""

from __future__ import annotations

import argparse
import collections
import json
import re
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def per_step(red: dict, more: dict) -> dict:
    """What the per-layer readers of these additions would read: device
    milliseconds per scope per step, ``unscoped`` as a share of all device
    self time (inherited and by own ``op_name``), the tick's mean and the
    pipeline's wait per step."""
    steps = red.get("spans", {}).get("bench.dispatch", {}).get("count", 0)
    scopes, spans = more.get("scopes", {}), more.get("program_spans", {})
    out = {}
    if steps and scopes:
        out.update({f"{k}_ms": 1e3 * v / steps for k, v in scopes.items()})
        total = sum(scopes.values())
        out["unscoped_share_pct"] = 100.0 * scopes["unscoped"] / total
        out["unscoped_own_share_pct"] = (
            100.0 * more["scopes_own"]["unscoped"] / total)
    tick = spans.get("repro.monitor.tick")
    if tick and tick["count"]:
        out["monitor_tick_ms"] = 1e3 * tick["seconds"] / tick["count"]
        out["monitor_ticks"] = tick["count"]
    wait = spans.get("repro.pipeline.wait")
    if wait and steps:
        out["pipeline_wait_ms"] = 1e3 * wait["seconds"] / steps
    return out


def unscoped_top(dev_events: dict, window: tuple, hlo: str,
                 top: int = 10) -> list:
    """The operations with most device self time in the window whose own
    ``op_name`` names no scope, each with its seconds, the scope it
    inherits and its instruction's text."""
    from harness import scopes, trace
    own, inherited = scopes.instruction_scopes(hlo)
    secs = collections.Counter()
    for events in dev_events.values():
        for name, t in trace.self_times(trace._clip(events, *window)):
            if own.get(name, scopes.UNSCOPED) == scopes.UNSCOPED:
                secs[name] += t
    text = dict(re.findall(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$", hlo, re.M))
    return [[n, t, inherited.get(n, scopes.UNSCOPED), text.get(n, "")[:400]]
            for n, t in secs.most_common(top)]


def step_seconds(bench_spans: list) -> list:
    """Seconds between the starts of successive ``bench.dispatch`` spans:
    one traced step each, less the first."""
    starts = sorted(s for n, s, _ in bench_spans if n == "bench.dispatch")
    return [(b - a) * 1e-9 for a, b in zip(starts, starts[1:])]


def span_cost(n: int) -> float:
    """Seconds of one profiler-sink span with no profiler session."""
    from repro.core.telemetry import Telemetry
    tel = Telemetry()
    t0 = time.perf_counter()
    for _ in range(n):
        with tel.span("repro.cost"):
            pass
    return (time.perf_counter() - t0) / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("--span-cost", type=int, default=0)
    args = ap.parse_args(argv)

    from harness import cells, device, scopes, trace, train_cell
    cell = cells.find(args.workload)
    dev = device.check(cell.workload["chips"])
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    steps = cell.traffic["traced_steps"]
    workdir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
    out = Path(args.out) if args.out else None
    try:
        job = train_cell.Job(cell, args.seed, workdir)
        losses = job.compared_steps()["losses"]
        win = job.window(args.seconds)
        trace_dir = str(workdir / "trace")
        red = job.traced(steps, trace_dir)
        hlo = job.compiled.as_text()
        dev_events, bench_spans, about = trace.read_xplane(trace_dir)
        window = next((s, s + d) for n, s, d in bench_spans
                      if n == trace.WINDOW_SPAN)
        more = scopes.reduce(dev_events, scopes.read_host(trace_dir), hlo)
        traced_s = step_seconds(bench_spans)
        result = {
            "workload": args.workload, "seed": args.seed, "device": dev,
            "compared_losses": losses,
            "untraced_step_median_s": statistics.median(win["step_s"]),
            "untraced_steps": win["steps"],
            "traced_step_median_s": statistics.median(traced_s),
            "traced_step_s": traced_s,
            "per_step": per_step(red, more),
            "reduce": red, "scopes": more,
            "unscoped_top": unscoped_top(dev_events, window, hlo),
            "planes": about,
        }
        if out is not None:
            out.mkdir(parents=True, exist_ok=True)
            (out / "step.hlo.txt").write_text(hlo)
            if args.keep_trace:
                shutil.copytree(trace_dir, out / "trace", dirs_exist_ok=True)
        job.close()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if args.span_cost:
        result["span_cost_s"] = span_cost(args.span_cost)
    line = json.dumps(result)
    if out is not None:
        (out / "reduction.json").write_text(line)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
