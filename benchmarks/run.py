"""Benchmark driver — one benchmark per paper table/figure/claim.

Prints ``name,us_per_call,derived`` CSV rows (stdout), writes rendered
dashboards under experiments/dashboards/, and emits machine-readable
results to ``experiments/BENCH_splunklite.json`` so the performance
trajectory is tracked across PRs.

  data_volume   — paper §5 log-volume table
  overhead      — paper §4 negligible-overhead claim
  roofline_view — paper Fig. 2
  job_view      — paper Fig. 3
  detectors     — paper §4.4 specialized views / §5 case studies
  splunklite    — analysis-layer query latency (columnar vs legacy rows)
  sharded       — multi-aggregator scatter/gather fan-out vs single store
  incremental   — segment-keyed partial-aggregate cache: cold vs warm
  remote        — worker-process shard fleet vs in-process sharded
  replication   — replicated shards: hedged-scatter p99 vs unhedged
                  with one artificially slow member
  faults        — fault-tolerance overhead: hardened warm fleet query
                  vs checksums/retry/breakers all off (<= 1.15x)
  telemetry     — tracing + self-ingestion overhead: traced warm fleet
                  query vs tracing off (<= 1.10x)
  compaction    — segment compaction + compressed tiers: cold query
                  pre/post, byte ratio, rollup vs raw scan
  restart       — aggregator cold-start: mmap segments vs line replay
  transport     — rsyslog-analog throughput
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import EXPERIMENTS  # noqa: E402


def _parse_row(line: str):
    name, us, derived = line.split(",", 2)
    try:
        us_val = float(us)
    except ValueError:
        us_val = None
    return {"name": name, "us_per_call": us_val, "derived": derived}


def main() -> None:
    from benchmarks import monitoring as mbench
    from benchmarks.bench_faults import bench_faults
    from benchmarks.bench_replication import bench_replication
    from benchmarks.bench_telemetry import bench_telemetry
    only = set(sys.argv[1:])
    out = EXPERIMENTS
    out.mkdir(parents=True, exist_ok=True)
    benches = [
        mbench.bench_data_volume,
        mbench.bench_overhead,
        mbench.bench_roofline_view,
        mbench.bench_job_view,
        mbench.bench_detectors,
        mbench.bench_anomaly,
        mbench.bench_splunklite,
        mbench.bench_sharded,
        mbench.bench_incremental,
        mbench.bench_remote,
        bench_replication,
        bench_faults,
        bench_telemetry,
        mbench.bench_service,
        mbench.bench_compaction,
        mbench.bench_restart,
        mbench.bench_transport,
    ]
    if only:
        benches = [b for b in benches
                   if b.__name__.replace("bench_", "") in only]
    print("name,us_per_call,derived")
    results = []
    failures = 0
    for bench in benches:
        try:
            for line in bench(out):
                print(line, flush=True)
                results.append(_parse_row(line))
        except Exception as exc:  # noqa: BLE001
            failures += 1
            line = f"{bench.__name__},ERROR,{type(exc).__name__}: {exc}"
            print(line, flush=True)
            results.append(_parse_row(line))
    # merge into the tracked results file by row name so filtered runs
    # (e.g. `run.py splunklite`) update their rows without clobbering
    # the rest of the trajectory
    bench_path = out / "BENCH_splunklite.json"
    merged = {}
    try:
        for r in json.loads(bench_path.read_text()).get("rows", []):
            merged[r["name"]] = r
    except (OSError, ValueError, KeyError):
        pass
    # a bench that ran again supersedes its previous ERROR row (error
    # rows are keyed by the bench function name)
    for bench in benches:
        merged.pop(bench.__name__, None)
    for r in results:
        merged[r["name"]] = r
    stale_failures = sum(1 for r in merged.values()
                         if r["us_per_call"] is None)
    bench_path.write_text(json.dumps(
        {"rows": list(merged.values()), "failures": stale_failures},
        indent=2) + "\n")
    if failures:
        raise SystemExit(f"{failures} benchmarks failed")


if __name__ == "__main__":
    main()
