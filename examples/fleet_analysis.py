"""Fleet analysis: the application-support workflow from the paper.

Simulates a day of jobs on a cluster (hpcmd daemons on every host, island
relays, central aggregation), then walks the paper's §4.4 dashboards:
roofline overview -> specialized views -> detailed job view -> per-job
report, plus the §4.6 automated findings.

    PYTHONPATH=src python examples/fleet_analysis.py
"""

import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.core import Aggregator, JobManifest, query
from repro.core.daemon import DaemonConfig, Hpcmd
from repro.core.dashboards import (markdown_table, render_roofline_svg,
                                   roofline_points,
                                   view_idle_accelerators,
                                   view_low_participation,
                                   view_memory_underuse,
                                   view_top_apps_by_device_hours)
from repro.core.derived import TPU_V5E
from repro.core.detectors import DetectorBank
from repro.core.report import generate_report
from repro.core.sources import StaticStepCost, StepClock, XlaCostSource
from repro.core.transport import IslandRelay, StreamFileSink


def simulate_fleet(root: Path, n_islands=2, jobs_per_island=4,
                   hosts_per_job=3, samples=24):
    """Run real daemons for synthetic jobs; returns manifests."""
    rng = np.random.default_rng(0)
    manifests = {}
    apps = ["gemma2-27b", "qwen3-8b", "mamba2-780m", "hymba-1.5b"]
    island_dirs = []
    for isl in range(n_islands):
        node_dirs = []
        for j in range(jobs_per_island):
            job = f"cobra.{isl}{j:02d}"
            app = apps[(isl * jobs_per_island + j) % len(apps)]
            behaviour = ("hang" if (isl, j) == (0, 2)
                         else "idle" if (isl, j) == (1, 1)
                         else "healthy")
            man = JobManifest(job_id=job, user=f"user{j % 3}", app=app,
                              num_hosts=hosts_per_job,
                              num_chips=hosts_per_job * 4,
                              extra={"large_memory": "1"} if j == 3 else {})
            manifests[job] = man
            flops = rng.uniform(0.5, 2.0) * 1e13
            for h in range(hosts_per_job):
                host = f"isl{isl}-node{j:02d}{h}"
                spool = root / "nodes" / host
                node_dirs.append(spool)
                clock = StepClock()
                d = Hpcmd(spool, DaemonConfig(align_to_clock=False),
                          host=host, manifest=man)
                src = XlaCostSource(clock, TPU_V5E)  # simulated v5e
                src.set_cost(StaticStepCost(
                    flops=flops, bytes=flops / rng.uniform(2, 200),
                    collective_bytes=flops / 500, num_chips=4,
                    tokens_per_step=8192))
                d.add_source(src)
                from repro.core.sources import DeviceSource, EnvSource

                class FakeDevice(DeviceSource):
                    def collect(self, now):
                        frac = 0.02 if behaviour == "idle" else 0.6
                        return {"local_devices": 4, "devices_reporting": 4,
                                "hbm_bytes_in_use": frac * 64e9,
                                "hbm_bytes_limit": 64e9,
                                "hbm_frac_used": frac}
                d.add_source(FakeDevice())
                d.add_source(EnvSource(extra={"app": app}))
                step = 0
                for s in range(samples):
                    ts = 1000.0 + s * 10.0
                    stalled = (behaviour == "hang" and s > samples // 2)
                    if not stalled and behaviour != "idle":
                        step += 1
                        clock.record(step, tokens=8192, loss=3.0 - s * 0.05,
                                     ts=ts)
                    d.tick(ts + 0.5)
                d.spool.close()
        island_dirs.append((root / f"island{isl}", node_dirs))

    # per-island relays -> central inbox (paper §4.3)
    inbox = root / "inbox"
    for isl, (idir, node_dirs) in enumerate(island_dirs):
        relay = IslandRelay(node_dirs, idir, island_name=f"island{isl}")
        relay.pump()
        uplink = relay.uplink(StreamFileSink(inbox / f"island{isl}.log"))
        uplink.ship_once()
    return manifests


def main() -> None:
    root = Path(tempfile.mkdtemp(prefix="repro-fleet-"))
    print(f"workdir: {root}")
    manifests = simulate_fleet(root)
    # sharded ingest/query tier: hosts route to two aggregator shards
    # and every dashboard query below runs scatter/gather across them
    # (drop `shards=` for a single-store aggregator) — docs/sharding.md
    agg = Aggregator(root / "inbox", shards=2)
    n = agg.pump()
    print(f"aggregated {n} records from "
          f"{len(agg.store.hosts())} hosts, {len(agg.store.jobs())} jobs "
          f"across {agg.store.num_shards} shards "
          f"(sizes {agg.store.shard_sizes()})\n")

    # --- Fig 2: roofline overview ---------------------------------------
    points = roofline_points(agg.store, manifests)
    svg = render_roofline_svg(points, TPU_V5E)
    (root / "roofline.svg").write_text(svg)
    print(f"roofline overview: {root / 'roofline.svg'} "
          f"({len(points)} jobs)\n")

    # --- custom staff query (paper: Splunk query language) --------------
    rows = query(agg.store,
                 "search kind=perf gflops>0 "
                 "| stats avg(gflops_per_chip) avg(ai) count by job "
                 "| sort -avg_gflops_per_chip | head 5")
    print("top jobs by GFLOP/s/chip:")
    print(markdown_table(rows))

    # --- specialized views (§4.4) ----------------------------------------
    print("top apps by device-hours:")
    print(markdown_table(view_top_apps_by_device_hours(agg.store,
                                                       manifests)))
    print("accelerators reserved but idle:")
    print(markdown_table(view_idle_accelerators(agg.store)))
    print("large-memory underuse:")
    print(markdown_table(view_memory_underuse(agg.store, manifests)))
    print("low host participation:")
    print(markdown_table(view_low_participation(agg.store, manifests)))

    # --- automated findings (§4.6) ---------------------------------------
    bank = DetectorBank()
    events = bank.scan(agg.store, manifests)
    print("automated findings:")
    for e in events:
        print(f"  [{e.severity:8s}] {e.job:12s} {e.detector}: {e.message}")

    # --- per-job report for the worst offender ---------------------------
    if events:
        job = events[0].job
        report = generate_report(agg.store, job, root / "reports" / job,
                                 manifests, hw=TPU_V5E)
        print(f"\nper-job report for {job}: {report}")


if __name__ == "__main__":
    main()
