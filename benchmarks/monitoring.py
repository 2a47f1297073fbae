"""Benchmarks for the monitoring system itself — one per paper
table/figure/claim.

* ``bench_data_volume``   — paper §5: ~3 KiB/node/sample, ~1.8 GiB/day for
  ~4200 nodes.  We measure OUR bytes/node/sample and extrapolate.
* ``bench_overhead``      — paper §4: "negligible overhead".  Train steps
  with monitoring on vs off.
* ``bench_roofline_view`` — paper Fig. 2: roofline overview render from a
  fleet of jobs.
* ``bench_job_view``      — paper Fig. 3: detailed job view (temporal +
  min/median/max statistical aggregation).
* ``bench_detectors``     — paper §4.4/§5 specialized views: planted
  anomalies; precision/recall + scan latency.
* ``bench_splunklite``    — query latency on a 100k-record store.
* ``bench_incremental``   — repeated fleet queries through the
  segment-keyed partial-aggregate cache: cold vs warm vs
  append-then-requery (docs/incremental.md).
* ``bench_compaction``    — docs/storage.md tiers: cold query pre/post
  segment compaction, compressed-tier byte ratio, rollup query vs the
  raw columnar scan it must match.
* ``bench_restart``       — §4.3 retention: aggregator cold-start from
  persisted columnar segments (mmap) vs full wire-line replay.
* ``bench_remote``        — remote shard execution (docs/remote.md):
  fleet query over 4 worker processes (overlapped scatter + worker-side
  partial caches) vs the same-run in-process sharded path.
* ``bench_service``       — multi-tenant query service (docs/service.md):
  p50/p99 latency and dedup hit rate under 8 simultaneous queriers
  (cheap dashboard refreshes + expensive batch scans) vs the same
  workload behind one global lock.
"""

from __future__ import annotations

import time
from pathlib import Path

import numpy as np

from benchmarks.common import row, timeit


def _fleet_store(n_jobs=24, hosts_per_job=4, samples=30, seed=0,
                 plant_anomalies=True, store=None):
    """Synthetic fleet: healthy jobs + planted hang/idle/low-mfu jobs.
    Pass a pre-configured ``store`` (e.g. a durable one) to fill it."""
    from repro.core.aggregator import MetricStore
    from repro.core.daemon import JobManifest
    from repro.core.schema import MetricRecord
    rng = np.random.default_rng(seed)
    if store is None:
        store = MetricStore()
    manifests = {}
    planted = {"hang": set(), "idle_accelerator": set(), "low_mfu": set()}
    apps = ["gemma2-27b", "qwen3-8b", "mamba2-780m", "llama4-scout-17b-a16e"]
    for j in range(n_jobs):
        job = f"job.{j:03d}"
        app = apps[j % len(apps)]
        man = JobManifest(job_id=job, app=app, user=f"user{j % 5}",
                          num_hosts=hosts_per_job,
                          num_chips=hosts_per_job * 4)
        manifests[job] = man
        kind = "healthy"
        if plant_anomalies:
            if j % 8 == 5:
                kind = "hang"
                planted["hang"].add(job)
            elif j % 8 == 6:
                kind = "idle"
                planted["idle_accelerator"].add(job)
            elif j % 8 == 7:
                kind = "lowmfu"
                planted["low_mfu"].add(job)
        base_g = rng.uniform(40, 90)
        for h in range(hosts_per_job):
            host = f"node{j:03d}-{h}"
            for s in range(samples):
                ts = 1000.0 + s * 10.0
                stalled = kind == "hang" and s > samples // 2
                # idle-accelerator jobs still make (host-side) progress —
                # low but nonzero device numbers, hbm untouched
                g = (0.0 if stalled
                     else 5.0 if kind == "idle" else base_g * 16)
                mfu = (0.02 if kind == "lowmfu"
                       else (0.0 if g == 0 else rng.uniform(0.3, 0.5)))
                store.insert(MetricRecord(ts, host, job, "perf", {
                    "gflops": g, "gflops_per_chip": g / 16,
                    "mfu": mfu, "ai": float(rng.uniform(1, 300)),
                    "steps_per_s": 0.0 if stalled else 1.0,
                    "step_time_s": float(rng.uniform(0.9, 1.2)),
                    "step": s}))
                store.insert(MetricRecord(ts, host, job, "device", {
                    "hbm_frac_used": 0.01 if kind == "idle"
                    else float(rng.uniform(0.4, 0.8)),
                    "local_devices": 4}))
    return store, manifests, planted


def bench_data_volume(out_dir: Path):
    """Measure bytes per node per sample; extrapolate fleet volume."""
    import tempfile
    from repro.core.daemon import DaemonConfig, Hpcmd, JobManifest
    from repro.core.derived import TPU_V5E
    from repro.core.sources import (DeviceSource, EnvSource, ProcSource,
                                    StaticStepCost, StepClock,
                                    XlaCostSource)
    tmp = Path(tempfile.mkdtemp())
    clock = StepClock()
    d = Hpcmd(tmp / "spool", DaemonConfig(align_to_clock=False),
              host="bench-node", manifest=JobManifest(job_id="bench.1",
                                                      app="gemma2-27b"))
    src = XlaCostSource(clock, TPU_V5E)  # a simulated v5e node
    src.set_cost(StaticStepCost(flops=1e12, bytes=1e11,
                                collective_bytes=1e9, num_chips=4,
                                tokens_per_step=4096))
    d.add_source(src)
    d.add_source(DeviceSource())
    d.add_source(ProcSource())
    d.add_source(EnvSource())
    n_samples = 20
    for i in range(n_samples):
        clock.record(i, tokens=4096, loss=2.0, ts=1000.0 + i)
        d.tick(1000.0 + i + 0.5)
    total = sum(p.stat().st_size for p in (tmp / "spool").glob("*.log"))
    bytes_per_sample = total / n_samples
    # paper: 10-min sampling, DRACO+COBRA ~= 4190 nodes
    nodes = 4190
    per_day = bytes_per_sample * nodes * (24 * 6)
    us = timeit(lambda: d.tick(time.time()), warmup=1, iters=10)
    return [
        row("data_volume.bytes_per_node_sample", us,
            f"{bytes_per_sample:.0f}B (paper ~3KiB)"),
        row("data_volume.fleet_per_day_gib", us,
            f"{per_day / 2**30:.2f}GiB@{nodes}nodes (paper ~1.8GiB)"),
    ]


def bench_overhead(out_dir: Path):
    """Per-step cost of monitoring: train with monitor on vs off."""
    import jax
    import jax.numpy as jnp
    import tempfile
    from repro.configs import get_arch, reduced
    from repro.core import JobManifest, TrainMonitor
    from repro.models import Model, ModelOptions
    from repro.data import SyntheticSource
    from repro.optim import AdamW, OptimizerConfig
    from repro.train import StepConfig, make_train_step

    cfg = reduced(get_arch("qwen3-8b"))
    model = Model(cfg, options=ModelOptions(remat_policy="full",
                                            attn_chunk=16))
    params = model.init(jax.random.PRNGKey(0))
    opt = AdamW(OptimizerConfig())
    state = opt.init(params)
    src = SyntheticSource(cfg, 64, 8)
    batch = {k: jnp.asarray(v) for k, v in src.get(0).items()}
    step = jax.jit(make_train_step(model, opt, StepConfig(ce_seq_chunk=32)))
    p2, s2, _, _ = step(params, state, None, batch)  # compile

    def run(monitor):
        p, s = params, state
        t0 = time.perf_counter()
        for i in range(20):
            p, s, _, m = step(p, s, None, batch)
            if monitor is not None:
                monitor.on_step(i, loss=1.0, tokens=512)
        jax.block_until_ready(p)
        return (time.perf_counter() - t0) / 20 * 1e6

    bare_us = run(None)
    tmp = Path(tempfile.mkdtemp())
    mon = TrainMonitor(tmp, JobManifest(job_id="ovh.1", app=cfg.name),
                       interval_s=0.5, align_to_clock=False)
    mon_us = run(mon)
    mon.stop()
    ovh = max(mon_us - bare_us, 0.0)
    pct = ovh / bare_us * 100
    return [
        row("overhead.bare_step", bare_us, "us/step"),
        row("overhead.monitored_step", mon_us,
            f"+{pct:.2f}% (paper: negligible)"),
    ]


def bench_roofline_view(out_dir: Path):
    """Fig. 2: roofline overview of a fleet."""
    from repro.core.dashboards import render_roofline_svg, roofline_points
    from repro.core.derived import TPU_V5E
    store, manifests, _ = _fleet_store()
    points = roofline_points(store, manifests)
    svg = render_roofline_svg(points, TPU_V5E)
    out = out_dir / "dashboards"
    out.mkdir(parents=True, exist_ok=True)
    (out / "roofline.svg").write_text(svg)
    us = timeit(lambda: render_roofline_svg(
        roofline_points(store, manifests), TPU_V5E))
    return [row("roofline_view.render", us,
                f"{len(points)}jobs->{out / 'roofline.svg'}")]


def bench_job_view(out_dir: Path):
    """Fig. 3: detailed job view + statistical aggregation."""
    from repro.core.dashboards import (job_metric_series,
                                       job_statistical_view,
                                       render_timeseries_svg)
    store, manifests, _ = _fleet_store()
    job = "job.000"

    def render():
        series = job_metric_series(store, job, "gflops")
        stat = job_statistical_view(store, job, "gflops")
        s1 = render_timeseries_svg(series, "gflops", "gflops")
        s2 = render_timeseries_svg(stat, "stat", "gflops")
        return s1, s2

    s1, s2 = render()
    out = out_dir / "dashboards"
    out.mkdir(parents=True, exist_ok=True)
    (out / "job_view.svg").write_text(s1)
    (out / "job_view_stat.svg").write_text(s2)
    us = timeit(render)
    n = len(list(store.select(job=job, kind="perf")))
    return [row("job_view.render", us, f"{n}samples")]


def bench_detectors(out_dir: Path):
    """§4.4/§5 specialized views: planted-anomaly precision/recall."""
    from repro.core.detectors import DetectorBank
    store, manifests, planted = _fleet_store()
    bank = DetectorBank()
    events = bank.scan(store, manifests)
    results = []
    for det in ("hang", "idle_accelerator", "low_mfu"):
        found = {e.job for e in events if e.detector == det}
        want = planted[det]
        tp = len(found & want)
        prec = tp / len(found) if found else 1.0
        rec = tp / len(want) if want else 1.0
        results.append((det, prec, rec))
    us = timeit(lambda: DetectorBank().scan(store, manifests))
    rows = [row(f"detectors.{d}", us, f"prec={p:.2f},recall={r:.2f}")
            for d, p, r in results]
    assert all(p == 1.0 and r == 1.0 for _, p, r in results), results
    return rows


def bench_splunklite(out_dir: Path):
    """Query engine latency on a larger store: columnar executor vs the
    legacy row executor on the same query/workload, plus a 100k+-record
    columnar-only sample."""
    from repro.core.splunklite import query
    store, manifests, _ = _fleet_store(n_jobs=60, hosts_per_job=8,
                                       samples=40)
    q = ("search kind=perf gflops>0 "
         "| stats avg(gflops) p90(step_time_s) count by job "
         "| sort -avg_gflops | head 10")
    us = timeit(lambda: query(store, q), warmup=1, iters=5)
    us_rows = timeit(lambda: query(store, q, engine="rows"),
                     warmup=1, iters=3)
    rows = [
        row("splunklite.fleet_query", us, f"{len(store)}records"),
        row("splunklite.fleet_query_rows", us_rows,
            f"{len(store)}records,legacy={us_rows / max(us, 1e-9):.1f}x"),
    ]
    big, _m, _p = _fleet_store(n_jobs=110, hosts_per_job=8, samples=60)
    us_big = timeit(lambda: query(big, q), warmup=1, iters=5)
    rows.append(row("splunklite.fleet_query_100k", us_big,
                    f"{len(big)}records"))
    return rows


def bench_anomaly(out_dir: Path):
    """§4.6 outlook: streaming EWMA/CUSUM anomaly detection — planted
    regression recall + per-record latency."""
    import time as _t
    import numpy as np
    from repro.core.anomaly import AnomalyBank
    from repro.core.schema import MetricRecord
    rng = np.random.default_rng(0)
    recs = []
    for host in range(8):
        for s in range(200):
            g = 800 + rng.standard_normal() * 8
            if host == 3 and s >= 120:
                g = 350.0 + rng.standard_normal() * 8  # planted regression
            recs.append(MetricRecord(1000.0 + s, f"n{host}", "j1", "perf",
                                     {"gflops": float(g)}))
    # 6-sigma threshold: at 4 sigma a 1600-sample noise stream is
    # expected to produce ~1 false alarm (EWMA variance warmup); the
    # planted regression sits at ~55 sigma either way
    bank = AnomalyBank(metrics=("gflops",), z_thresh=6.0)
    t0 = _t.perf_counter()
    for r in recs:
        bank.feed(r)
    dt = (_t.perf_counter() - t0) / len(recs) * 1e6
    flagged_hosts = {e.fields.get("host") for e in bank.events
                     if e.detector == "ewma_anomaly"}
    hit = "n3" in flagged_hosts
    fp = len(flagged_hosts - {"n3"})
    assert hit and fp == 0, (flagged_hosts,)
    return [row("anomaly.ewma_stream", dt,
                f"recall=1.0,fp_hosts={fp},n={len(recs)}")]


def bench_sharded(out_dir: Path):
    """Sharded query fan-out vs the single-store path on the same
    ≥100k-record fleet workload and the same fleet query.  Emits the
    sharded time, the same-run single-store time (the CI guard
    normalizes by it so runner speed cancels), and an exact-gather
    fallback sample."""
    from repro.core.shards import ShardedAggregator
    from repro.core.splunklite import query
    single, _m, _p = _fleet_store(n_jobs=110, hosts_per_job=8, samples=60)
    sharded = ShardedAggregator(num_shards=4)
    _fleet_store(n_jobs=110, hosts_per_job=8, samples=60, store=sharded)
    assert len(sharded) == len(single)
    q = ("search kind=perf gflops>0 "
         "| stats avg(gflops) p90(step_time_s) count by job "
         "| sort -avg_gflops | head 10")
    # results agree (quantiles within the documented bound)
    got = {r["job"]: r for r in query(sharded, q)}
    want = {r["job"]: r for r in query(single, q)}
    assert got.keys() == want.keys()
    for job, w in want.items():
        assert got[job]["count"] == w["count"]
        assert abs(got[job]["avg_gflops"] - w["avg_gflops"]) <= 1e-6
    # interleave the two paths so allocator/CPU drift cancels out of
    # the ratio (they run on identical data in the same windows)
    sh_t, si_t = [], []
    query(sharded, q), query(single, q)  # warmup
    for _ in range(9):
        t0 = time.perf_counter()
        query(sharded, q)
        sh_t.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        query(single, q)
        si_t.append(time.perf_counter() - t0)
    us_sharded = sorted(sh_t)[len(sh_t) // 2] * 1e6
    us_single = sorted(si_t)[len(si_t) // 2] * 1e6
    assert sharded.scatter_queries > 0  # the plan actually fanned out
    ratio = us_sharded / max(us_single, 1e-9)
    # acceptance: fan-out must not lose to the single store it shards
    # (generous ceiling for noisy shared CI runners)
    assert ratio <= 1.35, (us_sharded, us_single)
    q_exact = "search kind=perf gflops>0 | stats first(app) by job"
    us_exact = timeit(lambda: query(sharded, q_exact), warmup=1, iters=3)
    return [
        row("sharded.fleet_query", us_sharded,
            f"{len(sharded)}records,4shards,{ratio:.2f}x_of_single"),
        row("sharded.fleet_query_single", us_single,
            f"{len(single)}records,same_run_baseline"),
        row("sharded.exact_gather", us_exact,
            f"{len(sharded)}records,row_gather_fallback"),
    ]


def bench_incremental(out_dir: Path):
    """Incremental query engine (docs/incremental.md): repeated fleet
    queries against the segment-keyed partial-aggregate cache on the
    ≥100k-record workload — cold (empty cache) vs warm (all sealed
    segments cached: only the append buffer recomputes) vs
    append-then-requery (buffer + newly sealed segments only), with
    byte parity between the cached and uncached runs asserted."""
    from repro.core.schema import MetricRecord
    from repro.core.shards import ShardedAggregator
    from repro.core.splunklite import query
    store, _m, _p = _fleet_store(n_jobs=110, hosts_per_job=8, samples=60)
    q = ("search kind=perf gflops>0 "
         "| stats avg(gflops) p90(step_time_s) count by job "
         "| sort -avg_gflops | head 10")

    def cold():
        store.partial_cache.clear()
        return query(store, q, engine="incremental")

    def warm():
        return query(store, q, engine="incremental")

    us_cold = timeit(cold, warmup=1, iters=5)
    warm()  # prime
    us_warm = timeit(warm, warmup=1, iters=9)
    # cached and uncached runs must be byte-identical
    store.partial_cache.clear()
    assert warm() == warm(), "warm rerun diverged"
    stats = store.last_query_stats
    assert stats["mode"] == "incremental"
    assert stats["segments_computed"] == 0, stats
    assert stats["segments_cached"] == len(store._sealed)
    speedup = us_cold / max(us_warm, 1e-9)
    # acceptance: a warm repeated fleet query is >= 5x cheaper than the
    # same-run cold scan (it only recomputes the append buffer)
    assert speedup >= 5.0, (us_cold, us_warm)
    # append-then-requery: new samples land in the buffer; the sealed
    # fleet stays cached (explain counters prove it)
    def append_requery():
        store.insert(MetricRecord(1e7 + append_requery.i, "nZ", "job.000",
                                  "perf", {"gflops": 1.0,
                                           "step": append_requery.i}))
        append_requery.i += 1
        return query(store, q, engine="incremental")
    append_requery.i = 0
    us_append = timeit(append_requery, warmup=1, iters=5)
    stats = store.last_query_stats
    assert stats["segments_computed"] == 0, stats
    assert stats["buffer_rows"] == len(store._buffer)
    # sharded stores consult per-shard caches on every query
    sharded = ShardedAggregator(num_shards=4)
    _fleet_store(n_jobs=110, hosts_per_job=8, samples=60, store=sharded)
    query(sharded, q)  # prime
    us_sh_warm = timeit(lambda: query(sharded, q), warmup=1, iters=9)
    assert sharded.last_query_stats["segments_computed"] == 0
    return [
        row("incremental.fleet_query_cold", us_cold,
            f"{len(store)}records,{len(store._sealed)}segments"),
        row("incremental.fleet_query_warm", us_warm,
            f"{speedup:.1f}x_vs_cold,buffer_only"),
        row("incremental.append_requery", us_append,
            f"buffer={len(store._buffer)}rows,0_segments_recomputed"),
        row("incremental.sharded_fleet_query_warm", us_sh_warm,
            "4shards,per-shard_caches"),
    ]


def bench_remote(out_dir: Path):
    """Remote shard execution (docs/remote.md): the ≥100k-record fleet
    workload is built into a durable 4-shard store, then served by 4
    worker processes.  Measures the warm remote fleet query (worker-
    side partial caches primed; only append buffers recompute) against
    the same-run in-process sharded warm latency, plus a cold run with
    worker caches cleared.  Asserts byte parity with the in-process
    result, the ≤3x warm-latency acceptance bound, and that the
    overlap path issued every shard request before the first merge.
    Workers are started and stopped under hard deadlines — a hung
    worker cannot wedge the job."""
    import shutil
    import tempfile
    from repro.core.remote import RemoteShardedAggregator
    from repro.core.shards import ShardedAggregator
    from repro.core.splunklite import query
    tmp = Path(tempfile.mkdtemp())
    fleet = None
    try:
        sharded = ShardedAggregator(num_shards=4, directory=tmp / "fleet",
                                    seal_threshold=4096)
        _fleet_store(n_jobs=110, hosts_per_job=8, samples=60, store=sharded)
        n = len(sharded)
        q = ("search kind=perf gflops>0 "
             "| stats avg(gflops) p90(step_time_s) count by job "
             "| sort -avg_gflops | head 10")
        query(sharded, q)  # prime the in-process per-shard caches
        us_inproc = timeit(lambda: query(sharded, q), warmup=1, iters=9)
        want = query(sharded, q)
        sharded.close()
        # the worker fleet re-adopts the durable shard dirs (segments
        # mmap back in, WAL tails replay) — the PR 2 restart path
        fleet = RemoteShardedAggregator(num_shards=4,
                                        directory=tmp / "fleet",
                                        seal_threshold=4096,
                                        worker_idle_timeout_s=300.0,
                                        spawn_timeout_s=60.0)
        assert len(fleet) == n

        def cold():
            for sh in fleet.shards:
                sh.rpc("clear_cache")
            fleet.drop_scatter_memos()
            return query(fleet, q)

        got = cold()
        assert got == want, "remote rows diverged from in-process sharded"
        us_cold = timeit(cold, warmup=1, iters=3)
        query(fleet, q)  # prime worker caches
        us_warm = timeit(lambda: query(fleet, q), warmup=1, iters=9)
        stats = fleet.last_query_stats
        assert stats["mode"] == "scatter_gather" and stats["remote"]
        assert stats["segments_computed"] == 0, stats
        assert stats["degraded_shards"] == 0, stats
        assert stats["overlap"], \
            "scatter must issue all shard requests before the first merge"
        ratio = us_warm / max(us_inproc, 1e-9)
        # acceptance: warm remote fleet query within 3x of the same-run
        # in-process sharded warm latency (wire framing + codec is the
        # only extra work — partials are small)
        assert ratio <= 3.0, (us_warm, us_inproc)
        return [
            row("remote.fleet_query_warm", us_warm,
                f"{n}records,4workers,{ratio:.2f}x_of_inproc"),
            row("remote.fleet_query_cold", us_cold,
                "worker_caches_cleared"),
            row("remote.fleet_query_inproc", us_inproc,
                "same_run_in_process_sharded_warm"),
        ]
    finally:
        if fleet is not None:
            fleet.close()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_service(out_dir: Path):
    """Multi-tenant query service (docs/service.md) under load: 8
    simultaneous queriers — six dashboard tenants re-refreshing a small
    cheap query set (batch-deduped / result-cached) plus two analyst
    tenants running distinct expensive fleet scans at batch priority —
    against the ≥100k-record fleet store.  Measures per-op p50/p99
    latency under load, the dedup+cache hit rate, and aggregate
    throughput vs a *lock-serialized* direct path running the exact
    same thread/op mix (what the coordinator was before the service).
    Asserts byte parity with the direct path, ≥2x aggregate throughput
    vs the lock-serialized run, and that dedup actually collapsed the
    repeated refreshes.  The p99 row is normalized in CI by the
    same-run single-thread scan latency, keeping the guard
    machine-independent."""
    import threading
    from repro.core.service import QueryService
    from repro.core.splunklite import query

    store, _m, _p = _fleet_store(n_jobs=110, hosts_per_job=8, samples=60)
    n = len(store)
    cheap = [
        "search kind=perf | stats avg(gflops) count by job | sort job "
        "| head 15",
        "search kind=device | stats avg(hbm_frac_used) by job | sort job "
        "| head 15",
        "search kind=perf | timechart span=60 avg(mfu)",
    ]
    scans = [
        f"search kind=perf gflops>{x} | stats avg(gflops) p90(step_time_s) "
        "dc(host) by job | sort -avg_gflops | head 20"
        for x in (0, 100, 200, 300)
    ]
    want = {q: query(store, q) for q in cheap + scans}  # direct oracle

    def workload(run_op):
        """8 threads: 6 refreshers x 40 cheap ops, 2 scanners x 8 scans."""
        threads = [threading.Thread(
            target=lambda t=t: [run_op(t, cheap[i % len(cheap)], "cheap")
                                for i in range(40)]) for t in range(6)]
        threads += [threading.Thread(
            target=lambda t=t: [run_op(t, scans[i % len(scans)], "scan")
                                for i in range(8)]) for t in (6, 7)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        return (time.perf_counter() - t0) * 1e6

    # --- lock-serialized baseline: the pre-service coordinator shape
    big_lock = threading.Lock()
    locked_failures = []

    def locked_op(tenant, q, _klass):
        with big_lock:
            if query(store, q) != want[q]:  # pragma: no cover
                locked_failures.append(q)

    us_locked = workload(locked_op)
    assert not locked_failures

    # --- the service run: same mix, latencies recorded per op
    svc = QueryService(store, max_concurrency=4, tenant_quota=0)
    lat_lock = threading.Lock()
    latencies = []
    svc_failures = []

    def service_op(tenant, q, klass):
        t0 = time.perf_counter()
        rows = svc.query(q, tenant=f"t{tenant}",
                         priority="batch" if klass == "scan"
                         else "interactive")
        us = (time.perf_counter() - t0) * 1e6
        with lat_lock:
            latencies.append(us)
        if rows != want[q]:  # pragma: no cover
            svc_failures.append(q)

    us_svc = workload(service_op)
    counters = dict(svc.counters)
    svc.close()
    assert not svc_failures, "service rows diverged from direct path"
    latencies.sort()
    p50 = latencies[len(latencies) // 2]
    p99 = latencies[min(len(latencies) - 1, int(len(latencies) * 0.99))]
    hit_rate = ((counters["deduped"] + counters["result_cache_hits"])
                / max(counters["submitted"], 1))
    speedup = us_locked / max(us_svc, 1e-9)
    # acceptance: the repeated refreshes must coalesce (one execution
    # serves many waiters), and the same workload must clear 2x the
    # lock-serialized aggregate throughput
    assert counters["executed"] < counters["submitted"], counters
    assert hit_rate >= 0.3, counters
    assert speedup >= 2.0, (us_svc, us_locked)
    us_scan_serial = timeit(lambda: query(store, scans[0]),
                            warmup=1, iters=5)
    return [
        row("service.query_p50_loaded", p50,
            f"{n}records,8queriers"),
        row("service.query_p99_loaded", p99,
            f"dedup_hit_rate={hit_rate:.2f}"),
        row("service.scan_serial", us_scan_serial,
            "same_run_single_thread_direct"),
        row("service.workload_concurrent", us_svc,
            f"{speedup:.2f}x_vs_locked,executed={counters['executed']}"
            f"/{counters['submitted']}"),
        row("service.workload_locked", us_locked,
            "global_lock_direct_path"),
    ]


def bench_restart(out_dir: Path):
    """Aggregator cold-start on the 100k+-record fleet workload:
    mmap-load of persisted columnar segments (+ WAL replay of the
    unsealed tail) vs. full wire-line replay of a consolidated archive
    (the pre-persistence restart path)."""
    import shutil
    import tempfile
    from repro.core.aggregator import MetricStore
    from repro.core.schema import encode_line
    tmp = Path(tempfile.mkdtemp())
    try:
        store = MetricStore(seal_threshold=4096, directory=tmp / "store")
        _fleet_store(n_jobs=110, hosts_per_job=8, samples=60, store=store)
        n = len(store)
        wal_lines = len((tmp / "store" / "wal.log").read_text().splitlines())
        archive = [encode_line(r) for r in store.records]
        store.close()

        def cold_start():
            MetricStore(seal_threshold=4096, directory=tmp / "store").close()

        us_cold = timeit(cold_start, warmup=1, iters=3)
        us_replay = timeit(lambda: MetricStore(seal_threshold=4096)
                           .ingest_lines(archive), warmup=0, iters=1)
        speedup = us_replay / max(us_cold, 1e-9)
        # measured ~16x; the floor only catches the mmap path degrading
        # to a re-parse, with headroom for noisy shared CI runners
        assert speedup >= 3.0, (us_cold, us_replay)
        return [
            row("restart.cold_start", us_cold,
                f"{n}records,wal_replayed={wal_lines},"
                f"{speedup:.1f}x_vs_line_replay"),
            row("restart.line_replay", us_replay, f"{n}records"),
        ]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_compaction(out_dir: Path):
    """Segment compaction + tiered storage (docs/storage.md) on the
    ≥100k-record fleet workload sealed into hundreds of small segments
    (a long-running aggregator's steady state).  Measures the *cold*
    fleet query — fresh read-only open per call, so every manifest and
    payload is re-read from disk — before vs after compaction into
    compressed cold-tier segments, the compressed-vs-raw byte ratio,
    and a rollup-tier aggregate vs the same query forced down the raw
    columnar scan.  Asserts the ISSUE 6 acceptance floors: >= 10x
    segment-count reduction, >= 3x cold-query speedup, identical rows
    pre/post compaction, and rollup aggregates matching the raw scan."""
    import shutil
    import tempfile
    from repro.core.aggregator import MetricStore
    from repro.core.splunklite import query
    tmp = Path(tempfile.mkdtemp())
    try:
        store = MetricStore(seal_threshold=128, directory=tmp / "store")
        _fleet_store(n_jobs=110, hosts_per_job=8, samples=60, store=store)
        store.seal()
        n = len(store)
        segs_before = len(store._sealed)
        bytes_raw = store.storage_stats()["bytes"]
        store.close()
        q = ("search kind=perf gflops>0 "
             "| stats avg(gflops) p90(step_time_s) count by job "
             "| sort -avg_gflops | head 10")

        def cold_query():
            st = MetricStore(seal_threshold=128, directory=tmp / "store",
                             read_only=True)
            try:
                return query(st, q)
            finally:
                st.close()

        want = cold_query()
        us_pre = timeit(cold_query, warmup=1, iters=3)
        rw = MetricStore(seal_threshold=128, directory=tmp / "store")
        cstats = rw.compact()
        segs_after = len(rw._sealed)
        storage = rw.storage_stats()
        cold_tier = storage["tiers"]["cold"]
        rw.close()
        assert cold_query() == want, "rows diverged after compaction"
        us_post = timeit(cold_query, warmup=1, iters=3)
        reduction = segs_before / max(segs_after, 1)
        speedup = us_pre / max(us_post, 1e-9)
        # acceptance floors from ISSUE 6 (measured with headroom)
        assert reduction >= 10.0, (segs_before, segs_after)
        assert speedup >= 3.0, (us_pre, us_post)
        byte_ratio = cold_tier["bytes"] / max(cold_tier["raw_bytes"], 1)
        # rollup tier: bucketed partial-aggregate columns answer the
        # fleet aggregate without touching any raw segment
        ru = MetricStore(seal_threshold=128, directory=tmp / "store")
        ru.apply_retention(rollups=[(60.0, 0.0)])
        rq = "kind=perf ts>=0 | stats avg(gflops) count by job"
        got_ru = {r["job"]: r for r in query(ru, rq)}
        want_ru = {r["job"]: r
                   for r in query(ru, rq, engine="columnar")}
        assert got_ru.keys() == want_ru.keys()
        for job, w in want_ru.items():
            assert got_ru[job]["count"] == w["count"]
            assert abs(got_ru[job]["avg_gflops"] - w["avg_gflops"]) <= 1e-6
        assert ru.last_query_stats["rollup_segments"] > 0
        us_rollup = timeit(lambda: query(ru, rq), warmup=1, iters=5)
        us_raw = timeit(lambda: query(ru, rq, engine="columnar"),
                        warmup=1, iters=5)
        ru.close()
        return [
            row("compaction.cold_query_pre", us_pre,
                f"{n}records,{segs_before}segments,uncompacted"),
            row("compaction.cold_query_post", us_post,
                f"{segs_after}segments,{reduction:.0f}x_fewer,"
                f"{speedup:.1f}x_faster,"
                f"{cstats['rows']}rows_merged"),
            row("compaction.compressed_bytes", cold_tier["bytes"],
                f"{byte_ratio:.2f}x_of_raw,{bytes_raw}raw_bytes"),
            row("compaction.rollup_query", us_rollup,
                f"gran=60s,{us_raw / max(us_rollup, 1e-9):.1f}"
                "x_vs_raw_scan"),
            row("compaction.rollup_query_raw", us_raw,
                "same_run_raw_columnar_scan"),
        ]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_transport(out_dir: Path):
    """rsyslog-analog throughput: lines/s through spool->ship->ingest."""
    import tempfile
    from repro.core.aggregator import Aggregator
    from repro.core.schema import MetricRecord, encode_line
    from repro.core.transport import Shipper, Spool, StreamFileSink
    tmp = Path(tempfile.mkdtemp())
    sp = Spool(tmp / "spool")
    lines = [encode_line(MetricRecord(1000.0 + i, "n0", "j", "perf",
                                      {"gflops": float(i), "step": i}))
             for i in range(5000)]
    t0 = time.perf_counter()
    for ln in lines:
        sp.write_line(ln)
    agg = Aggregator(tmp / "inbox")
    Shipper(tmp / "spool", StreamFileSink(tmp / "inbox" / "n0.log")
            ).ship_once()
    n = agg.pump()
    dt = time.perf_counter() - t0
    assert n == 5000
    return [row("transport.pipeline", dt / n * 1e6,
                f"{n / dt:.0f}lines_per_s")]
