"""Serving launcher: batched greedy decoding with monitoring + report.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --reduced \
        --requests 8 --max-new 16 --workdir /tmp/serve-job --report
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_arch, reduced
from repro.core import Aggregator, JobManifest, TrainMonitor, query
from repro.core.report import generate_report
from repro.core.transport import Shipper, StreamFileSink
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, mesh_num_chips
from repro.models import Model, ModelOptions
from repro.train.serve import ServeEngine, ServeRequest


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--workdir", default="/tmp/repro-serve")
    ap.add_argument("--monitor-interval", type=float, default=0.25)
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--use-pallas", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    workdir = Path(args.workdir)
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = make_local_mesh()
    model = Model(cfg, options=ModelOptions(
        use_pallas=args.use_pallas, attn_chunk=256))
    params = model.init(jax.random.PRNGKey(0))
    job_id = f"serve.{cfg.name}.{os.getpid()}"
    manifest = JobManifest(job_id=job_id, app=cfg.name, shape="decode",
                           num_hosts=1, num_chips=mesh_num_chips(mesh),
                           started_ts=time.time())
    monitor = TrainMonitor(workdir, manifest,
                           interval_s=args.monitor_interval,
                           align_to_clock=False)
    engine = ServeEngine(model, params, batch_size=args.requests,
                         max_len=args.prompt_len + args.max_new + 8,
                         monitor=monitor)
    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        engine.submit(ServeRequest(
            prompt=rng.integers(0, cfg.vocab_size, args.prompt_len,
                                dtype=np.int64).astype(np.int32),
            max_new_tokens=args.max_new))
    t0 = time.time()
    done = engine.run()
    dt = time.time() - t0
    total_tokens = sum(len(r.out) for r in done)
    monitor.stop()
    print(f"[serve] {len(done)} requests, {total_tokens} tokens in "
          f"{dt:.2f}s ({total_tokens / dt:.1f} tok/s)", flush=True)

    inbox = workdir / "inbox"
    Shipper(monitor.daemon.spool.root,
            StreamFileSink(inbox / "host0.log")).ship_once()
    if args.report:
        agg = Aggregator(inbox)
        agg.pump()
        out = generate_report(agg.store, job_id,
                              workdir / "reports" / job_id,
                              {job_id: manifest})
        rows = query(agg.store, f"search kind=perf job={job_id} "
                                "| stats max(steps_per_s)")
        print(f"[serve] report: {out}; {rows}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
