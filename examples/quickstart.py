"""Quickstart: train a tiny monitored model, query its metrics, write the
per-job report.

    PYTHONPATH=src python examples/quickstart.py
"""

import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

from repro.configs import get_arch, reduced
from repro.core import Aggregator, JobManifest, TrainMonitor, query
from repro.core.report import generate_report
from repro.core.transport import Shipper, StreamFileSink
from repro.data import Pipeline, SyntheticSource
from repro.models import Model, ModelOptions
from repro.optim import AdamW, OptimizerConfig
from repro.train import StepConfig, make_train_step


def main() -> None:
    workdir = Path(tempfile.mkdtemp(prefix="repro-quickstart-"))
    cfg = reduced(get_arch("qwen3-8b"))
    model = Model(cfg, options=ModelOptions(attn_chunk=32))
    params = model.init(jax.random.PRNGKey(0))
    optimizer = AdamW(OptimizerConfig(lr=1e-3, warmup_steps=5,
                                      total_steps=40))
    opt_state = optimizer.init(params)

    # --- monitoring: one hpcmd daemon for this "host" -------------------
    manifest = JobManifest(job_id="quickstart.1", user="you",
                           app=cfg.name, num_hosts=1, num_chips=1)
    monitor = TrainMonitor(workdir, manifest, interval_s=0.5,
                           align_to_clock=False)

    pipe = Pipeline(SyntheticSource(cfg, seq_len=64, batch=4),
                    stats=monitor.pipeline_stats)
    step = make_train_step(model, optimizer, StepConfig(ce_seq_chunk=32))
    sample = {k: jnp.asarray(v) for k, v in pipe.next().items()}
    compiled = jax.jit(step).lower(params, opt_state, None,
                                   sample).compile()
    figures = monitor.register_compiled(compiled, tokens_per_step=4 * 64)
    print(f"compiled step: {figures['flops']:.2e} FLOPs/step, "
          f"{figures['collective_bytes']:.2e} collective B/step, "
          f"dominant roofline term: "
          f"{figures.get('dominant', 'none (no peaks for this device)')}")

    for i in range(30):
        batch = {k: jnp.asarray(v) for k, v in pipe.next().items()}
        params, opt_state, _, metrics = compiled(params, opt_state, None,
                                                 batch)
        monitor.on_step(i + 1, loss=float(metrics["loss"]),
                        tokens=4 * 64)
    pipe.close()
    monitor.stop()

    # --- transport -> aggregation -> analysis ---------------------------
    agg = Aggregator(workdir / "inbox")
    Shipper(monitor.daemon.spool.root,
            StreamFileSink(workdir / "inbox" / "host0.log")).ship_once()
    agg.pump()
    rows = query(agg.store,
                 "search kind=perf gflops>0 "
                 "| stats avg(gflops) avg(mfu) p50(step_time_s) count")
    print("splunklite:", rows[0])
    report = generate_report(agg.store, "quickstart.1",
                             workdir / "report", {"quickstart.1": manifest})
    print(f"report written: {report} (open report.html in a browser)")


if __name__ == "__main__":
    main()
