"""End-to-end integration: monitored training producing queryable metrics,
reports, detector events; serving engine; elastic restart; dry-run cell.
"""

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = Path(__file__).resolve().parents[1] / "src"


def test_monitored_training_end_to_end(tmp_path):
    from repro.configs import get_arch, reduced
    from repro.core import (TPU_V5E, Aggregator, JobManifest,
                            TrainMonitor, query)
    from repro.core.report import generate_report
    from repro.core.transport import Shipper, StreamFileSink
    from repro.models import Model, ModelOptions
    from repro.data import Pipeline, SyntheticSource
    from repro.optim import AdamW, OptimizerConfig
    from repro.train import StepConfig, make_train_step

    cfg = reduced(get_arch("gemma3-4b"))
    model = Model(cfg, options=ModelOptions(remat_policy="full",
                                            attn_chunk=16))
    params = model.init(jax.random.PRNGKey(0))
    opt = AdamW(OptimizerConfig(warmup_steps=2, total_steps=30))
    state = opt.init(params)
    man = JobManifest(job_id="it.1", app=cfg.name, num_hosts=1,
                      num_chips=1)
    mon = TrainMonitor(tmp_path, man, host="h0", interval_s=0.0,
                       hw=TPU_V5E, align_to_clock=False)
    src = SyntheticSource(cfg, 32, 4)
    pipe = Pipeline(src, stats=mon.pipeline_stats)
    step = make_train_step(model, opt, StepConfig(ce_seq_chunk=16))
    compiled = jax.jit(step).lower(params, state, None, {
        k: jnp.asarray(v) for k, v in src.get(0).items()}).compile()
    figures = mon.register_compiled(compiled, tokens_per_step=4 * 32)
    assert figures["flops"] > 0 and figures["dominant"] in (
        "compute", "memory", "collective")
    for i in range(6):
        batch = {k: jnp.asarray(v) for k, v in pipe.next().items()}
        params, state, _, metrics = compiled(params, state, None, batch)
        mon.on_step(i + 1, loss=float(metrics["loss"]), tokens=4 * 32)
    pipe.close()
    mon.stop()
    # ship -> aggregate -> query -> report
    agg = Aggregator(tmp_path / "inbox")
    Shipper(mon.daemon.spool.root,
            StreamFileSink(tmp_path / "inbox" / "h0.log")).ship_once()
    n = agg.pump()
    assert n > 0
    rows = query(agg.store, "search kind=perf gflops>0 "
                            "| stats avg(gflops) avg(mfu) count")
    assert rows and rows[0]["count"] >= 1
    rows = query(agg.store, "search kind=pipeline "
                            "| stats max(tokens_total)")
    assert rows[0]["max_tokens_total"] >= 6 * 128
    report = generate_report(agg.store, "it.1", tmp_path / "rep",
                             {"it.1": man})
    assert report.exists()
    html = (tmp_path / "rep" / "report.html").read_text()
    assert "svg" in html


def test_serve_engine_greedy(tmp_path):
    from repro.configs import get_arch, reduced
    from repro.models import Model, ModelOptions
    from repro.train.serve import ServeEngine, ServeRequest

    cfg = reduced(get_arch("qwen3-8b"))
    model = Model(cfg, options=ModelOptions(remat_policy="none",
                                            attn_chunk=16))
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, batch_size=2, max_len=64)
    eng.submit(ServeRequest(prompt=np.arange(5, dtype=np.int32) + 3,
                            max_new_tokens=4))
    eng.submit(ServeRequest(prompt=np.arange(8, dtype=np.int32) + 1,
                            max_new_tokens=4))
    done = eng.run()
    assert len(done) == 2
    for r in done:
        assert r.out.shape == (4,)
        assert (r.out >= 0).all() and (r.out < cfg.vocab_size).all()


@pytest.mark.slow
def test_elastic_restart_after_injected_failure(tmp_path):
    """Supervisor restarts a deliberately-crashing child; training
    completes from checkpoint."""
    cmd = [sys.executable, "-m", "repro.launch.elastic",
           "--workdir", str(tmp_path), "--max-restarts", "2", "--",
           "--arch", "qwen3-8b", "--reduced", "--steps", "12",
           "--seq-len", "32", "--batch", "4", "--checkpoint-every", "4",
           "--monitor-interval", "0.5", "--fail-at-step", "6",
           "--job-id", "elastic.test"]
    env = {"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin",
           "HOME": "/root"}
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=420,
                         env=env)
    assert "injected failure" in out.stdout
    assert "resumed from step" in out.stdout
    assert "[elastic] job completed" in out.stdout, out.stdout[-2000:]


@pytest.mark.slow
def test_dryrun_single_cell_subprocess():
    """One real dry-run cell (decode — fastest compile) on the 512-device
    production mesh, exercising the exact deliverable-(e) path."""
    out_dir = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch",
           "mamba2-780m", "--shape", "decode_32k"]
    env = {"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin",
           "HOME": "/root"}
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=560,
                         env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(
        (out_dir / "16x16" / "mamba2-780m__decode_32k.json").read_text())
    assert rec["ok"] and rec["chips"] == 256
    assert rec["fits_hbm"]
    assert rec["dominant"] in ("compute", "memory", "collective")


def test_compile_cache_location(monkeypatch):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(SRC.parent / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
