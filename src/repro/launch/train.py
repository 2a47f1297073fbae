"""Production training launcher with integrated monitoring.

Runs a real training job (``--reduced`` sizes on the CPU, published widths
on a TPU — see chip_smoke.py; on several chips the parameters, optimizer
state and batch are sharded over a ("data", "model") mesh):
data pipeline -> jit'd train step -> checkpointing -> hpcmd monitoring ->
per-job report.  This is the end-to-end driver used by the examples and
by the elastic supervisor (launch/elastic.py), which restarts this
process on failure and relies on --resume auto-restore.

Usage::

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
        --steps 100 --seq-len 128 --batch 8 --workdir /tmp/job --resume
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch, reduced
from repro.configs.base import ArchConfig
from repro.core import (Aggregator, JobManifest, TrainMonitor, query)
from repro.core.report import generate_report
from repro.core.telemetry import Registry
from repro.core.transport import Shipper, StreamFileSink
from repro.data import Pipeline, SyntheticSource
from repro.data.pipeline import MemmapSource
from repro.models import Model, ModelOptions
from repro.models.attention import tile_plan
from repro.optim import AdamW, OptimizerConfig
from repro.optim.optimizer import OptState
from repro.train import StepConfig, make_train_step
from repro.train.sharding import ShardingCtx, param_shardings
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, mesh_num_chips


PRESET_100M = dict(num_layers=12, d_model=768, num_heads=12,
                   num_kv_heads=4, head_dim=64, d_ff=2048, vocab_size=32768)


def build_config(args) -> ArchConfig:
    cfg = get_arch(args.arch)
    if args.preset_100m:
        cfg = dataclasses.replace(cfg, **PRESET_100M,
                                  name=cfg.name + "-100m", dtype="float32")
    elif args.reduced:
        cfg = reduced(cfg)
    return cfg


def build_model(cfg: ArchConfig, args, ctx=None) -> Model:
    return Model(cfg, ctx=ctx, options=ModelOptions(remat_policy=args.remat))


def record_attention_tiles(model: Model, seq_len: int,
                           registry: Registry) -> Dict[str, float]:
    """Share of the attention tiles the train step computes, by layer kind
    (``local``, ``global``), from ``tile_plan`` at the step's length (meta
    tokens included); set as the gauge ``repro.attention.live_tile_share``.
    """
    cfg = model.cfg
    if not cfg.has_attention:
        return {}
    pos = jnp.arange(seq_len + cfg.num_meta_tokens, dtype=jnp.int32)
    shares = {}
    for kind in sorted(set(cfg.layer_kinds()) & {"local", "global"}):
        plan = tile_plan(pos, pos, chunk=model.opt.attn_chunk, causal=True,
                         window=cfg.window_size if kind == "local" else None)
        shares[kind] = int(plan.sum()) / plan.size
        registry.gauge("repro.attention.live_tile_share",
                       kind=kind).set(shares[kind])
    return shares


def build_optimizer(args) -> AdamW:
    return AdamW(OptimizerConfig(lr=args.lr, warmup_steps=10,
                                 total_steps=max(args.steps, 11)))


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny smoke-size variant of the arch")
    ap.add_argument("--preset-100m", action="store_true",
                    help="~100M-param variant of the arch family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--workdir", default="/tmp/repro-train")
    ap.add_argument("--job-id", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--monitor-interval", type=float, default=2.0)
    ap.add_argument("--no-monitor", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="full",
                    choices=["none", "full", "dots", "dots_no_batch"])
    ap.add_argument("--use-pallas", action="store_true",
                    help="refused: the Pallas kernels are forward-only")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--corpus", default=None,
                    help="binary uint32 token corpus (else synthetic)")
    ap.add_argument("--num-hosts", type=int, default=1,
                    help="simulated host count for pipeline sharding")
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--report", action="store_true",
                    help="generate the per-job report at the end")
    ap.add_argument("--fail-at-step", type=int, default=0,
                    help="crash deliberately (fault-tolerance demos)")
    args = ap.parse_args(argv)
    if args.use_pallas:
        ap.error("--use-pallas: the Pallas kernels have no backward pass, "
                 "so they cannot train; they serve (launch/serve.py)")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    enable_compile_cache()
    workdir = Path(args.workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    cfg = build_config(args)
    mesh = make_local_mesh(args.model_axis)
    ctx = ShardingCtx(mesh=mesh) if mesh_num_chips(mesh) > 1 else None
    model = build_model(cfg, args, ctx)
    optimizer = build_optimizer(args)
    job_id = args.job_id or f"train.{cfg.name}.{os.getpid()}"
    manifest = JobManifest(
        job_id=job_id, user=os.environ.get("USER", "user"),
        app=cfg.name, shape=f"seq{args.seq_len}xb{args.batch}",
        num_hosts=args.num_hosts, num_chips=mesh_num_chips(mesh),
        mesh_shape=str(dict(mesh.shape)), started_ts=time.time())
    monitor = TrainMonitor(workdir, manifest,
                           host=f"host{args.host_id:04d}",
                           interval_s=args.monitor_interval,
                           enabled=not args.no_monitor)

    # ---- state init / resume ------------------------------------------
    # on a mesh, parameters and optimizer moments follow the sharding
    # rules (ZeRO-style over "data") and the batch splits over "data"
    key = jax.random.PRNGKey(0)
    p_shard = opt_shard = None
    if ctx is not None:
        p_shard = param_shardings(jax.eval_shape(model.init, key), ctx)
        opt_shard = OptState(NamedSharding(mesh, PartitionSpec()),
                             p_shard, p_shard)
    ckpt = CheckpointManager(workdir / "ckpt", keep=3,
                             host_id=args.host_id)
    start_step = 0
    params = opt_state = None
    if args.resume:
        restored = ckpt.restore_latest()
        if restored is not None:
            start_step, tree, meta = restored
            o = tree["opt"]
            params = jax.device_put(tree["params"], p_shard)
            opt_state = jax.device_put(
                OptState(o["count"], o["mu"], o["nu"]), opt_shard)
            print(f"[train] resumed from step {start_step}", flush=True)
    if params is None:
        params = jax.jit(model.init, out_shardings=p_shard)(key)
        opt_state = jax.jit(optimizer.init, out_shardings=opt_shard)(params)

    def put_batch(host_batch):
        return {k: jax.device_put(v, None if ctx is None else NamedSharding(
                    mesh, ctx.spec(("batch",) + (None,) * (v.ndim - 1),
                                   v.shape)))
                for k, v in host_batch.items()}

    # ---- data -----------------------------------------------------------
    if args.corpus:
        source = MemmapSource(args.corpus, cfg, args.seq_len, args.batch,
                              host_id=args.host_id,
                              num_hosts=args.num_hosts)
    else:
        source = SyntheticSource(cfg, args.seq_len, args.batch,
                                 host_id=args.host_id,
                                 num_hosts=args.num_hosts)
    pipe = Pipeline(source, stats=monitor.pipeline_stats,
                    start_step=start_step)

    # ---- compile + register with the monitor ---------------------------
    step_fn = make_train_step(model, optimizer, StepConfig(
        num_microbatches=args.microbatches,
        compress_grads=args.compress_grads))
    sample = put_batch(source.get(start_step))
    jitted = jax.jit(step_fn, donate_argnums=(0, 1),
                     out_shardings=(p_shard, opt_shard, None, None))
    t_compile = time.perf_counter()
    compiled = jitted.lower(params, opt_state, None, sample).compile()
    t_compile = time.perf_counter() - t_compile
    figures = monitor.register_compiled(
        compiled, tokens_per_step=args.batch * args.seq_len)
    print(f"[train] compiled in {t_compile:.2f}s: "
          f"{figures['flops']:.3e} flops/step/dev, "
          f"dominant={figures.get('dominant', 'no peak for this device')}",
          flush=True)
    tiles = record_attention_tiles(model, args.seq_len,
                                   monitor.daemon.telemetry.registry)
    if tiles:
        print(f"[train] attention tiles computed: {tiles}", flush=True)

    # ---- loop -----------------------------------------------------------
    t_last = time.time()
    for step in range(start_step, args.steps):
        if (args.fail_at_step and step == args.fail_at_step
                and start_step == 0):
            # transient fault: only the fresh (non-resumed) incarnation
            # crashes — restarted-from-checkpoint runs proceed
            print(f"[train] injected failure at step {step}", flush=True)
            os._exit(17)
        t0 = time.perf_counter()
        batch = put_batch(pipe.next())
        wait = time.perf_counter() - t0
        params, opt_state, _, metrics = compiled(params, opt_state, None,
                                                 batch)
        loss = float(metrics["loss"])
        monitor.on_step(step + 1, loss=loss,
                        tokens=args.batch * args.seq_len)
        if (step + 1) % args.checkpoint_every == 0 \
                or step + 1 == args.steps:
            t_save = time.perf_counter()
            ckpt.save(step + 1, {
                "params": jax.tree_util.tree_map(np.asarray, params),
                "opt": {"count": np.asarray(opt_state.count),
                        "mu": jax.tree_util.tree_map(np.asarray,
                                                     opt_state.mu),
                        "nu": jax.tree_util.tree_map(np.asarray,
                                                     opt_state.nu)}})
            print(f"[train] checkpoint at step {step + 1} saved in "
                  f"{time.perf_counter() - t_save:.2f}s", flush=True)
        if (step + 1) % 10 == 0 or step == start_step:
            dt = time.time() - t_last
            t_last = time.time()
            print(f"[train] step {step + 1}/{args.steps} "
                  f"loss={loss:.4f} ({dt:.1f}s/10 steps)", flush=True)
    pipe.close()
    monitor.stop()

    # ---- ship logs + report --------------------------------------------
    inbox = workdir / "inbox"
    sink = StreamFileSink(inbox / f"host{args.host_id:04d}.log")
    Shipper(monitor.daemon.spool.root, sink,
            delete_shipped=False).ship_once()
    if args.report:
        agg = Aggregator(inbox)
        agg.pump()
        out = generate_report(agg.store, job_id, workdir / "reports" /
                              job_id, {job_id: manifest})
        rows = query(agg.store,
                     f"search kind=perf job={job_id} gflops>0 "
                     "| stats avg(gflops) avg(mfu) count")
        print(f"[train] report: {out}; perf summary: {rows}", flush=True)
    print("[train] done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
