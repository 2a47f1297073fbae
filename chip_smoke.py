#!/usr/bin/env python3
"""Smoke run of the monitored trainer on a TPU.

    python chip_smoke.py              # one chip: device, kernels, main path
    python chip_smoke.py --chips 4    # four chips: the sharded step only

Every phase runs in this one process, which holds the chip(s):

* device  - the first device is a TPU whose kind has published peaks
            (``repro.core.derived.HARDWARE``);
* kernels - the Pallas kernels, lowered for the chip, at the qwen3-8b and
            gemma3-4b attention layouts and the mamba2-780m SSD layout,
            against the pure-jnp references;
* main    - ``repro.launch.train.main`` trains mamba2-780m at published
            widths (seq 2048, batch 4, bf16, random weights from a seed)
            for 10 steps with the monitor on, saves the final checkpoint,
            ships the records and writes the job report; the records are
            then read back through an ``Aggregator`` and checked;
* chips=4 - one train step of the same model on one chip, then the
            launcher's step on a 2x2 ("data", "model") mesh from the same
            parameters and batch: the step-1 losses must agree and the
            ingested ``kind=net`` record must show collective bytes.

Progress and measurements go to earlier lines.  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only when
every phase passed; any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SEED = 0
ARCH = "mamba2-780m"
TRAIN_ARGV = ["--arch", ARCH, "--seq-len", "2048", "--batch", "4",
              "--monitor-interval", "0.5"]
MAIN_STEPS = 10
# (arch, sliding window) — gemma3-4b's local layers use window 1024
ATTENTION_LAYOUTS = (("qwen3-8b", 0), ("gemma3-4b", 1024), ("gemma3-4b", 0))
ATTENTION_SEQ = 2048
SSD_BATCH, SSD_SEQ = 2, 2048
# the tolerances tests/test_kernels.py holds the kernels to
ATOL = {"bfloat16": 2e-2, "float32": 1e-4}
LOSS_ATOL = 2e-2          # bf16 model, 1 chip vs 2x2 mesh


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ------------------------------------------------------------------ device

def device_phase(chips: int) -> dict:
    import jax
    from repro.core.derived import hardware_for
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX's first device is {dev.platform} "
                           f"({dev.device_kind})")
    hw = hardware_for(dev.platform, dev.device_kind)  # unknown kind raises
    if len(devs) < chips:
        raise RuntimeError(f"{chips} chips asked for, JAX sees {len(devs)}")
    log(f"device: {len(devs)} x {dev.device_kind} ({hw.name}: "
        f"{hw.peak_flops:.3e} FLOP/s, {hw.hbm_bytes:.3e} B HBM)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


# ----------------------------------------------------------------- kernels

def _compiled(fn, *args):
    import jax
    t = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t
    if "tpu_custom_call" not in compiled.as_text():
        raise RuntimeError("compiled kernel holds no tpu_custom_call")
    return compiled, secs


def _max_err(out, ref) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(out, np.float32)
                               - np.asarray(ref, np.float32))))


def kernel_phase() -> None:
    import functools
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.kernels.ops import flash_attention_op, ssd_op
    from repro.kernels.ref import ref_attention
    from repro.models.ssm import ssd_chunked

    failures = []
    key = jax.random.PRNGKey(SEED)
    # the references (and the XLA parts of ssd_op) at full f32 precision,
    # which the kernels use for f32 operands
    with jax.default_matmul_precision("highest"):
        for arch, window in ATTENTION_LAYOUTS:
            cfg = get_arch(arch)
            d = cfg.resolved_head_dim
            key, kq, kk, kv = jax.random.split(key, 4)
            q = jax.random.normal(kq, (1, ATTENTION_SEQ, cfg.num_heads, d),
                                  jnp.bfloat16)
            k = jax.random.normal(kk, (1, ATTENTION_SEQ, cfg.num_kv_heads, d),
                                  jnp.bfloat16)
            v = jax.random.normal(kv, k.shape, jnp.bfloat16)
            compiled, secs = _compiled(functools.partial(
                flash_attention_op, causal=True, window=window,
                interpret=False), q, k, v)
            ref = jax.jit(functools.partial(ref_attention, causal=True,
                                            window=window))(q, k, v)
            err = _max_err(compiled(q, k, v), ref)
            tol = ATOL["bfloat16"]
            log(f"kernel flash_attention {arch} {cfg.num_heads}q/"
                f"{cfg.num_kv_heads}kv x {d} seq {ATTENTION_SEQ} window "
                f"{window}: compile {secs:.2f}s, max|err| {err:.3e} "
                f"(atol {tol})")
            if not err <= tol:
                failures.append(f"flash_attention {arch} window {window}")

        cfg = get_arch(ARCH)
        h, p, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (SSD_BATCH, SSD_SEQ, h, p)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(ks[1], (SSD_BATCH, SSD_SEQ, h)))
        a_log = jnp.log(jnp.linspace(1.0, 8.0, h))
        bm = jax.random.normal(ks[2], (SSD_BATCH, SSD_SEQ, n)) * 0.3
        cm = jax.random.normal(ks[3], (SSD_BATCH, SSD_SEQ, n)) * 0.3
        compiled, secs = _compiled(functools.partial(
            ssd_op, chunk=cfg.ssm_chunk, interpret=False), x, dt, a_log, bm, cm)
        y, h_fin = compiled(x, dt, a_log, bm, cm)
        y_ref, h_ref = jax.jit(functools.partial(
            ssd_chunked, chunk=cfg.ssm_chunk))(x, dt, a_log, bm, cm)
        err = max(_max_err(y, y_ref), _max_err(h_fin, h_ref))
        tol = ATOL["float32"]
        log(f"kernel ssd {ARCH} {h} heads x {p}, state {n}, chunk "
            f"{cfg.ssm_chunk}, seq {SSD_SEQ}: compile {secs:.2f}s, "
            f"max|err| {err:.3e} (atol {tol})")
        if not err <= tol:
            failures.append("ssd")
    if failures:
        raise AssertionError(f"kernels outside tolerance: {failures}")


# -------------------------------------------------------------------- main

def _job_rows(store, job: str, kind: str = "") -> list:
    from repro.core import query
    return query(store, f"search job={job}" + (f" kind={kind}" if kind
                                                else ""))


def _read_job(workdir: Path, job: str):
    from repro.core import Aggregator
    agg = Aggregator(workdir / "inbox")
    if agg.pump() <= 0:
        raise AssertionError("the job's inbox holds no records")
    bad = [r for r in _job_rows(agg.store, job) if "source_error" in r]
    if bad:
        raise AssertionError(f"sources failed: {bad[:3]}")
    return agg


def _memory_stats() -> dict:
    import jax
    return jax.devices()[0].memory_stats()


def main_phase(workdir: Path) -> None:
    from repro.core import query
    from repro.launch import train

    job = f"chip-smoke.{ARCH}"
    argv = TRAIN_ARGV + ["--steps", str(MAIN_STEPS), "--report",
                         "--workdir", str(workdir), "--job-id", job]
    log(f"main: train.main {' '.join(argv)}; "
        f"{shutil.disk_usage(workdir).free / 1e9:.1f} GB free in workdir")
    t = time.perf_counter()
    if train.main(argv) != 0:
        raise AssertionError("train.main failed")
    log(f"main: train.main returned after {time.perf_counter() - t:.2f}s")
    stats = _memory_stats()
    log(f"main: peak_bytes_in_use {stats['peak_bytes_in_use']} of "
        f"bytes_limit {stats['bytes_limit']}")

    agg = _read_job(workdir, job)
    perf = [r for r in _job_rows(agg.store, job, "perf")
            if r.get("gflops", 0) > 0 and r.get("step_time_s", 0) > 0]
    if not perf:
        raise AssertionError("no perf record with gflops>0, step_time_s>0")
    step_times = sorted(r["step_time_s"] for r in perf)
    log(f"main: {len(perf)} perf samples; step_time_s min "
        f"{step_times[0]:.4f} median {step_times[len(step_times) // 2]:.4f}"
        f"; mfu {[round(r.get('mfu', float('nan')), 4) for r in perf]}")
    device = _job_rows(agg.store, job, "device")
    if not any(r.get("hbm_bytes_limit", 0) > 0 for r in device):
        raise AssertionError(f"no device record with hbm_bytes_limit: "
                             f"{device[:2]}")
    meta = _job_rows(agg.store, job, "meta")
    if not meta or any(r.get("backend") != "tpu" for r in meta):
        raise AssertionError(f"meta record not from the TPU: {meta[:1]}")
    net = _job_rows(agg.store, job, "net")
    log(f"main: HLO FLOP/step {net[0]['hlo_flops']:.4e}, HLO traffic "
        f"bytes/step {net[0]['hlo_traffic_bytes']:.4e}")
    rows = query(agg.store, f"search kind=perf job={job} gflops>0 "
                            "| stats avg(gflops) avg(mfu) count")
    if not rows or not rows[0].get("count"):
        raise AssertionError("stats query returned no rows")
    log(f"main: stats {rows[0]}")
    report = workdir / "reports" / job / "report.html"
    if not report.is_file():
        raise AssertionError(f"no report at {report}")
    agg.close()


# -------------------------------------------------------------- four chips

def four_chip_phase(workdir: Path) -> None:
    import jax
    from repro.data import SyntheticSource
    from repro.launch import train
    from repro.train import StepConfig, make_train_step

    # one chip: the launcher's model, optimizer, init and first batch
    args = train.parse_args(TRAIN_ARGV + ["--steps", "1"])
    cfg = train.build_config(args)
    model = train.build_model(cfg, args)
    optimizer = train.build_optimizer(args)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    opt_state = jax.jit(optimizer.init)(params)
    batch = SyntheticSource(cfg, args.seq_len, args.batch).get(0)
    t = time.perf_counter()
    out = jax.jit(make_train_step(model, optimizer, StepConfig()),
                  donate_argnums=(0, 1))(params, opt_state, None, batch)
    loss_one = float(out[3]["loss"])
    log(f"chips=4: one-chip step-1 loss {loss_one:.6f} "
        f"({time.perf_counter() - t:.2f}s with compile)")
    del params, opt_state, out
    gc.collect()

    # four chips: the launcher on a 2x2 ("data", "model") mesh
    job = f"chip-smoke-4.{ARCH}"
    argv = TRAIN_ARGV + ["--steps", "1", "--model-axis", "2",
                         "--workdir", str(workdir), "--job-id", job]
    log(f"chips=4: train.main {' '.join(argv)}")
    if train.main(argv) != 0:
        raise AssertionError("train.main failed")
    agg = _read_job(workdir, job)
    perf = [r for r in _job_rows(agg.store, job, "perf")
            if r.get("step") == 1]
    if not perf:
        raise AssertionError("no perf record for step 1")
    loss_mesh = float(perf[0]["loss"])
    log(f"chips=4: mesh step-1 loss {loss_mesh:.6f}, |diff| "
        f"{abs(loss_mesh - loss_one):.3e} (atol {LOSS_ATOL})")
    if not abs(loss_mesh - loss_one) <= LOSS_ATOL:
        raise AssertionError("losses disagree")
    net = _job_rows(agg.store, job, "net")
    coll = {k: v for k, v in net[0].items() if k.startswith("coll_")}
    log(f"chips=4: collectives per step {coll}")
    if not net[0].get("coll_bytes", 0) > 0:
        raise AssertionError("no collective bytes on the mesh")
    meta = _job_rows(agg.store, job, "meta")
    if meta[0].get("num_chips") != 4 or meta[0].get("backend") != "tpu":
        raise AssertionError(f"meta record: {meta[0]}")
    agg.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    import jax
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_events.update([event]))

    device = device_phase(args.chips)
    # the job directory (which holds the ~7.8 GB checkpoint while the
    # main phase runs) lives outside the checkout and goes at the end
    workdir = Path(tempfile.mkdtemp(prefix="chip-smoke-"))
    try:
        t = time.perf_counter()
        if args.chips == 4:
            four_chip_phase(workdir)
        else:
            kernel_phase()
            gc.collect()
            log(f"kernels passed in {time.perf_counter() - t:.2f}s")
            t = time.perf_counter()
            main_phase(workdir)
        log(f"{'chips=4' if args.chips == 4 else 'main'} passed in "
            f"{time.perf_counter() - t:.2f}s")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    log(f"compile cache {cache_dir}: "
        f"{cache_events['/jax/compilation_cache/cache_hits']} hits, "
        f"{cache_events['/jax/compilation_cache/cache_misses']} misses")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
