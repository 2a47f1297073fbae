import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell and
extract the roofline terms from the compiled artifact.

This is how the distribution config is proven coherent without hardware:
``.lower().compile()`` must succeed for the 16x16 production mesh AND the
2x16x16 multi-pod mesh for every cell; ``memory_analysis()`` proves the
per-device footprint fits, ``cost_analysis()`` + HLO collective parsing
feed EXPERIMENTS.md §Dry-run / §Roofline.

Resumable: one JSON per cell under experiments/dryrun/<mesh>/; existing
cells are skipped unless --force.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-27b \
        --shape train_4k [--multi-pod] [--variant optimized]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import (ARCH_IDS, SHAPES, applicable, get_arch,
                           get_shape, skip_reason)
from repro.core import hlo as hlo_mod
from repro.core import hlo_cost as hlo_cost_mod
from repro.core.derived import TPU_V5E, roofline_terms
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_production_mesh, mesh_num_chips
from repro.models.modality import batch_specs
from repro.models.transformer import Model, ModelOptions
from repro.optim.optimizer import AdamW
from repro.train.sharding import ShardingCtx, param_shardings
from repro.train.step import StepConfig, make_train_step
from repro.train.serve import make_serve_step

OUT_ROOT = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


# Per-cell knobs for the §Perf hillclimb variants.  "baseline" is the
# paper-faithful configuration; named variants apply one optimization at a
# time (EXPERIMENTS.md §Perf documents hypothesis/result for each).
_BASE = dict(remat_policy="full", attn_chunk=2048,
             num_microbatches=4, ssm_chunk=0,
             seq_rule=("model",))

VARIANTS = {
    # Production default: full remat, 4 microbatches, Megatron-style
    # sequence-parallel residual stream (seq sharded over the model axis
    # between blocks — without it the per-layer saved activations are
    # replicated 16x over the model axis and big archs do not fit HBM;
    # the "no_seqpar" variant quantifies exactly that).
    "baseline": dict(_BASE),
    "no_seqpar": dict(_BASE, seq_rule=()),
    # §Perf hillclimb levers (one change each vs baseline):
    "remat_dots": dict(_BASE, remat_policy="dots"),
    "remat_none": dict(_BASE, remat_policy="none"),
    "microbatch1": dict(_BASE, num_microbatches=1),
    "microbatch2": dict(_BASE, num_microbatches=2),
    "microbatch8": dict(_BASE, num_microbatches=8),
    "attn_chunk_4k": dict(_BASE, attn_chunk=4096),
    "attn_chunk_1k": dict(_BASE, attn_chunk=1024),
    "ssm_chunk_128": dict(_BASE, ssm_chunk=128),
    "ssm_chunk_64": dict(_BASE, ssm_chunk=64),
}


def build_cell(arch_id: str, shape_id: str, multi_pod: bool,
               variant: str = "baseline"):
    """Lower + compile one cell; returns the result record dict."""
    arch = get_arch(arch_id)
    shape = get_shape(shape_id)
    knobs = VARIANTS[variant]
    if knobs.get("ssm_chunk"):
        import dataclasses
        arch = dataclasses.replace(arch, ssm_chunk=knobs["ssm_chunk"])
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_num_chips(mesh)
    ctx = ShardingCtx(mesh=mesh)
    seq_rule = knobs.get("seq_rule", ())
    ctx = ctx.with_rules(seq=tuple(seq_rule))
    model = Model(arch, ctx=ctx, options=ModelOptions(
        use_pallas=False,
        remat_policy=knobs["remat_policy"],
        attn_chunk=knobs["attn_chunk"]))
    in_specs = specs_mod.input_specs(arch, shape)
    in_sh = specs_mod.input_shardings(ctx, in_specs)
    params_shape, _ = specs_mod.abstract_state(model)
    params_sh = param_shardings(params_shape, ctx)

    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            optimizer = AdamW()
            opt_shape = jax.eval_shape(optimizer.init, params_shape)
            opt_sh = specs_mod.opt_state_shardings(ctx, params_sh,
                                                   opt_shape)
            step = make_train_step(
                model, optimizer,
                StepConfig(num_microbatches=knobs["num_microbatches"]),
                grad_shardings=params_sh)
            jitted = jax.jit(
                step,
                in_shardings=(params_sh, opt_sh, None, in_sh),
                donate_argnums=(0, 1))
            lowered = jitted.lower(params_shape, opt_shape, None, in_specs)
            tokens_per_step = shape.global_batch * shape.seq_len
            model_flops = 6.0 * arch.active_param_count() * tokens_per_step
        elif shape.kind == "prefill":
            def prefill(params, batch):
                return model.prefill(params, batch)
            jitted = jax.jit(prefill, in_shardings=(params_sh, in_sh))
            lowered = jitted.lower(params_shape, in_specs)
            tokens_per_step = shape.global_batch * shape.seq_len
            model_flops = 2.0 * arch.active_param_count() * tokens_per_step
        else:  # decode
            serve = make_serve_step(model)
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch,
                                         shape.seq_len))
            cache_sh = specs_mod.cache_shardings(ctx, model, cache_shape)
            jitted = jax.jit(serve,
                             in_shardings=(params_sh, in_sh, cache_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_shape, in_specs, cache_shape)
            tokens_per_step = shape.global_batch
            model_flops = 2.0 * arch.active_param_count() * tokens_per_step
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    xla_cost = hlo_mod.cost_figures(compiled)      # per-device, loop-naive
    mem = hlo_mod.memory_figures(compiled)         # per-device
    try:
        text = compiled.as_text()
    except Exception:  # noqa: BLE001
        text = ""
    # loop-aware static analysis (scan bodies x trip counts) — see
    # core/hlo_cost.py; xla_cost counts while bodies once and is kept
    # for reference only.  Traffic tags attribute HBM bytes to the
    # attention-score / SSD-decay tensors that the Pallas kernels keep in
    # VMEM on real TPUs (XLA fallback materializes them).
    attn_chunk = knobs["attn_chunk"]
    ssm_q = arch.ssm_chunk
    seq_like = {shape.seq_len, shape.seq_len + arch.num_meta_tokens,
                attn_chunk}

    def tag(result_type: str) -> str:
        shapes = hlo_cost_mod._shape_dims(result_type)
        for _, dims in shapes:
            if len(dims) >= 2:
                a, b = dims[-2], dims[-1]
                if (arch.has_attention and a in seq_like and b in seq_like
                        and a * b >= 1 << 20):
                    return "attn_scores"
                if (arch.ssm_state and a == ssm_q and b == ssm_q):
                    return "ssd_decay"
        return ""

    cost = hlo_cost_mod.analyze_hlo(text, tag_fn=tag)  # per-device program
    terms = roofline_terms(cost.flops * chips, cost.traffic_bytes * chips,
                           cost.collective_bytes * chips, chips,
                           TPU_V5E)
    # Pallas-kernel-adjusted memory term: score/decay tensors stay in VMEM
    kernel_saved = sum(cost.traffic_by_tag.values())
    memory_s_flash = max(cost.traffic_bytes - kernel_saved, 0.0) \
        / TPU_V5E.hbm_bw
    hbm_frac = mem["total_bytes_per_device"] / TPU_V5E.hbm_bytes
    rec = {
        "arch": arch_id,
        "shape": shape_id,
        "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "variant": variant,
        "knobs": knobs,
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        # per-device figures from the loop-aware HLO analysis
        "flops_per_device": cost.flops,
        "bytes_per_device": cost.traffic_bytes,
        "collective_bytes_per_device": cost.collective_bytes,
        "collective_counts": dict(cost.collective_counts),
        "collective_bytes_by_kind": dict(cost.collective_bytes_by_kind),
        "loop_trips": dict(cost.loop_trips),
        "xla_cost_analysis_raw": xla_cost,  # loop-naive, reference only
        "memory": mem,
        "hbm_frac_used": hbm_frac,
        "fits_hbm": hbm_frac <= 1.0,
        # roofline (§Roofline)
        "compute_s": terms.compute_s,
        "memory_s": terms.memory_s,
        "memory_s_flash": memory_s_flash,
        "traffic_by_tag": dict(cost.traffic_by_tag),
        "collective_s": terms.collective_s,
        "dominant": terms.dominant,
        "bound_step_s": terms.bound_s,
        "model_flops": model_flops,
        "useful_flops_ratio": (model_flops
                               / max(cost.flops * chips, 1.0)),
        "tokens_per_step": tokens_per_step,
        "params_total": arch.param_count(),
        "params_active": arch.active_param_count(),
    }
    return rec


def out_path(arch_id, shape_id, multi_pod, variant) -> Path:
    mesh = "2x16x16" if multi_pod else "16x16"
    d = OUT_ROOT / mesh
    d.mkdir(parents=True, exist_ok=True)
    suffix = "" if variant == "baseline" else f"__{variant}"
    return d / f"{arch_id}__{shape_id}{suffix}.json"


def run_cell(arch_id, shape_id, multi_pod, variant="baseline",
             force=False) -> dict:
    path = out_path(arch_id, shape_id, multi_pod, variant)
    if path.exists() and not force:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    arch = get_arch(arch_id)
    shape = get_shape(shape_id)
    reason = skip_reason(arch, shape)
    if reason:
        rec = {"arch": arch_id, "shape": shape_id, "ok": False,
               "skipped": True, "reason": reason,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "variant": variant}
    else:
        try:
            rec = build_cell(arch_id, shape_id, multi_pod, variant)
        except Exception as exc:  # noqa: BLE001
            rec = {"arch": arch_id, "shape": shape_id, "ok": False,
                   "skipped": False,
                   "mesh": "2x16x16" if multi_pod else "16x16",
                   "variant": variant,
                   "error": f"{type(exc).__name__}: {exc}",
                   "traceback": traceback.format_exc()[-2000:]}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) cell on both meshes")
    ap.add_argument("--variant", default="baseline",
                    choices=sorted(VARIANTS))
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in sorted(SHAPES):
                for mp in ((False, True) if not args.multi_pod
                           else (True,)):
                    cells.append((a, s, mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        meshes = ((False, True) if args.both_meshes
                  else ((args.multi_pod),))
        cells = [(args.arch, args.shape, mp) for mp in meshes]

    failures = 0
    for arch_id, shape_id, mp in cells:
        t0 = time.time()
        rec = run_cell(arch_id, shape_id, mp, args.variant, args.force)
        mesh = rec.get("mesh")
        if rec.get("skipped"):
            status = "SKIP (" + rec["reason"][:50] + "...)"
        elif rec.get("ok"):
            status = (f"ok  dom={rec['dominant']:<10} "
                      f"bound={rec['bound_step_s'] * 1e3:8.2f}ms "
                      f"hbm={rec['hbm_frac_used'] * 100:5.1f}% "
                      f"compile={rec.get('compile_s', 0):6.1f}s")
        else:
            status = "FAIL " + rec.get("error", "?")[:80]
            failures += 1
        print(f"[dryrun] {arch_id:26s} {shape_id:12s} {mesh:8s} "
              f"{rec.get('variant', ''):12s} {status} "
              f"({time.time() - t0:.1f}s)", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
