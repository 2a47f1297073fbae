"""Nemotron-H (``nemotron-3-nano-30b-a3b``): the program against the plain
float32 reference the benchmark keeps (``bench/configs/nemotron_h_ref.py``),
at a small size on seeded random weights.

Layers of all three kinds (Mamba-2 with two B/C groups, sigmoid-routed
relu^2 experts with a shared expert, attention without positions), 4 of 8
experts held. The program computes in float32 here, as the reference does,
so the tolerances only have to cover the order of summation; the program's
chunked cross-entropy takes its backward in bf16 by design
(``train/step.py``), so these tests use the plain one.
"""

import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import cells, layout  # noqa: E402

from repro.configs import get_arch, reduced  # noqa: E402
from repro.models import Model, ModelOptions, moe as moe_mod  # noqa: E402
from repro.models import ssm as ssm_mod  # noqa: E402
from repro.optim import AdamW, OptimizerConfig  # noqa: E402
from repro.train import StepConfig, make_train_step  # noqa: E402
from repro.train.step import make_loss_fn  # noqa: E402

ref_mod = cells._load_module(BENCH / "configs" / "nemotron_h_ref.py",
                             "nemotron_h_ref_tests")

TINY = dict(num_layers=5, layer_pattern="MEM*E", d_model=64, vocab_size=97,
            num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32,
            num_experts=8, moe_experts_held=4, experts_per_token=2,
            shared_expert_ff=48, ssm_state=8, ssm_num_heads=4,
            ssm_headdim=16, ssm_groups=2, ssm_chunk=16, dtype="float32")


def tiny_config(**over):
    c = json.loads((BENCH / "configs" /
                    "nemotron-3-nano-30b-a3b-7l.json").read_text())
    c.update(TINY, **over)
    return c


def program(c):
    base = get_arch(c["program_arch"])
    fields = {f.name for f in dataclasses.fields(base)} - {"name"}
    cfg = dataclasses.replace(base, **{k: v for k, v in c.items()
                                       if k in fields})
    return cfg, Model(cfg, options=ModelOptions(remat_policy="full",
                                                attn_chunk=16))


def batches(n, seed=3, b=2, s=32, vocab=97):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        t = rng.integers(0, vocab, (b, s + 1))
        out.append({"tokens": jnp.asarray(t[:, :-1], jnp.int32),
                    "labels": jnp.asarray(t[:, 1:], jnp.int32),
                    "loss_mask": jnp.ones((b, s), jnp.float32)})
    return out


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-30))


def test_published_parameter_count():
    cfg = get_arch("nemotron-3-nano-30b-a3b")
    assert cfg.param_count() == 31_577_940_288
    assert cfg.layer_kinds().count("moe") == 23
    assert cfg.layer_kinds().count("ssm") == 23
    assert cfg.layer_kinds().count("global") == 6
    cut = dataclasses.replace(cfg, num_layers=7, layer_pattern="MEMEM*E",
                              vocab_size=16384, moe_experts_held=8)
    assert cut.param_count() == 528_093_120


def test_program_matches_reference_loss_gradients_and_steps():
    c = tiny_config()
    cfg, model = program(c)
    params = layout.make_params(c, 7)
    assert layout.leaf_names(params) == layout.leaf_names(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    bs = batches(3)
    # loss and every gradient leaf; float32 both sides, so the gaps are
    # rounding in a different order of summation (read: 2e-5 at most)
    loss_fn = make_loss_fn(model, StepConfig(ce_seq_chunk=0))
    (loss, metrics), g_prog = jax.value_and_grad(loss_fn, has_aux=True)(
        params, bs[0])
    ref = ref_mod.Reference(c)
    r_loss, g_ref = jax.value_and_grad(ref.loss)(
        params, bs[0]["tokens"], bs[0]["labels"], bs[0]["loss_mask"])
    assert abs(float(loss) - float(r_loss)) <= 1e-5 * abs(float(r_loss))
    for name, a, b in zip(layout.leaf_names(g_prog),
                          jax.tree_util.tree_leaves(g_prog),
                          jax.tree_util.tree_leaves(g_ref)):
        if name.endswith("router_bias"):   # picks experts, never weighs
            assert float(jnp.max(jnp.abs(a))) == 0.0
            continue
        assert rel(a, b) < 1e-4, name
    assert float(metrics["moe_dropped_slots"]) == 0.0
    assert "moe_lb_loss" not in metrics      # no auxiliary loss: CE alone
    # three AdamW steps: each loss, and each leaf's change from the seed
    t = c["train"]
    opt = AdamW(OptimizerConfig(**{k: t[k] for k in (
        "lr", "warmup_steps", "total_steps", "min_lr_frac", "b1", "b2",
        "eps", "weight_decay", "clip_norm")}))
    step = jax.jit(make_train_step(model, opt, StepConfig(ce_seq_chunk=0)))
    p, state, losses = params, opt.init(params), []
    for b in bs:
        p, state, _, m = step(p, state, None, b)
        losses.append(float(m["loss"]))
    r = ref_mod.run_steps(c, t, 7, bs)
    np.testing.assert_allclose(losses, r["losses"], rtol=1e-5)
    change = {n: float(jnp.linalg.norm(a - b)) for n, a, b in zip(
        layout.leaf_names(p), jax.tree_util.tree_leaves(p),
        jax.tree_util.tree_leaves(params))}
    # Adam divides each element's step by its own gradient's size, so an
    # element whose gradient is near 0 moves on rounding alone: a few per
    # mille of a leaf's change (read: 3e-4 at most)
    for name, v in r["change"].items():
        assert abs(change[name] - v) <= 3e-3 * max(v, 1e-12), name


def _moe_params(c, seed=5):
    cfg, model = program(c)
    p = layout.make_params(c, seed)
    moe = jax.tree_util.tree_map(lambda a: a[0], p["blocks"]["moe"])
    return cfg, moe


def _unwritten_rows_nan(real, seen):
    """``ragged_dot`` as the TPU's kernel behaves past the last group: the
    rows there hold whatever was in memory (NaN here), in the result and
    in the input gradient alike. Each call's rows in groups go to
    ``seen``."""
    def past(out, sizes):
        return jnp.where((jnp.arange(out.shape[0]) < jnp.sum(sizes))[:, None],
                         out, jnp.nan)

    @jax.custom_vjp
    def dot(a, b, sizes):
        return past(real(a, b, sizes), sizes)

    def fwd(a, b, sizes):
        return dot(a, b, sizes), (a, b, sizes)

    def bwd(res, ct):
        a, b, sizes = res
        da, db = jax.vjp(lambda a, b: real(a, b, sizes), a, b)[1](ct)
        return past(da, sizes), db, None

    dot.defvjp(fwd, bwd)

    def call(a, b, group_sizes):
        seen.append((int(jnp.sum(group_sizes)), a.shape[0]))
        return dot(a, b, group_sizes)
    return call


def test_held_shares_add_up_to_the_uncut_layer(monkeypatch):
    """Eight experts in four shares of two: each share's part of the
    result, with the shared expert (which every chip computes alike)
    counted once, adds up to the reference's whole layer, and so do the
    gradients. A share's rows past its routed slots lie outside every
    group, where the TPU's grouped matmul writes nothing (NaN here): they
    reach neither."""
    c = tiny_config(moe_experts_held=8)
    cfg, moe = _moe_params(c)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, c["d_model"]))
    whole = ref_mod.Reference(c).experts(moe, x)
    seen = []
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _unwritten_rows_nan(jax.lax.ragged_dot, seen))

    def shares(moe, x):
        h = ref_mod.rms(x, moe["norm_scale"], c["norm_eps"])
        shared = moe_mod._ffn(cfg, h, moe["shared_up"], moe["shared_down"],
                              matmul=lambda a, b: a @ b)
        total, held = shared, 0.0
        for first in range(0, 8, 2):
            share = dict(moe, w_up=moe["w_up"][first:first + 2],
                         w_down=moe["w_down"][first:first + 2])
            y, aux = moe_mod.apply_moe(share, cfg, h, first=first)
            assert float(aux["moe_dropped_slots"]) == 0.0
            total, held = total + (y - shared), held + aux["moe_held_slots"]
        return total, held

    total, held = shares(moe, x)
    assert rel(total, whole) < 1e-5
    assert float(held) == 2 * 32 * c["experts_per_token"]
    assert any(rows > live for live, rows in seen)   # rows outside groups
    g = jax.grad(lambda m, x: jnp.sum(jnp.sin(shares(m, x)[0])),
                 argnums=(0, 1))(moe, x)
    gr = jax.grad(lambda m, x: jnp.sum(jnp.sin(
        ref_mod.Reference(c).experts(m, x))), argnums=(0, 1))(moe, x)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(gr)):
        assert bool(jnp.all(jnp.isfinite(a)))
        if float(jnp.linalg.norm(b)):
            assert rel(a, b) < 1e-4
    y_all, aux = moe_mod.apply_moe(moe, cfg, ref_mod.rms(
        x, moe["norm_scale"], c["norm_eps"]))
    assert rel(y_all, whole) < 1e-5


def test_a_skewed_router_drops_no_slot():
    """Every expert held, and the selection bias sends every token to
    experts 0 and 1: the two take every slot, and none is dropped."""
    c = tiny_config(moe_experts_held=8)
    cfg, moe = _moe_params(c)
    moe = dict(moe, router_bias=moe["router_bias"].at[0].set(10.0)
               .at[1].set(9.0))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, c["d_model"]))
    h = ref_mod.rms(x, moe["norm_scale"], c["norm_eps"])
    y, aux = moe_mod.apply_moe(moe, cfg, h)
    slots = 2 * 32 * c["experts_per_token"]
    assert float(aux["moe_held_slots"]) == slots
    assert float(aux["moe_dropped_slots"]) == 0.0
    # two experts of eight hold every slot: max over mean is 8 / 2
    assert float(aux["moe_load_max_over_mean"]) == pytest.approx(4.0)
    assert rel(x + y, x + ref_mod.Reference(c).experts(moe, x)) < 1e-5


def test_a_cut_holds_its_balanced_share_under_a_skewed_router():
    """Four of eight experts held, and the selection bias favours two of
    them: the held experts still take exactly their balanced share of each
    sequence's slots, and the output is the reference's, which pins the
    share by an offset on the held experts' scores."""
    c = tiny_config(moe_experts_held=4)
    cfg, moe = _moe_params(c)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, c["d_model"]))
    h = ref_mod.rms(x, moe["norm_scale"], c["norm_eps"])
    share = c["experts_per_token"] * 32 * 4 // 8
    for bias in (moe["router_bias"], moe["router_bias"].at[0].set(0.3)
                 .at[1].set(0.2), moe["router_bias"].at[5].set(0.4)):
        m = dict(moe, router_bias=bias)
        y, aux = moe_mod.apply_moe(m, cfg, h)
        assert float(aux["moe_held_slots"]) == 2 * share
        assert float(aux["moe_dropped_slots"]) == 0.0
        assert rel(x + y, x + ref_mod.Reference(c).experts(m, x)) < 1e-5


def test_dropped_slots_counts_held_slots_past_the_bound(monkeypatch):
    """The counter reads the held slots that no row computes: with the
    row bound cut five short of the held share, five."""
    c = tiny_config(moe_experts_held=4)
    cfg, moe = _moe_params(c)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 32, c["d_model"]))
    bound = moe_mod.row_bound
    monkeypatch.setattr(moe_mod, "row_bound",
                        lambda *a: bound(*a) - 5)
    _, aux = moe_mod.apply_moe(moe, cfg, h)
    assert float(aux["moe_dropped_slots"]) == 5.0


def test_every_slot_row_is_in_a_group(monkeypatch):
    """A cut's row bound is its held share, so every row of the grouped
    matmul lies in a group, and a grouped matmul that writes nothing
    outside the groups (NaN here, as on the TPU) leaves the layer's output
    and gradients the reference's."""
    c = tiny_config(moe_experts_held=4)
    cfg, moe = _moe_params(c)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, c["d_model"]))
    seen = []
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _unwritten_rows_nan(jax.lax.ragged_dot, seen))

    def prog(moe, x):
        h = ref_mod.rms(x, moe["norm_scale"], c["norm_eps"])
        return jnp.sum(jnp.sin(moe_mod.apply_moe(moe, cfg, h)[0]))

    def ref(moe, x):
        return jnp.sum(jnp.sin(ref_mod.Reference(c).experts(moe, x)))

    (y, g), (r, gr) = (jax.value_and_grad(f, argnums=(0, 1))(moe, x)
                       for f in (prog, ref))
    assert abs(float(y) - float(r)) <= 1e-4 * abs(float(r))
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(gr)):
        assert bool(jnp.all(jnp.isfinite(a)))
        if float(jnp.linalg.norm(b)):
            assert rel(a, b) < 1e-4
    share = 2 * c["experts_per_token"] * 32 * 4 // 8
    assert seen and set(seen) == {(share, share)}


def test_one_group_ssd_is_the_old_ssd_bit_for_bit():
    """One group, with d_inner = expand x d_model: the grouped path gives
    the single-group SSD's output and state bit for bit, and the mixer of
    mamba2 stated with the new keys lowers to the same program."""
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    b, s, h, p, n = 2, 64, 4, 16, 8
    x = jax.random.normal(k[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)))
    a_log = jnp.log(jnp.linspace(1.0, 16.0, h))
    bm, cm = (jax.random.normal(kk, (b, s, n)) for kk in k[2:4])
    old = ssm_mod.ssd_chunked(x, dt, a_log, bm, cm, 16)
    grouped = ssm_mod.ssd_chunked(x, dt, a_log, bm[:, :, None],
                                  cm[:, :, None], 16)
    for a, g in zip(old, grouped):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(g))

    cfg = reduced(get_arch("mamba2-780m"))
    stated = dataclasses.replace(cfg, ssm_num_heads=cfg.ssm_heads,
                                 ssm_groups=1)
    assert stated.d_inner == cfg.ssm_expand * cfg.d_model
    params = ssm_mod.init_ssm_params(k[4], cfg, jnp.float32)
    u = jax.random.normal(k[0], (2, 64, cfg.d_model))
    run = lambda c: jax.jit(lambda p, u: ssm_mod.apply_ssm_mixer(p, c, u))
    np.testing.assert_array_equal(np.asarray(run(cfg)(params, u)),
                                  np.asarray(run(stated)(params, u)))
    assert (run(cfg).lower(params, u).as_text()
            == run(stated).lower(params, u).as_text())


def test_grouped_decode_matches_the_grouped_scan():
    """Decoding token by token through grouped B/C reads the state the
    chunked scan leaves."""
    k = jax.random.split(jax.random.PRNGKey(4), 5)
    b, s, h, p, n, g = 1, 8, 4, 8, 4, 2
    x = jax.random.normal(k[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)))
    a_log = jnp.log(jnp.linspace(1.0, 4.0, h))
    bm, cm = (jax.random.normal(kk, (b, s, g, n)) for kk in k[2:4])
    y, h_final = ssm_mod.ssd_chunked(x, dt, a_log, bm, cm, 4)
    state = jnp.zeros((b, h, p, n))
    for i in range(s):
        yi, state = ssm_mod.ssd_decode_step(state, x[:, i], dt[:, i], a_log,
                                            bm[:, i], cm[:, i])
        np.testing.assert_allclose(yi, y[:, i], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(state, h_final, rtol=1e-4, atol=1e-5)
