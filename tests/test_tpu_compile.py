"""Compile-only checks against a described TPU v5e (no chip attached).

The TPU compiler is installed even where no chip is, and it refuses what
the Pallas interpreter accepts: blocks not aligned to the tiling, kernels
that need too much fast memory, programs that do not fit the chip.  These
tests compile the Pallas kernels at the real head layouts and the full-
width mamba2-780m training step for one described v5e chip, and check
that the SSD's cumulative sums compile to no windowed reduction.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core import hlo_cost
from repro.data import SyntheticSource
from repro.kernels.ops import flash_attention_op, ssd_op
from repro.launch import train
from repro.models.ssm import ssd_chunked
from repro.train import StepConfig, make_train_step
from repro.train.step import make_loss_fn

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, sharding), tree)


@pytest.mark.parametrize("arch,window", [("qwen3-8b", 0),
                                         ("gemma3-4b", 1024),
                                         ("gemma3-4b", 0)])
def test_flash_attention_compiles_at_real_layout(one_chip, arch, window):
    cfg = get_arch(arch)
    d, s = cfg.resolved_head_dim, 2048
    q = _sds((1, s, cfg.num_heads, d), jnp.bfloat16, one_chip)
    kv = _sds((1, s, cfg.num_kv_heads, d), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention_op(
        q, k, v, causal=True, window=window, interpret=False)
    ).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_compiles_at_mamba2_layout(one_chip):
    cfg = get_arch("mamba2-780m")
    b, s, h, p, n = 2, 2048, cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    compiled = jax.jit(lambda x, dt, a, bm, cm: ssd_op(
        x, dt, a, bm, cm, chunk=cfg.ssm_chunk, interpret=False)).lower(
        _sds((b, s, h, p), jnp.bfloat16, one_chip),
        _sds((b, s, h), jnp.float32, one_chip),
        _sds((h,), jnp.float32, one_chip),
        _sds((b, s, n), jnp.bfloat16, one_chip),
        _sds((b, s, n), jnp.bfloat16, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def mamba2_step(one_chip):
    """The launcher's step at published widths, seq 2048, batch 4:
    (args, params' shapes, compiled step)."""
    args = train.parse_args(["--arch", "mamba2-780m", "--seq-len", "2048",
                             "--batch", "4", "--steps", "10"])
    cfg = train.build_config(args)
    model = train.build_model(cfg, args)
    optimizer = train.build_optimizer(args)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    batch = SyntheticSource(cfg, args.seq_len, args.batch).get(0)
    step = make_train_step(model, optimizer, StepConfig())
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        _on(params, one_chip), _on(opt_state, one_chip), None,
        _on(batch, one_chip)).compile()
    return args, params, compiled


def test_mamba2_780m_train_step_fits_one_chip(mamba2_step):
    args, params, compiled = mamba2_step
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total
    cost = hlo_cost.analyze_hlo(compiled.as_text())
    # 6·N·tokens is the floor; full remat recomputes the forward
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert cost.flops > 6 * n_params * args.batch * args.seq_len


def test_mamba2_780m_train_step_has_no_windowed_reduction(mamba2_step):
    """The SSD's within-chunk cumulative sums run as matmuls: on TPU a
    ``cumsum`` would lower to a ``reduce-window`` of about Q adds an
    output, in the forward and again in the backward."""
    assert "reduce-window" not in mamba2_step[2].as_text()


def test_grouped_ssd_grad_has_no_windowed_reduction(one_chip):
    """The grouped SSD's gradient at nemotron-3-nano-30b-a3b's layout
    (64 heads in 8 B/C groups, chunk 128, seq 8192, batch 4)."""
    cfg = get_arch("nemotron-3-nano-30b-a3b")
    b, s, h, p = 4, 8192, cfg.ssm_heads, cfg.ssm_headdim
    g, n = cfg.ssm_groups, cfg.ssm_state
    assert (h, g, cfg.ssm_chunk) == (64, 8, 128)

    def loss(x, dt, a_log, bm, cm):
        return jnp.sum(ssd_chunked(x, dt, a_log, bm, cm,
                                   cfg.ssm_chunk)[0].astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        _sds((b, s, h, p), jnp.bfloat16, one_chip),
        _sds((b, s, h), jnp.float32, one_chip),
        _sds((h,), jnp.float32, one_chip),
        _sds((b, s, g, n), jnp.bfloat16, one_chip),
        _sds((b, s, g, n), jnp.bfloat16, one_chip)).compile()
    text = compiled.as_text()
    assert "dot(" in text or "convolution(" in text
    assert "reduce-window" not in text


def test_attention_grad_compiles_without_pallas(one_chip):
    """The gradient goes through the XLA attention path (the kernels have
    no VJP): qwen3-8b at published widths, cut to two layers."""
    import dataclasses
    cfg = dataclasses.replace(get_arch("qwen3-8b"), num_layers=2)
    args = train.parse_args(["--arch", "qwen3-8b", "--seq-len", "2048",
                             "--batch", "1"])
    model = train.build_model(cfg, args)
    assert not model.opt.use_pallas
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = SyntheticSource(cfg, args.seq_len, args.batch).get(0)
    grad = jax.grad(lambda p, b: make_loss_fn(model, StepConfig())(p, b)[0])
    compiled = jax.jit(grad).lower(_on(params, one_chip),
                                   _on(batch, one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_train_refuses_pallas():
    with pytest.raises(SystemExit):
        train.parse_args(["--arch", "mamba2-780m", "--use-pallas"])
