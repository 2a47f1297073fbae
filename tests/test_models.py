"""Per-architecture smoke tests (reduced configs): one forward/train step
on CPU asserting output shapes + no NaNs, plus prefill/decode consistency
and Pallas-path parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch, reduced
from repro.models import Model, ModelOptions, make_batch

OPTS = ModelOptions(remat_policy="none", attn_chunk=16)


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(aid):
        if aid not in cache:
            cfg = reduced(get_arch(aid))
            model = Model(cfg, options=OPTS)
            params = model.init(jax.random.PRNGKey(0))
            batch = make_batch(cfg, seq_len=32, batch=2, kind="train")
            cache[aid] = (cfg, model, params, batch)
        return cache[aid]
    return get


@pytest.mark.parametrize("aid", ARCH_IDS)
def test_arch_forward_shapes_and_finite(built, aid):
    cfg, model, params, batch = built(aid)
    logits, aux = jax.jit(model.forward)(params, batch)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    if cfg.is_moe:
        assert float(aux["moe_dropped_slots"]) == 0.0
        assert ("moe_lb_loss" in aux) == (cfg.moe_router == "softmax")


@pytest.mark.parametrize("aid", ARCH_IDS)
def test_arch_train_step_no_nans(built, aid):
    from repro.optim import AdamW, OptimizerConfig
    from repro.train import StepConfig, make_train_step
    cfg, model, params, batch = built(aid)
    opt = AdamW(OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=10))
    state = opt.init(params)
    step = jax.jit(make_train_step(model, opt, StepConfig()))
    params2, state2, _, metrics = step(params, state, None, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2.count) == 1
    # params actually changed
    a = jax.tree_util.tree_leaves(params)[3]
    b = jax.tree_util.tree_leaves(params2)[3]
    assert not np.allclose(np.asarray(a, np.float32),
                           np.asarray(b, np.float32))


@pytest.mark.parametrize("aid", ARCH_IDS)
def test_arch_prefill_matches_forward(built, aid):
    cfg, model, params, batch = built(aid)
    logits, _ = jax.jit(model.forward)(params, batch)
    plogits, cache = jax.jit(
        lambda p, b: model.prefill(p, b, extra_slots=4))(params, batch)
    err = float(jnp.max(jnp.abs(plogits[:, 0] - logits[:, -1])))
    assert err < 2e-2, err
    assert int(cache["pos"]) == 32 + cfg.num_meta_tokens


@pytest.mark.parametrize("aid", ARCH_IDS)
def test_arch_decode_step(built, aid):
    cfg, model, params, batch = built(aid)
    _, cache = jax.jit(
        lambda p, b: model.prefill(p, b, extra_slots=4))(params, batch)
    if "tokens" in batch:
        db = {"tokens": batch["tokens"][:, -1:]}
    else:
        db = {"embeds": batch["embeds"][:, -1:]}
    dlogits, cache2 = jax.jit(model.decode_step)(params, db, cache)
    assert dlogits.shape == (2, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(dlogits)))
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


@pytest.mark.parametrize("aid", ["gemma2-9b", "mamba2-780m", "hymba-1.5b",
                                 "qwen3-8b", "musicgen-medium"])
def test_pallas_path_parity(built, aid):
    cfg, model, params, batch = built(aid)
    m_p = Model(cfg, options=ModelOptions(remat_policy="none",
                                          attn_chunk=16,
                                          use_pallas=True))
    lx, _ = jax.jit(model.forward)(params, batch)
    lp, _ = jax.jit(m_p.forward)(params, batch)
    assert float(jnp.max(jnp.abs(lx - lp))) < 5e-2


@pytest.mark.parametrize("aid", ARCH_IDS)
def test_param_count_is_the_built_tree(aid):
    """The published size's count is the tree ``init`` builds, leaf for
    leaf: norms, gates and cross blocks included."""
    cfg = get_arch(aid)
    tree = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    assert cfg.param_count() == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def test_decode_sequence_matches_forward():
    """Greedy decode token-by-token must agree with teacher-forced
    forward logits (qwen3 reduced)."""
    cfg = reduced(get_arch("qwen3-8b"))
    model = Model(cfg, options=OPTS)
    params = model.init(jax.random.PRNGKey(1))
    batch = make_batch(cfg, seq_len=16, batch=1, kind="train")
    logits, _ = jax.jit(model.forward)(params, batch)
    _, cache = jax.jit(
        lambda p, b: model.prefill(p, b, extra_slots=8))(
            params, {"tokens": batch["tokens"][:, :8]})
    decode = jax.jit(model.decode_step)
    for t in range(8, 12):
        dl, cache = decode(params, {"tokens": batch["tokens"][:, t:t + 1]},
                           cache)
        err = float(jnp.max(jnp.abs(dl[:, 0] - logits[:, t])))
        assert err < 2e-2, (t, err)


def test_mixed_window_layers_differ_from_global():
    """gemma's local layers must actually mask: compare against a config
    with all-global attention."""
    import dataclasses
    cfg = reduced(get_arch("gemma2-9b"))
    cfg_local = dataclasses.replace(cfg, window_size=4)
    cfg_global = dataclasses.replace(cfg, attn_pattern="global")
    batch = make_batch(cfg, seq_len=32, batch=1, kind="train")
    params = Model(cfg_local, options=OPTS).init(jax.random.PRNGKey(0))
    l1, _ = jax.jit(Model(cfg_local, options=OPTS).forward)(params, batch)
    l2, _ = jax.jit(Model(cfg_global, options=OPTS).forward)(params, batch)
    assert float(jnp.max(jnp.abs(l1 - l2))) > 1e-3
