"""Training step: loss, gradient accumulation, optimizer apply — the
function the dry-run lowers and the launcher runs.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.transformer import Model
from repro.optim import compression
from repro.optim.optimizer import AdamW, OptState


@dataclass(frozen=True)
class StepConfig:
    num_microbatches: int = 1
    moe_lb_weight: float = 0.01
    moe_z_weight: float = 1e-3
    compress_grads: bool = False   # int8 EF quantization (cross-pod sim)
    ce_seq_chunk: int = 512        # chunked CE: logits never materialize
                                   # beyond [B, chunk, V]; 0 disables


@jax.named_scope("model.loss")
def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: jnp.ndarray, ctx=None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mean masked token CE, vocab-shard-friendly.

    The gold logit is extracted with a one-hot contraction (sharded like
    the logits) instead of ``take_along_axis``/``argmax`` — the latter
    lower to gathers over the *unsharded* vocab axis and materialize a
    [B, S, V] iota (16+ GB for 256k vocabs).  logsumexp/max reduce over
    the sharded axis via cheap all-reduces."""
    logits = logits.astype(jnp.float32)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.bfloat16)
    if ctx is not None:
        onehot = ctx.act(onehot, "batch", "seq", "vocab")
    gold = jnp.sum(logits * onehot.astype(jnp.float32), axis=-1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    nll = lse - gold
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    max_logit = jnp.max(logits, axis=-1)
    acc = ((gold >= max_logit) * mask).sum() / denom
    return loss, acc


@jax.named_scope("model.loss")
def chunked_cross_entropy(model: Model, params, hidden, labels, mask,
                          seq_chunk: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """CE over sequence chunks with a hand-written VJP.

    Forward never materializes more than one [B, chunk, V] logits tile;
    backward recomputes each tile and accumulates the unembedding-table
    gradient in a carry that is explicitly *vocab-sharded* each iteration.
    (Plain autodiff through either a scan or an unrolled loop leaves that
    accumulator — V x d in f32, 4-5 GB for 200k+ vocabs — unsharded or
    alive once per chunk.)  This is what makes huge-vocab training fit;
    see EXPERIMENTS.md §Perf.
    """
    cfg = model.cfg
    from repro.models.layers import rms_norm, softcap as softcap_fn
    y = rms_norm(hidden, params["final_norm_scale"], cfg.norm_eps)
    tied = cfg.tie_embeddings
    table = (params["embed"]["table"] if tied else params["lm_head"])
    cap = cfg.final_logit_softcap

    b, s, d = y.shape
    n = max(s // seq_chunk, 1)
    chunk = s // n
    assert chunk * n == s, (s, seq_chunk)

    def chunked(t, trail):
        return t.reshape((b, n, chunk) + trail).transpose(
            (1, 0, 2) + tuple(range(3, 3 + len(trail))))

    def constrain_dtable(dt):
        if model.ctx is None:
            return dt
        logical = ("vocab", "fsdp") if tied else ("fsdp", "vocab")
        return model.ctx.act(dt, *logical)

    def logits_of(y_c, w):
        if tied:
            pre = jnp.einsum("bcd,vd->bcv", y_c.astype(jnp.float32),
                             w.astype(jnp.float32))
        else:
            pre = jnp.einsum("bcd,dv->bcv", y_c.astype(jnp.float32),
                             w.astype(jnp.float32))
        return softcap_fn(pre, cap), pre

    def chunk_sums(y_c, w, l_c, m_c):
        logits, _ = logits_of(y_c, w)
        onehot = jax.nn.one_hot(l_c, logits.shape[-1], dtype=jnp.bfloat16)
        if model.ctx is not None:
            onehot = model.ctx.act(onehot, "batch", "seq", "vocab")
        gold = jnp.sum(logits * onehot.astype(jnp.float32), axis=-1)
        lse = jax.nn.logsumexp(logits, axis=-1)
        nll = ((lse - gold) * m_c).sum()
        correct = ((gold >= jnp.max(logits, axis=-1)) * m_c).sum()
        return nll, correct, onehot, lse

    @jax.custom_vjp
    def ce_sums(y, w, labels, mask):
        def body(carry, xs):
            nll, cor = carry
            y_c, l_c, m_c = xs
            pn, pc, _, _ = chunk_sums(y_c, w, l_c, m_c)
            return (nll + pn, cor + pc), None
        (nll, cor), _ = jax.lax.scan(
            body, (jnp.float32(0.0), jnp.float32(0.0)),
            (chunked(y, (d,)), chunked(labels, ()), chunked(mask, ())))
        return nll, cor

    def ce_sums_fwd(y, w, labels, mask):
        out = ce_sums(y, w, labels, mask)
        return out, (y, w, labels, mask)

    def ce_sums_bwd(res, g):
        y, w, labels, mask = res
        dnll = g[0].astype(jnp.float32)

        def body(dtable, xs):
            y_c, l_c, m_c = xs
            logits, pre = logits_of(y_c, w)
            onehot = jax.nn.one_hot(l_c, logits.shape[-1],
                                    dtype=jnp.bfloat16)
            if model.ctx is not None:
                onehot = model.ctx.act(onehot, "batch", "seq", "vocab")
            lse = jax.nn.logsumexp(logits, axis=-1)
            p = jnp.exp(logits - lse[..., None])
            dlogits = (p - onehot.astype(jnp.float32)) \
                * (m_c[..., None] * dnll)
            if cap:
                dlogits = dlogits * (1.0 - jnp.square(logits / cap))
            dl16 = dlogits.astype(jnp.bfloat16)
            if tied:
                dy_c = jnp.einsum("bcv,vd->bcd", dl16,
                                  w.astype(jnp.bfloat16))
                dw_c = jnp.einsum("bcv,bcd->vd", dl16,
                                  y_c.astype(jnp.bfloat16))
            else:
                dy_c = jnp.einsum("bcv,dv->bcd", dl16,
                                  w.astype(jnp.bfloat16))
                dw_c = jnp.einsum("bcd,bcv->dv", y_c.astype(jnp.bfloat16),
                                  dl16)
            dtable = constrain_dtable(dtable + dw_c.astype(jnp.float32))
            return dtable, dy_c

        dt0 = constrain_dtable(jnp.zeros(w.shape, jnp.float32))
        dtable, dy_chunks = jax.lax.scan(
            body, dt0,
            (chunked(y, (d,)), chunked(labels, ()), chunked(mask, ())))
        dy = dy_chunks.transpose(1, 0, 2, 3).reshape(b, s, d)
        return (dy.astype(y.dtype), dtable.astype(w.dtype), None, None)

    ce_sums.defvjp(ce_sums_fwd, ce_sums_bwd)

    nll, correct = ce_sums(y, table, labels, mask)
    denom = jnp.maximum(mask.sum(), 1.0)
    return nll / denom, correct / denom


def make_loss_fn(model: Model, step_cfg: StepConfig):
    def loss_fn(params, batch):
        if step_cfg.ce_seq_chunk:
            hidden, aux = model.forward_hidden(params, batch)
            loss, acc = chunked_cross_entropy(
                model, params, hidden, batch["labels"],
                batch["loss_mask"], step_cfg.ce_seq_chunk)
        else:
            logits, aux = model.forward(params, batch)
            loss, acc = cross_entropy(logits, batch["labels"],
                                      batch["loss_mask"], ctx=model.ctx)
        total = loss
        metrics = {"ce_loss": loss, "accuracy": acc}
        if "moe_lb_loss" in aux:      # softmax routers only
            total = (total + step_cfg.moe_lb_weight * aux["moe_lb_loss"]
                     + step_cfg.moe_z_weight * aux["moe_z_loss"])
        metrics.update(aux)
        metrics["loss"] = total
        return total, metrics
    return loss_fn


def make_train_step(model: Model, optimizer: AdamW,
                    step_cfg: Optional[StepConfig] = None,
                    grad_shardings=None):
    """Returns ``train_step(params, opt_state, err_state, batch)`` ->
    (params, opt_state, err_state, metrics).

    ``err_state`` is the error-feedback buffer tree (zeros unless
    ``compress_grads``; pass None to disable entirely).
    With ``num_microbatches > 1`` the batch's leading dim is split and
    gradients accumulate in f32 before a single optimizer apply — the
    deferred-all-reduce pattern (collectives fire once per step, not once
    per microbatch).

    ``grad_shardings``: optional NamedSharding tree matching params;
    gradients are constrained to it (keeps e.g. the embedding-scatter
    gradient vocab-sharded instead of replicated)."""
    step_cfg = step_cfg or StepConfig()
    loss_fn = make_loss_fn(model, step_cfg)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def constrain_grads(grads):
        if grad_shardings is None:
            return grads
        return jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s),
            grads, grad_shardings)

    def compute_grads(params, batch):
        n_mb = step_cfg.num_microbatches
        if n_mb <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return constrain_grads(grads), metrics
        split = lambda x: x.reshape((n_mb, x.shape[0] // n_mb)
                                    + x.shape[1:])
        mb_batch = jax.tree_util.tree_map(split, batch)

        def body(acc, mb):
            (_, metrics), grads = grad_fn(params, mb)
            grads = constrain_grads(grads)
            acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), acc, grads)
            return acc, metrics

        def zero_like(p):
            z = jnp.zeros(p.shape, jnp.float32)
            return z

        zeros = constrain_grads(jax.tree_util.tree_map(zero_like, params))
        acc, metrics_stack = jax.lax.scan(body, zeros, mb_batch)
        metrics = jax.tree_util.tree_map(lambda m: m.mean(0),
                                         metrics_stack)
        grads = jax.tree_util.tree_map(lambda a: a / n_mb, acc)
        return grads, metrics

    def train_step(params, opt_state: OptState, err_state, batch):
        grads, metrics = compute_grads(params, batch)
        if step_cfg.compress_grads and err_state is not None:
            grads, err_state = compression.compress_tree(grads, err_state)
        params, opt_state, opt_metrics = optimizer.update(
            grads, opt_state, params)
        metrics.update(opt_metrics)
        return params, opt_state, err_state, metrics

    return train_step


def make_eval_step(model: Model, step_cfg: Optional[StepConfig] = None):
    loss_fn = make_loss_fn(model, step_cfg or StepConfig())

    def eval_step(params, batch):
        _, metrics = loss_fn(params, batch)
        return metrics
    return eval_step
