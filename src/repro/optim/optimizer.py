"""AdamW with warmup-cosine schedule and global-norm clipping.

Self-contained (no optax): state is a pytree mirroring params (f32 m/v),
so the parameter sharding rules apply unchanged to optimizer state —
ZeRO-style sharded optimizer comes for free from the FSDP param specs.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptimizerConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


class OptState(NamedTuple):
    count: jnp.ndarray
    mu: Any
    nu: Any


def lr_at(cfg: OptimizerConfig, step) -> jnp.ndarray:
    step = jnp.asarray(step, jnp.float32)
    warm = cfg.lr * step / jnp.maximum(cfg.warmup_steps, 1)
    frac = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (
        1 + jnp.cos(jnp.pi * frac))
    return jnp.where(step < cfg.warmup_steps, warm, cfg.lr * cos)


def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(sum(leaves))


class AdamW:
    def __init__(self, cfg: Optional[OptimizerConfig] = None):
        self.cfg = cfg or OptimizerConfig()

    def init(self, params) -> OptState:
        zeros = lambda t: jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), t)
        return OptState(count=jnp.zeros((), jnp.int32),
                        mu=zeros(params), nu=zeros(params))

    @jax.named_scope("optim.update")
    def update(self, grads, state: OptState, params
               ) -> Tuple[Any, OptState, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        count = state.count + 1
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
        lr = lr_at(cfg, count)

        def upd(g, m, v, p):
            g = g.astype(jnp.float32) * scale
            m = cfg.b1 * m + (1 - cfg.b1) * g
            v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
            mh = m / (1 - cfg.b1 ** count.astype(jnp.float32))
            vh = v / (1 - cfg.b2 ** count.astype(jnp.float32))
            step = mh / (jnp.sqrt(vh) + cfg.eps)
            if p.ndim >= 2:  # decoupled weight decay on matrices only
                step = step + cfg.weight_decay * p.astype(jnp.float32)
            new_p = p.astype(jnp.float32) - lr * step
            return new_p.astype(p.dtype), m, v

        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_m = jax.tree_util.tree_leaves(state.mu)
        flat_v = jax.tree_util.tree_leaves(state.nu)
        flat_p = jax.tree_util.tree_leaves(params)
        new_p, new_m, new_v = [], [], []
        for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p):
            np_, nm, nv = upd(g, m, v, p)
            new_p.append(np_)
            new_m.append(nm)
            new_v.append(nv)
        unflatten = treedef.unflatten
        metrics = {"grad_norm": gnorm, "lr": lr}
        return (unflatten(new_p),
                OptState(count=count, mu=unflatten(new_m),
                         nu=unflatten(new_v)),
                metrics)
