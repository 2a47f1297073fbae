"""Mixture-of-Experts FFN: a router over every expert, and the part of the
result that the experts held here give, with no token-slot dropped.

The layer is told which experts it holds: ``first`` and the number of
stacked expert weights it is given (all of them on one chip; a share of
them per expert shard of a mesh, where the shares' outputs plus the shared
expert once add up to the whole layer). It routes every token over all
``num_experts``:

* ``softmax``: top-k of the softmax, with a load-balance and a z-loss term;
* ``sigmoid`` (DeepSeek-V3, Nemotron-H): top-k of the sigmoid scores plus a
  selection bias (``router_bias``), weighted by the scores alone; no
  auxiliary loss.

The weights are renormalised over the top k and scaled by
``moe_routed_scale``. The token-slots routed to held experts are sorted by
expert and each projection runs as one grouped matmul
(``jax.lax.ragged_dot``) over them, every such slot inside the row bound,
so none is dropped. Rows past the routed ones lie outside every group and
are zeroed going in and coming out: the TPU's grouped matmul leaves them
unwritten, and its time follows the rows in groups. Each row's output is
added, weighted, to its token. Experts are gated SiLU (``swiglu``) or
``relu2``, ``down(relu(up x)^2)``. The shared expert runs once over every
token.

A configuration that holds fewer experts than it routes to
(``moe_experts_held``) is one chip's cut of an expert-parallel layout with
no exchange between chips. Its held experts take exactly their balanced
share of each sequence's slots, ``k * seq * held / num_experts``: one
selection offset per sequence, added to the held experts' selection scores
alone, lifts or lowers them until that many slots fall to them. It stands
in for the balancing that holds each expert near the mean load in the
deployment; without it the held share follows the training, and the
step's time follows the held share. That share is then the row bound.

On a mesh, each data shard routes and sorts its own tokens and each expert
shard computes its experts' part, summed over the expert shards: the
data-parallel layout already holds a data shard's tokens on every expert
shard, so no token is exchanged.

Three device counters leave through ``aux`` into the step's metrics:
``moe_held_slots`` (token-slots routed to held experts),
``moe_load_max_over_mean`` (the busiest held expert's rows over the mean)
and ``moe_dropped_slots`` (held slots with no row of their expert's group
inside the bound: 0).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init

F32 = jnp.float32


def init_moe_params(key, cfg, dtype) -> Dict[str, jnp.ndarray]:
    d, ff, e, n = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.experts_held
    ks = jax.random.split(key, 7)
    params = {
        "norm_scale": jnp.zeros((d,), dtype),  # pre-FFN norm
        "router": dense_init(ks[0], (d, e), F32),
        "w_up": dense_init(ks[2], (n, d, ff), dtype, fan_in=d),
        "w_down": dense_init(ks[3], (n, ff, d), dtype, fan_in=ff),
    }
    if cfg.expert_act == "swiglu":
        params["w_gate"] = dense_init(ks[1], (n, d, ff), dtype, fan_in=d)
    if cfg.moe_router == "sigmoid":
        params["router_bias"] = jnp.zeros((e,), F32)
    if cfg.shared_expert:
        sf = cfg.shared_ff
        params["shared_up"] = dense_init(ks[5], (d, sf), dtype)
        params["shared_down"] = dense_init(ks[6], (sf, d), dtype, fan_in=sf)
        if cfg.expert_act == "swiglu":
            params["shared_gate"] = dense_init(ks[4], (d, sf), dtype)
    return params


def aux_zeros(cfg) -> Dict[str, jnp.ndarray]:
    """The ``aux`` a model with no expert layer run yet carries."""
    keys = ["moe_held_slots", "moe_load_max_over_mean", "moe_dropped_slots"]
    if cfg.moe_router == "softmax":
        keys += ["moe_lb_loss", "moe_z_loss"]
    return {k: jnp.float32(0.0) for k in keys}


def combine_aux(acc: Dict, new: Dict) -> Dict:
    """Over layers: the load ratio's worst layer, every other term summed."""
    return {k: (jnp.maximum if k == "moe_load_max_over_mean" else jnp.add)(
        acc[k], new[k]) for k in acc}


def is_cut(cfg) -> bool:
    """One chip's cut of an expert-parallel layout: fewer experts held than
    routed to, and the held share pinned."""
    return 0 < cfg.moe_experts_held < cfg.num_experts


def balanced_share(cfg, seq: int, n: int) -> int:
    """Slots of one sequence that ``n`` held experts of a cut take."""
    return cfg.experts_per_token * seq * n // cfg.num_experts


def row_bound(cfg, bsz: int, seq: int, n: int) -> int:
    """Rows of the grouped matmuls: a cut's held share of the batch's
    slots, else every slot."""
    if is_cut(cfg):
        return bsz * balanced_share(cfg, seq, n)
    return bsz * seq * cfg.experts_per_token


def _pinned_top_k(pick, k: int, first: int, n: int, seqs: int, share: int):
    """Top ``k`` experts of each token by ``pick [T, E]`` after one offset per
    sequence on the scores of experts ``first .. first+n``, the offset that
    gives them exactly ``share`` of the sequence's slots. For each token the
    a-th best held expert enters its top k once the offset passes the gap
    between the (k+1-a)-th best other expert and it; a sequence's ``share``
    smallest gaps are the slots the held experts take, and a token takes
    its best ``a`` held experts and ``k - a`` best others."""
    t, e = pick.shape
    m = min(k, n)
    if e - n < k:
        raise ValueError(f"a cut holding {n} of {e} experts leaves fewer "
                         f"than k={k} others to route to")
    pick = jax.lax.stop_gradient(pick)
    held = (jnp.arange(e) >= first) & (jnp.arange(e) < first + n)
    hv, hi = jax.lax.top_k(jnp.where(held, pick, -jnp.inf), m)   # [T, m]
    ov, oi = jax.lax.top_k(jnp.where(held, -jnp.inf, pick), k)   # [T, k]
    gap = (ov[:, k - m:][:, ::-1] - hv).reshape(seqs, -1)        # a = 1..m
    first_gaps = jnp.argsort(gap, axis=-1, stable=True)[:, :share]
    taken = jnp.zeros(gap.shape, jnp.int32).at[
        jnp.arange(seqs)[:, None], first_gaps].set(1)
    a = taken.reshape(t, m).sum(-1, keepdims=True)               # [T, 1]
    j = jnp.arange(k)[None, :]
    from_held = jnp.pad(hi, ((0, 0), (0, k - m)))
    from_other = jnp.take_along_axis(oi, jnp.maximum(j - a, 0), axis=-1)
    return jnp.where(j < a, from_held, from_other)


def route(params, cfg, xf: jnp.ndarray, first: int, n: int, seqs: int):
    """Top-k experts of each token ``xf [T, d]`` over all ``num_experts``;
    in a cut, with the held experts ``first .. first+n`` pinned to their
    balanced share of each of the ``seqs`` sequences. Returns (logits [T,E],
    probs or scores [T,E], experts [T,k], weights [T,k])."""
    k = cfg.experts_per_token
    logits = jnp.einsum("td,de->te", xf.astype(F32), params["router"])
    if cfg.moe_router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        pick = scores + params["router_bias"]
    else:
        scores = pick = jax.nn.softmax(logits, axis=-1)
    if is_cut(cfg):
        idx = _pinned_top_k(pick, k, first, n, seqs,
                            balanced_share(cfg, xf.shape[0] // seqs, n))
    else:
        _, idx = jax.lax.top_k(pick, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return logits, scores, idx, w * cfg.moe_routed_scale


def _ffn(cfg, x, up, down, gate=None, matmul=None):
    """One expert's (or, with ``matmul`` grouped, each held expert's) FFN."""
    h = matmul(x, up)
    if cfg.expert_act == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.silu(matmul(x, gate)) * h
    return matmul(h, down)


def _routed(params, cfg, x: jnp.ndarray, first
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """The held experts' part of the routed result for ``x [B, S, d]``."""
    bsz, seq, d = x.shape
    k = cfg.experts_per_token
    n = params["w_up"].shape[0]
    xf = x.reshape(-1, d)
    t = xf.shape[0]
    logits, scores, idx, w = route(params, cfg, xf, first, n, bsz)

    # token-slots (slot j of token t at j*T + t) sorted by held expert;
    # slots routed elsewhere sort last
    local = idx.T.reshape(-1) - first
    held = (local >= 0) & (local < n)
    key = jnp.where(held, local, n)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[key].add(1)[:n]
    routed = jnp.sum(sizes)
    bound = row_bound(cfg, bsz, seq, n)
    slot = order[:bound]
    tok = slot % t
    live = jnp.arange(bound) < routed
    rows = jnp.where(live[:, None], jnp.take(xf, tok, axis=0), 0)
    out = _ffn(cfg, rows, params["w_up"], params["w_down"],
               params.get("w_gate"),
               lambda a, b: jax.lax.ragged_dot(a, b, sizes))   # [bound, d]
    gate = jnp.where(live, jnp.take(w.T.reshape(-1), slot), 0.0)
    y = jnp.zeros((t, d), F32).at[tok].add(
        jnp.where(live[:, None], out, 0).astype(F32) * gate[:, None])

    # a held slot counts as computed when its row, inside the bound, lies
    # in its own expert's group
    group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(bound),
                             side="right")
    computed = jnp.sum(live & (group == jnp.take(key, slot)))
    routed = routed.astype(F32)
    aux = {"moe_held_slots": routed,
           "moe_load_max_over_mean":
               jnp.max(sizes).astype(F32) / jnp.maximum(routed / n, 1e-9),
           "moe_dropped_slots": (jnp.sum(held) - computed).astype(F32)}
    if cfg.moe_router == "softmax":
        e = cfg.num_experts
        me = scores.mean(axis=0)                               # [E]
        ce = jax.nn.one_hot(idx[:, 0], e).mean(axis=0)         # [E]
        aux["moe_lb_loss"] = (me * ce).sum() * e
        aux["moe_z_loss"] = jnp.square(
            jax.nn.logsumexp(logits, axis=-1)).mean()
    return y.astype(x.dtype).reshape(bsz, seq, d), aux


def _axes(ax) -> tuple:
    return () if ax is None else (ax,) if isinstance(ax, str) else tuple(ax)


def _routed_sharded(params, cfg, x: jnp.ndarray, ctx):
    """``_routed`` per shard of ``ctx.mesh``: the batch split over its data
    axes, the held experts over its expert axes, the parts summed over the
    expert shards."""
    mesh = ctx.mesh
    n = params["w_up"].shape[0]
    b_ax = _axes(ctx.mesh_axes("batch", x.shape[0]))
    e_ax = _axes(ctx.mesh_axes("experts", n))
    if e_ax and is_cut(cfg):
        raise ValueError("a one-chip cut's expert layer cannot shard its "
                         "experts")
    per_shard = n // math.prod(mesh.shape[a] for a in e_ax)
    experts = ("w_up", "w_down", "w_gate")
    p = {k: v for k, v in params.items()
         if k in experts or k.startswith("router")}
    specs = {k: P(e_ax or None) if k in experts else P() for k in p}

    def body(p, xs):
        first = jax.lax.axis_index(e_ax) * per_shard if e_ax else 0
        y, aux = _routed(p, cfg, xs, first)
        every = b_ax + e_ax
        if e_ax:
            y = jax.lax.psum(y, e_ax)
        for name in aux:
            if name == "moe_load_max_over_mean":
                aux[name] = jax.lax.pmax(aux[name], every)
            elif name in ("moe_lb_loss", "moe_z_loss"):
                if b_ax:    # alike over the expert shards already
                    aux[name] = jax.lax.pmean(aux[name], b_ax)
            else:
                aux[name] = jax.lax.psum(aux[name], every)
        return y, aux

    # the body makes every output alike over the axes it is not split on;
    # with that tracked (check_vma) this JAX transposes the router's and
    # x's gradient without their sum over the expert shards
    return jax.shard_map(body, mesh=mesh, in_specs=(specs, P(b_ax or None)),
                         out_specs=(P(b_ax or None), P()),
                         check_vma=False)(p, x)


@jax.named_scope("model.moe")
def apply_moe(params, cfg, x: jnp.ndarray, first: int = 0, ctx=None
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """MoE FFN over the held experts ``first ..`` (as many as
    ``params["w_up"]`` stacks), or, with a ``ctx`` whose mesh has more than
    one device, over each shard's.  x: [B, S, d] (pre-normed).  Returns (y,
    aux)."""
    mesh = getattr(ctx, "mesh", None)
    if mesh is not None and mesh.size > 1:
        y, aux = _routed_sharded(params, cfg, x, ctx)
    else:
        y, aux = _routed(params, cfg, x, first)
    if cfg.shared_expert:
        y = y + _ffn(cfg, x, params["shared_up"], params["shared_down"],
                     params.get("shared_gate"),
                     lambda a, b: jnp.einsum("bsd,df->bsf", a, b))
    return y, aux
