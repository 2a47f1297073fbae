"""The family-generic model: one scanned layer stack covering the assigned
architectures (dense / MoE / SSM / hybrid / audio / VLM), and for an
``interleaved`` model one stack per kind of layer, walked in the order of
its ``layer_pattern``.

Key structural decisions (see DESIGN.md §5):

* **scan over stacked layer params** — per-layer weights carry a leading
  ``[L]`` dim and run under ``jax.lax.scan``, keeping HLO size and compile
  time O(1) in depth.  Per-layer *statics* that differ inside a stack
  (gemma local/global window, per-layer rope theta) are passed as traced
  scan inputs, so one traced body serves every layer.
* **caches as scan xs/ys** — KV/SSM state is stacked ``[L, ...]`` and
  flows through the scan as per-layer slices, giving natural donation.
* **VLM grouping** — cross-attention blocks every k layers are handled by
  an outer scan over groups (inner scan over k self layers + one gated
  cross block), so cross params exist only where they are used.
* **interleaved layers** — a model whose layers differ in kind (Mamba-2
  mixer ``M``, expert layer ``E``, attention ``*``) keeps one parameter
  stack per kind and walks its pattern layer by layer, each block under
  the remat policy; its serving cache holds KV for the attention layers
  and conv/SSM state for the Mamba layers, each stacked by its own kind.
* **remat** — each block body can be wrapped in ``jax.checkpoint`` with a
  selectable policy (a §Perf lever).
* **named scopes** — the training step's work carries stable
  ``jax.named_scope`` names, so a device trace can be read by layer:
  ``model.embed``, ``model.layers`` (the layer scan), ``model.block`` (a
  block's norms, residual adds and hybrid fuse), ``model.ssm``,
  ``model.attention``, ``model.mlp``, ``model.moe``, ``model.loss`` (final norm, logits,
  cross entropy; ``train/step.py``) and ``optim.update``
  (``optim/optimizer.py``).  Backward ops carry their forward's scope
  as ``transpose(jvp(<scope>))``, custom-VJP backward rules included.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (dense_init, embed_lookup, gated_mlp, rope,
                                 rms_norm, unembed)

Params = Dict[str, Any]

REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


@dataclass
class ModelOptions:
    use_pallas: bool = False
    remat_policy: str = "full"        # applied to train forward only
    remat_prevent_cse: bool = True    # keep saved residuals in model dtype
    attn_chunk: int = 256             # attention tile edge; longer keys tile


class Model:
    """Functional model: ``init`` -> params; ``forward`` (train),
    ``prefill`` and ``decode_step`` (serving).  ``ctx`` is an optional
    ShardingCtx."""

    def __init__(self, cfg: ArchConfig, ctx=None,
                 options: Optional[ModelOptions] = None) -> None:
        self.cfg = cfg
        self.ctx = ctx
        self.opt = options or ModelOptions()
        self.dtype = jnp.dtype(cfg.dtype)
        kinds = cfg.layer_kinds()
        self.windows = jnp.array(
            [cfg.window_size if k == "local" else (1 << 30) for k in kinds],
            jnp.int32)
        theta_g = cfg.rope_theta_global or cfg.rope_theta
        self.thetas = jnp.array(
            [cfg.rope_theta if k == "local" else theta_g for k in kinds],
            jnp.float32)
        # cross-attention bookkeeping (VLM)
        cross_set = set(cfg.cross_attn_layers())
        self.n_cross = len(cross_set)
        slots, c = [], 0
        for i in range(cfg.num_layers):
            slots.append(c)
            if i in cross_set:
                c += 1
        self.cross_flags = jnp.array(
            [1 if i in cross_set else 0 for i in range(cfg.num_layers)],
            jnp.int32)
        self.cross_slots = jnp.array(slots, jnp.int32)

    # ------------------------------------------------------------------ init
    def _init_attn(self, key, n_layers: int) -> Params:
        cfg, dt = self.cfg, self.dtype
        d, hq, hkv, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                          cfg.resolved_head_dim)
        ks = jax.random.split(key, 4)
        L = (n_layers,)
        p = {
            "norm_scale": jnp.zeros(L + (d,), dt),
            "wq": dense_init(ks[0], L + (d, hq, hd), dt, fan_in=d),
            "wk": dense_init(ks[1], L + (d, hkv, hd), dt, fan_in=d),
            "wv": dense_init(ks[2], L + (d, hkv, hd), dt, fan_in=d),
            "wo": dense_init(ks[3], L + (hq, hd, d), dt, fan_in=hq * hd),
        }
        if cfg.qk_norm:
            p["q_norm"] = jnp.zeros(L + (hd,), dt)
            p["k_norm"] = jnp.zeros(L + (hd,), dt)
        if cfg.post_norms:
            p["post_norm_scale"] = jnp.zeros(L + (d,), dt)
        return p

    def _init_mlp(self, key, n_layers: int) -> Params:
        cfg, dt = self.cfg, self.dtype
        d, ff = cfg.d_model, cfg.d_ff
        ks = jax.random.split(key, 3)
        L = (n_layers,)
        p = {
            "norm_scale": jnp.zeros(L + (d,), dt),
            "w_gate": dense_init(ks[0], L + (d, ff), dt, fan_in=d),
            "w_up": dense_init(ks[1], L + (d, ff), dt, fan_in=d),
            "w_down": dense_init(ks[2], L + (ff, d), dt, fan_in=ff),
        }
        if cfg.post_norms:
            p["post_norm_scale"] = jnp.zeros(L + (d,), dt)
        return p

    def _init_stacked(self, init_one, key, n_layers: int) -> Params:
        keys = jax.random.split(key, n_layers)
        return jax.vmap(init_one)(keys)

    def init(self, key) -> Params:
        cfg, dt = self.cfg, self.dtype
        kE, kH, kB, kX, kM = jax.random.split(key, 5)
        params: Params = {
            "embed": {"table": (jax.random.normal(
                kE, (cfg.vocab_size, cfg.d_model), jnp.float32)
                * 0.02).astype(dt)},
            "final_norm_scale": jnp.zeros((cfg.d_model,), dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(kH, (cfg.d_model, cfg.vocab_size),
                                           dt)
        blocks: Params = {}
        L = cfg.num_layers
        n_attn = n_ssm = n_moe = L
        if cfg.layer_pattern:
            kinds = cfg.layer_kinds()
            n_attn, n_ssm, n_moe = (kinds.count(k)
                                    for k in ("global", "ssm", "moe"))
        if cfg.has_attention:
            blocks["attn"] = self._init_attn(jax.random.fold_in(kB, 0),
                                             n_attn)
        if cfg.family in ("ssm", "hybrid", "interleaved"):
            blocks["ssm"] = self._init_stacked(
                lambda k: ssm_mod.init_ssm_params(k, cfg, dt),
                jax.random.fold_in(kB, 1), n_ssm)
        if cfg.family == "hybrid":
            blocks["fuse"] = {
                "attn_norm": jnp.zeros((L, cfg.d_model), dt),
                "ssm_norm": jnp.zeros((L, cfg.d_model), dt),
                "beta_attn": jnp.ones((L,), jnp.float32),
                "beta_ssm": jnp.ones((L,), jnp.float32),
            }
        if cfg.is_moe:
            blocks["moe"] = self._init_stacked(
                lambda k: moe_mod.init_moe_params(k, cfg, dt),
                jax.random.fold_in(kB, 2), n_moe)
        elif cfg.d_ff:
            blocks["mlp"] = self._init_mlp(jax.random.fold_in(kB, 3), L)
        params["blocks"] = blocks
        if self.n_cross:
            params["xblocks"] = {
                "attn": self._init_attn(jax.random.fold_in(kX, 0),
                                        self.n_cross),
                "mlp": self._init_mlp(jax.random.fold_in(kX, 1),
                                      self.n_cross),
                "gate_attn": jnp.zeros((self.n_cross,), jnp.float32),
                "gate_mlp": jnp.zeros((self.n_cross,), jnp.float32),
            }
        if cfg.num_meta_tokens:
            params["meta_tokens"] = (jax.random.normal(
                kM, (cfg.num_meta_tokens, cfg.d_model), jnp.float32)
                * 0.02).astype(dt)
        return params

    # -------------------------------------------------------------- helpers
    def _constrain(self, x, *logicals):
        if self.ctx is not None:
            return self.ctx.act(x, *logicals)
        return x

    def _scale(self) -> float:
        cfg = self.cfg
        return cfg.query_scale or cfg.resolved_head_dim ** -0.5

    def _qkv(self, p, h, positions, theta):
        cfg = self.cfg
        q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        if cfg.rope:
            q = rope(q, positions, theta)
            k = rope(k, positions, theta)
        q = self._constrain(q, "batch", None, "heads", None)
        k = self._constrain(k, "batch", None, "kv_heads", None)
        v = self._constrain(v, "batch", None, "kv_heads", None)
        return q, k, v

    def _attn_out(self, p, out):
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        if "post_norm_scale" in p:
            y = rms_norm(y, p["post_norm_scale"], self.cfg.norm_eps)
        return y

    def _attend_seq(self, q, k, v, positions, window):
        """Sequence attention: Pallas flash kernel (TPU fast path) or the
        XLA online-softmax fallback.  ``window`` is a traced per-layer
        scalar; under Pallas, mixed local/global stacks branch with
        ``lax.cond`` over the two static window values."""
        cfg = self.cfg
        if self.opt.use_pallas:
            from repro.kernels.ops import flash_attention_op

            def call(win: int):
                return flash_attention_op(
                    q, k, v, causal=True, window=win,
                    softcap=cfg.attn_logit_softcap, scale=self._scale(),
                    block_q=min(128, q.shape[1]),
                    block_k=min(128, k.shape[1]))
            kinds = set(cfg.layer_kinds())
            if "local" in kinds and "global" in kinds:
                return jax.lax.cond(window < (1 << 30),
                                    lambda: call(cfg.window_size),
                                    lambda: call(0))
            if "local" in kinds:
                return call(cfg.window_size)
            return call(0)
        return attn_mod.attend(
            q, k, v, positions, positions, causal=True, window=window,
            cap=cfg.attn_logit_softcap, scale=self._scale(),
            chunk=self.opt.attn_chunk)

    @jax.named_scope("model.attention")
    def _self_attention(self, p, x, positions, window, theta):
        """Pre-norm self attention over the fresh sequence (train/prefill).
        Returns (block output, (k, v)) — k/v feed the prefill cache."""
        cfg = self.cfg
        h = rms_norm(x, p["norm_scale"], cfg.norm_eps)
        h = self._constrain(h, "batch", "seq", "embed")
        q, k, v = self._qkv(p, h, positions, theta)
        out = self._attend_seq(q, k, v, positions, window)
        return self._attn_out(p, out), (k, v)

    @jax.named_scope("model.mlp")
    def _mlp(self, p, x):
        cfg = self.cfg
        h = rms_norm(x, p["norm_scale"], cfg.norm_eps)
        h = self._constrain(h, "batch", "seq", "embed")
        act = "gelu" if cfg.scale_embed else "silu"   # gemma family: gelu
        y = gated_mlp(h, p["w_gate"], p["w_up"], p["w_down"], act=act)
        if "post_norm_scale" in p:
            y = rms_norm(y, p["post_norm_scale"], cfg.norm_eps)
        return y

    def _hybrid_mix(self, fuse, attn_out, ssm_out):
        cfg = self.cfg
        return (rms_norm(attn_out, fuse["attn_norm"], cfg.norm_eps)
                * fuse["beta_attn"].astype(attn_out.dtype)
                + rms_norm(ssm_out, fuse["ssm_norm"], cfg.norm_eps)
                * fuse["beta_ssm"].astype(ssm_out.dtype)) * 0.5

    def _moe(self, p, x):
        return moe_mod.apply_moe(
            p, self.cfg, rms_norm(x, p["norm_scale"], self.cfg.norm_eps),
            ctx=self.ctx)

    # ------------------------------------------------------------ VLM bits
    def _image_kv(self, params, batch):
        """Per-cross-block K/V projections of the stub patch embeddings.
        Returns (k, v): [n_cross, B, T, Hkv, D]."""
        img = batch["image_embeds"].astype(self.dtype)
        xp = params["xblocks"]["attn"]
        k = jnp.einsum("btd,ndhk->nbthk", img, xp["wk"])
        v = jnp.einsum("btd,ndhk->nbthk", img, xp["wv"])
        return k, v

    def _cross_block(self, xp, idx, x, img_kv):
        """Gated cross-attention block; idx is a traced slot index."""
        cfg = self.cfg
        p = jax.tree_util.tree_map(lambda a: a[idx], xp["attn"])
        h = rms_norm(x, p["norm_scale"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
        k, v = img_kv[0][idx], img_kv[1][idx]
        sq, skv = q.shape[1], k.shape[1]
        out = attn_mod.attend(
            q, k, v, jnp.zeros((sq,), jnp.int32),
            jnp.zeros((skv,), jnp.int32), causal=False, window=None,
            cap=0.0, scale=self._scale(), chunk=self.opt.attn_chunk)
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        x = x + jnp.tanh(xp["gate_attn"][idx]).astype(x.dtype) * y
        mp = jax.tree_util.tree_map(lambda a: a[idx], xp["mlp"])
        x = x + jnp.tanh(xp["gate_mlp"][idx]).astype(x.dtype) \
            * self._mlp(mp, x)
        return x

    # -------------------------------------------------------------- embed
    @jax.named_scope("model.embed")
    def embed_inputs(self, params, batch) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.frontend == "audio_frames":
            x = batch["embeds"].astype(self.dtype)
        else:
            # constraining the table keeps its gather-backward (scatter)
            # gradient vocab-sharded instead of replicated
            table = self._constrain(params["embed"]["table"],
                                    "vocab", "fsdp")
            x = embed_lookup(table, batch["tokens"],
                             scale_by_dim=cfg.scale_embed)
        if cfg.num_meta_tokens:
            meta = jnp.broadcast_to(
                params["meta_tokens"][None],
                (x.shape[0],) + params["meta_tokens"].shape).astype(x.dtype)
            x = jnp.concatenate([meta, x], axis=1)
        return self._constrain(x, "batch", "seq", "embed")

    def _logits(self, params, x) -> jnp.ndarray:
        cfg = self.cfg
        x = rms_norm(x, params["final_norm_scale"], cfg.norm_eps)
        table = (params["embed"]["table"] if cfg.tie_embeddings
                 else params["lm_head"])
        table = self._constrain(
            table, *(("vocab", "fsdp") if cfg.tie_embeddings
                     else ("fsdp", "vocab")))
        logits = unembed(x, table, cfg.tie_embeddings,
                         cfg.final_logit_softcap)
        return self._constrain(logits, "batch", "seq", "vocab")

    def forward_hidden(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        """Training forward up to (but excluding) the unembedding.
        Used by the chunked cross-entropy path (train/step.py), which
        never materializes the full [B, S, V] logits."""
        return self._forward_trunk(params, batch)

    # ------------------------------------------------------- train forward
    @jax.named_scope("model.block")
    def _block_train(self, bp, x, window, theta, positions, aux):
        cfg = self.cfg
        if cfg.family == "ssm":
            return x + ssm_mod.apply_ssm_mixer(bp["ssm"], cfg, x, use_pallas=self.opt.use_pallas), aux
        if cfg.family == "hybrid":
            attn_out, _ = self._self_attention(bp["attn"], x, positions,
                                               window, theta)
            ssm_out = ssm_mod.apply_ssm_mixer(bp["ssm"], cfg, x, use_pallas=self.opt.use_pallas)
            x = x + self._hybrid_mix(bp["fuse"], attn_out, ssm_out)
            return x + self._mlp(bp["mlp"], x), aux
        attn_out, _ = self._self_attention(bp["attn"], x, positions,
                                           window, theta)
        x = x + attn_out
        if cfg.is_moe:
            y, a = self._moe(bp["moe"], x)
            x = x + y
            aux = moe_mod.combine_aux(aux, a)
        elif cfg.d_ff:
            x = x + self._mlp(bp["mlp"], x)
        return x, aux

    def forward(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        """Full-sequence forward (training).  Returns (logits, aux)."""
        x, aux = self._forward_trunk(params, batch)
        with jax.named_scope("model.loss"):
            return self._logits(params, x), aux

    def _forward_trunk(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg
        x = self.embed_inputs(params, batch)
        seq = x.shape[1]
        positions = jnp.arange(seq, dtype=jnp.int32)
        aux0 = moe_mod.aux_zeros(cfg) if cfg.is_moe else {}
        policy = REMAT_POLICIES.get(self.opt.remat_policy)
        remat = self.opt.remat_policy != "none"
        if cfg.layer_pattern:
            return self._pattern_trunk(params, x, positions, aux0,
                                       policy if remat else False)

        def body(carry, xs):
            x, aux = carry
            bp, window, theta = xs
            x, aux = self._block_train(bp, x, window, theta, positions, aux)
            x = self._constrain(x, "batch", "seq", "embed")
            return (x, aux), None

        if self.n_cross:
            img_kv = self._image_kv(params, batch)
            every = cfg.cross_attn_every
            n_groups = cfg.num_layers // every
            grouped = jax.tree_util.tree_map(
                lambda a: a.reshape((n_groups, every) + a.shape[1:]),
                params["blocks"])
            windows = self.windows.reshape(n_groups, every)
            thetas = self.thetas.reshape(n_groups, every)

            # nested remat: inner per-layer body AND the outer group are
            # checkpointed, so bwd of a group recomputes one layer at a
            # time instead of holding 5 layers of intermediates.
            inner = (jax.checkpoint(body, policy=policy,
                                   prevent_cse=self.opt.remat_prevent_cse)
                     if remat else body)

            def group_body(carry, xs):
                bp, window, theta, idx = xs
                (x, aux), _ = jax.lax.scan(inner, carry,
                                           (bp, window, theta))
                x = self._cross_block(params["xblocks"], idx, x, img_kv)
                x = self._constrain(x, "batch", "seq", "embed")
                return (x, aux), None

            if remat:
                group_body = jax.checkpoint(group_body, policy=policy,
                                            prevent_cse=self.opt.remat_prevent_cse)
            with jax.named_scope("model.layers"):
                (x, aux), _ = jax.lax.scan(
                    group_body, (x, aux0),
                    (grouped, windows, thetas,
                     jnp.arange(n_groups, dtype=jnp.int32)))
        else:
            scanned = (jax.checkpoint(body, policy=policy,
                                   prevent_cse=self.opt.remat_prevent_cse)
                       if remat else body)
            with jax.named_scope("model.layers"):
                (x, aux), _ = jax.lax.scan(scanned, (x, aux0),
                                           (params["blocks"], self.windows,
                                            self.thetas))
        if cfg.num_meta_tokens:
            x = x[:, cfg.num_meta_tokens:]
        return x, aux

    # --------------------------------------------------- interleaved layers
    def _pattern_layers(self, params):
        """(layer index, kind, its parameters) of each layer of an
        interleaved model, in pattern order: layer ``i`` of a kind takes
        the next slice of that kind's stack."""
        stacks = {"ssm": "ssm", "moe": "moe", "global": "attn"}
        taken: Dict[str, int] = {}
        for i, kind in enumerate(self.cfg.layer_kinds()):
            j = taken[kind] = taken.get(kind, -1) + 1
            yield i, kind, jax.tree_util.tree_map(
                lambda a: a[j], params["blocks"][stacks[kind]])

    def _pattern_trunk(self, params, x, positions, aux, policy):
        """Training forward of an interleaved model: each layer is
        ``x + mixer(norm(x))`` (each mixer applies its own pre-norm),
        checkpointed under ``policy`` unless it is False."""
        cfg = self.cfg

        def block(kind, i):
            @jax.named_scope("model.block")
            def run(p, x):
                if kind == "ssm":
                    return x + ssm_mod.apply_ssm_mixer(
                        p, cfg, x, use_pallas=self.opt.use_pallas), {}
                if kind == "moe":
                    y, a = self._moe(p, x)
                    return x + y, a
                y, _ = self._self_attention(p, x, positions, self.windows[i],
                                            self.thetas[i])
                return x + y, {}
            if policy is False:
                return run
            return jax.checkpoint(run, policy=policy,
                                  prevent_cse=self.opt.remat_prevent_cse)

        with jax.named_scope("model.layers"):
            for i, kind, p in self._pattern_layers(params):
                x, a = block(kind, i)(p, x)
                if a:
                    aux = moe_mod.combine_aux(aux, a)
                x = self._constrain(x, "batch", "seq", "embed")
        if cfg.num_meta_tokens:
            x = x[:, cfg.num_meta_tokens:]
        return x, aux

    def _pattern_prefill(self, params, x, positions, extra_slots):
        """Prefill of an interleaved model: KV of each attention layer and
        conv/SSM state of each Mamba layer, each stacked by its kind."""
        cfg = self.cfg
        parts: Dict[str, list] = {"k": [], "v": [], "ssm": [], "conv": []}
        for i, kind, p in self._pattern_layers(params):
            if kind == "ssm":
                y, st = ssm_mod.apply_ssm_mixer(
                    p, cfg, x, use_pallas=self.opt.use_pallas,
                    return_state=True)
                parts["ssm"].append(st["ssm"])
                parts["conv"].append(st["conv"])
            elif kind == "moe":
                y, _ = self._moe(p, x)
            else:
                y, (k, v) = self._self_attention(
                    p, x, positions, self.windows[i], self.thetas[i])
                if extra_slots:
                    pad = ((0, 0), (0, extra_slots), (0, 0), (0, 0))
                    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
                parts["k"].append(k)
                parts["v"].append(v)
            x = self._constrain(x + y, "batch", "seq", "embed")
        cache: Params = {"pos": jnp.asarray(x.shape[1], jnp.int32)}
        cache.update({name: jnp.stack(ps) for name, ps in parts.items()
                      if ps})
        return self._logits(params, x[:, -1:]), cache

    def _pattern_decode(self, params, x, cache, attn_decode):
        """One-token decode of an interleaved model through its cache."""
        cfg = self.cfg
        parts: Dict[str, list] = {"k": [], "v": [], "ssm": [], "conv": []}
        for i, kind, p in self._pattern_layers(params):
            if kind == "ssm":
                j = len(parts["ssm"])
                y, st = ssm_mod.apply_ssm_decode(
                    p, cfg, x, {"ssm": cache["ssm"][j],
                                "conv": cache["conv"][j]})
                parts["ssm"].append(st["ssm"])
                parts["conv"].append(st["conv"])
            elif kind == "moe":
                y, _ = self._moe(p, x)
            else:
                j = len(parts["k"])
                y, kc, vc = attn_decode(p, x, self.windows[i], self.thetas[i],
                                        cache["k"][j], cache["v"][j])
                parts["k"].append(kc)
                parts["v"].append(vc)
            x = x + y
        new_cache: Params = {"pos": cache["pos"] + 1}
        new_cache.update({name: jnp.stack(ps) for name, ps in parts.items()
                          if ps})
        return self._logits(params, x), new_cache

    # ------------------------------------------------------------- serving
    def prefill(self, params, batch, extra_slots: int = 0
                ) -> Tuple[jnp.ndarray, Params]:
        """Process the full prompt.  Returns (last-position logits, cache).
        ``extra_slots`` pre-allocates room for subsequent decode steps."""
        cfg = self.cfg
        x = self.embed_inputs(params, batch)
        total = x.shape[1]
        positions = jnp.arange(total, dtype=jnp.int32)
        if cfg.layer_pattern:
            return self._pattern_prefill(params, x, positions, extra_slots)
        img_kv = self._image_kv(params, batch) if self.n_cross else None

        def body(x, xs):
            bp, window, theta, is_cross, slot = xs
            cache_out = {}
            if cfg.family == "ssm":
                y, st = ssm_mod.apply_ssm_mixer(bp["ssm"], cfg, x, use_pallas=self.opt.use_pallas,
                                                return_state=True)
                x = x + y
                cache_out.update(st)
            elif cfg.family == "hybrid":
                attn_out, (k, v) = self._self_attention(
                    bp["attn"], x, positions, window, theta)
                ssm_out, st = ssm_mod.apply_ssm_mixer(bp["ssm"], cfg, x, use_pallas=self.opt.use_pallas,
                                                      return_state=True)
                x = x + self._hybrid_mix(bp["fuse"], attn_out, ssm_out)
                x = x + self._mlp(bp["mlp"], x)
                cache_out.update(st)
                cache_out["k"], cache_out["v"] = k, v
            else:
                attn_out, (k, v) = self._self_attention(
                    bp["attn"], x, positions, window, theta)
                x = x + attn_out
                if cfg.is_moe:
                    y, _ = self._moe(bp["moe"], x)
                    x = x + y
                elif cfg.d_ff:
                    x = x + self._mlp(bp["mlp"], x)
                cache_out["k"], cache_out["v"] = k, v
            if self.n_cross:
                x = jax.lax.cond(
                    is_cross > 0,
                    lambda x: self._cross_block(params["xblocks"], slot, x,
                                                img_kv),
                    lambda x: x, x)
            x = self._constrain(x, "batch", "seq", "embed")
            return x, cache_out

        x, layer_caches = jax.lax.scan(
            body, x, (params["blocks"], self.windows, self.thetas,
                      self.cross_flags, self.cross_slots))
        cache: Params = {"pos": jnp.asarray(total, jnp.int32)}
        if cfg.has_attention:
            k, v = layer_caches["k"], layer_caches["v"]
            if extra_slots:
                pad = ((0, 0), (0, 0), (0, extra_slots), (0, 0), (0, 0))
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            cache["k"], cache["v"] = k, v
        if cfg.family in ("ssm", "hybrid"):
            cache["ssm"] = layer_caches["ssm"]
            cache["conv"] = layer_caches["conv"]
        if self.n_cross:
            cache["xk"], cache["xv"] = img_kv
        logits = self._logits(params, x[:, -1:])
        return logits, cache

    def init_cache(self, batch_size: int, max_len: int) -> Params:
        """Allocate an empty decode cache (for cost analysis / cold decode).
        ``max_len`` includes room for tokens to be decoded; meta tokens are
        added on top."""
        cfg, dt = self.cfg, self.dtype
        L = n_ssm = cfg.num_layers
        if cfg.layer_pattern:
            L, n_ssm = (cfg.layer_kinds().count(k) for k in ("global", "ssm"))
        total = max_len + cfg.num_meta_tokens
        cache: Params = {"pos": jnp.zeros((), jnp.int32)}
        if cfg.has_attention:
            kvshape = (L, batch_size, total, cfg.num_kv_heads,
                       cfg.resolved_head_dim)
            cache["k"] = jnp.zeros(kvshape, dt)
            cache["v"] = jnp.zeros(kvshape, dt)
        if cfg.family in ("ssm", "hybrid", "interleaved"):
            cache["ssm"] = jnp.zeros((n_ssm, batch_size, cfg.ssm_heads,
                                      cfg.ssm_headdim, cfg.ssm_state),
                                     jnp.float32)
            cache["conv"] = jnp.zeros((n_ssm, batch_size, cfg.conv_width - 1,
                                       cfg.ssm_conv_dim), dt)
        if self.n_cross:
            cache["xk"] = jnp.zeros((self.n_cross, batch_size,
                                     cfg.num_image_tokens, cfg.num_kv_heads,
                                     cfg.resolved_head_dim), dt)
            cache["xv"] = jnp.zeros_like(cache["xk"])
        return cache

    def decode_step(self, params, batch, cache) -> Tuple[jnp.ndarray, Params]:
        """One-token decode.  batch: {"tokens": [B,1]} or {"embeds":
        [B,1,d]}.  Returns (logits [B,1,V], new cache)."""
        cfg = self.cfg
        pos = cache["pos"]
        if cfg.frontend == "audio_frames":
            x = batch["embeds"].astype(self.dtype)
        else:
            x = embed_lookup(params["embed"]["table"], batch["tokens"],
                             scale_by_dim=cfg.scale_embed)
        positions = pos[None]
        max_total = cache["k"].shape[2] if cfg.has_attention else 0

        def attn_decode(bp, x, window, theta, k_cache, v_cache):
            h = rms_norm(x, bp["norm_scale"], cfg.norm_eps)
            q, k_new, v_new = self._qkv(bp, h, positions, theta)
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, k_new.astype(k_cache.dtype), (0, pos, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, v_new.astype(v_cache.dtype), (0, pos, 0, 0))
            kv_pos = jnp.arange(max_total, dtype=jnp.int32)
            kv_pos = jnp.where(kv_pos <= pos, kv_pos, -1)
            out = attn_mod.attend(
                q, k_cache, v_cache, positions, kv_pos, causal=True,
                window=window, cap=cfg.attn_logit_softcap,
                scale=self._scale(), chunk=self.opt.attn_chunk)
            return self._attn_out(bp, out), k_cache, v_cache

        if cfg.layer_pattern:
            return self._pattern_decode(params, x, cache, attn_decode)

        def body(x, xs):
            (bp, window, theta, is_cross, slot, kc, vc, ssm_st,
             conv_st) = xs
            out_cache = {}
            if cfg.family == "ssm":
                y, st = ssm_mod.apply_ssm_decode(
                    bp["ssm"], cfg, x, {"ssm": ssm_st, "conv": conv_st})
                x = x + y
                out_cache["ssm"], out_cache["conv"] = st["ssm"], st["conv"]
            elif cfg.family == "hybrid":
                attn_out, kc, vc = attn_decode(bp["attn"], x, window,
                                               theta, kc, vc)
                ssm_out, st = ssm_mod.apply_ssm_decode(
                    bp["ssm"], cfg, x, {"ssm": ssm_st, "conv": conv_st})
                x = x + self._hybrid_mix(bp["fuse"], attn_out, ssm_out)
                x = x + self._mlp(bp["mlp"], x)
                out_cache.update({"ssm": st["ssm"], "conv": st["conv"],
                                  "k": kc, "v": vc})
            else:
                attn_out, kc, vc = attn_decode(bp["attn"], x, window,
                                               theta, kc, vc)
                x = x + attn_out
                if cfg.is_moe:
                    y, _ = self._moe(bp["moe"], x)
                    x = x + y
                elif cfg.d_ff:
                    x = x + self._mlp(bp["mlp"], x)
                out_cache["k"], out_cache["v"] = kc, vc
            if self.n_cross:
                x = jax.lax.cond(
                    is_cross > 0,
                    lambda x: self._cross_block(
                        params["xblocks"], slot, x,
                        (cache["xk"], cache["xv"])),
                    lambda x: x, x)
            return x, out_cache

        L = cfg.num_layers
        dummy = jnp.zeros((L, 1), self.dtype)
        xs = (params["blocks"], self.windows, self.thetas,
              self.cross_flags, self.cross_slots,
              cache.get("k", dummy), cache.get("v", dummy),
              cache.get("ssm", dummy), cache.get("conv", dummy))
        x, layer_caches = jax.lax.scan(body, x, xs)
        new_cache: Params = {"pos": pos + 1}
        for key in ("k", "v", "ssm", "conv"):
            if key in cache:
                new_cache[key] = layer_caches[key]
        for key in ("xk", "xv"):
            if key in cache:
                new_cache[key] = cache[key]
        logits = self._logits(params, x)
        return logits, new_cache
