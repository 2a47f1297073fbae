"""The model: one description of each layer, walked in train, prefill and
decode alike.

* **the layer plan** — every layer is a residual mixer followed by a
  residual FFN.  ``layer_plan`` reads the config once and names each
  layer's pair: attention and an MLP (dense, audio, VLM) or MoE (moe); an
  SSM and no FFN (ssm); hymba's fused attention ‖ SSM and an MLP
  (hybrid); and for an ``interleaved`` model one pair a character of its
  ``layer_pattern``: ``M`` an SSM, ``E`` MoE alone, ``*`` attention alone.
  Parameters are held in one stack per part (``blocks/attn``,
  ``blocks/ssm``, ``blocks/fuse``, ``blocks/mlp``, ``blocks/moe``), each as
  deep as the layers that use it; the serving cache likewise (KV for the
  attention layers, conv/SSM state for the SSM layers).  A new kind of
  layer is one row of the plan plus its mixer or FFN.
* **one layer function** — ``_layer`` applies one layer in every mode.
  The modes differ only in the state a mixer takes and gives back: train
  none, prefill gives back KV or conv/SSM state, decode takes the layer's
  cache slice and gives back the updated slice.
* **one walker** — ``_run_layers``.  A plan that repeats one layer runs
  under ``jax.lax.scan`` over the stacked parameters, keeping HLO size and
  compile time O(1) in depth; per-layer statics that differ inside a
  stack (local/global window, rope theta) are traced scan inputs and the
  caches flow through as per-layer slices.  A VLM's cross-attention block
  every k layers is applied by an outer scan over groups of k layers, so
  cross params exist only where they are used.  Any other plan is walked
  layer by layer, each layer taking the next slice of its parts' stacks.
* **remat** — in train each layer (and a VLM's group) runs under
  ``jax.checkpoint`` with a selectable policy (a §Perf lever).
* **named scopes** — the training step's work carries stable
  ``jax.named_scope`` names, so a device trace can be read by layer:
  ``model.embed``, ``model.layers`` (the walk), ``model.block`` (a
  layer's norms, residual adds and hybrid fuse), ``model.ssm``,
  ``model.attention``, ``model.mlp``, ``model.moe``, ``model.loss`` (final
  norm, logits, cross entropy; ``train/step.py``) and ``optim.update``
  (``optim/optimizer.py``).  Backward ops carry their forward's scope
  as ``transpose(jvp(<scope>))``, custom-VJP backward rules included.
"""

from __future__ import annotations

import collections
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

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

# the parameter stacks each mixer and FFN uses, and the cache each stack
# keeps per layer
STACKS = {"attn": ("attn",), "ssm": ("ssm",), "hybrid": ("attn", "ssm", "fuse"),
          "mlp": ("mlp",), "moe": ("moe",), None: ()}
CACHES = {"attn": ("k", "v"), "ssm": ("ssm", "conv")}


def layer_plan(cfg: ArchConfig) -> List[Tuple[Optional[str], Optional[str]]]:
    """(mixer, FFN) of each layer, in order; None where a layer has none."""
    if cfg.layer_pattern:
        rows = {"M": ("ssm", None), "E": (None, "moe"), "*": ("attn", None)}
        return [rows[ch] for ch in cfg.layer_pattern]
    mixer = cfg.family if cfg.family in ("ssm", "hybrid") else "attn"
    ffn = "moe" if cfg.is_moe else "mlp" if cfg.d_ff else None
    return [(mixer, ffn)] * cfg.num_layers


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
        self.plan = layer_plan(cfg)
        self.scanned = len(set(self.plan)) == 1
        # each layer's slot in each stack it uses; the stacks' depths
        taken: collections.Counter = collections.Counter()
        self.slots = []
        for mixer, ffn in self.plan:
            names = STACKS[mixer] + STACKS[ffn]
            self.slots.append({s: taken[s] for s in names})
            taken.update(names)
        self.stacks = dict(taken)
        self.n_cross = len(cfg.cross_attn_layers())

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

    def _init_fuse(self, _key, n_layers: int) -> Params:
        d, dt = self.cfg.d_model, self.dtype
        return {"attn_norm": jnp.zeros((n_layers, d), dt),
                "ssm_norm": jnp.zeros((n_layers, d), dt),
                "beta_attn": jnp.ones((n_layers,), jnp.float32),
                "beta_ssm": jnp.ones((n_layers,), jnp.float32)}

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

        def stacked(init_one):
            return lambda k, n: jax.vmap(init_one)(jax.random.split(k, n))
        # each stack's key is folded in from its place in this order
        inits = {"attn": self._init_attn,
                 "ssm": stacked(lambda k: ssm_mod.init_ssm_params(k, cfg, dt)),
                 "moe": stacked(lambda k: moe_mod.init_moe_params(k, cfg, dt)),
                 "mlp": self._init_mlp, "fuse": self._init_fuse}
        params["blocks"] = {
            name: init(jax.random.fold_in(kB, i), self.stacks[name])
            for i, (name, init) in enumerate(inits.items())
            if self.stacks.get(name)}
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

    # ---------------------------------------------------- mixers and FFNs
    @jax.named_scope("model.attention")
    def _attention(self, p, x, positions, window, theta, kv=None):
        """Pre-norm self attention.  Without ``kv``, over the fresh
        sequence (train/prefill); with the layer's cache slice ``kv``, one
        token at ``positions[0]`` (decode).  Returns (output, {k, v}): the
        fresh keys and values, or the updated cache slice."""
        cfg = self.cfg
        h = rms_norm(x, p["norm_scale"], cfg.norm_eps)
        h = self._constrain(h, "batch", "seq", "embed")
        q, k, v = self._qkv(p, h, positions, theta)
        if kv is None:
            out = self._attend_seq(q, k, v, positions, window)
        else:
            pos = positions[0]
            k = jax.lax.dynamic_update_slice(
                kv["k"], k.astype(kv["k"].dtype), (0, pos, 0, 0))
            v = jax.lax.dynamic_update_slice(
                kv["v"], v.astype(kv["v"].dtype), (0, pos, 0, 0))
            kv_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
            kv_pos = jnp.where(kv_pos <= pos, kv_pos, -1)
            out = attn_mod.attend(
                q, k, v, positions, kv_pos, causal=True, window=window,
                cap=cfg.attn_logit_softcap, scale=self._scale(),
                chunk=self.opt.attn_chunk)
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        if "post_norm_scale" in p:
            y = rms_norm(y, p["post_norm_scale"], cfg.norm_eps)
        return y, {"k": k, "v": v}

    def _ssm(self, p, x, mode, state):
        """The Mamba-2 mixer: (output, conv/SSM state), the state empty in
        train."""
        cfg = self.cfg
        if mode == "decode":
            return ssm_mod.apply_ssm_decode(p, cfg, x, state)
        out = ssm_mod.apply_ssm_mixer(p, cfg, x, use_pallas=self.opt.use_pallas,
                                      return_state=mode == "prefill")
        return out if mode == "prefill" else (out, {})

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

    # ------------------------------------------------------------- layers
    def _layer(self, kind, p, x, window, theta, positions, mode, state):
        """One layer in ``mode`` (train | prefill | decode): ``x +
        mixer(x)``, then ``x + ffn(x)``, each applying its own pre-norm.
        ``p`` holds the layer's slice of each stack it uses and ``state``
        its cache slice in decode.  Returns (x, MoE aux, the layer's new
        cache slice: empty in train).  The walker calls it under
        ``model.block``."""
        mixer, ffn = kind
        new: Params = {}
        if mixer in ("attn", "hybrid"):
            y, kv = self._attention(p["attn"], x, positions, window, theta,
                                    state if mode == "decode" else None)
            if mode != "train":
                new.update(kv)
        if mixer in ("ssm", "hybrid"):
            y_ssm, st = self._ssm(p["ssm"], x, mode, state)
            new.update(st)
            y = (self._hybrid_mix(p["fuse"], y, y_ssm) if mixer == "hybrid"
                 else y_ssm)
        if mixer:
            x = x + y
        aux: Dict[str, jnp.ndarray] = {}
        if ffn == "mlp":
            x = x + self._mlp(p["mlp"], x)
        elif ffn == "moe":
            y, aux = self._moe(p["moe"], x)
            x = x + y
        return x, aux, new

    def _run_layers(self, params, x, positions, mode, cache=None,
                    img_kv=None):
        """Every layer in order, in ``mode``; ``cache`` holds the layers'
        stacked cache in decode.  Returns (x, MoE aux, the layers' new
        cache stacked by name)."""
        cfg = self.cfg
        aux = moe_mod.aux_zeros(cfg) if cfg.is_moe else {}
        remat = mode == "train" and self.opt.remat_policy != "none"

        def ckpt(f):
            if not remat:
                return f
            return jax.checkpoint(
                f, policy=REMAT_POLICIES.get(self.opt.remat_policy),
                prevent_cse=self.opt.remat_prevent_cse)

        def combine(aux, a):
            return moe_mod.combine_aux(aux, a) if a else aux

        def constrain(x):
            return self._constrain(x, "batch", "seq", "embed")

        if not self.scanned:
            parts: Dict[str, list] = collections.defaultdict(list)
            with jax.named_scope("model.layers"):
                for i, (kind, slots) in enumerate(zip(self.plan, self.slots)):
                    p = {s: jax.tree_util.tree_map(
                        lambda a, j=j: a[j], params["blocks"][s])
                        for s, j in slots.items()}
                    state = {n: cache[n][j] for s, j in slots.items()
                             for n in CACHES.get(s, ())} if cache else {}

                    def run(p, x, state, i=i, kind=kind):
                        with jax.named_scope("model.block"):
                            return self._layer(
                                kind, p, x, self.windows[i], self.thetas[i],
                                positions, mode, state)
                    x, a, state = ckpt(run)(p, x, state)
                    aux = combine(aux, a)
                    x = constrain(x)
                    for n, v in state.items():
                        parts[n].append(v)
            return x, aux, {n: jnp.stack(v) for n, v in parts.items()}

        def body(carry, xs):
            x, aux = carry
            p, window, theta, state = xs
            with jax.named_scope("model.block"):
                x, a, state = self._layer(self.plan[0], p, x, window, theta,
                                          positions, mode, state)
            return (constrain(x), combine(aux, a)), state

        xs = (params["blocks"], self.windows, self.thetas, cache or {})
        if not self.n_cross:
            with jax.named_scope("model.layers"):
                (x, aux), states = jax.lax.scan(ckpt(body), (x, aux), xs)
            return x, aux, states
        # VLM: scan groups of ``every`` layers, each followed by its gated
        # cross block.  Under remat both the layer and the group are
        # checkpointed, so the backward of a group recomputes one layer at
        # a time instead of holding the group's intermediates.
        every = cfg.cross_attn_every
        n_groups = cfg.num_layers // every
        inner = ckpt(body)

        def group_body(carry, xs):
            layers, idx = xs
            (x, aux), states = jax.lax.scan(inner, carry, layers)
            x = self._cross_block(params["xblocks"], idx, x, img_kv)
            return (constrain(x), aux), states

        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape((n_groups, every) + a.shape[1:]), xs)
        with jax.named_scope("model.layers"):
            (x, aux), states = jax.lax.scan(
                ckpt(group_body), (x, aux),
                (grouped, jnp.arange(n_groups, dtype=jnp.int32)))
        return x, aux, jax.tree_util.tree_map(
            lambda a: a.reshape((cfg.num_layers,) + a.shape[2:]), states)

    # ------------------------------------------------------- train forward
    def forward(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        """Full-sequence forward (training).  Returns (logits, aux)."""
        x, aux = self.forward_hidden(params, batch)
        with jax.named_scope("model.loss"):
            return self._logits(params, x), aux

    def forward_hidden(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        """Training forward up to (but excluding) the unembedding.
        Used by the chunked cross-entropy path (train/step.py), which
        never materializes the full [B, S, V] logits."""
        x = self.embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        img_kv = self._image_kv(params, batch) if self.n_cross else None
        x, aux, _ = self._run_layers(params, x, positions, "train",
                                     img_kv=img_kv)
        if self.cfg.num_meta_tokens:
            x = x[:, self.cfg.num_meta_tokens:]
        return x, aux

    # ------------------------------------------------------------- serving
    def prefill(self, params, batch, extra_slots: int = 0
                ) -> Tuple[jnp.ndarray, Params]:
        """Process the full prompt.  Returns (last-position logits, cache).
        ``extra_slots`` pre-allocates room for subsequent decode steps."""
        x = self.embed_inputs(params, batch)
        total = x.shape[1]
        img_kv = self._image_kv(params, batch) if self.n_cross else None
        x, _, cache = self._run_layers(
            params, x, jnp.arange(total, dtype=jnp.int32), "prefill",
            img_kv=img_kv)
        if extra_slots and "k" in cache:
            pad = ((0, 0), (0, 0), (0, extra_slots), (0, 0), (0, 0))
            cache["k"], cache["v"] = (jnp.pad(cache[n], pad) for n in "kv")
        cache["pos"] = jnp.asarray(total, jnp.int32)
        if self.n_cross:
            cache["xk"], cache["xv"] = img_kv
        return self._logits(params, x[:, -1:]), cache

    def init_cache(self, batch_size: int, max_len: int) -> Params:
        """Allocate an empty decode cache (for cost analysis / cold decode).
        ``max_len`` includes room for tokens to be decoded; meta tokens are
        added on top."""
        cfg, dt = self.cfg, self.dtype
        cache: Params = {"pos": jnp.zeros((), jnp.int32)}
        if self.stacks.get("attn"):
            kvshape = (self.stacks["attn"], batch_size,
                       max_len + cfg.num_meta_tokens, cfg.num_kv_heads,
                       cfg.resolved_head_dim)
            cache["k"] = jnp.zeros(kvshape, dt)
            cache["v"] = jnp.zeros(kvshape, dt)
        if self.stacks.get("ssm"):
            for n, a in ssm_mod.init_ssm_state(cfg, batch_size, dt).items():
                cache[n] = jnp.zeros((self.stacks["ssm"],) + a.shape, a.dtype)
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
        xkv = (cache["xk"], cache["xv"]) if self.n_cross else None
        x, _, new_cache = self._run_layers(
            params, x, pos[None], "decode",
            {n: cache[n] for n in ("k", "v", "ssm", "conv") if n in cache},
            xkv)
        new_cache["pos"] = pos + 1
        if self.n_cross:
            new_cache["xk"], new_cache["xv"] = xkv
        return self._logits(params, x), new_cache
