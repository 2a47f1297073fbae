"""Plain float32 reference of the training step that the
`nemotron-3-nano-30b-a3b-7l` cell runs, written from its configuration file
alone.

The model (NVIDIA Nemotron 3 Nano, Nemotron-H layers), in straightforward
``jax.numpy`` at ``Precision.HIGHEST``:

* embedding lookup over the vocabulary slice;
* each layer in the order of ``layer_pattern`` is ``x + mixer(rms(x))``
  with a ``(1 + scale)`` RMSNorm gain:
  - ``M``, Mamba-2: one input projection to ``z, x, B, C, dt`` with
    ``ssm_groups`` groups of B and C, a causal depthwise convolution with a
    bias over ``x, B, C``, SiLU, ``dt = softplus(dt + dt_bias)``, the state
    space written plainly per group (heads ``g*H/G ..`` read group ``g``'s
    B and C), ``y = C h + d_skip x``, ``rms(y * silu(z))`` over each
    group's channels, the output projection;
  - ``E``: router logits ``rms(x) W_r`` over all ``num_experts``; experts
    picked by the top ``k`` of ``sigmoid(logits) + router_bias``, the held
    experts' scores offset per sequence so that exactly their balanced
    share of its slots falls to them (``Reference.pinned``), weighted
    by the sigmoid scores, renormalised over the ``k`` and scaled by
    ``moe_routed_scale``; every held expert (``0 .. moe_experts_held``)
    runs ``down(relu(up x)^2)`` on every token and is weighted by its
    gate, which is 0 where the token did not route to it, so no slot can
    be dropped; plus the shared expert on every token;
  - ``*``: grouped-query attention, causal, with no positional embedding;
* the loss is the mean token cross-entropy of the untied unembedding;
* AdamW as ``hybrid_lm_ref.py`` has it.

It is computed in blocks so that one chip holds it: one row of the batch
at a time, the gradient summed over the rows; each layer under
``jax.checkpoint``; the state space in chunks of 128 carried by a scan;
attention by blocks of queries; the cross-entropy in blocks of positions.

``low=True`` is the control, as in ``hybrid_lm_ref.py``: the tensors the
program holds in bf16 rounded to fp8 (e4m3, gradients e5m2).

The step loop and AdamW are ``hybrid_lm_ref.py``'s own: ``run_steps``
runs that module's ``run_steps`` from a private copy of it whose
``Reference`` is this module's.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Sequence

import jax
import jax.numpy as jnp


def _load_base():
    path = Path(__file__).with_name("hybrid_lm_ref.py")
    spec = importlib.util.spec_from_file_location("nemotron_h_ref_base", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_base = _load_base()
F32, rms, _block = _base.F32, _base.rms, _base._block
ATTN_BLOCK, CE_BLOCK = _base.ATTN_BLOCK, _base.CE_BLOCK


def relu2(x):
    return jnp.square(jax.nn.relu(x))


class Reference(_base.Reference):
    """Loss of configuration ``c`` on float32 parameters in the program's
    layout (see ``harness.layout``). The state space, the matmul helper
    and the cross-entropy are ``hybrid_lm_ref.Reference``'s; the layers,
    the trunk and the loss over rows are this model's."""

    def __init__(self, c: dict, low: bool = False):
        if c["family"] != "interleaved":
            raise ValueError(f"no reference for family {c['family']!r}")
        self.c = c
        self.low = low

    # ------------------------------------------------------------- mixer
    def mixer(self, q, u):
        c = self.c
        nh, hp, n, g = (c["ssm_num_heads"], c["ssm_headdim"], c["ssm_state"],
                        c["ssm_groups"])
        di, gn, hg = nh * hp, g * n, nh // g
        b, s, _ = u.shape
        proj = self.mm("bsd,de->bse", rms(u, q["norm_scale"], c["norm_eps"]),
                       q["in_proj"])
        z, xbc, dt = (proj[..., :di], proj[..., di:2 * di + 2 * gn],
                      proj[..., 2 * di + 2 * gn:])
        w = q["conv_w"]
        width = w.shape[0]
        xp = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
        xbc = sum(xp[:, i:i + s] * w[i] for i in range(width)) + q["conv_b"]
        xbc = self.q(jax.nn.silu(xbc))
        xs = xbc[..., :di].reshape(b, s, nh, hp)
        bm = xbc[..., di:di + gn].reshape(b, s, g, n)
        cm = xbc[..., di + gn:].reshape(b, s, g, n)
        dt = jax.nn.softplus(dt + q["dt_bias"])
        a = -jnp.exp(q["a_log"])
        y = jnp.concatenate(
            [self.ssd(xs[:, :, i * hg:(i + 1) * hg], dt[..., i * hg:(i + 1) * hg],
                      a[i * hg:(i + 1) * hg], bm[:, :, i], cm[:, :, i])
             for i in range(g)], axis=2)
        y = self.q(y)
        y = y + q["d_skip"][:, None] * xs                        # [b,s,h,p]
        gated = (y.reshape(b, s, di) * jax.nn.silu(z)).reshape(b, s, g,
                                                                  di // g)
        y = rms(gated, q["gate_norm_scale"].reshape(g, di // g),
                c["norm_eps"]).reshape(b, s, di)
        return self.mm("bse,ed->bsd", self.q(y), q["out_proj"])

    # ----------------------------------------------------------- experts
    def experts(self, m, x):
        c = self.c
        k, held = c["experts_per_token"], c["moe_experts_held"]
        h = rms(x, m["norm_scale"], c["norm_eps"])
        logits = jnp.einsum("bsd,de->bse", self.q(h), m["router"],
                            precision=_base.HI)
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(self.pinned(scores + m["router_bias"]), k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / jnp.sum(w, -1, keepdims=True) * c["moe_routed_scale"]
        out = self.mm("bsf,fd->bsd",
                      relu2(self.mm("bsd,df->bsf", h, m["shared_up"])),
                      m["shared_down"])
        for e in range(held):
            gate = jnp.sum(jnp.where(idx == e, w, 0.0), -1)      # [b,s]
            y = self.mm("bsf,fd->bsd",
                        relu2(self.mm("bsd,df->bsf", h, m["w_up"][e])),
                        m["w_down"][e])
            out = out + gate[..., None] * y
        return out

    def pinned(self, pick):
        """The selection scores ``pick [b, s, E]``; where fewer experts are
        held than routed to, with one offset per sequence on the held
        experts' scores: the offset at which exactly their balanced share
        of the sequence's slots, ``k s held / E``, falls to them (halfway
        between the gaps that admit that many slots and one more)."""
        c = self.c
        k, held, e = (c["experts_per_token"], c["moe_experts_held"],
                      c["num_experts"])
        if held >= e:
            return pick
        b, s, _ = pick.shape
        m = min(k, held)
        hv = jax.lax.top_k(pick[..., :held], m)[0]           # best first
        ov = jax.lax.top_k(pick[..., held:], k)[0]
        # the a-th best held expert enters a token's top k once the
        # offset passes the (k+1-a)-th best other expert
        gaps = jnp.sort(jnp.stack([ov[..., k - a] - hv[..., a - 1]
                                   for a in range(1, m + 1)], -1)
                        .reshape(b, -1), -1)
        share = k * s * held // e
        offset = (gaps[:, share - 1] + gaps[:, share]) / 2
        return pick + jnp.where(jnp.arange(e) < held,
                                offset[:, None, None], 0.0)

    # --------------------------------------------------------- attention
    def attention(self, a, x):
        c = self.c
        h = rms(x, a["norm_scale"], c["norm_eps"])
        q = self.mm("bsd,dhk->bshk", h, a["wq"])
        k = self.mm("bsd,dhk->bshk", h, a["wk"])
        v = self.mm("bsd,dhk->bshk", h, a["wv"])
        rep = c["num_heads"] // c["num_kv_heads"]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        b, t, hq, d = q.shape
        qb = _block(t, ATTN_BLOCK)
        pos = jnp.arange(t)

        def block(_, xs):
            qc, qp = xs
            s = self.mm("bqhd,bkhd->bhqk", qc, k) * d ** -0.5
            s = jnp.where(pos[None, :] <= qp[:, None], s, -jnp.inf)
            return None, self.mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

        _, out = jax.lax.scan(
            jax.checkpoint(block), None,
            (q.reshape(b, t // qb, qb, hq, d).swapaxes(0, 1),
             pos.reshape(t // qb, qb)))
        out = out.swapaxes(0, 1).reshape(b, t, hq, d)
        return self.mm("bshk,hkd->bsd", out, a["wo"])

    # -------------------------------------------------------------- model
    def trunk(self, p, tokens):
        x = self.q(p["embed"]["table"][tokens])
        blocks, taken = p["blocks"], {}
        kinds = {"M": ("ssm", self.mixer), "E": ("moe", self.experts),
                 "*": ("attn", self.attention)}
        for ch in self.c["layer_pattern"]:
            name, fn = kinds[ch]
            j = taken[name] = taken.get(name, -1) + 1
            lp = jax.tree_util.tree_map(lambda t: t[j], blocks[name])
            x = jax.checkpoint(lambda lp, x, fn=fn: self.q(x + fn(lp, x)))(
                lp, x)
        return x

    def loss(self, p, tokens, labels, mask):
        """The batch's mean token loss, one row at a time."""
        row_loss = super().loss

        def row(total, xs):
            t, l, m = (a[None] for a in xs)
            return total + row_loss(p, t, l, m) * jnp.sum(m), None

        total, _ = jax.lax.scan(jax.checkpoint(row), jnp.float32(0.0),
                                (tokens, labels, mask))
        return total / jnp.maximum(jnp.sum(mask), 1.0)


def run_steps(c: dict, t: dict, seed: int, batches: Sequence[dict],
              low: bool = False) -> dict:
    """``hybrid_lm_ref.run_steps`` with this module's ``Reference``: the
    first ``len(batches)`` AdamW steps from the seed's weights."""
    _base.Reference = Reference
    return _base.run_steps(c, t, seed, batches, low)
