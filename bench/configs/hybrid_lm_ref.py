"""Plain float32 reference of the training step that the `mamba2-780m` and
`hymba-1.5b` cells run, written from their configuration files alone.

The model is the one the program trains (``models/transformer.py`` with
``family`` ``ssm`` or ``hybrid``), written out in straightforward
``jax.numpy`` at ``Precision.HIGHEST``:

* embedding lookup; Hymba prepends ``num_meta_tokens`` learned rows;
* each layer, pre-norm RMSNorm with a ``(1 + scale)`` gain, then
  - ``ssm``: ``x + mixer(x)``;
  - ``hybrid``: ``x + (rms(attn(x)) * beta_a + rms(mixer(x)) * beta_s) / 2``,
    then ``x + mlp(x)`` (SwiGLU);
* the mixer is Mamba-2: one input projection to ``z, x, B, C, dt``, a
  causal depthwise convolution of width ``conv_width`` over ``x, B, C``,
  SiLU, ``dt = softplus(dt + dt_bias)``, the selective state space
  ``h_t = exp(-exp(a_log) dt_t) h_{t-1} + dt_t x_t B_t^T``,
  ``y_t = C_t h_t + d_skip x_t``, a SiLU(z) gate, RMSNorm and the output
  projection;
* attention is grouped-query with rotary embeddings over all positions
  (meta tokens included), causal, with a sliding window except at the
  anchor layers (first, middle, last);
* the loss is the mean token cross-entropy of the tied (``mamba2``) or
  separate (``hymba``) unembedding;
* AdamW with the launcher's warm-up, global-norm clipping and decoupled
  weight decay on every leaf of rank two or more as stored (the stacked
  per-layer norm scales and SSM vectors included, as the program does).

Independence from the program: the state space is computed in chunks of
128 (the program uses 256) by a scan that carries the state, attention in
blocks of queries, the cross-entropy in blocks of positions. Parameters
are stored in the configuration's dtype between steps, as the
configuration states (and returned from the update in that dtype, so the
rounding cannot be elided), and every computation is float32. Adam's moments
live in host memory between steps so that the reference fits one chip
with the program's state freed.

``low=True`` is the control: the same step computed one precision below
the configuration's bf16, that is in fp8, at the points where the program
holds bf16 tensors: the inputs of every matrix product, the projections'
outputs, the convolution's and the state space's outputs, the gated
output, the embedding and the residual stream after each layer are
rounded to e4m3 with a per-tensor scale, and their gradients to e5m2.
Accumulation and elementwise arithmetic stay float32.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness import layout

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
SSD_CHUNK = 128
ATTN_BLOCK = 128
CE_BLOCK = 256


def _qdq(x, dtype, top):
    """Round ``x`` to ``dtype`` with one scale that maps its largest
    magnitude to ``top``. The scale falls back to 1 where it is zero (also
    where ``amax / top`` flushes to zero), and the quotient is held to
    ``top``, which a rounded division can pass by an ulp: ``e4m3fn`` has no
    infinity, and turns any value past its range into NaN."""
    s = jnp.max(jnp.abs(x)) / top
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(x / s, -top, top).astype(dtype).astype(F32) * s


@jax.custom_vjp
def fp8(x):
    return _qdq(x, jnp.float8_e4m3fn, 448.0)


fp8.defvjp(lambda x: (fp8(x), None),
           lambda _, g: (_qdq(g, jnp.float8_e5m2, 57344.0),))


def _block(n: int, want: int) -> int:
    b = want
    while n % b:
        b //= 2
    return max(b, 1)


def _group(n: int) -> int:
    """Layers per checkpointed group: a divisor of n near sqrt(n)."""
    best = 1
    for g in range(1, n + 1):
        if n % g == 0 and abs(g - math.sqrt(n)) < abs(best - math.sqrt(n)):
            best = g
    return best


def rms(x, scale, eps):
    x = x.astype(F32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (1.0 + scale.astype(F32)))


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-(jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * inv                       # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


class Reference:
    """Loss of configuration ``c`` on float32 parameters in the program's
    layout (see ``harness.layout``)."""

    def __init__(self, c: dict, low: bool = False):
        if c["family"] not in ("ssm", "hybrid"):
            raise ValueError(f"no reference for family {c['family']!r}")
        self.c = c
        self.low = low
        n = c["num_layers"]
        if c["num_heads"]:
            anchors = {0, n // 2, n - 1}
            self.windows = jnp.array(
                [(1 << 30) if i in anchors else c["window_size"]
                 for i in range(n)], jnp.int32)
        else:
            self.windows = jnp.zeros((n,), jnp.int32)

    def q(self, x):
        """A tensor the program holds in its model dtype."""
        return fp8(x) if self.low else x

    def mm(self, eq, *ops):
        out = jnp.einsum(eq, *(self.q(o) for o in ops), precision=HI,
                         preferred_element_type=F32)
        return self.q(out)

    # ------------------------------------------------------------- mixer
    def ssd(self, x, dt, a, bm, cm):
        """x [B,S,H,P], dt [B,S,H], a [H] (negative), bm/cm [B,S,N]."""
        b, s, h, p = x.shape
        n = bm.shape[-1]
        q = SSD_CHUNK
        pad = (-s) % q
        if pad:  # dt = 0 neither decays the state nor adds to it
            x, dt, bm, cm = (jnp.pad(t, ((0, 0), (0, pad))
                                     + ((0, 0),) * (t.ndim - 2))
                             for t in (x, dt, bm, cm))
        nc = (s + pad) // q

        def chunks(t):
            return t.reshape((b, nc, q) + t.shape[2:]).swapaxes(0, 1)

        idx = jnp.arange(q)
        causal = (idx[:, None] >= idx[None, :])[None, :, :, None]

        def body(state, xs):
            xc, dtc, bc, cc = xs
            cs = jnp.cumsum(dtc * a, axis=1)                     # [b,q,h]
            seg = cs[:, :, None, :] - cs[:, None, :, :]          # [b,t,s,h]
            decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)),
                              0.0)
            xdt = xc * dtc[..., None]
            g = self.mm("btn,bsn->bts", cc, bc)
            y = self.mm("btsh,bshp->bthp", g[..., None] * decay, xdt)
            y = y + self.mm("btn,bhpn->bthp", cc, state) \
                * jnp.exp(cs)[..., None]
            last = cs[:, -1]                                     # [b,h]
            w = jnp.exp(last[:, None, :] - cs)
            state = (state * jnp.exp(last)[:, :, None, None]
                     + self.mm("bshp,bsn->bhpn", xdt * w[..., None], bc))
            return state, y

        state0 = jnp.zeros((b, h, p, n), F32)
        _, ys = jax.lax.scan(jax.checkpoint(body), state0,
                             tuple(chunks(t) for t in (x, dt, bm, cm)))
        return ys.swapaxes(0, 1).reshape(b, nc * q, h, p)[:, :s]

    def mixer(self, q, u):
        c = self.c
        di = c["ssm_expand"] * c["d_model"]
        nh, n = di // c["ssm_headdim"], c["ssm_state"]
        b, s, _ = u.shape
        proj = self.mm("bsd,de->bse", rms(u, q["norm_scale"], c["norm_eps"]),
                       q["in_proj"])
        z, xbc, dt = (proj[..., :di], proj[..., di:2 * di + 2 * n],
                      proj[..., 2 * di + 2 * n:])
        w = q["conv_w"]
        width = w.shape[0]
        xp = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
        xbc = sum(xp[:, i:i + s] * w[i] for i in range(width))
        xbc = self.q(jax.nn.silu(xbc))
        xs, bm, cm = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
        dt = jax.nn.softplus(dt + q["dt_bias"])
        xh = xs.reshape(b, s, nh, c["ssm_headdim"])
        y = self.q(self.ssd(xh, dt, -jnp.exp(q["a_log"]), bm, cm))
        y = (y + q["d_skip"][:, None] * xh).reshape(b, s, di)
        y = self.q(rms(y * jax.nn.silu(z), q["gate_norm_scale"],
                       c["norm_eps"]))
        return self.mm("bse,ed->bsd", y, q["out_proj"])

    # --------------------------------------------------------- attention
    def attention(self, a, x, pos, window):
        c = self.c
        h = rms(x, a["norm_scale"], c["norm_eps"])
        q = rope(self.mm("bsd,dhk->bshk", h, a["wq"]), pos, c["rope_theta"])
        k = rope(self.mm("bsd,dhk->bshk", h, a["wk"]), pos, c["rope_theta"])
        v = self.mm("bsd,dhk->bshk", h, a["wv"])
        g = c["num_heads"] // c["num_kv_heads"]
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        b, t, hq, d = q.shape
        qb = _block(t, ATTN_BLOCK)
        scale = d ** -0.5

        def block(_, xs):
            qc, qp = xs
            s = self.mm("bqhd,bkhd->bhqk", qc, k) * scale
            ok = (pos[None, :] <= qp[:, None]) & (qp[:, None] - pos[None, :]
                                                  < window)
            p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
            return None, self.mm("bhqk,bkhd->bqhd", p, v)

        _, out = jax.lax.scan(
            jax.checkpoint(block), None,
            (q.reshape(b, t // qb, qb, hq, d).swapaxes(0, 1),
             pos.reshape(t // qb, qb)))
        out = out.swapaxes(0, 1).reshape(b, t, hq, d)
        return self.mm("bshk,hkd->bsd", out, a["wo"])

    def mlp(self, m, x):
        h = rms(x, m["norm_scale"], self.c["norm_eps"])
        g = jax.nn.silu(self.mm("bsd,df->bsf", h, m["w_gate"]))
        u = self.mm("bsd,df->bsf", h, m["w_up"])
        return self.mm("bsf,fd->bsd", g * u, m["w_down"])

    # -------------------------------------------------------------- model
    def layer(self, bp, x, pos, window):
        c = self.c
        if c["family"] == "ssm":
            return x + self.mixer(bp["ssm"], x)
        f, eps = bp["fuse"], c["norm_eps"]
        att = self.attention(bp["attn"], x, pos, window)
        ssm = self.mixer(bp["ssm"], x)
        x = x + 0.5 * (rms(att, f["attn_norm"], eps) * f["beta_attn"]
                       + rms(ssm, f["ssm_norm"], eps) * f["beta_ssm"])
        return x + self.mlp(bp["mlp"], x)

    def trunk(self, p, tokens):
        c = self.c
        x = self.q(p["embed"]["table"][tokens])
        m = c["num_meta_tokens"]
        if m:
            meta = jnp.broadcast_to(p["meta_tokens"][None],
                                    (x.shape[0],) + p["meta_tokens"].shape)
            x = jnp.concatenate([meta, x], axis=1)
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)
        n = c["num_layers"]
        g = _group(n)

        def one(x, xs):
            bp, window = xs
            return self.q(self.layer(bp, x, pos, window)), None

        def group(x, xs):
            x, _ = jax.lax.scan(jax.checkpoint(one), x, xs)
            return x, None

        grouped = jax.tree_util.tree_map(
            lambda t: t.reshape((n // g, g) + t.shape[1:]),
            (p["blocks"], self.windows))
        x, _ = jax.lax.scan(jax.checkpoint(group), x, grouped)
        return x[:, m:]

    def loss(self, p, tokens, labels, mask):
        c = self.c
        y = self.q(rms(self.trunk(p, tokens), p["final_norm_scale"],
                       c["norm_eps"]))
        if c["tie_embeddings"]:
            w, eq = p["embed"]["table"], "bcd,vd->bcv"
        else:
            w, eq = p["lm_head"], "bcd,dv->bcv"
        b, s, d = y.shape
        cb = _block(s, CE_BLOCK)

        def block(total, xs):
            yc, lc, mc = xs
            logits = self.mm(eq, yc, w)
            gold = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
            nll = jax.nn.logsumexp(logits, -1) - gold
            return total + jnp.sum(nll * mc), None

        def chunks(t):
            return t.reshape((b, s // cb, cb) + t.shape[2:]).swapaxes(0, 1)

        total, _ = jax.lax.scan(jax.checkpoint(block), jnp.float32(0.0),
                                (chunks(y), chunks(labels), chunks(mask)))
        return total / jnp.maximum(jnp.sum(mask), 1.0)


# ------------------------------------------------------------- optimizer

def lr_at(t: dict, count: int) -> float:
    """The launcher's warm-up then cosine schedule at update ``count``."""
    if count < t["warmup_steps"]:
        return t["lr"] * count / max(t["warmup_steps"], 1)
    frac = min(max((count - t["warmup_steps"])
                   / max(t["total_steps"] - t["warmup_steps"], 1), 0.0), 1.0)
    return t["lr"] * (t["min_lr_frac"] + (1 - t["min_lr_frac"]) * 0.5
                      * (1 + math.cos(math.pi * frac)))


def _adam_leaf(g, m, v, p, scale, lr, count, *, t, decay):
    """One leaf's AdamW update; ``p`` comes and goes in its stored dtype,
    so the rounding to it is real (a float32 round trip through bf16
    inside one program may be elided as excess precision)."""
    dtype = p.dtype
    p = p.astype(F32)
    g = g * scale
    m = t["b1"] * m + (1 - t["b1"]) * g
    v = t["b2"] * v + (1 - t["b2"]) * g * g
    mh = m / (1 - t["b1"] ** count)
    vh = v / (1 - t["b2"] ** count)
    step = mh / (jnp.sqrt(vh) + t["eps"])
    if decay:
        step = step + t["weight_decay"] * p
    return (p - lr * step).astype(dtype), m, v


_sums = jax.jit(lambda t: [jnp.sum(jnp.square(x.astype(F32)))
                           for x in jax.tree_util.tree_leaves(t)])


def sq_norms(tree) -> Dict[str, float]:
    """Per-leaf squared norms, in float32 on the device, read to the host."""
    return dict(zip(layout.leaf_names(tree),
                    (float(s) for s in _sums(tree))))


def run_steps(c: dict, t: dict, seed: int, batches: Sequence[dict],
              low: bool = False) -> dict:
    """The first ``len(batches)`` AdamW steps from the seed's weights.

    Returns each step's loss, the per-leaf norm of the first gradient as
    the optimizer applies it (clipped) and its elements at
    ``layout.sample_positions``, and the per-leaf norm of the parameters'
    change after the last step."""
    ref = Reference(c, low)
    p = layout.make_params(c, seed)       # stored in the configured dtypes
    names = layout.leaf_names(p)
    value_grad = jax.jit(lambda ps, *batch: jax.value_and_grad(ref.loss)(
        jax.tree_util.tree_map(lambda x: x.astype(F32), ps), *batch))
    adam = {}
    moments: Optional[list] = None
    losses, first_grad, first_sample = [], {}, {}
    for count, batch in enumerate(batches, 1):
        loss, grads = value_grad(p, batch["tokens"], batch["labels"],
                                 batch["loss_mask"])
        sq = sq_norms(grads)
        gnorm = math.sqrt(sum(sq.values()))
        scale = min(1.0, t["clip_norm"] / max(gnorm, 1e-9))
        if count == 1:
            first_grad = {k: math.sqrt(v) * scale for k, v in sq.items()}
            first_sample = {k: v * scale for k, v in
                            layout.sample_leaves(grads, seed).items()}
        lr = lr_at(t, count)
        flat_p, tree = jax.tree_util.tree_flatten(p)
        flat_g = jax.tree_util.tree_leaves(grads)
        del p, grads
        new_p, new_m = [], []
        for i, (g, x) in enumerate(zip(flat_g, flat_p)):
            key = (x.ndim >= 2, x.shape, str(x.dtype))
            if key not in adam:
                adam[key] = jax.jit(
                    lambda *a, _d=key[0]: _adam_leaf(*a, t=t, decay=_d),
                    donate_argnums=(0, 1, 2, 3))
            if moments is None:
                m = jnp.zeros(x.shape, F32)
                v = jnp.zeros(x.shape, F32)
            else:
                m, v = (jax.device_put(a) for a in moments[i])
            flat_g[i] = flat_p[i] = None
            x, m, v = adam[key](g, m, v, x, scale, lr, float(count))
            new_p.append(x)
            if count < len(batches):
                new_m.append((np.asarray(m), np.asarray(v)))
            del g, m, v
        moments = new_m
        p = jax.tree_util.tree_unflatten(tree, new_p)
        del new_p
        losses.append(float(loss))
    p0 = layout.make_params(c, seed)
    diff = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: x.astype(F32) - y.astype(F32), a, b))(p, p0)
    del p, p0
    change = {k: math.sqrt(v) for k, v in sq_norms(diff).items()}
    assert list(change) == names
    return {"losses": losses, "grad": first_grad, "grad_sample": first_sample,
            "change": change}
