"""Mamba-2 SSD (state-space duality) blocks — attention-free sequence mixing.

Implements the chunked SSD algorithm (Dao & Gu, arXiv:2405.21060): the
sequence is split into chunks; within a chunk the computation is a masked
matmul ("attention-like", MXU-friendly), across chunks a tiny recurrence
carries the [H, P, N] state.  This TPU-native formulation is exactly why
SSD exists — the quadratic-in-chunk part maps onto the systolic array, and
the recurrence is O(S/Q) sequential steps on small tensors.

Decode is the classic O(1) recurrent update.  The intra-chunk matmuls are
also available as a Pallas kernel (repro.kernels.ssd_scan).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, rms_norm, trunc_normal


# ----------------------------------------------------------------- SSD core

def chunk_cumsum(a: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumulative sum over the last axis, as one matmul against a
    [Q, Q] lower-triangular ones matrix.  a: [..., Q] f32 -> [..., Q] f32.

    On TPU ``jnp.cumsum`` lowers to a windowed reduction, about Q adds an
    output on the vector unit; this runs on the MXU, and its backward is
    the transposed matmul.  HIGHEST keeps every bit of the f32 log-decays
    (the default precision rounds them to bf16; the ones are exact)."""
    q = a.shape[-1]
    tri = jnp.tril(jnp.ones((q, q), jnp.float32))
    return jnp.einsum("...k,ik->...i", a, tri,
                      precision=jax.lax.Precision.HIGHEST)


def segsum(cs: jnp.ndarray) -> jnp.ndarray:
    """Segment-sum from a chunk's cumulative sum ``cs`` of ``a``:
    out[..., i, j] = cs[i] - cs[j] = sum_{k=j+1..i} a[..., k] (i>=j),
    -inf elsewhere.  cs: [..., Q] -> [..., Q, Q]."""
    q = cs.shape[-1]
    ss = cs[..., :, None] - cs[..., None, :]
    idx = jnp.arange(q)
    mask = idx[:, None] >= idx[None, :]
    return jnp.where(mask, ss, -jnp.inf)


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, a_log: jnp.ndarray,
                b_mat: jnp.ndarray, c_mat: jnp.ndarray, chunk: int,
                h0: Optional[jnp.ndarray] = None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan.

    x: [B,S,H,P] inputs; dt: [B,S,H] (post-softplus); a_log: [H];
    b_mat/c_mat: [B,S,N] (one group, broadcast over heads) or [B,S,G,N]
    (G groups, each shared by H/G consecutive heads);
    h0: optional initial state [B,H,P,N].
    Returns (y [B,S,H,P], h_final [B,H,P,N]).
    """
    if b_mat.ndim == 4:
        return _ssd_grouped(x, dt, a_log, b_mat, c_mat, chunk, h0)
    bsz, s_orig, h, p = x.shape
    n = b_mat.shape[-1]
    pad = (-s_orig) % chunk
    if pad:
        # dt=0 padding is exact: decay exp(0)=1, contribution x*dt=0.
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
    s = s_orig + pad
    nc = s // chunk
    a = (-jnp.exp(a_log.astype(jnp.float32)))[None, None, :] * dt.astype(
        jnp.float32)                                   # [B,S,H] log-decay
    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])

    # chunked views
    def chunked(t, trailing):
        return t.reshape((bsz, nc, chunk) + trailing)
    ac = a.reshape(bsz, nc, chunk, h).transpose(0, 3, 1, 2)   # [B,H,C,Q]
    xc = chunked(xdt, (h, p))                                  # [B,C,Q,H,P]
    bc = chunked(b_mat.astype(jnp.float32), (n,))              # [B,C,Q,N]
    cc = chunked(c_mat.astype(jnp.float32), (n,))              # [B,C,Q,N]

    a_cs = chunk_cumsum(ac)                                    # [B,H,C,Q]

    # 1. intra-chunk ("diagonal block") — quadratic in Q, matmul-shaped
    l_mat = jnp.exp(segsum(a_cs))                              # [B,H,C,Q,Q]
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp",
                        cc, bc, l_mat, xc)

    # 2. per-chunk states
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)              # [B,H,C,Q]
    states = jnp.einsum("bcsn,bhcs,bcshp->bchpn", bc, decay_states, xc)

    # 3. inter-chunk recurrence (tiny sequential scan over chunks)
    chunk_decay = jnp.exp(a_cs[..., -1])                       # [B,H,C]
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    else:
        h0 = h0.astype(jnp.float32)

    def step(carry, xs):
        st, dec = xs                                           # [B,H,P,N],[B,H]
        new = carry * dec[..., None, None] + st
        return new, carry                                      # emit state ENTERING chunk

    (h_final, prev_states) = jax.lax.scan(
        step, h0,
        (states.transpose(1, 0, 2, 3, 4),                      # [C,B,H,P,N]
         chunk_decay.transpose(2, 0, 1)))                      # [C,B,H]
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)         # [B,C,H,P,N]

    # 4. inter-chunk output
    state_decay_out = jnp.exp(a_cs)                            # [B,H,C,Q]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp",
                       cc, prev_states, state_decay_out)

    y = (y_diag + y_off).reshape(bsz, s, h, p)[:, :s_orig]
    return y.astype(x.dtype), h_final


def _ssd_grouped(x, dt, a_log, b_mat, c_mat, chunk, h0):
    """``ssd_chunked`` once per B/C group, over that group's heads."""
    bsz, s, h, p = x.shape
    g = b_mat.shape[2]
    hg = h // g
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, b_mat.shape[-1]), jnp.float32)
    y, h_final = jax.vmap(
        lambda x, dt, a, bm, cm, h0: ssd_chunked(x, dt, a, bm, cm, chunk, h0),
        in_axes=(2, 2, 0, 2, 2, 1), out_axes=(2, 1))(
        x.reshape(bsz, s, g, hg, p), dt.reshape(bsz, s, g, hg),
        a_log.reshape(g, hg), b_mat, c_mat,
        h0.reshape((bsz, g, hg) + h0.shape[2:]))
    return (y.reshape(bsz, s, h, p),
            h_final.reshape((bsz, h) + h_final.shape[3:]))


def ssd_decode_step(h: jnp.ndarray, x: jnp.ndarray, dt: jnp.ndarray,
                    a_log: jnp.ndarray, b_mat: jnp.ndarray,
                    c_mat: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One recurrent step.  h: [B,H,P,N]; x: [B,H,P]; dt: [B,H];
    b_mat/c_mat: [B,N], or [B,G,N] with G groups of H/G heads.
    Returns (y [B,H,P], h')."""
    eq_in, eq_out = "bhp,bn->bhpn", "bhpn,bn->bhp"
    if b_mat.ndim == 3:
        hg = x.shape[1] // b_mat.shape[1]
        b_mat, c_mat = (jnp.repeat(t, hg, axis=1) for t in (b_mat, c_mat))
        eq_in, eq_out = "bhp,bhn->bhpn", "bhpn,bhn->bhp"
    h = h.astype(jnp.float32)
    dec = jnp.exp(-jnp.exp(a_log.astype(jnp.float32))[None, :]
                  * dt.astype(jnp.float32))                    # [B,H]
    xdt = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]
    upd = jnp.einsum(eq_in, xdt, b_mat.astype(jnp.float32))
    h_new = h * dec[..., None, None] + upd
    y = jnp.einsum(eq_out, h_new, c_mat.astype(jnp.float32))
    return y.astype(x.dtype), h_new


# ------------------------------------------------------------------ conv1d

def conv1d_causal(x: jnp.ndarray, w: jnp.ndarray,
                  hist: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Depthwise causal conv.  x: [B,S,C]; w: [W,C]; hist: [B,W-1,C]
    (carried decode/prefill state; zeros when None)."""
    width = w.shape[0]
    if hist is None:
        hist = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([hist, x], axis=1)
    out = jnp.zeros_like(x)
    for i in range(width):
        out = out + xp[:, i:i + x.shape[1]] * w[i][None, None, :]
    return out


def conv1d_step(x: jnp.ndarray, w: jnp.ndarray, hist: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decode step.  x: [B,C]; hist: [B,W-1,C]."""
    width = w.shape[0]
    xp = jnp.concatenate([hist, x[:, None, :]], axis=1)        # [B,W,C]
    y = jnp.einsum("bwc,wc->bc", xp, w)
    return y, xp[:, 1:]


# ------------------------------------------------------------- mamba2 block

def init_ssm_params(key, cfg, dtype) -> Dict[str, jnp.ndarray]:
    """Parameters for one Mamba-2 mixer (pre-norm included)."""
    d, di = cfg.d_model, cfg.d_inner
    nh = cfg.ssm_heads
    conv_ch = cfg.ssm_conv_dim
    proj_out = di + conv_ch + nh     # z, xBC, dt
    k1, k2, k3, k4 = jax.random.split(key, 4)
    extra = {"conv_b": jnp.zeros((conv_ch,), dtype)} if cfg.conv_bias else {}
    return {
        **extra,
        "norm_scale": jnp.zeros((d,), dtype),
        "in_proj": dense_init(k1, (d, proj_out), dtype),
        "conv_w": trunc_normal(k2, (cfg.conv_width, conv_ch),
                               1.0 / math.sqrt(cfg.conv_width), dtype),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "gate_norm_scale": jnp.zeros((di,), dtype),
        "out_proj": dense_init(k3, (di, d), dtype),
    }


def _split_proj(cfg, proj):
    di, conv = cfg.d_inner, cfg.ssm_conv_dim
    z = proj[..., :di]
    x_bc = proj[..., di:di + conv]
    dt = proj[..., di + conv:]
    return z, x_bc, dt


def _split_xbc(cfg, x_bc):
    """x, B, C; with several groups B and C are [..., G, N]."""
    di, gn = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
    x, b_mat, c_mat = (x_bc[..., :di], x_bc[..., di:di + gn],
                       x_bc[..., di + gn:])
    if cfg.ssm_groups > 1:
        shape = b_mat.shape[:-1] + (cfg.ssm_groups, cfg.ssm_state)
        b_mat, c_mat = b_mat.reshape(shape), c_mat.reshape(shape)
    return x, b_mat, c_mat


def _gated_norm(cfg, params, y, z):
    """RMSNorm of ``y * silu(z)``, over each group's channels."""
    y = y * jax.nn.silu(z)
    g = cfg.ssm_groups
    if g == 1:
        return rms_norm(y, params["gate_norm_scale"], cfg.norm_eps)
    shape = y.shape[:-1] + (g, y.shape[-1] // g)
    return rms_norm(y.reshape(shape),
                    params["gate_norm_scale"].reshape(shape[-2:]),
                    cfg.norm_eps).reshape(y.shape)


@jax.named_scope("model.ssm")
def apply_ssm_mixer(params, cfg, u: jnp.ndarray,
                    state: Optional[Dict[str, jnp.ndarray]] = None,
                    return_state: bool = False,
                    use_pallas: bool = False):
    """Sequence-mode Mamba-2 mixer (train/prefill).

    u: [B,S,d_model] (already pre-normed by caller or not — this function
    applies its own pre-norm).  Returns y [B,S,d_model] (+ state dict).
    """
    bsz, s, _ = u.shape
    nh, p = cfg.ssm_heads, cfg.ssm_headdim
    x_in = rms_norm(u, params["norm_scale"], cfg.norm_eps)
    proj = jnp.einsum("bsd,de->bse", x_in, params["in_proj"])
    z, x_bc_pre, dt_raw = _split_proj(cfg, proj)
    hist0 = state["conv"] if state is not None else None
    x_bc = conv1d_causal(x_bc_pre, params["conv_w"], hist0)
    if cfg.conv_bias:
        x_bc = x_bc + params["conv_b"]
    x_bc = jax.nn.silu(x_bc)
    x, b_mat, c_mat = _split_xbc(cfg, x_bc)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"][None, None, :])
    xh = x.reshape(bsz, s, nh, p)
    h0 = state["ssm"] if state is not None else None
    if use_pallas:
        from repro.kernels.ops import ssd_op
        y, h_final = ssd_op(xh, dt, params["a_log"], b_mat, c_mat,
                            chunk=min(cfg.ssm_chunk, s), h0=h0)
    else:
        y, h_final = ssd_chunked(xh, dt, params["a_log"], b_mat, c_mat,
                                 min(cfg.ssm_chunk, s), h0)
    y = y + params["d_skip"].astype(y.dtype)[None, None, :, None] \
        * xh
    y = y.reshape(bsz, s, cfg.d_inner)
    y = _gated_norm(cfg, params, y, z)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])
    if not return_state:
        return out
    width = cfg.conv_width
    pad = jnp.zeros((bsz, width - 1, x_bc_pre.shape[-1]), x_bc_pre.dtype)
    if hist0 is not None:
        pad = hist0
    conv_hist = jnp.concatenate([pad, x_bc_pre], axis=1)[:, -(width - 1):]
    return out, {"ssm": h_final, "conv": conv_hist}


def apply_ssm_decode(params, cfg, u: jnp.ndarray,
                     state: Dict[str, jnp.ndarray]):
    """One-token decode.  u: [B,1,d_model]; state: {ssm:[B,H,P,N],
    conv:[B,W-1,C]}.  Returns (y [B,1,d_model], new_state)."""
    bsz = u.shape[0]
    nh, p = cfg.ssm_heads, cfg.ssm_headdim
    x_in = rms_norm(u[:, 0], params["norm_scale"], cfg.norm_eps)
    proj = jnp.einsum("bd,de->be", x_in, params["in_proj"])
    z, x_bc, dt_raw = _split_proj(cfg, proj)
    x_bc, conv_hist = conv1d_step(x_bc, params["conv_w"], state["conv"])
    if cfg.conv_bias:
        x_bc = x_bc + params["conv_b"]
    x_bc = jax.nn.silu(x_bc)
    x, b_mat, c_mat = _split_xbc(cfg, x_bc)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"][None, :])
    xh = x.reshape(bsz, nh, p)
    y, h_new = ssd_decode_step(state["ssm"], xh, dt, params["a_log"],
                               b_mat, c_mat)
    y = y + params["d_skip"].astype(y.dtype)[None, :, None] * xh
    y = y.reshape(bsz, cfg.d_inner)
    y = _gated_norm(cfg, params, y, z)
    out = jnp.einsum("be,ed->bd", y, params["out_proj"])[:, None, :]
    return out, {"ssm": h_new, "conv": conv_hist}


def init_ssm_state(cfg, batch: int, dtype) -> Dict[str, jnp.ndarray]:
    conv_ch = cfg.ssm_conv_dim
    return {
        "ssm": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_headdim,
                          cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_ch), dtype),
    }
