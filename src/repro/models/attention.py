"""Attention: GQA with causal/sliding-window masks, softcap, online-softmax
tiling, and decode against global or ring (sliding-window) KV caches.

Two execution paths:

* **direct** — one einsum, for short sequences (and smoke tests);
* **tiled** — ``lax.scan`` over query tiles and, inside, over key tiles
  with online softmax (running max / normalizer), the XLA-level
  flash-attention formulation.  A tile pair runs under ``lax.cond`` only
  where ``tile_plan`` finds a (query, key) pair the causal bound, the
  window and the invalid slots leave live, so the trip counts stay
  static while dead tiles cost no work.  This is what keeps prefill_32k
  temp memory bounded, and its Pallas twin in
  ``repro.kernels.flash_attention`` is the TPU fast path.

The sliding window is a *traced* scalar so that gemma-style local/global
alternation can live inside one scanned layer stack (global layers simply
pass window = 2^30).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import softcap as _softcap

NEG_INF = -1e30
GLOBAL_WINDOW = jnp.int32(1 << 30)


def _mask(q_pos: jnp.ndarray, kv_pos: jnp.ndarray, causal: bool,
          window) -> jnp.ndarray:
    """[..., Sq, Skv] boolean validity mask from positions.

    kv_pos < 0 marks invalid (padded / not-yet-filled) slots.
    """
    q = q_pos[..., :, None].astype(jnp.int32)
    k = kv_pos[..., None, :].astype(jnp.int32)
    valid = k >= 0
    if causal:
        valid &= k <= q
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        valid &= (q - k) < w
    return valid


def _direct_attend(q, k, v, q_pos, kv_pos, *, causal, window, cap, scale):
    b, sq, n_kv, g, d = q.shape
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = _softcap(logits, cap)
    mask = _mask(q_pos, kv_pos, causal, window)          # [b?, sq, skv]
    while mask.ndim < logits.ndim:
        mask = mask[..., None, :, :] if mask.ndim >= 2 else mask
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out


def _tile_sizes(sq: int, chunk: int) -> Tuple[int, int]:
    """(query tile, key tile): ``chunk`` keys a tile, and as many queries,
    or all of them where there are fewer (one decode query)."""
    return min(chunk, sq), chunk


def _tile_bounds(pos, tile: int, keep):
    """Per tile of ``tile`` positions: the least and the greatest position
    among those ``keep`` marks, and whether ``keep`` marks any.  Positions
    stay below 2^30 (``GLOBAL_WINDOW``), which stands in for none."""
    n_tiles = -(-pos.shape[0] // tile)
    pad = n_tiles * tile - pos.shape[0]
    pos = jnp.pad(pos.astype(jnp.int32), (0, pad)).reshape(n_tiles, tile)
    keep = jnp.pad(keep, (0, pad)).reshape(n_tiles, tile)
    lo = jnp.min(jnp.where(keep, pos, GLOBAL_WINDOW), axis=1)
    hi = jnp.max(jnp.where(keep, pos, -GLOBAL_WINDOW), axis=1)
    return lo, hi, keep.any(axis=1)


def tile_plan(q_pos: jnp.ndarray, kv_pos: jnp.ndarray, *, chunk: int,
              causal: bool, window) -> jnp.ndarray:
    """[n_q_tiles, n_kv_tiles] bool: the (query tile, key tile) pairs that
    hold at least one (query, key) pair ``_mask`` leaves live.

    Each tile is reduced to the least and greatest of its positions (keys
    at position < 0 left out); a tile pair is live when those bounds admit
    a key at or before a query (``causal``) and less than ``window``
    behind it.  The bounds only widen what the tiles hold, so a pair found
    dead holds no live (query, key) pair.  Decided at run time: positions
    and ``window`` may be traced.
    """
    tq, tk = _tile_sizes(q_pos.shape[0], chunk)
    q_lo, q_hi, q_any = _tile_bounds(q_pos, tq,
                                     jnp.ones(q_pos.shape, bool))
    k_lo, k_hi, k_any = _tile_bounds(kv_pos, tk, kv_pos >= 0)
    live = q_any[:, None] & k_any[None, :]
    if causal:
        live &= k_lo[None, :] <= q_hi[:, None]
    if window is not None:
        live &= (q_lo[:, None] - k_hi[None, :]) < jnp.asarray(window,
                                                              jnp.int32)
    return live


def _tile_logits(q, kc, kp, q_pos, causal, window, cap, scale):
    """[b, n_kv, g, tq, tk] masked (soft-capped) logits for one tile.
    Also returns the pre-cap scores (needed for the softcap derivative)."""
    raw = jnp.einsum("bqhgd,bkhd->bhgqk", q, kc,
                     preferred_element_type=jnp.float32) * scale
    capped = _softcap(raw, cap)
    mask = _mask(q_pos, kp, causal, window)              # [tq, tk]
    logits = jnp.where(mask[None, None, None], capped, NEG_INF)
    return logits, capped, mask


def _tile(x, i, size, axis):
    return jax.lax.dynamic_slice_in_dim(x, i * size, size, axis=axis)


def _flash_fwd(q, k, v, q_pos, kv_pos, window, plan, causal, cap, scale):
    """Online-softmax forward over the live tiles of ``plan``.
    Returns (out [b,h,g,sq,d] f32, lse [b,h,g,sq])."""
    b, sq, n_kv, g, d = q.shape
    nq, nk = plan.shape
    tq, tk = sq // nq, k.shape[1] // nk

    def q_tile(_, i):
        qi, qpi = _tile(q, i, tq, 1), _tile(q_pos, i, tq, 0)

        def kv_tile(carry, j):
            def live(carry):
                m, l, acc = carry
                vc = _tile(v, j, tk, 1)
                logits, _, _ = _tile_logits(
                    qi, _tile(k, j, tk, 1), _tile(kv_pos, j, tk, 0), qpi,
                    causal, window, cap, scale)
                m_new = jnp.maximum(m, logits.max(axis=-1))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(logits - m_new[..., None])
                l_new = l * alpha + p.sum(axis=-1)
                acc_new = acc * alpha[..., None] + jnp.einsum(
                    "bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc
                ).astype(jnp.float32)
                return m_new, l_new, acc_new
            return jax.lax.cond(plan[i, j], live, lambda c: c, carry), None

        m0 = jnp.full((b, n_kv, g, tq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, n_kv, g, tq), jnp.float32)
        a0 = jnp.zeros((b, n_kv, g, tq, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_tile, (m0, l0, a0),
                                      jnp.arange(nk))
        l_safe = jnp.maximum(l, 1e-30)
        return None, (acc / l_safe[..., None], m + jnp.log(l_safe))

    _, (out, lse) = jax.lax.scan(q_tile, None, jnp.arange(nq))
    out = jnp.moveaxis(out, 0, 3).reshape(b, n_kv, g, sq, d)
    lse = jnp.moveaxis(lse, 0, 3).reshape(b, n_kv, g, sq)
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _flash_attend(q, k, v, q_pos, kv_pos, window, plan, causal, cap,
                  scale):
    """Tiled attention with a flash-style custom VJP.

    Queries and keys come in whole tiles (``plan``'s shape divides their
    lengths); only the tile pairs ``plan`` marks live are computed,
    forward and backward.  Without the custom VJP, AD of the loops would
    save every tile's probabilities for the backward pass; the backward
    recomputes each live tile's logits from (q, k, lse) instead, like the
    Pallas/TPU flash backward.  ``window`` is an int32 scalar array (may
    be traced; 2^30 disables), gradient None.
    """
    out, _ = _flash_fwd(q, k, v, q_pos, kv_pos, window, plan, causal, cap,
                        scale)
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)


def _flash_attend_fwd(q, k, v, q_pos, kv_pos, window, plan, causal, cap,
                      scale):
    out, lse = _flash_fwd(q, k, v, q_pos, kv_pos, window, plan, causal,
                          cap, scale)
    out_t = out.transpose(0, 3, 1, 2, 4).astype(q.dtype)
    return out_t, (q, k, v, q_pos, kv_pos, window, plan, out, lse)


def _flash_attend_bwd(causal, cap, scale, res, g_out):
    """dQ per query tile over its live key tiles; dK and dV per key tile
    over its live query tiles, added into f32 buffers of the keys."""
    q, k, v, q_pos, kv_pos, window, plan, out, lse = res
    nq, nk = plan.shape
    tq, tk = q.shape[1] // nq, k.shape[1] // nk
    do = g_out.transpose(0, 2, 3, 1, 4).astype(jnp.float32)  # [b,h,g,sq,d]
    delta = jnp.sum(do * out, axis=-1)                       # [b,h,g,sq]

    def q_tile(dkv, i):
        qi = _tile(q, i, tq, 1).astype(jnp.float32)
        qpi, doi = _tile(q_pos, i, tq, 0), _tile(do, i, tq, 3)
        lse_i, delta_i = _tile(lse, i, tq, 3), _tile(delta, i, tq, 3)

        def kv_tile(carry, j):
            def live(carry):
                dq_i, dk, dv = carry
                kc = _tile(k, j, tk, 1).astype(jnp.float32)
                vc = _tile(v, j, tk, 1).astype(jnp.float32)
                logits, capped, mask = _tile_logits(
                    qi, kc, _tile(kv_pos, j, tk, 0), qpi, causal, window,
                    cap, scale)
                p = jnp.exp(logits - lse_i[..., None])       # [b,h,g,tq,tk]
                dv_c = jnp.einsum("bhgqk,bhgqd->bkhd", p, doi)
                dp = jnp.einsum("bhgqd,bkhd->bhgqk", doi, vc)
                ds = p * (dp - delta_i[..., None])           # d wrt capped
                if cap:
                    ds = ds * (1.0 - jnp.square(capped / cap))
                ds = jnp.where(mask[None, None, None], ds, 0.0) * scale
                dq_i = dq_i + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kc)
                dk_c = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qi)
                return (dq_i,
                        jax.lax.dynamic_update_slice_in_dim(
                            dk, _tile(dk, j, tk, 1) + dk_c, j * tk, axis=1),
                        jax.lax.dynamic_update_slice_in_dim(
                            dv, _tile(dv, j, tk, 1) + dv_c, j * tk, axis=1))
            return jax.lax.cond(plan[i, j], live, lambda c: c, carry), None

        dq0 = jnp.zeros(qi.shape, jnp.float32)
        (dq_i, dk, dv), _ = jax.lax.scan(kv_tile, (dq0,) + dkv,
                                         jnp.arange(nk))
        return (dk, dv), dq_i

    dkv0 = jnp.zeros(k.shape, jnp.float32)
    (dk, dv), dq = jax.lax.scan(q_tile, (dkv0, dkv0), jnp.arange(nq))
    dq = jnp.moveaxis(dq, 0, 1).reshape(q.shape)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None, None, None)


_flash_attend.defvjp(_flash_attend_fwd, _flash_attend_bwd)


def _tiled_attend(q, k, v, q_pos, kv_pos, *, causal, window, cap, scale,
                  chunk: int):
    """Online-softmax attention over tiles of queries and keys
    (``_tile_sizes``), computing only the tile pairs ``tile_plan`` finds
    live.  Queries and keys are padded to whole tiles: the padded keys are
    masked (position -1) and the padded queries' rows dropped."""
    b, sq, n_kv, g, d = q.shape
    skv = k.shape[1]
    window_arr = (GLOBAL_WINDOW if window is None
                  else jnp.asarray(window, jnp.int32))
    plan = tile_plan(q_pos, kv_pos, chunk=chunk, causal=causal,
                     window=window_arr)
    tq, tk = _tile_sizes(sq, chunk)
    pad_q = plan.shape[0] * tq - sq
    pad_k = plan.shape[1] * tk - skv
    q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    q_pos = jnp.pad(q_pos, ((0, pad_q),), constant_values=-1)
    k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    kv_pos = jnp.pad(kv_pos, ((0, pad_k),), constant_values=-1)
    out = _flash_attend(q, k, v, q_pos, kv_pos, window_arr, plan, causal,
                        cap, scale)
    return out[:, :sq]


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           q_pos: jnp.ndarray, kv_pos: jnp.ndarray, *,
           causal: bool = True, window=None, cap: float = 0.0,
           scale: Optional[float] = None, chunk: int = 0) -> jnp.ndarray:
    """Grouped-query attention.

    q: [B, Sq, Hq, D];  k/v: [B, Skv, Hkv, D];  q_pos: [Sq]; kv_pos: [Skv]
    (position < 0 == invalid slot).  Returns [B, Sq, Hq, D].  With more
    than ``chunk`` keys, attention runs over tiles of ``chunk`` keys and
    as many queries and skips the tiles the mask leaves dead.
    """
    b, sq, hq, d = q.shape
    n_kv = k.shape[2]
    g = hq // n_kv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, n_kv, g, d)
    if chunk and k.shape[1] > chunk:
        out = _tiled_attend(qg, k, v, q_pos, kv_pos, causal=causal,
                            window=window, cap=cap, scale=scale,
                            chunk=chunk)
    else:
        out = _direct_attend(qg, k, v, q_pos, kv_pos, causal=causal,
                             window=window, cap=cap, scale=scale)
    return out.reshape(b, sq, hq, d)


# ---------------------------------------------------------------- caches ----

def ring_slot_positions(pos, width: int) -> jnp.ndarray:
    """Token position stored in each ring-buffer slot after writing
    position ``pos`` (traced scalar); -1 when the slot is still empty.

    Slot s holds the most recent position p <= pos with p % width == s.
    """
    s = jnp.arange(width, dtype=jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    p = pos - jnp.mod(pos - s, width)
    return jnp.where(p >= 0, p, -1)


def ring_gather_indices(seq_len: int, width: int) -> jnp.ndarray:
    """Indices into a [S] sequence whose last ``width`` tokens fill the
    ring buffer slots (static version, used by prefill).  Invalid -> 0 with
    positions marked -1 separately."""
    s = jnp.arange(width, dtype=jnp.int32)
    last = seq_len - 1
    p = last - jnp.mod(last - s, width)
    return p  # may be negative if seq_len < width


def build_ring_cache(k: jnp.ndarray, v: jnp.ndarray, width: int
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fill a ring cache from a full prefill sequence [B, S, Hkv, D]."""
    seq_len = k.shape[1]
    idx = ring_gather_indices(seq_len, width)
    safe = jnp.clip(idx, 0, seq_len - 1)
    kc = jnp.take(k, safe, axis=1)
    vc = jnp.take(v, safe, axis=1)
    return kc, vc
