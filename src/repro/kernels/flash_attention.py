"""Pallas TPU flash attention: tiled online-softmax with GQA, causal +
sliding-window masking, and gemma-style logit softcap.

TPU adaptation notes (DESIGN.md §2): the tiling is chosen for the
HBM→VMEM→MXU hierarchy — Q tiles of ``block_q`` rows stay resident in
VMEM while K/V stream through in ``block_k`` tiles on the sequentially-
iterated last grid axis; running max/normalizer live in VMEM scratch
(lane-replicated, [block_q, 128]) so the MXU sees back-to-back
[block_q, d] × [d, block_k] matmuls.  Causally-dead K/V tiles are skipped
with ``pl.when`` (and the index maps never fetch them twice).

Layout: the public contract is q [B, Sq, Hq, D], k/v [B, Skv, Hkv, D].
The wrapper transposes to head-major [B, H, S, D] so that every block's
last two dims are (rows, D): tile-aligned rows and the whole head dim, as
the TPU compiler requires.  Grid (B, Hq, Sq/block_q, Skv/block_k), last
axis "arbitrary" (sequential).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# scores and P·V in f32 throughout, as in the reference: exact f32
# matmuls on the MXU (the default precision would round them to bf16)
_PREC = jax.lax.Precision.HIGHEST


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, softcap: float,
            block_q: int, block_k: int, q_offset: int, kv_len: int):
    b, h, qi, kj = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                    pl.program_id(3))
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # tile-level skip: entirely-masked K/V tiles do no work
    q_max = q_offset + qi * block_q + block_q - 1
    q_min = q_offset + qi * block_q
    tile_dead = False
    if causal:
        tile_dead = kj * block_k > q_max
    if window > 0:
        tile_dead = jnp.logical_or(
            tile_dead, (kj + 1) * block_k - 1 < q_min - window + 1)

    @pl.when(jnp.logical_not(tile_dead))
    def _compute():
        q = q_ref[...].astype(jnp.float32)                   # [bq, d]
        k = k_ref[...].astype(jnp.float32)                   # [bk, d]
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=_PREC,
                                preferred_element_type=jnp.float32)
        s = s * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0:1]                               # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                               # [bq, bk]
        l_new = l_scr[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc_scr[...] * alpha + jax.lax.dot(
            p, v, precision=_PREC, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[...] = acc

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        out = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_ref[...] = out.astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: Optional[float] = None,
                    q_offset: int = 0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False,
                    kv_len: Optional[int] = None) -> jnp.ndarray:
    """Tiled attention.  window=0 disables the sliding window; GQA is
    expressed through the index maps (no K/V materialization per q-head).
    ``kv_len`` masks trailing cache padding (defaults to k.shape[1])."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kv_len = kv_len if kv_len is not None else skv

    bq = min(block_q, sq)
    bk = min(block_k, skv)
    pq, pk = (-sq) % bq, (-skv) % bk
    # head-major [B, H, S, D]; padded rows are masked (kv) or dropped (q)
    q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    nq, nk = q.shape[2] // bq, k.shape[2] // bk

    grid = (b, hq, nq, nk)
    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=bq, block_k=bk, q_offset=q_offset,
        kv_len=kv_len)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, d),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, bk, d),
                         lambda b, h, i, j, g=g: (b, h // g, j, 0)),
            pl.BlockSpec((None, None, bk, d),
                         lambda b, h, i, j, g=g: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, d),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out.transpose(0, 2, 1, 3)[:, :sq]
