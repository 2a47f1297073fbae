"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk block.

The SSD algorithm's hot spot is the per-chunk quadratic part:
``y_diag = (C Bᵀ ∘ L) X`` plus the per-chunk state contribution
``S_c = (B ∘ decay)ᵀ X`` — three [Q,·]×[·,Q|P] matmuls per (batch, head,
chunk).  This kernel runs them on the MXU with all chunk operands resident
in VMEM; the decay cumsums and the tiny inter-chunk recurrence stay in XLA
(see repro.models.ssm.ssd_chunked for the reference pipeline).  The
cumsums are O(S) work but not cheap on TPU: XLA runs ``jnp.cumsum`` as a
windowed reduction (``reduce-window``) of about one add per element of the
chunk for each output, on the vector unit.  ``ssd_chunked`` computes them
as one lower-triangular matmul a chunk on the MXU (``chunk_cumsum``); the
wrapper here (``kernels.ops.ssd_op``) still uses ``jnp.cumsum``.

Grid: (B, H, n_chunks); blocks: one chunk per program instance.  The
wrapper lays the per-head operands out head-major ([B, H, S, P], and the
log-decay both as a column [B, H, S, 1] and as a row [B, H, 1, S]) so that
every block's last two dims are tile-aligned or whole, as the TPU
compiler requires.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 operands throughout: exact f32 matmuls on the MXU, as in the
# XLA reference (the default precision would round them to bf16)
_PREC = jax.lax.Precision.HIGHEST


def _kernel(xdt_ref, acol_ref, arow_ref, b_ref, c_ref, y_ref, st_ref, *,
            chunk: int):
    # xdt: [Q, P] (x*dt); acol/arow: [Q, 1] / [1, Q] cumsum of the
    # log-decay within the chunk; b/c: [Q, N]
    xdt = xdt_ref[...].astype(jnp.float32)
    acol = acol_ref[...].astype(jnp.float32)
    arow = arow_ref[...].astype(jnp.float32)
    bm = b_ref[...].astype(jnp.float32)
    cm = c_ref[...].astype(jnp.float32)

    seg = acol - arow                                    # [Q, Q]
    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    l_mat = jnp.where(iq >= jq, jnp.exp(seg), 0.0)

    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 precision=_PREC,
                                 preferred_element_type=jnp.float32)
    scores = scores * l_mat                              # [Q, Q]
    y = jax.lax.dot(scores, xdt, precision=_PREC,
                    preferred_element_type=jnp.float32)  # [Q, P]

    decay_st = jnp.exp(acol[chunk - 1:chunk, :] - acol)  # [Q, 1]
    b_dec = bm * decay_st                                # [Q, N]
    states = jax.lax.dot_general(b_dec, xdt, (((0,), (0,)), ((), ())),
                                 precision=_PREC,
                                 preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    st_ref[...] = states.astype(st_ref.dtype)            # [N, P]


def ssd_intra_chunk(xdt: jnp.ndarray, a_cs: jnp.ndarray, b_mat: jnp.ndarray,
                    c_mat: jnp.ndarray, chunk: int,
                    interpret: bool = False
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Intra-chunk SSD.

    xdt: [B, S, H, P] (inputs pre-multiplied by dt);
    a_cs: [B, S, H] within-chunk cumulative log-decay;
    b_mat/c_mat: [B, S, N].
    Returns (y_diag [B, S, H, P], states [B, NC, H, N, P]).
    """
    bsz, s, h, p = xdt.shape
    n = b_mat.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    xdt_hm = xdt.transpose(0, 2, 1, 3)                   # [B, H, S, P]
    a_hm = a_cs.transpose(0, 2, 1)                       # [B, H, S]
    y, st = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((None, None, chunk, p),
                         lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((None, None, chunk, 1),
                         lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((None, None, 1, chunk),
                         lambda b, hh, c: (b, hh, 0, c)),
            pl.BlockSpec((None, chunk, n), lambda b, hh, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, n), lambda b, hh, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, p),
                         lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((None, None, None, n, p),
                         lambda b, hh, c: (b, c, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nc, h, n, p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(xdt_hm, a_hm[..., None], a_hm[:, :, None, :], b_mat, c_mat)
    return y.transpose(0, 2, 1, 3), st
