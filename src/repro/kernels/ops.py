"""jit'd public wrappers around the Pallas kernels.

``interpret=None`` auto-selects: real lowering on TPU, the Pallas
interpreter on the CPU (where the tests validate the kernels).  Any other
backend is an error: the kernels are written for the TPU only.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import ssd_scan as ssd


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels target the TPU; backend {backend!r} has "
            "neither their lowering nor the CPU interpreter")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "q_offset", "block_q",
    "block_k", "interpret", "kv_len"))
def flash_attention_op(q, k, v, *, causal: bool = True, window: int = 0,
                       softcap: float = 0.0, scale: Optional[float] = None,
                       q_offset: int = 0, block_q: int = 128,
                       block_k: int = 128,
                       interpret: Optional[bool] = None,
                       kv_len: Optional[int] = None) -> jnp.ndarray:
    return fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        scale=scale, q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=_auto_interpret(interpret), kv_len=kv_len)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_op(x, dt, a_log, b_mat, c_mat, *, chunk: int = 256,
           h0: Optional[jnp.ndarray] = None,
           interpret: Optional[bool] = None
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full SSD scan with the Pallas intra-chunk kernel + XLA recurrence.

    Same contract as repro.models.ssm.ssd_chunked:
    x [B,S,H,P], dt [B,S,H] (post-softplus), a_log [H], b/c [B,S,N].
    Returns (y [B,S,H,P], h_final [B,H,P,N]).
    """
    bsz, s_orig, h, p = x.shape
    n = b_mat.shape[-1]
    pad = (-s_orig) % chunk
    if pad:  # dt=0 padding is exact (no decay, no contribution)
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
    s = s_orig + pad
    nc = s // chunk
    a = (-jnp.exp(a_log.astype(jnp.float32)))[None, None, :] * dt.astype(
        jnp.float32)                                     # [B,S,H]
    xdt = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]
    # within-chunk cumsum of log-decay
    a_c = a.reshape(bsz, nc, chunk, h)
    a_cs = jnp.cumsum(a_c, axis=2).reshape(bsz, s, h)

    y_diag, states_np = ssd.ssd_intra_chunk(
        xdt, a_cs, b_mat, c_mat, chunk,
        interpret=_auto_interpret(interpret))
    states = states_np.transpose(0, 1, 2, 4, 3)          # [B,C,H,P,N]

    chunk_decay = jnp.exp(a_cs.reshape(bsz, nc, chunk, h)[:, :, -1]
                          ).transpose(0, 2, 1)           # [B,H,C]
    h0 = (jnp.zeros((bsz, h, p, n), jnp.float32) if h0 is None
          else h0.astype(jnp.float32))

    def step(carry, xs):
        st, dec = xs
        new = carry * dec[..., None, None] + st
        return new, carry

    h_final, prev_states = jax.lax.scan(
        step, h0, (states.transpose(1, 0, 2, 3, 4),
                   chunk_decay.transpose(2, 0, 1)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)   # [B,C,H,P,N]

    cc = c_mat.reshape(bsz, nc, chunk, n).astype(jnp.float32)
    state_decay_out = jnp.exp(a_cs.reshape(bsz, nc, chunk, h))
    y_off = jnp.einsum("bcln,bchpn,bclh->bclhp", cc, prev_states,
                       state_decay_out)
    y = y_diag.reshape(bsz, nc, chunk, h, p) + y_off
    return (y.reshape(bsz, s, h, p)[:, :s_orig].astype(x.dtype), h_final)
