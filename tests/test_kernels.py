"""Pallas kernel validation (interpret mode) against pure-jnp oracles:
shape/dtype sweeps + hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import flash_attention_op, ssd_op
from repro.kernels.ref import ref_attention, ref_ssd_intra_chunk
from repro.kernels.ssd_scan import ssd_intra_chunk
from repro.models.ssm import ssd_chunked


def _mk_qkv(key, b, sq, skv, hq, hkv, d, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, hkv, d), jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


SWEEP = [
    # (b, sq, skv, hq, hkv, d, window, cap, dtype)
    (1, 128, 128, 4, 4, 32, 0, 0.0, jnp.float32),
    (2, 128, 128, 4, 2, 64, 0, 50.0, jnp.float32),
    (1, 256, 256, 8, 2, 32, 64, 0.0, jnp.float32),
    (2, 64, 256, 4, 1, 16, 0, 0.0, jnp.float32),   # q shorter (decode-ish)
    (1, 1, 128, 4, 2, 64, 0, 0.0, jnp.float32),    # single-token decode
    (1, 96, 96, 2, 2, 32, 17, 30.0, jnp.float32),  # odd sizes + both caps
    (1, 128, 128, 4, 4, 32, 0, 0.0, jnp.bfloat16),
    (2, 128, 128, 8, 4, 128, 32, 50.0, jnp.bfloat16),
]


@pytest.mark.parametrize("b,sq,skv,hq,hkv,d,window,cap,dtype", SWEEP)
def test_flash_attention_sweep(b, sq, skv, hq, hkv, d, window, cap, dtype):
    q, k, v = _mk_qkv(jax.random.PRNGKey(0), b, sq, skv, hq, hkv, d, dtype)
    off = skv - sq
    out = flash_attention_op(q, k, v, causal=True, window=window,
                             softcap=cap, q_offset=off, block_q=64,
                             block_k=64, interpret=True)
    ref = ref_attention(q, k, v, causal=True, window=window, softcap=cap,
                        q_offset=off)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_flash_attention_kv_len_masking():
    q, k, v = _mk_qkv(jax.random.PRNGKey(1), 1, 8, 128, 2, 2, 32,
                      jnp.float32)
    out = flash_attention_op(q, k, v, causal=False, kv_len=100,
                             block_q=64, block_k=64, interpret=True)
    ref = ref_attention(q, k, v, causal=False, kv_len=100)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@given(sq=st.sampled_from([32, 64, 100]),
       hkv=st.sampled_from([1, 2]),
       g=st.sampled_from([1, 2, 3]),
       d=st.sampled_from([16, 32]),
       window=st.sampled_from([0, 8, 24]),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_flash_attention_property(sq, hkv, g, d, window, seed):
    q, k, v = _mk_qkv(jax.random.PRNGKey(seed), 1, sq, sq, hkv * g, hkv,
                      d, jnp.float32)
    out = flash_attention_op(q, k, v, causal=True, window=window,
                             block_q=32, block_k=32, interpret=True)
    ref = ref_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


SSD_SWEEP = [
    # (b, s, h, p, n, q)
    (1, 64, 2, 8, 16, 16),
    (2, 128, 4, 16, 32, 32),
    (1, 256, 3, 32, 64, 64),
]


@pytest.mark.parametrize("b,s,h,p,n,q", SSD_SWEEP)
def test_ssd_intra_chunk_sweep(b, s, h, p, n, q):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    xdt = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    a = -jnp.abs(jax.random.normal(ks[1], (b, s, h))) * 0.1
    a_cs = jnp.cumsum(a.reshape(b, s // q, q, h), axis=2).reshape(b, s, h)
    bm = jax.random.normal(ks[2], (b, s, n)) * 0.3
    cm = jax.random.normal(ks[3], (b, s, n)) * 0.3
    y, states = ssd_intra_chunk(xdt, a_cs, bm, cm, q, interpret=True)
    y_ref, st_ref = ref_ssd_intra_chunk(xdt, a_cs, bm, cm, q)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4)
    # kernel emits [B,C,H,N,P]; oracle [B,C,H,N,P] too
    np.testing.assert_allclose(np.asarray(states), np.asarray(st_ref),
                               atol=1e-4)


def test_ssd_op_matches_model_reference():
    key = jax.random.PRNGKey(3)
    B, S, H, P, N, Q = 2, 96, 4, 16, 32, 32
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a_log = jnp.log(jnp.linspace(1.0, 8.0, H))
    bm = jax.random.normal(ks[2], (B, S, N)) * 0.3
    cm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    h0 = jax.random.normal(ks[4], (B, H, P, N)) * 0.1
    y1, h1 = ssd_op(x, dt, a_log, bm, cm, chunk=Q, h0=h0, interpret=True)
    y2, h2 = ssd_chunked(x, dt, a_log, bm, cm, Q, h0=h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-4)


@given(s=st.sampled_from([32, 64]), h=st.sampled_from([1, 3]),
       p=st.sampled_from([8, 16]), n=st.sampled_from([8, 32]),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_ssd_op_property(s, h, p, n, seed):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (1, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, s, h)))
    a_log = jnp.zeros((h,))
    bm = jax.random.normal(ks[2], (1, s, n)) * 0.3
    cm = jax.random.normal(ks[3], (1, s, n)) * 0.3
    y1, h1 = ssd_op(x, dt, a_log, bm, cm, chunk=16, interpret=True)
    y2, h2 = ssd_chunked(x, dt, a_log, bm, cm, 16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-4)


def test_kernels_refuse_backends_without_lowering(monkeypatch):
    """Interpret mode is the CPU's; elsewhere only the TPU lowering runs."""
    from repro.kernels import ops
    assert ops._auto_interpret(None) is True          # the tests' CPU
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="TPU"):
        ops._auto_interpret(None)
    assert ops._auto_interpret(True) is True
