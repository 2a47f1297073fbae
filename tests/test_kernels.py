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
from repro.models.ssm import chunk_cumsum, ssd_chunked


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


def _log_decays(key, shape, a_log):
    """Log-decays as large as the cells make them: -exp(a_log) x dt, with
    a_log up to log 16 and dt = softplus(N(0, 1) + bias) per head."""
    ks = jax.random.split(key, 2)
    bias = jax.random.uniform(ks[0], (shape[-1],), minval=-1.0, maxval=1.0)
    dt = jax.nn.softplus(jax.random.normal(ks[1], shape) + bias)
    return dt, -jnp.exp(a_log) * dt


@pytest.mark.parametrize("q", [256, 128])
def test_chunk_cumsum_is_the_f32_cumsum(q):
    """The MXU form of the within-chunk cumulative sum is an f32 sum of
    the f32 log-decays: within f32 summation's bound of a float64 sum and
    of ``jnp.cumsum``."""
    h = 48
    _, a = _log_decays(jax.random.PRNGKey(q), (4, 8, q, h),
                       jnp.log(jnp.linspace(1.0, 16.0, h)))
    a = a.transpose(0, 3, 1, 2)                        # [B,H,C,Q]
    got = np.asarray(chunk_cumsum(a))
    want = np.asarray(jnp.cumsum(a, axis=-1))
    exact = np.cumsum(np.asarray(a, np.float64), axis=-1)
    assert got.dtype == np.float32
    assert np.abs(exact).max() > 1000                  # large decays reached
    # any f32 sum of Q terms of one sign is within Q·2^-24 of the total;
    # a bf16 rounding of the decays would be off by about 2^-9
    rtol = q * 2.0 ** -24
    np.testing.assert_allclose(got, exact, rtol=rtol)
    np.testing.assert_allclose(got, want, rtol=2 * rtol)


def _ssd_recurrence(x, dt, a_log, bm, cm, h0):
    """Step-by-step float64 SSD: h_t = exp(a_t) h_{t-1} + dt_t x_t B_t,
    y_t = h_t C_t, with B/C [B,S,G,N] shared by H/G consecutive heads."""
    hg = x.shape[2] // bm.shape[2]
    bh, ch = (jnp.repeat(t, hg, axis=2) for t in (bm, cm))

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs
        dec = jnp.exp(-jnp.exp(a_log) * dt_t)          # [B,H]
        h = (h * dec[..., None, None]
             + jnp.einsum("bhp,bhn->bhpn", x_t * dt_t[..., None], b_t))
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    h_final, y = jax.lax.scan(
        step, h0, tuple(t.swapaxes(0, 1) for t in (x, dt, bh, ch)))
    return y.swapaxes(0, 1), h_final


@pytest.mark.parametrize("groups", [1, 8])
def test_ssd_chunked_matches_a_float64_recurrence(groups):
    """Output, final state and the gradients in x, dt, a_log, B and C of
    the chunked SSD (one group, and 8 B/C groups) against a float64
    step-by-step recurrence; the sequence pads to a third chunk."""
    b, s, h, p, n, chunk = 2, 80, 16, 8, 8, 32
    ks = jax.random.split(jax.random.PRNGKey(groups), 6)
    a_log = jnp.log(jnp.linspace(1.0, 16.0, h))
    dt, _ = _log_decays(ks[0], (b, s, h), a_log)
    x = jax.random.normal(ks[1], (b, s, h, p))
    bm, cm = (jax.random.normal(k, (b, s, groups, n)) * 0.5
              for k in ks[2:4])
    h0 = jax.random.normal(ks[4], (b, h, p, n)) * 0.1
    wy, wh = (jax.random.normal(k, shape) for k, shape in
              zip(jax.random.split(ks[5]), ((b, s, h, p), (b, h, p, n))))

    def loss(fn, x, dt, a_log, bm, cm):
        y, h_final = fn(x, dt, a_log, bm, cm)
        return jnp.sum(y * wy) + jnp.sum(h_final * wh), (y, h_final)

    def prog(x, dt, a_log, bm, cm):
        if groups == 1:
            bm, cm = bm[:, :, 0], cm[:, :, 0]
        return ssd_chunked(x, dt, a_log, bm, cm, chunk, h0)

    args = (x, dt, a_log, bm, cm)
    grad = jax.value_and_grad(loss, argnums=(1, 2, 3, 4, 5), has_aux=True)
    (_, got), dgot = grad(prog, *args)
    with jax.enable_x64(True):
        args64 = [jnp.asarray(np.asarray(t), jnp.float64)
                  for t in args + (h0, wy, wh)]
        h0_64, wy, wh = args64[5:]
        (_, want), dwant = grad(
            lambda *a: _ssd_recurrence(*a, h0_64), *args64[:5])
        want, dwant = jax.tree_util.tree_map(np.asarray, (want, dwant))
    # a_log's gradient sums terms of both signs over every position, and
    # dt's over each head's decays: f32 keeps fewer of their digits
    rtol = {"dt": 2e-5, "a_log": 2e-4}
    for name, g, w in zip(("y", "h_final", "x", "dt", "a_log", "B", "C"),
                          got + dgot, want + dwant):
        err = np.linalg.norm(np.asarray(g, np.float64) - w)
        assert err <= rtol.get(name, 2e-6) * np.linalg.norm(w), name


def test_kernels_refuse_backends_without_lowering(monkeypatch):
    """Interpret mode is the CPU's; elsewhere only the TPU lowering runs."""
    from repro.kernels import ops
    assert ops._auto_interpret(None) is True          # the tests' CPU
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="TPU"):
        ops._auto_interpret(None)
    assert ops._auto_interpret(True) is True
