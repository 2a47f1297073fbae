"""The tiled attention path: parity with the direct einsum for the output
and the gradients, the tile plan's liveness, and the tile shares the
launcher records."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.telemetry import Registry
from repro.launch.train import record_attention_tiles
from repro.models import Model
from repro.models import attention as attn

CHUNK = 16
B, HQ, HKV, D = 2, 4, 2, 8


def _inputs(sq, skv, seed=0):
    kq, kk, kv, kc = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(kq, (B, sq, HQ, D), jnp.float32),
            jax.random.normal(kk, (B, skv, HKV, D), jnp.float32),
            jax.random.normal(kv, (B, skv, HKV, D), jnp.float32),
            jax.random.normal(kc, (B, sq, HQ, D), jnp.float32))


def _direct(q, k, v, q_pos, kv_pos, *, causal, window, cap):
    qg = q.reshape(q.shape[:2] + (HKV, HQ // HKV, D))
    out = attn._direct_attend(qg, k, v, q_pos, kv_pos, causal=causal,
                              window=window, cap=cap, scale=D ** -0.5)
    return out.reshape(q.shape)


def _tiled(q, k, v, q_pos, kv_pos, *, causal, window, cap):
    return attn.attend(q, k, v, q_pos, kv_pos, causal=causal, window=window,
                       cap=cap, chunk=CHUNK)


def _value_and_grads(fn, q, k, v, ct, **kw):
    def loss(q, k, v):
        out = fn(q, k, v, **kw)
        return jnp.sum(out * ct), out
    grads, out = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return out, grads


def _decode_slots(skv, pos):
    slots = jnp.arange(skv, dtype=jnp.int32)
    return jnp.where(slots <= pos, slots, -1)


# (sq, skv, q_pos, kv_pos, causal, window, cap); None positions: a fresh
# sequence, 0..s-1
CASES = {
    "causal_global": (48, 48, None, None, True, None, 0.0),
    "window_below_a_tile": (48, 48, None, None, True, 5, 0.0),
    "window_not_a_tile_multiple": (48, 48, None, None, True, 23, 0.0),
    "window_longer_than_sequence": (48, 48, None, None, True, 1000, 0.0),
    "window_softcap": (48, 48, None, None, True, 23, 5.0),
    # 136 = 8.5 tiles of 16, as 2,176 positions are 8.5 tiles of 256, with
    # the window at 64 (1,024 / 16)
    "length_the_tile_does_not_divide": (136, 136, None, None, True, 64, 0.0),
    "decode_slots": (1, 64, jnp.array([40], jnp.int32), _decode_slots(64, 40),
                     True, 30, 0.0),
    "cross_attention": (24, 40, jnp.zeros(24, jnp.int32),
                        jnp.zeros(40, jnp.int32), False, None, 0.0),
}


@pytest.mark.parametrize("case", [*CASES, "window_traced_in_scan"])
def test_tiled_attention_matches_direct(case):
    if case == "window_traced_in_scan":
        sq = skv = 48
        q, k, v, ct = _inputs(sq, skv)
        pos = jnp.arange(sq, dtype=jnp.int32)
        windows = jnp.array([5, 1 << 30, 23], jnp.int32)

        def layers(fn):
            def body(_, w):
                return None, _value_and_grads(fn, q, k, v, ct, q_pos=pos,
                                              kv_pos=pos, causal=True,
                                              window=w, cap=0.0)
            return jax.jit(lambda: jax.lax.scan(body, None, windows)[1])()
        want, got = layers(_direct), layers(_tiled)
    else:
        sq, skv, q_pos, kv_pos, causal, window, cap = CASES[case]
        q, k, v, ct = _inputs(sq, skv)
        kw = dict(q_pos=jnp.arange(sq, dtype=jnp.int32) if q_pos is None
                  else q_pos,
                  kv_pos=jnp.arange(skv, dtype=jnp.int32) if kv_pos is None
                  else kv_pos,
                  causal=causal, window=window, cap=cap)
        want, got = (
            jax.jit(lambda: _value_and_grads(fn, q, k, v, ct, **kw))()
            for fn in (_direct, _tiled))
    (out_w, grads_w), (out_g, grads_g) = want, got
    np.testing.assert_allclose(out_g, out_w, rtol=1e-5, atol=1e-5)
    for name, gw, gg in zip("qkv", grads_w, grads_g):
        np.testing.assert_allclose(gg, gw, rtol=1e-5, atol=1e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("seed", range(12))
def test_tile_plan_skips_no_live_pair(seed):
    rng = np.random.default_rng(seed)
    sq, skv = int(rng.integers(1, 70)), int(rng.integers(CHUNK + 1, 90))
    chunk = int(rng.choice([4, 8, 16]))
    q_pos = rng.integers(0, 100, sq)
    if rng.random() < 0.5:                 # a sequence, not scattered
        q_pos = np.sort(q_pos)
    kv_pos = rng.permutation(120)[:skv]
    kv_pos[rng.random(skv) < 0.3] = -1     # unfilled slots
    causal = bool(rng.random() < 0.7)
    window = None if rng.random() < 0.3 else int(rng.integers(1, 60))
    plan = np.asarray(attn.tile_plan(jnp.asarray(q_pos), jnp.asarray(kv_pos),
                                     chunk=chunk, causal=causal,
                                     window=window))
    live = np.asarray(attn._mask(jnp.asarray(q_pos), jnp.asarray(kv_pos),
                                 causal, window))
    tq, tk = min(chunk, sq), chunk
    assert plan.shape == (-(-sq // tq), -(-skv // tk))
    for i, j in zip(*np.nonzero(~plan)):
        assert not live[i * tq:(i + 1) * tq, j * tk:(j + 1) * tk].any(), \
            (i, j)
    assert plan.any() or not live.any()


def test_hymba_tile_shares_recorded_by_the_launcher():
    cfg = dataclasses.replace(get_arch("hymba-1.5b"), num_layers=16)
    model = Model(cfg)
    windows = [int(w) for w in model.windows]
    assert windows == [1 << 30 if i in (0, 8, 15) else 1024
                       for i in range(16)]
    registry = Registry()
    shares = record_attention_tiles(model, 2048, registry)
    # 2,176 positions in 9 tiles of 256: the causal bound leaves 45 of the
    # 81 tile pairs, the window of 1,024 then 35
    assert shares == {"global": 45 / 81, "local": 35 / 81}
    assert sorted((s["labels"]["kind"], s["value"])
                  for s in registry.snapshot()
                  if s["name"] == "repro.attention.live_tile_share") == [
        ("global", 45 / 81), ("local", 35 / 81)]
