"""The model-FLOP function against a hand count and against 6·N·tokens."""

import json

import pytest

from harness import cells, flops


def config(name):
    return json.loads((cells.BENCH / "configs" / f"{name}.json").read_text())


def test_ssm_hand_count():
    c = dict(config("mamba2-780m"), num_layers=2, d_model=8, vocab_size=10,
             ssm_state=2, ssm_headdim=4, ssm_chunk=2, conv_width=2)
    # d_inner 16, 4 heads, conv channels 20, in_proj width 16*2+2*2+4 = 40
    seq = 4
    per_pos = 2 * 8 * 40 + 2 * 2 * 20 + 2 * 16 * 8 + 4 * 4 * 4 * 2
    causal = 1 + 2 + 1 + 2                       # chunk 2 over 4 positions
    diag = causal * (2 * 2 + 2 * 4 * 4)
    fwd = 2 * (per_pos * seq + diag) + 2 * 8 * 10 * seq
    assert flops.train_step_flops(c, seq, 3) == 3 * 3 * fwd


def test_hybrid_hand_count_is_window_aware():
    c = dict(config("hymba-1.5b"), num_layers=3, d_model=8, vocab_size=10,
             num_heads=2, num_kv_heads=1, head_dim=4, d_ff=6,
             window_size=2, ssm_state=2, ssm_headdim=4, ssm_chunk=4,
             conv_width=2, num_meta_tokens=1)
    seq, t = 3, 4                                # 3 tokens + 1 meta token
    ssm = 2 * 8 * 40 + 2 * 2 * 20 + 2 * 16 * 8 + 4 * 4 * 4 * 2
    mlp = 3 * 2 * 8 * 6
    diag = (1 + 2 + 3 + 4) * (2 * 2 + 2 * 4 * 4)
    proj = 2 * 8 * 4 * (2 + 2) + 2 * 2 * 4 * 8
    keys_global, keys_local = 1 + 2 + 3 + 4, 1 + 2 + 2 + 2
    attn = lambda keys: proj * t + 4 * 2 * 4 * keys
    # layers 0, 1, 2 are all anchors of a three-layer stack: global
    fwd = (3 * ((ssm + mlp) * t + diag) + 3 * attn(keys_global)
           + 2 * 8 * 10 * seq)
    assert cells.shapes(c).forward_flops_per_sequence(c, seq) == fwd
    c5 = dict(c, num_layers=5)                  # anchors 0, 2, 4; 1, 3 local
    fwd5 = (5 * ((ssm + mlp) * t + diag) + 3 * attn(keys_global)
            + 2 * attn(keys_local) + 2 * 8 * 10 * seq)
    assert cells.shapes(c5).forward_flops_per_sequence(c5, seq) == fwd5


@pytest.mark.parametrize("name", ["mamba2-780m", "hymba-1.5b",
                                  "hymba-1.5b-32l"])
def test_at_least_six_n_tokens(name):
    c = config(name)
    n = cells.shapes(c).matmul_param_count(c)
    tokens = 2048 * 4
    f = flops.train_step_flops(c, 2048, 4)
    assert f >= 6 * n * tokens
    assert f <= 1.15 * 6 * n * tokens           # chunk and attention terms
