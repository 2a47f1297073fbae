"""Model FLOPs of one training step, from the configuration's shapes.

Forward plus backward is three times the forward's multiply-adds (two
FLOPs each); recomputation under remat does not count. The forward counts
every matrix product the model needs per position of the trunk (meta
tokens included, since every layer computes them):

* Mamba-2 mixer: input and output projections, the depthwise convolution,
  and the SSD chunk terms at the configuration's ``ssm_chunk``: ``C B^T``
  and its product with ``x`` over the causal part of the chunk, the
  chunk's state ``x^T B`` and the state's read-out ``C h``;
* attention: the four projections, and ``Q K^T`` and ``P V`` over the keys
  each query may see (causal, and at most ``window_size`` on local layers);
* gated MLP: three projections;
* the unembedding over the sequence's own positions.

Norms, activations and the scan's recurrence are left out: elementwise
work is a small share and is not what a FLOP/s peak measures.
"""

from __future__ import annotations

from harness import layout


def _layer_kinds(c: dict):
    n = c["num_layers"]
    if not c["num_heads"]:
        return ["none"] * n
    if c.get("attn_pattern") == "swa_mostly":
        anchors = {0, n // 2, n - 1}
        return ["global" if i in anchors else "local" for i in range(n)]
    return ["global"] * n


def forward_flops_per_sequence(c: dict, seq_len: int) -> float:
    d, V = c["d_model"], c["vocab_size"]
    t_len = seq_len + c["num_meta_tokens"]
    per_pos = 0.0        # FLOPs per trunk position that do not depend on it
    total = 0.0
    if c["ssm_state"]:
        s = layout.ssm_sizes(c)
        di, nh, n, p = s["d_inner"], s["heads"], c["ssm_state"], \
            c["ssm_headdim"]
        per_pos += 2 * d * (2 * di + 2 * n + nh)     # in_proj
        per_pos += 2 * c["conv_width"] * s["conv_ch"]  # conv
        per_pos += 2 * di * d                         # out_proj
        per_pos += 2 * 2 * nh * p * n                 # chunk state + read-out
        q = c["ssm_chunk"]
        causal = sum((t % q) + 1 for t in range(t_len))
        ssd_diag = causal * (2 * n + 2 * nh * p)      # C B^T, then times x
        total += ssd_diag * c["num_layers"]
    if c["d_ff"]:
        per_pos += 3 * 2 * d * c["d_ff"]
    total += per_pos * t_len * c["num_layers"]
    if c["num_heads"]:
        hq, hkv, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
        proj = 2 * d * hd * (hq + 2 * hkv) + 2 * hq * hd * d
        for kind in _layer_kinds(c):
            win = c["window_size"] if kind == "local" else t_len
            keys = sum(min(t + 1, win) for t in range(t_len))
            total += proj * t_len + 2 * 2 * hq * hd * keys
    total += 2 * d * V * seq_len                      # unembedding
    return float(total)


def train_step_flops(c: dict, seq_len: int, batch: int) -> float:
    """Model FLOPs of one optimizer step over ``batch`` sequences."""
    return 3.0 * batch * forward_flops_per_sequence(c, seq_len)
