"""Parameter layout and model FLOPs of the `mamba2-780m`, `hymba-1.5b` and
`hymba-1.5b-32l` configurations, from their configuration files alone.

A configuration names this module under ``shapes``; the harness asks it
for three things:

* ``leaves(c)``: every leaf of the program's parameter tree
  (``models/transformer.py``), written down here so that the reference and
  the program are fed the same weights without either making them;
* ``forward_flops_per_sequence(c, seq_len)``: the model FLOPs of one
  forward pass over one sequence;
* ``matmul_param_count(c)``: the parameters that enter a matrix product.

The model is an embedding (Hymba: with ``num_meta_tokens`` learned rows in
front), layers that are all alike, and an unembedding. A layer holds a
Mamba-2 mixer with one B/C group and ``d_inner = ssm_expand * d_model``,
attention, or both side by side with Hymba's fuse, then a gated MLP where
``d_ff`` is set.

The forward count takes every matrix product the model needs per position
of the trunk (meta tokens included, since every layer computes them):

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

import math
from typing import Dict, List, Tuple

# (path, shape, dtype, init, std); init is one of
# normal | trunc | zeros | ones | a_log
Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], str, str, float]


def ssm_sizes(c: dict) -> Dict[str, int]:
    d_inner = c["ssm_expand"] * c["d_model"]
    return {"d_inner": d_inner, "heads": d_inner // c["ssm_headdim"],
            "conv_ch": d_inner + 2 * c["ssm_state"]}


def leaves(c: dict) -> List[Leaf]:
    """Every leaf of the parameter tree of configuration ``c``, stored in
    the type the program trains it in: the model dtype, except the SSM's
    ``dt_bias``, ``a_log``, ``d_skip`` and the hybrid mix's betas, which
    stay float32."""
    d, L, V, dt = c["d_model"], c["num_layers"], c["vocab_size"], c["dtype"]
    f32 = "float32"
    out: List[Leaf] = [
        (("embed", "table"), (V, d), dt, "normal", 0.02),
        (("final_norm_scale",), (d,), dt, "zeros", 0.0),
    ]
    if not c["tie_embeddings"]:
        out.append((("lm_head",), (d, V), dt, "trunc", d ** -0.5))
    if c["num_heads"]:
        hq, hkv, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
        a = ("blocks", "attn")
        out += [
            (a + ("norm_scale",), (L, d), dt, "zeros", 0.0),
            (a + ("wq",), (L, d, hq, hd), dt, "trunc", d ** -0.5),
            (a + ("wk",), (L, d, hkv, hd), dt, "trunc", d ** -0.5),
            (a + ("wv",), (L, d, hkv, hd), dt, "trunc", d ** -0.5),
            (a + ("wo",), (L, hq, hd, d), dt, "trunc", (hq * hd) ** -0.5),
        ]
    if c["ssm_state"]:
        s = ssm_sizes(c)
        di, nh, n = s["d_inner"], s["heads"], c["ssm_state"]
        b = ("blocks", "ssm")
        out += [
            (b + ("norm_scale",), (L, d), dt, "zeros", 0.0),
            (b + ("in_proj",), (L, d, 2 * di + 2 * n + nh), dt, "trunc",
             d ** -0.5),
            (b + ("conv_w",), (L, c["conv_width"], s["conv_ch"]), dt,
             "trunc", c["conv_width"] ** -0.5),
            (b + ("dt_bias",), (L, nh), f32, "zeros", 0.0),
            (b + ("a_log",), (L, nh), f32, "a_log", 0.0),
            (b + ("d_skip",), (L, nh), f32, "ones", 0.0),
            (b + ("gate_norm_scale",), (L, di), dt, "zeros", 0.0),
            (b + ("out_proj",), (L, di, d), dt, "trunc", di ** -0.5),
        ]
    if c["num_heads"] and c["ssm_state"]:
        f = ("blocks", "fuse")
        out += [
            (f + ("attn_norm",), (L, d), dt, "zeros", 0.0),
            (f + ("ssm_norm",), (L, d), dt, "zeros", 0.0),
            (f + ("beta_attn",), (L,), f32, "ones", 0.0),
            (f + ("beta_ssm",), (L,), f32, "ones", 0.0),
        ]
    if c["d_ff"]:
        m, ff = ("blocks", "mlp"), c["d_ff"]
        out += [
            (m + ("norm_scale",), (L, d), dt, "zeros", 0.0),
            (m + ("w_gate",), (L, d, ff), dt, "trunc", d ** -0.5),
            (m + ("w_up",), (L, d, ff), dt, "trunc", d ** -0.5),
            (m + ("w_down",), (L, ff, d), dt, "trunc", ff ** -0.5),
        ]
    if c["num_meta_tokens"]:
        out.append((("meta_tokens",), (c["num_meta_tokens"], d), dt,
                    "normal", 0.02))
    return out


def matmul_param_count(c: dict) -> int:
    """Parameters that enter a matrix multiplication (all but norms,
    biases and per-head scalars); the unembedding counts once when tied."""
    n = 0
    for path, shape, _, init, _ in leaves(c):
        name = path[-1]
        if name in ("table", "lm_head", "wq", "wk", "wv", "wo", "in_proj",
                    "out_proj", "w_gate", "w_up", "w_down", "conv_w"):
            n += math.prod(shape)
    return n


def layer_kinds(c: dict) -> List[str]:
    """Each layer's attention: ``none``, ``global``, or ``local`` (within
    ``window_size``); ``swa_mostly`` keeps global attention at the first,
    middle and last layers."""
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
        s = ssm_sizes(c)
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
        for kind in layer_kinds(c):
            win = c["window_size"] if kind == "local" else t_len
            keys = sum(min(t + 1, win) for t in range(t_len))
            total += proj * t_len + 2 * 2 * hq * hd * keys
    total += 2 * d * V * seq_len                      # unembedding
    return float(total)
