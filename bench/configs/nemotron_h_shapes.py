"""Parameter layout and model FLOPs of the `nemotron-3-nano-30b-a3b-7l`
configuration (Nemotron-H: Mamba-2, expert and attention layers
interleaved), from its configuration file alone.

A configuration names this module under ``shapes``; the harness asks it
for ``leaves(c)``, ``forward_flops_per_sequence(c, seq_len)`` and
``matmul_param_count(c)`` (see ``hybrid_lm_shapes.py``). It also gives
``expert_matmul_work(c, seq_len, batch)``, the work of the held experts'
grouped matmuls in one training step, for a roofline share of them.

The model is an embedding, layers in the order of ``layer_pattern``, each
``x + mixer(rmsnorm(x))``, and an untied unembedding:

* ``M``: a Mamba-2 mixer with ``ssm_num_heads`` heads of ``ssm_headdim``
  and ``ssm_groups`` B/C groups of ``ssm_state``, a convolution with a
  bias, and the gated RMSNorm per group;
* ``E``: a router over all ``num_experts`` (with a selection bias), the
  ``moe_experts_held`` experts held here, each ``down(relu(up x)^2)``, and
  one shared expert of ``shared_expert_ff``;
* ``*``: grouped-query attention without positions.

The program keeps one stack per kind: ``blocks/attn``, ``blocks/moe`` and
``blocks/ssm``, each with a leading dimension of that kind's layer count.

The forward count takes every matrix product per position:

* ``M``: input and output projections, the depthwise convolution, and the
  SSD chunk terms at ``ssm_chunk``: ``C B^T`` per group and its product
  with ``x`` per head over the causal part of the chunk, the chunk's state
  ``x^T B`` and the state's read-out ``C h``;
* ``E``: the router's ``num_experts`` outputs; the held experts' two
  projections over the slots routed to them, ``k x held / num_experts``
  per token (the program pins the held experts to that share of each
  sequence's slots); the shared expert's two projections;
* ``*``: the four projections, and ``Q K^T`` and ``P V`` over the causal
  half of the score matrix;
* the unembedding over the vocabulary slice.

Norms, activations, the router's top-k, the sort and the scan's recurrence
are left out.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

# (path, shape, dtype, init, std); init is one of
# normal | trunc | zeros | ones | a_log
Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], str, str, float]

BYTES = {"bfloat16": 2, "float32": 4}


def counts(c: dict) -> Dict[str, int]:
    """Layers of each kind: ``attn`` (*), ``moe`` (E), ``ssm`` (M)."""
    p = c["layer_pattern"]
    if len(p) != c["num_layers"]:
        raise ValueError(f"layer_pattern {p!r} is not {c['num_layers']} "
                         "layers")
    return {"attn": p.count("*"), "moe": p.count("E"), "ssm": p.count("M")}


def ssm_sizes(c: dict) -> Dict[str, int]:
    di = c["ssm_num_heads"] * c["ssm_headdim"]
    gn = c["ssm_groups"] * c["ssm_state"]
    return {"d_inner": di, "heads": c["ssm_num_heads"], "gn": gn,
            "conv_ch": di + 2 * gn}


def leaves(c: dict) -> List[Leaf]:
    """Every leaf of the program's parameter tree, in its flattening order
    (keys sorted), stored in the type the program trains it in: the model
    dtype, except the router, its selection bias and the SSM's ``a_log``,
    ``d_skip`` and ``dt_bias``, which stay float32."""
    d, V, dt = c["d_model"], c["vocab_size"], c["dtype"]
    f32 = "float32"
    n = counts(c)
    out: List[Leaf] = []
    if n["attn"]:
        L, hq, hkv, hd = n["attn"], c["num_heads"], c["num_kv_heads"], \
            c["head_dim"]
        a = ("blocks", "attn")
        out += [
            (a + ("norm_scale",), (L, d), dt, "zeros", 0.0),
            (a + ("wk",), (L, d, hkv, hd), dt, "trunc", d ** -0.5),
            (a + ("wo",), (L, hq, hd, d), dt, "trunc", (hq * hd) ** -0.5),
            (a + ("wq",), (L, d, hq, hd), dt, "trunc", d ** -0.5),
            (a + ("wv",), (L, d, hkv, hd), dt, "trunc", d ** -0.5),
        ]
    if n["moe"]:
        L, e, held = n["moe"], c["num_experts"], c["moe_experts_held"]
        ff, sf = c["d_ff"], c["shared_expert_ff"]
        m = ("blocks", "moe")
        out += [
            (m + ("norm_scale",), (L, d), dt, "zeros", 0.0),
            (m + ("router",), (L, d, e), f32, "trunc", d ** -0.5),
            (m + ("router_bias",), (L, e), f32, "zeros", 0.0),
            (m + ("shared_down",), (L, sf, d), dt, "trunc", sf ** -0.5),
            (m + ("shared_up",), (L, d, sf), dt, "trunc", d ** -0.5),
            (m + ("w_down",), (L, held, ff, d), dt, "trunc", ff ** -0.5),
            (m + ("w_up",), (L, held, d, ff), dt, "trunc", d ** -0.5),
        ]
    if n["ssm"]:
        L, s = n["ssm"], ssm_sizes(c)
        di, nh, conv = s["d_inner"], s["heads"], s["conv_ch"]
        b = ("blocks", "ssm")
        out += [
            (b + ("a_log",), (L, nh), f32, "a_log", 0.0),
            (b + ("conv_b",), (L, conv), dt, "zeros", 0.0),
            (b + ("conv_w",), (L, c["conv_width"], conv), dt, "trunc",
             c["conv_width"] ** -0.5),
            (b + ("d_skip",), (L, nh), f32, "ones", 0.0),
            (b + ("dt_bias",), (L, nh), f32, "zeros", 0.0),
            (b + ("gate_norm_scale",), (L, di), dt, "zeros", 0.0),
            (b + ("in_proj",), (L, d, di + conv + nh), dt, "trunc",
             d ** -0.5),
            (b + ("norm_scale",), (L, d), dt, "zeros", 0.0),
            (b + ("out_proj",), (L, di, d), dt, "trunc", di ** -0.5),
        ]
    out += [
        (("embed", "table"), (V, d), dt, "normal", 0.02),
        (("final_norm_scale",), (d,), dt, "zeros", 0.0),
        (("lm_head",), (d, V), dt, "trunc", d ** -0.5),
    ]
    return out


MATMUL_LEAVES = ("table", "lm_head", "wq", "wk", "wv", "wo", "in_proj",
                 "out_proj", "conv_w", "router", "w_up", "w_down",
                 "shared_up", "shared_down")


def matmul_param_count(c: dict) -> int:
    """Parameters that enter a matrix multiplication (all but norms,
    biases and per-head scalars)."""
    return sum(math.prod(shape) for path, shape, *_ in leaves(c)
               if path[-1] in MATMUL_LEAVES)


def routed_slots(c: dict, tokens: int) -> float:
    """Token-slots routed to the held experts of one layer:
    ``tokens x k x held / num_experts``, the share the program pins them to
    (for whole sequences)."""
    return (tokens * c["experts_per_token"] * c["moe_experts_held"]
            / c["num_experts"])


def forward_flops_per_sequence(c: dict, seq_len: int) -> float:
    d, V, t = c["d_model"], c["vocab_size"], seq_len
    n = counts(c)
    s = ssm_sizes(c)
    di, nh, p, N = s["d_inner"], s["heads"], c["ssm_headdim"], c["ssm_state"]
    ssm = t * (2 * d * (di + s["conv_ch"] + nh)      # in_proj
               + 2 * c["conv_width"] * s["conv_ch"]  # conv
               + 2 * di * d                          # out_proj
               + 2 * 2 * nh * p * N)                 # chunk state, read-out
    q = c["ssm_chunk"]
    causal = sum((i % q) + 1 for i in range(t))
    ssm += causal * (2 * s["gn"] + 2 * nh * p)       # C B^T per group, x x
    ff, sf = c["d_ff"], c["shared_expert_ff"]
    moe = (t * 2 * d * c["num_experts"]              # router, all outputs
           + routed_slots(c, t) * 2 * 2 * d * ff     # held experts' up, down
           + t * 2 * 2 * d * sf)                     # shared expert
    hq, hkv, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    attn = (t * (2 * d * hd * (hq + 2 * hkv) + 2 * hq * hd * d)
            + 2 * 2 * hq * hd * (t * (t + 1) // 2))  # causal half
    return float(n["ssm"] * ssm + n["moe"] * moe + n["attn"] * attn
                 + 2 * d * V * t)                    # unembedding


def expert_matmul_work(c: dict, seq_len: int, batch: int,
                       rows: float = None, remat: bool = True) -> dict:
    """FLOPs and HBM bytes of the held experts' grouped matmuls in one
    training step, summed over the E layers: for each of the two
    projections a forward call (twice under full remat: the backward
    recomputes it), the input gradient and the weight gradient. ``rows`` is
    the token-slots routed to held experts per layer (``moe_held_slots``
    over the E layers reads it on the chip); by default their pinned share.
    Each call reads its two operands and writes its result once, in the
    model dtype."""
    if rows is None:
        rows = routed_slots(c, seq_len * batch)
    d, ff, held = c["d_model"], c["d_ff"], c["moe_experts_held"]
    b = BYTES[c["dtype"]]
    w = held * d * ff                     # one projection's held weights
    fwd_calls = 2 if remat else 1
    flops = bytes_ = 0.0
    for _ in ("up", "down"):
        flops += (fwd_calls + 2) * 2 * rows * d * ff
        # forward: W, X in, Y out; input grad: dY, W, dX; weight grad:
        # X, dY, dW
        bytes_ += fwd_calls * (w + rows * (d + ff)) * b
        bytes_ += 2 * (w + rows * (d + ff)) * b
    layers = counts(c)["moe"]
    return {"flops": flops * layers, "bytes": bytes_ * layers,
            "rows_per_layer": rows, "calls": (fwd_calls + 2) * 2 * layers}
