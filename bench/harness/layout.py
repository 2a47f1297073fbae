"""Parameter layout of the language models the training cells run, and
their weights from the seed.

The layout is the program's pytree (``models/transformer.py``), written
down here from the configuration file alone so that the reference and the
program are fed the same weights without either making them. Every leaf is
drawn from its own key, folded from the seed, in one jitted call, and
stored in the type the program trains it in: the model dtype, except the
SSM's ``dt_bias``, ``a_log``, ``d_skip`` and the hybrid mix's betas, which
stay float32.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# (path, shape, dtype, init, std); init is one of
# normal | trunc | zeros | ones | a_log
Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], str, str, float]

# elements of each leaf that the comparison reads whole (sample_leaves)
SAMPLE = 1 << 16


def ssm_sizes(c: dict) -> Dict[str, int]:
    d_inner = c["ssm_expand"] * c["d_model"]
    return {"d_inner": d_inner, "heads": d_inner // c["ssm_headdim"],
            "conv_ch": d_inner + 2 * c["ssm_state"]}


def leaves(c: dict) -> List[Leaf]:
    """Every leaf of the parameter tree of configuration ``c``."""
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


def seed_key(seed: int):
    """A key from any whole number: the low and high 32 bits both count."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _draw(key, shape, init, std):
    if init == "normal":
        return jax.random.normal(key, shape, jnp.float32) * std
    if init == "trunc":
        return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                           jnp.float32) * std
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "a_log":  # A = -exp(a_log) spread over [1, 16] per layer
        return jnp.broadcast_to(
            jnp.log(jnp.linspace(1.0, 16.0, shape[-1], dtype=jnp.float32)),
            shape)
    raise ValueError(init)


def set_path(tree: dict, path, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def leaf_names(tree) -> List[str]:
    """``a/b/c`` paths of a tree's leaves, in its flattening order."""
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def make_params(c: dict, seed: int) -> dict:
    """The whole parameter tree from the seed, on the default device, in one
    jitted call. Equal seeds give equal trees."""
    spec = leaves(c)

    def build(key):
        tree: dict = {}
        for i, (path, shape, dtype, init, std) in enumerate(spec):
            value = _draw(jax.random.fold_in(key, i), shape, init, std)
            set_path(tree, path, value.astype(dtype))
        return tree

    return jax.jit(build)(seed_key(seed))


def sample_positions(tree, seed: int, k: int = SAMPLE) -> List[np.ndarray]:
    """``k`` flat positions in each leaf of ``tree`` (every position of a
    smaller leaf), drawn from the seed and the leaf's place in the tree, in
    ascending order. Trees of one layout get the same positions, and every
    seed the same shapes."""
    out = []
    for i, x in enumerate(jax.tree_util.tree_leaves(tree)):
        n = math.prod(x.shape)
        if n <= k:
            out.append(np.arange(n, dtype=np.int32))
        else:
            rng = np.random.default_rng([int(seed), i])
            out.append(np.sort(rng.integers(0, n, k)).astype(np.int32))
    return out


_gather = jax.jit(lambda tree, pos: [
    x.reshape(-1)[p].astype(jnp.float32)
    for x, p in zip(jax.tree_util.tree_leaves(tree), pos)])


def sample_leaves(tree, seed: int, k: int = SAMPLE) -> Dict[str, np.ndarray]:
    """The elements of every leaf at ``sample_positions``, as float32 host
    arrays by leaf path."""
    pos = sample_positions(tree, seed, k)
    return dict(zip(leaf_names(tree),
                    (np.asarray(v) for v in _gather(tree, pos))))


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
