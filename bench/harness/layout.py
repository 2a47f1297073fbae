"""Weights from the seed, in the parameter layout of the language models
the training cells run.

The layout is the program's pytree (``models/transformer.py``), written
down from the configuration file alone by the shapes module the
configuration names, so that the reference and the program are fed the
same weights without either making them. Every leaf is drawn from its own
key, folded from the seed, in one jitted call, and stored in the type the
shapes module gives. This module also draws the positions the comparison
samples from each leaf.
"""

from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells

# elements of each leaf that the comparison reads whole (sample_leaves)
SAMPLE = 1 << 16


def leaves(c: dict):
    """Every leaf of configuration ``c``'s parameter tree, as its shapes
    module (``cells.shapes``) writes them: (path, shape, dtype, init,
    std)."""
    return cells.shapes(c).leaves(c)


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


def make_params(c: dict, seed: int, shardings=None) -> dict:
    """The whole parameter tree from the seed in one jitted call: on the
    default device, or where ``shardings`` (a tree like the parameters')
    puts each leaf, so that no leaf is ever whole on one chip. Equal seeds
    give equal trees, however they are placed."""
    spec = leaves(c)

    def build(key):
        tree: dict = {}
        for i, (path, shape, dtype, init, std) in enumerate(spec):
            value = _draw(jax.random.fold_in(key, i), shape, init, std)
            set_path(tree, path, value.astype(dtype))
        return tree

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


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
