"""The plain float32 reference of ``hybrid_lm_ref.py``, spread over the
chips of a cell whose training state does not fit one chip.

The mathematics is that module's, unchanged: its ``Reference`` gives the
loss, and its AdamW (``lr_at``, ``_adam_leaf``) the update, with the
parameters stored in the configuration's dtype between steps. Only where
the numbers live differs. Each batch's rows are split over the chips, one
micro-batch of rows to a chip, and every parameter, gradient and Adam
moment is split over them along one axis, by plain ``jax.device_put``
shardings and one sharding constraint on the residual stream; the
compiler sums the gradient over the chips' rows. Adam's
moments stay on the chips between steps. Nothing here imports the program
or takes anything it made: the weights come from the seed
(``harness.layout``), placed by this module's own rule.
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from harness import layout

_spec = importlib.util.spec_from_file_location(
    "hybrid_lm_ref", Path(__file__).with_name("hybrid_lm_ref.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

F32 = jnp.float32


class Reference(base.Reference):
    """``hybrid_lm_ref.Reference`` with the residual stream held split by
    rows (``rows``, a sharding of its first axis) at each layer's entry
    and exit. That changes where the numbers live and nothing of what is
    computed: left to itself, the compiler splits the stream by features
    and keeps every row on every chip."""

    def __init__(self, c: dict, low: bool = False, rows=None):
        super().__init__(c, low)
        self.rows = rows

    def layer(self, bp, x, pos, window):
        hold = lambda t: jax.lax.with_sharding_constraint(t, self.rows)
        return hold(super().layer(bp, hold(x), pos, window))


def split_axis(path, shape, n: int):
    """The axis a leaf is split along over ``n`` chips: the largest that
    ``n`` divides, never the layer axis of a stacked block (the layer scan
    slices it); None keeps the leaf whole on every chip."""
    first = 1 if path[0] == "blocks" else 0
    fits = [a for a in range(first, len(shape)) if shape[a] % n == 0]
    return max(fits, key=lambda a: shape[a]) if fits else None


def placements(c: dict, mesh: Mesh) -> dict:
    """A tree of shardings like the parameters'."""
    n, tree = mesh.devices.size, {}
    for path, shape, *_ in layout.leaves(c):
        spec = [None] * len(shape)
        a = split_axis(path, shape, n)
        if a is not None:
            spec[a] = "chips"
        layout.set_path(tree, path,
                        NamedSharding(mesh, PartitionSpec(*spec)))
    return tree


def run_steps(c: dict, t: dict, seed: int, batches: Sequence[dict],
              low: bool = False) -> dict:
    """The first ``len(batches)`` AdamW steps from the seed's weights, as
    ``hybrid_lm_ref.run_steps`` returns them: each step's loss, the
    per-leaf norm of the first gradient as the optimizer applies it
    (clipped) and its elements at ``layout.sample_positions``, and the
    per-leaf norm of the parameters' change after the last step."""
    mesh = Mesh(np.array(jax.devices()), ("chips",))
    place = placements(c, mesh)
    rows = NamedSharding(mesh, PartitionSpec("chips"))
    ref = Reference(c, low, rows)
    whole = NamedSharding(mesh, PartitionSpec())
    p = layout.make_params(c, seed, place)
    names = layout.leaf_names(p)
    value_grad = jax.jit(
        lambda ps, *batch: jax.value_and_grad(ref.loss)(
            jax.tree_util.tree_map(lambda x: x.astype(F32), ps), *batch),
        out_shardings=(whole, place))
    zeros = jax.jit(lambda ps: jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, F32), ps), out_shardings=place)
    m, v = zeros(p), zeros(p)
    adam = {}
    losses, first_grad, first_sample = [], {}, {}
    for count, batch in enumerate(batches, 1):
        loss, grads = value_grad(p, *(
            jax.device_put(batch[k], rows)
            for k in ("tokens", "labels", "loss_mask")))
        sq = base.sq_norms(grads)
        gnorm = math.sqrt(sum(sq.values()))
        scale = min(1.0, t["clip_norm"] / max(gnorm, 1e-9))
        if count == 1:
            first_grad = {k: math.sqrt(x) * scale for k, x in sq.items()}
            first_sample = {k: x * scale for k, x in
                            layout.sample_leaves(grads, seed).items()}
        lr = base.lr_at(t, count)
        flat_p, tree = jax.tree_util.tree_flatten(p)
        flat_g, flat_m, flat_v = (jax.tree_util.tree_leaves(x)
                                  for x in (grads, m, v))
        del p, grads, m, v
        out = []
        for g, mi, vi, x in zip(flat_g, flat_m, flat_v, flat_p):
            key = (x.ndim >= 2, x.shape, str(x.dtype), x.sharding)
            if key not in adam:
                adam[key] = jax.jit(
                    lambda *a, _d=key[0]: base._adam_leaf(
                        *a, t=t, decay=_d),
                    donate_argnums=(0, 1, 2, 3))
            out.append(adam[key](g, mi, vi, x, scale, lr, float(count)))
        del flat_g, flat_m, flat_v, flat_p
        p, m, v = (jax.tree_util.tree_unflatten(tree, [o[i] for o in out])
                   for i in range(3))
        del out
        losses.append(float(loss))
    del m, v
    p0 = layout.make_params(c, seed, place)
    diff = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: x.astype(F32) - y.astype(F32), a, b))(p, p0)
    del p, p0
    change = {k: math.sqrt(x) for k, x in base.sq_norms(diff).items()}
    assert list(change) == names
    return {"losses": losses, "grad": first_grad, "grad_sample": first_sample,
            "change": change}
