"""The expert layer on a mesh: each data shard routes its own tokens and
each expert shard computes its experts' part, summed over the expert shards.
On four CPU devices (a process of its own, as the device count is fixed at
start), the model's loss, every gradient leaf and the counters equal those
of the same model with no mesh, for meshes (data, model) of (4, 1), (2, 2)
and (1, 4)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

CODE = r"""
import json, sys
import jax, jax.numpy as jnp
from repro.configs import get_arch, reduced
from repro.launch.mesh import make_local_mesh
from repro.models import Model, ModelOptions, make_batch
from repro.train.sharding import ShardingCtx

cfg = reduced(get_arch(sys.argv[1]))
opts = ModelOptions(remat_policy="none", attn_chunk=16)
plain = Model(cfg, options=opts)
params = plain.init(jax.random.PRNGKey(0))
batch = make_batch(cfg, 32, 4, "train", 1)


def run(model):
    def loss(p):
        logits, aux = model.forward(p, batch)
        return jnp.mean(jnp.square(logits.astype(jnp.float32))), aux
    (l, aux), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    return l, aux, g


l0, aux0, g0 = run(plain)
out = {}
for model_axis in (1, 2, 4):
    ctx = ShardingCtx(mesh=make_local_mesh(model_axis))
    l1, aux1, g1 = run(Model(cfg, ctx=ctx, options=opts))
    norms = [jnp.linalg.norm(a) for a in jax.tree_util.tree_leaves(g0)]
    floor = 1e-3 * jnp.median(jnp.stack(norms))
    gaps = {}
    for (path, a), b, norm in zip(jax.tree_util.tree_leaves_with_path(g0),
                                  jax.tree_util.tree_leaves(g1), norms):
        # over the larger of the leaf's norm and a thousandth of the median
        # leaf's: a top-1 router's gradient is 0 up to rounding on both sides
        gaps[jax.tree_util.keystr(path)] = float(
            jnp.linalg.norm(a - b) / jnp.maximum(norm, floor))
    out[model_axis] = {
        "loss": [float(l0), float(l1)],
        "held": [float(aux0["moe_held_slots"]), float(aux1["moe_held_slots"])],
        "dropped": float(aux1["moe_dropped_slots"]),
        "gaps": gaps}
print(json.dumps(out))
"""


@pytest.mark.parametrize("arch", ["granite-moe-3b-a800m",
                                  "llama4-scout-17b-a16e"])
def test_sharded_expert_layer_equals_the_unsharded(arch):
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", CODE, arch], env=env,
                         check=True, timeout=300, capture_output=True,
                         text=True)
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert set(out) == {"1", "2", "4"}
    for mesh, r in out.items():
        l0, l1 = r["loss"]
        # float32 both sides; the sums run in another order over shards
        assert abs(l1 - l0) <= 1e-5 * abs(l0), mesh
        assert r["held"][0] == r["held"][1], mesh
        assert r["dropped"] == 0.0, mesh
        worst = max(r["gaps"].items(), key=lambda kv: kv[1])
        assert worst[1] < 1e-4, (mesh, worst)
