"""A configuration's own shapes module: found by name, and for the two
configurations that were there before the module existed, the same leaves,
values and FLOPs as the harness's own layout and FLOP count gave."""

import hashlib
import json
import shutil

import pytest

from harness import cells, flops

MAMBA2 = {
    "embed/table": (50280, 1536), "final_norm_scale": (1536,),
    "blocks/ssm/norm_scale": (48, 1536),
    "blocks/ssm/in_proj": (48, 1536, 6448),
    "blocks/ssm/conv_w": (48, 4, 3328), "blocks/ssm/dt_bias": (48, 48),
    "blocks/ssm/a_log": (48, 48), "blocks/ssm/d_skip": (48, 48),
    "blocks/ssm/gate_norm_scale": (48, 3072),
    "blocks/ssm/out_proj": (48, 3072, 1536)}
HYMBA = {
    "embed/table": (32001, 1600), "final_norm_scale": (1600,),
    "lm_head": (1600, 32001), "blocks/attn/norm_scale": (16, 1600),
    "blocks/attn/wq": (16, 1600, 25, 64), "blocks/attn/wk": (16, 1600, 5, 64),
    "blocks/attn/wv": (16, 1600, 5, 64), "blocks/attn/wo": (16, 25, 64, 1600),
    "blocks/ssm/norm_scale": (16, 1600),
    "blocks/ssm/in_proj": (16, 1600, 6482),
    "blocks/ssm/conv_w": (16, 4, 3232), "blocks/ssm/dt_bias": (16, 50),
    "blocks/ssm/a_log": (16, 50), "blocks/ssm/d_skip": (16, 50),
    "blocks/ssm/gate_norm_scale": (16, 3200),
    "blocks/ssm/out_proj": (16, 3200, 1600),
    "blocks/fuse/attn_norm": (16, 1600), "blocks/fuse/ssm_norm": (16, 1600),
    "blocks/fuse/beta_attn": (16,), "blocks/fuse/beta_ssm": (16,),
    "blocks/mlp/norm_scale": (16, 1600),
    "blocks/mlp/w_gate": (16, 1600, 5504), "blocks/mlp/w_up": (16, 1600, 5504),
    "blocks/mlp/w_down": (16, 5504, 1600), "meta_tokens": (128, 1600)}

# (leaves in order, matmul_param_count, train_step_flops(c, 2048, 4), and
# the sha256 of repr(leaves(c)), which fixes each leaf's dtype, init and
# scale and so its values from a seed), as the harness gave them before
# the shapes moved into the configuration's module
BEFORE = {
    "mamba2-780m": (MAMBA2, 779759616, 41152313032704.0,
                    "65c8fc06dcffeda1969f51507bc8ac1e"
                    "6320455795f517c470460b0888be68c5"),
    "hymba-1.5b": (HYMBA, 871480448, 45349149278208.0,
                   "e5af27c247d1876f6451c0c6ce54b823"
                   "f703f90d1ead253534529b1be939e3e0"),
}


def config(name):
    return json.loads((cells.BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_leaves_and_flops_are_as_before_the_move(name):
    c = config(name)
    shapes = cells.shapes(c)
    want, matmul, step_flops, digest = BEFORE[name]
    leaves = shapes.leaves(c)
    assert {"/".join(p): s for p, s, *_ in leaves} == want
    assert ["/".join(p) for p, *_ in leaves] == list(want)
    assert hashlib.sha256(repr(leaves).encode()).hexdigest() == digest
    assert shapes.matmul_param_count(c) == matmul
    assert flops.train_step_flops(c, 2048, 4) == step_flops


def test_the_32_layer_hymba_is_the_16_layer_one_twice_as_deep():
    c16, c32 = config("hymba-1.5b"), config("hymba-1.5b-32l")
    shapes = cells.shapes(c32)
    for (p16, s16, *r16), (p32, s32, *r32) in zip(shapes.leaves(c16),
                                                  shapes.leaves(c32)):
        assert p16 == p32 and r16 == r32
        assert s32 == ((32,) + s16[1:] if p16[0] == "blocks" else s16)
    assert sum(__import__("math").prod(s) for _, s, *_ in
               shapes.leaves(c32)) == 1_641_127_360
    assert flops.train_step_flops(c32, 2048, 16) == 352114489688064.0


def test_find_loads_the_configurations_shapes_module():
    for w in cells.benchmark()["workloads"]:
        cell = cells.find(w["name"])
        shapes = cell.shapes()
        c = cell.config
        assert shapes.leaves(c) and shapes.matmul_param_count(c) > 0
        assert shapes.forward_flops_per_sequence(c, 16) > 0


def test_a_missing_shapes_module_is_refused_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(cells.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = cells.benchmark()
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    path = root / "bench" / "configs" / "mamba2-780m.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    shapes="no_such_shapes.py")))
    with pytest.raises(FileNotFoundError, match="no_such_shapes.py"):
        cells.find("mamba2-780m.train", root)
    assert cells.find("hymba-1.5b.train", root).shapes()
