"""The `nemotron-3-nano-30b-a3b-7l` configuration and its cell: the shapes
module against the program's parameter tree, its FLOP count and the held
experts' grouped-matmul work against a hand count, and a run at a small
size on the CPU, sound (correct) and with each planted fault (not
correct)."""

import contextlib
import dataclasses
import io
import json
import math

import jax
import pytest

import tiny
from harness import cells, flops

WORKLOAD = "nemotron-3-nano-30b-a3b-7l.train-8k"
# every kind of layer, two B/C groups, 4 of 8 experts held, 2 per token
TINY = dict(num_layers=5, layer_pattern="MEM*E", d_model=64, vocab_size=97,
            num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32,
            num_experts=8, moe_experts_held=4, experts_per_token=2,
            shared_expert_ff=48, ssm_state=8, ssm_num_heads=4,
            ssm_headdim=16, ssm_groups=2, ssm_chunk=16)
# Limits read at this size, where the cell's own were read at its size: on
# seeds 3, 2**31+5 and 4e9+7 (CPU) the sound program reads change_gap
# 0.0082-0.0104, grad_gap 0.0075-0.031 and grad_diff 0.17-0.60 (the
# largest on 2**31+5, in the SSM's a_log: bf16 rounding at width 64), the
# fp8 control 0.015-0.038, 0.14-0.52 and 0.81-2.03, half the batch
# 0.031-0.039, 0.18-0.25 and 1.05-1.86. loss_gap, which the cell compares
# too, reads 0.0007-0.0035 (sound, and 0.0023 on the seed below), 0.0027-
# 0.0085 (control) and 0.0057-0.0175 (half the batch) at this size. The runs
# below take one seed.
LIMITS = {"change_gap": 0.02, "grad_gap": 0.1, "grad_diff": 0.6,
          "loss_gap": 0.005}


def config():
    return json.loads((cells.BENCH / "configs" /
                       "nemotron-3-nano-30b-a3b-7l.json").read_text())


def run(trace=0, fault=None, seed=4294967311):
    """(exit code, parsed last line or None), as ``tiny.run``."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = tiny.load_run().main(
            ["--workload", WORKLOAD, "--seed", str(seed), "--seconds", "1",
             "--trace", str(trace)],
            device_check=tiny.fake_device, config_update=TINY,
            traffic_update=tiny.TRAFFIC, limits_update=LIMITS, fault=fault)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]) if rc == 0 and lines else None


@pytest.mark.parametrize("sizes", [{}, TINY])
def test_leaves_are_the_programs_parameter_tree(sizes):
    from harness.train_cell import program_config
    from repro.models import Model
    c = dict(config(), **sizes)
    tree = jax.eval_shape(Model(program_config(c)).init,
                          jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    leaves = cells.shapes(c).leaves(c)
    assert len(leaves) == len(flat)
    for (path, shape, dtype, *_), (kp, leaf) in zip(leaves, flat):
        assert path == tuple(k.key for k in kp)
        assert (shape, dtype) == (leaf.shape, leaf.dtype.name), path


def test_the_cut_counts_528_million_parameters():
    c = config()
    n = sum(math.prod(s) for _, s, *_ in cells.shapes(c).leaves(c))
    assert n == 528_093_120


def test_flops_hand_count():
    c = dict(config(), num_layers=3, layer_pattern="ME*", d_model=8,
             vocab_size=10, num_heads=2, num_kv_heads=1, head_dim=4, d_ff=6,
             num_experts=4, moe_experts_held=2, experts_per_token=2,
             shared_expert_ff=5, ssm_state=2, ssm_num_heads=4,
             ssm_headdim=2, ssm_groups=2, ssm_chunk=2, conv_width=2)
    seq = 4
    # M: d_inner 8, B and C 2 groups of 2, conv channels 16, in_proj 8+16+4
    m = seq * (2 * 8 * 28 + 2 * 2 * 16 + 2 * 8 * 8 + 2 * 2 * 4 * 2 * 2)
    m += (1 + 2 + 1 + 2) * (2 * 4 + 2 * 4 * 2)  # C B^T per group, times x
    # E: router's 4 outputs; 2 of 4 experts held, 2 per token: one routed
    # slot per token, the held share; the shared expert
    e = seq * (2 * 8 * 4 + 1 * 2 * 2 * 8 * 6 + 2 * 2 * 8 * 5)
    # *: projections, then scores and values over 1+2+3+4 causal keys
    a = seq * (2 * 8 * 4 * 4 + 2 * 2 * 4 * 8) + 4 * 2 * 4 * 10
    fwd = m + e + a + 2 * 8 * 10 * seq
    assert cells.shapes(c).forward_flops_per_sequence(c, seq) == fwd
    assert flops.train_step_flops(c, seq, 3) == 3 * 3 * fwd


def test_expert_work_hand_count():
    c = config()
    shapes = cells.shapes(c)
    # 32,768 tokens x 6 slots x 8 of 128 experts = 12,288 rows a layer
    w = shapes.expert_matmul_work(c, 8192, 4)
    rows = 12288
    assert w["rows_per_layer"] == rows
    one = 2 * rows * 2688 * 1856               # one projection, one call
    assert w["flops"] == 3 * 2 * 4 * one       # 3 E layers, 2 projections,
    assert w["calls"] == 3 * 2 * 4             # fwd twice, dX, dW
    weights = 8 * 2688 * 1856
    moved = weights + rows * (2688 + 1856)
    assert w["bytes"] == 3 * 2 * 4 * moved * 2  # bf16
    plain = shapes.expert_matmul_work(c, 8192, 4, remat=False)
    assert plain["flops"] == 3 * 2 * 3 * one


def test_full_step_flops():
    step = flops.train_step_flops(config(), 8192, 4)
    assert 57.5e12 < step < 58.1e12            # 57.8 TFLOP at 8,192 x 4


def test_sound_tiny_run_is_correct():
    rc, r = run()
    assert rc == 0
    assert r["correct"] is True, r["checks"]
    assert set(r["checks"]) == set(LIMITS)


def test_traced_tiny_run_carries_per_layer_metrics():
    rc, r = run(trace=1)
    assert rc == 0
    names = {m["name"] for m in cells.find(WORKLOAD).per_layer}
    assert names == {"host_input_ms", "monitor_ms", "step_mfu",
                     "device_idle_share"}
    assert set(r["metrics"]) <= names and "step_mfu" in r["metrics"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "double_leaf"])
def test_planted_fault_is_not_correct(fault):
    rc, r = run(fault=fault)
    assert rc == 0
    assert r["correct"] is False, r["checks"]


def test_program_config_takes_the_files_sizes():
    from harness.train_cell import program_config
    cfg = program_config(config())
    assert (cfg.num_layers, cfg.layer_pattern, cfg.vocab_size,
            cfg.experts_held, cfg.num_experts) == (7, "MEMEM*E", 16384, 8,
                                                   128)
    assert dataclasses.replace(cfg, moe_experts_held=128,
                               vocab_size=131072).param_count() > 4e9
