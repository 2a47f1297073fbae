"""The window keeps steps dispatched ahead of the loss it reads: the steps
so run are the steps run one by one, and the window counts every step it
sent, over the time until the last of them was read."""

import jax
import numpy as np
import pytest

import tiny
from harness import cells
from harness.train_cell import Job

WORKLOAD = "mamba2-780m.train"
SEED = 2**32 + 9


def tiny_job(path):
    cell = cells.find(WORKLOAD)
    cell.config.update(tiny.TINY[cell.config["name"]])
    cell.traffic.update(tiny.TRAFFIC)
    job = Job(cell, SEED, path)
    job.compared_steps()
    return job


@pytest.mark.parametrize("ahead", [2, 5])
def test_steps_dispatched_ahead_equal_steps_one_by_one(ahead, tmp_path):
    one = tiny_job(tmp_path / "one")
    for _ in range(6):
        one.step()
    early = tiny_job(tmp_path / "early")
    early.ahead = ahead
    reads = early._run(steps=6)
    assert len(reads) == 6 and reads == sorted(reads)
    assert early.n == one.n
    for a, b in zip(jax.tree_util.tree_leaves((one.params, one.opt_state)),
                    jax.tree_util.tree_leaves((early.params,
                                               early.opt_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    one.close()
    early.close()


def test_window_counts_every_step_sent(tmp_path):
    job = tiny_job(tmp_path)
    assert 1 <= job.ahead <= 16
    n0 = job.n
    win = job.window(0.5)
    assert win["steps"] == job.n - n0 == len(win["step_s"])
    assert win["tokens"] == win["steps"] * job.tokens
    assert win["seconds"] >= 0.5
    assert win["seconds"] == pytest.approx(sum(win["step_s"]))
    assert win["compiles"] == 0
    job.close()
