"""A cell of four chips takes the launcher's sharded path: on four CPU
devices, the tiny hybrid configuration as a four-chip cell gives the same
compared steps as the same job on one device, with its weights and Adam's
state split over the devices, and the reference spread over the devices
gives what the one-device reference gives."""

import math

import jax
import pytest

import tiny
from harness import cells, compare, layout
from harness.train_cell import Job, reference_batches

WORKLOAD = "hymba-1.5b-32l.train-4chip"
SEED = 2**33 + 17


def tiny_cell(chips):
    cell = cells.find(WORKLOAD)
    cell.config.update(tiny.TINY[cell.config["name"]])
    cell.traffic.update(tiny.TRAFFIC)
    cell.workload = dict(cell.workload, chips=chips)
    return cell


@pytest.fixture(scope="module")
def jobs(tmp_path_factory):
    assert jax.device_count() == 4
    out = {}
    for chips in (1, 4):
        job = Job(tiny_cell(chips), SEED, tmp_path_factory.mktemp(f"c{chips}"))
        out[chips] = (job, job.compared_steps())
    yield out
    for job, _ in out.values():
        job.close()


def test_sharded_job_gives_the_one_device_jobs_steps(jobs):
    one, four = jobs[1][1], jobs[4][1]
    nums, _ = compare.gaps(four, one)
    assert nums["loss_gap"] < 1e-3, nums
    assert nums["change_gap"] <= cells.find(WORKLOAD).limits["change_gap"]
    assert all(math.isfinite(x) for x in four["losses"])


def test_no_leaf_of_the_state_is_whole_on_one_device(jobs):
    job = jobs[4][0]
    split = whole = 0
    for tree in (job.params, job.opt_state.mu, job.opt_state.nu):
        for name, x, want in zip(layout.leaf_names(tree),
                                 jax.tree_util.tree_leaves(tree),
                                 jax.tree_util.tree_leaves(job.p_shard)):
            assert x.sharding == want, name
            assert len(x.sharding.device_set) == 4, name
            if x.addressable_shards[0].data.shape != x.shape:
                split += x.size
            else:
                whole += x.size
    # norms, biases and the meta tokens are kept whole on every device by
    # the program's rules; every matrix is split
    assert split > 0.95 * (split + whole)
    one = jobs[1][0]
    assert len(one.params["embed"]["table"].sharding.device_set) == 1


def test_spread_reference_gives_the_one_device_references_steps():
    cell = tiny_cell(4)
    rows = cell.reference()
    base = cells._load_module(cells.BENCH / "configs" / "hybrid_lm_ref.py",
                              "ref_base")
    batches = reference_batches(cell, SEED)
    c, t = cell.config, cell.config["train"]
    spread = rows.run_steps(c, t, SEED, batches)
    plain = base.run_steps(c, t, SEED, batches)
    assert spread["losses"] == pytest.approx(plain["losses"], rel=1e-5)
    nums, _ = compare.gaps(spread, plain)
    assert nums["grad_gap"] < 1e-4 and nums["grad_diff"] < 1e-4, nums
    assert nums["change_gap"] < 2e-2, nums
