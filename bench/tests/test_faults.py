"""A run with the timed path broken underneath comes out not correct:
once for each fault a one-chip training cell can have (the exchange
between chips does not exist on one chip)."""

import pytest

import tiny


@pytest.mark.parametrize("fault", [
    "unchanged",      # the step returns its state unchanged
    "half_batch",     # half of the batch left out, the mean over the rest
    "double_leaf",    # an answer altered where it is produced: one leaf's
                      # update applied twice
])
@pytest.mark.parametrize("workload", ["mamba2-780m.train", "hymba-1.5b.train"])
def test_planted_fault_is_not_correct(workload, fault):
    rc, r, _ = tiny.run(workload, fault=fault)
    assert rc == 0
    assert r["correct"] is False, r["checks"]
