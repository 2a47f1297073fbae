"""A run with the timed path broken underneath comes out not correct:
once for each fault a training cell can have. The exchange between chips
exists only in a cell of more than one chip."""

import pytest

import tiny

FAULTS = [
    "unchanged",      # the step returns its state unchanged
    "half_batch",     # half of the batch left out, the mean over the rest
    "double_leaf",    # an answer altered where it is produced: one leaf's
                      # update applied twice
]
CASES = ([(w, f) for w in ("mamba2-780m.train", "hymba-1.5b.train")
          for f in FAULTS]
         + [("hymba-1.5b-32l.train-4chip", f)
            for f in FAULTS + ["no_exchange"]])   # each chip's own rows only


@pytest.mark.parametrize("workload,fault", CASES)
def test_planted_fault_is_not_correct(workload, fault):
    rc, r, _ = tiny.run(workload, fault=fault)
    assert rc == 0
    assert r["correct"] is False, r["checks"]
