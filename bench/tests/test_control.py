"""The control: the reference computed in fp8 and put in the program's
place reads several times the sound program on ``grad_diff``, and where
the cell compares ``grad_diff`` it comes out as not correct through the
cell's own limits. ``PERF.md`` gives the chip's readings at the cells'
own sizes (``calibrate.py``)."""

import math

import pytest

import tiny
from harness import cells, compare
from harness.train_cell import Job, reference_batches


@pytest.mark.parametrize("workload", ["mamba2-780m.train", "hymba-1.5b.train",
                                      "hymba-1.5b-32l.train-4chip"])
@pytest.mark.parametrize("seed", [3, 2**31 + 5, 4_000_000_007])
def test_control_reads_above_the_program(workload, seed, tmp_path):
    cell = cells.find(workload)
    cell.config.update(tiny.TINY[cell.config["name"]])
    cell.traffic.update(tiny.TRAFFIC)
    cell.limits.update(tiny.LIMITS.get(workload, {}))
    ref = cell.reference()
    batches = reference_batches(cell, seed)
    c, t = cell.config, cell.config["train"]
    sound = ref.run_steps(c, t, seed, batches)
    control = ref.run_steps(c, t, seed, batches, low=True)
    job = Job(cell, seed, tmp_path)
    program = job.compared_steps()
    job.close()
    ctl, _ = compare.gaps(control, sound)
    prog, _ = compare.gaps(program, sound)
    assert all(math.isfinite(v) for v in ctl.values()), ctl
    assert ctl["grad_diff"] >= 3 * prog["grad_diff"], (ctl, prog)
    assert compare.judge(prog, cell.limits)[0] is True, prog
    if "grad_diff" in cell.limits:
        assert compare.judge(ctl, cell.limits)[0] is False, ctl
