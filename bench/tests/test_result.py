"""The shape of a run's last line, and the refusal to run without a chip."""

import pytest

import tiny
from harness import cells, device


@pytest.fixture(scope="module")
def plain():
    return tiny.run("mamba2-780m.train")


def test_last_line_shape(plain):
    rc, r, _ = plain
    assert rc == 0
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for m in cells.benchmark()["end_to_end"]:
        if m["name"] in r["metrics"]:
            assert r["metrics"][m["name"]]["unit"] == m["unit"]
            assert r["metrics"][m["name"]]["value"] > 0
    assert set(r["device"]) >= {"platform", "kind", "count",
                                "memory_peak_bytes"}
    for chk in r["checks"].values():
        assert set(chk) == {"value", "limit"}


def test_sound_tiny_run_is_correct(plain):
    assert plain[1]["correct"] is True, plain[1]["checks"]


@pytest.mark.parametrize("workload", ["mamba2-780m.train",
                                      "hymba-1.5b-32l.train-4chip"])
def test_traced_line_carries_per_layer_metrics(workload):
    rc, r, _ = tiny.run(workload, trace=1)
    assert rc == 0
    assert r["device"]["count"] == cells.find(workload).workload["chips"]
    names = {m["name"] for m in cells.find(workload).per_layer}
    assert set(r["metrics"]) <= names
    assert "step_mfu" in r["metrics"]          # no device trace on the CPU
    assert 0 < r["metrics"]["step_mfu"]["value"] < 100


def test_no_chip_no_result():
    rc, r, out = tiny.run("mamba2-780m.train", check=device.check)
    assert rc != 0 and r is None and out == ""


def test_unknown_workload_no_result():
    rc, r, out = tiny.run("mamba2-780m.nothing")
    assert rc != 0 and out == ""


@pytest.mark.parametrize("bad", [float("nan"), float("inf"), None])
def test_a_number_that_is_not_finite_fails(bad):
    from harness import compare
    correct, checks = compare.judge({"change_gap": bad}, {"change_gap": 0.1})
    assert correct is False
    assert isinstance(checks["change_gap"]["value"], str)


def _sound():
    import numpy as np
    return {"losses": [1.0], "grad": {"a": 1.0, "b": 1.0},
            "grad_sample": {"a": np.ones(3), "b": np.ones(3)},
            "change": {"a": 1.0, "b": 1.0}}


def test_a_leaf_that_is_not_finite_reads_inf():
    from harness import compare
    ref = _sound()
    prog = dict(ref, change={"a": 1.0, "b": float("nan")})
    assert compare.gaps(prog, ref)[0]["change_gap"] == float("inf")


def test_a_sampled_gradient_that_is_not_finite_reads_inf():
    import numpy as np
    from harness import compare
    ref = _sound()
    prog = dict(ref, grad_sample={"a": np.ones(3),
                                  "b": np.array([1.0, np.nan, 1.0])})
    assert compare.gaps(prog, ref)[0]["grad_diff"] == float("inf")
    assert compare.gaps(ref, ref)[0]["grad_diff"] == 0.0
