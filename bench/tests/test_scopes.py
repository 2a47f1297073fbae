"""The trace read by the program's names, on a small synthetic trace and
HLO text."""

import pytest

from harness import scopes, trace

MS = 1_000_000  # ns

HLO = """\
HloModule jit_train_step

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %exp.1 = f32[4]{0} exponential(%p), metadata={op_name="jit(train_step)/jvp(model.layers)/while/body/model.block/model.ssm/exp"}
}

%wrapped_reduce_computation (q: f32[4]) -> f32[4] {
  %q = f32[4]{0} parameter(0)
  ROOT %rw.1 = f32[4]{0} reduce-window(%q), metadata={op_name="jit(train_step)/transpose(jvp(model.layers))/while/body/checkpoint/model.block/model.attention/cumsum"}
}

%body.1 (c: f32[4]) -> f32[4] {
  %c = f32[4]{0} parameter(0)
  %fusion.2 = f32[4]{0} fusion(%c), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(model.layers)/while/body/model.block/model.ssm/exp"}
  ROOT %wrapped_reduce = f32[4]{0} fusion(%fusion.2), kind=kLoop, calls=%wrapped_reduce_computation
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %while.1 = f32[4]{0} while(%a), body=%body.1, metadata={op_name="jit(train_step)/jvp(model.layers)/while"}
  %dot.3 = f32[4]{0} dot(%while.1, %a), metadata={op_name="jit(train_step)/transpose(jvp(model.loss))/model.loss/while/body/bcv,bcd->vd/dot_general"}
  %copy.4 = f32[4]{0} copy(%dot.3)
  %add.6 = f32[4]{0} add(%copy.4, %a), metadata={op_name="jit(train_step)/add_any"}
  %copy-start.7 = (f32[4]{0}, f32[4]{0}, u32[]{:S(2)}) copy-start(%a)
  %copy-done.8 = f32[4]{0} copy-done(%copy-start.7)
  ROOT %fusion.5 = f32[4]{0} fusion(%add.6, %copy-done.8), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/optim.update/mul"}
}
"""


def test_innermost_scope_is_the_last_in_the_path():
    assert scopes.innermost_scope(
        "jit(f)/transpose(jvp(model.layers))/while/body/checkpoint/"
        "model.block/model.ssm/dot_general") == "model.ssm"
    assert scopes.innermost_scope("jit(f)/jvp(model.layers)/while") \
        == "model.layers"
    # a name that only starts or ends like a scope is none
    assert scopes.innermost_scope("jit(f)/model.ssmx/my.model.loss") is None


def test_instruction_scopes_nested_fused_and_unscoped():
    own, s = scopes.instruction_scopes(HLO)
    assert s["fusion.2"] == own["fusion.2"] == "model.ssm"
    assert s["while.1"] == own["while.1"] == "model.layers"
    assert s["dot.3"] == own["dot.3"] == "model.loss"
    assert s["fusion.5"] == own["fusion.5"] == "optim.update"
    # an op_name outside every scope is the program's unscoped work
    assert s["add.6"] == own["add.6"] == scopes.UNSCOPED
    # no metadata of its own: unscoped by its own op_name; inherited, the
    # scope of the root of the computation it calls, else of its direct
    # operands
    assert own["wrapped_reduce"] == own["copy.4"] == scopes.UNSCOPED
    assert s["wrapped_reduce"] == "model.attention"
    assert s["copy.4"] == "model.loss"
    # nothing from further away: the copy's operand is a parameter, and
    # the copy's end has only the copy's start, which has no scope either
    assert s["copy-start.7"] == scopes.UNSCOPED
    assert s["copy-done.8"] == scopes.UNSCOPED
    assert s["a"] == scopes.UNSCOPED


def test_device_time_goes_to_the_innermost_scope_of_each_operation():
    device = {"/device:TPU:0": [
        ("%while.1 = f32[4] while(...)", 0, 60 * MS),
        ("fusion.2", 5 * MS, 20 * MS),              # inside the while
        ("wrapped_reduce", 30 * MS, 10 * MS),       # inside the while
        ("dot.3", 60 * MS, 15 * MS),
        ("copy.4", 75 * MS, 5 * MS),
        ("add.6", 80 * MS, 5 * MS),
        ("fusion.5", 85 * MS, 10 * MS),
        ("not_in_hlo.9", 95 * MS, 5 * MS),
        ("dot.3", 150 * MS, 10 * MS)]}              # after the window
    host = [("bench.window", 0, 100 * MS)]
    r = scopes.reduce(device, host, HLO)
    got = r["scopes"]
    assert set(got) == set(scopes.SCOPES) | {scopes.UNSCOPED}
    assert got["model.layers"] == pytest.approx(0.030)   # 60 - 20 - 10
    assert got["model.ssm"] == pytest.approx(0.020)
    assert got["model.attention"] == pytest.approx(0.010)
    assert got["model.loss"] == pytest.approx(0.020)     # dot + copy
    assert got["optim.update"] == pytest.approx(0.010)
    assert got[scopes.UNSCOPED] == pytest.approx(0.010)  # add + unknown
    assert got["model.mlp"] == 0.0
    # by own op_name alone, the reduce-window and the copy are unscoped
    got_own = r["scopes_own"]
    assert got_own["model.attention"] == 0.0
    assert got_own["model.loss"] == pytest.approx(0.015)
    assert got_own[scopes.UNSCOPED] == pytest.approx(0.025)
    assert sum(got_own.values()) == pytest.approx(sum(got.values()))
    # the scopes share out exactly the device time the ops have
    own = sum(t for _, t in trace.self_times(
        trace._clip(device["/device:TPU:0"], 0, 100 * MS)))
    assert sum(got.values()) == pytest.approx(own)


def test_program_spans_with_self_time():
    host = [("bench.window", 0, 100 * MS),
            ("repro.monitor.tick", 10 * MS, 10 * MS),
            ("repro.monitor.sample", 11 * MS, 3 * MS),
            ("repro.monitor.sample", 15 * MS, 4 * MS),
            ("repro.pipeline.wait", 40 * MS, 2 * MS),
            ("repro.pipeline.wait", 60 * MS, 1 * MS),
            ("repro.pipeline.wait", 120 * MS, 1 * MS),   # after the window
            ("bench.on_step", 9 * MS, 12 * MS),
            ("TransferFromDevice", 30 * MS, 5 * MS)]
    sp = scopes.program_spans(host, 0, 100 * MS)
    assert set(sp) == {"repro.monitor.tick", "repro.monitor.sample",
                       "repro.pipeline.wait"}
    assert sp["repro.monitor.tick"] == {
        "seconds": pytest.approx(0.010), "count": 1,
        "self_seconds": pytest.approx(0.003)}
    assert sp["repro.monitor.sample"]["count"] == 2
    assert sp["repro.monitor.sample"]["self_seconds"] == pytest.approx(0.007)
    assert sp["repro.pipeline.wait"]["seconds"] == pytest.approx(0.003)
    assert sp["repro.pipeline.wait"]["count"] == 2


def test_idle_gaps_named_by_what_covers_most_of_them():
    device = {"/device:TPU:0": [("dot.3", 0, 10 * MS),
                                ("dot.3", 20 * MS, 20 * MS),
                                ("dot.3", 48 * MS, 20 * MS),
                                ("dot.3", 74 * MS, 20 * MS)]}
    host = [("bench.window", 0, 100 * MS),
            ("bench.on_step", 9 * MS, 12 * MS),
            # gap 10..20: the tick and its sample cover all of it, the
            # sample is the innermost; a runtime event covers less
            ("repro.monitor.tick", 9 * MS, 12 * MS),
            ("repro.monitor.sample", 10 * MS, 10 * MS),
            ("ReadSyncFlag", 10 * MS, 2 * MS),
            # gap 40..48: a runtime transfer, no program span
            ("TransferFromDevice", 39 * MS, 6 * MS),
            ("$python_frame", 0, 100 * MS),
            # gap 68..74: the pipeline's wait covers 1 ms, a device put
            # the rest
            ("repro.pipeline.wait", 67 * MS, 2 * MS),
            ("DevicePutWithSharding", 69 * MS, 5 * MS)]
    # gap 94..100: nothing
    r = scopes.reduce(device, host, HLO)
    assert r["idle_gaps_program"] == [
        ["repro.monitor.sample", pytest.approx(0.010)],
        ["TransferFromDevice", pytest.approx(0.008)],
        ["DevicePutWithSharding", pytest.approx(0.006)],
        ["host", pytest.approx(0.006)]]
    # the same gaps, in the same order, as the existing reduction's
    old = trace.reduce(device, host)
    assert [g for _, g in old["idle_gaps"]] == [
        g for _, g in r["idle_gaps_program"]]


def test_existing_reduction_is_unchanged_by_program_spans():
    """``trace.reduce`` reads only the ``bench.*`` spans: the program's
    spans and the runtime's events in the same trace change none of its
    keys."""
    device = {"/device:TPU:0": [("fusion.1", 10 * MS, 40 * MS),
                                ("fusion.2", 20 * MS, 10 * MS),
                                ("copy.3", 60 * MS, 30 * MS)]}
    bench = [("bench.window", 0, 100 * MS),
             ("bench.input", 0, 8 * MS),
             ("bench.on_step", 50 * MS, 10 * MS),
             ("bench.dispatch", 10 * MS, 2 * MS)]
    program = [("repro.monitor.tick", 50 * MS, 9 * MS),
               ("repro.pipeline.wait", 0, 7 * MS),
               ("TransferFromDevice", 90 * MS, 5 * MS)]
    assert trace.reduce(device, bench + program) == trace.reduce(device,
                                                                 bench)


def test_nothing_to_read_gives_nothing():
    assert scopes.reduce({}, [("bench.window", 0, MS)], HLO) == {}
    assert scopes.reduce({"d": [("dot.3", 0, MS)]}, [], HLO) == {}
    # a program without scopes or spans: all device time is unscoped
    r = scopes.reduce({"d": [("dot.3", 0, MS)]},
                      [("bench.window", 0, MS)], "")
    assert r["scopes"][scopes.UNSCOPED] == pytest.approx(0.001)
    assert r["program_spans"] == {}
    assert r["idle_gaps_program"] == []
