"""Stable names on the training path (docs/observability.md): the model's
and the optimizer's named scopes in the compiled train step, and the
monitor's and the input pipeline's spans in a profiler session's trace."""

import collections
import glob
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs import get_arch, reduced
from repro.core.daemon import DaemonConfig, Hpcmd, JobManifest
from repro.core.hooks import TrainMonitor
from repro.core.sources import MetricSource
from repro.core.telemetry import NULL_SPAN, Telemetry
from repro.data import Pipeline, SyntheticSource
from repro.models import Model, ModelOptions, make_batch
from repro.optim import AdamW, OptimizerConfig
from repro.train import StepConfig, make_train_step

SCOPES = ("model.embed", "model.layers", "model.block", "model.ssm",
          "model.attention", "model.mlp", "model.moe", "model.loss",
          "optim.update")
_SCOPE = re.compile(r"(?<![\w.])(%s)(?![\w.])"
                    % "|".join(re.escape(s) for s in SCOPES))
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\S+\s+"
                          r"([\w\-]+)\(")


def _innermost(line):
    op = re.search(r'op_name="([^"]*)"', line)
    found = _SCOPE.findall(op.group(1)) if op else []
    return (found[-1] if found else None), (op.group(1) if op else "")


@pytest.fixture(scope="module")
def step_text():
    """The compiled train step's HLO text of a reduced architecture, with
    KV blocks small enough that attention takes the flash custom VJP and
    the cross entropy its chunked custom VJP."""
    cache = {}

    def get(aid):
        if aid not in cache:
            cfg = reduced(get_arch(aid))
            model = Model(cfg, options=ModelOptions(attn_chunk=16))
            params = model.init(jax.random.PRNGKey(0))
            opt = AdamW(OptimizerConfig())
            step = make_train_step(model, opt, StepConfig(ce_seq_chunk=32))
            batch = make_batch(cfg, seq_len=64, batch=2, kind="train")
            cache[aid] = jax.jit(step).lower(
                params, opt.init(params), None, batch).compile().as_text()
        return cache[aid]
    return get


@pytest.mark.parametrize("aid,layers", [
    ("mamba2-780m", {"model.ssm"}),
    ("hymba-1.5b", {"model.ssm", "model.attention", "model.mlp"}),
    ("nemotron-3-nano-30b-a3b", {"model.ssm", "model.attention",
                                 "model.moe"})])
def test_every_matmul_of_the_train_step_is_in_a_scope(step_text, aid,
                                                      layers):
    scoped = collections.defaultdict(list)
    seen = set()
    for line in step_text(aid).splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        scope, op_name = _innermost(line)
        seen.add(scope)
        if m.group(2) in ("dot", "convolution"):
            assert scope is not None, line.strip()[:300]
            scoped[scope].append(op_name)
    assert layers | {"model.loss"} <= set(scoped)
    assert {"model.embed", "model.layers", "model.block",
            "optim.update"} <= seen
    # the custom-VJP backward rules: the chunked cross entropy's (its
    # table gradient, tied or not) and, for attention, the flash
    # backward's dV
    assert any("bcv,bcd->vd" in n or "bcd,bcv->dv" in n
               for n in scoped["model.loss"])
    if "model.attention" in layers:
        assert any("bhgqk,bhgqd->bkhd" in n
                   for n in scoped["model.attention"])


class _Counter(MetricSource):
    name = "counter"
    kind = "perf"

    def collect(self, now):
        return {"v": 1}


def _host_events(trace_dir):
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out[e.name].append(dict(e.stats))
    return out


def test_monitor_and_pipeline_spans_reach_the_profiler_trace(tmp_path):
    mon = TrainMonitor(tmp_path / "job", JobManifest(job_id="j1"),
                       host="h0", interval_s=60.0, align_to_clock=False)
    pipe = Pipeline(SyntheticSource(reduced(get_arch("mamba2-780m")),
                                    seq_len=8, batch=2),
                    stats=mon.pipeline_stats)
    trace_dir = tmp_path / "trace"
    jax.profiler.start_trace(str(trace_dir))
    try:
        pipe.next()
        mon.on_step(1, loss=1.0, tokens=16)      # the first step ticks
    finally:
        jax.profiler.stop_trace()
        pipe.close()
    mon.stop()
    events = _host_events(trace_dir)
    assert len(events["repro.monitor.tick"]) == 1
    kinds = {s.get("kind") for s in events["repro.monitor.sample"]}
    assert {"device", "proc", "pipeline"} <= kinds
    assert len(events["repro.pipeline.wait"]) == 1
    # the tracers are off: their spans reached the trace and no ring
    for tracer in (mon.daemon.telemetry.tracer, pipe.telemetry.tracer):
        assert tracer.stats()["spans_started"] == 0
        assert tracer.finished_traces() == []


def test_a_recording_tracer_also_writes_into_a_session(tmp_path):
    tel = Telemetry(tracing=True)
    trace_dir = tmp_path / "trace"
    jax.profiler.start_trace(str(trace_dir))
    try:
        with tel.span("repro.outer") as outer:
            outer.child("repro.inner", {"kind": "k"}).finish()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(trace_dir)
    assert len(events["repro.outer"]) == 1
    assert [s.get("kind") for s in events["repro.inner"]] == ["k"]
    (tid,) = tel.tracer.finished_traces()
    assert [s["name"] for s in tel.tracer.trace(tid)] == ["repro.inner",
                                                          "repro.outer"]


def test_no_session_and_tracing_off_hands_out_no_spans(tmp_path):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert Telemetry().span("repro.monitor.tick") is NULL_SPAN
    d = Hpcmd(tmp_path / "spool", DaemonConfig(align_to_clock=False),
              host="h0", manifest=JobManifest(job_id="j1"))
    d.add_source(_Counter())
    assert d.tick() == 1
    d.stop(final_tick=False)
    assert d.telemetry.tracer.stats()["spans_started"] == 0
    assert d.telemetry.tracer.finished_traces() == []


def test_profiler_sink_never_imports_jax():
    """The fleet's processes run without JAX: a sink that finds it not
    loaded records as before and loads nothing."""
    code = ("import sys\n"
            "from repro.core.telemetry import NULL_SPAN, Telemetry\n"
            "on = Telemetry(tracing=True)\n"
            "on.span('repro.x').finish()\n"
            "assert len(on.tracer.finished_traces()) == 1\n"
            "assert Telemetry().span('repro.x') is NULL_SPAN\n"
            "assert 'jax' not in sys.modules\n")
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, PYTHONPATH=src)
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=60)
