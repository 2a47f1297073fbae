"""Daemon behaviour: clock alignment, suspend, idle-node policy, one-shot
sources."""

from repro.core.daemon import DaemonConfig, Hpcmd, JobManifest
from repro.core.schema import parse_line
from repro.core.sources import MetricSource


class DummySource(MetricSource):
    name = "dummy"
    kind = "perf"

    def __init__(self):
        self.calls = 0

    def collect(self, now):
        self.calls += 1
        return {"v": self.calls}


class OneShot(MetricSource):
    name = "meta"
    kind = "meta"
    once = True

    def collect(self, now):
        return {"hello": 1}


class Exploding(MetricSource):
    name = "boom"
    kind = "perf"

    def collect(self, now):
        raise RuntimeError("sensor failure")


def mk(tmp_path, manifest=True, **cfg):
    d = Hpcmd(tmp_path / "spool",
              DaemonConfig(align_to_clock=False, interval_s=1.0, **cfg),
              host="n0",
              manifest=JobManifest(job_id="j1") if manifest else None)
    return d


def read_records(tmp_path):
    recs = []
    for seg in sorted((tmp_path / "spool").glob("segment-*.log")):
        for line in seg.read_text().splitlines():
            rec = parse_line(line)
            if rec:
                recs.append(rec)
    return recs


def test_tick_writes_records(tmp_path):
    d = mk(tmp_path)
    d.add_source(DummySource())
    assert d.tick(100.0) == 1
    assert d.tick(101.0) == 1
    recs = read_records(tmp_path)
    assert len(recs) == 2 and recs[0].job == "j1"


def test_idle_node_not_monitored(tmp_path):
    d = mk(tmp_path, manifest=False)
    d.add_source(DummySource())
    assert d.node_state == "idle"
    assert d.tick(100.0) == 0
    d.set_manifest(JobManifest(job_id="j2"))
    assert d.tick(101.0) == 1
    assert read_records(tmp_path)[0].job == "j2"


def test_suspend_resume(tmp_path):
    d = mk(tmp_path)
    src = DummySource()
    d.add_source(src)
    with d.suspended():
        assert d.tick(100.0) == 0
    assert d.tick(101.0) == 1
    assert src.calls == 1


def test_once_source_emits_once_per_job(tmp_path):
    d = mk(tmp_path)
    d.add_source(OneShot())
    assert d.tick(1.0) == 1
    assert d.tick(2.0) == 0
    d.set_manifest(JobManifest(job_id="j2"))  # new job -> re-emit
    assert d.tick(3.0) == 1


def test_source_errors_are_contained(tmp_path):
    d = mk(tmp_path)
    d.add_source(Exploding())
    d.add_source(DummySource())
    assert d.tick(1.0) == 2  # error record + real record
    recs = read_records(tmp_path)
    assert any("source_error" in r.fields for r in recs)


def test_clock_alignment():
    d = Hpcmd("/tmp/unused-spool-align",
              DaemonConfig(align_to_clock=True, interval_s=600.0),
              host="n0", manifest=JobManifest(job_id="j"))
    # paper: samples align to wall-clock multiples of the interval
    assert d.next_sample_time(1000.0) == 1200.0
    assert d.next_sample_time(1200.0) == 1800.0
    assert d.next_sample_time(1799.9) == 1800.0


def test_manifest_roundtrip(tmp_path):
    man = JobManifest(job_id="cobra.42", user="alice", app="gemma2-27b",
                      num_hosts=64, num_chips=256, extra={"large_memory": "1"})
    man.save(tmp_path / "m.json")
    got = JobManifest.load(tmp_path / "m.json")
    assert got == man
    assert JobManifest.load(tmp_path / "missing.json") is None


def test_hardware_keyed_by_device_kind():
    import pytest
    from repro.core.derived import TPU_V5E, hardware_for, local_hardware
    assert hardware_for("tpu", "TPU v5 lite") is TPU_V5E
    assert hardware_for("cpu", "cpu") is None
    assert local_hardware() is None          # the tests run on the CPU
    with pytest.raises(KeyError):
        hardware_for("tpu", "TPU v99")


def test_perf_record_has_mfu_only_against_known_peaks():
    from repro.core.derived import TPU_V5E
    from repro.core.sources import StaticStepCost, StepClock, XlaCostSource
    fields = {}
    for hw in (None, TPU_V5E):
        clock = StepClock()
        src = XlaCostSource(clock, hw)
        src.set_cost(StaticStepCost(flops=1e12, bytes=1e10))
        clock.record(1, ts=10.0)
        src.collect(10.0)                    # anchors the window
        clock.record(3, ts=12.0)
        fields[hw] = src.collect(12.0)
    assert "mfu" not in fields[None] and fields[None]["gflops"] > 0
    assert fields[TPU_V5E]["mfu"] > 0


def test_monitor_on_cpu_writes_no_roofline(tmp_path):
    import pytest
    from repro.core.hooks import TrainMonitor

    class NoText:
        def as_text(self):
            raise RuntimeError("executable cannot be serialized")

    mon = TrainMonitor(tmp_path, JobManifest(job_id="j"), enabled=False)
    assert mon.hw is None
    with pytest.raises(RuntimeError):
        mon.register_compiled(NoText())
