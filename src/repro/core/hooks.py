"""Integration of the monitoring daemon with JAX training/serving loops.

``TrainMonitor`` is what an application (or our launcher) embeds: it owns
the hpcmd daemon, registers the standard source set, extracts static
per-step cost figures from the compiled executable, and receives one cheap
callback per step.  Sampling stays on the daemon's clock-aligned interval,
so per-step overhead is two integer updates — the paper's negligible-
overhead requirement (validated by benchmarks/overhead.py).
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Dict, Optional

from repro.core import hlo_cost
from repro.core.daemon import DaemonConfig, Hpcmd, JobManifest
from repro.core.derived import HardwareSpec, local_hardware, roofline_terms
from repro.core.sources import (CollectiveSource, DeviceSource, EnvSource,
                                PipelineSource, PipelineStats, ProcSource,
                                StaticStepCost, StepClock, XlaCostSource)


class TrainMonitor:
    """Job-side monitoring harness.

    In-loop (deterministic) mode: call :meth:`on_step` every step; the
    monitor ticks the daemon when the sampling interval elapses.
    Thread mode: :meth:`start` runs the daemon loop in the background.
    """

    def __init__(self, workdir: os.PathLike, manifest: JobManifest,
                 host: Optional[str] = None, interval_s: float = 5.0,
                 hw: Optional[HardwareSpec] = None, enabled: bool = True,
                 align_to_clock: bool = True) -> None:
        self.enabled = enabled
        self.workdir = Path(workdir)
        self.manifest = manifest
        # peaks of the device this process runs on unless given; None on
        # the CPU, which then gets no mfu or roofline figures
        self.hw = hw if hw is not None else local_hardware()
        self.clock = StepClock()
        self.pipeline_stats = PipelineStats()
        host = host or "host0"
        spool_dir = self.workdir / "spool" / host
        cfg = DaemonConfig(interval_s=interval_s,
                           align_to_clock=align_to_clock)
        self.daemon = Hpcmd(spool_dir, cfg, host=host, manifest=manifest)
        self.cost_source = XlaCostSource(self.clock, self.hw)
        self.daemon.add_source(self.cost_source)
        self.daemon.add_source(DeviceSource())
        self.daemon.add_source(ProcSource())
        self.daemon.add_source(PipelineSource(self.pipeline_stats))
        self.daemon.add_source(EnvSource(extra={
            "app": manifest.app, "shape": manifest.shape,
            "num_hosts": manifest.num_hosts,
            "num_chips": manifest.num_chips,
            "mesh": manifest.mesh_shape}))
        # persist the manifest for the aggregator / scheduler integration
        manifest.save(self.workdir / "manifests" / f"{manifest.job_id}.json")
        self._next_tick = 0.0
        self.static_cost: Optional[StaticStepCost] = None
        self.roofline: Optional[Dict[str, float]] = None

    # ------------------------------------------------------------- compile
    def register_compiled(self, compiled, tokens_per_step: int = 0,
                          num_chips: Optional[int] = None) -> Dict[str, float]:
        """Extract static per-step cost figures from a compiled step.

        Returns the figure dict, with the roofline terms where the
        device's peaks are known.
        """
        chips = num_chips or self.manifest.num_chips
        # loop-aware static analysis (core/hlo_cost.py): exact per-step
        # FLOPs / HBM traffic / collective bytes off the executable.
        cost = hlo_cost.analyze_hlo(compiled.as_text())
        static = StaticStepCost(
            flops=cost.flops, bytes=cost.traffic_bytes,
            collective_bytes=cost.collective_bytes,
            num_chips=chips, tokens_per_step=tokens_per_step)
        self.static_cost = static
        self.cost_source.set_cost(static)
        if self.enabled:
            self.daemon.add_source(CollectiveSource(cost.as_fields()))
        figures = {"flops": cost.flops, "bytes": cost.traffic_bytes,
                   "collective_bytes": cost.collective_bytes}
        if self.hw is not None:
            self.roofline = roofline_terms(
                cost.flops * chips, cost.traffic_bytes * chips,
                cost.collective_bytes * chips, chips, self.hw).as_dict()
            figures.update(self.roofline)
        return figures

    def set_static_cost(self, cost: StaticStepCost) -> None:
        """Direct injection (multi-host simulation / tests)."""
        self.static_cost = cost
        self.cost_source.set_cost(cost)

    # ---------------------------------------------------------------- steps
    def on_step(self, step: int, loss: float = float("nan"),
                tokens: int = 0, now: Optional[float] = None) -> None:
        if not self.enabled:
            return
        now = time.time() if now is None else now
        self.clock.record(step, tokens=tokens, loss=loss, ts=now)
        if now >= self._next_tick:
            self.daemon.tick(now)
            self._next_tick = self.daemon.next_sample_time(now)

    def on_batch_fetched(self, tokens: int, wait_s: float) -> None:
        if self.enabled:
            self.pipeline_stats.on_batch(tokens, wait_s)

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        if self.enabled:
            self.daemon.start()

    def stop(self) -> None:
        if self.enabled:
            self.daemon.stop(final_tick=True)

    def suspended(self):
        return self.daemon.suspended()

    def __enter__(self) -> "TrainMonitor":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


def load_manifests(workdir: os.PathLike) -> Dict[str, JobManifest]:
    """Read every job manifest the launcher has written under workdir."""
    out: Dict[str, JobManifest] = {}
    mdir = Path(workdir) / "manifests"
    if mdir.is_dir():
        for p in sorted(mdir.glob("*.json")):
            man = JobManifest.load(p)
            if man is not None:
                out[man.job_id] = man
    return out
