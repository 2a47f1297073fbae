"""The hpcmd daemon analog (paper §4.2).

One daemon per host process.  It samples its registered sources at
clock-aligned intervals (synchronization across hosts via the system
clock, *zero* inter-host communication), attributes samples to the job
described by the launcher-written manifest (the SLURM-integration analog),
writes key=value lines to the local spool, and can be suspended so an
external profiler gets the "counters" to itself.

Per the paper's policy, hosts without a (single) job are not monitored
unless ``monitor_idle`` is set.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import socket
import threading
import time
from dataclasses import asdict, dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

from repro.core.schema import MetricRecord, encode_line
from repro.core.sources import MetricSource
from repro.core.telemetry import Telemetry
from repro.core.transport import Spool


@dataclass
class JobManifest:
    """Written by the launcher; read by the daemon (SLURM analog)."""

    job_id: str
    user: str = "unknown"
    app: str = "unknown"          # architecture / application name
    shape: str = ""               # input-shape id
    num_hosts: int = 1
    num_chips: int = 1
    mesh_shape: str = ""
    started_ts: float = 0.0
    extra: Dict[str, str] = field(default_factory=dict)

    def save(self, path: os.PathLike) -> None:
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        tmp = p.with_suffix(".tmp")
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(asdict(self), f, indent=1)
        os.replace(tmp, p)

    @classmethod
    def load(cls, path: os.PathLike) -> Optional["JobManifest"]:
        try:
            with open(path, encoding="utf-8") as f:
                d = json.load(f)
            return cls(**d)
        except (OSError, ValueError, TypeError):
            return None


@dataclass
class DaemonConfig:
    interval_s: float = 600.0     # paper: one sample per 10 minutes
    align_to_clock: bool = True   # paper: sync across nodes via system clock
    monitor_idle: bool = False    # paper: skip idle/shared nodes
    max_segment_bytes: int = 1 << 20
    spool_fsync: bool = False     # fsync spool writes (crash-safe samples)


class Hpcmd:
    """The monitoring daemon.

    Deterministic embedding: call :meth:`tick` directly (tests, in-loop
    usage).  Background embedding: :meth:`start` / :meth:`stop` run the
    same tick loop in a daemon thread.

    Each sampling round is a ``repro.monitor.tick`` span with one
    ``repro.monitor.sample`` child per source (attribute ``kind``), in
    the daemon's ``telemetry``: written into a running profiler
    session's trace, free otherwise.
    """

    def __init__(self, spool_dir: os.PathLike,
                 config: Optional[DaemonConfig] = None,
                 host: Optional[str] = None,
                 manifest: Optional[JobManifest] = None) -> None:
        self.config = config or DaemonConfig()
        self.host = host or socket.gethostname()
        self.manifest = manifest
        self.telemetry = Telemetry(node=self.host)
        self.spool = Spool(spool_dir,
                           max_segment_bytes=self.config.max_segment_bytes,
                           fsync=self.config.spool_fsync)
        self.sources: List[MetricSource] = []
        self._once_done: set = set()
        self._suspended = 0
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.samples_written = 0

    # ------------------------------------------------------------- sources
    def add_source(self, source: MetricSource) -> "Hpcmd":
        self.sources.append(source)
        return self

    # ----------------------------------------------------------- job state
    def set_manifest(self, manifest: Optional[JobManifest]) -> None:
        with self._lock:
            self.manifest = manifest
            self._once_done.clear()  # new job -> re-emit one-shot meta

    def load_manifest(self, path: os.PathLike) -> None:
        self.set_manifest(JobManifest.load(path))

    @property
    def node_state(self) -> str:
        return "allocated" if self.manifest is not None else "idle"

    # ------------------------------------------------------------- suspend
    def suspend(self) -> None:
        """Paper §4.2: users may suspend hpcmd to get exclusive access to
        hardware counters for profilers (VTune/PAPI analog)."""
        with self._lock:
            self._suspended += 1

    def resume(self) -> None:
        with self._lock:
            self._suspended = max(0, self._suspended - 1)

    @contextlib.contextmanager
    def suspended(self):
        self.suspend()
        try:
            yield
        finally:
            self.resume()

    @property
    def is_suspended(self) -> bool:
        return self._suspended > 0

    # ---------------------------------------------------------------- tick
    def tick(self, now: Optional[float] = None) -> int:
        """Run one sampling round.  Returns #records written."""
        now = time.time() if now is None else now
        if self.is_suspended:
            return 0
        if self.manifest is None and not self.config.monitor_idle:
            return 0
        job = self.manifest.job_id if self.manifest else "idle"
        written = 0
        with self.telemetry.span("repro.monitor.tick") as tick:
            for src in self.sources:
                if src.once and id(src) in self._once_done:
                    continue
                with tick.child("repro.monitor.sample", {"kind": src.kind}):
                    fields = src.safe_collect(now)
                    if fields is None:
                        continue
                    if src.once:
                        self._once_done.add(id(src))
                    rec = MetricRecord(ts=now, host=self.host, job=job,
                                       kind=src.kind, fields=fields)
                    self.spool.write_line(encode_line(rec))
                    written += 1
        self.samples_written += written
        return written

    def next_sample_time(self, now: Optional[float] = None) -> float:
        now = time.time() if now is None else now
        iv = self.config.interval_s
        if not self.config.align_to_clock:
            return now + iv
        return (math.floor(now / iv) + 1) * iv

    # ----------------------------------------------------------- threading
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()

        def _loop() -> None:
            while not self._stop.is_set():
                target = self.next_sample_time()
                while not self._stop.is_set():
                    delay = target - time.time()
                    if delay <= 0:
                        break
                    self._stop.wait(min(delay, 0.25))
                if self._stop.is_set():
                    break
                self.tick(target)

        self._thread = threading.Thread(target=_loop, name="hpcmd",
                                        daemon=True)
        self._thread.start()

    def stop(self, final_tick: bool = True) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        if final_tick:
            self.tick()
        self.spool.close()
