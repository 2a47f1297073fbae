"""Metric sources — the hpcmd data-source layer (paper §4.1), TPU-adapted.

Each source is a cheap, *never-raising* callable that returns one bundle of
fields per sample.  The daemon owns scheduling; sources own measurement.
Mapping to the paper (see DESIGN.md §2 for the full table):

* ``XlaCostSource``   — CPU core/uncore PMU analog (FLOPs, bytes, AI, MFU)
* ``CollectiveSource``— network-counter analog (ICI traffic)
* ``DeviceSource``    — nvidia-smi analog (device memory occupancy)
* ``ProcSource``      — ps/numastat//proc analog (RSS, threads, loadavg)
* ``PipelineSource``  — I/O analog (data-pipeline throughput and stalls)
* ``EnvSource``       — job environment capture (one-shot meta record)
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Tuple

from repro.core import derived
from repro.core.derived import HardwareSpec

Fields = Dict[str, object]


class MetricSource:
    """Base class.  ``collect`` must be cheap and must not raise."""

    name = "base"
    kind = "meta"
    once = False  # one-shot sources emit a single record then go quiet

    def collect(self, now: float) -> Optional[Fields]:
        raise NotImplementedError

    def safe_collect(self, now: float) -> Optional[Fields]:
        try:
            return self.collect(now)
        except Exception as exc:  # noqa: BLE001 — monitoring must not kill jobs
            return {"source_error": f"{type(exc).__name__}: {exc}",
                    "source_name": self.name}


# --------------------------------------------------------------------- clock

@dataclass
class StepEvent:
    ts: float
    step: int
    tokens: int
    loss: float
    cum_tokens: int = 0


class StepClock:
    """Shared step progress state, fed by the training/serving loop hook.

    Samples are differenced between daemon ticks, so the daemon sees the
    *rate* over its own sampling window — matching hpcmd's interval
    semantics rather than per-step noise.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._events: Deque[StepEvent] = deque(maxlen=4096)
        self.last_step = -1
        self.last_loss = float("nan")
        self.total_tokens = 0
        self._last_sample: Optional[StepEvent] = None

    def record(self, step: int, tokens: int = 0,
               loss: float = float("nan"), ts: Optional[float] = None) -> None:
        with self._lock:
            self.total_tokens += tokens
            ev = StepEvent(ts if ts is not None else time.time(), step,
                           tokens, loss, cum_tokens=self.total_tokens)
            self._events.append(ev)
            self.last_step = step
            self.last_loss = loss

    def window(self, now: Optional[float] = None
               ) -> Optional[Tuple[StepEvent, StepEvent]]:
        """(previous-sample anchor, latest event); advances the anchor.

        When no new step events arrived since the last sample, a synthetic
        zero-progress window ending at ``now`` is returned — this is what
        makes hanging jobs *visible* (paper §5: livelocked processes keep
        "running" while GFLOP/s drops to zero).
        """
        with self._lock:
            if not self._events:
                return None
            latest = self._events[-1]
            prev = self._last_sample
            if prev is None:
                self._last_sample = latest
                return None
            if latest.ts <= prev.ts:
                t = now if now is not None else time.time()
                if t <= prev.ts:
                    return None
                return prev, StepEvent(t, prev.step, 0, prev.loss,
                                       cum_tokens=prev.cum_tokens)
            self._last_sample = latest
            return prev, latest


# ------------------------------------------------------------------ XLA cost

@dataclass
class StaticStepCost:
    """Per-step figures from the compiled executable (per chip)."""

    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    num_chips: int = 1
    tokens_per_step: int = 0


class XlaCostSource(MetricSource):
    """PMU analog: achieved GFLOP/s, HBM GB/s, AI, MFU.

    The per-step FLOP/byte figures are static properties of the compiled
    step; runtime cost of this source is two clock reads per sample —
    the "negligible overhead" property the paper demands of hpcmd.
    """

    name = "xla_cost"
    kind = "perf"

    def __init__(self, clock: StepClock,
                 hw: Optional[HardwareSpec]) -> None:
        self.clock = clock
        self.hw = hw
        self.cost = StaticStepCost()

    def set_cost(self, cost: StaticStepCost) -> None:
        self.cost = cost

    def collect(self, now: float) -> Optional[Fields]:
        win = self.clock.window(now)
        if win is None:
            return None
        prev, latest = win
        dt = latest.ts - prev.ts
        dstep = latest.step - prev.step
        if dstep <= 0 or dt <= 0:
            # no forward progress in this window — still emit, the hang
            # detector keys off exactly this case
            fields = {"step": latest.step, "steps_per_s": 0.0,
                      "tokens_per_s": 0.0, "loss": latest.loss,
                      "gflops": 0.0, "gflops_per_chip": 0.0, "hbm_gbs": 0.0,
                      "ici_gbs": 0.0, "ai": 0.0, "step_time_s": 0.0}
            if self.hw is not None:
                fields["mfu"] = 0.0
            return fields
        step_time = dt / dstep
        c = self.cost
        fields = derived.perf_fields(
            c.flops * c.num_chips, c.bytes * c.num_chips,
            c.collective_bytes * c.num_chips, step_time, c.num_chips, self.hw)
        fields.update({
            "step": latest.step,
            "steps_per_s": dstep / dt,
            "tokens_per_s": (
                (latest.cum_tokens - prev.cum_tokens) / dt
                if latest.cum_tokens > prev.cum_tokens
                else dstep * c.tokens_per_step / dt),
            "loss": latest.loss,
        })
        return fields


class CollectiveSource(MetricSource):
    """Network-counter analog: static per-step collective mix from the HLO."""

    name = "collectives"
    kind = "net"
    once = True

    def __init__(self, coll_fields: Dict[str, float]) -> None:
        self._fields = dict(coll_fields)

    def collect(self, now: float) -> Optional[Fields]:
        return dict(self._fields)


# -------------------------------------------------------------------- device

class DeviceSource(MetricSource):
    """nvidia-smi analog: per-device memory occupancy via jax."""

    name = "device"
    kind = "device"

    def __init__(self, devices: Optional[List] = None) -> None:
        self._devices = devices

    def collect(self, now: float) -> Optional[Fields]:
        import jax
        devs = self._devices if self._devices is not None else jax.local_devices()
        in_use, limit, reporting = 0.0, 0.0, 0
        for d in devs:
            try:
                stats = d.memory_stats()
            except Exception:  # noqa: BLE001
                stats = None
            if not stats:
                continue
            reporting += 1
            in_use += float(stats.get("bytes_in_use", 0))
            limit += float(stats.get("bytes_limit", 0))
        fields: Fields = {
            "local_devices": len(devs),
            "devices_reporting": reporting,
            "hbm_bytes_in_use": in_use,
        }
        if limit:
            fields["hbm_bytes_limit"] = limit
            fields["hbm_frac_used"] = in_use / limit
        return fields


# ---------------------------------------------------------------------- proc

class ProcSource(MetricSource):
    """ps / /proc analog: host-side process metrics, stdlib only."""

    name = "proc"
    kind = "proc"

    def __init__(self, pid: Optional[int] = None) -> None:
        self.pid = pid or os.getpid()
        self._page = os.sysconf("SC_PAGE_SIZE")

    def collect(self, now: float) -> Optional[Fields]:
        fields: Fields = {"pid": self.pid}
        try:
            with open(f"/proc/{self.pid}/statm") as f:
                parts = f.read().split()
            fields["rss_bytes"] = int(parts[1]) * self._page
            fields["vsz_bytes"] = int(parts[0]) * self._page
        except OSError:
            pass
        try:
            with open(f"/proc/{self.pid}/stat") as f:
                stat = f.read()
            # field 20 (1-based) = num_threads; fields 14/15 = utime/stime
            after = stat.rsplit(")", 1)[1].split()
            fields["num_threads"] = int(after[17])
            tick = os.sysconf("SC_CLK_TCK")
            fields["cpu_seconds"] = (int(after[11]) + int(after[12])) / tick
        except (OSError, IndexError, ValueError):
            pass
        try:
            with open("/proc/loadavg") as f:
                fields["loadavg_1m"] = float(f.read().split()[0])
        except (OSError, ValueError):
            pass
        return fields


# ------------------------------------------------------------------ pipeline

class PipelineStats:
    """Counters owned by the data pipeline; source reports windowed deltas."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.batches = 0
        self.tokens = 0
        self.wait_s = 0.0

    def on_batch(self, tokens: int, wait_s: float) -> None:
        with self._lock:
            self.batches += 1
            self.tokens += tokens
            self.wait_s += wait_s

    def snapshot(self) -> Tuple[int, int, float]:
        with self._lock:
            return self.batches, self.tokens, self.wait_s


class PipelineSource(MetricSource):
    """I/O analog: data-pipeline throughput and input stalls."""

    name = "pipeline"
    kind = "pipeline"

    def __init__(self, stats: PipelineStats) -> None:
        self.stats = stats
        self._prev: Tuple[float, int, int, float] = (0.0, 0, 0, 0.0)

    def collect(self, now: float) -> Optional[Fields]:
        b, t, w = self.stats.snapshot()
        pt, pb, ptok, pw = self._prev
        self._prev = (now, b, t, w)
        dt = now - pt
        if pt == 0.0 or dt <= 0:
            return {"batches_total": b, "tokens_total": t,
                    "input_wait_s_total": round(w, 6)}
        return {
            "batches_total": b,
            "tokens_total": t,
            "input_wait_s_total": round(w, 6),
            "batches_per_s": (b - pb) / dt,
            "input_tokens_per_s": (t - ptok) / dt,
            "input_stall_frac": max(0.0, min(1.0, (w - pw) / dt)),
        }


# ----------------------------------------------------------------------- env

class EnvSource(MetricSource):
    """One-shot job metadata record (paper: job environment capture)."""

    name = "env"
    kind = "meta"
    once = True

    ENV_WHITELIST = ("SLURM_JOB_ID", "SLURM_NTASKS", "XLA_FLAGS",
                     "JAX_PLATFORMS", "REPRO_ARCH", "REPRO_SHAPE")

    def __init__(self, extra: Optional[Fields] = None) -> None:
        self.extra = dict(extra or {})

    def collect(self, now: float) -> Optional[Fields]:
        fields: Fields = {
            "python": sys.version.split()[0],
            "argv": " ".join(sys.argv[:4])[:200],
        }
        try:
            import jax
            fields["jax_version"] = jax.__version__
            fields["backend"] = jax.default_backend()
            fields["device_kind"] = jax.devices()[0].device_kind
            fields["device_count"] = jax.device_count()
        except Exception:  # noqa: BLE001
            pass
        for key in self.ENV_WHITELIST:
            if key in os.environ:
                fields[f"env_{key}"] = os.environ[key][:200]
        fields.update(self.extra)
        return fields
