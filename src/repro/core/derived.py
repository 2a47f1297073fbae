"""Derived performance metrics and the three-term TPU roofline model.

The paper derives GFLOP/s, memory bandwidth, and arithmetic intensity from
PMU counters and places jobs on a roofline built from CPU-RAM bandwidth
(§4.4).  Our TPU adaptation keeps the same two roofline axes (AI in
FLOP/byte vs performance in GFLOP/s) and extends the model with the
collective (ICI) term required for multi-chip jobs (DESIGN.md §6.3).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class HardwareSpec:
    """Published per-chip peaks of one accelerator part."""

    name: str
    peak_flops: float                # bf16 FLOP/s per chip
    hbm_bw: float                    # bytes/s per chip
    ici_bw: float                    # bytes/s per ICI link
    hbm_bytes: float                 # HBM capacity per chip

    @property
    def ridge_ai(self) -> float:
        """Arithmetic intensity at the roofline ridge point."""
        return self.peak_flops / self.hbm_bw

    def attainable_flops(self, ai: float) -> float:
        """Roofline-attainable FLOP/s at arithmetic intensity ``ai``."""
        return min(self.peak_flops, ai * self.hbm_bw)


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
# at 819 GB/s, 1,600 Gbit/s of ICI per chip over four links.
TPU_V5E = HardwareSpec(name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                       ici_bw=50e9, hbm_bytes=16e9)

# Keyed by ``jax.Device.device_kind``, as the runtime names the part.
HARDWARE: Dict[str, HardwareSpec] = {"TPU v5 lite": TPU_V5E}


def hardware_for(platform: str, device_kind: str) -> Optional[HardwareSpec]:
    """Peaks of a device as JAX reports it.

    The host CPU has no entry and gets ``None``: no utilization or
    roofline figure is computed against it.  An accelerator missing from
    :data:`HARDWARE` raises rather than borrowing another part's peaks.
    """
    if platform == "cpu":
        return None
    if device_kind not in HARDWARE:
        raise KeyError(f"no published peaks for {platform} device kind "
                       f"{device_kind!r}; add them to derived.HARDWARE")
    return HARDWARE[device_kind]


def local_hardware() -> Optional[HardwareSpec]:
    """Peaks of the first device of this process (see hardware_for)."""
    import jax
    dev = jax.devices()[0]
    return hardware_for(dev.platform, dev.device_kind)


@dataclass(frozen=True)
class RooflineTerms:
    """The three per-step time terms (seconds) for a compiled step on a mesh."""

    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        """Lower bound on step time assuming perfect overlap of the three
        engines (MXU / HBM / ICI): max of the terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def serial_s(self) -> float:
        """Upper bound assuming zero overlap."""
        return self.compute_s + self.memory_s + self.collective_s

    def as_dict(self) -> Dict[str, float]:
        return {"compute_s": self.compute_s, "memory_s": self.memory_s,
                "collective_s": self.collective_s, "dominant": self.dominant}


def roofline_terms(hlo_flops: float, hlo_bytes: float,
                   collective_bytes: float, num_chips: int,
                   hw: HardwareSpec) -> RooflineTerms:
    """Three-term roofline from whole-program figures.

    ``hlo_flops``/``hlo_bytes`` are whole-step totals over all chips
    (XLA ``cost_analysis`` on the SPMD-partitioned module is per-chip
    already; callers must pass per-chip totals — see launch/dryrun.py).
    """
    return RooflineTerms(
        compute_s=hlo_flops / (num_chips * hw.peak_flops),
        memory_s=hlo_bytes / (num_chips * hw.hbm_bw),
        collective_s=collective_bytes / (num_chips * hw.ici_bw),
    )


# ---------------------------------------------------------------- job metrics

def achieved_gflops(flops_per_step: float, step_time_s: float) -> float:
    if step_time_s <= 0:
        return 0.0
    return flops_per_step / step_time_s / 1e9


def achieved_gbs(bytes_per_step: float, step_time_s: float) -> float:
    if step_time_s <= 0:
        return 0.0
    return bytes_per_step / step_time_s / 1e9


def arithmetic_intensity(flops: float, bytes_moved: float) -> float:
    if bytes_moved <= 0:
        return 0.0
    return flops / bytes_moved


def mfu(flops_per_step: float, step_time_s: float, num_chips: int,
        hw: HardwareSpec) -> float:
    """Model-FLOPs utilization in [0,1]."""
    if step_time_s <= 0 or num_chips <= 0:
        return 0.0
    return flops_per_step / (step_time_s * num_chips * hw.peak_flops)


def model_flops_per_token(n_params: int) -> float:
    """The standard 6·N approximation (fwd+bwd) per token."""
    return 6.0 * n_params


def useful_flops_ratio(model_flops: float, hlo_flops: float) -> float:
    """MODEL_FLOPS / HLO_FLOPS — how much of compiled compute is 'useful'.
    Catches remat recompute and redundancy waste (task spec §Roofline)."""
    if hlo_flops <= 0:
        return 0.0
    return model_flops / hlo_flops


def perf_fields(flops_per_step: float, bytes_per_step: float,
                collective_bytes_per_step: float, step_time_s: float,
                num_chips: int, hw: Optional[HardwareSpec]
                ) -> Dict[str, float]:
    """The standard derived-metric bundle hpcmd emits per perf sample
    (``mfu`` only where the part's peak is known)."""
    gfl = achieved_gflops(flops_per_step, step_time_s)
    out = {
        "gflops": gfl,
        "gflops_per_chip": gfl / max(num_chips, 1),
        "hbm_gbs": achieved_gbs(bytes_per_step, step_time_s),
        "ici_gbs": achieved_gbs(collective_bytes_per_step, step_time_s),
        "ai": arithmetic_intensity(flops_per_step, bytes_per_step),
        "step_time_s": step_time_s,
    }
    if hw is not None:
        out["mfu"] = mfu(flops_per_step, step_time_s, num_chips, hw)
    return out
