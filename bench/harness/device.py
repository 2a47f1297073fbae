"""The device a run measures, and its published peaks.

A run measures only on an accelerator whose kind is in ``peaks.json``;
anything else is an error, never a fallback to the CPU.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = json.loads((Path(__file__).with_name("peaks.json")).read_text())


class NoChip(RuntimeError):
    pass


def check(chips: int) -> dict:
    """The device block of the result line; raises NoChip unless JAX sees
    ``chips`` accelerators of a kind with published peaks."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform == "cpu":
        raise NoChip(f"no accelerator: JAX's first device is {dev}")
    if dev.device_kind not in PEAKS:
        raise NoChip(f"no published peaks for {dev.device_kind!r}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips asked for, JAX sees {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def peak_flops(kind: str) -> float:
    return float(PEAKS[kind]["bf16_flops_per_s"])


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))
