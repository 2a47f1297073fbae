"""The benchmark's own tests: run by path (``python -m pytest bench/tests``),
on the CPU, never on the chip."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four CPU devices, so that a cell of four chips runs its sharded path here
os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=4"]))
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
