"""Model FLOPs of one training step.

Forward plus backward is three times the forward's multiply-adds (two
FLOPs each); recomputation under remat does not count. The forward's count
per sequence is the configuration's own: ``forward_flops_per_sequence`` of
the shapes module it names (``cells.shapes``), which says what it counts.
"""

from __future__ import annotations

from harness import cells


def train_step_flops(c: dict, seq_len: int, batch: int) -> float:
    """Model FLOPs of one optimizer step over ``batch`` sequences."""
    return 3.0 * batch * cells.shapes(c).forward_flops_per_sequence(
        c, seq_len)
