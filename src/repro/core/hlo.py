"""Post-SPMD HLO text analysis: collective traffic extraction.

XLA's ``cost_analysis()`` does not report collective bytes, so (per the
task spec) we parse the compiled module text and sum the operand sizes of
every ``all-gather`` / ``all-reduce`` / ``reduce-scatter`` / ``all-to-all``
/ ``collective-permute`` op.  This is the "network counter" data source of
the monitoring system: the per-step ICI traffic is a static property of the
compiled executable, exactly like the FLOP count.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s2": 0.25, "u2": 0.25, "s4": 0.5, "u4": 0.5,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8,
    "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1, "f8e5m2": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
    "f4e2m1fn": 0.5, "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

# dtype[d0,d1,...] possibly followed by layout {..}
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")

COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
)

# op line:  %name = <type> <opcode>(...), attrs
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+([a-z][a-z0-9\-]*)\(")


def shape_bytes(dtype: str, dims: str) -> float:
    width = _DTYPE_BYTES.get(dtype)
    if width is None:
        return 0.0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * width


def _sum_shapes(text: str) -> float:
    return sum(shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(text))


def _balanced_paren_span(line: str, start: int) -> Tuple[int, int]:
    """Return (open_idx, close_idx) of the operand list starting at
    ``start`` (index of the opening paren)."""
    depth = 0
    for i in range(start, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return start, i
    return start, len(line) - 1


@dataclass
class CollectiveStats:
    count: int = 0
    operand_bytes: float = 0.0
    result_bytes: float = 0.0


@dataclass
class CollectiveSummary:
    per_kind: Dict[str, CollectiveStats] = field(default_factory=dict)

    @property
    def total_operand_bytes(self) -> float:
        return sum(s.operand_bytes for s in self.per_kind.values())

    @property
    def total_result_bytes(self) -> float:
        return sum(s.result_bytes for s in self.per_kind.values())

    @property
    def total_count(self) -> int:
        return sum(s.count for s in self.per_kind.values())

    def as_fields(self) -> Dict[str, float]:
        out: Dict[str, float] = {
            "coll_bytes": self.total_operand_bytes,
            "coll_result_bytes": self.total_result_bytes,
            "coll_count": float(self.total_count),
        }
        for kind, s in sorted(self.per_kind.items()):
            key = kind.replace("-", "_")
            out[f"coll_{key}_bytes"] = s.operand_bytes
            out[f"coll_{key}_count"] = float(s.count)
        return out


def _normalize_opcode(opcode: str) -> str:
    for suffix in ("-start", "-done"):
        if opcode.endswith(suffix):
            return opcode[: -len(suffix)]
    return opcode


def collective_summary(hlo_text: str) -> CollectiveSummary:
    """Scan compiled (post-partitioning) HLO text for collective ops.

    Operand types appear inline in HLO long form
    (``all-reduce(f32[8,128]{1,0} %add.3)``), so operand bytes are read
    directly off the op line.  ``*-done`` ops are skipped to avoid double
    counting async pairs.
    """
    summary = CollectiveSummary()
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        result_type, opcode = m.group(1), m.group(2)
        if opcode.endswith("-done"):
            continue
        kind = _normalize_opcode(opcode)
        if kind not in COLLECTIVE_KINDS:
            continue
        open_idx = line.find("(", m.end() - 1)
        _, close_idx = _balanced_paren_span(line, open_idx)
        operand_text = line[open_idx + 1: close_idx]
        st = summary.per_kind.setdefault(kind, CollectiveStats())
        st.count += 1
        rb = _sum_shapes(result_type)
        ob = _sum_shapes(operand_text)
        # short-form HLO omits operand types; result size is the correct
        # operand size for all-reduce/permute and an upper bound otherwise
        st.operand_bytes += ob if ob else rb
        st.result_bytes += rb
    return summary


def collective_bytes(hlo_text: str) -> float:
    """Total operand bytes across all collective ops (task-spec metric)."""
    return collective_summary(hlo_text).total_operand_bytes


# ----------------------------------------------------------- cost extraction

def cost_figures(compiled) -> Dict[str, float]:
    """Normalize ``compiled.cost_analysis()`` into {flops, bytes}.

    XLA:CPU/TPU report per-partition figures on the partitioned module.
    """
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    return {"flops": max(flops, 0.0), "bytes": max(byts, 0.0)}


def memory_figures(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        out[k] = float(getattr(ma, k, 0) or 0)
    out["total_bytes_per_device"] = (
        out["argument_size_in_bytes"] + out["output_size_in_bytes"]
        + out["temp_size_in_bytes"] - out.get("alias_size_in_bytes", 0.0))
    return out
