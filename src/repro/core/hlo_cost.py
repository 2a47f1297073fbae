"""Loop-aware static cost analysis of post-optimization HLO text.

``compiled.cost_analysis()`` counts each ``while`` body ONCE, so any
scan-over-layers program (ours: every model) is undercounted by ~the layer
count.  This analyzer parses the partitioned HLO text, builds the call
graph (entry -> while bodies / fusions / calls), extracts loop trip counts
from the loop-condition constants, and accumulates:

* **flops** — 2 x prod(result_dims) x prod(contraction_dims) per ``dot``
  (including dots inside fusion subcomputations), and per TPU grouped
  matmul (``jax.lax.ragged_dot``, a ``ragged-dot`` custom call) 2 x its
  row operand's elements x the result's last dim, at the static row
  bound; x loop multiplier;
* **traffic bytes** — operand + result bytes of every top-level op in a
  computation (post-fusion top-level ops are the kernel boundaries, i.e.
  the HBM traffic model), x loop multiplier;
* **collective bytes/counts** — per kind, x loop multiplier.

This is the TPU analog of the paper's PMU counters: exact static per-step
figures read off the compiled executable (validated against XLA's own
cost analysis on loop-free programs in tests/test_hlo_cost.py).
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from repro.core.hlo import _DTYPE_BYTES, COLLECTIVE_KINDS, _normalize_opcode

# ---------------------------------------------------------------- parsing --

_COMP_HEADER_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^{]*\))?\s*->.*{\s*$")
_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
# result types may be tuples containing /*index=N*/ comments (with '='),
# so the type capture must be permissive; the opcode is the first
# whitespace-preceded word directly followed by '('.
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[a-z].*?)\s"
    r"([a-z][a-z0-9\-]*)\(")
_ATTR_COMP_RE = re.compile(
    r"\b(body|condition|to_apply|calls|branch_computations)="
    r"(%?[\w.\-]+|\{[^}]*\})")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_CONST_INT_RE = re.compile(r"\bconstant\((\d+)\)")
_DOT_DNUMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def _shape_dims(text: str) -> List[Tuple[str, List[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        out.append((dt, [int(d) for d in dims.split(",")] if dims else []))
    return out


def _bytes_of_type(text: str) -> float:
    total = 0.0
    for dt, dims in _shape_dims(text):
        width = _DTYPE_BYTES.get(dt)
        if width is None:
            continue
        total += width * (math.prod(dims) if dims else 1)
    return total


@dataclass
class Instruction:
    name: str
    result_type: str
    opcode: str
    line: str

    def operands_text(self) -> str:
        i = self.line.find(self.opcode + "(")
        if i < 0:
            return ""
        start = i + len(self.opcode)
        depth = 0
        for j in range(start, len(self.line)):
            c = self.line[j]
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
                if depth == 0:
                    return self.line[start + 1:j]
        return self.line[start + 1:]

    def operand_names(self) -> List[str]:
        return _OPERAND_NAME_RE.findall(self.operands_text())

    def called(self) -> Dict[str, List[str]]:
        """attr -> computation names for body/condition/calls/..."""
        out: Dict[str, List[str]] = {}
        for m in _ATTR_COMP_RE.finditer(self.line):
            attr, blob = m.group(1), m.group(2)
            names = re.findall(r"%?([\w.\-]+)", blob)
            out.setdefault(attr, []).extend(names)
        return out


@dataclass
class Computation:
    name: str
    is_entry: bool
    instructions: List[Instruction] = field(default_factory=list)
    types: Dict[str, str] = field(default_factory=dict)  # name -> type

    def operand_bytes(self, inst: Instruction) -> float:
        total = 0.0
        text = inst.operands_text()
        inline = _bytes_of_type(text)
        if inline:
            return inline  # long-form HLO with inline operand types
        for name in inst.operand_names():
            t = self.types.get(name)
            if t:
                total += _bytes_of_type(t)
        return total


def parse_computations(hlo_text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in hlo_text.splitlines():
        line = raw.rstrip()
        if cur is None:
            stripped = line.strip()
            if stripped.endswith("{"):
                m = _COMP_HEADER_RE.match(stripped)
                if m:
                    cur = Computation(name=m.group(2),
                                      is_entry=bool(m.group(1)))
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(line)
        if m:
            inst = Instruction(name=m.group(1),
                               result_type=m.group(2).strip(),
                               opcode=m.group(3), line=line)
            cur.instructions.append(inst)
            cur.types[inst.name] = inst.result_type
    if cur is not None:
        comps[cur.name] = cur
    return comps


# ------------------------------------------------------------- cost model --

_SKIP_TRAFFIC_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "while", "call", "conditional", "after-all", "iota",
    "get-dimension-size", "partition-id", "replica-id", "copy-start",
    "copy-done",
}


def _instruction_traffic(comp: Computation, inst: Instruction,
                         comps: Dict[str, "Computation"]) -> float:
    """HBM traffic of one top-level (kernel-boundary) instruction.

    Slice-aware: ``dynamic-update-slice`` is an in-place RMW of the update
    region only; ``dynamic-slice`` reads the slice, not the whole buffer.
    Fusions rooted in a DUS (XLA in-place fusions) and fusions that merely
    slice a big parameter are treated accordingly — without this, a scan
    that checkpoints activations into a [L, ...] stack appears to move the
    whole stack every layer.
    """
    op = inst.opcode
    if op == "dynamic-slice":
        return 2.0 * _bytes_of_type(inst.result_type)  # read slice + write
    if op == "dynamic-update-slice":
        names = inst.operand_names()
        upd = _bytes_of_type(comp.types.get(names[1], "")) if len(
            names) > 1 else 0.0
        return 2.0 * upd  # read update + write region (buffer aliased)
    if op != "fusion":
        return (_bytes_of_type(inst.result_type)
                + comp.operand_bytes(inst))

    called = inst.called().get("calls", [])
    fcomp = comps.get(called[0]) if called else None
    if fcomp is None or not fcomp.instructions:
        return (_bytes_of_type(inst.result_type)
                + comp.operand_bytes(inst))
    # map fusion parameters to "effective read bytes"
    param_by_idx: Dict[int, Instruction] = {}
    for fi in fcomp.instructions:
        if fi.opcode == "parameter":
            m = re.search(r"parameter\((\d+)\)", fi.line)
            if m:
                param_by_idx[int(m.group(1))] = fi
    root = fcomp.instructions[-1]
    dus_buffer_param = None
    if root.opcode == "dynamic-update-slice":
        names = root.operand_names()
        if names:
            dus_buffer_param = names[0]
    reads = 0.0
    operand_names = inst.operand_names()
    for idx, oname in enumerate(operand_names):
        fparam = param_by_idx.get(idx)
        full = _bytes_of_type(comp.types.get(oname, ""))
        if fparam is None:
            reads += full
            continue
        if fparam.name == dus_buffer_param:
            continue  # aliased in-place target: no full read
        # if the param is only consumed by dynamic-slice ops, the kernel
        # reads just the slices
        slice_bytes, other_use = 0.0, False
        for fi in fcomp.instructions:
            if fi is fparam:
                continue
            if fparam.name in fi.operand_names():
                if fi.opcode == "dynamic-slice":
                    slice_bytes += _bytes_of_type(fi.result_type)
                else:
                    other_use = True
        if other_use or (slice_bytes == 0.0):
            reads += full
        else:
            reads += slice_bytes
    if root.opcode == "dynamic-update-slice":
        names = root.operand_names()
        upd = _bytes_of_type(fcomp.types.get(names[1], "")) if len(
            names) > 1 else 0.0
        write = 2.0 * upd
    else:
        write = _bytes_of_type(inst.result_type)
    return reads + write


def _dot_flops(inst: Instruction, comp: Computation) -> float:
    """2 * prod(result) * prod(lhs contracting dims)."""
    shapes = _shape_dims(inst.result_type)
    if not shapes:
        return 0.0
    result_elems = math.prod(shapes[0][1]) if shapes[0][1] else 1
    # lhs type: inline or resolved through the def map
    ops_text = inst.operands_text()
    op_shapes = _shape_dims(ops_text)
    if not op_shapes:
        names = inst.operand_names()
        if names:
            t = comp.types.get(names[0], "")
            op_shapes = _shape_dims(t)
    if not op_shapes:
        return 0.0
    lhs_dims = op_shapes[0][1]
    m = _DOT_DNUMS_RE.search(inst.line)
    if m and m.group(1):
        contract = 1
        for d in m.group(1).split(","):
            idx = int(d)
            if idx < len(lhs_dims):
                contract *= lhs_dims[idx]
    else:
        contract = lhs_dims[-1] if lhs_dims else 1
    return 2.0 * result_elems * contract


_LAYOUTS_RE = re.compile(r"operand_layout_constraints=\{(.*?)\}, [a-z_]+=")


def _ragged_dot_flops(inst: Instruction) -> float:
    """A grouped matmul as the TPU lowers it: a custom call named
    ``ragged-dot-*`` whose last two operands are the matrices. Forward,
    lhs gradient and rhs gradient alike do 2 x (row operand's elements) x
    (result's last dim) at the static row bound."""
    if not inst.name.startswith("ragged-dot") or "metadata" in inst.name:
        return 0.0
    m = _LAYOUTS_RE.search(inst.line)
    shapes = _shape_dims(m.group(1)) if m else []
    result = _shape_dims(inst.result_type)
    if len(shapes) < 2 or not result or not result[0][1]:
        return 0.0
    return 2.0 * math.prod(shapes[-2][1]) * result[0][1][-1]


def _trip_count(cond: Computation) -> int:
    """Loop trip count: the largest integer constant in the condition
    computation (all our scans have static trip counts)."""
    best = 1
    for inst in cond.instructions:
        for m in _CONST_INT_RE.finditer(inst.line):
            best = max(best, int(m.group(1)))
    return best


@dataclass
class HloCost:
    flops: float = 0.0
    traffic_bytes: float = 0.0
    collective_bytes: float = 0.0
    collective_result_bytes: float = 0.0
    collective_counts: Dict[str, int] = field(default_factory=dict)
    collective_bytes_by_kind: Dict[str, float] = field(default_factory=dict)
    loop_trips: Dict[str, int] = field(default_factory=dict)
    traffic_by_tag: Dict[str, float] = field(default_factory=dict)

    def add_collective(self, kind: str, operand_bytes: float,
                       result_bytes: float, mult: float) -> None:
        self.collective_bytes += operand_bytes * mult
        self.collective_result_bytes += result_bytes * mult
        self.collective_counts[kind] = (self.collective_counts.get(kind, 0)
                                        + int(mult))
        self.collective_bytes_by_kind[kind] = (
            self.collective_bytes_by_kind.get(kind, 0.0)
            + operand_bytes * mult)

    def as_fields(self) -> Dict[str, float]:
        out = {"coll_bytes": self.collective_bytes,
               "coll_count": float(sum(self.collective_counts.values())),
               "hlo_flops": self.flops,
               "hlo_traffic_bytes": self.traffic_bytes}
        for kind, b in sorted(self.collective_bytes_by_kind.items()):
            key = kind.replace("-", "_")
            out[f"coll_{key}_bytes"] = b
            out[f"coll_{key}_count"] = float(
                self.collective_counts.get(kind, 0))
        return out


def analyze_hlo(hlo_text: str, tag_fn=None) -> HloCost:
    """``tag_fn(result_type_str) -> Optional[str]`` attributes traffic to
    named buckets (e.g. attention-score tensors) in ``traffic_by_tag``."""
    comps = parse_computations(hlo_text)
    cost = HloCost()
    entries = [c for c in comps.values() if c.is_entry]
    if not entries and comps:
        entries = [list(comps.values())[-1]]

    fusion_cache: Dict[str, float] = {}

    def fusion_flops(name: str) -> float:
        if name in fusion_cache:
            return fusion_cache[name]
        fusion_cache[name] = 0.0  # cycle guard
        comp = comps.get(name)
        total = 0.0
        if comp is not None:
            for inst in comp.instructions:
                if inst.opcode in ("dot", "convolution"):
                    total += _dot_flops(inst, comp)
                for names in inst.called().values():
                    for sub in names:
                        if sub in comps and sub != name:
                            total += fusion_flops(sub)
        fusion_cache[name] = total
        return total

    stack: List[str] = []

    def walk(comp: Computation, mult: float) -> None:
        if comp.name in stack:  # defensive: HLO has no recursion
            return
        stack.append(comp.name)
        for inst in comp.instructions:
            op = inst.opcode
            base = _normalize_opcode(op)
            if op in ("dot", "convolution"):
                cost.flops += _dot_flops(inst, comp) * mult
            elif op == "custom-call":
                cost.flops += _ragged_dot_flops(inst) * mult
            if base in COLLECTIVE_KINDS and not op.endswith("-done"):
                ob = comp.operand_bytes(inst)
                rb = _bytes_of_type(inst.result_type)
                cost.add_collective(base, ob, rb, mult)
            called = inst.called()
            if op == "fusion":
                for name in called.get("calls", []):
                    cost.flops += fusion_flops(name) * mult
            elif op == "while":
                body_names = called.get("body", [])
                cond_names = called.get("condition", [])
                cond = comps.get(cond_names[0]) if cond_names else None
                trips = _trip_count(cond) if cond is not None else 1
                cost.loop_trips[f"{comp.name}/{inst.name}"] = trips
                for name in body_names:
                    if name in comps:
                        walk(comps[name], mult * trips)
            elif op in ("call", "custom-call", "conditional"):
                for key in ("to_apply", "calls", "branch_computations"):
                    for name in called.get(key, []):
                        if name in comps:
                            walk(comps[name], mult)
            if op not in _SKIP_TRAFFIC_OPS and not op.endswith("-done"):
                traffic = _instruction_traffic(comp, inst, comps) * mult
                cost.traffic_bytes += traffic
                if tag_fn is not None:
                    tag = tag_fn(inst.result_type)
                    if tag:
                        cost.traffic_by_tag[tag] = (
                            cost.traffic_by_tag.get(tag, 0.0) + traffic)
        stack.pop()

    for entry in entries:
        walk(entry, 1.0)
    return cost
