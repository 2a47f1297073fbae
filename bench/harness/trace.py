"""Reduction of a profiler trace to the numbers the metrics read.

Input: the device operations of each accelerator (``XLA Ops`` lines of the
``/device:...`` planes) and the benchmark's own host spans (``bench.*``
``TraceAnnotation`` events), all on the trace's one clock, and the traced
window (the ``bench.window`` span).

Output:

* ``busy_s``: the union of the device-operation intervals inside the
  window, averaged over the devices;
* ``window_s`` and ``idle_share`` = 1 - busy / window;
* ``spans``: total seconds and count of each host span name;
* ``device_ops``: the ten operations with most device time of their
  own (less the operations nested in them);
* ``collective_s``: the device time of the collective operations (by the
  HLO opcode their names carry: all-gather, reduce-scatter, all-reduce,
  collective-permute, all-to-all, and the TPU's async-collective, each
  with its ``-start`` and ``-done`` halves), averaged over the devices:
  the collectives that run on their own. One that the compiler runs
  inside a fusion beside a matmul is hidden behind it, and counts as that
  fusion's compute;
* ``idle_gaps``: the ten longest gaps between device operations, each
  named by the host span that overlaps it most (``host`` if none does).
"""

from __future__ import annotations

import collections
import glob
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start ns, duration ns
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events: Sequence[Event], lo: float, hi: float):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


_COLLECTIVE = re.compile(r"(all-gather|reduce-scatter|all-reduce|"
                         r"collective-permute|all-to-all|async-collective)"
                         r"(-start|-done)?(\.\d+)*")


def is_collective(name: str) -> bool:
    """Whether an operation's name carries a collective's opcode."""
    return _COLLECTIVE.fullmatch(op_name(name)) is not None


def self_times(events) -> List[Tuple[str, float]]:
    """Seconds of each operation less the operations nested in it (a
    ``while`` holds its body's operations on the same line)."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []                 # [name, start, end, child time]

    def close(item):
        out.append((op_name(item[0]), (item[2] - item[1] - item[3]) * 1e-9))
        if stack:
            stack[-1][3] += item[2] - item[1]

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        stack.append([name, a, b, 0.0])
    while stack:
        close(stack.pop())
    return out


def reduce(device_events: Dict[str, Sequence[Event]],
           host_spans: Sequence[Event], top: int = 10) -> dict:
    windows = [(s, s + d) for n, s, d in host_spans if n == WINDOW_SPAN]
    if not windows or not device_events:
        return {}
    lo, hi = windows[0]
    window_s = (hi - lo) * 1e-9
    busy, op_time = [], collections.Counter()
    collective_s = 0.0
    gaps: List[Tuple[float, float]] = []
    for events in device_events.values():
        clipped = list(_clip(events, lo, hi))
        for name, t in self_times(clipped):
            op_time[name] += t
            if is_collective(name):
                collective_s += t
        u = union((a, b) for _, a, b in clipped)
        busy.append(sum(b - a for a, b in u) * 1e-9)
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    spans: Dict[str, List[float]] = {}
    own = [(n, s, s + d) for n, s, d in host_spans
           if n.startswith(SPAN_PREFIX) and n != WINDOW_SPAN]
    for n, a, b in own:
        tot = spans.setdefault(n, [0.0, 0])
        tot[0] += (b - a) * 1e-9
        tot[1] += 1

    def label(a: float, b: float) -> str:
        best, name = 0.0, "host"
        for n, s, e in own:
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, name = ov, n
        return name

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    busy_s = sum(busy) / len(busy)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "spans": {n: {"seconds": v[0], "count": v[1]}
                  for n, v in spans.items()},
        "device_ops": [[n, t] for n, t in op_time.most_common(top)],
        "collective_s": collective_s / len(device_events),
        "idle_gaps": [[label(a, b), (b - a) * 1e-9] for a, b in gaps[:top]],
    }


def read_xplane(trace_dir: str) -> Tuple[Dict[str, List[Event]],
                                         List[Event], dict]:
    """Device operations per device plane, host spans, and a short
    description of the planes for the run's log."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return {}, [], {"files": 0}
    pd = ProfileData.from_file(paths[-1])
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    planes = {}
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        planes[plane.name] = sorted(lines)[:12]
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            line = lines.get("XLA Ops")
            if line is not None:
                device[plane.name] = [(e.name, e.start_ns, e.duration_ns)
                                      for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
    return device, host, {"files": len(paths), "planes": planes}
