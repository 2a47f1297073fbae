"""The trace read by the program's own names.

``harness.trace`` names device time by XLA instruction (``fusion.632``),
which changes with every compile, and names idle gaps by the benchmark's
own ``bench.*`` spans. This module adds what the program itself names:

* ``scopes``: each device operation's own time (``trace.self_times``)
  goes to the innermost of the program's eight named scopes in that
  instruction's ``op_name`` (the compiled step's HLO text), or to
  ``unscoped``; an instruction with no ``op_name`` takes its fused root's
  or its direct operands' (``scopes_own`` gives the same without that);
* ``program_spans``: seconds, count and self seconds (less the ``repro.*``
  spans nested in them) of each ``repro.*`` host span, the spans the
  program's profiler sink writes (``repro.monitor.tick``,
  ``repro.monitor.sample``, ``repro.pipeline.wait``);
* ``idle_gaps_program``: the same longest gaps as ``idle_gaps``, each
  named by the host event that covers most of it: a ``repro.*`` span or
  the runtime's own event (a transfer, a device put, the wait in
  ``np.asarray``), else ``host``.

Everything is clipped to the traced window (``bench.window``). A trace of
a program that has no scopes or spans gives all its device time to
``unscoped`` and no program spans, and raises nothing.
"""

from __future__ import annotations

import collections
import glob
import re
from typing import Dict, List, Optional, Sequence, Tuple

from harness import trace

Event = trace.Event
SCOPES = ("model.embed", "model.layers", "model.block", "model.ssm",
          "model.attention", "model.mlp", "model.loss", "optim.update")
UNSCOPED = "unscoped"
PROGRAM_PREFIX = "repro."

_SCOPE = re.compile(r"(?<![\w.])(%s)(?![\w.])"
                    % "|".join(re.escape(s) for s in SCOPES))
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERANDS = re.compile(r"\s[a-z][\w\-]*\((%[^)]*)\)")
_NAME = re.compile(r"%([\w.\-]+)")


def innermost_scope(op_name: str) -> Optional[str]:
    """``jit(f)/transpose(jvp(model.layers))/.../model.ssm/dot`` ->
    ``model.ssm``: the last of the eight scopes in the path."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def instruction_scopes(hlo_text: str
                       ) -> Tuple[Dict[str, str], Dict[str, str]]:
    """Instruction name -> its scope, by two rules, for every instruction
    of the compiled module's text: ``(own, inherited)``.

    ``own``: the scope innermost in the instruction's own ``op_name``,
    ``unscoped`` where it names none or the instruction has none.
    ``inherited``: the same, except that an instruction the compiler left
    without ``op_name`` (layout copies, the cumulative sums it lowers to
    ``reduce-window``, the ends of asynchronous copies and slices, the CPU
    backend's wrapped single-op fusions) takes the scope of the root of
    the computation it calls (``calls=``), else the most common scope of
    its direct operands by that same step, else ``unscoped``. Nothing is
    inherited from further away, so no scope leaks across a layer's
    edge."""
    op: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    roots: Dict[str, str] = {}
    comp = ""
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(2)
        found = _OP_NAME.search(line)
        op[name] = ((innermost_scope(found.group(1)) or UNSCOPED) if found
                    else None)
        called = _CALLS.search(line)
        if called is not None:
            calls[name] = called.group(1)
        args = _OPERANDS.search(line)
        operands[name] = _NAME.findall(args.group(1)) if args else []
        if m.group(1):
            roots[comp] = name

    def direct(name: str) -> Optional[str]:
        """The instruction's own scope, else its called root's."""
        return op.get(name) or op.get(roots.get(calls.get(name, ""), ""))

    def inherit(name: str) -> str:
        scope = direct(name)
        if scope is None:
            counts = collections.Counter(
                s for s in map(direct, operands[name]) if s is not None)
            scope = counts.most_common(1)[0][0] if counts else None
        return scope or UNSCOPED

    own = {n: s or UNSCOPED for n, s in op.items()}
    return own, {n: inherit(n) for n in op}


def device_scopes(device_events: Dict[str, Sequence[Event]], lo: float,
                  hi: float, scope_of: Dict[str, str]) -> Dict[str, float]:
    """Seconds of device self time per scope inside [lo, hi], averaged
    over the devices; every scope and ``unscoped`` appear."""
    out = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
    for events in device_events.values():
        for name, t in trace.self_times(trace._clip(events, lo, hi)):
            out[scope_of.get(name, UNSCOPED)] += t / len(device_events)
    return out


def _self_seconds(spans: Sequence[Tuple[str, float, float]]
                  ) -> List[float]:
    """Seconds of each span less those of the spans directly nested in
    it (held inside it, and inside no other span held inside it)."""
    def inside(j, i):
        return j != i and spans[i][1] <= spans[j][1] \
            and spans[j][2] <= spans[i][2]
    out = []
    for i, (_, s, e) in enumerate(spans):
        kids = [j for j in range(len(spans)) if inside(j, i)]
        direct = [j for j in kids if not any(inside(j, k) for k in kids)]
        out.append((e - s - sum(spans[j][2] - spans[j][1] for j in direct))
                   * 1e-9)
    return out


def program_spans(host_events: Sequence[Event], lo: float, hi: float
                  ) -> Dict[str, dict]:
    """Seconds, count and self seconds of each ``repro.*`` span that
    starts inside [lo, hi]."""
    own = [(n, s, s + d) for n, s, d in host_events
           if n.startswith(PROGRAM_PREFIX) and lo <= s < hi]
    out: Dict[str, dict] = {}
    for (name, a, b), self_s in zip(own, _self_seconds(own)):
        tot = out.setdefault(name, {"seconds": 0.0, "count": 0,
                                    "self_seconds": 0.0})
        tot["seconds"] += (b - a) * 1e-9
        tot["count"] += 1
        tot["self_seconds"] += self_s
    return out


def _gaps(device_events: Dict[str, Sequence[Event]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Every gap between device operations inside [lo, hi], longest
    first: the gaps ``trace.reduce`` names in ``idle_gaps``."""
    gaps: List[Tuple[float, float]] = []
    for events in device_events.values():
        u = trace.union((a, b) for _, a, b in trace._clip(events, lo, hi))
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return gaps


def idle_gaps_program(device_events: Dict[str, Sequence[Event]],
                      host_events: Sequence[Event], lo: float, hi: float,
                      top: int = 10) -> List[list]:
    """The longest gaps, each named by the host event that covers most of
    it: a ``repro.*`` span or an event of the runtime (a transfer, a
    device put, the wait in ``np.asarray``), a span first where they
    cover as much, then the shortest (the innermost); ``host`` where
    none does."""
    # the harness's own spans and Python's frames ("$...") name nothing
    candidates = [(n, s, s + d, n.startswith(PROGRAM_PREFIX))
                  for n, s, d in host_events
                  if not n.startswith((trace.SPAN_PREFIX, "$"))
                  and s < hi and s + d > lo]
    out = []
    for a, b in _gaps(device_events, lo, hi)[:top]:
        best, label = None, "host"
        for n, s, e, program in candidates:
            covered = min(b, e) - max(a, s)
            key = (covered, program, s - e)
            if covered > 0 and (best is None or key > best):
                best, label = key, n
        out.append([label, (b - a) * 1e-9])
    return out


def reduce(device_events: Dict[str, Sequence[Event]],
           host_events: Sequence[Event], hlo_text: str,
           top: int = 10) -> dict:
    """The additions, for the window of ``bench.window``; empty where
    there is no window or no device operation. ``scopes`` attributes by
    ``instruction_scopes``' inherited rule, ``scopes_own`` by each
    instruction's own ``op_name`` alone."""
    windows = [(s, s + d) for n, s, d in host_events
               if n == trace.WINDOW_SPAN]
    if not windows or not device_events:
        return {}
    lo, hi = windows[0]
    own, inherited = instruction_scopes(hlo_text)
    return {
        "scopes": device_scopes(device_events, lo, hi, inherited),
        "scopes_own": device_scopes(device_events, lo, hi, own),
        "program_spans": program_spans(host_events, lo, hi),
        "idle_gaps_program": idle_gaps_program(device_events, host_events,
                                               lo, hi, top),
    }


def read_host(trace_dir: str) -> List[Event]:
    """Every event of the host planes of the newest ``.xplane.pb`` under
    ``trace_dir``: the spans of the benchmark and of the program, and the
    runtime's own events."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return []
    host: List[Event] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events]
    return host
