"""Fleet self-observability: tracing, metrics, and self-ingestion.

The fleet (coordinator, :class:`~repro.core.service.QueryService`,
workers, replicas) historically exposed its vitals through scattered
stats dicts — ``explain()``, ``last_query_stats``, breaker and hedge
counters, per-worker ``explain`` ops.  This module unifies them behind
three layers:

1. **Distributed tracing** — :class:`Tracer` produces :class:`Span`
   records (``trace_id``/``span_id``/``parent_id``, monotonic start /
   duration, typed attributes) around every query phase: admission,
   plan compile, per-shard scatter, hedge attempts, retries, merge,
   finalize, gather.  Trace context travels over the wire protocol as
   an optional ``trace`` field on ``scatter``/``gather`` requests,
   negotiated at ``hello`` (a worker advertises ``"trace": True``;
   old workers never see the field), so one trace stitches coordinator
   and worker spans.  Finished traces land in a bounded ring buffer;
   traces slower than a threshold are retained in a slow-query log.

2. **Unified metrics registry** — :class:`Registry` holds counters,
   gauges, and histograms with a small label model, plus pull-based
   *collectors*: callables that snapshot live component state (shard
   counters, breaker states, replica stats, cache hit rates) on
   demand with zero hot-path cost.  ``explain()`` and
   ``QueryService.stats()`` are views over the same collector
   functions, so the registry and the legacy dicts cannot diverge.

3. **Self-ingestion** — :class:`SelfMonitor` periodically snapshots
   the registry into :class:`~repro.core.schema.MetricRecord` rows
   (``kind="fleet"``, ``job="_fleet"``) and inserts them into a
   dedicated ``_telemetry`` store, so splunklite queries, dashboards,
   and detectors run over the fleet's own vitals exactly like tenant
   data — continuously, over the remote fleet, including under fault
   injection.

Run ``python -m repro.core.telemetry --help`` for the ops CLI
(trace-tree pretty printing, registry JSON dumps, a live demo).

Naming conventions (see docs/observability.md): metric names are
lowercase dotted paths ``<component>.<noun>[_<unit>]`` (e.g.
``remote.retries``, ``service.queue_depth``, ``cache.partial.hits``);
labels are few and low-cardinality (``shard``, ``tenant``, ``op``).
Self-ingested field keys keep the dots — they are valid
:data:`~repro.core.schema._KEY_RE` keys and valid splunklite field
names.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import (Any, Callable, Dict, Iterable, List, Optional,
                    Sequence, Tuple)

__all__ = [
    "Span", "Tracer", "Counter", "Gauge", "Histogram", "Registry",
    "Telemetry", "SelfMonitor", "format_trace", "main",
    "TRACE_RING_MAX", "SLOW_QUERY_THRESHOLD_S",
]

TRACE_RING_MAX = 128          # finished traces retained in the ring
LIVE_TRACE_MAX = 256          # open traces before oldest is evicted
SLOW_LOG_MAX = 32             # slow-query exemplars retained
SLOW_QUERY_THRESHOLD_S = 0.25
HIST_BOUNDS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
               0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

_SAN_RE = re.compile(r"[^A-Za-z0-9_.]")


def _new_id() -> str:
    """64-bit random hex id (trace and span ids share the format)."""
    return os.urandom(8).hex()


def sanitize_metric_key(name: str) -> str:
    """Coerce ``name`` into a valid record field key (schema
    ``_KEY_RE``): illegal characters become ``_`` and a leading
    non-letter gets an underscore prefix.  Dots are preserved — they
    are legal in both field keys and splunklite field names."""
    out = _SAN_RE.sub("_", name)
    if not out or not (out[0].isalpha() or out[0] == "_"):
        out = "_" + out
    return out


# ---------------------------------------------------------------------------
# spans + tracer
# ---------------------------------------------------------------------------

class Span:
    """One timed operation inside a trace.

    ``trace_id`` groups spans into a request; ``parent_id`` links the
    tree (``None`` marks the root).  ``start`` is wall-clock (for
    cross-process ordering in displays); duration is measured on the
    monotonic clock.  ``attrs`` carries typed attributes (shard index,
    attempt number, cache disposition, ...).  Use as a context
    manager — an exception marks the span ``status="error"``."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "node",
                 "start", "duration_s", "status", "attrs",
                 "_t0", "_tracer", "_finished", "_annotation")

    recording = True

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 parent_id: Optional[str],
                 attrs: Optional[Dict[str, Any]] = None,
                 annotation: Any = None) -> None:
        self.trace_id = trace_id
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.name = name
        self.node = tracer.node
        self.start = time.time()
        self.duration_s = 0.0
        self.status = "ok"
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self._t0 = time.monotonic()
        self._tracer = tracer
        self._finished = False
        self._annotation = annotation

    # -- attribute + lifecycle --------------------------------------------
    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def child(self, name: str, attrs: Optional[Dict[str, Any]] = None
              ) -> "Span":
        return self._tracer.start_span(name, parent=self, attrs=attrs)

    def ctx(self) -> Dict[str, str]:
        """Wire-propagatable trace context."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def finish(self, status: Optional[str] = None) -> "Span":
        if self._finished:
            return self
        self._finished = True
        self.duration_s = time.monotonic() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        if status is not None:
            self.status = status
        self._tracer._record(self)
        return self

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None and self.status == "ok":
            self.status = "error"
            self.attrs.setdefault("error", repr(exc))
        self.finish()

    def to_dict(self) -> Dict[str, Any]:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name,
                "node": self.node, "start": self.start,
                "duration_s": self.duration_s, "status": self.status,
                "attrs": dict(self.attrs)}


class _NullSpan:
    """Do-nothing span returned when tracing is disabled; supports the
    full :class:`Span` surface so call sites stay branch-free."""

    __slots__ = ()
    recording = False
    trace_id = ""
    span_id = ""
    parent_id = None
    name = ""
    node = ""
    start = 0.0
    duration_s = 0.0
    status = "ok"
    attrs: Dict[str, Any] = {}

    def set(self, **attrs: Any) -> "_NullSpan":
        return self

    def child(self, name: str, attrs: Optional[Dict] = None) -> "_NullSpan":
        return self

    def ctx(self) -> Dict[str, str]:
        return {}

    def finish(self, status: Optional[str] = None) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        return None

    def to_dict(self) -> Dict[str, Any]:
        return {}


NULL_SPAN = _NullSpan()


def _profiler_annotation(name: str, attrs: Optional[Dict[str, Any]]):
    """A ``jax.profiler.TraceAnnotation`` named ``name``, entered, while a
    profiler session runs in this process; else None.  JAX is only looked
    up where it is already loaded, so the fleet's processes never import
    it for this."""
    jax = sys.modules.get("jax")
    annotation = getattr(getattr(jax, "profiler", None),
                         "TraceAnnotation", None)
    if annotation is None or not annotation.is_enabled():
        return None
    ann = annotation(name, **(attrs or {}))
    ann.__enter__()
    return ann


class _ProfilerSpan(_NullSpan):
    """A disabled tracer's span while a profiler session runs: it enters
    and exits its ``TraceAnnotation`` and records nothing."""

    __slots__ = ("_tracer", "_annotation")

    def __init__(self, tracer: "Tracer", annotation: Any) -> None:
        self._tracer, self._annotation = tracer, annotation

    def child(self, name: str, attrs: Optional[Dict] = None):
        return self._tracer.start_span(name, parent=self, attrs=attrs)

    def finish(self, status: Optional[str] = None) -> "_ProfilerSpan":
        annotation, self._annotation = self._annotation, None
        if annotation is not None:
            annotation.__exit__(None, None, None)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.finish()


class Tracer:
    """Produces spans and collects finished traces.

    A trace is *sealed* when its root span (``parent_id is None``)
    finishes: its spans move from the live table into a bounded ring
    buffer, and traces slower than ``slow_threshold_s`` are copied
    into the slow-query log with an exemplar.  Spans adopted from
    remote processes (:meth:`adopt`) splice into whichever table
    currently holds the trace.  All public methods are thread-safe.

    While a JAX profiler session runs, every span also enters a
    ``jax.profiler.TraceAnnotation`` of its name, so the span lands in
    that session's trace on the device trace's clock.  A disabled
    tracer's span then does only that; outside a session it is
    ``NULL_SPAN``."""

    def __init__(self, enabled: bool = True, node: str = "coordinator",
                 ring_max: int = TRACE_RING_MAX,
                 slow_threshold_s: float = SLOW_QUERY_THRESHOLD_S,
                 slow_log_max: int = SLOW_LOG_MAX) -> None:
        self.enabled = bool(enabled)
        self.node = node
        self.ring_max = int(ring_max)
        self.slow_threshold_s = float(slow_threshold_s)
        self._lock = threading.Lock()
        self._live: "OrderedDict[str, List[Dict]]" = OrderedDict()
        self._ring: "OrderedDict[str, List[Dict]]" = OrderedDict()
        self._slow: deque = deque(maxlen=int(slow_log_max))
        self._tls = threading.local()
        self.spans_started = 0
        self.spans_dropped = 0

    # -- span creation ----------------------------------------------------
    def start_span(self, name: str, parent: Optional[Span] = None,
                   parent_ctx: Optional[Dict[str, str]] = None,
                   attrs: Optional[Dict[str, Any]] = None):
        """Start a span.  ``parent`` links locally; ``parent_ctx``
        (a ``{"trace_id", "span_id"}`` dict off the wire) links across
        processes.  With neither, a new root trace begins."""
        annotation = _profiler_annotation(name, attrs)
        if not self.enabled:
            return (NULL_SPAN if annotation is None
                    else _ProfilerSpan(self, annotation))
        if parent is not None and parent.recording:
            trace_id, parent_id = parent.trace_id, parent.span_id
        elif parent_ctx and parent_ctx.get("trace_id"):
            trace_id = str(parent_ctx["trace_id"])
            parent_id = str(parent_ctx.get("span_id") or "") or None
        else:
            trace_id, parent_id = _new_id(), None
        with self._lock:
            self.spans_started += 1
        return Span(self, name, trace_id, parent_id, attrs, annotation)

    # -- thread-local "current span" --------------------------------------
    def current(self):
        """The span most recently activated on this thread (or the
        null span)."""
        return getattr(self._tls, "span", NULL_SPAN)

    class _Activation:
        __slots__ = ("_tracer", "_span", "_prev")

        def __init__(self, tracer: "Tracer", span) -> None:
            self._tracer, self._span, self._prev = tracer, span, None

        def __enter__(self):
            self._prev = getattr(self._tracer._tls, "span", NULL_SPAN)
            self._tracer._tls.span = self._span
            return self._span

        def __exit__(self, *exc) -> None:
            self._tracer._tls.span = self._prev

    def activate(self, span) -> "Tracer._Activation":
        """Context manager installing ``span`` as this thread's
        current span (picked up by downstream layers that accept no
        explicit parent)."""
        return Tracer._Activation(self, span)

    # -- collection -------------------------------------------------------
    def _record(self, span: Span) -> None:
        d = span.to_dict()
        with self._lock:
            if span.parent_id is None:
                spans = self._live.pop(span.trace_id, [])
                spans.append(d)
                self._seal_locked(span.trace_id, spans, d)
            else:
                self._append_live_locked(span.trace_id, d)

    def _append_live_locked(self, trace_id: str, d: Dict) -> None:
        if trace_id in self._ring:           # root already sealed
            self._ring[trace_id].append(d)
            return
        bucket = self._live.get(trace_id)
        if bucket is None:
            bucket = self._live[trace_id] = []
            while len(self._live) > LIVE_TRACE_MAX:
                self._live.popitem(last=False)
                self.spans_dropped += 1
        bucket.append(d)

    def _seal_locked(self, trace_id: str, spans: List[Dict],
                     root: Dict) -> None:
        self._ring[trace_id] = spans
        self._ring.move_to_end(trace_id)
        while len(self._ring) > self.ring_max:
            self._ring.popitem(last=False)
        if root["duration_s"] >= self.slow_threshold_s:
            self._slow.append({
                "ts": root["start"], "trace_id": trace_id,
                "name": root["name"],
                "duration_s": root["duration_s"],
                "attrs": dict(root["attrs"]),
                "exemplar": [dict(s) for s in spans],
            })

    def adopt(self, spans: Iterable[Dict]) -> int:
        """Splice finished span dicts from another process (worker
        replies) into their traces.  Returns the count adopted."""
        n = 0
        with self._lock:
            for d in spans or ():
                tid = d.get("trace_id")
                if not tid:
                    continue
                self._append_live_locked(str(tid), dict(d))
                n += 1
        return n

    def take_trace(self, trace_id: str) -> List[Dict]:
        """Remove and return every span recorded for ``trace_id``
        (workers use this to ship a request's spans back in the
        reply)."""
        with self._lock:
            out = self._live.pop(trace_id, [])
            out += self._ring.pop(trace_id, [])
        return out

    # -- inspection -------------------------------------------------------
    def trace(self, trace_id: str) -> List[Dict]:
        with self._lock:
            spans = self._ring.get(trace_id) or self._live.get(trace_id)
            return [dict(s) for s in spans] if spans else []

    def last_trace(self) -> Tuple[Optional[str], List[Dict]]:
        """(trace_id, spans) of the most recently sealed trace."""
        with self._lock:
            if not self._ring:
                return None, []
            tid = next(reversed(self._ring))
            return tid, [dict(s) for s in self._ring[tid]]

    def finished_traces(self) -> List[str]:
        with self._lock:
            return list(self._ring)

    def slow_queries(self) -> List[Dict]:
        with self._lock:
            return [dict(e) for e in self._slow]

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"spans_started": self.spans_started,
                    "spans_dropped": self.spans_dropped,
                    "traces_finished": len(self._ring),
                    "traces_live": len(self._live),
                    "slow_queries": len(self._slow)}


def format_trace(spans: Sequence[Dict], unit_us: bool = True) -> str:
    """Render a span list as an indented tree, children ordered by
    start time; orphaned spans (parent not present — e.g. dropped by
    the ring) attach under a synthetic root."""
    if not spans:
        return "(empty trace)"
    by_id = {s["span_id"]: s for s in spans}
    children: Dict[Optional[str], List[Dict]] = {}
    for s in spans:
        pid = s.get("parent_id")
        if pid is not None and pid not in by_id:
            pid = None
        children.setdefault(pid, []).append(s)
    for kids in children.values():
        kids.sort(key=lambda s: (s.get("start", 0.0), s.get("name", "")))
    lines: List[str] = []

    def emit(span: Dict, depth: int) -> None:
        dur = span.get("duration_s", 0.0)
        dur_txt = (f"{dur * 1e6:10.1f}us" if unit_us
                   else f"{dur * 1e3:10.3f}ms")
        status = span.get("status", "ok")
        mark = {"ok": " ", "error": "!", "cancelled": "x"}.get(status, "?")
        attrs = span.get("attrs") or {}
        attr_txt = ("  " + " ".join(f"{k}={attrs[k]!r}"
                                    for k in sorted(attrs)) if attrs else "")
        lines.append(f"{dur_txt} {mark} {'  ' * depth}"
                     f"{span.get('node', '?')}/{span.get('name', '?')}"
                     f"{attr_txt}")
        for kid in children.get(span["span_id"], ()):
            emit(kid, depth + 1)

    for root in children.get(None, ()):
        emit(root, 0)
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class Counter:
    """Monotonic counter.  ``inc`` is lock-protected; reads are a
    single attribute load."""

    __slots__ = ("name", "labels", "value", "_lock")
    kind = "counter"

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...]
                 ) -> None:
        self.name, self.labels = name, labels
        self.value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n

    def samples(self) -> List[Tuple[str, float]]:
        return [(self.name, self.value)]


class Gauge:
    """Point-in-time value; ``set`` replaces, ``add`` adjusts."""

    __slots__ = ("name", "labels", "value", "_lock")
    kind = "gauge"

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...]
                 ) -> None:
        self.name, self.labels = name, labels
        self.value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        self.value = float(v)

    def add(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n

    def samples(self) -> List[Tuple[str, float]]:
        return [(self.name, self.value)]


class Histogram:
    """Fixed-bound histogram with count/sum/max and estimated
    percentiles (linear interpolation inside the winning bucket)."""

    __slots__ = ("name", "labels", "bounds", "counts", "count", "sum",
                 "max", "_lock")
    kind = "histogram"

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...],
                 bounds: Sequence[float] = HIST_BOUNDS) -> None:
        self.name, self.labels = name, labels
        self.bounds = tuple(float(b) for b in bounds)
        self.counts = [0] * (len(self.bounds) + 1)
        self.count = 0
        self.sum = 0.0
        self.max = 0.0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        i = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self.counts[i] += 1
            self.count += 1
            self.sum += v
            if v > self.max:
                self.max = v

    def quantile(self, q: float) -> float:
        with self._lock:
            total, counts = self.count, list(self.counts)
        if total == 0:
            return 0.0
        rank = q * total
        seen = 0.0
        for i, c in enumerate(counts):
            if seen + c >= rank and c:
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = self.bounds[i] if i < len(self.bounds) else self.max
                frac = (rank - seen) / c
                return lo + (hi - lo) * min(max(frac, 0.0), 1.0)
            seen += c
        return self.max

    def samples(self) -> List[Tuple[str, float]]:
        return [(self.name + ".count", float(self.count)),
                (self.name + ".sum", self.sum),
                (self.name + ".max", self.max),
                (self.name + ".p50", self.quantile(0.50)),
                (self.name + ".p95", self.quantile(0.95)),
                (self.name + ".p99", self.quantile(0.99))]


def _label_key(labels: Dict[str, Any]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class Registry:
    """Unified metric registry.

    Two ingestion styles:

    * **instruments** — :meth:`counter` / :meth:`gauge` /
      :meth:`histogram` get-or-create a named instrument (with an
      optional small label set) for code that pushes measurements;
    * **collectors** — :meth:`register_collector` attaches a callable
      returning ``{name: value}`` evaluated only at snapshot time, so
      hot paths keep their plain attribute counters and the registry
      stays the single read-side source (``explain()`` /
      ``QueryService.stats()`` call the same collector functions).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: "OrderedDict[Tuple[str, Tuple], Any]" = OrderedDict()
        self._collectors: "OrderedDict[str, Callable[[], Dict[str, float]]]" \
            = OrderedDict()

    # -- instruments ------------------------------------------------------
    def _instrument(self, cls, name: str, labels: Dict[str, Any],
                    **kw: Any):
        key = (name, _label_key(labels))
        with self._lock:
            inst = self._metrics.get(key)
            if inst is None:
                inst = self._metrics[key] = cls(name, key[1], **kw)
            elif not isinstance(inst, cls):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(inst).__name__}")
            return inst

    def counter(self, name: str, **labels: Any) -> Counter:
        return self._instrument(Counter, name, labels)

    def gauge(self, name: str, **labels: Any) -> Gauge:
        return self._instrument(Gauge, name, labels)

    def histogram(self, name: str, bounds: Sequence[float] = HIST_BOUNDS,
                  **labels: Any) -> Histogram:
        return self._instrument(Histogram, name, labels, bounds=bounds)

    # -- collectors -------------------------------------------------------
    def register_collector(self, name: str,
                           fn: Callable[[], Dict[str, float]]) -> None:
        with self._lock:
            self._collectors[name] = fn

    def unregister_collector(self, name: str) -> None:
        with self._lock:
            self._collectors.pop(name, None)

    def collect(self, name: str) -> Dict[str, float]:
        """Evaluate one named collector (the ``explain()``/``stats()``
        read path uses this so legacy views and the registry share a
        single source)."""
        with self._lock:
            fn = self._collectors.get(name)
        return dict(fn()) if fn is not None else {}

    # -- snapshots --------------------------------------------------------
    def snapshot(self) -> List[Dict[str, Any]]:
        """Every sample: ``{"name", "labels", "value"}`` — instruments
        first, then collector output (empty labels)."""
        with self._lock:
            instruments = list(self._metrics.values())
            collectors = list(self._collectors.items())
        out: List[Dict[str, Any]] = []
        for inst in instruments:
            labels = dict(inst.labels)
            for name, value in inst.samples():
                out.append({"name": name, "labels": labels,
                            "value": float(value)})
        for cname, fn in collectors:
            try:
                data = fn()
            except Exception:       # a sick component must not kill scrapes
                data = {cname + ".collector_errors": 1.0}
            for name, value in data.items():
                if isinstance(value, (int, float)) and not isinstance(
                        value, bool):
                    out.append({"name": name, "labels": {},
                                "value": float(value)})
        return out

    def flat_snapshot(self) -> Dict[str, float]:
        """Samples flattened to ``{field_key: value}`` with labels
        folded into the key (``name.k_v``) and keys sanitized to the
        record-schema grammar — the self-ingestion wire format."""
        flat: Dict[str, float] = {}
        for s in self.snapshot():
            key = s["name"]
            for k, v in sorted(s["labels"].items()):
                key += f".{k}_{v}"
            flat[sanitize_metric_key(key)] = s["value"]
        return flat


# ---------------------------------------------------------------------------
# facade + self-ingestion
# ---------------------------------------------------------------------------

class Telemetry:
    """One tracer + one registry, shared by every fleet layer.

    Stores and services create a default instance with tracing *off*
    (registry collectors are pull-based and free); pass
    ``Telemetry(tracing=True)`` to record spans.  The instance is
    inherited downward — ``QueryService`` adopts its store's
    telemetry, the remote aggregator shares its instance with every
    ``RemoteShard``/``ReplicaSet`` member.  The training path's monitor
    and input pipeline own a ``Telemetry()`` each: their spans reach a
    running profiler session's trace and nothing else (:class:`Tracer`)."""

    def __init__(self, tracing: bool = False, node: str = "coordinator",
                 slow_threshold_s: float = SLOW_QUERY_THRESHOLD_S,
                 ring_max: int = TRACE_RING_MAX) -> None:
        self.tracer = Tracer(enabled=tracing, node=node,
                             ring_max=ring_max,
                             slow_threshold_s=slow_threshold_s)
        self.registry = Registry()

    @property
    def tracing(self) -> bool:
        return self.tracer.enabled

    def span(self, name: str, parent=None, parent_ctx=None, attrs=None):
        return self.tracer.start_span(name, parent=parent,
                                      parent_ctx=parent_ctx, attrs=attrs)


class SelfMonitor:
    """Pumps registry snapshots into a ``_telemetry`` store.

    Each :meth:`pump` emits one ``kind="fleet"`` record whose fields
    are the flat registry snapshot, plus one ``kind="event"`` record
    per new slow query.  ``sink`` is anything with ``insert(record)``
    (an in-memory :class:`~repro.core.aggregator.MetricStore`, a columnar
    store, or a shard of the fleet itself).  :meth:`maybe_pump` is the
    interval-gated form for embedding in existing pump loops."""

    def __init__(self, telemetry: Telemetry, sink: Any,
                 host: str = "fleet-coordinator", job: str = "_fleet",
                 interval_s: float = 5.0) -> None:
        self.telemetry = telemetry
        self.sink = sink
        self.host = host
        self.job = job
        self.interval_s = float(interval_s)
        self.pumps = 0
        self.records_emitted = 0
        self._last_pump = 0.0
        self._slow_seen = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def collect(self, now: Optional[float] = None) -> List[Any]:
        """Build (without inserting) this cycle's records."""
        from .schema import MetricRecord
        ts = time.time() if now is None else float(now)
        fields = self.telemetry.registry.flat_snapshot()
        for name, value in self.telemetry.tracer.stats().items():
            fields[sanitize_metric_key("tracer." + name)] = float(value)
        records = [MetricRecord(ts=ts, host=self.host, job=self.job,
                                kind="fleet", fields=fields)]
        slow = self.telemetry.tracer.slow_queries()
        with self._lock:
            fresh = slow[self._slow_seen:]
            self._slow_seen = len(slow)
        for entry in fresh:
            records.append(MetricRecord(
                ts=float(entry["ts"]), host=self.host, job=self.job,
                kind="event",
                fields={"event": "slow_query",
                        "trace_id": entry["trace_id"],
                        "name": entry["name"],
                        "duration_s": float(entry["duration_s"])}))
        return records

    def pump(self, now: Optional[float] = None) -> int:
        """Snapshot + insert; returns the number of records emitted."""
        records = self.collect(now)
        for rec in records:
            self.sink.insert(rec)
        with self._lock:
            self.pumps += 1
            self.records_emitted += len(records)
            self._last_pump = time.monotonic()
        return len(records)

    def maybe_pump(self, now: Optional[float] = None) -> int:
        with self._lock:
            due = (time.monotonic() - self._last_pump) >= self.interval_s
        return self.pump(now) if due else 0

    # -- optional background pump -----------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="self-monitor")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.pump()
            except Exception:
                pass            # the monitor must never take down the fleet


# ---------------------------------------------------------------------------
# ops CLI
# ---------------------------------------------------------------------------

def _cmd_trace(path: str, unit_ms: bool) -> int:
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    spans = data.get("spans", data) if isinstance(data, dict) else data
    print(format_trace(spans, unit_us=not unit_ms))
    return 0


def _cmd_registry(path: Optional[str]) -> int:
    if path:
        with open(path, "r", encoding="utf-8") as fh:
            print(json.dumps(json.load(fh), indent=2, sort_keys=True))
        return 0
    print(json.dumps({}, indent=2))
    return 0


def _cmd_demo(shards: int, slow_ms: float) -> int:
    """Run a tiny traced fleet in-process and print its trace tree,
    registry snapshot, and a self-ingestion query."""
    import tempfile

    from .aggregator import MetricStore
    from .schema import MetricRecord
    from .shards import ShardedAggregator
    from . import splunklite

    telemetry = Telemetry(tracing=True, slow_threshold_s=slow_ms / 1e3)
    with tempfile.TemporaryDirectory() as tmp:
        agg = ShardedAggregator(num_shards=shards, directory=tmp,
                                seal_threshold=256, telemetry=telemetry)
        for i in range(1024):
            agg.insert(MetricRecord(
                ts=1e6 + i, host=f"n{i % 8}", job=f"job.{i % 4}",
                kind="perf", fields={"gflops": float(i % 97)}))
        q = ("search kind=perf | stats avg(gflops) count by job "
             "| sort -avg_gflops")
        rows, _stats = agg.query_with_stats(q)
        tid, spans = telemetry.tracer.last_trace()
        print(f"# query: {q}\n# rows: {len(rows)}   trace: {tid}\n")
        print(format_trace(spans))
        tstore = MetricStore()
        monitor = SelfMonitor(telemetry, tstore, interval_s=0.0)
        monitor.pump()
        print("\n# registry snapshot (flat):")
        print(json.dumps(telemetry.registry.flat_snapshot(), indent=2,
                         sort_keys=True))
        print("\n# self-ingestion query:")
        for r in splunklite.query(
                tstore, "search kind=fleet | head 1"):
            print(json.dumps(r, sort_keys=True, default=str))
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.core.telemetry",
        description="Fleet telemetry ops tools: pretty-print trace "
                    "trees, dump registry snapshots, run a traced demo.")
    sub = p.add_subparsers(dest="cmd", required=True)
    pt = sub.add_parser("trace", help="pretty-print a trace tree from a "
                                      "JSON span dump")
    pt.add_argument("path", help="JSON file: a span list or "
                                 "{'spans': [...]}")
    pt.add_argument("--ms", action="store_true",
                    help="durations in milliseconds (default: us)")
    pr = sub.add_parser("registry", help="pretty-print a registry "
                                         "snapshot JSON dump")
    pr.add_argument("path", nargs="?", help="snapshot JSON file")
    pd = sub.add_parser("demo", help="run a traced in-process fleet and "
                                     "print trace + registry + "
                                     "self-ingestion output")
    pd.add_argument("--shards", type=int, default=2)
    pd.add_argument("--slow-ms", type=float, default=0.0,
                    help="slow-query threshold in ms (0 logs everything)")
    args = p.parse_args(argv)
    if args.cmd == "trace":
        return _cmd_trace(args.path, args.ms)
    if args.cmd == "registry":
        return _cmd_registry(args.path)
    return _cmd_demo(args.shards, args.slow_ms)


if __name__ == "__main__":
    sys.exit(main())
