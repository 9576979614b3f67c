"""Request-scoped tracing: contextvars span trees, W3C traceparent, and a
bounded in-memory flight recorder.

Aggregate metrics (registry.py) answer "how slow is the p99?"; this module
answers "why was *this* request slow?" — the Dapper-style question. One
request = one trace: the serving transport opens a root span (ingesting an
inbound ``traceparent`` header so external callers correlate), every layer
underneath attaches child spans and span events (cache hit/miss, recompile,
pad-bucket chosen), and the completed tree lands in the flight recorder,
browsable at ``GET /debug/traces``.

Design constraints, matching the rest of observability/:

- **pure stdlib** — importable before jax; no I/O on the hot path.
- **contextvars, not threading.local** — the serving plane hops threads
  constantly (transport → dispatcher → prefetch worker → partition pool);
  a context is captured once with :func:`propagate` and re-installed in the
  worker, so spans opened there land in the right trace.
- **one span primitive** — :func:`span` is what library code calls at a
  layer boundary: a ``jax.profiler.TraceAnnotation`` (so the span is on the
  profiler's timeline beside the device's operations) and a child span of
  the active request trace when there is one. (A stopgap rides on it: one
  row of :func:`span_log`, until the benchmark's captures record host
  events.)
- **cheap when idle** — with no active trace, ``start_span`` returns an
  inert context manager and ``add_event`` is a dict lookup + None check;
  traces are only ever minted explicitly (:func:`start_trace`).
- **bounded** — traces cap their span/event counts, and the flight
  recorder keeps a ring of the last N traces plus an always-keep set for
  requests over the slow threshold, so memory is finite by construction.

Exemplars: :func:`set_exemplars` installs :func:`current_trace_id` as the
registry's exemplar provider, so latency histogram observations made under
an active span carry the trace_id into the OpenMetrics exposition
(``# {trace_id="..."}``). Default OFF — the rendered /metrics text stays
byte-identical to plain Prometheus 0.0.4 unless explicitly enabled.
"""

from __future__ import annotations

import contextvars
import functools
import itertools
import os
import threading
import time
from array import array
from collections import OrderedDict, deque
from typing import Callable, Dict, List, Optional, Tuple

from . import registry as _registry

__all__ = [
    "Span",
    "Trace",
    "FlightRecorder",
    "new_trace_id",
    "new_span_id",
    "new_request_id",
    "parse_traceparent",
    "format_traceparent",
    "start_trace",
    "start_span",
    "activate",
    "add_event",
    "propagate",
    "current_span",
    "current_trace_id",
    "current_request_id",
    "span",
    "set_exemplars",
    "exemplars_enabled",
    "get_flight_recorder",
    "configure_recorder",
]

#: Hard cap on spans (and events per span) recorded into one trace — a
#: runaway loop attaching spans must degrade to a truncated trace, never
#: to unbounded memory. Drops are counted on the trace.
MAX_SPANS_PER_TRACE = 512
MAX_EVENTS_PER_SPAN = 64

#: The active span (one per logical request flow). A ContextVar so that
#: ``contextvars.copy_context()`` captures it for propagate(), and nested
#: activations unwind correctly on the same thread.
_SPAN: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "mmlspark_active_span", default=None)


# -- id minting (THE place request/trace/span ids come from: tpulint TPU008
# -- flags ad-hoc uuid4().hex minting elsewhere) ------------------------------
def new_trace_id() -> str:
    """128-bit lowercase-hex trace id (W3C trace-context format)."""
    return os.urandom(16).hex()


def new_span_id() -> str:
    """64-bit lowercase-hex span id (W3C trace-context format)."""
    return os.urandom(8).hex()


def new_request_id() -> str:
    """Serving-plane request id — same 32-hex shape the routing table and
    journal always used, minted here so tracing and routing stay joined."""
    return os.urandom(16).hex()


# -- W3C traceparent ----------------------------------------------------------
def _is_hex(s: str) -> bool:
    return bool(s) and all(c in "0123456789abcdef" for c in s)


def parse_traceparent(header: Optional[str]) -> Optional[Tuple[str, str]]:
    """``(trace_id, parent_span_id)`` from a ``traceparent`` header, or
    None when absent/malformed (per spec: a bad header starts a new trace,
    it never errors the request)."""
    if not header:
        return None
    parts = header.strip().lower().split("-")
    if len(parts) < 4:
        return None
    version, trace_id, span_id, flags = parts[0], parts[1], parts[2], parts[3]
    if len(version) != 2 or not _is_hex(version) or version == "ff":
        return None
    if version == "00" and len(parts) != 4:
        return None
    if len(trace_id) != 32 or not _is_hex(trace_id) \
            or trace_id == "0" * 32:
        return None
    if len(span_id) != 16 or not _is_hex(span_id) or span_id == "0" * 16:
        return None
    if len(flags) != 2 or not _is_hex(flags):
        return None
    return trace_id, span_id


def format_traceparent(span: "Span") -> str:
    """``00-{trace_id}-{span_id}-01`` for outbound hops / response echo."""
    return f"00-{span.trace_id}-{span.span_id}-01"


# -- span / trace data model --------------------------------------------------
class Span:
    """One timed operation inside a trace. End is idempotent — the first
    ``end()`` wins (a timed-out request later answered must not re-close
    its root), and ending the root hands the trace to the flight
    recorder."""

    __slots__ = ("trace", "name", "span_id", "parent_id", "attrs", "events",
                 "start_ts", "thread", "_start", "_dur")

    def __init__(self, name: str, trace: "Trace",
                 parent_id: Optional[str] = None,
                 attrs: Optional[dict] = None):
        self.trace = trace
        self.name = name
        self.span_id = new_span_id()
        self.parent_id = parent_id
        self.attrs = dict(attrs) if attrs else {}
        self.events: List[dict] = []
        self.start_ts = time.time()
        self.thread = threading.current_thread().name
        self._start = time.perf_counter()
        self._dur: Optional[float] = None

    @property
    def trace_id(self) -> str:
        return self.trace.trace_id

    @property
    def duration(self) -> Optional[float]:
        return self._dur

    @property
    def ended(self) -> bool:
        return self._dur is not None

    def event(self, name: str, **fields: object) -> None:
        """Attach a timestamped point event (cache miss, pad bucket, ...)."""
        with self.trace._lock:
            if len(self.events) >= MAX_EVENTS_PER_SPAN:
                self.trace.dropped += 1
                return
            self.events.append({
                "name": name, "ts": time.time(),
                **({"fields": fields} if fields else {})})

    def set(self, **attrs: object) -> None:
        """Add attributes to a span that is still open or already closed."""
        with self.trace._lock:
            self.attrs.update(attrs)

    def end(self, **attrs: object) -> bool:
        """Close the span; False when it was already closed (exactly-once).
        Ending the root span records the whole trace."""
        with self.trace._lock:
            if self._dur is not None:
                return False
            self._dur = time.perf_counter() - self._start
            if attrs:
                self.attrs.update(attrs)
        if self is self.trace.root:
            get_flight_recorder().record(self.trace)
        return True

    def to_dict(self) -> dict:
        return {"name": self.name, "span_id": self.span_id,
                "parent_id": self.parent_id, "trace_id": self.trace_id,
                "start_ts": self.start_ts, "duration_s": self._dur,
                "thread": self.thread, "attrs": dict(self.attrs),
                "events": list(self.events)}


class Trace:
    """All spans of one request, keyed by a W3C trace id."""

    def __init__(self, trace_id: str,
                 remote_parent_id: Optional[str] = None):
        self.trace_id = trace_id
        #: span id of the caller's span when the trace was ingested from an
        #: inbound traceparent — the upstream half lives in *their* tracer
        self.remote_parent_id = remote_parent_id
        self.root: Optional[Span] = None
        self.dropped = 0
        self._lock = threading.Lock()
        self._spans: List[Span] = []

    def _add(self, span: Span) -> bool:
        with self._lock:
            if len(self._spans) >= MAX_SPANS_PER_TRACE:
                self.dropped += 1
                return False
            self._spans.append(span)
            return True

    @property
    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    @property
    def duration(self) -> Optional[float]:
        return self.root.duration if self.root is not None else None

    def summary(self) -> dict:
        root = self.root
        return {"trace_id": self.trace_id,
                "name": root.name if root else None,
                "request_id": root.attrs.get("request_id") if root else None,
                "start_ts": root.start_ts if root else None,
                "duration_s": self.duration,
                "spans": len(self.spans),
                "dropped": self.dropped}

    def to_dict(self) -> dict:
        """Span TREE (children nested under parents) + the summary."""
        spans = self.spans
        nodes = {s.span_id: dict(s.to_dict(), children=[]) for s in spans}
        top: List[dict] = []
        for s in spans:
            parent = nodes.get(s.parent_id or "")
            (parent["children"] if parent is not None else top).append(
                nodes[s.span_id])
        return dict(self.summary(), roots=top)

    def to_chrome(self) -> dict:
        """Chrome-trace JSON (chrome://tracing / Perfetto): the one Chrome
        export of host spans; a profiler capture holds the same names."""
        spans = self.spans
        t0 = min((s.start_ts for s in spans), default=0.0)
        threads: Dict[str, int] = {}
        events = []
        for s in spans:
            tid = threads.setdefault(s.thread, len(threads))
            events.append({
                "name": s.name, "ph": "X", "pid": 0, "tid": tid,
                "ts": (s.start_ts - t0) * 1e6,
                "dur": (s.duration or 0.0) * 1e6,
                "args": {**s.attrs, "span_id": s.span_id,
                         "trace_id": self.trace_id}})
        return {"traceEvents": events, "displayTimeUnit": "ms"}


# -- context management -------------------------------------------------------
def start_trace(name: str, traceparent: Optional[str] = None,
                **attrs: object) -> Span:
    """Mint a root span (a new trace, or a continuation of the caller's
    trace when ``traceparent`` parses). NOT activated — pair with
    :func:`activate`, and close it explicitly with ``span.end()``."""
    parent = parse_traceparent(traceparent)
    if parent is not None:
        trace = Trace(parent[0], remote_parent_id=parent[1])
        root = Span(name, trace, parent_id=parent[1], attrs=attrs)
    else:
        trace = Trace(new_trace_id())
        root = Span(name, trace, attrs=attrs)
    trace.root = root
    trace._add(root)
    return root


class _Activation:
    """``with activate(span):`` — install without owning: the span is NOT
    ended on exit (roots end at reply time, on another thread)."""

    __slots__ = ("_span", "_token")

    def __init__(self, span: Optional[Span]):
        self._span = span
        self._token = None

    def __enter__(self) -> Optional[Span]:
        if self._span is not None:
            self._token = _SPAN.set(self._span)
        return self._span

    def __exit__(self, *exc: object) -> None:
        if self._token is not None:
            _SPAN.reset(self._token)


def activate(span: Optional[Span]) -> _Activation:
    """Make ``span`` the current span for the with-block (no-op on None)."""
    return _Activation(span)


class _SpanScope:
    """``with start_span(...):`` — child span owned by the block: activated
    on enter, ended (and deactivated) on exit."""

    __slots__ = ("_span", "_token")

    def __init__(self, span: Optional[Span]):
        self._span = span
        self._token = None

    def __enter__(self) -> Optional[Span]:
        if self._span is not None:
            self._token = _SPAN.set(self._span)
        return self._span

    def __exit__(self, *exc: object) -> None:
        if self._token is not None:
            _SPAN.reset(self._token)
        if self._span is not None:
            self._span.end()


def start_span(name: str, **attrs: object) -> _SpanScope:
    """Open a child of the current span for the with-block. Inert (yields
    None) when no trace is active — library code can call this
    unconditionally; cost outside a trace is one ContextVar read."""
    parent = _SPAN.get()
    if parent is None:
        return _SpanScope(None)
    child = Span(name, parent.trace, parent_id=parent.span_id, attrs=attrs)
    if not parent.trace._add(child):
        return _SpanScope(None)
    return _SpanScope(child)


def add_event(name: str, **fields: object) -> None:
    """Attach a point event to the current span; no-op outside a trace."""
    span = _SPAN.get()
    if span is not None:
        span.event(name, **fields)


def current_span() -> Optional[Span]:
    return _SPAN.get()


def current_trace_id() -> Optional[str]:
    span = _SPAN.get()
    return span.trace_id if span is not None else None


def current_request_id() -> Optional[str]:
    """The request id of the active trace (stamped on the root span by the
    serving transport), falling back to the active span's own attr."""
    span = _SPAN.get()
    if span is None:
        return None
    root = span.trace.root
    rid = root.attrs.get("request_id") if root is not None else None
    return rid if rid is not None else span.attrs.get("request_id")


def propagate(fn: Callable) -> Callable:
    """Capture the CURRENT context (the active span and any other
    ContextVars) and re-install it around every call of ``fn``.

    The explicit bridge across thread hops: plain ``threading.Thread`` /
    pool workers start with an EMPTY context, so spans opened there would
    silently fall outside the trace. Wrap the worker's callable at
    submission time::

        prepare = propagate(self._prepare)      # dispatch thread, in-trace
        PrefetchIterator((prepare(sl) for sl in slices), depth=2)

    Unlike ``Context.run`` the captured context is re-entered by value
    (set/reset per call), so one wrapped fn is safe to call concurrently
    from many workers."""
    captured = contextvars.copy_context()

    @functools.wraps(fn)
    def wrapped(*args: object, **kwargs: object):
        tokens = [(var, var.set(value)) for var, value in captured.items()]
        try:
            return fn(*args, **kwargs)
        finally:
            for var, token in reversed(tokens):
                var.reset(token)

    return wrapped


# -- the span primitive -------------------------------------------------------
#: ``jax.profiler.TraceAnnotation``, looked up at the first span (this module
#: imports before jax); False where jax is not installed.
_ANNOTATION: object = None

class _SpanRing:
    """The last ``maxlen`` closed spans ``(name, thread ident, start, end)``
    in columns made once, at the first row: a name's reference and three
    machine integers a row, 32 bytes and no object of its own (a deque of
    tuples is ~190 bytes a row, and until it is full it takes new memory
    for every row all through a run). ``append`` takes no lock: a slot is
    claimed by one atomic step of a counter. ``rows`` is for a reader that
    runs once the writers are quiet: beside a running writer it can return
    a slot's older row."""

    __slots__ = ("maxlen", "_next", "_names", "_threads", "_starts", "_ends",
                 "_skipped", "_lock")

    def __init__(self, maxlen: int):
        self.maxlen = maxlen
        self._lock = threading.Lock()
        self.clear()

    def clear(self) -> None:
        self._names: Optional[list] = None
        self._next = itertools.count()
        #: the counter's steps that ``rows`` took to learn how far it is:
        #: their slots hold no row of theirs
        self._skipped: set = set()

    def _columns(self) -> list:
        with self._lock:
            if self._names is None:
                zeros = bytes(8 * self.maxlen)
                self._threads = array("Q", zeros)
                self._starts = array("q", zeros)
                self._ends = array("q", zeros)
                self._names = [None] * self.maxlen
            return self._names

    def append(self, row: Tuple[str, int, int, int]) -> None:
        names = self._names
        if names is None:
            names = self._columns()
        i = next(self._next) % self.maxlen
        names[i], self._threads[i], self._starts[i], self._ends[i] = row

    def rows(self) -> List[Tuple[str, int, int, int]]:
        """Oldest first, in closing order."""
        names = self._names
        if names is None:
            return []
        with self._lock:
            n = next(self._next)        # so many steps so far; this is one
            first = max(0, n - self.maxlen)
            self._skipped = {j for j in self._skipped if j >= first} | {n}
            skipped = self._skipped
        size = self.maxlen
        return [(names[j % size], self._threads[j % size],
                 self._starts[j % size], self._ends[j % size])
                for j in range(first, n)
                if j not in skipped and names[j % size] is not None]

    def __len__(self) -> int:
        return len(self.rows())


#: STOPGAP, not an operator feature. The benchmark's captures are taken at
#: ``host_tracer_level=0`` (no TraceMe recorded, so no annotation either),
#: and its harness is not this module's to change: until it records host
#: events, or brackets its capture with a start/stop hook, the last closed
#: spans are kept here, ``(name, thread ident, start, end)`` in
#: ``time.time_ns()``, the clock the profiler stamps its events with, so a
#: device-only trace can be laid over them. One constant ring, no knob:
#: memory is bounded, appends take no lock. Goes when its readers
#: (``benchmarks/idle_gaps.py`` and what is built on it) read the trace's
#: host plane instead.
#:
#: Its size: the benchmark traces the FIRST seconds of a 51 s window and
#: reads the ring after the window, its drain and the engine's stop, so
#: the ring must hold rows a round x rounds a second x ~60 s and the traced
#: stretch is then its oldest part. An engine round writes 10 rows (12 with
#: a prefill chunk): 1,400 rows/s at GPT-2 XL's 133 rounds/s (86,896 rows
#: at the read in a cold process), 1,050 at 32 streams' 100 rounds/s, the
#: transform path ~120. 262,144 rows hold ~3 minutes of the fastest cell:
#: 8 MB in all (32 bytes a row), taken when the first span closes. A reader
#: that finds the ring's oldest row inside its stretch must give no number.
_SPAN_LOG = _SpanRing(262144)


def span_log() -> List[Tuple[str, int, int, int]]:
    """``[(name, thread ident, start_ns, end_ns), ...]`` of the last closed
    spans, in closing order (an inner span closes before the one around
    it). See ``_SPAN_LOG``: a stopgap for the benchmark."""
    return _SPAN_LOG.rows()


class _Span:
    """``with span(name, **attrs):`` — see :func:`span`."""

    __slots__ = ("_name", "_attrs", "_annotation", "_scope", "_t0")

    def __init__(self, name: str, attrs: dict):
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> Optional[Span]:
        global _ANNOTATION
        if _ANNOTATION is None:
            try:
                from jax.profiler import TraceAnnotation
                _ANNOTATION = TraceAnnotation
            except Exception:
                _ANNOTATION = False
        # outside a trace not even the inert scope is made
        self._scope = (start_span(self._name, **self._attrs)
                       if _SPAN.get() is not None else None)
        child = self._scope.__enter__() if self._scope is not None else None
        self._annotation = (_ANNOTATION(self._name, **self._attrs)
                            if _ANNOTATION else None)
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.time_ns()
        return child

    def __exit__(self, *exc: object) -> None:
        _SPAN_LOG.append((self._name, threading.get_ident(), self._t0,
                          time.time_ns()))
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if self._scope is not None:
            self._scope.__exit__(*exc)


def span(name: str, **attrs: object) -> _Span:
    """THE span primitive, for every layer boundary of a hot path.

    The block is a ``jax.profiler.TraceAnnotation`` (``attrs`` are its
    keyword arguments), so a profiler capture that records host events shows
    it beside the device's operations, and when a request trace is active it
    is also a child span of it (the block's value, else None). Outside a
    trace no :class:`Span` is made. Worker threads inherit the request trace
    through :func:`propagate`."""
    return _Span(name, attrs)


# -- exemplars ----------------------------------------------------------------
def set_exemplars(enabled: bool) -> None:
    """Toggle OpenMetrics exemplars: when on, histogram observations made
    under an active span capture the trace_id, and the exposition appends
    ``# {trace_id="..."} value`` to their bucket lines. Default off —
    /metrics stays byte-identical to plain Prometheus 0.0.4 text."""
    _registry.set_exemplar_provider(current_trace_id if enabled else None)


def exemplars_enabled() -> bool:
    return _registry.exemplar_provider() is not None


# -- flight recorder ----------------------------------------------------------
class FlightRecorder:
    """Bounded store of completed request traces.

    Two tiers: a ring of the last ``capacity`` traces (anything), plus an
    always-keep set (capped at ``slow_keep``, oldest evicted) for traces
    whose root duration meets ``slow_threshold`` — so the one slow request
    from an hour ago is still there after the ring wrapped ten thousand
    fast ones."""

    def __init__(self, capacity: int = 64, slow_threshold: float = 1.0,
                 slow_keep: int = 32):
        self._lock = threading.Lock()
        self.configure(capacity=capacity, slow_threshold=slow_threshold,
                       slow_keep=slow_keep)

    def configure(self, capacity: Optional[int] = None,
                  slow_threshold: Optional[float] = None,
                  slow_keep: Optional[int] = None) -> "FlightRecorder":
        with self._lock:
            if capacity is not None:
                self._ring: "deque[Trace]" = deque(
                    getattr(self, "_ring", ()), maxlen=max(1, int(capacity)))
            if slow_threshold is not None:
                self._slow_threshold = float(slow_threshold)
            if slow_keep is not None:
                self._slow_keep = max(1, int(slow_keep))
                if not hasattr(self, "_slow"):
                    self._slow: "OrderedDict[str, Trace]" = OrderedDict()
        return self

    @property
    def slow_threshold(self) -> float:
        return self._slow_threshold

    def record(self, trace: Trace) -> None:
        dur = trace.duration
        with self._lock:
            if dur is not None and dur >= self._slow_threshold:
                self._slow[trace.trace_id] = trace
                self._slow.move_to_end(trace.trace_id)
                while len(self._slow) > self._slow_keep:
                    self._slow.popitem(last=False)
            else:
                self._ring.append(trace)

    def get(self, trace_id: str) -> Optional[Trace]:
        with self._lock:
            trace = self._slow.get(trace_id)
            if trace is not None:
                return trace
            for t in self._ring:
                if t.trace_id == trace_id:
                    return t
        return None

    def traces(self) -> List[Trace]:
        """Newest first; slow-kept traces listed ahead of the ring."""
        with self._lock:
            slow = list(self._slow.values())
            ring = [t for t in self._ring if t.trace_id not in self._slow]
        return list(reversed(slow)) + list(reversed(ring))

    def summaries(self) -> List[dict]:
        return [t.summary() for t in self.traces()]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._slow.clear()


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, ""))
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return default


_RECORDER = FlightRecorder(
    capacity=_env_int("MMLSPARK_TPU_TRACE_RING", 64),
    slow_threshold=_env_float("MMLSPARK_TPU_TRACE_SLOW_SECONDS", 1.0),
    slow_keep=_env_int("MMLSPARK_TPU_TRACE_SLOW_KEEP", 32))


def get_flight_recorder() -> FlightRecorder:
    return _RECORDER


def configure_recorder(capacity: Optional[int] = None,
                       slow_threshold: Optional[float] = None,
                       slow_keep: Optional[int] = None) -> FlightRecorder:
    """Adjust the process-global recorder's knobs (tests, ops tuning)."""
    return _RECORDER.configure(capacity=capacity,
                               slow_threshold=slow_threshold,
                               slow_keep=slow_keep)
