"""Per-worker HTTP server with epoch-keyed queues and replay.

Parity: ``WorkerServer`` (``HTTPSourceV2.scala:476-697``) — a lightweight
HTTP server per worker process; incoming requests are parked in an
epoch-keyed queue (``:512-518``), handed to the engine as batches, and
answered later through a routing table (``replyTo``/``respondToHTTPExchange``,
``:536-554``). Unanswered requests of an epoch survive an engine restart and
are re-served (history rehydration, ``:489-506,556-568``).

Implementation: ``ThreadingHTTPServer`` (one thread per connection, parked on
a per-request ``threading.Event`` until the reply lands) — the Python shape
of the reference's ``com.sun.net.httpserver`` + blocked ``HttpExchange``.
"""

from __future__ import annotations

import asyncio
import logging
import os
import queue
import socket
import threading
import time
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

from ..io.http.schema import (EntityData, HeaderData, HTTPRequestData,
                              HTTPResponseData, StatusLineData)
from ..observability import (CONTENT_TYPE as _PROM_CONTENT_TYPE,
                             build_info as _build_info,
                             classify_route as _classify_route,
                             counter as _metric_counter,
                             gauge as _metric_gauge,
                             get_ledger as _get_ledger,
                             get_tracker as _get_tracker,
                             get_watchdog as _get_watchdog,
                             histogram as _metric_histogram,
                             log_event as _log_event,
                             process_uptime_seconds as _process_uptime,
                             register_hbm_gauges as _register_hbm_gauges,
                             render as _render_metrics)
from ..observability import tracing as _tracing
from ..observability.timeseries import (acquire_sampler as _acquire_sampler,
                                        get_alert_engine as _get_alert_engine,
                                        get_store as _get_ts_store,
                                        release_sampler as _release_sampler,
                                        render_sparklines as
                                        _render_sparklines)
from ..reliability import (Deadline, get_injector as _get_injector,
                           open_breakers as _open_breakers)
from ..reliability.lock_sanitizer import new_lock
from .admission import AdmissionQueue, TenantOverBudget
from .registry import get_registry as _get_model_registry

__all__ = ["CachedRequest", "Overloaded", "WorkerServer"]

# serving-plane metrics (docs/observability.md) — scraped at GET /metrics,
# which every WorkerServer answers as a built-in control route
_M_REQUESTS = _metric_counter(
    "mmlspark_serving_requests_total",
    "HTTP requests answered by the worker server",
    ("transport", "method", "code"))
_M_REQ_LATENCY = _metric_histogram(
    "mmlspark_serving_request_seconds",
    "End-to-end request latency: body read to reply written (streaming "
    "replies are observed at stream open)", ("transport",))
_M_QUEUE_DEPTH = _metric_gauge(
    "mmlspark_serving_queue_depth",
    "Requests parked in the epoch queue awaiting a dispatcher", ("port",))
_M_INFLIGHT = _metric_gauge(
    "mmlspark_serving_inflight_requests",
    "Requests accepted but not yet answered (routing-table size)",
    ("port",))
# same object the watchdog registers per-device callbacks on — declared
# here so health_digest can sum it without touching watchdog internals
_M_HBM_IN_USE = _metric_gauge(
    "mmlspark_device_hbm_bytes_in_use",
    "Device memory in use (memory_stats; backends without it expose "
    "nothing)", ("device",))
_M_SHED = _metric_counter(
    "mmlspark_requests_shed_total",
    "Requests rejected 429 by bounded-queue admission control")
_M_WRITE_LAG = _metric_histogram(
    "mmlspark_serving_stream_write_lag_seconds",
    "The longest wait of one of a stream's chunks in the transport "
    "(StreamingReply.send to the socket write's return), observed once a "
    "stream, at its close, on the writer's thread")


_STREAM_TIMEOUT_EVENT = b'data: {"error": "stream reply timeout"}\n\n'


class Overloaded(RuntimeError):
    """The parked-request queue is full — the transports turn this into
    ``429 Too Many Requests`` + ``Retry-After`` (shed early rather than
    park unboundedly and 504 late)."""

    def __init__(self, retry_after: float = 1.0):
        super().__init__("serving queue full")
        self.retry_after = retry_after


def _entity_bytes(response) -> Optional[bytes]:
    """Reply body bytes for the shadow diff (None for streaming replies —
    stream content is unjoinable, the diff records only arrival)."""
    entity = getattr(response, "entity", None)
    content = getattr(entity, "content", None)
    return content if isinstance(content, bytes) else None


def _trace_headers(cached: Optional["CachedRequest"]
                   ) -> List[Tuple[str, str]]:
    """Response correlation headers for a queued request: the request id
    (the handle `reply` keys on) and the W3C traceparent of the root span,
    so callers can fetch the span tree from /debug/traces."""
    if cached is None or cached.trace_span is None:
        return []
    return [("X-Request-Id", cached.request_id),
            ("traceparent", _tracing.format_traceparent(cached.trace_span))]


class StreamingReply:
    """A reply delivered incrementally (Server-Sent Events by default).

    Returned by :meth:`WorkerServer.reply_stream`; the owning transport
    writes ``200`` + ``Content-Type: text/event-stream`` +
    ``Connection: close`` (no content length — the stream ends when the
    server closes it), then drains chunks as they arrive. ``send`` and
    ``close`` are callable from any thread; sends after ``close`` are
    dropped. Stream CONTENT is not journaled — the reply record marks the
    request answered when the stream opens (the documented at-most-once
    reply window applies to the whole stream).
    """

    _CLOSE = object()

    def __init__(self, content_type: str = "text/event-stream",
                 trace_span: Optional[object] = None):
        self.content_type = content_type
        #: the request's root span: it ends when the stream closes
        self._trace_span = trace_span
        #: ``(queued_at, bytes)`` chunks, then the close sentinel
        self._q: "queue.Queue" = queue.Queue()
        self._notify = None
        self._lock = new_lock("serving.server.StreamingReply._lock")
        self._closed = False
        #: chunks the transport has written, and how long they lay between
        #: ``send`` and the write's return (``write_lag``)
        self._writes = 0
        self._write_lag_sum_s = 0.0
        self._write_lag_max_s = 0.0

    def send(self, data) -> None:
        if isinstance(data, str):
            data = data.encode("utf-8")
        with self._lock:
            if self._closed:
                return
            # _q is unbounded: put() never blocks, it only appends — the
            # lock pairs the closed-check with the enqueue
            self._q.put((time.perf_counter(),  # tpulint: disable=TPU014
                         bytes(data)))
            notify = self._notify
        if notify is not None:
            notify()

    def send_event(self, payload) -> None:
        """One SSE ``data:`` event carrying a JSON payload."""
        import json as _json
        self.send(f"data: {_json.dumps(payload)}\n\n")

    def close(self, **attrs: object) -> None:
        """End the stream, and with it the request's trace: ``attrs`` (the
        owner's account of the request) close the root span."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            # unbounded queue — see send()
            self._q.put(StreamingReply._CLOSE)  # tpulint: disable=TPU014
            notify = self._notify
        if self._trace_span is not None:
            # idempotent: a transport that timed the request out closed it
            self._trace_span.end(status=200, streaming=True, **attrs)
        if notify is not None:
            notify()

    def write_lag(self) -> Dict[str, float]:
        """``writes``, ``write_lag_sum_s``, ``write_lag_max_s``: the chunks
        the transport has written so far and their wait between ``send``
        and the socket write's return. A chunk still queued is not in
        them."""
        with self._lock:
            return {"writes": self._writes,
                    "write_lag_sum_s": self._write_lag_sum_s,
                    "write_lag_max_s": self._write_lag_max_s}

    # -- transport side -----------------------------------------------------
    def _written(self, queued_at: float) -> None:
        """The transport's write of the chunk stamped ``queued_at`` has
        returned (called on the writer's thread, once a chunk of every
        stream: it holds the GIL the engine's thread is waiting for, so it
        does the least it can, and the histogram is left to the close)."""
        lag = time.perf_counter() - queued_at
        with self._lock:
            self._writes += 1
            self._write_lag_sum_s += lag
            if lag > self._write_lag_max_s:
                self._write_lag_max_s = lag

    def _stream_written(self) -> None:
        """The transport has written the stream to its close."""
        if self._writes:
            _M_WRITE_LAG.observe(self._write_lag_max_s)

    def _register(self, notify) -> None:
        """Async transport: fire ``notify()`` (thread-safe) whenever a
        chunk lands; fires immediately if chunks are already queued."""
        with self._lock:
            self._notify = notify
            pending = not self._q.empty()
        if pending:
            notify()

    def _get(self, timeout: Optional[float]):
        """Blocking chunk fetch (threaded transport): a ``(queued_at,
        bytes)`` chunk, the close sentinel, or None on timeout."""
        try:
            return self._q.get(timeout=timeout)
        except queue.Empty:
            return None

    def _drain_nowait(self):
        out = []
        while True:
            try:
                out.append(self._q.get_nowait())
            except queue.Empty:
                return out


@dataclass
class CachedRequest:
    """Parity: ``CachedRequest`` — a parked exchange + its id."""
    request_id: str
    epoch: int
    request: HTTPRequestData
    #: True when rehydrated from the journal after a process restart — the
    #: original connection is gone; the reply is journaled, not delivered
    replayed: bool = False
    #: root span of this request's trace (observability/tracing.py); None
    #: for replayed requests (the original caller's connection is gone)
    trace_span: Optional[object] = field(default=None, repr=False)
    #: remaining-budget carried in from X-Mmlspark-Deadline (reliability/
    #: policy.py) — caps how long the transport parks this request
    deadline: Optional[Deadline] = field(default=None, repr=False)
    #: tenant from X-Mmlspark-Tenant (SLO/cost workload class dimension)
    tenant: str = "default"
    #: resolved model version ("name@version") from X-Mmlspark-Model via
    #: the registry; None for unversioned (single-model) requests
    model_label: Optional[str] = None
    #: True for a synthetic shadow mirror — never journaled, its reply is
    #: joined/diffed by the registry instead of reaching a caller
    shadow: bool = False
    #: monotonic enqueue timestamp — get_batch charges the ledger's
    #: queue_wait_seconds from it at dequeue
    enqueued_at: float = field(default_factory=time.monotonic, repr=False)
    _done: threading.Event = field(default_factory=threading.Event, repr=False)
    _response: Optional[HTTPResponseData] = field(default=None, repr=False)

    _cbs: List[object] = field(default_factory=list, repr=False)
    _cb_lock: threading.Lock = field(default_factory=threading.Lock,
                                     repr=False)

    def respond(self, response: HTTPResponseData) -> None:
        with self._cb_lock:
            self._response = response
            self._done.set()
            cbs = list(self._cbs)
            self._cbs.clear()
        for cb in cbs:
            cb(response)

    def add_done_callback(self, cb) -> None:
        """Fire ``cb(response)`` exactly once when the reply lands — the
        async transport's bridge out of dispatcher threads (and the
        shadow-traffic join). Multiple callbacks are supported; each
        fires once, in registration order. Safe against respond() racing
        the registration."""
        with self._cb_lock:
            if not self._done.is_set():
                self._cbs.append(cb)
                return
            response = self._response
        cb(response)

    def wait(self, timeout: Optional[float]) -> Optional[HTTPResponseData]:
        if self._done.wait(timeout):
            return self._response
        return None


class _Handler(BaseHTTPRequestHandler):
    server_version = "mmlspark-tpu-serving/1.0"
    protocol_version = "HTTP/1.1"
    # headers and body go out as separate sends; without TCP_NODELAY, Nagle
    # holds the body until the client's delayed ACK (~40 ms) on every
    # keep-alive request — the difference between 23 and 750 req/s/conn
    disable_nagle_algorithm = True

    def log_message(self, fmt, *args):
        # quiet on stderr, but not dropped: access lines (and the parse
        # errors BaseHTTPRequestHandler reports through log_error) become
        # structured DEBUG events — raise the mmlspark_tpu.events logger
        # level to see them, no code edit required
        try:
            line = fmt % args
        except Exception:
            line = fmt
        _log_event("http_access", level=logging.DEBUG,
                   client=self.client_address[0], line=line)

    def _read_body(self) -> bytes:
        te = (self.headers.get("Transfer-Encoding") or "").lower()
        if "chunked" in te:
            # drain chunked framing; leaving it unread would corrupt the
            # keep-alive connection for the next pipelined request
            chunks = []
            while True:
                size_line = self.rfile.readline(65536).strip()
                size = int(size_line.split(b";")[0] or b"0", 16)
                if size == 0:
                    while self.rfile.readline(65536) not in (b"\r\n", b"\n", b""):
                        pass  # trailers
                    break
                chunks.append(self.rfile.read(size))
                self.rfile.read(2)  # CRLF after each chunk
            return b"".join(chunks)
        length = int(self.headers.get("Content-Length") or 0)
        return self.rfile.read(length) if length else b""

    def _handle(self):
        ws: "WorkerServer" = self.server.worker_server  # type: ignore[attr-defined]
        t0 = time.perf_counter()
        try:
            body = self._read_body()
        except (ValueError, ConnectionError):
            self.send_response(400, "bad request body")
            self.send_header("Content-Length", "0")
            self.end_headers()
            self.close_connection = True
            ws._observe_request("threaded", self.command, 400,
                                time.perf_counter() - t0, path=self.path)
            return
        req = HTTPRequestData(
            url=self.path, method=self.command,
            headers=[HeaderData(k, v) for k, v in self.headers.items()],
            entity=EntityData(content=body, content_length=len(body)) if body else None)
        # control routes (internal cross-worker endpoints: reply forwarding,
        # request forwarding) answer synchronously, bypassing the queue
        cached = None
        ctrl = ws._control_route(self.path)
        if ctrl is not None:
            try:
                resp = ctrl(req)
            except Exception as e:  # control failures must not park forever
                resp = HTTPResponseData(
                    entity=EntityData.from_string(str(e)),
                    status_line=StatusLineData(status_code=500))
        else:
            try:
                cached = ws._enqueue(req)
            except Overloaded as e:
                self.send_response(429, "overloaded")
                self.send_header("Retry-After", f"{e.retry_after:g}")
                self.send_header("Content-Length", "0")
                self.end_headers()
                ws._observe_request("threaded", self.command, 429,
                                    time.perf_counter() - t0, path=self.path)
                return
            except Exception as e:
                # enqueue failure (journal append, injected fault): answer
                # 500 instead of killing this connection's handler thread
                body500 = str(e).encode()
                self.send_response(500, "enqueue failed")
                self.send_header("Content-Length", str(len(body500)))
                self.end_headers()
                self.wfile.write(body500)
                ws._observe_request("threaded", self.command, 500,
                                    time.perf_counter() - t0, path=self.path)
                return
            resp = cached.wait(ws.wait_budget(cached))
        if resp is None:
            if cached is not None and cached.trace_span is not None:
                cached.trace_span.end(status=504)
            self.send_response(504, "serving reply timeout")
            for name, value in _trace_headers(cached):
                self.send_header(name, value)
            self.send_header("Content-Length", "0")
            self.end_headers()
            ws._observe_request("threaded", self.command, 504,
                                time.perf_counter() - t0, path=self.path,
                                trace_span=cached.trace_span
                                if cached is not None else None)
            return
        tspan = cached.trace_span if cached is not None else None
        if isinstance(resp, StreamingReply):
            # incremental reply: preamble now, chunks until close(); the
            # connection ends with the stream (no content length exists)
            ws._observe_request("threaded", self.command, 200,
                                time.perf_counter() - t0, path=self.path,
                                trace_span=tspan)
            self.send_response(200)
            self.send_header("Content-Type", resp.content_type)
            self.send_header("Cache-Control", "no-store")
            for name, value in _trace_headers(cached):
                self.send_header(name, value)
            self.send_header("Connection", "close")
            self.end_headers()
            self.close_connection = True
            while True:
                item = resp._get(ws.reply_timeout)
                if item is StreamingReply._CLOSE:
                    resp._stream_written()
                    break
                if item is None:
                    # per-chunk timeout: a silently truncated 200 would
                    # read as a short successful stream — emit an explicit
                    # final error event and stop accepting sends
                    resp.close()
                    queued_at, chunk = None, _STREAM_TIMEOUT_EVENT
                else:
                    queued_at, chunk = item
                try:
                    self.wfile.write(chunk)
                    self.wfile.flush()
                except (ConnectionError, BrokenPipeError):
                    break
                if queued_at is None:
                    break
                resp._written(queued_at)
            return
        payload = resp.entity.content if resp.entity else b""
        ws._observe_request("threaded", self.command,
                            resp.status_line.status_code,
                            time.perf_counter() - t0, path=self.path,
                            trace_span=tspan)
        self.send_response(resp.status_line.status_code,
                           resp.status_line.reason_phrase or None)
        sent = {h.name.lower() for h in resp.headers}
        for h in resp.headers:
            if h.name.lower() not in ("content-length", "connection"):
                self.send_header(h.name, h.value)
        for name, value in _trace_headers(cached):
            if name.lower() not in sent:
                self.send_header(name, value)
        if "content-type" not in sent and payload:
            self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        if payload:
            self.wfile.write(payload)

    do_GET = do_POST = do_PUT = do_DELETE = _handle


class _AsyncHTTPServer:
    """Event-loop transport: ALL connections multiplexed on one asyncio IO
    thread; replies cross from dispatcher threads via
    ``call_soon_threadsafe``.

    The thread-per-connection transport collapses past ~50 concurrent
    keep-alive connections (GIL convoy across 64 handler threads measured
    ~150 req/s with multi-second stalls); the reference's
    ``com.sun.net.httpserver`` is likewise selector-based rather than
    thread-per-connection (``HTTPSourceV2.scala:476-697``)."""

    def __init__(self, ws: "WorkerServer", host: str, port: int):
        self._ws = ws
        self._host = host
        self._loop = asyncio.new_event_loop()
        self._ready = threading.Event()
        self._server = None
        self._error: Optional[BaseException] = None
        self.port: Optional[int] = None
        self._thread = threading.Thread(target=self._run, args=(port,),
                                        name="serving-aio", daemon=True)
        self._thread.start()
        if not self._ready.wait(10):
            raise RuntimeError("async serving transport failed to start")
        if self._error is not None:     # e.g. EADDRINUSE — surface the cause
            raise self._error

    def _run(self, port: int) -> None:
        asyncio.set_event_loop(self._loop)

        async def boot():
            self._server = await asyncio.start_server(
                self._handle_conn, self._host, port)
            self.port = self._server.sockets[0].getsockname()[1]

        try:
            self._loop.run_until_complete(boot())
        except BaseException as e:
            self._error = e
            self._loop.close()
            self._ready.set()
            return
        self._ready.set()
        try:
            self._loop.run_forever()
        finally:
            self._loop.close()

    async def _read_request(self, reader, writer):
        line = await reader.readline()
        if not line or line in (b"\r\n", b"\n"):
            return None
        parts = line.decode("latin-1").rstrip("\r\n").split()
        if len(parts) < 2:
            return None
        method, path = parts[0], parts[1]
        headers, hmap = [], {}
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            if len(headers) >= 100:     # http.client's own header cap
                raise ValueError("got more than 100 headers")
            k, _, v = h.decode("latin-1").partition(":")
            k, v = k.strip(), v.strip()
            headers.append(HeaderData(k, v))
            hmap[k.lower()] = v
        if "100-continue" in hmap.get("expect", "").lower():
            # curl (any body > 1 KB) parks until the interim response —
            # the threaded transport's handle_expect_100 equivalent
            writer.write(b"HTTP/1.1 100 Continue\r\n\r\n")
            await writer.drain()
        if "chunked" in hmap.get("transfer-encoding", "").lower():
            chunks = []
            while True:
                size_line = (await reader.readline()).strip()
                size = int(size_line.split(b";")[0] or b"0", 16)
                if size == 0:
                    while (await reader.readline()) not in (b"\r\n", b"\n",
                                                            b""):
                        pass    # trailers
                    break
                chunks.append(await reader.readexactly(size))
                await reader.readexactly(2)     # CRLF after each chunk
            body = b"".join(chunks)
        else:
            length = int(hmap.get("content-length") or 0)
            body = await reader.readexactly(length) if length else b""
        req = HTTPRequestData(
            url=path, method=method, headers=headers,
            entity=EntityData(content=body, content_length=len(body))
            if body else None)
        return req, hmap.get("connection", "").lower() == "close"

    @staticmethod
    def _render(resp: HTTPResponseData,
                extra_headers: List[Tuple[str, str]] = ()) -> bytes:
        """Serialize status + headers + body into ONE buffer (a single send
        — immune to the Nagle/delayed-ACK stall by construction)."""
        payload = resp.entity.content if resp.entity else b""
        status = resp.status_line.status_code
        reason = (resp.status_line.reason_phrase or "").replace("\r", "") \
            .replace("\n", "")
        lines = [f"HTTP/1.1 {status} {reason}".rstrip().encode("latin-1")]
        sent = set()
        for h in resp.headers:
            if h.name.lower() not in ("content-length", "connection"):
                lines.append(f"{h.name}: {h.value}".encode("latin-1"))
                sent.add(h.name.lower())
        for name, value in extra_headers:
            if name.lower() not in sent:
                lines.append(f"{name}: {value}".encode("latin-1"))
        if "content-type" not in sent and payload:
            lines.append(b"Content-Type: application/json")
        lines.append(f"Content-Length: {len(payload)}".encode("latin-1"))
        lines.append(b"")
        return b"\r\n".join(lines) + b"\r\n" + payload

    async def _handle_conn(self, reader, writer):
        sock = writer.get_extra_info("socket")
        if sock is not None:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        ws = self._ws
        try:
            while True:
                try:
                    parsed = await self._read_request(reader, writer)
                except (ValueError, asyncio.LimitOverrunError):
                    # malformed framing (bad Content-Length / chunk size /
                    # oversized header) — answer 400 like the threaded
                    # transport instead of silently resetting
                    writer.write(self._render(HTTPResponseData(
                        status_line=StatusLineData(
                            status_code=400,
                            reason_phrase="bad request body"))))
                    await writer.drain()
                    # no parsed request line — count it, skip the latency
                    # observation (t0 would include keep-alive idle time)
                    ws._observe_request("async", "?", 400, None)
                    break
                if parsed is None:
                    break
                req, close = parsed
                t0 = time.perf_counter()
                cached = None
                ctrl = ws._control_route(req.url)
                if ctrl is not None:
                    # control routes may block on cross-worker HTTP — keep
                    # them off the IO thread
                    try:
                        resp = await self._loop.run_in_executor(None, ctrl,
                                                                req)
                    except Exception as e:
                        resp = HTTPResponseData(
                            entity=EntityData.from_string(str(e)),
                            status_line=StatusLineData(status_code=500))
                else:
                    # enqueue off the IO thread: the bounded queue.put can
                    # block when parked requests hit max_queue, and a
                    # configured journal fsyncs per request — either would
                    # freeze EVERY multiplexed connection if run here. The
                    # executor provides natural backpressure instead.
                    try:
                        cached = await self._loop.run_in_executor(
                            None, ws._enqueue, req)
                    except Overloaded as e:
                        resp = HTTPResponseData(
                            headers=[HeaderData("Retry-After",
                                                f"{e.retry_after:g}")],
                            status_line=StatusLineData(
                                status_code=429,
                                reason_phrase="overloaded"))
                    except Exception as e:
                        # enqueue failure (journal append, injected fault)
                        # — answer 500, keep the connection multiplexing
                        resp = HTTPResponseData(
                            entity=EntityData.from_string(str(e)),
                            status_line=StatusLineData(status_code=500))
                    else:
                        fut = self._loop.create_future()

                        def _cb(response, fut=fut):
                            try:
                                self._loop.call_soon_threadsafe(
                                    lambda: None if fut.done()
                                    else fut.set_result(response))
                            except RuntimeError:
                                # loop already closed (shutdown race) — the
                                # reply has nowhere to go; don't kill the
                                # dispatcher thread delivering it
                                pass

                        cached.add_done_callback(_cb)
                        try:
                            resp = await asyncio.wait_for(
                                fut, ws.wait_budget(cached))
                        except asyncio.TimeoutError:
                            if cached.trace_span is not None:
                                cached.trace_span.end(status=504)
                            resp = HTTPResponseData(
                                status_line=StatusLineData(
                                    status_code=504,
                                    reason_phrase="serving reply timeout"))
                tspan = cached.trace_span if cached is not None else None
                echo = _trace_headers(cached)
                if isinstance(resp, StreamingReply):
                    ws._observe_request("async", req.method, 200,
                                        time.perf_counter() - t0,
                                        path=req.url, trace_span=tspan)
                    echo_raw = b"".join(
                        f"{n}: {v}\r\n".encode("latin-1") for n, v in echo)
                    writer.write(
                        b"HTTP/1.1 200 OK\r\n"
                        b"Content-Type: "
                        + resp.content_type.encode("ascii")
                        + b"\r\nCache-Control: no-store\r\n"
                        + echo_raw
                        + b"Connection: close\r\n\r\n")
                    await writer.drain()
                    # chunks cross from dispatcher threads via a
                    # call_soon_threadsafe-set event; the IO thread never
                    # blocks on the stream
                    ev = asyncio.Event()
                    resp._register(lambda: self._loop.call_soon_threadsafe(
                        ev.set))
                    ended = False
                    while not ended:
                        try:
                            await asyncio.wait_for(ev.wait(),
                                                   ws.reply_timeout)
                        except asyncio.TimeoutError:
                            # explicit final error event — a silently
                            # truncated 200 would read as success
                            resp.close()
                            writer.write(_STREAM_TIMEOUT_EVENT)
                            await writer.drain()
                            break
                        ev.clear()
                        stamps = []
                        for item in resp._drain_nowait():
                            if item is StreamingReply._CLOSE:
                                ended = True
                                break
                            stamps.append(item[0])
                            writer.write(item[1])
                        await writer.drain()
                        for queued_at in stamps:
                            resp._written(queued_at)
                    resp._stream_written()
                    break                      # stream ends the connection
                ws._observe_request("async", req.method,
                                    resp.status_line.status_code,
                                    time.perf_counter() - t0,
                                    path=req.url, trace_span=tspan)
                writer.write(self._render(resp, echo))
                await writer.drain()
                if close:
                    break
        except (ConnectionError, asyncio.IncompleteReadError):
            pass
        finally:
            try:
                writer.close()
            # per-connection teardown race on an already-reset socket:
            # nothing to recover, and an event per closed keep-alive
            # connection would be pure noise
            except Exception:  # tpulint: disable=TPU009
                pass

    def close(self) -> None:
        def _stop():
            if self._server is not None:
                self._server.close()
            self._loop.stop()

        self._loop.call_soon_threadsafe(_stop)
        self._thread.join(timeout=5)


class WorkerServer:
    """HTTP listener + epoch request queue + reply routing table.

    ``transport="threaded"`` (default) is thread-per-connection;
    ``transport="async"`` multiplexes every connection on one asyncio IO
    thread — the shape to use past ~50 concurrent connections."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 api_path: str = "/", reply_timeout: float = 60.0,
                 max_queue: int = 10_000,
                 journal_path: Optional[str] = None,
                 journal_fsync: bool = True,
                 transport: str = "threaded",
                 shed_retry_after: float = 1.0):
        if transport not in ("threaded", "async"):
            # validate BEFORE opening the journal: failing after would leak
            # the journal fd and leave a half-built object
            raise ValueError(f"unknown transport {transport!r} "
                             "(expected 'threaded' or 'async')")
        self.reply_timeout = reply_timeout
        #: Retry-After hint (seconds) sent with 429 shed responses
        self.shed_retry_after = shed_retry_after
        self._closed = False
        #: path prefix → fn(HTTPRequestData) -> HTTPResponseData. The
        #: telemetry endpoints are registered FIRST: _control_route matches
        #: prefixes in insertion order, so a later catch-all (e.g. the
        #: distributed forwarder's "/") cannot shadow /metrics or /healthz
        self.control_routes: Dict[str, object] = {
            "/healthz": self._healthz_route,
            "/metrics": self._metrics_route,
            "/debug/traces": self._debug_traces_route,
            "/debug/slo": self._debug_slo_route,
            "/debug/costs": self._debug_costs_route,
            "/debug/scenario": self._debug_scenario_route,
            "/debug/timeseries": self._debug_timeseries_route,
            "/debug/profile": self._debug_profile_route,
            "/debug/registry": self._debug_registry_route,
            "/models": self._models_route,
        }
        #: guards the single on-demand profiler capture slot
        self._profile_lock = threading.Lock()
        self._profile_active: Optional[dict] = None
        self._profile_thread: Optional[threading.Thread] = None
        #: request_id → CachedRequest (reference: routingTable ``:689``)
        self._routing: Dict[str, CachedRequest] = {}
        #: epoch → {request_id: CachedRequest} (reference: historyQueues)
        self._history: Dict[int, Dict[str, CachedRequest]] = {}
        self._epoch = 0
        self._lock = threading.Lock()
        #: durable epoch/request journal (the HTTPOffset role,
        #: ``HTTPSourceV2.scala:96-113``) — survives PROCESS death
        self._journal = None
        pending = {}
        #: live decode sessions rehydrated from the journal at construction
        #: — a restarted worker hands these to its engine via
        #: ``ContinuousDecoder.restore_session`` (cold path; the pages died
        #: with the previous process)
        self.replayed_sessions: Dict[str, dict] = {}
        if journal_path is not None:
            from .journal import ServingJournal
            self._journal = ServingJournal(journal_path, fsync=journal_fsync)
            self._epoch, pending = self._journal.replay()
            self.replayed_sessions = self._journal.replay_sessions()
        # the queue must hold every rehydrated request up front (no consumer
        # exists yet) — a journal larger than max_queue must not deadlock
        # the constructor. Tenant weights come live from the process-global
        # model registry, so /models tenant edits apply without a restart.
        self._queue: AdmissionQueue = AdmissionQueue(
            max(max_queue, len(pending)),
            weight_fn=lambda t: _get_model_registry().tenant_weight(t))
        for rid, (epoch, request) in pending.items():
            cached = CachedRequest(rid, epoch, request, replayed=True)
            self._routing[rid] = cached
            self._history.setdefault(epoch, {})[rid] = cached
            # unconditional append: rehydrated requests were admitted in a
            # previous life — tenant budgets must not drop them now
            self._queue.put(cached)
        self.host = host
        self.api_path = api_path
        try:
            if transport == "async":
                self._httpd = None
                self._aio: Optional[_AsyncHTTPServer] = _AsyncHTTPServer(
                    self, host, port)
                self.port = self._aio.port
            elif transport == "threaded":
                self._aio = None
                self._httpd = ThreadingHTTPServer((host, port), _Handler)
                # keep-alive handler threads must not block process exit
                self._httpd.daemon_threads = True
                self._httpd.worker_server = self  # type: ignore[attr-defined]
                self.port = self._httpd.server_address[1]
                self._thread = threading.Thread(
                    target=self._httpd.serve_forever,
                    name=f"serving-{self.port}", daemon=True)
                self._thread.start()
        except BaseException:
            # transport startup failed (e.g. EADDRINUSE) after the journal
            # was opened — close it so the half-built object leaks no fd
            if self._journal is not None:
                self._journal.close()
            raise
        # callback gauges, sampled at scrape/snapshot time (zero hot-path
        # cost); labeled by port so concurrent servers don't collide —
        # close() drops the series
        _M_QUEUE_DEPTH.set_function(self._queue.qsize, port=str(self.port))
        _M_INFLIGHT.set_function(self.pending_count, port=str(self.port))
        # idempotent: (re)stamps mmlspark_build_info so any scraped server
        # exposes version/jax/backend even after a registry reset in tests;
        # HBM gauges only register when jax is already initialized (neither
        # triggers a backend import)
        _build_info()
        _register_hbm_gauges()
        # time-series plane (observability/timeseries.py): the registry
        # sampler is process-global and refcounted — however many servers
        # a test process runs, one scrape thread feeds one store; close()
        # releases it. The per-port sources feed the queue-saturation
        # alert and the drain-rate history suggest_retry_after seeds its
        # EWMA from after an idle gap (history_key ties the queue to its
        # labeled series).
        self._ts_sampler = _acquire_sampler()
        self._ts_sampler.add_source(
            "mmlspark_queue_saturation", self._queue_saturation,
            port=str(self.port))
        self._ts_sampler.add_source(
            "mmlspark_queue_drain_rate",
            lambda: self._queue.drain_rate() or None, port=str(self.port))
        self._queue.history_key = str(self.port)

    @property
    def address(self) -> str:
        return f"http://{self.host}:{self.port}{self.api_path}"

    def _control_route(self, path: str):
        for prefix, fn in self.control_routes.items():
            if path.startswith(prefix):
                return fn
        return None

    # -- telemetry ----------------------------------------------------------
    def _observe_request(self, transport: str, method: Optional[str],
                         code: int, seconds: Optional[float],
                         path: Optional[str] = None,
                         trace_span: Optional[object] = None) -> None:
        # "/_"-prefixed paths are internal cross-worker hops (/_reply,
        # /_forward) — counting them would double-bill one logical request
        # across workers; only the OWNING worker's user-facing answer counts
        if path is not None and path.startswith("/_"):
            return
        _M_REQUESTS.inc(transport=transport, method=method or "?",
                        code=str(code))
        tenant = "default"
        model = "default"
        if trace_span is not None:
            attrs = getattr(trace_span, "attrs", {})
            tenant = attrs.get("tenant", "default")
            # registry-resolved requests carry "name@version" — the SLO
            # class dimension check_canaries() compares windows over
            model = attrs.get("model", "default")
        # same admission rule as requests_total, so the per-class SLO
        # scorecard totals reconcile against that counter exactly
        _get_tracker().observe(transport=transport,
                               route=_classify_route(path),
                               model=model,
                               seconds=seconds, error=code >= 500,
                               tenant=tenant)
        if seconds is not None:
            # under an active span the histogram captures the trace_id as
            # an OpenMetrics exemplar (when tracing.set_exemplars is on)
            with _tracing.activate(trace_span):
                _M_REQ_LATENCY.observe(seconds, transport=transport)

    #: a watchdog stall younger than this marks /healthz degraded
    STALL_DEGRADED_SECONDS = 60.0

    def _degraded_reasons(self) -> List[str]:
        """Soft-failure signals for /healthz. Degraded is advisory — the
        response stays HTTP 200 so load balancers keep the worker in
        rotation while operators (and the e2e suite) see WHY it is
        struggling: open circuits to peers, a nearly-full admission queue,
        or a recent device-stall verdict from the watchdog."""
        reasons = []
        for peer in _open_breakers():
            reasons.append(f"breaker_open:{peer}")
        maxsize = self._queue.maxsize
        if maxsize > 0 and self._queue.qsize() >= 0.8 * maxsize:
            reasons.append(
                f"queue_pressure:{self._queue.qsize()}/{maxsize}")
        age = _get_watchdog().last_stall_age()
        if age is not None and age <= self.STALL_DEGRADED_SECONDS:
            reasons.append(f"watchdog_stall:{round(age, 1)}s_ago")
        # sustained-signal alerts (observability/timeseries.py): a rule in
        # its firing state names itself here until it resolves — one bad
        # sample never degrades health, the hysteresis window must hold
        for rule in _get_alert_engine().firing():
            reasons.append(f"alert_firing:{rule}")
        return reasons

    def _queue_saturation(self) -> float:
        """Admission-queue fill fraction, sampled into the store per tick
        (the default queue-saturation alert reads this series)."""
        maxsize = self._queue.maxsize
        return self._queue.qsize() / maxsize if maxsize > 0 else 0.0

    def _hbm_bytes_in_use(self) -> Optional[float]:
        """Summed ``mmlspark_device_hbm_bytes_in_use`` across devices, or
        None before the watchdog's HBM gauges register (jax not yet
        initialized). Rides the health digest because worker_snapshot()
        federates counters and histograms only — a gauge would never
        reach the driver's cluster series otherwise."""
        rows = _M_HBM_IN_USE.series()
        if not rows:
            return None
        total = 0.0
        for _labels, series in rows:
            try:
                total += float(series.get())
            except Exception:
                return None
        return total

    def health_digest(self) -> Dict[str, object]:
        """Compact health fields the distributed heartbeat piggybacks to
        the driver registry (serving/distributed.py): queue depth,
        in-flight count, open breakers, and the age of the last watchdog
        stall — enough for ``GET /workers`` to show WHY a worker is
        struggling without another per-worker scrape."""
        age = _get_watchdog().last_stall_age()
        return {"queue_depth": self._queue.qsize(),
                "in_flight": self.pending_count(),
                "open_breakers": sorted(_open_breakers()),
                "stall_age_seconds": None if age is None else round(age, 3),
                "hbm_bytes_in_use": self._hbm_bytes_in_use(),
                "degraded": bool(self._degraded_reasons()),
                # federated registry/admission state: which versions this
                # worker serves (live/canary per model) and its per-tenant
                # backlog — GET /workers shows rollout + fairness posture
                # cluster-wide without per-worker scrapes
                "registry": _get_model_registry().digest(),
                "admission": self._queue.snapshot(),
                # durability posture: journal size, live (recoverable)
                # sessions, per-type record counts — the fields the driver
                # needs to decide whether a dead worker's sessions are
                # worth a cold reassignment sweep
                "journal": (self._journal.digest()
                            if self._journal is not None else None)}

    def _healthz_route(self, request: HTTPRequestData) -> HTTPResponseData:
        import json as _json
        with self._lock:
            pending = len(self._routing)
            epoch = self._epoch
        reasons = self._degraded_reasons()
        body = {"status": "degraded" if reasons else "ok",
                "reasons": reasons,
                "transport": "async" if self._aio is not None else "threaded",
                "port": self.port,
                "queued": self._queue.qsize(),
                "pending": pending,
                "epoch": epoch,
                "uptime_seconds": round(_process_uptime(), 3)}
        return HTTPResponseData(
            headers=[HeaderData("Content-Type", "application/json")],
            entity=EntityData.from_string(_json.dumps(body)),
            status_line=StatusLineData(status_code=200))

    def _metrics_route(self, request: HTTPRequestData) -> HTTPResponseData:
        # Content-Type must ride in resp.headers — the transports render
        # those, not the entity's content_type field
        return HTTPResponseData(
            headers=[HeaderData("Content-Type", _PROM_CONTENT_TYPE)],
            entity=EntityData.from_string(_render_metrics(),
                                          content_type=_PROM_CONTENT_TYPE),
            status_line=StatusLineData(status_code=200))

    def _debug_traces_route(self, request: HTTPRequestData
                            ) -> HTTPResponseData:
        """Flight-recorder browser. ``GET /debug/traces`` lists summaries
        (newest first, slow-kept traces ahead of the ring);
        ``GET /debug/traces/{trace_id}`` returns one full span tree, or
        Chrome-trace JSON with ``?format=chrome`` (loadable in
        chrome://tracing / Perfetto).

        Registered in ``control_routes`` ahead of any catch-all (the
        distributed forwarder appends "/" LAST), so it stays reachable on
        every worker."""
        import json as _json

        def _resp(payload: object, status: int = 200) -> HTTPResponseData:
            return HTTPResponseData(
                headers=[HeaderData("Content-Type", "application/json")],
                entity=EntityData.from_string(_json.dumps(payload)),
                status_line=StatusLineData(status_code=status))

        recorder = _tracing.get_flight_recorder()
        path, _, query = request.url.partition("?")
        trace_id = path[len("/debug/traces"):].strip("/")
        if not trace_id:
            return _resp({"slow_threshold_seconds": recorder.slow_threshold,
                          "traces": recorder.summaries()})
        trace = recorder.get(trace_id)
        if trace is None:
            return _resp({"error": "unknown trace_id",
                          "trace_id": trace_id}, status=404)
        if "format=chrome" in query:
            return _resp(trace.to_chrome())
        return _resp(trace.to_dict())

    def _debug_slo_route(self, request: HTTPRequestData) -> HTTPResponseData:
        """``GET /debug/slo`` — the rolling SLO scorecard for every
        workload class this process has served, plus the policy verdicts
        (p99 objective, availability, error-budget burn rate).

        Each successful render is also harvested into the tuning
        :class:`~mmlspark_tpu.tuning.observations.ObservationStore` as
        ``source="slo_scorecard"`` rows (skip with ``?harvest=0``), so
        the cost model sees quality alongside throughput."""
        import json as _json
        _, _, query = request.url.partition("?")
        card = _get_tracker().scorecard()
        if "harvest=0" not in query:
            # lazy: tuning imports observability; importing it the other
            # way at module scope would be a cycle
            from ..tuning.observations import harvest_scorecard
            card["harvested"] = harvest_scorecard(card)
        return HTTPResponseData(
            headers=[HeaderData("Content-Type", "application/json")],
            entity=EntityData.from_string(_json.dumps(card)),
            status_line=StatusLineData(status_code=200))

    def _debug_scenario_route(self, request: HTTPRequestData
                              ) -> HTTPResponseData:
        """``GET /debug/scenario`` — live progress of the scenario the
        loadgen harness is currently driving (sent/done/ok/shed/error
        counts and, once finished, the scorecard summary). Registered in
        ``control_routes``, so it serves on both transports; idle state
        when no scenario has ever run in this process."""
        import json as _json
        # lazy import: loadgen is a *client* of the serving plane — the
        # server must not require it at construction time
        from ..loadgen.progress import get_progress
        return HTTPResponseData(
            headers=[HeaderData("Content-Type", "application/json")],
            entity=EntityData.from_string(
                _json.dumps(get_progress().snapshot())),
            status_line=StatusLineData(status_code=200))

    def _debug_timeseries_route(self, request: HTTPRequestData
                                ) -> HTTPResponseData:
        """``GET /debug/timeseries`` — the process-global metric history
        (observability/timeseries.py): per-series downsampled windows plus
        the alert engine's rule state. Registered in ``control_routes``,
        so it serves on both transports.

        Query params: ``seconds`` (trailing window, default 120),
        ``series`` (comma-separated name filter), and ``format=text`` for
        the terminal sparkline triage view."""
        import json as _json
        _, _, query = request.url.partition("?")
        params: Dict[str, str] = {}
        for part in query.split("&"):
            key, _, value = part.partition("=")
            if key:
                params[key] = value
        try:
            seconds = float(params.get("seconds", "120"))
        except ValueError:
            seconds = 120.0
        names = ([n for n in params["series"].split(",") if n]
                 if params.get("series") else None)
        store = _get_ts_store()
        if params.get("format") == "text":
            return HTTPResponseData(
                headers=[HeaderData("Content-Type",
                                    "text/plain; charset=utf-8")],
                entity=EntityData.from_string(
                    _render_sparklines(store, seconds, names=names)),
                status_line=StatusLineData(status_code=200))
        engine = _get_alert_engine()
        payload = store.snapshot(seconds, names=names)
        payload["alerts"] = engine.state()
        payload["firing"] = engine.firing()
        return HTTPResponseData(
            headers=[HeaderData("Content-Type", "application/json")],
            entity=EntityData.from_string(_json.dumps(payload)),
            status_line=StatusLineData(status_code=200))

    def _debug_costs_route(self, request: HTTPRequestData
                           ) -> HTTPResponseData:
        """``GET /debug/costs`` — the cost ledger's per-class resource
        totals and the top-K heavy-hitter table (each entry joinable to
        ``/debug/traces/{trace_id}``).

        Each successful render is also harvested into the tuning
        :class:`~mmlspark_tpu.tuning.observations.ObservationStore` as
        ``source="cost_ledger"`` rows (skip with ``?harvest=0``), so the
        cost model sees attributed cost alongside throughput and SLO
        facts."""
        import json as _json
        _, _, query = request.url.partition("?")
        snap = _get_ledger().snapshot()
        if "harvest=0" not in query:
            # lazy import — tuning imports observability (see /debug/slo)
            from ..tuning.observations import harvest_costs
            snap["harvested"] = harvest_costs(snap)
        return HTTPResponseData(
            headers=[HeaderData("Content-Type", "application/json")],
            entity=EntityData.from_string(_json.dumps(snap)),
            status_line=StatusLineData(status_code=200))

    #: on-demand profiler capture length ceiling (seconds)
    MAX_PROFILE_SECONDS = 60.0

    def _debug_profile_route(self, request: HTTPRequestData
                             ) -> HTTPResponseData:
        """``GET /debug/profile?seconds=N`` — capture an on-demand
        ``jax.profiler`` device trace for N seconds (default 3, capped at
        :data:`MAX_PROFILE_SECONDS`) into a fresh directory under the
        watchdog's diagnostic dir, without restarting the worker.

        The capture runs on a background thread so neither transport's
        accept path blocks for N seconds; the response returns
        immediately with the log dir to point TensorBoard at. One capture
        at a time: a second request while one is running gets 409."""
        import json as _json

        def _resp(payload: object, status: int = 200) -> HTTPResponseData:
            return HTTPResponseData(
                headers=[HeaderData("Content-Type", "application/json")],
                entity=EntityData.from_string(_json.dumps(payload)),
                status_line=StatusLineData(status_code=status))

        _, _, query = request.url.partition("?")
        seconds = 3.0
        for part in query.split("&"):
            if part.startswith("seconds="):
                try:
                    seconds = float(part[len("seconds="):])
                except ValueError:
                    return _resp({"error": "bad seconds value"}, status=400)
        seconds = min(max(seconds, 0.05), self.MAX_PROFILE_SECONDS)
        wd = _get_watchdog()
        log_dir = os.path.join(
            wd.diag_dir(), f"profile_{self.port}_{int(time.time())}")
        with self._profile_lock:
            if self._profile_active is not None:
                return _resp({"error": "profile capture already active",
                              **self._profile_active}, status=409)
            self._profile_active = {"log_dir": log_dir, "seconds": seconds}

        def _capture() -> None:
            # tracked so close() can wait for an in-flight capture: tearing
            # the process down mid-stop_trace crashes inside the profiler
            from ..utils import profiling as _profiling
            try:
                with _profiling.trace(log_dir):
                    time.sleep(seconds)
                _log_event("profile_captured", log_dir=log_dir,
                           seconds=seconds, port=self.port)
            except Exception as exc:
                # profiler unavailable (no jax backend, capture collision)
                # — the endpoint must never take the worker down
                _log_event("profile_failed", level=logging.WARNING,
                           log_dir=log_dir, error=repr(exc))
            finally:
                with self._profile_lock:
                    self._profile_active = None

        os.makedirs(log_dir, exist_ok=True)
        t = threading.Thread(target=_capture, name="mmlspark-profile",
                             daemon=True)
        self._profile_thread = t
        t.start()
        return _resp({"started": True, "log_dir": log_dir,
                      "seconds": seconds})

    def _models_route(self, request: HTTPRequestData) -> HTTPResponseData:
        """``GET /models`` — registry snapshot; ``POST /models`` — admin
        actions (load/promote/rollback/retire/tenant/check) as a JSON
        body. Registered on both transports via control_routes. HTTP
        loads are declarative (no in-process handle/warm_up — engines
        register those directly via ``get_registry().load``)."""
        import json as _json

        def _resp(payload: object, status: int = 200) -> HTTPResponseData:
            return HTTPResponseData(
                entity=EntityData.from_string(_json.dumps(payload)),
                status_line=StatusLineData(status_code=status))

        registry = _get_model_registry()
        if (request.method or "GET").upper() != "POST":
            return _resp(registry.snapshot())
        try:
            req_body = (_json.loads(request.entity.string_content())
                        if request.entity else {})
        except ValueError:
            return _resp({"error": "invalid JSON body"}, 400)
        action = str(req_body.get("action", "")).lower()
        try:
            if action == "load":
                mv = registry.load(
                    req_body["name"], req_body["version"],
                    canary_percent=float(req_body.get("canary_percent",
                                                      0.0)),
                    shadow_percent=float(req_body.get("shadow_percent",
                                                      0.0)),
                    block=bool(req_body.get("block", True)))
                return _resp({"loaded": mv.snapshot()})
            if action == "promote":
                mv = registry.promote(req_body["name"], req_body["version"])
                return _resp({"promoted": mv.snapshot()})
            if action == "rollback":
                mv = registry.rollback(req_body["name"],
                                       req_body.get("version"),
                                       reason=str(req_body.get(
                                           "reason", "manual")))
                return _resp({"rolled_back":
                              mv.snapshot() if mv else None})
            if action in ("retire", "unload"):
                out = registry.retire(
                    req_body["name"], req_body["version"],
                    drain_timeout=float(req_body.get("drain_timeout",
                                                     5.0)))
                return _resp(out)
            if action == "tenant":
                registry.set_tenant(req_body["tenant"],
                                    float(req_body["weight"]))
                return _resp({"tenants": registry.tenants()})
            if action == "check":
                return _resp({"verdicts": registry.check_canaries()})
        except KeyError as exc:
            return _resp({"error": f"missing field: {exc}"}, 400)
        except ValueError as exc:
            return _resp({"error": str(exc)}, 400)
        return _resp({"error": f"unknown action {action!r}"}, 400)

    def _debug_registry_route(self, request: HTTPRequestData
                              ) -> HTTPResponseData:
        """``GET /debug/registry`` — full rollout state plus this
        worker's admission (WFQ) snapshot: version states, canary
        verdicts, shadow diffs, tenant weights and backlogs."""
        import json as _json
        registry = _get_model_registry()
        payload = {"registry": registry.snapshot(),
                   "canary_verdicts": registry.check_canaries(),
                   "admission": self._queue.snapshot()}
        return HTTPResponseData(
            entity=EntityData.from_string(_json.dumps(payload)),
            status_line=StatusLineData(status_code=200))

    # -- ingest -------------------------------------------------------------
    def _shed(self, tenant: str = "default", reason: str = "queue_full",
              exc: Optional[BaseException] = None) -> Overloaded:
        _M_SHED.inc()
        _get_tracker().shed(
            transport="async" if self._aio is not None else "threaded",
            route="api", tenant=tenant)
        # load-aware Retry-After: backlog over the measured drain rate,
        # scaled up for a tenant shed over its weighted budget; the
        # shed_retry_after knob survives as the floor
        retry_after = self._queue.suggest_retry_after(
            floor=self.shed_retry_after,
            tenant=tenant if isinstance(exc, TenantOverBudget) else None)
        _log_event("request_shed", port=self.port,
                   queued=self._queue.qsize(), tenant=tenant,
                   reason=reason, retry_after=retry_after)
        return Overloaded(retry_after)

    def _enqueue(self, request: HTTPRequestData) -> CachedRequest:
        # headers FIRST: the tenant decides which admission budget applies
        # and the model header decides which registry version serves
        traceparent = deadline = None
        tenant = "default"
        model_name = None
        for h in request.headers:
            name = h.name.lower()
            if name == "traceparent":
                traceparent = h.value
            elif name == "x-mmlspark-deadline":
                deadline = Deadline.from_header(h.value)
            elif name == "x-mmlspark-tenant":
                # free-form header, but cardinality-safe: the SLO tracker
                # and cost ledger both collapse classes beyond MAX_CLASSES
                # into "other", so a tenant burst cannot blow up labels
                tenant = h.value.strip() or "default"
            elif name == "x-mmlspark-model":
                model_name = h.value.strip() or None
        # admission control BEFORE any span/journal/routing work is spent
        # on a request we won't park: global full sheds everyone, tenant
        # budget sheds the over-budget tenant first (raises Overloaded →
        # the transports answer 429 + Retry-After)
        try:
            self._queue.check_admit(tenant)
        except TenantOverBudget as exc:
            raise self._shed(tenant, reason="tenant_budget",
                             exc=exc) from None
        except queue.Full as exc:
            raise self._shed(tenant, reason="queue_full", exc=exc) from None
        injector = _get_injector()
        if injector.enabled:
            injector.fire("enqueue")
        # ONE root span per logical request, minted at the single point
        # every ingest shape funnels through — both transports AND the
        # distributed forwarder (whose hop carries the original traceparent,
        # so the forwarded leg continues the same trace)
        request_id = _tracing.new_request_id()
        registry = _get_model_registry()
        resolution = None
        span_extra = {}
        if model_name is not None:
            # canary/shadow split happens HERE, at ingest: the resolved
            # "name@version" rides the root span's model attr, so SLO
            # windows and ledger classes separate candidate from incumbent
            resolution = registry.resolve(model_name, request_id)
            span_extra["model"] = resolution.label
        root = _tracing.start_trace(
            "server.request", traceparent=traceparent,
            request_id=request_id, method=request.method, url=request.url,
            route=_classify_route(request.url), tenant=tenant,
            transport="async" if self._aio is not None else "threaded",
            **span_extra)
        with self._lock:
            cached = CachedRequest(
                request_id, self._epoch, request, trace_span=root,
                deadline=deadline, tenant=tenant,
                model_label=resolution.label if resolution else None)
        # write-ahead, BEFORE the routing-table insert: a failed append
        # (disk full, journal closed mid-shutdown) must error this request
        # out cleanly instead of leaking a never-queued routing entry that
        # pins its epoch's history forever
        if self._journal is not None:
            self._journal.record_request(cached.request_id, cached.epoch,
                                         request, trace_id=root.trace_id)
        with self._lock:
            self._routing[cached.request_id] = cached
            self._history.setdefault(cached.epoch, {})[cached.request_id] = cached
        try:
            self._queue.put_nowait(cached)
        except queue.Full as exc:
            # lost the admission race — undo the bookkeeping above so the
            # shed request leaks no routing entry and won't rehydrate
            with self._lock:
                self._routing.pop(cached.request_id, None)
                self._history.get(cached.epoch, {}).pop(cached.request_id,
                                                        None)
            if self._journal is not None:
                self._journal.record_reply(cached.request_id)
            if resolution is not None:
                registry.note_done(resolution.label)
                if resolution.shadow is not None:
                    registry.note_done(resolution.shadow)
            root.end(status=429)
            reason = ("tenant_budget" if isinstance(exc, TenantOverBudget)
                      else "queue_full")
            raise self._shed(tenant, reason=reason, exc=exc) from None
        if resolution is not None and resolution.shadow is not None:
            self._mirror_shadow(cached, resolution.shadow)
        return cached

    def _mirror_shadow(self, primary: CachedRequest,
                       shadow_label: str) -> None:
        """Mirror an admitted request to the shadow (candidate) version: a
        synthetic CachedRequest that flows through the normal queue/engine
        path but is never journaled and never answers a caller — both
        replies land in the registry's shadow join, which diffs them."""
        registry = _get_model_registry()
        shadow_id = _tracing.new_request_id()
        cached = CachedRequest(shadow_id, primary.epoch, primary.request,
                               tenant=primary.tenant,
                               model_label=shadow_label, shadow=True)
        with self._lock:
            self._routing[shadow_id] = cached
            self._history.setdefault(cached.epoch, {})[shadow_id] = cached
        try:
            # best-effort: a full queue drops the mirror, never the primary
            self._queue.put_nowait(cached)
        except queue.Full:
            with self._lock:
                self._routing.pop(shadow_id, None)
                self._history.get(cached.epoch, {}).pop(shadow_id, None)
            registry.note_done(shadow_label)
            return
        trace_id = (primary.trace_span.trace.trace_id
                    if primary.trace_span is not None else None)
        registry.shadow_begin(primary.request_id, shadow_id, shadow_label,
                              trace_id=trace_id)
        primary.add_done_callback(
            lambda resp: registry.shadow_result(
                primary.request_id, _entity_bytes(resp), from_shadow=False))
        cached.add_done_callback(
            lambda resp: registry.shadow_result(
                primary.request_id, _entity_bytes(resp), from_shadow=True))

    def wait_budget(self, cached: CachedRequest) -> float:
        """How long a transport may park this request: ``reply_timeout``,
        clamped to the request's propagated deadline when it carries one."""
        if cached.deadline is None:
            return self.reply_timeout
        return max(0.0, cached.deadline.cap(self.reply_timeout))

    # -- engine side --------------------------------------------------------
    def get_batch(self, max_rows: int, timeout: float = 0.1):
        """Drain up to ``max_rows`` parked requests (blocks up to ``timeout``
        for the first one). Returns a list of :class:`CachedRequest`."""
        out = []
        try:
            out.append(self._queue.get(timeout=timeout))
        except queue.Empty:
            return out
        while len(out) < max_rows:
            try:
                out.append(self._queue.get_nowait())
            except queue.Empty:
                break
        self._charge_queue_wait(out)
        return out

    def _charge_queue_wait(self, batch) -> None:
        """Bill each dequeued request's park time to its own workload
        class — the cost-ledger charge site for queue_wait_seconds."""
        ledger = _get_ledger()
        now = time.monotonic()
        for cached in batch:
            span = cached.trace_span
            cls = tid = None
            if span is not None:
                attrs = span.attrs
                cls = (str(attrs.get("transport", "untraced")),
                       str(attrs.get("route", "api")),
                       str(attrs.get("model", "default")),
                       str(attrs.get("tenant", "default")))
                tid = span.trace.trace_id
            ledger.charge("queue_wait_seconds",
                          now - cached.enqueued_at, cls=cls, trace_id=tid)

    def _take_answered(self, request_id: str) -> Optional[CachedRequest]:
        """Pop a parked request and mark it answered (routing table,
        epoch history, journal reply record) — THE bookkeeping sequence
        for every reply shape, one-shot or streaming."""
        with self._lock:
            cached = self._routing.pop(request_id, None)
            if cached is not None:
                self._history.get(cached.epoch, {}).pop(request_id, None)
        if cached is not None:
            if cached.model_label is not None:
                # in-flight accounting: retire()'s drain barrier unblocks
                # once every resolved request of a version has answered
                _get_model_registry().note_done(cached.model_label)
            # shadow mirrors were never journaled as requests — recording
            # a reply for them would orphan the journal's pairing
            if self._journal is not None and not cached.shadow:
                self._journal.record_reply(request_id)
        return cached

    def trace_span(self, request_id: str):
        """Root span of a still-parked request (None when unknown/answered
        or untraced) — the engine activates it to attach batch spans."""
        with self._lock:
            cached = self._routing.get(request_id)
        return cached.trace_span if cached is not None else None

    def model_label(self, request_id: str) -> Optional[str]:
        """Resolved ``name@version`` of a still-parked request (None when
        unknown or unversioned) — serving engines group a drained batch
        by it to dispatch each row to its version's handle."""
        with self._lock:
            cached = self._routing.get(request_id)
        return cached.model_label if cached is not None else None

    def reply(self, request_id: str, response: HTTPResponseData) -> bool:
        """Route a response to the parked connection
        (parity: ``replyTo`` ``:536-554``)."""
        cached = self._take_answered(request_id)
        if cached is None:
            return False
        if cached.trace_span is not None:
            # idempotent close (False if the transport 504'd it already);
            # ending the root hands the trace to the flight recorder
            cached.trace_span.end(
                status=response.status_line.status_code)
        cached.respond(response)
        return True

    def reply_json(self, request_id: str, payload, status: int = 200) -> bool:
        import json as _json
        ent = EntityData.from_string(_json.dumps(payload))
        return self.reply(request_id, HTTPResponseData(
            entity=ent, status_line=StatusLineData(status_code=status)))

    def reply_stream(self, request_id: str,
                     content_type: str = "text/event-stream"
                     ) -> Optional[StreamingReply]:
        """Open an incremental (SSE) reply for a parked request; returns
        the handle to ``send``/``send_event``/``close`` on, or None when
        the request is unknown/already answered. The request is marked
        answered when the stream OPENS (stream content is not journaled —
        at-most-once, like the reply record itself)."""
        cached = self._take_answered(request_id)
        if cached is None:
            return None
        # the trace covers accept → stream CLOSE: queueing, prefill and
        # every token are inside it
        stream = StreamingReply(content_type, trace_span=cached.trace_span)
        cached.respond(stream)
        return stream

    # -- epoch / replay -----------------------------------------------------
    def commit_epoch(self) -> int:
        """Close the current epoch; fully-answered epochs drop their history
        (parity: ``commit`` ``:609-645``)."""
        with self._lock:
            done = [e for e, reqs in self._history.items()
                    if e < self._epoch and not reqs]
            for e in done:
                del self._history[e]
            self._epoch += 1
            epoch = self._epoch
        if self._journal is not None:
            self._journal.record_epoch(epoch)
            self._journal.maybe_compact(epoch)
        return epoch

    def replay_unanswered(self) -> int:
        """Re-enqueue every routed-but-unanswered request — the recovery a
        restarted reader performs (parity: ``registerPartition`` rehydration
        ``:489-506``). Returns the number of requests replayed."""
        # drain the live queue BEFORE snapshotting: a request that arrives
        # between snapshot and drain would otherwise be drained but absent
        # from the snapshot, and so lost
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        with self._lock:
            pending = [c for c in self._routing.values() if not c._done.is_set()]
        for c in pending:
            self._queue.put(c)
        return len(pending)

    def pending_count(self) -> int:
        with self._lock:
            return len(self._routing)

    @property
    def closed(self) -> bool:
        return self._closed

    def close(self) -> None:
        self._closed = True
        t = self._profile_thread
        if t is not None and t.is_alive():
            # bound the wait: a capture is at most MAX_PROFILE_SECONDS of
            # sleep plus stop_trace; a wedged profiler must not wedge close
            t.join(timeout=self.MAX_PROFILE_SECONDS + 10.0)
        _M_QUEUE_DEPTH.remove(port=str(self.port))
        _M_INFLIGHT.remove(port=str(self.port))
        # drop this port's sampler sources, then release the refcounted
        # sampler (the scrape thread stops with the last server); None'd
        # so a double close() cannot over-release
        if self._ts_sampler is not None:
            self._ts_sampler.remove_source("mmlspark_queue_saturation",
                                           port=str(self.port))
            self._ts_sampler.remove_source("mmlspark_queue_drain_rate",
                                           port=str(self.port))
            self._ts_sampler = None
            _release_sampler()
        if self._aio is not None:
            self._aio.close()
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._thread.join(timeout=5)
        if self._journal is not None:
            self._journal.close()
