"""HTTP generation endpoint over the continuous-batching decoder.

Completes the LLM-serving story (``serving/continuous.py``): clients POST
``{"tokens": [...], "max_new": N}`` and get ``{"tokens": [...]}`` back,
with every in-flight request sharing the slot-pool decoder. The HTTP
plumbing is the same WorkerServer the stateless engine uses
(parity anchor: ``HTTPSourceV2.scala:476-697``); what's new is the
lifecycle — a request parks across MANY engine ticks instead of one
transform, so the loop interleaves (admit → tick → reply-finished) rather
than (drain → transform → reply).

One driver thread owns the decoder (submissions ride the decoder's own
lock); replies route back through the server's request cache exactly like
batch replies, so journaling/replay semantics are untouched.
"""

from __future__ import annotations

import json
import logging
import threading
import time
import traceback
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from ..observability import (counter as _metric_counter,
                             histogram as _metric_histogram)
from ..observability import tracing as _tracing
from .continuous import ContinuousDecoder
from .server import StreamingReply, WorkerServer

__all__ = ["GenerationEngine", "Round", "recent_rounds", "recent_timelines",
           "RECENT_ROUNDS", "RECENT_TIMELINES"]

_log = logging.getLogger("mmlspark_tpu.serving")

#: How many requests' timelines :func:`recent_timelines` reaches back.
RECENT_TIMELINES = 4096
_RECENT: "deque[Dict[str, object]]" = deque(maxlen=RECENT_TIMELINES)


def recent_timelines() -> List[Dict[str, object]]:
    """The timelines (``submitted_at``, ``admitted_at``, ``first_token_at``,
    ``finished_at`` in ``time.perf_counter()`` seconds, ``prompt_tokens``,
    ``new_tokens``; a streamed request's also ``writes``,
    ``write_lag_sum_s``, ``write_lag_max_s``: its chunks' wait in the
    transport, ``StreamingReply.write_lag``, as the engine finished it)
    of the last :data:`RECENT_TIMELINES` requests this process's engines
    replied to, oldest first: what each request's root span closed with.
    The histograms keep the distribution since start and
    the flight recorder the traces it judges worth an operator's look;
    a percentile over one stretch of traffic needs every request of it,
    and a list shorter than :data:`RECENT_TIMELINES` has dropped none."""
    return list(_RECENT)


class Round(NamedTuple):
    """One round of an engine's loop (admit, step, pump, reply), as the
    engine's thread accounted for it: what a span's wall time cannot tell
    apart, its seconds on the CPU, waiting for the device, and neither."""
    #: ``time.perf_counter()`` at the round's end; the round began
    #: ``wall_s`` before (rounds follow each other without a gap, but for
    #: the loop's idle sleep, which belongs to none)
    ended_at: float
    wall_s: float
    #: the engine thread's CPU seconds in the round (``time.thread_time``;
    #: where the kernel accounts CPU time by its timer tick, 10 ms on the
    #: benchmark's machine, a single round's is 0 or a tick: read sums)
    cpu_s: float
    #: its seconds inside ``continuous.drain``, waiting for the device;
    #: ``wall_s - cpu_s - wait_s`` is what it spent off the CPU otherwise:
    #: waiting for the GIL, for a lock, in a blocking call
    wait_s: float
    #: decode dispatches (``decoder.tick`` spans opened)
    ticks: int
    #: token events handed to streaming replies, and the tokens they carry
    stream_events: int
    stream_tokens: int


#: How many rounds :func:`recent_rounds` reaches back: two minutes at 133
#: rounds a second (the fastest benchmark cell's).
RECENT_ROUNDS = 16384
_ROUNDS: "deque[Round]" = deque(maxlen=RECENT_ROUNDS)

_M_ROUND_SECONDS = _metric_histogram(
    "mmlspark_generation_round_seconds",
    "One round of the generation engine's loop (admit, step, pump, reply) "
    "on the wall clock",
    buckets=(0.0005, 0.001, 0.002, 0.003, 0.004, 0.006, 0.008, 0.01, 0.015,
             0.02, 0.03, 0.05, 0.1, 0.25, 1.0, 5.0))
_M_ROUND_CPU = _metric_counter(
    "mmlspark_generation_round_cpu_seconds_total",
    "CPU seconds of the generation engine's thread inside its rounds")
_M_STREAM_EVENTS = _metric_counter(
    "mmlspark_generation_stream_events_total",
    "Token events handed to streaming replies")
_M_STREAM_TOKENS = _metric_counter(
    "mmlspark_generation_stream_tokens_total",
    "Tokens those events carried")


def recent_rounds() -> List[Round]:
    """The last :data:`RECENT_ROUNDS` rounds of this process's engines,
    oldest first (a list that long may have dropped some)."""
    return list(_ROUNDS)


@dataclass
class _InFlight:
    """One parked generation: the server request, the decoder ticket, an
    open SSE stream when the client asked for one, and how many tokens
    that stream has already been sent."""
    rid: str
    ticket: object
    stream: Optional[StreamingReply] = None
    sent: int = 0


class GenerationEngine:
    """Serve ``{"tokens": [...], "max_new": N}`` → ``{"tokens": [...]}``
    over a :class:`ContinuousDecoder` slot pool."""

    def __init__(self, params, cfg, *, max_slots: int = 4,
                 max_len: int = 256, eos_id: Optional[int] = None,
                 default_max_new: int = 32,
                 host: str = "127.0.0.1", port: int = 0,
                 api_path: str = "/generate",
                 reply_timeout: float = 120.0,
                 transport: str = "threaded",
                 steps_per_dispatch: int = 1,
                 pipeline_depth: int = 2,
                 prefill_ahead: int = 0,
                 draft_params=None, draft_cfg=None, gamma: int = 4,
                 page_size: Optional[int] = None, prefill_chunk: int = 256,
                 kv_pages: Optional[int] = None, autotune: bool = False,
                 paged_attn: Optional[str] = None, mesh=None):
        self.decoder = ContinuousDecoder(
            params, cfg, max_slots=max_slots, max_len=max_len,
            eos_id=eos_id, steps_per_dispatch=steps_per_dispatch,
            pipeline_depth=pipeline_depth, prefill_ahead=prefill_ahead,
            draft_params=draft_params, draft_cfg=draft_cfg, gamma=gamma,
            page_size=page_size, prefill_chunk=prefill_chunk,
            kv_pages=kv_pages, autotune=autotune,
            paged_attn=paged_attn, mesh=mesh)
        self.default_max_new = int(default_max_new)
        self.server = WorkerServer(host, port, api_path,
                                   reply_timeout=reply_timeout,
                                   transport=transport)
        #: decoder rid -> _InFlight — ONE source of truth for in-flight
        #: work, mutated at one site per transition
        self._inflight: Dict[int, _InFlight] = {}
        #: what ``_pump_streams`` has sent since the engine was built
        self._stream_events = 0
        self._stream_tokens = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @property
    def address(self) -> str:
        return self.server.address.rstrip("/") + "/"

    def start(self) -> "GenerationEngine":
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"generation-engine-{self.server.port}")
        self._thread.start()
        return self

    def _admit_one(self, cached) -> None:
        """Parse + submit ONE request; any failure 400s only that request
        (a malformed field must not poison the batch or the in-flight set —
        the same isolation ServingEngine gets from its per-batch try).
        ``"stream": true`` opens a Server-Sent-Events reply instead: each
        engine tick pushes the newly emitted tokens as a ``data:`` event,
        and the final event carries ``done`` plus the full sequence."""
        rid = cached.request_id
        try:
            ent = cached.request.entity
            body = json.loads(ent.string_content()) if ent else {}
            toks = body.get("tokens")
            if not toks:
                raise ValueError("missing or empty 'tokens'")
            mn = int(body.get("max_new", self.default_max_new))
            pl = body.get("prefix_len")
            stream = bool(body.get("stream", False))
            # under the request's root span: the ticket keeps it, and the
            # decoder marks admission and first token on it
            with _tracing.activate(cached.trace_span):
                ticket = self.decoder.submit(
                    np.asarray(toks, np.int32), mn,
                    temperature=float(body.get("temperature", 0.0)),
                    top_k=int(body.get("top_k", 0)),
                    top_p=float(body.get("top_p", 1.0)),
                    seed=int(body.get("seed", 0)),
                    prefix_key=body.get("prefix_key"),
                    prefix_len=int(pl) if pl is not None else None)
        except Exception as e:
            self.server.reply_json(rid, {"error": str(e)}, status=400)
            return
        handle = self.server.reply_stream(rid) if stream else None
        self._inflight[ticket.rid] = _InFlight(rid, ticket, handle)

    def _admit_http(self, idle: bool) -> None:
        # mid-stream (live slots) the drain is non-blocking: a blocking
        # poll here would add its timeout to EVERY emitted token's latency;
        # only an idle engine waits for work
        for cached in self.server.get_batch(64, timeout=0.002 if idle else 0):
            self._admit_one(cached)

    def _pump_streams(self) -> None:
        """Push newly emitted tokens on every streaming reply."""
        events = tokens = 0
        for f in self._inflight.values():
            if f.stream is None:
                continue
            fresh = f.ticket.tokens[f.sent:]
            if fresh:
                f.stream.send_event({"tokens": list(fresh)})
                f.sent += len(fresh)
                events += 1
                tokens += len(fresh)
        self._stream_events += events
        self._stream_tokens += tokens

    def _reply_finished(self) -> None:
        """Answer the requests that are done. A streamed request's
        timeline also says what its chunks waited in the transport
        (``StreamingReply.write_lag``) AS OF NOW: the closing event is not
        yet sent, and a chunk of this round's pump may not be written."""
        done = [drid for drid, f in self._inflight.items()
                if f.ticket.done]
        for drid in done:
            f = self._inflight.pop(drid)
            rid, ticket, handle = f.rid, f.ticket, f.stream
            err = getattr(ticket, "error", None)
            timeline = ticket.timeline()
            if handle is not None:
                timeline.update(handle.write_lag())
            _RECENT.append(timeline)
            # the root span closes with the request's timeline: at the
            # stream's close, or with the one reply
            if handle is not None:
                if err is not None:
                    handle.send_event({"error": str(err)})
                else:
                    handle.send_event({"done": True,
                                       "tokens": list(ticket.tokens)})
                handle.close(**timeline)
                continue
            if ticket.span is not None:
                ticket.span.set(**timeline)
            if err is not None:
                # per-request admit failure (e.g. prefix mismatch): 400s
                # this client alone, the batch keeps decoding
                self.server.reply_json(rid, {"error": str(err)},
                                       status=400)
            else:
                self.server.reply_json(rid, {"tokens": ticket.tokens})
        if done:
            self.server.commit_epoch()

    def _marks(self):
        """The clocks and the running sums a :class:`Round` is the
        difference of, in its fields' order."""
        stats = self.decoder.stats
        return (time.perf_counter(), time.thread_time(),
                stats["drain_seconds"], stats["ticks"],
                self._stream_events, self._stream_tokens)

    def _loop(self) -> None:
        span = _tracing.span
        mark = self._marks()
        while not self._stop.is_set():
            try:
                with span("engine.admit_http"):
                    self._admit_http(idle=not self._inflight)
                stepped = self.decoder.step()
                with span("engine.pump_streams"):
                    self._pump_streams()
                with span("engine.reply_finished"):
                    self._reply_finished()
                now = self._marks()
                row = Round(now[0], *(b - a for a, b in zip(mark, now)))
                mark = now
                _ROUNDS.append(row)
                _M_ROUND_SECONDS.observe(row.wall_s)
                _M_ROUND_CPU.inc(row.cpu_s)
                if row.stream_events:
                    _M_STREAM_EVENTS.inc(row.stream_events)
                    _M_STREAM_TOKENS.inc(row.stream_tokens)
                if stepped == 0 and not self._inflight:
                    with span("engine.idle"):
                        self._stop.wait(0.005)
                    mark = self._marks()    # the sleep is no round's
            except Exception:
                _log.error("generation engine tick failed:\n%s",
                           traceback.format_exc())
                # fail every in-flight request rather than hang clients,
                # and free the slot pool (nothing will retire those slots
                # if step() keeps raising)
                self._fail_inflight("internal error", 500)
                try:
                    self.decoder.cancel_all()
                except Exception:
                    _log.error("decoder cancel_all failed:\n%s",
                               traceback.format_exc())
                # backoff: a persistent failure must not busy-spin the host
                self._stop.wait(0.2)

    def _fail_inflight(self, message: str, status: int) -> None:
        """Answer every in-flight request with an error — streaming
        clients get a final error event and a closed stream."""
        for f in self._inflight.values():
            if f.stream is not None:
                f.stream.send_event({"error": message})
                f.stream.close()
            else:
                self.server.reply_json(f.rid, {"error": message},
                                       status=status)
        self._inflight.clear()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
        # fail in-flight clients NOW instead of leaving their connections
        # parked until reply_timeout's 504
        self._fail_inflight("server shutting down", 503)
        self.decoder.cancel_all()
        self.decoder.stop()
        self.server.close()

    def __enter__(self) -> "GenerationEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
