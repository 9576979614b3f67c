"""Request tracing: W3C traceparent parsing, span trees, contextvars
propagation across thread hops (the prefetch-worker regression), the
flight recorder's ring + slow-keep tiers, OpenMetrics exemplars, trace
stamping on events/journal, and serving end-to-end on both transports.

The E2E test is the PR's acceptance bar: a POST carrying a traceparent
must come back with X-Request-Id / traceparent echo headers AND leave a
/debug/traces entry whose tree nests server.request → engine.batch →
runner.* stage spans — including the coerce/pad spans that run on the
prefetch WORKER thread (the old ``threading.local`` dead-end dropped
those silently).
"""

import json
import logging
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu import observability as obs
from mmlspark_tpu.observability import tracing as tr

TID = "ab" * 16
SID = "cd" * 8


@pytest.fixture(autouse=True)
def _clean_slate():
    obs.reset_all()
    tr.get_flight_recorder().clear()
    tr.configure_recorder(capacity=64, slow_threshold=1.0, slow_keep=32)
    yield
    tr.set_exemplars(False)
    tr.get_flight_recorder().clear()
    tr.configure_recorder(capacity=64, slow_threshold=1.0, slow_keep=32)
    obs.reset_all()


# ---------------------------------------------------------------------------
# traceparent


def test_parse_traceparent_roundtrip_and_normalization():
    assert tr.parse_traceparent(f"00-{TID}-{SID}-01") == (TID, SID)
    # input is case-normalized; trailing/leading whitespace tolerated
    assert tr.parse_traceparent(f" 00-{TID.upper()}-{SID}-00 ") == (TID, SID)
    # a future version may carry extra fields after flags
    assert tr.parse_traceparent(f"cc-{TID}-{SID}-01-extra") == (TID, SID)


@pytest.mark.parametrize("header", [
    None, "", "garbage", f"00-{TID}-{SID}",            # too few parts
    f"00-{'0' * 32}-{SID}-01",                         # all-zero trace id
    f"00-{TID}-{'0' * 16}-01",                         # all-zero span id
    f"ff-{TID}-{SID}-01",                              # forbidden version
    f"00-{TID}-{SID}-01-extra",                        # v00 is exactly 4 parts
    f"00-{TID[:-2]}-{SID}-01",                         # short trace id
    f"00-{TID}-{SID}zz"[:len(f'00-{TID}-{SID}-01')],   # non-hex
])
def test_parse_traceparent_rejects_malformed(header):
    assert tr.parse_traceparent(header) is None


def test_start_trace_continues_inbound_context():
    root = tr.start_trace("server.request", traceparent=f"00-{TID}-{SID}-01")
    assert root.trace_id == TID
    assert root.parent_id == SID
    assert root.trace.remote_parent_id == SID
    # the echo header advertises OUR span as the parent of downstream work
    echoed = tr.format_traceparent(root)
    assert echoed == f"00-{TID}-{root.span_id}-01"
    # malformed inbound → brand-new trace, never an error
    fresh = tr.start_trace("server.request", traceparent="ff-bogus")
    assert fresh.trace_id != TID and fresh.parent_id is None


# ---------------------------------------------------------------------------
# span trees


def test_span_tree_nesting_and_events():
    root = tr.start_trace("req", request_id="rid-1")
    with tr.activate(root):
        assert tr.current_trace_id() == root.trace_id
        assert tr.current_request_id() == "rid-1"
        with tr.start_span("outer", k="v") as outer:
            tr.add_event("milestone", n=1)
            with tr.start_span("inner"):
                pass
        assert outer.ended
    assert root.end(status=200)
    doc = root.trace.to_dict()
    assert doc["name"] == "req" and doc["request_id"] == "rid-1"
    (troot,) = doc["roots"]
    assert troot["name"] == "req"
    (child,) = troot["children"]
    assert child["name"] == "outer" and child["attrs"] == {"k": "v"}
    assert child["events"][0]["name"] == "milestone"
    (grand,) = child["children"]
    assert grand["name"] == "inner" and grand["children"] == []


def test_span_end_is_idempotent():
    root = tr.start_trace("req")
    assert root.end() is True
    dur = root.duration
    time.sleep(0.01)
    assert root.end() is False          # late double-close is harmless
    assert root.duration == dur


def test_start_span_inert_outside_a_trace():
    with tr.start_span("orphan") as s:
        assert s is None
        tr.add_event("nothing")         # no-op, must not raise
    assert tr.current_span() is None


def test_span_cap_drops_not_grows():
    root = tr.start_trace("req")
    with tr.activate(root):
        for i in range(tr.MAX_SPANS_PER_TRACE + 10):
            with tr.start_span(f"s{i}"):
                pass
    root.end()
    assert len(root.trace.spans) == tr.MAX_SPANS_PER_TRACE
    assert root.trace.dropped == 11
    assert root.trace.summary()["dropped"] == 11


def test_propagate_carries_context_into_plain_thread():
    seen = {}
    root = tr.start_trace("req", request_id="rid-2")

    def worker():
        seen["trace_id"] = tr.current_trace_id()
        seen["request_id"] = tr.current_request_id()
        with tr.start_span("worker.step"):
            pass

    with tr.activate(root):
        t = threading.Thread(target=tr.propagate(worker))
        t.start()
        t.join(5)
        bare = threading.Thread(target=worker)  # un-propagated control
    root.end()
    assert seen == {"trace_id": root.trace_id, "request_id": "rid-2"}
    assert "worker.step" in [s.name for s in root.trace.spans]
    bare.start()
    bare.join(5)
    assert seen["trace_id"] is None     # empty context without propagate()


# ---------------------------------------------------------------------------
# prefetch-worker regression (the span primitive off the dispatch thread)


def _make_runner(mini_batch_size=2, prefetch_depth=2, n=8):
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.runner import BatchRunner

    data = np.arange(n, dtype=np.float32)

    def kernel(params, feeds):
        return {"y": feeds["x"] * params["w"]}

    return BatchRunner(jax.jit(kernel), {"w": jnp.float32(2.0)},
                       coerce=lambda sl: {"x": data[sl]},
                       put=jax.device_put,
                       mini_batch_size=mini_batch_size,
                       prefetch_depth=prefetch_depth), n


def test_prefetch_worker_spans_land_in_span_log():
    """coerce/pad run on the PrefetchIterator worker thread; the one span
    primitive records them there with no tracer to install (the old
    threading.local tracer lost them), under the worker's thread ident."""
    import threading
    runner, n = _make_runner(mini_batch_size=2, prefetch_depth=2, n=8)
    tr._SPAN_LOG.clear()
    out = runner.run_and_drain(n)
    assert sum(b for _, b in out) == n
    log = tr.span_log()
    names = [name for name, *_ in log]
    assert names.count("runner.coerce") == 4
    assert names.count("runner.pad") == 4
    assert names.count("runner.next") == 5      # the last finds it exhausted
    # the counter counts the waits that brought a batch, as before the span
    assert runner.counters.snapshot()["prefetch_wait"]["calls"] == 4
    assert names.count("runner.h2d") == names.count("runner.dispatch") == 4
    assert "runner.run" in names and "runner.d2h" in names
    me = threading.get_ident()
    assert {t for name, t, *_ in log if name == "runner.coerce"} != {me}
    assert {t for name, t, *_ in log if name == "runner.dispatch"} == {me}
    # a stage's span and its counter come from one statement
    stages = runner.counters.snapshot()
    assert stages["h2d"]["calls"] == 4 and stages["coerce"]["calls"] == 4


def test_prefetch_worker_spans_join_request_trace():
    runner, n = _make_runner(mini_batch_size=2, prefetch_depth=2, n=8)
    root = tr.start_trace("req")
    with tr.activate(root):
        runner.run_and_drain(n)
    root.end()
    spans = root.trace.spans
    coerce = [s for s in spans if s.name == "runner.coerce"]
    assert len(coerce) == 4
    # ... and they really ran off-thread: the prefetch worker's name, not
    # the dispatch thread that owns the root span
    assert {s.thread for s in coerce} != {root.thread}
    events = [e["name"] for s in spans for e in s.events]
    assert "pad_bucket" in events
    # the first batch compiled; a warm batch is not an event any more
    assert "cache_miss" in events and "cache_hit" not in events


# ---------------------------------------------------------------------------
# flight recorder


def _ended_trace(duration=None):
    root = tr.start_trace("req")
    root.end()
    if duration is not None:
        root._dur = duration            # deterministic tier selection
    return root.trace


def test_recorder_ring_wraps_but_slow_traces_survive():
    rec = tr.FlightRecorder(capacity=4, slow_threshold=0.5, slow_keep=2)
    slow = _ended_trace(duration=2.0)
    rec.record(slow)
    fast = [_ended_trace(duration=0.001) for _ in range(10)]
    for t in fast:
        rec.record(t)
    # the ring wrapped ten fast traces through capacity 4 ...
    ids = [t.trace_id for t in rec.traces()]
    assert len(ids) == 5
    # ... newest first, slow-kept ahead of the ring, the slow one intact
    assert ids[0] == slow.trace_id
    assert ids[1:] == [t.trace_id for t in reversed(fast[-4:])]
    assert rec.get(slow.trace_id) is slow
    assert rec.get(fast[0].trace_id) is None          # evicted


def test_recorder_slow_keep_evicts_oldest_slow():
    rec = tr.FlightRecorder(capacity=4, slow_threshold=0.5, slow_keep=2)
    slows = [_ended_trace(duration=1.0 + i) for i in range(3)]
    for t in slows:
        rec.record(t)
    assert rec.get(slows[0].trace_id) is None
    assert [t.trace_id for t in rec.traces()] == [
        slows[2].trace_id, slows[1].trace_id]


def test_trace_to_chrome_shape():
    root = tr.start_trace("req")
    with tr.activate(root):
        with tr.start_span("stage", rows=3):
            pass
    root.end()
    doc = root.trace.to_chrome()
    assert doc["displayTimeUnit"] == "ms"
    stage = next(e for e in doc["traceEvents"] if e["name"] == "stage")
    assert stage["ph"] == "X" and stage["pid"] == 0
    assert stage["args"]["rows"] == 3
    assert stage["args"]["trace_id"] == root.trace_id


# ---------------------------------------------------------------------------
# exemplars


def test_metrics_unchanged_until_exemplars_enabled():
    h = obs.histogram("t_exemplar_seconds", "t", ())
    root = tr.start_trace("req")
    with tr.activate(root):
        h.observe(0.01)
    root.end()
    text = obs.render()
    assert "# {" not in text            # byte-identical Prometheus 0.0.4
    assert not tr.exemplars_enabled()

    tr.set_exemplars(True)
    assert tr.exemplars_enabled()
    root2 = tr.start_trace("req2")
    with tr.activate(root2):
        h.observe(0.02)
    root2.end()
    enabled = obs.render()
    assert f'# {{trace_id="{root2.trace_id}"}}' in enabled

    # flipping back off hides them again — scrape format reverts cleanly
    tr.set_exemplars(False)
    assert "# {" not in obs.render()


def test_exemplars_skip_observations_outside_a_trace():
    tr.set_exemplars(True)
    h = obs.histogram("t_exemplar2_seconds", "t", ())
    h.observe(0.01)                     # no active span → no exemplar
    assert "# {" not in obs.render()


# ---------------------------------------------------------------------------
# event log + journal stamping


def test_event_log_stamps_trace_and_request_id(caplog):
    root = tr.start_trace("req", request_id="rid-9")
    with caplog.at_level(logging.INFO, logger="mmlspark_tpu.events"):
        with tr.activate(root):
            obs.log_event("inside", x=1)
        obs.log_event("outside")
    root.end()
    inside, outside = [json.loads(r.getMessage()) for r in caplog.records]
    assert inside["event"] == "inside"
    assert inside["trace_id"] == root.trace_id
    assert inside["request_id"] == "rid-9"
    assert "trace_id" not in outside and "request_id" not in outside


def test_journal_persists_trace_id_through_compaction(tmp_path):
    from mmlspark_tpu.io.http.schema import EntityData, HTTPRequestData
    from mmlspark_tpu.serving.journal import ServingJournal

    def _req(body):
        return HTTPRequestData(entity=EntityData.from_string(body))

    p = str(tmp_path / "j.jsonl")
    j = ServingJournal(p)
    j.record_request("a", 0, _req("one"), trace_id=TID)
    j.record_request("b", 0, _req("two"))
    j.record_reply("b")
    recs = [json.loads(ln) for ln in open(p).read().splitlines()]
    assert recs[0]["trace"] == TID
    assert "trace" not in recs[1]
    # compaction rewrites the journal from raw records — the trace join
    # key must survive for replayed (crash-recovered) requests
    assert j.maybe_compact(epoch=1, min_lines=1)
    recs = [json.loads(ln) for ln in open(p).read().splitlines()]
    (live,) = [r for r in recs if r.get("t") == "req"]
    assert live["id"] == "a" and live["trace"] == TID
    j.close()


# ---------------------------------------------------------------------------
# serving end-to-end


def test_healthz_uptime_and_build_info():
    import requests
    from mmlspark_tpu.serving import WorkerServer
    server = WorkerServer()
    try:
        body = requests.get(
            f"http://127.0.0.1:{server.port}/healthz", timeout=10).json()
        assert body["status"] == "ok"
        assert body["uptime_seconds"] >= 0.0
        snap = obs.snapshot()
        (series,) = snap["mmlspark_build_info"]["series"]
        assert series["value"] == 1
        assert set(series["labels"]) == {"version", "jax", "backend"}
        assert series["labels"]["version"] not in ("", None)
    finally:
        server.close()


@pytest.mark.parametrize("transport", ["threaded", "async"])
def test_traced_request_end_to_end(transport):
    """Acceptance: POST with a traceparent through a real ServingEngine →
    echo headers on the response, and /debug/traces serves the span tree
    server.request → engine.batch → runner.* with prefetch-worker spans."""
    import jax
    import jax.numpy as jnp
    import requests
    from mmlspark_tpu.models.runner import BatchRunner
    from mmlspark_tpu.serving import ServingEngine

    def kernel(params, feeds):
        return {"y": feeds["x"] * params["w"]}

    jitted = jax.jit(kernel)
    params = {"w": jnp.float32(2.0)}

    def pipeline(df):
        x = np.asarray(df["x"], dtype=np.float32)
        # repeat each row so even a 1-row request spans several
        # minibatches and the prefetch worker thread actually runs
        rep = np.repeat(x, 8)
        runner = BatchRunner(jitted, params,
                             coerce=lambda sl: {"x": rep[sl]},
                             put=jax.device_put,
                             mini_batch_size=2, prefetch_depth=2)
        outs = runner.run_and_drain(len(rep))
        vals = np.concatenate([np.asarray(o["y"])[:b] for o, b in outs])
        return df.with_column("reply", vals[::8][:len(x)].astype(float))

    sent = f"00-{TID}-{SID}-01"
    with ServingEngine(pipeline, schema={"x": float},
                       transport=transport) as eng:
        r = requests.post(eng.address, json={"x": 21.0},
                          headers={"traceparent": sent}, timeout=30)
        assert r.status_code == 200 and r.json() == 42.0
        # echo headers: the request id for log joins, OUR root span as the
        # downstream parent of the caller's trace
        rid = r.headers["X-Request-Id"]
        echoed = tr.parse_traceparent(r.headers["traceparent"])
        assert echoed is not None and echoed[0] == TID

        base = f"http://127.0.0.1:{eng.server.port}/debug/traces"
        listing = requests.get(base, timeout=10).json()
        assert listing["slow_threshold_seconds"] == pytest.approx(
            tr.get_flight_recorder().slow_threshold)
        summary = next(t for t in listing["traces"]
                       if t["trace_id"] == TID)
        assert summary["request_id"] == rid
        assert summary["duration_s"] > 0

        doc = requests.get(f"{base}/{TID}", timeout=10).json()
        (troot,) = doc["roots"]
        assert troot["name"] == "server.request"
        assert troot["parent_id"] == SID            # continued, not minted
        assert troot["attrs"]["request_id"] == rid
        batch = next(c for c in troot["children"]
                     if c["name"] == "engine.batch")
        run = next(c for c in batch["children"] if c["name"] == "runner.run")
        flat, stack = [], [run]
        while stack:
            node = stack.pop()
            flat.append(node)
            stack.extend(node["children"])
        names = [n["name"] for n in flat]
        assert "runner.coerce" in names and "runner.pad" in names
        assert "runner.d2h" in [c["name"] for c in batch["children"]] \
            or "runner.d2h" in names
        # the coerce spans ran on the prefetch worker thread
        coerce_threads = {n["thread"] for n in flat
                          if n["name"] == "runner.coerce"}
        assert coerce_threads and coerce_threads != {troot["thread"]}

        chrome = requests.get(f"{base}/{TID}?format=chrome",
                              timeout=10).json()
        assert chrome["displayTimeUnit"] == "ms"
        assert any(e["name"] == "server.request"
                   for e in chrome["traceEvents"])

        missing = requests.get(f"{base}/{'9' * 32}", timeout=10)
        assert missing.status_code == 404
        assert missing.json()["error"] == "unknown trace_id"


def test_request_without_traceparent_mints_fresh_trace():
    import requests
    from mmlspark_tpu.serving import ServingEngine

    def pipeline(df):
        return df.with_column("reply", np.asarray(df["x"]) + 1.0)

    with ServingEngine(pipeline, schema={"x": float}) as eng:
        r = requests.post(eng.address, json={"x": 1.0}, timeout=30)
        assert r.status_code == 200
        echoed = tr.parse_traceparent(r.headers["traceparent"])
        assert echoed is not None
        trace = tr.get_flight_recorder().get(echoed[0])
        assert trace is not None
        assert trace.root.attrs["request_id"] == r.headers["X-Request-Id"]
