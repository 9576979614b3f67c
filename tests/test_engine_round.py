"""The engine thread's round, accounted for from inside (docs/observability.md,
"The engine's round"): the five phases that partition the benchmark's
``host_ms_per_tick.generate`` on a synthetic span list; the round log, the
stream counters and a chunk's wait in the transport on a tiny streamed
``GenerationEngine``, on both transports; the collector's pauses."""

import gc
import json
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

from benchmarks import idle_gaps, run as bench_run
from benchmarks.layer_metrics import _gc, _host_tick, _rounds
from mmlspark_tpu import observability as obs
from mmlspark_tpu.observability import registry
from mmlspark_tpu.observability import tracing as tr
from mmlspark_tpu.serving import generation
from mmlspark_tpu.serving.server import StreamingReply

# -- the five phases ----------------------------------------------------------
STRETCH = (1_000, 101_000)
ENGINE, HANDLER = 7, 9
#: one whole round, the loop's sleep, and a round the stretch cuts in two;
#: ``(name, start, end)`` in ns. Two spans no phase names lie under a chunk
#: window and under the pump.
ROUNDS = [
    ("engine.admit_http", 1_000, 1_200),
    ("decoder.step", 1_200, 9_000),
    ("decoder.admit", 1_300, 1_500),
    ("decoder.tick", 1_600, 3_600),              # a window rides it
    ("continuous.prefill_chunk", 1_700, 3_500),
    ("a.later.prs.span", 2_000, 2_300),
    ("decoder.account", 3_600, 4_000),
    ("decoder.state_snapshot", 4_100, 4_300),
    ("continuous.drain", 4_500, 6_500),
    ("decoder.retire", 6_500, 8_500),
    ("decoder.account", 6_600, 6_800),
    ("decoder.compact", 8_000, 8_400),
    ("engine.pump_streams", 9_000, 9_800),
    ("another.later.span", 9_100, 9_200),
    ("engine.reply_finished", 9_800, 10_000),
    ("engine.idle", 10_000, 15_000),
    ("decoder.step", 100_000, 103_000),
    ("decoder.tick", 100_500, 101_500),
]
#: a round that closed before the stretch began: the ring holds the stretch
BEFORE = [("engine.admit_http", 0, 100)]
#: ns in the stretch, by hand: two ticks start inside it
WANT_NS = dict(
    schedule=200 + 200 + 1_000 + 500,   # admit_http, admit, step's own x 2
    launch=200 + 1_500 + 300 + 200 + 400 + 500,
    account=400 + 200,
    retire=2_000 - 200 - 400,
    emit=800 + 200)


def found_of(engine_rows):
    return dict(stretch=STRETCH, threads={
        ENGINE: list(engine_rows),
        HANDLER: [("a.handlers.span", 2_000, 50_000)]})


@pytest.fixture
def spans(monkeypatch):
    """``idle_gaps.analysis`` answers with the synthetic list."""
    def use(engine_rows):
        monkeypatch.setattr(idle_gaps, "analysis",
                            lambda trace, counters: found_of(engine_rows))
    return use


def reader(name):
    return bench_run.load_by_path("layer_metrics", name).read


@pytest.mark.parametrize("phase", sorted(WANT_NS))
def test_a_phase_is_the_self_time_of_its_spans(spans, phase):
    spans(BEFORE + ROUNDS)
    got = reader(f"host_{phase}_ms_per_tick.generate")(None, {}, {}, {}, None)
    assert got == pytest.approx(WANT_NS[phase] / 1e6 / 2, abs=1e-12)


def test_the_five_phases_add_up_to_host_ms_per_tick(spans):
    spans(BEFORE + ROUNDS)
    whole = reader("host_ms_per_tick.generate")(None, {}, {}, {}, None)
    parts = _host_tick.phases(None, {})
    assert set(parts) == set(WANT_NS)
    assert whole == pytest.approx(8_000 / 1e6 / 2, abs=1e-12)
    assert sum(parts.values()) == pytest.approx(whole, abs=1e-9)   # ms


@pytest.mark.parametrize("phase", sorted(WANT_NS))
def test_a_wrapped_ring_reads_none(spans, phase):
    """The oldest row closed after the stretch began: the ring may have
    dropped spans of the stretch."""
    spans(ROUNDS)
    assert reader(f"host_{phase}_ms_per_tick.generate")(
        None, {}, {}, {}, None) is None


def test_no_trace_and_no_tick_read_none(spans, monkeypatch):
    spans(BEFORE + [r for r in ROUNDS if r[0] != "decoder.tick"])
    assert _host_tick.phases(None, {}) is None
    monkeypatch.setattr(idle_gaps, "analysis", lambda trace, counters: None)
    assert _host_tick.phases(None, {}) is None


# -- the round log's readers --------------------------------------------------
def rounds_of(*rows):
    return [generation.Round(*r) for r in rows]


#: ended_at, wall, cpu, wait, ticks, events, tokens; the stretch is [10, 14]
LOG = rounds_of(
    (9.5, 0.5, 0.1, 0.0, 1, 8, 8),          # before the stretch
    (10.2, 0.4, 0.1, 0.1, 1, 8, 8),         # began before it
    (10.5, 0.010, 0.004, 0.003, 1, 8, 8),
    (10.6, 0.008, 0.006, 0.002, 1, 0, 0),   # a round that sent nothing
    (10.7, 0.010, 0.004, 0.002, 1, 8, 9),   # one event carried two tokens
    (11.0, 0.020, 0.004, 0.012, 2, 4, 4),
    (14.1, 0.3, 0.1, 0.0, 1, 8, 8))         # ended after it
TRACED = dict(traced=dict(t0=10.0, t1=14.0))


@pytest.fixture
def rounds(monkeypatch):
    def use(rows):
        monkeypatch.setattr(generation, "recent_rounds", lambda: list(rows))
    return use


@pytest.mark.parametrize("name,want", [
    # (3 + 0 + 4 + 4) ms off the CPU and off the device over 5 ticks
    ("engine_offcpu_ms_per_tick.generate", 11.0 / 5),
    ("tokens_per_stream_event.generate", 21 / 20),
    # intervals 200 ms (8 events) and 300 ms (4): 95% of 12 is past the 8
    ("pump_interval_p95_ms.generate", 300.0)])
def test_round_log_readers_cut_to_the_traced_stretch(rounds, name, want):
    rounds(LOG)
    assert reader(name)(None, TRACED, {}, {}, None) == pytest.approx(want)


@pytest.mark.parametrize("name", ["engine_offcpu_ms_per_tick.generate",
                                  "tokens_per_stream_event.generate",
                                  "pump_interval_p95_ms.generate"])
def test_round_log_readers_read_none_where_there_is_nothing(
        rounds, monkeypatch, name):
    rounds(LOG)
    assert reader(name)(None, {}, {}, {}, None) is None         # untraced
    rounds(LOG[:2])
    assert reader(name)(None, TRACED, {}, {}, None) is None     # no round
    # a full log whose oldest round began after the stretch did
    rounds(LOG[2:])
    monkeypatch.setattr(generation, "RECENT_ROUNDS", len(LOG) - 2)
    assert _rounds.traced_rounds(TRACED) is None
    # the parent of the PR that added the log
    monkeypatch.delattr(generation, "recent_rounds")
    assert reader(name)(None, TRACED, {}, {}, None) is None


# -- a tiny streamed engine, on both transports -------------------------------
JOBS = [(5, 8), (20, 24), (6, 10)]      # (prompt tokens, new tokens)


def _stream(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(dict(payload, stream=True)).encode(),
        headers={"Content-Type": "application/json"})
    events = []
    with urllib.request.urlopen(req, timeout=120) as r:
        for line in r:
            if line.startswith(b"data: "):
                events.append(json.loads(line[6:]))
    return events


@pytest.fixture(scope="module", params=["threaded", "async"])
def streamed(request):
    """Three streamed requests on two slots (one prefills in chunks, one
    waits for a slot). The engine's rounds, the span log's rows, the
    requests' timelines, the clients' events, and the registry before and
    after."""
    from mmlspark_tpu.models.zoo.transformer import (TransformerConfig,
                                                     init_transformer)
    cfg = TransformerConfig(vocab=128, layers=2, d_model=64, heads=4,
                            d_ff=128, max_len=64, causal=True,
                            norm="rmsnorm", position="rope",
                            dtype=jnp.float32)
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab, n)]
               for n, _ in JOBS]
    replies = {}
    eng = generation.GenerationEngine(
        init_transformer(cfg, seed=0), cfg, max_slots=2, max_len=48,
        page_size=4, prefill_chunk=8, transport=request.param)
    tr._SPAN_LOG.clear()
    generation._ROUNDS.clear()
    generation._RECENT.clear()
    before = obs.snapshot()
    with eng:
        def client(i):
            replies[i] = _stream(eng.address, {"tokens": prompts[i],
                                               "max_new": JOBS[i][1]})
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(JOBS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
    return dict(rounds=generation.recent_rounds(), spans=tr.span_log(),
                timelines=generation.recent_timelines(), replies=replies,
                before=before, after=obs.snapshot())


def moved(run, name, field="value"):
    def total(snap):
        return sum(s[field] for s in snap.get(name, {}).get("series", ()))
    return total(run["after"]) - total(run["before"])


def test_every_round_has_a_row_on_two_clocks(streamed):
    rows = streamed["rounds"]
    assert rows and len(rows) < generation.RECENT_ROUNDS
    for r in rows:
        assert r.wall_s > 0.0 and r.cpu_s >= 0.0
        assert 0.0 <= r.wait_s <= r.wall_s
        assert r.ticks >= 0 and r.stream_tokens >= r.stream_events >= 0
    ends = [r.ended_at for r in rows]
    assert ends == sorted(ends)
    # a kernel may account CPU time by its 10 ms tick (the chip's machine
    # does): a round's CPU seconds can then pass its wall's, their sums by
    # no more than a tick
    assert sum(r.cpu_s for r in rows) <= sum(r.wall_s for r in rows) + 0.011


def test_the_rounds_ticks_are_the_tick_spans(streamed):
    ticks = sum(name == "decoder.tick" for name, *_ in streamed["spans"])
    assert sum(r.ticks for r in streamed["rounds"]) == ticks > 0


def test_the_rounds_wait_is_the_drain_spans(streamed):
    """``wait_s`` times what ``continuous.drain`` brackets, on another
    clock and with the span's own enter and exit outside."""
    drains = sum(b - a for name, _, a, b in streamed["spans"]
                 if name == "continuous.drain") / 1e9
    assert sum(r.wait_s for r in streamed["rounds"]) \
        == pytest.approx(drains, rel=0.2, abs=2e-3)


def test_the_rounds_tokens_are_the_tokens_served(streamed):
    served = sum(m for _, m in JOBS)
    assert sum(r.stream_tokens for r in streamed["rounds"]) == served
    token_events = [e for events in streamed["replies"].values()
                    for e in events if "tokens" in e and not e.get("done")]
    assert sum(r.stream_events for r in streamed["rounds"]) \
        == len(token_events)
    assert sum(len(e["tokens"]) for e in token_events) == served


@pytest.mark.parametrize("name,field,of", [
    ("mmlspark_generation_round_seconds", "count", "rounds"),
    ("mmlspark_generation_round_seconds", "sum", "wall_s"),
    ("mmlspark_generation_round_cpu_seconds_total", "value", "cpu_s"),
    ("mmlspark_generation_stream_events_total", "value", "stream_events"),
    ("mmlspark_generation_stream_tokens_total", "value", "stream_tokens")])
def test_the_registry_holds_the_same_sums(streamed, name, field, of):
    rows = streamed["rounds"]
    want = len(rows) if of == "rounds" else sum(getattr(r, of) for r in rows)
    assert moved(streamed, name, field) == pytest.approx(want)


def test_timelines_carry_what_the_chunks_waited(streamed):
    assert len(streamed["timelines"]) == len(JOBS)
    for a in streamed["timelines"]:
        # at least the first token's event was written before the engine
        # finished the request; the closing event is not among them
        assert 0 < a["writes"] <= a["new_tokens"]
        assert 0.0 <= a["write_lag_sum_s"] / a["writes"] \
            <= a["write_lag_max_s"] <= a["write_lag_sum_s"]
        assert a["write_lag_max_s"] < 60.0
    # the histogram: once a stream, by its writer, its longest wait (the
    # closing event's included)
    name = "mmlspark_serving_stream_write_lag_seconds"
    assert moved(streamed, name, "count") == len(JOBS)
    assert moved(streamed, name, "sum") >= sum(
        a["write_lag_max_s"] for a in streamed["timelines"]) - 1e-9


def test_write_lag_reader_takes_the_windows_requests(streamed):
    read = reader("stream_write_lag_p95_ms.generate")
    rows = streamed["timelines"]
    window = dict(t0=min(a["submitted_at"] for a in rows),
                  t1=max(a["submitted_at"] for a in rows) + 1.0)
    assert read(None, window, {}, {}, None) == pytest.approx(
        1e3 * max(a["write_lag_sum_s"] / a["writes"] for a in rows))
    assert read(None, dict(t0=0.0, t1=1.0), {}, {}, None) is None


def test_retire_and_account_lie_inside_a_step_and_off_the_drain(streamed):
    rows = streamed["spans"]
    steps = [(a, b) for name, _, a, b in rows if name == "decoder.step"]
    drains = [(a, b) for name, _, a, b in rows if name == "continuous.drain"]
    retires = [(a, b) for name, _, a, b in rows if name == "decoder.retire"]
    assert len(retires) == len(drains) > 0
    for name in ("decoder.retire", "decoder.account"):
        mine = [(a, b) for n, _, a, b in rows if n == name]
        assert mine
        for a, b in mine:
            assert any(lo <= a and b <= hi for lo, hi in steps)
            assert not any(a < hi and lo < b for lo, hi in drains)


def test_write_lag_counts_under_many_writers():
    """A lost update would leave ``writes`` short (a reply has one writer;
    the lock is what lets the engine's thread read the three together)."""
    reply = StreamingReply()
    n, each = 8, 500
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work():
            for _ in range(each):
                reply._written(time.perf_counter())
        threads = [threading.Thread(target=work) for _ in range(n)]
        for t in threads:
            t.start()
        seen = []
        while any(t.is_alive() for t in threads) and len(seen) < 10_000:
            seen.append(reply.write_lag())
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    assert reply.write_lag()["writes"] == n * each
    for a in seen:
        assert a["write_lag_max_s"] <= a["write_lag_sum_s"] or not a["writes"]


# -- the collector's pauses ---------------------------------------------------
@pytest.fixture
def heavy_heap():
    """Enough tracked objects alive that a full collection takes well over
    a millisecond."""
    junk = [[i] for i in range(400_000)]
    yield junk
    del junk[:]


def gc_counts(generation_):
    snap = obs.snapshot()

    def of(name):
        return sum(s["value"] for s in snap[name]["series"]
                   if s["labels"]["generation"] == str(generation_))
    return (of("mmlspark_process_gc_collections_total"),
            of("mmlspark_process_gc_pause_seconds_total"))


def test_a_forced_collection_lands_in_the_counters():
    n0, s0 = gc_counts(2)
    gc.collect()
    n1, s1 = gc_counts(2)
    assert n1 - n0 >= 1 and s1 > s0


def test_a_slow_collection_is_kept_with_its_start(heavy_heap):
    t0 = time.perf_counter()
    gc.collect()
    t1 = time.perf_counter()
    mine = [(at, s, g) for at, s, g in registry.recent_gc_pauses()
            if t0 <= at < t1]
    assert mine and mine[-1][2] == 2
    assert registry.GC_PAUSE_FLOOR_S <= mine[-1][1] <= t1 - t0
    window = dict(t0=t0, t1=t1)
    for name in ("gc_pause_max_ms.generate", "gc_pause_max_ms.transform"):
        assert reader(name)(None, window, {}, {}, None) \
            == pytest.approx(1e3 * max(s for _, s, _ in mine))


def test_gc_reader_reads_zero_none_and_a_full_list(monkeypatch):
    assert _gc.longest_pause_ms(dict(t0=-2.0, t1=-1.0)) == 0.0
    assert _gc.longest_pause_ms({}) is None
    monkeypatch.setattr(registry, "recent_gc_pauses",
                        lambda: [(5.0, 0.002, 2)])
    monkeypatch.setattr(registry, "RECENT_GC_PAUSES", 1)
    assert _gc.longest_pause_ms(dict(t0=4.0, t1=6.0)) is None
    assert _gc.longest_pause_ms(dict(t0=5.0, t1=6.0)) == 2.0
    monkeypatch.delattr(registry, "recent_gc_pauses")
    assert _gc.longest_pause_ms(dict(t0=5.0, t1=6.0)) is None


def test_concurrent_scrapes_publish_each_collection_once():
    """Two readers of the registry while collections run: the counters
    end at what the hook counted, not above it."""
    stop = threading.Event()

    def scrape():
        while not stop.is_set():
            obs.snapshot()
    threads = [threading.Thread(target=scrape) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(20):
            gc.collect(0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    gc.disable()
    try:
        held = [list(published) for *_, published in registry._GC_SERIES]
        registry.get_registry().metrics()
        for (*_, counted, published), was in zip(registry._GC_SERIES, held):
            assert published == counted
            assert all(b >= a for a, b in zip(was, published))
    finally:
        gc.enable()


# -- the span ring ------------------------------------------------------------
def test_span_ring_keeps_four_field_rows_in_closing_order():
    ring = tr._SpanRing(8)
    assert ring.rows() == [] and len(ring) == 0
    rows = [(f"s{i}", 2 ** 63 + i, 10 * i, 10 * i + 5) for i in range(5)]
    for row in rows:
        ring.append(row)
    assert ring.rows() == rows == ring.rows()       # a read takes nothing
    assert len(ring) == 5


def test_span_ring_wraps_onto_its_oldest_rows():
    ring = tr._SpanRing(8)
    for i in range(30):
        ring.append((f"s{i}", 7, i, i + 1))
    names = [name for name, *_ in ring.rows()]
    assert names == [f"s{i}" for i in range(22, 30)]
    for i in range(30, 33):                         # after a read, on
        ring.append((f"s{i}", 7, i, i + 1))
    names = [name for name, *_ in ring.rows()]
    assert names[-3:] == ["s30", "s31", "s32"]
    assert names == sorted(names, key=lambda s: int(s[1:]))
    assert 5 <= len(names) <= 8
    ring.clear()
    assert ring.rows() == []


def test_span_ring_loses_no_row_under_many_writers():
    ring = tr._SpanRing(1 << 15)
    n, each = 8, 2000
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work(k):
            for i in range(each):
                ring.append((f"t{k}", k, i, i + 1))
        threads = [threading.Thread(target=work, args=(k,)) for k in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    rows = ring.rows()
    assert len(rows) == n * each
    for k in range(n):      # each writer's rows whole and in its own order
        mine = [row for row in rows if row[1] == k]
        assert mine == [(f"t{k}", k, i, i + 1) for i in range(each)]


def test_the_span_log_is_one_ring_of_262144_rows():
    assert isinstance(tr._SPAN_LOG, tr._SpanRing)
    assert tr._SPAN_LOG.maxlen == 262144
    tr._SPAN_LOG.clear()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    (inner, thread, a0, a1), (outer, _, b0, b1) = tr.span_log()
    assert (inner, outer) == ("inner", "outer")     # closing order
    assert thread == threading.get_ident() and b0 <= a0 <= a1 <= b1
