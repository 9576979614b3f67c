"""Time-series plane: ring wraparound exactness, spike-preserving
downsample tiers, reset-tolerant rate(), sustained-signal hysteresis
under an injectable clock, the registry sampler's counter/gauge/histogram
reduction, fixed-memory byte accounting under a long synthetic run,
concurrent sample/query under the lock sanitizer, GET /debug/timeseries
on both transports, driver-side cluster series surviving an ungraceful
worker restart, /healthz alert reasons, and the mixed-tenant-chaos
acceptance drill (scorecard timeline dip+recovery around the restart,
queue-saturation alert firing during backlog and resolving after
quiesce).
"""

import http.client
import json
import threading
import time

import pytest

from mmlspark_tpu.observability import (counter, gauge, histogram,
                                        reset_all)
from mmlspark_tpu.observability.federation import FEDERATION_INTERVAL_ENV
from mmlspark_tpu.observability.ledger import reset_ledger
from mmlspark_tpu.observability.slo import reset_tracker
from mmlspark_tpu.observability.timeseries import (
    INTERVAL_ENV, AlertEngine, AlertRule, ClusterSampler, RegistrySampler,
    TimeSeriesStore, _Ring, default_alert_rules, get_alert_engine,
    get_sampler, get_store, parse_alert_rules, parse_tiers,
    render_sparklines, reset_alert_engine, reset_store, set_alert_engine,
    set_store)
from mmlspark_tpu.observability.watchdog import reset_watchdog
from mmlspark_tpu.reliability import get_injector, reset_breakers
from mmlspark_tpu.tuning.observations import (ObservationStore,
                                              set_store as set_obs_store,
                                              reset_store as reset_obs_store)


@pytest.fixture(autouse=True)
def _clean_slate():
    for reset in (reset_store, reset_alert_engine, reset_ledger,
                  reset_tracker, reset_watchdog, reset_breakers, reset_all):
        reset()
    get_injector().clear()
    set_obs_store(ObservationStore())
    yield
    for reset in (reset_store, reset_alert_engine, reset_ledger,
                  reset_tracker, reset_watchdog, reset_breakers,
                  reset_obs_store, reset_all):
        reset()
    get_injector().clear()


# ---------------------------------------------------------------------------
# ring + store core


def test_ring_wraparound_is_exact():
    """After wrapping, the ring holds exactly the last `slots` epochs —
    recycled buckets carry the new epoch's stats, never stale ones."""
    ring = _Ring(1.0, 8)
    for t in range(20):                      # 20 epochs through 8 slots
        ring.feed(float(t) + 0.5, float(t))
    rows = ring.buckets(now=19.5, seconds=8.0)
    assert [e for e, *_ in rows] == list(range(12, 20))
    for e, mn, mx, total, count, last in rows:
        assert mn == mx == last == float(e)
        assert count == 1.0 and total == float(e)
    # epochs older than the span are gone, not aliased
    assert ring.buckets(now=19.5, seconds=100.0) == rows


def test_downsample_tiers_preserve_min_max_mean():
    """A one-sample spike survives into the coarse tier's min/max even
    though the mean flattens it."""
    store = TimeSeriesStore(tiers=((1.0, 120), (10.0, 18)))
    for t in range(10):
        store.record("sig", 100.0 if t == 3 else 0.0, t=float(t))
    fine = store.range("sig", seconds=10.0, at=10.0, tier=0)
    assert [b["max"] for b in fine] == [0, 0, 0, 100, 0, 0, 0, 0, 0, 0]
    coarse = store.range("sig", seconds=10.0, at=10.0, tier=1)
    assert len(coarse) == 1
    b = coarse[0]
    assert b["min"] == 0.0 and b["max"] == 100.0
    assert b["mean"] == pytest.approx(10.0)
    assert b["count"] == 10 and b["last"] == 0.0


def test_range_picks_finest_covering_tier_and_merges_labels():
    store = TimeSeriesStore(tiers=((1.0, 10), (10.0, 10)))
    for t in range(30):
        store.record("depth", float(t % 7), {"port": "a"}, t=float(t))
        store.record("depth", float(t % 3), {"port": "b"}, t=float(t))
    # 30 s exceeds the fine tier's 10-slot span -> coarse tier
    buckets = store.range("depth", seconds=30.0, at=30.0)
    assert all(b["width"] == 10.0 for b in buckets)
    # labels=None merges: count sums both series
    assert all(b["count"] == 20 for b in buckets)
    one = store.range("depth", seconds=30.0, labels={"port": "b"}, at=30.0)
    assert all(b["max"] <= 2.0 for b in one)


def test_rate_tolerates_counter_reset():
    store = TimeSeriesStore(tiers=((1.0, 120),))
    for t, v in enumerate([0, 10, 20, 5, 15]):
        store.record("req_total", float(v), t=float(t), kind="counter")
    # increases: 10 + 10 + 5 (post-reset value) + 10 = 35 over 4 s
    assert store.rate("req_total", seconds=4.0, at=4.0) == \
        pytest.approx(8.75)
    # monotone series: plain delta over span
    store2 = TimeSeriesStore(tiers=((1.0, 120),))
    for t in range(5):
        store2.record("mono", float(10 * t), t=float(t), kind="counter")
    assert store2.rate("mono", seconds=4.0, at=4.0) == pytest.approx(10.0)
    # a single bucket is not evidence of a rate
    store3 = TimeSeriesStore(tiers=((1.0, 120),))
    store3.record("one", 5.0, t=0.0, kind="counter")
    assert store3.rate("one", seconds=4.0, at=0.5) is None


def test_sustained_requires_full_window_coverage():
    store = TimeSeriesStore(tiers=((1.0, 120),))
    store.record("hot", 9.0, t=10.0)
    # one fresh sample is never "sustained for 5s"
    assert not store.sustained("hot", lambda v: v > 1.0, 5.0, at=10.5)
    for t in range(11, 16):
        store.record("hot", 9.0, t=float(t))
    assert store.sustained("hot", lambda v: v > 1.0, 5.0, at=15.5)
    # one bad bucket inside the window breaks it
    store.record("hot", 0.0, t=16.0)
    assert not store.sustained("hot", lambda v: v > 1.0, 5.0, at=16.5)


def test_ewma_and_latest():
    store = TimeSeriesStore(tiers=((1.0, 60),))
    for t, v in enumerate([0.0, 0.0, 10.0]):
        store.record("sig", v, t=float(t))
    assert store.latest("sig") == (2.0, 10.0)
    ew = store.ewma("sig", seconds=3.0, at=3.0, alpha=0.5)
    assert 0.0 < ew < 10.0


def test_store_rejects_junk_and_parse_fallbacks():
    store = TimeSeriesStore(tiers=((1.0, 4),))
    assert not store.record("x", float("nan"))
    assert not store.record("x", "not-a-number")
    assert parse_tiers("garbage") == parse_tiers(None) or \
        parse_tiers("garbage") == parse_tiers("")
    assert parse_tiers("2x10,1x5") == ((1.0, 5), (2.0, 10))  # sorted
    rules = parse_alert_rules("q:series:gt:0.5:for=1:keep=2;bad;also:bad")
    assert len(rules) == 1
    assert rules[0].for_seconds == 1.0
    assert rules[0].keep_firing_seconds == 2.0


def test_byte_budget_bounded_under_long_synthetic_run():
    """The fixed-memory guarantee: a long run with more label sets than
    the cap never grows past byte_budget(), and overflow is counted as
    drops instead of allocation."""
    store = TimeSeriesStore(tiers=((1.0, 16), (8.0, 16)), max_series=16)
    budget = store.byte_budget()
    mid = None
    for i in range(50_000):
        store.record("m", float(i % 13), {"k": str(i % 40)},
                     t=float(i) * 0.01)
        if i == 25_000:
            mid = store.approx_bytes()
    assert store.approx_bytes() == mid        # flat after warm-up
    assert store.approx_bytes() <= budget
    stats = store.stats()
    assert stats["series"] == 16
    assert stats["dropped"] > 0               # the cap did its job
    assert stats["samples"] + stats["dropped"] == 50_000


def test_sparklines_render_shape():
    store = TimeSeriesStore(tiers=((1.0, 60),))
    for t in range(8):
        store.record("ramp", float(t), t=float(t) + 0.5)
    text = render_sparklines(store, seconds=8.0, at=8.0)
    assert text.startswith("ramp")
    assert "▁" in text and "█" in text
    assert "min=0" in text and "max=7" in text


# ---------------------------------------------------------------------------
# alert engine hysteresis


def _fake_clock():
    clock = {"t": 0.0}
    return clock, (lambda: clock["t"])


def test_alert_fires_only_when_sustained_and_does_not_flap():
    clock, fn = _fake_clock()
    store = TimeSeriesStore(tiers=((1.0, 120),), clock=fn)
    engine = AlertEngine(store, clock=fn, on_fire=())
    engine.add_rule(AlertRule("deep", "q", "gt", 5.0, for_seconds=3.0,
                              keep_firing_seconds=2.0, field="max"))
    transitions = []

    def step(t, value):
        clock["t"] = t
        store.record("q", value, t=t)
        transitions.extend(engine.evaluate())

    step(0.0, 9.0)
    step(1.0, 9.0)
    assert engine.firing() == []              # not sustained yet
    step(2.0, 9.0)
    step(3.0, 9.0)
    assert engine.firing() == ["deep"]
    # a one-bucket dip below threshold must NOT resolve (hysteresis)
    step(4.0, 1.0)
    assert engine.firing() == ["deep"]
    step(5.0, 9.0)                            # bad again: last_bad refreshed
    assert engine.firing() == ["deep"]
    # resolve only after keep_firing_seconds of continuously good evidence
    step(6.0, 1.0)
    assert engine.firing() == ["deep"]        # 6 - 5 = 1s < keep window
    step(7.0, 1.0)
    assert engine.firing() == []              # 7 - 5 = 2s: window elapsed
    kinds = [tr["to"] for tr in transitions]
    assert kinds == ["firing", "resolved"]    # exactly one cycle, no flap
    fire = transitions[0]
    assert fire["rule"] == "deep" and fire["window"]  # bundle-able context
    state = engine.state()["deep"]
    assert state["firing"] is False and state["op"] == "gt"


def test_alert_on_fire_hook_and_default_rules():
    clock, fn = _fake_clock()
    store = TimeSeriesStore(tiers=((1.0, 120),), clock=fn)
    seen = []
    engine = AlertEngine(store, clock=fn,
                         on_fire=[lambda rule, rec: seen.append(
                             (rule.name, rec["to"]))])
    engine.add_rule(AlertRule("hot", "s", "ge", 1.0, for_seconds=2.0))
    for t in range(3):
        clock["t"] = float(t)
        store.record("s", 2.0, t=float(t))
        engine.evaluate()
    assert seen == [("hot", "firing")]
    names = {r.name for r in default_alert_rules()}
    assert names == {"burn-rate", "queue-saturation", "breaker-flap",
                     "kv-quant-error"}


# ---------------------------------------------------------------------------
# registry sampler reduction


def test_sampler_reduces_counters_gauges_histograms():
    clock, fn = _fake_clock()
    store = TimeSeriesStore(tiers=((1.0, 120),), clock=fn)
    sampler = RegistrySampler(store, interval=0, clock=fn)
    c = counter("mmlspark_test_ts_total", "t", ("k",))
    g = gauge("mmlspark_test_ts_depth", "t")
    h = histogram("mmlspark_test_ts_lat", "t",
                  buckets=(0.1, 1.0, 10.0))
    g.set(7.0)
    sampler.tick(now=0.0)                     # baseline scrape
    c.inc(20, k="a")
    for _ in range(10):
        h.observe(0.5)
    g.set(9.0)
    clock["t"] = 2.0
    sampler.tick(now=2.0)
    # counter -> :rate over the 2 s interval
    assert store.latest("mmlspark_test_ts_total:rate",
                        {"k": "a"})[1] == pytest.approx(10.0)
    # gauge -> direct sample
    assert store.latest("mmlspark_test_ts_depth")[1] == 9.0
    # histogram -> interpolated p50/p99 from the interval's new counts
    p50 = store.latest("mmlspark_test_ts_lat:p50")[1]
    p99 = store.latest("mmlspark_test_ts_lat:p99")[1]
    assert 0.1 < p50 <= 1.0 and p50 <= p99 <= 1.0
    # counter reset (restart): rate records the post-reset value, not
    # a negative step
    c.inc(4, k="a")
    clock["t"] = 3.0
    sampler.tick(now=3.0)
    assert store.latest("mmlspark_test_ts_total:rate",
                        {"k": "a"})[1] == pytest.approx(4.0)
    # extra sources: sampled when they return a number, skipped on None
    vals = iter([0.25, None])
    sampler.add_source("mmlspark_test_ts_src", lambda: next(vals))
    clock["t"] = 4.0
    sampler.tick(now=4.0)
    clock["t"] = 5.0
    sampler.tick(now=5.0)
    assert store.latest("mmlspark_test_ts_src") == (4.0, 0.25)


# ---------------------------------------------------------------------------
# concurrency under the lock sanitizer


def test_concurrent_sample_and_query_under_lock_sanitizer(monkeypatch):
    import mmlspark_tpu.reliability.lock_sanitizer as ls
    monkeypatch.setenv(ls.SANITIZER_ENV, "1")
    ls.reset()
    assert ls.enabled()
    store = TimeSeriesStore(tiers=((0.01, 64), (0.1, 64)))
    engine = AlertEngine(store, on_fire=())
    engine.add_rule(AlertRule("busy", "m", "gt", 0.5, for_seconds=0.05))
    errors = []
    stop = threading.Event()

    def writer(i):
        try:
            n = 0
            while not stop.is_set():
                store.record("m", float(n % 10), {"w": str(i)})
                n += 1
        except Exception as exc:              # pragma: no cover
            errors.append(exc)

    def reader():
        try:
            while not stop.is_set():
                store.range("m", seconds=1.0)
                store.rate("m", seconds=1.0)
                store.snapshot(seconds=1.0)
                engine.evaluate()
        except Exception as exc:              # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(3)]
    threads += [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join(timeout=2.0)
    assert errors == []
    assert ls.cycle_reports() == [], (
        "lock-order cycles in the time-series plane:\n" + "\n".join(
            " -> ".join(r["sites"]) for r in ls.cycle_reports()))
    assert store.stats()["samples"] > 0


# ---------------------------------------------------------------------------
# /debug/timeseries over HTTP, both transports


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path)
    r = conn.getresponse()
    body = r.read()
    ctype = r.getheader("Content-Type", "")
    conn.close()
    return r.status, ctype, body


@pytest.mark.parametrize("transport", ["threaded", "async"])
def test_debug_timeseries_route_both_transports(transport, monkeypatch):
    from mmlspark_tpu.serving.server import WorkerServer
    monkeypatch.setenv(INTERVAL_ENV, "0")     # tests drive tick() directly
    ws = WorkerServer(transport=transport)
    try:
        for _ in range(3):
            assert _get(ws.port, "/healthz")[0] == 200
        sampler = get_sampler()
        assert sampler is not None and sampler.interval == 0
        sampler.tick()
        time.sleep(0.05)
        sampler.tick()                        # second scrape: rates exist
        status, ctype, body = _get(ws.port, "/debug/timeseries?seconds=60")
        assert status == 200 and ctype.startswith("application/json")
        payload = json.loads(body)
        names = {s["name"] for s in payload["series"]}
        assert "mmlspark_queue_saturation" in names
        assert "mmlspark_serving_requests_total:rate" in names
        assert payload["point_fields"] == \
            ["t", "mean", "min", "max", "last", "count"]
        assert payload["stats"]["approx_bytes"] <= \
            payload["stats"]["byte_budget"]
        assert "queue-saturation" in payload["alerts"]
        assert payload["firing"] == []
        # name filter
        _, _, filtered = _get(
            ws.port, "/debug/timeseries?series=mmlspark_queue_saturation")
        fnames = {s["name"] for s in json.loads(filtered)["series"]}
        assert fnames == {"mmlspark_queue_saturation"}
        # text sparkline view
        status, ctype, text = _get(
            ws.port,
            "/debug/timeseries?format=text&seconds=60"
            "&series=mmlspark_queue_saturation")
        assert status == 200 and ctype.startswith("text/plain")
        assert "mmlspark_queue_saturation" in text.decode("utf-8")
    finally:
        ws.close()
    assert get_sampler() is None              # refcount drained on close


def test_sampler_refcount_shared_across_servers(monkeypatch):
    from mmlspark_tpu.serving.server import WorkerServer
    monkeypatch.setenv(INTERVAL_ENV, "0")
    a = WorkerServer(transport="threaded")
    b = WorkerServer(transport="threaded")
    try:
        assert get_sampler() is not None
        a.close()
        assert get_sampler() is not None      # b still holds a ref
    finally:
        a.close()                             # double-close: no over-release
        b.close()
    assert get_sampler() is None


# ---------------------------------------------------------------------------
# /healthz alert reasons (satellite: firing shows up, resolving clears it)


def test_healthz_reports_firing_alert_and_clears_on_resolve(monkeypatch):
    from mmlspark_tpu.serving.server import WorkerServer
    monkeypatch.setenv(INTERVAL_ENV, "0")
    clock, fn = _fake_clock()
    store = TimeSeriesStore(tiers=((1.0, 120),), clock=fn)
    set_store(store)
    engine = AlertEngine(store, clock=fn, on_fire=())
    engine.add_rule(AlertRule("test-burn", "burn", "gt", 1.0,
                              for_seconds=2.0, keep_firing_seconds=1.0))
    set_alert_engine(engine)
    ws = WorkerServer(transport="threaded")
    try:
        for t in range(3):
            clock["t"] = float(t)
            store.record("burn", 5.0, t=float(t))
            engine.evaluate()
        assert engine.firing() == ["test-burn"]
        _, _, body = _get(ws.port, "/healthz")
        health = json.loads(body)
        assert health["status"] == "degraded"
        assert "alert_firing:test-burn" in health["reasons"]
        for t in range(3, 7):
            clock["t"] = float(t)
            store.record("burn", 0.0, t=float(t))
            engine.evaluate()
        assert engine.firing() == []
        _, _, body = _get(ws.port, "/healthz")
        health = json.loads(body)
        assert not any(r.startswith("alert_firing:")
                       for r in health["reasons"])
    finally:
        ws.close()


# ---------------------------------------------------------------------------
# driver-side cluster series


def test_cluster_sampler_series_survive_worker_restart(monkeypatch):
    from mmlspark_tpu.serving.distributed import ServingCluster
    monkeypatch.setenv(FEDERATION_INTERVAL_ENV, "0")
    monkeypatch.setenv(INTERVAL_ENV, "0")
    cluster = ServingCluster(2, reply_timeout=5.0)
    try:
        for w in cluster.workers:
            assert w.heartbeat()
        ts = cluster.driver.timeseries
        keys = dict(ts.store.series_keys())
        assert "cluster_queue_depth" in keys
        assert "cluster_in_flight" in keys
        before = ts.store.latest("cluster_queue_depth",
                                 {"worker": "worker-0"})
        assert before is not None
        n_series = len(ts.store.series_keys())
        # ungraceful restart: same id, fresh process-side state
        replacement = cluster.restart_worker("worker-0")
        assert replacement.heartbeat()
        after = ts.store.latest("cluster_queue_depth",
                                {"worker": "worker-0"})
        assert after is not None and after[0] > before[0]
        # keyed by worker id: the restarted worker CONTINUED its series
        assert len(ts.store.series_keys()) == n_series
        view = cluster.driver.cluster_view()
        names = {s["name"] for s in view["timeseries"]["series"]}
        assert "cluster_queue_depth" in names
    finally:
        cluster.close()


# ---------------------------------------------------------------------------
# acceptance: mixed-tenant chaos — timeline dip+recovery, alert lifecycle


def test_chaos_timeline_and_queue_saturation_alert_e2e(monkeypatch):
    from mmlspark_tpu.loadgen import (cluster_echo_engine, get_scenario,
                                      run_scenario)
    from mmlspark_tpu.serving.distributed import ServingCluster

    monkeypatch.setenv(FEDERATION_INTERVAL_ENV, "0")
    # fast real-time sampling so queue saturation accrues evidence at
    # sub-run granularity; short alert windows so the default-rule-shaped
    # queue-saturation alert can fire AND resolve inside one test
    monkeypatch.setenv(INTERVAL_ENV, "0.05")
    engine = AlertEngine(get_store(), on_fire=())
    for rule in default_alert_rules(for_seconds=0.3,
                                    keep_firing_seconds=0.5):
        engine.add_rule(rule)
    set_alert_engine(engine)

    restart_at = 0.7
    scenario = get_scenario(
        "mixed-tenant-chaos", duration_s=1.5, rate=150.0,
        faults="enqueue:error:every=3:times=24",
        restart_at_s=restart_at, restart_worker="worker-1",
        deadline_s=3.0, max_retries=2)
    # queue depth (3 x 4) far below sender concurrency: guaranteed backlog
    cluster = ServingCluster(3, reply_timeout=5.0, max_queue=4)
    stop = threading.Event()
    echo = cluster_echo_engine(cluster, stop, service_s=0.08, batch=4)
    try:
        card = run_scenario(scenario, cluster, senders=32)
        # quiesce: traffic over, echo engine still draining; the global
        # sampler keeps scraping an emptying queue until the alert's
        # keep-firing window of good evidence elapses
        deadline = time.monotonic() + 6.0
        while "queue-saturation" in engine.firing() and \
                time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        stop.set()
        echo.join(timeout=2.0)
        cluster.close()

    assert card["lost"] == 0 and card["shed"] > 0

    # -- timeline: complete, consistent, dip visible, recovery after ----
    tl = card["timeline"]
    buckets = tl["buckets"]
    assert buckets, "scorecard timeline must not be empty"
    assert sum(b["ok"] + b["shed"] + b["errors"] for b in buckets) == \
        card["ok"] + card["shed"] + card["errors"]
    assert sum(b["arrivals"] for b in buckets) == card["arrivals"]
    # chaos left a dent somewhere (injected faults + tiny queue)
    assert card["shed"] + card["errors"] > 0
    # the mid-run restart stalls senders: goodput dips visibly in the
    # buckets right after restart_at, then recovers
    bw = tl["bucket_s"]
    pre = [b for b in buckets if b["t0"] < restart_at]
    post = [b for b in buckets if restart_at <= b["t0"] < restart_at + 4 * bw]
    tail = [b for b in buckets if b["t0"] >= restart_at + 4 * bw]
    assert pre and post and tail
    dip = min(b["goodput_rps"] for b in post)
    assert dip < 0.6 * max(b["goodput_rps"] for b in pre), \
        "no visible goodput dip after the worker restart"
    assert max(b["goodput_rps"] for b in tail) > dip, \
        "no goodput recovery after the restart dip"
    assert any(b["ok"] > 0 for b in tail)

    # -- alert lifecycle: fired during backlog, resolved after quiesce --
    from mmlspark_tpu.observability import snapshot
    snap = snapshot()
    trans = {}
    for row in snap["mmlspark_alert_transitions_total"]["series"]:
        labels = row["labels"]
        trans[(labels["rule"], labels["to"])] = row["value"]
    assert trans.get(("queue-saturation", "firing"), 0) >= 1, \
        "queue-saturation alert never fired under a guaranteed backlog"
    assert trans.get(("queue-saturation", "resolved"), 0) >= 1, \
        "queue-saturation alert never resolved after quiesce"
    assert "queue-saturation" not in engine.firing()
    firing_gauge = {
        row["labels"]["rule"]: row["value"]
        for row in snap["mmlspark_alerts_firing"]["series"]}
    assert firing_gauge["queue-saturation"] == 0.0

    # the global store accrued sampled history across the run
    names = set(get_store().names())
    assert "mmlspark_queue_saturation" in names
