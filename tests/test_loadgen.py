"""Open-loop load generation: seeded arrival-process statistics, tenant
mix skew, scorecard math (fairness error, CO-corrected quantiles,
federated-counter parsing), the /debug/scenario route on both
transports, and the acceptance drill — a 3-worker ServingCluster under a
mixed-tenant open-loop scenario with seeded enqueue faults plus a
mid-run ungraceful worker restart, reconciled exactly against the
driver's federated counters with zero lost requests.
"""

import http.client
import json
import random
import statistics
import threading
import urllib.request

import pytest

from mmlspark_tpu.loadgen import (Arrival, TenantMix, cluster_echo_engine,
                                  diurnal_offsets, fairness_error,
                                  get_progress, get_scenario,
                                  heavy_tail_rows, interarrivals,
                                  merged_requests_total, plan,
                                  poisson_offsets, quantiles_ms,
                                  reset_progress, run_scenario)
from mmlspark_tpu.observability import reset_all
from mmlspark_tpu.observability.federation import FEDERATION_INTERVAL_ENV
from mmlspark_tpu.observability.ledger import reset_ledger
from mmlspark_tpu.observability.slo import reset_tracker
from mmlspark_tpu.observability.watchdog import reset_watchdog
from mmlspark_tpu.reliability import get_injector, reset_breakers
from mmlspark_tpu.serving.distributed import ServingCluster
from mmlspark_tpu.tuning.observations import ObservationStore, reset_store


@pytest.fixture(autouse=True)
def _clean_slate():
    for reset in (reset_ledger, reset_tracker, reset_watchdog,
                  reset_breakers, reset_store, reset_progress, reset_all):
        reset()
    get_injector().clear()
    yield
    for reset in (reset_ledger, reset_tracker, reset_watchdog,
                  reset_breakers, reset_store, reset_progress, reset_all):
        reset()
    get_injector().clear()


# ---------------------------------------------------------------------------
# arrival processes


def test_poisson_interarrival_mean_and_variance():
    rate = 50.0
    offs = poisson_offsets(rate, 40.0, random.Random(42))
    gaps = interarrivals(offs)
    assert len(gaps) > 1500
    mean = statistics.fmean(gaps)
    var = statistics.variance(gaps)
    # Exponential(rate): mean 1/rate, variance 1/rate^2
    assert mean == pytest.approx(1.0 / rate, rel=0.10)
    assert var == pytest.approx(1.0 / rate ** 2, rel=0.30)
    assert all(g > 0 for g in gaps)
    assert all(0 <= t < 40.0 for t in offs)


def test_poisson_seeded_determinism():
    assert poisson_offsets(20.0, 5.0, random.Random(7)) == \
        poisson_offsets(20.0, 5.0, random.Random(7))


def test_diurnal_modulation_shape():
    # period == duration: first half is the "day" (rate * (1+depth*sin)
    # above mean), second half the "night" — counts must separate hard
    duration = 20.0
    offs = diurnal_offsets(50.0, duration, random.Random(3), depth=0.8)
    first = sum(1 for t in offs if t < duration / 2)
    second = len(offs) - first
    assert first > second * 1.5
    # total volume stays near the base rate (the envelope integrates to
    # rate * duration over a full period)
    assert len(offs) == pytest.approx(50.0 * duration, rel=0.15)


def test_diurnal_zero_depth_is_plain_poisson_rate():
    offs = diurnal_offsets(40.0, 10.0, random.Random(5), depth=0.0)
    assert len(offs) == pytest.approx(400, rel=0.15)


def test_heavy_tail_rows_quantiles():
    rng = random.Random(11)
    xs = sorted(heavy_tail_rows(rng, median=8, alpha=1.6, cap=4096)
                for _ in range(20_000))
    med = xs[len(xs) // 2]
    p99 = xs[int(0.99 * len(xs))]
    assert 6 <= med <= 10                       # median lands where asked
    assert p99 >= 3 * med                       # the tail is actually heavy
    assert xs[-1] <= 4096 and xs[0] >= 1        # cap and floor hold


def test_tenant_mix_weights_and_prefix_skew():
    rng = random.Random(9)
    mix = TenantMix({"acme": 3.0, "beta": 1.0}, prefix_pool=4,
                    prefix_skew=1.1, keyed_fraction=0.75)
    picks = [mix.pick(rng) for _ in range(8000)]
    acme = sum(1 for t, _ in picks if t == "acme")
    assert acme / len(picks) == pytest.approx(0.75, abs=0.03)
    keyed = [p for _, p in picks if p is not None]
    assert len(keyed) / len(picks) == pytest.approx(0.75, abs=0.03)
    # Zipf skew: rank-1 prefixes are the hottest; keys are deterministic
    # "{tenant}-p{rank}" so affinity routing sees stable hot keys
    assert all(p.split("-p")[1].isdigit() for p in keyed)
    r1 = sum(1 for p in keyed if p.endswith("-p1"))
    r4 = sum(1 for p in keyed if p.endswith("-p4"))
    assert r1 > r4


def test_plan_is_deterministic_and_complete():
    sc = get_scenario("smoke")
    a, b = plan(sc), plan(sc)
    assert a == b and len(a) > 0
    assert [x.index for x in a] == list(range(len(a)))
    assert all(isinstance(x, Arrival) and x.rows >= 1 for x in a)
    assert {x.tenant for x in a} <= set(sc.tenants)
    assert {x.workload for x in a} <= set(sc.workloads)


# ---------------------------------------------------------------------------
# scorecard math


def test_fairness_error_known_shares():
    # achieved shares exactly proportional to weights → zero error
    assert fairness_error({"a": 30, "b": 10}, {"a": 3.0, "b": 1.0}) == 0.0
    # equal weights, one tenant starved: TV distance = 0.5
    assert fairness_error({"a": 40, "b": 0}, {"a": 1.0, "b": 1.0}) == \
        pytest.approx(0.5)
    # 60/40 against 50/50 → |0.6-0.5|/2 + |0.4-0.5|/2 = 0.1
    assert fairness_error({"a": 60, "b": 40}, {"a": 1.0, "b": 1.0}) == \
        pytest.approx(0.1)
    assert fairness_error({}, {}) == 0.0


def test_quantiles_ms_nearest_rank():
    assert quantiles_ms([]) is None
    q = quantiles_ms([i / 1000.0 for i in range(1, 101)])
    assert q["p50_ms"] == pytest.approx(51.0)
    assert q["p99_ms"] == pytest.approx(99.0)
    assert q["max_ms"] == pytest.approx(100.0)
    assert q["n"] == 100


def test_merged_requests_total_parses_federated_metrics():
    text = ("# HELP mmlspark_serving_requests_total h\n"
            'mmlspark_serving_requests_total{transport="threaded"} 12\n'
            'mmlspark_serving_requests_total{transport="async"} 30\n'
            'mmlspark_other_total{x="y"} 99\n')
    assert merged_requests_total(text) == 42.0


# ---------------------------------------------------------------------------
# /debug/scenario on both transports


def _get_json(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return json.loads(resp.read().decode("utf-8"))


def _get_json_port(port, path):
    # http.client, not urlopen: the async transport's keep-alive framing
    # and urllib don't get along (same convention as test_serving_async)
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read().decode("utf-8"))
    finally:
        conn.close()


@pytest.mark.parametrize("transport", ["threaded", "async"])
def test_debug_scenario_route_both_transports(transport):
    from mmlspark_tpu.serving.server import WorkerServer
    server = WorkerServer(transport=transport)
    try:
        assert _get_json_port(server.port, "/debug/scenario")["state"] == \
            "idle"
        progress = get_progress()
        progress.begin("drill", 10)
        progress.note_sent(3)
        progress.note_done("ok")
        live = _get_json_port(server.port, "/debug/scenario")
        assert live["scenario"] == "drill" and live["state"] == "running"
        assert live["sent"] == 3 and live["ok"] == 1
        progress.finish({"ok": 1})
        done = _get_json_port(server.port, "/debug/scenario")
        assert done["state"] == "done" and done["summary"] == {"ok": 1}
    finally:
        server.close()


# ---------------------------------------------------------------------------
# acceptance: chaos scenario against a 3-worker cluster


def test_scenario_e2e_chaos_scorecard(monkeypatch):
    # federate telemetry on every heartbeat so the final quiesced
    # heartbeat sweep gives the driver an exact same-instant view
    monkeypatch.setenv(FEDERATION_INTERVAL_ENV, "0")
    store = ObservationStore()
    # tiny admission queues + a slow engine put the offered rate well
    # above capacity: 429s (shed), honored Retry-After retries, and —
    # with the seeded enqueue faults and the mid-run ungraceful restart —
    # client-side breaker flaps, all deterministic in kind (not count).
    # The engine holds each batch of 4 for 0.08 s (50 rps against 150
    # offered): at 0.04 s the margin was 1.5x, and senders starved by a
    # busy host (six test workers) offered too little to shed anything
    scenario = get_scenario(
        "mixed-tenant-chaos", duration_s=1.5, rate=150.0,
        faults="enqueue:error:every=3:times=24",
        restart_at_s=0.7, restart_worker="worker-1",
        deadline_s=3.0, max_retries=2)
    # queue depth (3 workers x 4) below the sender concurrency (32), so
    # the open-loop burst MUST overflow admission into 429s
    cluster = ServingCluster(3, reply_timeout=5.0, max_queue=4)
    stop = threading.Event()
    engine = cluster_echo_engine(cluster, stop, service_s=0.08, batch=4)
    try:
        card = run_scenario(scenario, cluster, closed_loop_n=25,
                            senders=32, store=store, mesh_shape="single",
                            kv_dtype="int8")
        live = _get_json(cluster.workers[0].server.address
                         + "/debug/scenario")
    finally:
        stop.set()
        engine.join(timeout=2.0)
        cluster.close()

    # complete scorecard: every planned arrival ended somewhere
    assert card["arrivals"] > 100
    assert card["lost"] == 0
    assert card["ok"] + card["shed"] + card["errors"] == card["arrivals"]
    assert card["ok"] > 0

    # chaos left fingerprints: shed, retries (incl. honored Retry-After),
    # breaker transitions, injected faults
    assert card["shed"] > 0
    assert card["retry"]["retries"] > 0
    assert card["retry"]["amplification"] > 1.0
    assert card["retry"]["honored_retry_after"] > 0
    assert card["breaker"]["transitions"] > 0
    assert card["faults_injected"] > 0

    # the merged federated counter reconciles EXACTLY: every worker
    # heartbeat at the same quiesced instant, and the in-process cluster
    # shares one metrics registry, so merged == n_workers * global
    cl = card["cluster"]
    assert cl["reconciled"] is True, cl
    assert cl["merged_requests_total"] == \
        cl["workers"] * cl["global_requests_total"]

    # coordinated omission is visible: the open-loop (scheduled-send)
    # p99 exceeds the closed-loop p99 on the same workload
    assert card["loop_mode"] == "open"
    assert card["closed_loop"]["loop_mode"] == "closed"
    assert card["latency_ms"]["p99_ms"] > \
        card["closed_loop"]["latency_ms"]["p99_ms"]

    # scorecard rows landed in the ObservationStore via the existing
    # slo_scorecard source (cost rows harvest server-side via /debug/costs)
    rows = store.rows(source="slo_scorecard")
    assert rows
    assert all(r["sig"].startswith("slo:") for r in rows)

    # bench stamps + tenant accounting rode along
    assert card["mesh_shape"] == "single" and card["kv_dtype"] == "int8"
    assert set(card["tenants"]) <= set(scenario.tenants)
    assert 0.0 <= card["fairness_error"] <= 1.0
    for row in card["tenants"].values():
        assert row["arrivals"] == row["ok"] + row["shed"] + row["errors"]

    # the live route saw the run finish
    assert live["state"] == "done" and live["scenario"] == scenario.name
    assert live["summary"]["lost"] == 0


def test_smoke_scenario_clean_run(monkeypatch):
    # the CI-facing path: no restart, light faults, ample capacity —
    # everything lands, mostly ok, reconciliation still exact
    monkeypatch.setenv(FEDERATION_INTERVAL_ENV, "0")
    scenario = get_scenario("smoke", duration_s=1.0, rate=25.0)
    cluster = ServingCluster(3, reply_timeout=5.0, max_queue=256)
    stop = threading.Event()
    engine = cluster_echo_engine(cluster, stop, batch=16)
    try:
        card = run_scenario(scenario, cluster, closed_loop_n=8)
    finally:
        stop.set()
        engine.join(timeout=2.0)
        cluster.close()
    assert card["lost"] == 0
    assert card["ok"] + card["shed"] + card["errors"] == card["arrivals"]
    assert card["ok"] >= card["arrivals"] * 0.8
    assert card["cluster"]["reconciled"] is True
    assert card["harvested"]["slo_rows"] > 0
