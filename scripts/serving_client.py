"""Open-loop, rate-controlled HTTP load driver — run as its OWN process.

Thread-burst clients co-located in the server
process measure the client as much as the server. This driver (a) lives in a
separate process so the server's GIL is not shared, and (b) is open-loop:
each connection sends on a fixed schedule (target_rate/connections per
second) instead of as-fast-as-possible, the standard way to measure latency
at a controlled utilization (the coordinated-omission-aware shape). When the
client cannot keep its own schedule it SAYS so (``client_saturated``) rather
than silently under-reporting the server.

Usage:
    python serving_client.py URL TARGET_RPS DURATION_S CONNECTIONS < body.json

Prints one JSON line:
    {"target_rps": ..., "achieved_rps": ..., "p50_ms": ..., "p99_ms": ...,
     "errors": N, "late_frac": ..., "client_saturated": bool}
"""

import http.client
import json
import sys
import threading
import time
from urllib.parse import urlparse


def run(url: str, target_rps: float, duration_s: float, connections: int,
        body: bytes) -> dict:
    u = urlparse(url)
    interval = connections / target_rps       # per-connection send period
    lock = threading.Lock()
    all_lat, totals = [], {"sent": 0, "errors": 0, "late": 0}
    start = time.perf_counter() + 0.05        # common start line
    stop_at = start + duration_s

    def worker(idx: int):
        conn = http.client.HTTPConnection(u.hostname, u.port, timeout=10)
        lats, sent, errors, late = [], 0, 0, 0
        # stagger connections across one period so sends interleave evenly
        next_t = start + (idx / connections) * interval
        while True:
            now = time.perf_counter()
            if now >= stop_at:
                break
            if now < next_t:
                time.sleep(next_t - now)
            elif now - next_t > interval:
                late += 1                     # fell ≥1 full period behind
            t0 = time.perf_counter()
            try:
                conn.request("POST", u.path or "/", body,
                             {"Content-Type": "application/json"})
                r = conn.getresponse()
                r.read()
                if r.status >= 400:
                    # a fast 503 is a server failure, not a clean sample —
                    # counting it as success would let an overloaded server
                    # report a spotless curve
                    errors += 1
                else:
                    lats.append((time.perf_counter() - t0) * 1e3)
                    sent += 1
            except Exception:
                errors += 1
                conn.close()
                conn = http.client.HTTPConnection(u.hostname, u.port,
                                                  timeout=10)
            next_t += interval
        conn.close()
        with lock:
            all_lat.extend(lats)
            totals["sent"] += sent
            totals["errors"] += errors
            totals["late"] += late

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(connections)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    elapsed = time.perf_counter() - start
    achieved = totals["sent"] / elapsed if elapsed > 0 else 0.0
    late_frac = totals["late"] / max(totals["sent"] + totals["late"], 1)
    out = {
        "target_rps": target_rps,
        "achieved_rps": round(achieved, 1),
        "errors": totals["errors"],
        "late_frac": round(late_frac, 4),
        # the client admits it could not hold the schedule: numbers past
        # this point measure the load generator, not the server
        "client_saturated": bool(achieved < 0.95 * target_rps
                                 or late_frac > 0.05),
    }
    if all_lat:
        import statistics
        s = sorted(all_lat)
        out["p50_ms"] = round(s[len(s) // 2], 3)
        out["p99_ms"] = round(s[min(len(s) - 1, int(len(s) * 0.99))], 3)
        out["mean_ms"] = round(statistics.fmean(s), 3)
    return out


if __name__ == "__main__":
    url, rps, dur, conns = (sys.argv[1], float(sys.argv[2]),
                            float(sys.argv[3]), int(sys.argv[4]))
    body = sys.stdin.buffer.read() or b"{}"
    print(json.dumps(run(url, rps, dur, conns, body)))
