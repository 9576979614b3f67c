"""What the readers of a routed decoder share: the routing counters of the
traced stretch. The pool's counters are of the whole window; a traced run
traces its first ``trace_seconds``, so a count of the traced stretch is the
window's scaled by the decode steps the stretch held (the requests' own token
times, as the hybrid readers count them)."""

from benchmarks.layer_metrics import _hybrid


def routed_layers(config):
    return max(0, len(config.get("layers_held", ()))
               - config.get("first_k_dense_replace", 0))


def moved(counters, name):
    """The window's delta of the pool's ``moe_<name>``; None where the
    program counts no such thing."""
    value = counters.get("kv_stats", {}).get("moe_" + name)
    return value if value else None


def traced_share(counters):
    """Decode tokens of the traced stretch over the window's."""
    traced = _hybrid.traced_contexts(counters)
    events = counters.get("token_events")
    if not traced or not events:
        return None
    lo, hi = counters["t0"], counters["t1"]
    inside = sum(lo <= t < hi for t, _ in events)
    return len(traced) / inside if inside else None
