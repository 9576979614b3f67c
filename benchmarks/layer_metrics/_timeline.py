"""A request's timeline as the engine stamped it (``submitted_at``,
``admitted_at``, ``first_token_at``, ``finished_at``: ``time.perf_counter()``
seconds, the clock of the driver's window), from the program's list of the
requests it replied to (``serving.generation.recent_timelines``: what each
request's root span closed with)."""


def window_requests(counters):
    """The timelines of the requests the engine took in inside the driver's
    window. None where the program keeps none, and where its list is full:
    it may then have dropped a request of the window, and a percentile
    over the rest would pass for one over all."""
    try:
        from mmlspark_tpu.serving import generation
        rows = generation.recent_timelines()
        if len(rows) >= generation.RECENT_TIMELINES:
            return None
    except (ImportError, AttributeError):
        return None
    if "t0" not in counters or "t1" not in counters:
        return None
    return [a for a in rows
            if counters["t0"] <= a["submitted_at"] < counters["t1"]] or None


def spans_ms(counters, first, last):
    """``[1e3 * (last - first)]`` over the window's requests that have both
    stamps; None where there are none."""
    rows = window_requests(counters)
    if not rows:
        return None
    return [1e3 * (a[last] - a[first]) for a in rows
            if a.get(first) is not None and a.get(last) is not None] or None


def against_client(counters):
    """The client's first-token times less the engine's, order statistic by
    order statistic, for whoever prints the run's diagnostics: the two
    lists are of the same requests where their counts agree (the client's
    of those it sent in the window, the engine's of those submitted in it;
    a POST at the window's edge can fall on either side)."""
    engine = sorted(spans_ms(counters, "submitted_at", "first_token_at")
                    or [])
    client = sorted(1e3 * t for t in counters.get("ttft") or [])
    out = dict(requests_engine=len(engine), requests_client=len(client))
    if engine and len(engine) == len(client):
        gaps = sorted(c - e for c, e in zip(client, engine))
        out.update(client_minus_engine_ttft_ms=dict(
            min=gaps[0], median=gaps[len(gaps) // 2], max=gaps[-1]))
    return out
