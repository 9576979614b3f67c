"""The engine loop's round log (``serving.generation.recent_rounds``: one
row a round with its wall, CPU and device-wait seconds, its decode ticks
and the token events it handed to streams), cut to the traced stretch: the
stretch is on the same clock (``time.perf_counter()``), and after it the
profiler's stop holds the GIL beside the window for most of a minute, which
would read as the engine's thread waiting."""


def traced_rounds(counters):
    """The rounds that lie whole inside the traced stretch, oldest first.
    None where the run was not traced, where the program keeps no such log
    (the parent of the PR that added it) and where the log is full and its
    oldest round began after the stretch did: it may then have dropped
    rounds of the stretch."""
    traced = counters.get("traced")
    try:
        from mmlspark_tpu.serving import generation
        rows = generation.recent_rounds()
        full = len(rows) >= generation.RECENT_ROUNDS
    except (ImportError, AttributeError):
        return None
    if not traced or (full and rows[0].ended_at - rows[0].wall_s
                      > traced["t0"]):
        return None
    return [r for r in rows if traced["t0"] <= r.ended_at - r.wall_s
            and r.ended_at <= traced["t1"]] or None
