"""The pauses of Python's garbage collector (``observability.registry.
recent_gc_pauses``: start on ``time.perf_counter()``, seconds, generation,
of every collection that took a millisecond or more). A collection holds
the GIL: every host thread stands still and the device runs dry, which is
what a stalled run looks like from outside."""


def longest_pause_ms(counters):
    """The longest pause that began inside the window, 0.0 where none
    reached a millisecond. None where the program keeps no such list (the
    parent of the PR that added it), where the driver gave no window, and
    where the list is full and its oldest pause began after the window
    did: it may then have dropped one of the window's."""
    try:
        from mmlspark_tpu.observability import registry
        pauses = registry.recent_gc_pauses()
        full = len(pauses) >= registry.RECENT_GC_PAUSES
    except (ImportError, AttributeError):
        return None
    if "t0" not in counters or "t1" not in counters:
        return None
    if full and pauses[0][0] > counters["t0"]:
        return None
    return 1e3 * max((seconds for at, seconds, _ in pauses
                      if counters["t0"] <= at < counters["t1"]), default=0.0)
