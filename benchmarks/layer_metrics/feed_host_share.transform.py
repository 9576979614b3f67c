"""Share of the partition threads' window seconds in which the dispatching
thread waited for the feed: blocked on the coerce/pad worker
(``prefetch_wait``) or putting a batch on the device (``h2d``)."""

WAITS = ("prefetch_wait", "h2d")


def read(trace, counters, cell, config, peak):
    stages = counters.get("stage_seconds")
    if not stages:
        return None
    waited = sum(stages.get(s, 0.0) for s in WAITS)
    return 100.0 * waited / (counters["window_elapsed_s"]
                             * counters["partitions"])
