"""Share of the traced window in which the device was idle while some
partition thread was inside ``runner.run``: the dispatch loop's own gaps
(waiting for the feed, placing it, launching the program)."""

from benchmarks import idle_gaps


def read(trace, counters, cell, config, peak):
    found = idle_gaps.analysis(trace, counters)
    if found is None or not trace["devices"] or trace["window_s"] <= 0:
        return None
    inside = idle_gaps.in_pass_seconds(found)
    return None if inside is None else 100.0 * inside / trace["window_s"]
