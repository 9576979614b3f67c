"""The longest pause of Python's garbage collector that began inside the
window (``_gc``): a stalled run either shows its seconds here or rules
the collector out. 0.0 where no collection took a millisecond."""

from benchmarks.layer_metrics._gc import longest_pause_ms


def read(trace, counters, cell, config, peak):
    return longest_pause_ms(counters)
