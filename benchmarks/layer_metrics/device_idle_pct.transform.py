"""Share of the traced window in which no operation ran on the device."""

from benchmarks.layer_metrics._common import idle_pct


def read(trace, counters, cell, config, peak):
    return idle_pct(trace)
