"""Share of the traced window in which the device was idle with no
``runner.run`` open on any thread: the partition's blocking fetch
(``runner.d2h``), the host's collection of the results (``onnx.collect``,
``frame.concat``) and the next pass setting off. Counted from the gaps
outside the passes, not as what ``idle_in_pass_pct.transform`` leaves of
``device_idle_pct.transform``: the three come from two readings of the
trace, and what the two shares lack of the third is their clocks' error."""

from benchmarks import idle_gaps


def read(trace, counters, cell, config, peak):
    found = idle_gaps.analysis(trace, counters)
    if found is None or not trace["devices"] or trace["window_s"] <= 0:
        return None
    outside = idle_gaps.between_passes_seconds(found)
    return None if outside is None else 100.0 * outside / trace["window_s"]
