"""What the readers of a hybrid decoder's kernels share: the device seconds
of an operation the cell's ``trace_ops`` names, and the decode steps of the
traced stretch from the requests' own token times."""

from benchmarks import trace_reduce


def op_seconds(trace, cell, name):
    """Device seconds of ``cell["trace_ops"][name]`` (a pattern over
    ``<program>/<operation>``); None where the cell names no such operation
    or the trace holds none, as for a program that lacks it."""
    pattern = cell.get("trace_ops", {}).get(name)
    if not pattern or not trace.get("module_ops"):
        return None
    seconds, _calls = trace_reduce.op_seconds(trace, pattern, "module_ops")
    return seconds if seconds > 0 else None


def traced_contexts(counters):
    """Context length (cached positions, its own included) of every token a
    decode step emitted inside the traced stretch."""
    if "token_events" not in counters or "traced" not in counters:
        return None
    lo, hi = counters["traced"]["t0"], counters["traced"]["t1"]
    return [p for t, p in counters["token_events"] if lo <= t < hi]


def layers_of(config, kind):
    return sum(m == kind for m in config.get("mixer_types", ()))
