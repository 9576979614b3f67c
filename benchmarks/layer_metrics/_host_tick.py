"""``host_ms_per_tick.generate`` taken apart: the engine thread's host
milliseconds a decode tick, by what the thread was doing. Each of the five
phases is the SELF time (a span's duration less its children's) of the spans
it names, over the traced stretch, per ``decoder.tick`` span. A span of the
engine's thread that no phase names (a later PR's) counts under its nearest
named ancestor, and ``continuous.drain`` (the wait for the device) with
whatever lies under it under none, so the five add up to
``host_ms_per_tick.generate`` of the same run: that reader sums the spans of
``ROOTS`` less the drains, and every drain lies inside a ``decoder.step``.
Spans are clipped to the stretch and ticks counted as it does."""

from benchmarks import idle_gaps

PHASES = {
    "schedule": ("engine.admit_http", "decoder.step", "decoder.admit"),
    "launch": ("decoder.tick", "continuous.prefill",
               "continuous.prefill_chunk", "decoder.stage_prefills",
               "decoder.state_restore", "decoder.state_snapshot",
               "decoder.compact"),
    "account": ("decoder.account",),
    "retire": ("decoder.retire",),
    "emit": ("engine.pump_streams", "engine.reply_finished"),
}
#: what ``host_ms_per_tick.generate`` sums: the engine loop's own steps
ROOTS = ("decoder.step", "engine.admit_http", "engine.pump_streams",
         "engine.reply_finished")
#: named, and of no phase: the wait for the device and the loop's sleep
NO_PHASE = ("continuous.drain", "engine.idle")
TICK = "decoder.tick"
PHASE_OF = {name: phase for phase, names in PHASES.items() for name in names}


def self_ns(spans, lo, hi):
    """``{phase: nanoseconds}`` of one thread's (properly nested) spans
    ``[(name, start, end), ...]``: each span's time inside ``[lo, hi]`` less
    its children's, under its own phase or its nearest named ancestor's;
    only what lies under a span of ``ROOTS`` counts."""
    out = dict.fromkeys(PHASES, 0)
    stack = []      # [end, phase, under a root, clipped ns left to itself]

    def close():
        _, phase, rooted, own = stack.pop()
        if rooted and phase is not None:
            out[phase] += own

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= a:
            close()
        inside = max(0, min(b, hi) - max(a, lo))
        if stack:
            stack[-1][3] -= inside
        if name in NO_PHASE:
            phase = None
        elif name in PHASE_OF:
            phase = PHASE_OF[name]
        else:                   # a span no phase names: its parent's
            phase = stack[-1][1] if stack else None
        rooted = name in ROOTS or bool(stack and stack[-1][2])
        stack.append([b, phase, rooted, inside])
    while stack:
        close()
    return out


def phases(trace, counters):
    """``{phase: host ms a tick}`` over the traced stretch. None where there
    is no trace or no span to read, no tick in the stretch, or the oldest
    row the program's span ring still holds closed after the stretch began:
    the ring may then have dropped spans of the stretch, and a sum over the
    rest would pass for one over all."""
    found = idle_gaps.analysis(trace, counters)
    if found is None:
        return None
    if "host_tick_phases" not in found:     # one walk for the five readers
        found["host_tick_phases"] = _phases(found)
    return found["host_tick_phases"]


def _phases(found):
    lo, hi = found["stretch"]
    rows = [s for spans in found["threads"].values() for s in spans]
    ticks = sum(name == TICK and lo <= a < hi for name, a, _ in rows)
    if not ticks or min(b for _, _, b in rows) > lo:
        return None
    out = dict.fromkeys(PHASES, 0)
    for spans in found["threads"].values():
        for phase, ns in self_ns(spans, lo, hi).items():
            out[phase] += ns
    return {phase: ns / 1e6 / ticks for phase, ns in out.items()}


def read(phase, trace, counters):
    found = phases(trace, counters)
    return None if found is None else found[phase]
