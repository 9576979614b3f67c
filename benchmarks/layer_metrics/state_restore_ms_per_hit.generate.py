"""Host milliseconds the engine's thread spends in ``decoder.state_restore``
a prefix hit, over the traced stretch: the spans that opened inside it (one
an admission that found its context stored and copied the snapshot's rows, a
state and a tail a layer, into its slot), their time inside the stretch over
their number. The span closes when the copy is LAUNCHED (the device runs it
behind the tick in flight), so this is what a hit costs the scheduler, not
the copy's device seconds. None where the program opens no such span, no hit
fell in the stretch, or the span ring may have dropped rows of it (as
``_host_tick``)."""

from benchmarks import idle_gaps

RESTORE = "decoder.state_restore"


def read(trace, counters, cell, config, peak):
    found = idle_gaps.analysis(trace, counters)
    if found is None:
        return None
    lo, hi = found["stretch"]
    rows = [s for spans in found["threads"].values() for s in spans]
    if not rows or min(b for _, _, b in rows) > lo:
        return None
    inside = [min(b, hi) - a for name, a, b in rows
              if name == RESTORE and lo <= a < hi]
    return sum(inside) / 1e6 / len(inside) if inside else None
