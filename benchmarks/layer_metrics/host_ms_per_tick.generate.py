"""Host milliseconds the engine's thread spends per decode tick, over the
traced stretch: its seconds inside ``decoder.step`` and the engine loop's
own steps (``engine.admit_http``, ``engine.pump_streams``,
``engine.reply_finished``), less ``continuous.drain``, the one place it
waits for the device, over the ``decoder.tick`` spans. ``engine.idle`` is
the loop sleeping with nothing in flight and is not counted. What a tick
costs the host however fast the device gets."""

from benchmarks import idle_gaps

WORK = ("decoder.step", "engine.admit_http", "engine.pump_streams",
        "engine.reply_finished")
WAIT = "continuous.drain"
TICK = "decoder.tick"


def read(trace, counters, cell, config, peak):
    found = idle_gaps.analysis(trace, counters)
    if found is None:
        return None
    lo, hi = found["stretch"]
    # these names open on the engine's thread alone
    ticks = work = 0
    for spans in found["threads"].values():
        for name, a, b in spans:
            inside = max(0, min(b, hi) - max(a, lo))
            if name in WORK:
                work += inside
            elif name == WAIT:
                work -= inside
            elif name == TICK and lo <= a < hi:
                ticks += 1
    return work / 1e6 / ticks if ticks else None
