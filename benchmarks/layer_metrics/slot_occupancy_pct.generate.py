"""Tokens the decode ticks emitted over ticks x slots: how full the batch
was. A request's first token comes out of its prefill and is not a tick's."""


def read(trace, counters, cell, config, peak):
    kv = counters.get("kv_stats")
    if not kv or "token_events" not in counters:
        return None
    ticks = kv["attn_ticks_kernel"] - kv.get("prefill_chunks", 0)
    if ticks <= 0:
        return None
    emitted = sum(1 for t, _ in counters["token_events"]
                  if counters["t0"] <= t < counters["t1"])
    return 100.0 * emitted / (ticks * counters["slots"])
