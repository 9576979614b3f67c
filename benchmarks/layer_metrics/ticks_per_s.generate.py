"""Decode ticks the scheduler ran per second of the window: the pool's count
of paged-attention calls less those that were prefill chunks."""


def read(trace, counters, cell, config, peak):
    kv = counters.get("kv_stats")
    if not kv or "attn_ticks_kernel" not in kv:
        return None
    ticks = kv["attn_ticks_kernel"] - kv.get("prefill_chunks", 0)
    return ticks / counters["window_elapsed_s"]
