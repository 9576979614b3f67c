"""Prompt tokens the chunked-prefill scheduler's windows computed a second of
the window (the pool's ``prefill_tokens``): what a change to chunking trades
against decode."""


def read(trace, counters, cell, config, peak):
    tokens = counters.get("kv_stats", {}).get("prefill_tokens")
    if tokens is None or not counters.get("window_elapsed_s"):
        return None
    return tokens / counters["window_elapsed_s"]
