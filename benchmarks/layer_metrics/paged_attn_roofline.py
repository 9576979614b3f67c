"""The paged-attention kernel's share of its roofline over the traced
stretch: the KV bytes its decode calls had to read (every cached position of
every sequence that got a token, once) at the chip's peak bytes/s, over the
kernel's device seconds in the trace. Memory bound: a decode step does two
operations per byte read."""

from benchmarks import costs, trace_reduce


def read(trace, counters, cell, config, peak):
    pattern = cell.get("trace_ops", {}).get("paged_attn_decode")
    if not pattern or "token_events" not in counters:
        return None
    seconds, _calls = trace_reduce.op_seconds(trace, pattern, "module_ops")
    if seconds <= 0:
        return None
    lo, hi = counters["traced"]["t0"], counters["traced"]["t1"]
    positions = sum(p for t, p in counters["token_events"] if lo <= t < hi)
    nbytes = costs.paged_attention_bytes(
        positions, config["n_layer"], config["n_embd"], 2)
    least, _bound = costs.roofline_seconds(0, nbytes, peak)
    return 100.0 * least / seconds
