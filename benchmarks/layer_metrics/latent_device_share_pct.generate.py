"""Share of the decode tick's device seconds inside latent attention: the
absorbed decode kernel and, where a prefill window rides the tick, the
window's expanded attention over its row's latent pages. How far from free
the long context is."""

from benchmarks import trace_reduce
from benchmarks.layer_metrics import _hybrid


def read(trace, counters, cell, config, peak):
    tick = cell.get("trace_ops", {}).get("tick")
    decode = _hybrid.op_seconds(trace, cell, "latent_decode")
    if not tick or decode is None:
        return None
    window = _hybrid.op_seconds(trace, cell, "latent_window") or 0.0
    total, _runs = trace_reduce.op_seconds(trace, tick, "modules")
    return 100.0 * (decode + window) / total if total > 0 else None
