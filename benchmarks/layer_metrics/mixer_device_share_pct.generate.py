"""Share of the decode tick's device seconds spent inside the two mixers'
decode operations (the linear-attention step and the selected-block
attention): how far from free the long context is."""

from benchmarks import trace_reduce
from benchmarks.layer_metrics import _hybrid


def read(trace, counters, cell, config, peak):
    tick = cell.get("trace_ops", {}).get("tick")
    parts = [_hybrid.op_seconds(trace, cell, name)
             for name in ("lightning_decode", "sparse_decode")]
    if not tick or None in parts:
        return None
    total, _runs = trace_reduce.op_seconds(trace, tick, "modules")
    return 100.0 * sum(parts) / total if total > 0 else None
