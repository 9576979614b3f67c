"""Share of the decode tick's device seconds inside the routed feed-forward's
named operations: the grouped product over the experts touched and the
routing's top-k selections. The products with 0/1 matrices that lay the rows
out and weigh them back are unnamed fusions of the tick and are NOT counted:
the share is a floor."""

from benchmarks import trace_reduce
from benchmarks.layer_metrics import _hybrid


def read(trace, counters, cell, config, peak):
    tick = cell.get("trace_ops", {}).get("tick")
    product = _hybrid.op_seconds(trace, cell, "moe_experts")
    if not tick or product is None:
        return None
    routing = _hybrid.op_seconds(trace, cell, "moe_routing") or 0.0
    total, _runs = trace_reduce.op_seconds(trace, tick, "modules")
    return 100.0 * (product + routing) / total if total > 0 else None
