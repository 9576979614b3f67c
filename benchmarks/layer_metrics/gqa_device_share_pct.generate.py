"""Share of the decode tick's device seconds inside the grouped-query
layers' named operation, the fused paged decode kernel over the rows' pages.
The projections and a riding window's gathered and masked attention are
unnamed fusions of the tick and are NOT counted: the share is a floor."""

from benchmarks import trace_reduce
from benchmarks.layer_metrics import _hybrid


def read(trace, counters, cell, config, peak):
    tick = cell.get("trace_ops", {}).get("tick")
    sweep = _hybrid.op_seconds(trace, cell, "gqa_decode")
    if not tick or sweep is None:
        return None
    total, _runs = trace_reduce.op_seconds(trace, tick, "modules")
    return 100.0 * sweep / total if total > 0 else None
