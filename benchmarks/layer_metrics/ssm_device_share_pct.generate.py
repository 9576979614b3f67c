"""Share of the decode tick's device seconds inside the state-space layers'
named operation, the Pallas step over the rows' states. The projections, the
convolution, the gated norm and a riding window's chunked scan are unnamed
fusions of the tick and are NOT counted: the share is a floor."""

from benchmarks import trace_reduce
from benchmarks.layer_metrics import _hybrid


def read(trace, counters, cell, config, peak):
    tick = cell.get("trace_ops", {}).get("tick")
    step = _hybrid.op_seconds(trace, cell, "ssm_decode")
    if not tick or step is None:
        return None
    total, _runs = trace_reduce.op_seconds(trace, tick, "modules")
    return 100.0 * step / total if total > 0 else None
