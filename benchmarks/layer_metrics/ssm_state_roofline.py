"""The state-space decode step's share of its roofline over the traced
stretch: the state bytes its calls had to move (each live row's ``head_dim x
state`` state a head read and written once a state-space layer a step: the
pool's ``ssm_state_rows``, scaled to the stretch) at the chip's peak bytes/s,
over the step's device seconds in the trace. None where the program counts
no such rows or the trace holds no such step."""

from benchmarks import costs, costs_nemotron
from benchmarks.layer_metrics import _hybrid, _routed


def read(trace, counters, cell, config, peak):
    seconds = _hybrid.op_seconds(trace, cell, "ssm_decode")
    rows = counters.get("kv_stats", {}).get("ssm_state_rows")
    share = _routed.traced_share(counters)
    if seconds is None or not rows or share is None:
        return None
    nbytes = costs_nemotron.ssm_state_bytes(
        rows * share, config["mamba_num_heads"], config["mamba_head_dim"],
        config["ssm_state_size"])
    least, _bound = costs.roofline_seconds(0, nbytes, peak)
    return 100.0 * least / seconds
