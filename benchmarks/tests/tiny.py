"""The benchmark's cells at sizes a CPU test can hold: the same drivers,
references and comparisons, with the configuration's depth and widths, the
traffic's counts and the engine's chunk shrunk in memory. Never a chip
result; the limits of the real cells do not bind these sizes."""

import contextlib
import io
import json

from benchmarks import run, traffic

SHRINK = {
    "transform": dict(
        config=dict(stage_sizes=[1, 1, 1, 1], width=8, num_classes=10,
                    image_size=32),
        cell=dict(mini_batch_size=8),
        mix=dict(rows_per_pass=64, distinct_images=32, check_rows=8)),
    "generate": dict(
        config=dict(n_layer=2, n_embd=64, n_head=4, n_inner=256,
                    n_positions=128, vocab_size=256),
        cell=dict(slots=4, max_len=128, trace_seconds=1,
                  engine={"prefill_chunk": 32}),
        mix=dict(clients=4, requests_per_client=4,
                 prompt={"median": 28, "sigma": 0.6, "min": 9, "max": 80},
                 output={"median": 12, "sigma": 0.5, "min": 4, "max": 24},
                 max_total=128, ramp_seconds=1, check_requests=3,
                 warm=dict(plain_prompts=[16, 32], group_sizes=[1, 2, 4],
                           chunked_prompts=[40, 48, 64],
                           defrag=dict(prompts=[100, 100, 40],
                                       outputs=[2, 2, 8])))),
}


@contextlib.contextmanager
def shrunk():
    """Patch ``run.find_cell`` and ``traffic.load`` to the tiny sizes."""
    find_cell, load = run.find_cell, traffic.load

    def tiny_cell(manifest, workload):
        entry, cell, config = find_cell(manifest, workload)
        s = SHRINK[cell["driver"]]
        traffic.load = lambda name: dict(load(name), **s["mix"])
        return entry, dict(cell, **s["cell"]), dict(config, **s["config"])

    run.find_cell = tiny_cell
    try:
        yield
    finally:
        run.find_cell, traffic.load = find_cell, load


def run_cell(workload, seed=7, seconds=1.0, trace=0, driver_override=None):
    """Drive ``run.main`` past its look for a chip; returns (the last line
    as a dict, the lines before it)."""
    out = io.StringIO()
    with shrunk(), contextlib.redirect_stdout(out):
        run.main(["--workload", workload, "--seed", str(seed),
                  "--seconds", str(seconds), "--trace", str(trace)],
                 require_chip=False, driver_override=driver_override)
    lines = [json.loads(ln) for ln in out.getvalue().splitlines() if ln]
    return lines[-1], lines[:-1]
