"""Model FLOPs of the rows the window returned over the seconds the device
was busy, as a share of the chip's bf16 peak. Read only where the trace
covers the whole window: the rows of a shorter traced stretch are not known,
and the window's rows over a stretch's busy seconds would read too high."""

from benchmarks import costs


def read(trace, counters, cell, config, peak):
    if not trace["busy_s"] or "rows" not in counters:
        return None
    traced = counters["traced"]
    if traced["t0"] > counters["t0"] or traced["t1"] < counters["t1"]:
        return None
    flops = counters["rows"] * costs.resnet_flops_per_image(
        config["stage_sizes"], config["width"], config["num_classes"],
        config["image_size"])
    return 100.0 * flops / trace["busy_s"] / (
        peak["bf16_flops_per_s"] * counters["chips"])
