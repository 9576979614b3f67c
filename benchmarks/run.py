#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: finds the cell, its configuration, its driver and
its per-layer readers by the names in ``BENCHMARK.json``; refuses to start
unless JAX has a TPU with exactly the chips the cell asks for; builds inputs
and weights from ``--seed``; warms the cell's shapes (set-up); measures for
``--seconds``; compares the window's outputs with the plain reference after
the window; prints one JSON object as the last line of stdout. There is no
``if`` on a cell's or a metric's name here: a later PR adds files and entries.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
from concurrent import futures  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def say(**fields):
    print(json.dumps(fields), flush=True)


def refuse(message):
    print(f"benchmarks/run.py: {message}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_by_path(kind, name):
    """Import ``benchmarks/<kind>/<name>.py``; a metric's name may hold dots,
    so the file is loaded by its path and not by a module name."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class CompileEvents:
    """Counts what JAX reports of compilation: persistent-cache hits and
    misses, and every backend compile, whether or not a cache is on."""

    def __init__(self):
        self.hits = self.misses = self.compiles = 0

    def event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def duration(self, name, _seconds, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def snapshot(self):
        return dict(hits=self.hits, misses=self.misses,
                    compiles=self.compiles)


def find_cell(manifest, workload):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        refuse(f"no workload {workload!r} in BENCHMARK.json "
               f"(known: {sorted(cells)})")
    entry = cells[workload]
    cell = load_json(HERE, "workloads", f"{workload}.json")
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(ROOT, configs[entry["config"]]["file"])
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            refuse(f"{workload}: {key} is {cell[key]!r} in its file and "
                   f"{entry[key]!r} in BENCHMARK.json")
    return entry, cell, config


def metrics_of(manifest, section, workload, reported):
    """The metrics of ``section`` this cell reports: those that list it under
    ``workloads``, and those with no such key (for a per-layer metric, where
    the cell reports the end-to-end metric it moves)."""
    out = []
    for m in manifest[section]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def init_jax():
    """Import JAX with its persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says, else at a fixed path inside the
    checkout (the path is part of the cache's key), small programs kept."""
    if not os.environ.get(CACHE_DIR_ENV):
        os.environ[CACHE_DIR_ENV] = os.path.join(ROOT, ".jax_cache")
    os.makedirs(os.environ[CACHE_DIR_ENV], exist_ok=True)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a cache capped below one cell's programs (seen: 190 MB
    # on the chip's machine, 71 programs of the generation cell) evicts in
    # the order it is read, and every run compiles everything again
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


class Trace:
    """The profiler over the window, or over the start of it: device planes
    only (the host tracer slows the host's feed many times over). Started and
    stopped by the thread that drives the run, the main one: from a timer's
    thread ``stop_trace`` took three times as long for the same events."""

    def __init__(self, jax, trace_dir):
        self.jax = jax
        self.t1 = self.stop_s = None
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        self.t0 = time.perf_counter()

    def stop(self):
        self.t1 = time.perf_counter()
        self.jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - self.t1

    def over(self, window, seconds, trace_seconds):
        """``window(seconds)`` under the trace. A cell whose whole window
        would be too large a trace names ``trace_seconds``: the window then
        runs on a thread of its own (it sleeps, reads counters and joins
        its callers) while this one waits that long from ``t0`` and stops
        the profiler, however long the stop takes; what a traced run costs
        then depends on the stretch traced, not on the window."""
        if not trace_seconds:
            result = window(seconds)
            self.stop()
            return result
        with futures.ThreadPoolExecutor(1, "window") as pool:
            running = pool.submit(window, seconds)
            # returns early where the window is shorter than the stretch
            futures.wait([running], max(
                0.0, self.t0 + trace_seconds - time.perf_counter()))
            self.stop()
            return running.result()     # raises what the window raised


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return peaks, max((p for p in peaks if p is not None), default=None)


def main(argv=None, require_chip=True, driver_override=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    entry, cell, config = find_cell(manifest, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "mmlspark_tpu")):
        refuse("the system under test (mmlspark_tpu/) is not in this checkout")

    jax = init_jax()
    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu":
            refuse(f"needs a TPU; JAX found platform {dev.platform!r} "
                   f"({dev.device_kind})")
        if len(devices) != entry["chips"]:
            refuse(f"{args.workload} needs {entry['chips']} chip(s), JAX has "
                   f"{len(devices)}")
    peaks = load_json(HERE, "peaks.json")
    if require_chip and dev.device_kind not in peaks:
        refuse(f"device_kind {dev.device_kind!r} is not in peaks.json")
    peak = peaks.get(dev.device_kind)
    used = devices[:entry["chips"]]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    say(workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=device,
        cache_dir=os.environ[CACHE_DIR_ENV])

    events = CompileEvents()
    jax.monitoring.register_event_listener(events.event)
    jax.monitoring.register_event_duration_secs_listener(events.duration)

    t_jax = time.perf_counter() - T_START
    reference = load_by_path("references", config["reference"])
    make = driver_override or load_by_path("drivers", cell["driver"]).Driver
    driver = make(cell, config, args.seed, reference)
    t_built = time.perf_counter() - T_START
    try:
        driver.warm()
        at_setup = events.snapshot()
        setup_s = time.perf_counter() - T_START
        say(setup_parts=dict(to_devices_s=t_jax, build_s=t_built - t_jax,
                             warm_s=setup_s - t_built))

        trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
        if args.trace:
            trace = Trace(jax, trace_dir)
            result = trace.over(driver.window, args.seconds,
                                cell.get("trace_seconds"))
        else:
            trace = None
            result = driver.window(args.seconds)
        in_window = {k: v - at_setup[k]
                     for k, v in events.snapshot().items()}
        per_device_peak, memory_peak_bytes = memory_peak(used)
        device["memory_peak_bytes"] = memory_peak_bytes

        compared = driver.check()
    finally:
        driver.close()
    compared.append(dict(name="compiles_in_window",
                         value=in_window["compiles"] + in_window["misses"],
                         limit=0))
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in compared)
    say(compared=compared)
    say(setup_cache=at_setup, window_cache=in_window,
        peak_bytes_in_use=per_device_peak,
        samples=result.get("samples", {}),
        window_metrics=result["metrics"],
        window_elapsed_s=result.get("elapsed_s"))

    values = dict(result["metrics"], setup_s=setup_s)
    line = dict(correct=correct, attempted=result["attempted"],
                failed=result["failed"], device=device)
    if not trace:
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(manifest, "end_to_end", args.workload,
                                values)}
    else:
        from benchmarks import idle_gaps, trace_reduce
        t_reduce = time.perf_counter()
        reduced = trace_reduce.reduce_trace(trace_dir, trace.t1 - trace.t0)
        # what the trace cost: the stop's seconds grow with the events
        say(trace_bytes=os.path.getsize(trace_reduce.find_xplane(trace_dir)),
            traced_s=trace.t1 - trace.t0, stop_trace_s=trace.stop_s,
            device_op_events=sum(n for _, n in reduced["ops"].values()),
            reduce_s=time.perf_counter() - t_reduce)
        counters = dict(result.get("counters", {}),
                        traced=dict(t0=trace.t0, t1=trace.t1),
                        setup_cache=at_setup, window_cache=in_window,
                        window_elapsed_s=result.get("elapsed_s"),
                        chips=entry["chips"])
        metrics = {}
        for m in metrics_of(manifest, "per_layer", args.workload, values):
            value = load_by_path("layer_metrics", m["name"]).read(
                reduced, counters, cell, config, peak)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        # parsed once for the readers above; None where a run has no
        # device plane, no start time or no span of the program's
        gaps = idle_gaps.analysis(reduced, counters)
        line["breakdown"] = {"device_ops": trace_reduce.top_ops(reduced),
                             "idle_gaps": gaps["top"] if gaps else []}
        say(trace_modules=sorted(
            ([k, v[0], v[1]] for k, v in reduced["modules"].items()),
            key=lambda r: -r[1])[:10], trace_devices=reduced["devices"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
