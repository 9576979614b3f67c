"""From a profiler trace (``*.xplane.pb``) to the few numbers the benchmark
reads: device busy seconds, per-operation device seconds, collective time.

Only ``jax.profiler.ProfileData`` is needed. A device is a plane named
``/device:TPU:<n>``; its operations are the events of the line ``XLA Ops``
(one event per executed HLO operation, start and duration in nanoseconds);
its programs are the events of ``XLA Modules``. Other lines of a device plane
(steps, TraceMe scopes) overlap the operations and are not counted as work.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
_SUFFIX = re.compile(r"(\.\d+)+$")
_MODULE_ID = re.compile(r"\(\d+\)$")


def op_key(name):
    """``fusion.123`` -> ``fusion``: one HLO operation unrolled over layers
    carries a different number in each, and the sum is what matters."""
    return _SUFFIX.sub("", name.split(" = ")[0].strip().lstrip("%"))


def union_seconds(intervals):
    """Length of the union of ``(start_ns, end_ns)`` intervals, in seconds."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total / 1e9


def exposed_seconds(collectives, compute):
    """Seconds of the ``collectives`` intervals during which no ``compute``
    interval runs: the union of the collectives less its overlap with the
    union of compute."""
    both = union_seconds(list(collectives) + list(compute))
    return both - union_seconds(compute)


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_profile(profile, window_s):
    """Reduce a ``ProfileData`` to a plain dict.

    ``busy_s`` is the union of operation intervals, averaged over the device
    planes found; ``ops`` maps an operation key to ``[seconds, count]`` summed
    over devices and divided by their number (so it compares with ``busy_s``);
    ``modules`` likewise for whole programs, and ``module_ops`` for
    ``<program>/<operation>``, an operation filed under the program whose
    interval holds its start; ``collective_s`` and ``collective_exposed_s``
    are per-device averages too. ``window_s`` is the host's length of the
    traced stretch, or the span of the device's own events where that is
    longer (the profiler records until it has really stopped)."""
    devices = []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops, modules, module_ops = {}, {}, {}
        intervals, coll, comp, runs = [], [], [], []
        lines = {ln.name: ln for ln in plane.lines}
        for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines
                   else ()):
            lo = float(ev.start_ns)
            hi = lo + float(ev.duration_ns)
            key = _MODULE_ID.sub("", ev.name)
            runs.append((lo, hi, key))
            row = modules.setdefault(key, [0.0, 0])
            row[0] += (hi - lo) / 1e9
            row[1] += 1
        runs.sort()
        starts = [r[0] for r in runs]
        for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            lo = float(ev.start_ns)
            hi = lo + float(ev.duration_ns)
            key = op_key(ev.name)
            intervals.append((lo, hi))
            (coll if COLLECTIVE.match(key) else comp).append((lo, hi))
            i = bisect.bisect_right(starts, lo) - 1
            inside = runs[i][2] if i >= 0 and lo < runs[i][1] else "-"
            for table, k in ((ops, key), (module_ops, f"{inside}/{key}")):
                row = table.setdefault(k, [0.0, 0])
                row[0] += (hi - lo) / 1e9
                row[1] += 1
        span = ((max(hi for _, hi in intervals)
                 - min(lo for lo, _ in intervals)) / 1e9 if intervals else 0.0)
        devices.append(dict(
            name=plane.name, busy_s=union_seconds(intervals), ops=ops,
            modules=modules, module_ops=module_ops, span_s=span,
            collective_s=union_seconds(coll),
            collective_exposed_s=exposed_seconds(coll, comp) if coll else 0.0))
    n = len(devices)
    if n == 0:
        return dict(devices=0, window_s=window_s, busy_s=0.0, ops={},
                    modules={}, module_ops={}, collective_s=0.0,
                    collective_exposed_s=0.0)

    def merged(field):
        out = {}
        for d in devices:
            for key, (sec, cnt) in d[field].items():
                row = out.setdefault(key, [0.0, 0.0])
                row[0] += sec / n
                row[1] += cnt / n
        return out

    return dict(
        devices=n, window_s=max([window_s] + [d["span_s"] for d in devices]),
        busy_s=sum(d["busy_s"] for d in devices) / n,
        ops=merged("ops"), modules=merged("modules"),
        module_ops=merged("module_ops"),
        collective_s=sum(d["collective_s"] for d in devices) / n,
        collective_exposed_s=sum(d["collective_exposed_s"]
                                 for d in devices) / n)


def reduce_trace(trace_dir, window_s):
    import jax
    return reduce_profile(
        jax.profiler.ProfileData.from_file(find_xplane(trace_dir)), window_s)


def top_ops(reduced, n=10):
    """``[[name, seconds], ...]``: the operations with most device time."""
    rows = sorted(((k, v[0]) for k, v in reduced["ops"].items()),
                  key=lambda kv: -kv[1])
    return [[k, s] for k, s in rows[:n]]


def op_seconds(reduced, pattern, table="ops"):
    """``(seconds, count)`` of the operations whose key matches ``pattern``;
    with ``table="module_ops"`` the key is ``<program>/<operation>``."""
    rx = re.compile(pattern)
    sec = cnt = 0.0
    for key, (s, c) in reduced[table].items():
        if rx.search(key):
            sec += s
            cnt += c
    return sec, cnt
