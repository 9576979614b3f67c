"""Name the device's idle gaps by what the host was doing in them.

The device's side comes from the profiler's trace: a device plane's busy
intervals are the union of its ``XLA Ops`` events, its idle gaps what is
left of the traced stretch. The host's side comes from the program's own
spans (``mmlspark_tpu.observability.tracing.span``), read from the program's
span log: rows ``(name, thread, start, end)`` in ``time.time_ns()``, the
clock of the trace's ``profile_start_time``. ``run.py`` takes the trace with
the host tracer off, so the trace itself holds no host event (and with it
on, the profiler puts every Python thread's annotations on one line,
``python``, where they do not nest). A program without the log (the parent
of the PR that added it) gives no spans, and every reader built on this
gives None.

The two clocks agree only to about a millisecond: at ``host_tracer_level=0``
the device's events read 1.0-1.4 ms early against ``time.time_ns()`` on the
v5e (a probe program started, by the trace, before the call that launched
it; PERF.md, PR 26). A gap of a few milliseconds can so be named for the
span next to the right one. Every run checks its own clocks, in
``diagnostics``: ``first_launch_ms`` (``first_launches`` below) and
``idle_s_in_gaps_under_2ms``, the idle time a shift of that size touches.
The shares inside and outside a pass rest on gaps of 100 ms and more.

A gap belongs to the innermost span open at its start, on each thread that
has one open: a gap that several threads see counts its whole length under
each of their names, so the names' seconds can add up to more than the idle
seconds; ``unattributed`` is the gaps no thread had a span open in.
"""

import bisect
import glob
import os
import time

from benchmarks import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENVIRONMENT_PLANE = "Task Environment"
UNATTRIBUTED = "unattributed"
SHORT_GAP_NS = 2_000_000
#: a pass over a partition: ``BatchRunner.run``, first batch to last launch
PASS = ("runner.run",)
#: a gap this long has the device drained: what ends it is a first launch
DRAINED_NS = 10_000_000
#: the spans a thread launches device work from at the start of a pass
#: (on a resident column the prefetch worker's slice is the first program)
LAUNCH = ("runner.coerce", "runner.h2d", "runner.dispatch")


def merged(intervals):
    """Sorted, disjoint ``[(lo, hi), ...]`` covering the same points."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def complement(cover, lo, hi):
    """What ``[lo, hi]`` holds outside the merged intervals ``cover``."""
    out, at = [], lo
    for a, b in cover:
        if b <= lo or a >= hi:
            continue
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return out


def overlap_ns(gaps, cover):
    """Length of the ``gaps`` inside the merged intervals ``cover``."""
    starts = [a for a, _ in cover]
    total = 0
    for lo, hi in gaps:
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(cover) and cover[i][0] < hi:
            total += max(0, min(hi, cover[i][1]) - max(lo, cover[i][0]))
            i += 1
    return total


def device_busy(profile):
    """``[merged busy intervals of each device plane]``, in the trace's
    nanoseconds (from the start of the profile)."""
    out = []
    for plane in profile.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                out.append(merged(
                    (ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events))
    return out


def profile_start_ns(profile):
    """The wall clock (``time.time_ns()``) at the trace's zero, or None."""
    for plane in profile.planes:
        if plane.name == ENVIRONMENT_PLANE:
            return dict(plane.stats).get("profile_start_time")
    return None


def program_spans(origin_ns):
    """``{thread: [(name, lo, hi), ...]}`` from the program's span log, in
    nanoseconds from ``origin_ns`` on the wall clock; empty where the
    program keeps no such log."""
    try:
        from mmlspark_tpu.observability import tracing
        rows = tracing.span_log()
    except (ImportError, AttributeError):
        return {}
    out = {}
    for name, thread, t0, t1 in rows:
        out.setdefault(thread, []).append(
            (name, t0 - origin_ns, t1 - origin_ns))
    return out


def innermost(spans):
    """``(times, names)``: over ``[times[i], times[i+1])`` the innermost of
    one thread's (properly nested) spans is ``names[i]``, None for none."""
    times, names, stack = [], [], []

    def mark(t):
        name = stack[-1][1] if stack else None
        if times and times[-1] == t:
            names[-1] = name
        elif not names or names[-1] != name:
            times.append(t)
            names.append(name)

    for name, lo, hi in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= lo:
            t = stack.pop()[0]
            mark(t)
        stack.append((hi, name))
        mark(lo)
    while stack:
        t = stack.pop()[0]
        mark(t)
    return times, names


def attribute(gaps, lines):
    """``({name: seconds}, named_seconds)``: each gap under the innermost
    span open at its start on every thread (``lines``: ``innermost`` of
    each) that has one; ``named_seconds`` counts a gap once if any thread
    names it."""
    by_name, named = {}, 0
    for lo, hi in gaps:
        seen = set()
        for times, names in lines:
            i = bisect.bisect_right(times, lo) - 1
            if i >= 0 and names[i] is not None:
                seen.add(names[i])
        for name in seen or {UNATTRIBUTED}:
            by_name[name] = by_name.get(name, 0.0) + (hi - lo) / 1e9
        if seen:
            named += hi - lo
    return by_name, named / 1e9


def seconds_in_spans(gaps, lines):
    """``{name: seconds}``: the gaps' time by the innermost span each
    thread was in meanwhile, summed over the threads (so a second of idle
    that four threads spend in ``runner.d2h`` counts four)."""
    out = {}
    for times, names in lines:
        for lo, hi in gaps:
            i = max(bisect.bisect_right(times, lo) - 1, 0)
            while i < len(times) and times[i] < hi:
                end = times[i + 1] if i + 1 < len(times) else hi
                inside = min(hi, end) - max(lo, times[i])
                if names[i] is not None and inside > 0:
                    out[names[i]] = out.get(names[i], 0.0) + inside / 1e9
                i += 1
    return out


def find_trace():
    """The newest trace under ``.bench_trace/``: ``run.py`` keeps one, the
    running cell's, until the readers are done."""
    paths = glob.glob(os.path.join(ROOT, ".bench_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def first_launches(gaps, threads):
    """The run's own check of the two clocks: ``[(after_start, after_end),
    ...]`` in ms, one pair per gap that left the device drained: the gap's
    end (the first device operation after it) less the start and less the
    end of the first launching span opened since the gap began. Where that
    span launched the operation, a sound pair of clocks reads ``after_start``
    at or above 0 and ``after_end`` at or under the launch latency (some
    tenths of a ms): an operation before the call that launched it, or well
    after the call returned, is the clocks' disagreement, and the span's
    length the most this check can miss."""
    spans = sorted((lo, hi) for rows in threads.values()
                   for name, lo, hi in rows if name in LAUNCH)
    starts = [lo for lo, _ in spans]
    out = []
    for g0, g1 in gaps:
        i = bisect.bisect_left(starts, g0)
        if g1 - g0 >= DRAINED_NS and i < len(spans):
            out.append(((g1 - spans[i][0]) / 1e6, (g1 - spans[i][1]) / 1e6))
    return out


def spread(values):
    """``[least, median, most]``; None of nothing."""
    ordered = sorted(values)
    return [ordered[0], ordered[len(ordered) // 2], ordered[-1]] \
        if ordered else None


def analyse(profile, stretch_wall_ns, reduced=None):
    """Everything the readers share, from one parsed trace. ``stretch`` is
    the traced stretch on the wall clock. None where the trace has no start
    time or the program gave no spans; ``gaps`` is empty where it has no
    device plane. ``diagnostics`` is for whoever prints the run's lines
    (``reduced``, ``trace_reduce``'s dict, adds its own idle seconds)."""
    origin = profile_start_ns(profile)
    busy = [b for b in device_busy(profile) if b]
    if origin is None:
        return None
    threads = program_spans(origin)
    if not threads:
        return None
    lo, hi = (t - origin for t in stretch_wall_ns)
    gaps = [complement(device, lo, hi) for device in busy]
    n = max(len(gaps), 1)
    lines = [innermost(spans) for spans in threads.values()]
    by_name, named_s, in_spans = {}, 0.0, {}
    for device_gaps in gaps:
        names, named = attribute(device_gaps, lines)
        named_s += named / n
        for name, seconds in names.items():
            by_name[name] = by_name.get(name, 0.0) + seconds / n
        for name, seconds in seconds_in_spans(device_gaps, lines).items():
            in_spans[name] = in_spans.get(name, 0.0) + seconds / n
    idle_s = sum(b - a for g in gaps for a, b in g) / 1e9 / n
    top = [[k, s] for k, s in sorted(by_name.items(),
                                     key=lambda kv: -kv[1])[:10]]
    launches = first_launches([g for d in gaps for g in d], threads)
    return dict(
        threads=threads, gaps=gaps, stretch=(lo, hi), in_spans=in_spans,
        idle_s=idle_s, named_s=named_s, by_name=by_name, top=top,
        diagnostics=dict(
            idle_gaps=top, idle_s=idle_s,
            idle_thread_seconds_in_span=sorted(
                ([k, v] for k, v in in_spans.items()),
                key=lambda kv: -kv[1])[:10],
            idle_named_share=named_s / idle_s if idle_s else None,
            idle_s_by_trace_reduce=(reduced["window_s"] - reduced["busy_s"]
                                    if reduced else None),
            idle_s_in_gaps_under_2ms=sum(
                b - a for g in gaps for a, b in g
                if b - a < SHORT_GAP_NS) / 1e9 / n,
            first_launch_ms=dict(
                n=len(launches),
                after_span_start=spread(a for a, _ in launches),
                after_span_end=spread(b for _, b in launches)),
            longest_gaps_ms=sorted(
                ((b - a) / 1e6 for g in gaps for a, b in g),
                reverse=True)[:5]))


_CACHE = {}


def analysis(reduced, counters):
    """``analyse`` of the running cell's trace, parsed once for all the
    readers of a run; None where there is nothing to read."""
    path = find_trace()
    traced = counters.get("traced")
    if path is None or not traced:
        return None
    if path not in _CACHE:
        import jax
        to_wall = time.time_ns() - time.perf_counter_ns()
        _CACHE.clear()
        _CACHE[path] = analyse(
            jax.profiler.ProfileData.from_file(path),
            [int(traced[k] * 1e9) + to_wall for k in ("t0", "t1")], reduced)
    return _CACHE[path]


def pass_cover(found):
    """Where any thread was inside a pass: merged intervals."""
    return merged((lo, hi) for spans in found["threads"].values()
                  for name, lo, hi in spans if name in PASS)


def in_pass_seconds(found):
    """Idle seconds while any thread was inside a pass (per-device
    average); None where the trace had no device plane."""
    if not found["gaps"]:
        return None
    cover = pass_cover(found)
    return sum(overlap_ns(g, cover) for g in found["gaps"]) / 1e9 \
        / len(found["gaps"])


def between_passes_seconds(found):
    """Idle seconds while no thread was inside a pass, counted on their
    own: the gaps cut to what the traced stretch holds outside the
    passes. With ``in_pass_seconds`` it makes this file's idle seconds;
    how far those are from ``trace_reduce``'s (which takes the stretch
    from the host's clock and the busy time from the whole trace) is the
    two shares' residual against ``device_idle_pct``."""
    if not found["gaps"]:
        return None
    outside = complement(pass_cover(found), *found["stretch"])
    return sum(overlap_ns(g, outside) for g in found["gaps"]) / 1e9 \
        / len(found["gaps"])
