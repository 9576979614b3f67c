"""Compilation-cache management and AOT bucket warm-up.

XLA compiles one executable per (program, input shapes, placement) triple, and
that compile lands — multi-second for real graphs — on whatever request is
unlucky enough to arrive first in each padding bucket. This module removes the
stall from both ends:

* **Persistent compilation cache** — :func:`enable_persistent_cache` turns on
  JAX's on-disk executable cache (placed by ``JAX_COMPILATION_CACHE_DIR``,
  else one fixed directory inside the checkout), so a process restart
  deserializes yesterday's executables instead of recompiling them. TVM (arxiv 1802.04799) and ONNX-MLIR (arxiv 2008.08272)
  both land on the same conclusion: once the graph is static, inference
  performance is decided at the compile-cache and host↔device boundary.
* **AOT warm-up** — :func:`warm_up_jitted` drives a jitted program through
  every padding-bucket shape in the expected vocabulary *before* first
  traffic, populating the in-process jit cache (and, when enabled, the
  persistent cache). ``ONNXModel.warm_up`` / ``JaxModel.warm_up`` and the
  ``ServingEngine`` pre-serve hook are thin wrappers over this.
* **Stage counters** — :class:`StageCounters` instruments the feed/drain
  pipeline (coerce / pad / h2d / compile / dispatch / d2h) with near-zero
  overhead so ``bench.py`` can report where partition wall-clock actually
  goes.
"""

from __future__ import annotations

import os
import threading

from ..reliability.lock_sanitizer import new_lock
import time
from typing import Callable, Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

from ..observability import counter as _metric_counter
from ..observability import tracing as _tracing
from ..observability import watch as _watch
from .padding import bucket_size

__all__ = ["enable_persistent_cache", "persistent_cache_dir", "StageCounters",
           "jit_cache_size", "jitted", "warm_up_jitted", "warm_up_model",
           "resolve_input_specs"]

#: JAX's own variable: where it is set, the cache lives there and this
#: module sets no directory in code
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: where the cache lives otherwise — one fixed path inside the checkout (the
#: path is part of the cache key, so a directory that moves never hits)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# Registry mirrors (docs/observability.md has the catalog). Stage counters
# stay per-model objects for snapshot parity with the reference; every
# StageCounters.add also feeds the process-global labeled counters below so
# GET /metrics sees aggregate pipeline time without any plumbing. The
# cache-outcome counters are shared with models/runner.py, which owns the
# per-dispatch attribution.
M_STAGE_SECONDS = _metric_counter(
    "mmlspark_runner_stage_seconds_total",
    "Cumulative feed/drain pipeline wall-clock by stage", ("stage",))
M_STAGE_CALLS = _metric_counter(
    "mmlspark_runner_stage_calls_total",
    "Feed/drain pipeline stage invocations", ("stage",))
M_STAGE_BYTES = _metric_counter(
    "mmlspark_runner_stage_bytes_total",
    "Bytes crossing the host<->device boundary by stage", ("stage",))
M_CACHE_HITS = _metric_counter(
    "mmlspark_compile_cache_hits_total",
    "Dispatches served by an already-compiled executable")
M_CACHE_MISSES = _metric_counter(
    "mmlspark_compile_cache_misses_total",
    "Dispatches that paid an inline XLA trace+compile")
M_STEADY_RECOMPILES = _metric_counter(
    "mmlspark_compile_cache_steady_state_recompiles_total",
    "Compiles observed by the dispatch loop, i.e. outside warm-up — "
    "nonzero means a bucket is missing from the warm_up vocabulary")
M_WARMUP_BUCKETS = _metric_counter(
    "mmlspark_compile_cache_warmup_buckets_total",
    "Padding buckets executed ahead of traffic by warm_up")
M_WARMUP_SECONDS = _metric_counter(
    "mmlspark_compile_cache_warmup_seconds_total",
    "Wall-clock spent in AOT warm-up")

def enable_persistent_cache(cache_dir: Optional[str] = None) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    The directory is placed from outside: where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX has already read it and this function writes no directory
    into ``jax.config`` (``cache_dir`` is ignored). Where it is not set, the
    cache goes to ``cache_dir``, default :data:`DEFAULT_CACHE_DIR` — never a
    path built from a temporary name, a pid or the time. Idempotent; the
    min-compile-time and min-entry-size gates are zeroed so small graphs
    (unit-test MLPs, per-bucket variants of one model) are cached too — the
    default 1 s gate would silently skip exactly the programs serving
    warm-up cares about.
    """
    import jax
    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = cache_dir or DEFAULT_CACHE_DIR
        if jax.config.jax_compilation_cache_dir != path:
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def persistent_cache_dir() -> Optional[str]:
    """The directory JAX's persistent compilation cache writes to, if any
    (``jax.config`` is the single source of truth)."""
    import jax
    return jax.config.jax_compilation_cache_dir or None


def jit_cache_size(jitted) -> Optional[int]:
    """Entries in a jitted callable's in-process executable cache; ``None``
    for a callable that is not a ``jax.jit`` product — callers must treat
    that as "unknown", not zero.
    """
    size = getattr(jitted, "_cache_size", None)
    return int(size()) if size is not None else None


_JITTED: Dict[str, Callable] = {}


def jitted(name: str, fn: Callable,
           static_argnums: Optional[Tuple[int, ...]] = None) -> Callable:
    """Return a jitted version of ``fn`` cached process-wide under ``name``.

    Per-call ``@jax.jit`` closures create a fresh function object every
    invocation, so jax's jit cache never hits and every transform
    recompiles; stages register their kernels here once, keyed by a stable
    name. The first caller's ``fn`` wins — callers must pass a pure function
    whose behavior is fully determined by its arguments (+ static args)."""
    if name not in _JITTED:
        import jax
        _JITTED[name] = (jax.jit(fn, static_argnums=static_argnums)
                         if static_argnums is not None else jax.jit(fn))
    return _JITTED[name]


class StageCounters:
    """Lightweight per-stage timing/byte counters for the feed/drain pipeline.

    Stages are free-form strings; the runner uses ``coerce``, ``pad``,
    ``h2d``, ``compile``, ``dispatch``, ``d2h``. Thread-safe (partitions run
    concurrently); ~100 ns per ``add``, so it stays on in production. The
    compile/dispatch split is attributed by observing jit-cache growth
    around each dispatch, so under concurrent partitions a compile may be
    double-attributed — counters are diagnostics, not an audit log.
    """

    def __init__(self):
        self._lock = new_lock("ops.compile_cache.StageCounters._lock")
        self._stages: Dict[str, Dict[str, float]] = {}

    def add(self, stage: str, seconds: float, nbytes: int = 0,
            count: int = 1) -> None:
        with self._lock:
            s = self._stages.setdefault(
                stage, {"calls": 0, "seconds": 0.0, "bytes": 0})
            s["calls"] += count
            s["seconds"] += seconds
            s["bytes"] += nbytes
        # mirror into the process-global registry (aggregated over models)
        M_STAGE_SECONDS.inc(seconds, stage=stage)
        M_STAGE_CALLS.inc(count, stage=stage)
        if nbytes:
            M_STAGE_BYTES.inc(nbytes, stage=stage)

    class _Timer:
        __slots__ = ("_c", "_stage", "_nbytes", "_span", "_t0")

        def __init__(self, counters, stage, nbytes, span):
            self._c, self._stage, self._nbytes = counters, stage, nbytes
            self._span = span

        def __enter__(self):
            if self._span is not None:
                self._span.__enter__()
            self._t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            self._c.add(self._stage, time.perf_counter() - self._t0,
                        self._nbytes)
            if self._span is not None:
                self._span.__exit__(*exc)
            return False

    def timer(self, stage: str, nbytes: int = 0, span: Optional[str] = None,
              **attrs: object) -> "StageCounters._Timer":
        """Time the block into ``stage``; with ``span`` the same statement
        opens that span (``attrs`` are its attributes), so a boundary never
        has the counter without the span or the span without the counter."""
        return self._Timer(self, stage, nbytes,
                           _tracing.span(span, **attrs) if span else None)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: {"calls": int(v["calls"]),
                        "seconds": round(float(v["seconds"]), 6),
                        "bytes": int(v["bytes"])}
                    for k, v in sorted(self._stages.items())}

    def reset(self) -> None:
        with self._lock:
            self._stages.clear()

    def total_seconds(self, stage: str) -> float:
        with self._lock:
            s = self._stages.get(stage)
            return float(s["seconds"]) if s else 0.0


def resolve_input_specs(inputs: Iterable, feed: Dict[str, str],
                        transpose: Dict[str, Sequence[int]],
                        overrides: Optional[Dict[str, tuple]] = None
                        ) -> Dict[str, Tuple[np.dtype, tuple]]:
    """Per-row (dtype, shape) of each *fed* model input, for warm-up zeros.

    ``inputs`` are converted-model value infos (``.name``, ``.numpy_dtype``,
    ``.shape``). Inputs routed through ``transpose_dict`` are fed in the
    column's layout, so the declared (post-transpose) shape is run backwards
    through the permutation. ``overrides`` ({name: (dtype, row_shape)}) wins
    outright — required when the declared shape is symbolic, or when the
    column's dtype differs from the graph's (uint8 images into a float
    input).
    """
    overrides = dict(overrides or {})
    specs: Dict[str, Tuple[np.dtype, tuple]] = {}
    for vi in inputs:
        if vi.name not in feed:
            continue
        if vi.name in overrides:
            dt, shape = overrides[vi.name]
            specs[vi.name] = (np.dtype(dt), tuple(shape))
            continue
        declared = list(vi.shape)
        perm = transpose.get(vi.name)
        if perm is not None:
            if len(perm) != len(declared):
                raise ValueError(
                    f"transpose_dict[{vi.name!r}] permutes {len(perm)} axes "
                    f"but the input declares {len(declared)}")
            fed = [None] * len(declared)
            for i, p in enumerate(perm):
                fed[p] = declared[i]
            declared = fed
        row_shape = declared[1:]
        if any(not isinstance(d, int) for d in row_shape):
            raise ValueError(
                f"input {vi.name!r} has symbolic per-row shape {row_shape}; "
                f"pass input_specs={{{vi.name!r}: (dtype, row_shape)}} to "
                f"warm_up")
        specs[vi.name] = (np.dtype(vi.numpy_dtype), tuple(row_shape))
    return specs


def warm_up_jitted(jitted, params, specs: Dict[str, Tuple[np.dtype, tuple]],
                   batch_sizes: Sequence[int], shards: int = 1,
                   put: Optional[Callable] = None,
                   counters: Optional[StageCounters] = None,
                   buckets: Optional[Sequence[int]] = None,
                   prog: Optional[str] = None) -> dict:
    """Compile (and prime the caches for) every padding-bucket shape.

    For each requested batch size the *padded* feed size is derived exactly
    as the runner derives it (``bucket_size`` over the active ladder, then
    rounded up to a multiple of ``shards``), zero-filled feeds are placed
    with ``put`` and run through ``jitted`` once, blocking on the result.
    That single throwaway execution is what populates jax's in-process jit
    cache — a bare ``lower().compile()`` produces an executable but leaves
    the cache cold, so the first real batch would still pay tracing +
    compile. With the persistent cache on (:func:`enable_persistent_cache`,
    or ``JAX_COMPILATION_CACHE_DIR``) the compile also lands on disk for the
    next process.

    ``buckets`` is the runner's padding ladder (``None`` = power-of-two):
    warm-up derives each padded size through the *same* ladder, so it
    compiles exactly the shapes the runner can produce — a caller on a
    custom ladder no longer pays for power-of-two buckets its batches can
    never land in.

    ``prog`` names the program for the collective auditor
    (``parallel.collective_audit``): with the audit enabled, every
    warmed bucket's compiled HLO is walked for collectives right after
    its warm-up call (which has just primed jax's compilation cache, so
    the extra ``lower().compile()`` is a lookup, not a second compile).

    Returns ``{"buckets": [padded sizes], "compiles": n, "seconds": s}``.
    ``compiles`` is ``None`` when the jit cache is not introspectable.
    """
    import jax

    # lazy: ops must stay importable without pulling the parallel package
    from ..parallel import collective_audit as _collective_audit

    if put is None:
        put = jax.device_put
    ladder = None if not buckets else tuple(sorted({int(b)
                                                    for b in buckets}))
    buckets = sorted({-(-bucket_size(int(b), ladder) // max(1, shards))
                      * max(1, shards) for b in batch_sizes if int(b) > 0})
    before = jit_cache_size(jitted)
    t_start = time.perf_counter()
    with _tracing.start_span("compile_cache.warm_up", buckets=len(buckets)), \
            _watch("compile_warmup") as _w:
        for size in buckets:
            t_b = time.perf_counter()
            feeds = {name: put(np.zeros((size,) + shape, dtype=dt))
                     for name, (dt, shape) in specs.items()}
            outs = jitted(params, feeds)
            # tpulint: disable=TPU001 — warm-up MUST fence each bucket so
            # the timed window covers the compile, not later steady-state
            # batches
            jax.block_until_ready(outs)
            if prog is not None and _collective_audit.enabled():
                _collective_audit.get_auditor().record_lowered(
                    prog, jitted, params, feeds)
            # heartbeat per bucket: the stall budget covers ONE compile,
            # not the whole ladder
            _w.beat()
            _tracing.add_event("warm_bucket", padded=size,
                               seconds=round(time.perf_counter() - t_b, 4))
    elapsed = time.perf_counter() - t_start
    after = jit_cache_size(jitted)
    compiles = (after - before) if (after is not None and before is not None) \
        else None
    if counters is not None and buckets:
        counters.add("compile", elapsed, count=compiles or len(buckets))
    if buckets:
        M_WARMUP_BUCKETS.inc(len(buckets))
        M_WARMUP_SECONDS.inc(elapsed)
    return {"buckets": buckets, "compiles": compiles,
            "seconds": round(elapsed, 4)}


def warm_up_model(model, jitted, specs, batch_sizes,
                  background: bool = False,
                  buckets: Optional[Sequence[int]] = None):
    """Warm every placement a model's traffic can hit (shared by
    ``ONNXModel.warm_up`` / ``JaxModel.warm_up``).

    With round-robin chip pinning the jit cache keys on the committed
    device, so every local chip gets its own warm pass; with a default mesh
    (or unpinned default placement) one pass suffices. ``model`` supplies
    ``_placement_params(pidx)``, ``mesh_sharded``/``pin_devices`` and its
    ``stage_counters``. ``background=True`` runs on a daemon thread and
    returns it; otherwise returns aggregated
    ``{"buckets", "compiles", "seconds", "placements"}``.
    """
    from ..parallel.mesh import get_default_mesh, local_devices

    def _warm():
        n_placements = 1
        if not (model.get("mesh_sharded") and get_default_mesh()
                is not None) and model.pin_devices:
            n_placements = max(1, len(local_devices()))
        stats = {"buckets": [], "compiles": 0, "seconds": 0.0,
                 "placements": 0}
        seen = set()
        for pidx in range(n_placements):
            placement, params = model._placement_params(pidx)
            if placement.key in seen:
                continue
            seen.add(placement.key)
            s = warm_up_jitted(jitted, params, specs, batch_sizes,
                               shards=placement.shards, put=placement.put,
                               counters=model.stage_counters,
                               buckets=buckets)
            stats["buckets"] = sorted(set(stats["buckets"])
                                      | set(s["buckets"]))
            if s["compiles"] is None:
                stats["compiles"] = None
            elif stats["compiles"] is not None:
                stats["compiles"] += s["compiles"]
            stats["seconds"] = round(stats["seconds"] + s["seconds"], 4)
            stats["placements"] += 1
        return stats

    if background:
        # tpulint: disable=TPU025 — run-once background warm-up over a
        # finite placement list, not a service loop; a crash leaves the
        # cache cold (first real request compiles) and must not restart
        t = threading.Thread(target=_warm, daemon=True,
                             name=f"warmup-{model.uid}")
        t.start()
        return t
    return _warm()
