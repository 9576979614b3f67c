"""BatchRunner — the shared device feed/drain pipeline of the graph runners.

``ONNXModel`` and ``JaxModel`` used to each carry their own copy of the
partition loop, and both copies had the same three stalls: the first batch of
every padding bucket paid a full XLA compile inline, outputs drained at
partition end through serialized per-batch per-column ``np.asarray`` host
copies, and all coerce/pad host work ran on the dispatch thread. This module
is the one implementation both models now share, with the stalls engineered
out:

* **prefetch** — coerce/pad of batch k+1 runs on a background worker
  (:class:`~mmlspark_tpu.stages.batching.PrefetchIterator`, the
  ``DynamicBufferedBatcher`` producer machinery), bounded by
  ``prefetch_depth`` prepared batches of host memory;
* **async feed** — host→device transfers enqueue immediately at dispatch
  time, overlapping the previous batch's compute;
* **overlapped drain** — ``copy_to_host_async()`` is issued per output the
  moment a batch is dispatched, so device→host transfers overlap compute.
  The model stages take the batches in order as each one's fetch lands
  (:meth:`BatchRunner.drain_each`) and write its rows ONCE, to their place
  in the frame's one array a column (:class:`FrameOutputs`, :func:`collect`),
  under the device's work on the batches behind it; ``drain`` is the one
  batched ``jax.device_get`` over every pending output, for a caller that
  wants them all.

Every stage is instrumented through :class:`~mmlspark_tpu.ops.compile_cache.
StageCounters` (coerce / pad / h2d / compile / dispatch / d2h), cheap enough
to stay on in production and surfaced by ``bench.py``.
"""

from __future__ import annotations

import threading

from ..reliability.lock_sanitizer import new_lock
import time
from typing import (Callable, Dict, Iterable, Iterator, List, Optional,
                    Sequence, Tuple)

import jax
import numpy as np

from ..core.residency import is_device_array, record_hit
from ..observability import charge as _ledger_charge
from ..observability import counter as _metric_counter
from ..observability import tracing as _tracing
from ..observability import watch as _watch
from ..ops.compile_cache import (M_CACHE_HITS, M_CACHE_MISSES,
                                 M_STEADY_RECOMPILES, StageCounters,
                                 jit_cache_size)
from ..ops.padding import bucket_size, pad_axis, pad_axis_device
from ..stages.batching import PrefetchIterator, batch_slices

__all__ = ["BatchRunner", "FrameOutputs", "StagingSlabPool", "collect"]

_BFLOAT16 = np.dtype(jax.numpy.bfloat16)

M_SLAB_ALLOCS = _metric_counter(
    "mmlspark_staging_slab_allocs_total",
    "host staging slabs allocated (first touch of a shape/dtype signature)")
M_SLAB_REUSE = _metric_counter(
    "mmlspark_staging_slab_reuse_total",
    "host staging slab acquisitions served from the pool")


class StagingSlabPool:
    """Reusable host staging buffers for the coerce/pad prefetch worker.

    Padding into a small circulating set of pre-touched slabs (instead of a
    fresh ``np.pad`` allocation per batch) is the host-side half of h2d
    overlap: the buffers are stable, faulted-in pages — the closest thing to
    pinned memory the numpy layer can express — so the async ``device_put``
    streams from warm memory while the next batch is being prepared. At most
    ``depth`` free slabs per (shape, dtype) signature are retained
    (double-buffered by default: one being transferred, one being filled);
    shape bucketing keeps the signature set tiny, so steady state allocates
    nothing.
    """

    def __init__(self, depth: int = 2):
        self.depth = max(1, int(depth))
        self._lock = new_lock("models.runner.StagingSlabPool._lock")
        self._free: Dict[tuple, List[np.ndarray]] = {}
        self._issued: set = set()
        self.allocs = 0
        self.reuses = 0

    def acquire(self, shape, dtype) -> np.ndarray:
        key = (tuple(shape), np.dtype(dtype).str)
        with self._lock:
            free = self._free.get(key)
            if free:
                buf = free.pop()
                self.reuses += 1
                M_SLAB_REUSE.inc()
            else:
                buf = np.empty(key[0], dtype=dtype)
                self.allocs += 1
                M_SLAB_ALLOCS.inc()
            self._issued.add(id(buf))
        return buf

    def release(self, arr) -> bool:
        """Return a slab to the pool; silently ignores foreign arrays, so
        callers can release every feed they dispatched."""
        if not isinstance(arr, np.ndarray):
            return False
        with self._lock:
            if id(arr) not in self._issued:
                return False
            self._issued.discard(id(arr))
            free = self._free.setdefault((arr.shape, arr.dtype.str), [])
            if len(free) < self.depth:
                free.append(arr)
            return True

    def stats(self) -> Dict[str, float]:
        with self._lock:
            total = self.allocs + self.reuses
            return {"allocs": self.allocs, "reuses": self.reuses,
                    "reuse_rate": (self.reuses / total) if total else None}


class FrameOutputs:
    """One host array a column for a whole frame's pass.

    The partitions of one ``transform`` share it: each writes its batches'
    rows to its own row range as they come off the device, and returns the
    view of that range. The views of consecutive partitions are adjacent
    slices of one buffer, which ``concat`` recognises and joins without a
    copy, so an output row is copied on the host once. A column's array is
    allocated when its first batch tells the row shape (``np.empty``: a page
    costs nothing until the partition that owns it writes it). Rows come out
    in ``dtypes[name]`` where the stage names one, bfloat16 widened to
    float32, anything else as the device returned it.
    """

    def __init__(self, bounds: Sequence[Tuple[int, int]],
                 dtypes: Optional[Dict[str, type]] = None):
        #: ``DataFrame.partition_bounds()``: partition ``i`` owns rows
        #: ``bounds[i][0]`` up to ``bounds[i][1]``
        self.bounds = list(bounds)
        self.nrows = self.bounds[-1][1] if self.bounds else 0
        self._dtypes = dict(dtypes or {})
        self._lock = new_lock("models.runner.FrameOutputs._lock")
        self._columns: Dict[str, np.ndarray] = {}

    def _column(self, name: str, chunk: np.ndarray) -> np.ndarray:
        buf = self._columns.get(name)
        if buf is None:
            with self._lock:
                buf = self._columns.get(name)
                if buf is None:
                    dtype = self._dtypes.get(name) or (
                        np.float32 if chunk.dtype == _BFLOAT16
                        else chunk.dtype)
                    buf = self._columns[name] = np.empty(
                        (self.nrows,) + chunk.shape[1:], dtype)
        if buf.shape[1:] != chunk.shape[1:]:
            raise ValueError(
                f"output {name!r}: a batch of row shape {chunk.shape[1:]} "
                f"after one of {buf.shape[1:]}")
        return buf

    def write(self, name: str, at: int, chunk: np.ndarray) -> None:
        """``chunk``'s rows to rows ``at`` onward: the cast and the copy in
        one pass over the bytes."""
        buf = self._column(name, chunk)
        np.copyto(buf[at:at + len(chunk)], chunk, casting="unsafe")

    def rows(self, names: Iterable[str], lo: int, hi: int
             ) -> Dict[str, np.ndarray]:
        return {name: self._columns[name][lo:hi] for name in names}


def collect(batches: Iterable[Tuple[Dict[str, np.ndarray], int]],
            outputs: FrameOutputs, pidx: int,
            names: Optional[Sequence[str]] = None) -> Dict[str, np.ndarray]:
    """Partition ``pidx``'s drained ``batches`` (``[(host outputs, valid
    rows)]``) into its rows of ``outputs``, the padding cut off; returns the
    partition's view of each column (``names``, or every output; none for
    a partition without rows)."""
    at = lo = outputs.bounds[pidx][0]
    for outs, b in batches:
        if names is None:
            names = list(outs)
        for name in names:
            outputs.write(name, at, outs[name][:b])
        at += b
    return outputs.rows(names, lo, at) if at > lo else {}


class BatchRunner:
    """Run one partition's rows through a jitted program in padded batches.

    ``coerce(sl) -> {feed name: host ndarray}`` is the model-specific part
    (column lookup, dtype coercion, reshape); everything downstream —
    padding, placement, dispatch, drain, instrumentation — is shared.
    """

    def __init__(self, jitted, params,
                 coerce: Callable[[slice], Dict[str, np.ndarray]],
                 put: Callable, shards: int = 1, mini_batch_size: int = 64,
                 prefetch_depth: int = 2,
                 counters: Optional[StageCounters] = None,
                 staging: Optional[StagingSlabPool] = None,
                 buckets: Optional[Tuple[int, ...]] = None,
                 tuning: str = "", model_sig: Optional[str] = None,
                 placement_key: str = "default"):
        self.jitted = jitted
        self.params = params
        self.coerce = coerce
        self.put = put
        self.shards = max(1, int(shards))
        self.mini_batch_size = max(1, int(mini_batch_size))
        self.prefetch_depth = max(0, int(prefetch_depth))
        self.counters = counters if counters is not None else StageCounters()
        # model-owned so slabs amortize across transform calls, not just
        # batches of one partition
        self.staging = staging
        # custom padding-bucket ladder (None = power-of-two default); the
        # ladder must cover the largest batch the runner can produce
        self.buckets = (None if not buckets
                        else tuple(sorted({int(b) for b in buckets})))
        if self.buckets and self.mini_batch_size > self.buckets[-1]:
            raise ValueError(
                f"mini_batch_size={self.mini_batch_size} exceeds the "
                f"largest bucket {self.buckets[-1]} of the ladder")
        if tuning not in ("", "auto"):
            raise ValueError(f"tuning must be '' or 'auto', got {tuning!r}")
        self.tuning = tuning
        self.model_sig = model_sig
        self.placement_key = str(placement_key)
        self._tuned = False           # "auto" resolved the store already
        self.decision = None          # the applied TuningDecision, if any
        self._samples: Dict[int, Dict[str, float]] = {}

    # -- tuning: consult the observation store, harvest samples back ---------
    def _resolve_auto(self, n_rows: int) -> None:
        """``tuning="auto"``: on first run, fit the observation store for
        this model signature and apply the picked config. A cold store is
        not an error — the defaults stand and this run's harvest becomes
        the training data a later process decides from."""
        self._tuned = True
        from ..tuning.cost_model import resolve_tuning
        decision = resolve_tuning(
            self.model_sig or "anonymous", self.placement_key,
            {int(n_rows): 1},
            defaults=(self.mini_batch_size, self.prefetch_depth))
        if decision is None:
            return
        self.decision = decision
        self.mini_batch_size = max(1, decision.mini_batch_size)
        self.prefetch_depth = max(0, decision.prefetch_depth)
        self.buckets = decision.buckets

    def _note_sample(self, padded: int, b: int, *, seconds: float = 0.0,
                     prep_seconds: float = 0.0, compile_seconds: float = 0.0,
                     compiles: int = 0, batches: int = 0) -> None:
        s = self._samples.setdefault(
            int(padded), {"rows": 0, "batches": 0, "seconds": 0.0,
                          "prep_seconds": 0.0, "compile_seconds": 0.0,
                          "compiles": 0})
        s["rows"] += int(b)
        s["batches"] += int(batches)
        s["seconds"] += float(seconds)
        s["prep_seconds"] += float(prep_seconds)
        s["compile_seconds"] += float(compile_seconds)
        s["compiles"] += int(compiles)

    def _flush_samples(self) -> None:
        """Emit the accumulated per-bucket samples as observations (called
        at drain time — the ``harvests at drain`` contract)."""
        if not self._samples or self.model_sig is None:
            self._samples.clear()
            return
        from ..tuning.observations import harvest_samples
        samples = [dict(bucket=k, **v)
                   for k, v in sorted(self._samples.items())]
        self._samples.clear()
        harvest_samples(
            self.model_sig, self.placement_key,
            {"mini_batch_size": self.mini_batch_size,
             "prefetch_depth": self.prefetch_depth,
             "buckets": None if self.buckets is None else list(self.buckets)},
            samples)

    # -- host side: coerce + pad (runs on the prefetch worker) ---------------
    def _prepare(self, sl: slice
                 ) -> Tuple[Dict[str, np.ndarray], int, int, float]:
        c = self.counters
        t_prep = time.perf_counter()
        with c.timer("coerce", span="runner.coerce"):
            feeds = self.coerce(sl)
        b = 0
        with c.timer("pad", span="runner.pad"):
            padded_feeds = {}
            padded = 0
            for name, arr in feeds.items():
                b = len(arr)
                padded = bucket_size(b, self.buckets)
                padded = -(-padded // self.shards) * self.shards
                if is_device_array(arr):
                    # device feed (resident column slice): pad on device,
                    # nothing crosses the bus
                    padded_feeds[name] = pad_axis_device(arr, padded)
                elif self.staging is not None:
                    buf = self.staging.acquire((padded,) + arr.shape[1:],
                                               arr.dtype)
                    buf[:b] = arr
                    if padded > b:
                        buf[b:] = 0
                    padded_feeds[name] = buf
                else:
                    padded_feeds[name] = pad_axis(arr, padded)
            _tracing.add_event("pad_bucket", rows=b, padded=padded)
        return padded_feeds, b, padded, time.perf_counter() - t_prep

    def _prepared_batches(self, n_rows: int):
        slices = batch_slices(n_rows, self.mini_batch_size)
        if self.prefetch_depth > 0 and len(slices) > 1:
            # batch k+1's coerce/pad overlaps batch k's h2d + dispatch; the
            # depth bound caps host memory at that many prepared batches.
            # The worker thread starts with an empty context — propagate()
            # carries the active trace across, so coerce/pad spans land in
            # the request's trace
            prepare = _tracing.propagate(self._prepare)
            return PrefetchIterator((prepare(sl) for sl in slices),
                                    depth=self.prefetch_depth)
        return (self._prepare(sl) for sl in slices)

    # -- device side: feed, dispatch, overlapped drain -----------------------
    def run(self, n_rows: int) -> List[Tuple[dict, int]]:
        """Dispatch every minibatch; returns [(device outputs, valid rows)].

        JAX dispatch returns futures, so the loop never blocks on compute;
        each batch's outputs start their device→host copy immediately
        (``copy_to_host_async``) instead of at partition end.
        """
        c = self.counters
        if self.tuning == "auto" and not self._tuned:
            self._resolve_auto(n_rows)
        pending: List[Tuple[dict, int]] = []
        with _tracing.span("runner.run", rows=n_rows):
            batches = self._prepared_batches(n_rows)
            # prefetch_wait: time the dispatch thread blocks on the coerce/
            # pad worker — zero when host prep fully overlaps device work;
            # bench derives its h2d-overlap fraction from this vs coerce+pad
            prefetching = isinstance(batches, PrefetchIterator)
            it = iter(batches)
            while True:
                # the span is around every next(); the counter counts the
                # waits that brought a batch (not the exhausted one), and
                # only a worker's: inline, the wait IS coerce + pad, which
                # have their own counters
                with _tracing.span("runner.next"):
                    t0 = time.perf_counter()
                    prepared = next(it, None)
                    waited = time.perf_counter() - t0
                if prepared is None:
                    break
                if prefetching:
                    c.add("prefetch_wait", waited)
                feeds_host, b, padded, prep_s = prepared
                device_fed = [k for k, v in feeds_host.items()
                              if is_device_array(v)]
                if device_fed:
                    record_hit(len(device_fed))
                nbytes = sum(a.nbytes for k, a in feeds_host.items()
                             if k not in device_fed)
                # cost attribution: bill this batch's padding waste and
                # feed bytes to the ambient trace's workload class
                _ledger_charge("padding_waste_rows", padded - b)
                _ledger_charge("h2d_bytes", nbytes)
                with c.timer("h2d", nbytes, span="runner.h2d", bytes=nbytes,
                             resident=len(device_fed)):
                    # put() is placement-aware; for an already-resident feed
                    # it is a same-device no-op (or an on-chip move), never
                    # a host round-trip
                    feeds = {k: self.put(v) for k, v in feeds_host.items()}
                before = jit_cache_size(self.jitted)
                with _tracing.span("runner.dispatch", padded=padded) as sp:
                    t0 = time.perf_counter()
                    outs = self.jitted(self.params, feeds)
                    elapsed = time.perf_counter() - t0
                after = jit_cache_size(self.jitted)
                compiled = (after - before if before is not None
                            and after is not None else 0)
                if sp is not None:
                    sp.set(compiled=compiled)
                if compiled > 0:
                    # the dispatch call blocked on trace+compile — a bucket
                    # the warm-up vocabulary missed; attribute the stall
                    # honestly
                    c.add("compile", elapsed, count=compiled)
                    _ledger_charge("compile_seconds", elapsed)
                    M_CACHE_MISSES.inc(compiled)
                    M_STEADY_RECOMPILES.inc(compiled)
                    _tracing.add_event("cache_miss", compiles=compiled,
                                       seconds=elapsed)
                    self._note_sample(padded, b, batches=1,
                                      prep_seconds=prep_s,
                                      compile_seconds=elapsed,
                                      compiles=compiled)
                else:
                    c.add("dispatch", elapsed)
                    _ledger_charge("device_seconds", elapsed)
                    M_CACHE_HITS.inc()
                    self._note_sample(padded, b, batches=1, seconds=elapsed,
                                      prep_seconds=prep_s)
                if self.staging is not None:
                    # a slab may only circulate once its async h2d has
                    # finished reading it: block on the *input* transfers
                    # (not the compute) before returning buffers to the pool
                    for k, v in feeds.items():
                        if k not in device_fed:
                            try:
                                # tpulint: disable=TPU001 — waits for the
                                # INPUT transfer (not compute): the slab is
                                # immutable-until-transfer-completes and may
                                # only recirculate after the copy lands
                                v.block_until_ready()
                            except Exception:
                                pass
                    for v in feeds_host.values():
                        self.staging.release(v)
                for v in outs.values():
                    try:
                        v.copy_to_host_async()
                    except Exception:
                        break  # backend without async copy; drain still works
                pending.append((outs, b))
        return pending

    def drain(self, pending: List[Tuple[dict, int]]
              ) -> List[Tuple[Dict[str, np.ndarray], int]]:
        """One batched device→host fetch over every pending output; flushes
        the per-bucket tuning samples accumulated since the last drain."""
        if not pending:
            self._flush_samples()
            return []
        t0 = time.perf_counter()
        # device_get is where a wedged device parks the dispatcher forever
        # — the watchdog turns that silent hang into a diagnostic bundle
        with _tracing.span("runner.d2h", batches=len(pending)), \
                _watch("runner_drain"):
            host = jax.device_get([outs for outs, _ in pending])
        self._drained(time.perf_counter() - t0,
                      sum(a.nbytes for outs in host for a in outs.values()))
        return [(outs, b) for outs, (_, b) in zip(host, pending)]

    def drain_each(self, pending: List[Tuple[dict, int]]
                   ) -> Iterator[Tuple[Dict[str, np.ndarray], int]]:
        """``drain`` a batch at a time, in order: each batch's host outputs
        as soon as ITS fetch (started at dispatch) has landed, so what the
        caller does with batch k runs under the device's work on the batches
        behind it and only the last batch's remains when the device is
        done. Counted as ``drain`` is: one ``d2h`` a partition, the seconds
        this thread waited for the device."""
        waited, nbytes = 0.0, 0
        try:
            for outs, b in pending:
                t0 = time.perf_counter()
                with _tracing.span("runner.d2h", batches=1), \
                        _watch("runner_drain"):
                    # tpulint: disable=TPU001 — the point of the loop: every
                    # batch is dispatched already and its copy under way
                    # (copy_to_host_async), so a fetch waits for ITS batch
                    # alone and the host's work on it runs under the device's
                    # on the batches behind it
                    host = jax.device_get(outs)
                waited += time.perf_counter() - t0
                nbytes += sum(a.nbytes for a in host.values())
                yield host, b
        finally:
            if pending:
                self._drained(waited, nbytes)
            else:
                self._flush_samples()

    def _drained(self, elapsed: float, nbytes: int) -> None:
        self.counters.add("d2h", elapsed, nbytes)
        # async dispatch settles inside device_get, so the drain wall time
        # IS device time — ledger device_seconds reconciles with the
        # dispatch+d2h stage counters by construction
        _ledger_charge("device_seconds", elapsed)
        _ledger_charge("d2h_bytes", nbytes)
        # async dispatch means compute largely settles inside device_get:
        # attribute the drain across buckets by row share so the per-bucket
        # fit sees the true device cost, not just the enqueue time
        total_rows = sum(s["rows"] for s in self._samples.values()) or 1
        for s in self._samples.values():
            s["seconds"] += elapsed * (s["rows"] / total_rows)
        self._flush_samples()

    def run_and_drain(self, n_rows: int
                      ) -> List[Tuple[Dict[str, np.ndarray], int]]:
        return self.drain(self.run(n_rows))
