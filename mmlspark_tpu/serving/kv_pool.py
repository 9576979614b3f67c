"""Paged KV pool — vLLM-style page-granular cache management for serving.

`serving/continuous.py` historically gave every slot a contiguous
``(H, max_len, hd)`` cache region: simple, but each slot pins worst-case
memory, prefix reuse needs a device copy into the slot, and a retiring
short request strands the tail of its region. This module supplies the
PagedAttention answer (PAPERS.md: vLLM) at the allocator level:

* **pages** — the physical cache is one ``(num_pages, H, page_size, 2*hd)``
  buffer per layer, K beside V on the minor axis
  (`models/zoo/transformer.init_paged_cache`; why that layout:
  `ops/paged_attention.py`); requests are sized in pages for the tokens
  they can actually produce, not ``max_len``;
* **block tables** — each slot owns a row of physical page ids; attention
  gathers through it (`decode_step_paged` / `decode_window_paged`) and the
  result is bitwise-equal to the contiguous path;
* **copy-on-write prefix sharing** — whole pages of a cached prompt prefix
  are shared across requests by bumping a refcount; only the boundary page
  (which the new request will write into) is copied. Shared pages are
  never written: the first writable position of a joining request always
  lands at or past the copy boundary;
* **defrag on retire** — frees go back to a min-heap (lowest index first,
  keeping the live span dense); when the live span still drifts past the
  in-use count by `defrag threshold` pages, :meth:`compact` returns a
  permutation the engine applies with one device gather;
* **residency budgeting** — the pool's device bytes are pinned against the
  `ResidencyManager` budget (PR 6) via a fixed reservation, so KV pressure
  evicts LRU *data* columns instead of silently overcommitting HBM.

Physical page 0 is the **trash page**: never allocated, the redirect
target for inactive-row writebacks and for block-table entries past a
row's allocation. Its contents are garbage by design and never read
(attention masks trim reads to each row's true length).

The pool is host-side bookkeeping plus a handle to the device buffers;
all methods assume the caller (the engine) serializes access under its
own lock — there is no internal locking.
"""

from __future__ import annotations

import hashlib
import heapq
import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp

from ..core.residency import get_residency_manager
from ..models.zoo.hybrid import SLOT_KEYS, pool_shapes
from ..observability import (charge as _ledger_charge,
                             counter as _metric_counter,
                             gauge as _metric_gauge)
from ..parallel.moe import MOE_STATS

__all__ = ["PagedKVPool", "PoolExhausted", "KVAutotuner", "prefix_hash",
           "AFFINITY_HEADER", "affinity_headers"]

M_PAGES_TOTAL = _metric_gauge(
    "mmlspark_kvpool_pages_total",
    "Physical KV pages in the pool (excluding the trash page)")
M_PAGES_IN_USE = _metric_gauge(
    "mmlspark_kvpool_pages_in_use",
    "KV pages currently referenced by a slot or a cached prefix")
M_PAGE_SIZE = _metric_gauge(
    "mmlspark_kvpool_page_size",
    "Tokens a KV page holds: the keys one grid step of the paged decode "
    "kernel folds (derived from max_len unless the engine was given one)")
M_PAGES_PER_SLOT = _metric_gauge(
    "mmlspark_kvpool_pages_per_slot",
    "Width of a slot's block table: pages a slot spans at full length, the "
    "most grid steps the decode kernel takes for a row")
M_GRID_STEPS_SHARE = _metric_gauge(
    "mmlspark_kvpool_grid_steps_share",
    "Grid steps the paged decode kernel's calls have swept (the pages their "
    "rows needed, by the scheduler's positions) over slots x pages a slot, "
    "since the pool was built")
M_PREFIX_SHARE_HITS = _metric_counter(
    "mmlspark_kvpool_prefix_share_hits_total",
    "Physical pages shared into an admitted request from a cached prefix "
    "(each shared page counts once per acquiring request)")
M_DEFRAG_MOVES = _metric_counter(
    "mmlspark_kvpool_defrag_moves_total",
    "Live pages relocated by compaction gathers")
M_PREFILL_CHUNKS = _metric_counter(
    "mmlspark_kvpool_prefill_chunks_total",
    "Prefill chunks executed by the chunked-prefill scheduler")
M_PREFILL_TOKENS = _metric_counter(
    "mmlspark_kvpool_prefill_tokens_total",
    "Prompt tokens the chunked-prefill scheduler's windows computed")
M_PREFILL_CHUNKS_RIDING_SHARE = _metric_gauge(
    "mmlspark_kvpool_prefill_chunks_riding_share",
    "Prefill chunks whose window rode a decode tick (one dispatch, one read "
    "of the feed-forward weights for both) over the prefill chunks executed, "
    "since the pool was built")
M_LATENT_WINDOW_KEYS = _metric_gauge(
    "mmlspark_kvpool_latent_window_keys",
    "Keys the prefill windows of a model with latent-attention layers have "
    "attended over, in whole tiles of the window's fold (a window rebuilds K "
    "and V of its row's context a tile at a time, up to its last key), since "
    "the pool was built")
M_LATENT_SWEEP_PAGES = _metric_gauge(
    "mmlspark_kvpool_latent_sweep_pages",
    "Pages the decode ticks' absorbed latent kernel had to fold (the pages "
    "that hold a key of a live row, every mla layer's call), since the pool "
    "was built")
M_LATENT_SWEEP_STEPS = _metric_gauge(
    "mmlspark_kvpool_latent_sweep_steps",
    "Grid steps those calls swept (a step folds a block of a row's pages; a "
    "row with nothing to read takes one), since the pool was built: pages "
    "over steps is how full the blocks ran")
M_SELECT_WALK_PAGES = _metric_gauge(
    "mmlspark_kvpool_select_walk_pages",
    "Pages the decode ticks' selected-block kernel had to fold (the pages "
    "of the blocks listed for a live row's KV heads, every sparse layer's "
    "call), since the pool was built")
M_SELECT_WALK_STEPS = _metric_gauge(
    "mmlspark_kvpool_select_walk_steps",
    "Grid steps those calls walked (a step folds a block of a (row, KV "
    "head)'s listed pages; an idle row walks its empty list), since the pool "
    "was built: pages over steps is how full the blocks ran")
M_SSM_STATE_ROWS = _metric_gauge(
    "mmlspark_kvpool_ssm_state_rows",
    "States the decode ticks' state-space step had to read and write (live "
    "rows of a call, every ssm layer's), since the pool was built")
M_PREFIX_TOKENS_SHARED = _metric_gauge(
    "mmlspark_kvpool_prefix_tokens_shared",
    "Tokens of stored prefix pages admitted requests took by reference "
    "(whole shared pages; a boundary page is copied, not shared), since the "
    "pool was built")
M_ALLOC_FAILURES = _metric_counter(
    "mmlspark_kvpool_alloc_failures_total",
    "Page allocations that failed even after prefix eviction")
M_AUTOTUNE_GAMMA = _metric_gauge(
    "mmlspark_kvpool_autotune_gamma",
    "Current speculative draft length chosen by the KV autotuner")
M_AUTOTUNE_CHUNK = _metric_gauge(
    "mmlspark_kvpool_autotune_chunk_budget",
    "Current prefill chunk budget (tokens) chosen by the KV autotuner")
M_AUTOTUNE_DEPTH = _metric_gauge(
    "mmlspark_kvpool_autotune_pipeline_depth",
    "Current decode pipeline depth (in-flight steps) chosen by the KV "
    "autotuner")
M_GATHER_BYTES = _metric_counter(
    "mmlspark_kvpool_gather_bytes_total",
    "HBM bytes moved by gather-impl paged attention materializing "
    "contiguous K/V before attending (0 under the Pallas kernel, which "
    "reads pages in place)")
M_KERNEL_TICKS = _metric_counter(
    "mmlspark_kvpool_kernel_ticks_total",
    "Paged-attention decode calls dispatched, by implementation (kernel | "
    "gather) and, for a model with sparse-attention layers, by whether the "
    "call selected blocks (sparse) or every row was still under dense_len "
    "(dense)",
    labelnames=("impl",))
M_MOE = _metric_counter(
    "mmlspark_kvpool_moe_total",
    "A routed feed-forward's decode ticks, by count: pairs_routed ((token, "
    "expert) pairs the live rows routed, over all experts and routed layers), "
    "pairs_held (those on the experts this process holds), pairs_dropped "
    "(held pairs given no row: always 0, routing is dropless), "
    "pairs_misplaced (pairs multiplied by another expert's weights: always "
    "0), experts_touched (distinct held experts with a pair, summed over layers "
    "and ticks), tiles (16-row tiles of pairs in use, likewise), product_steps "
    "(grid steps the experts' product ran, an expert's run of tiles a step: "
    "tiles over product_steps is how many tiles a product folds), "
    "expert_load_max (the largest expert's pairs in a tick, any layer, summed "
    "over ticks)",
    labelnames=("count",))
M_STATE_SNAPSHOTS = _metric_counter(
    "mmlspark_kvpool_state_snapshots_total",
    "Linear-attention state snapshots kept with a cached prefix, by event: "
    "stored (a prefix registered), restored (a hit copied one into its "
    "slot), evicted (the prefix was released)",
    labelnames=("event",))
M_STATE_SNAPSHOT_BYTES = _metric_counter(
    "mmlspark_kvpool_state_snapshot_bytes_total",
    "Device bytes of the state snapshots stored, restored and evicted",
    labelnames=("event",))


#: the gauge that follows a stat :meth:`PagedKVPool.note` adds to
_GAUGES = {"latent_window_keys": M_LATENT_WINDOW_KEYS,
           "latent_sweep_pages": M_LATENT_SWEEP_PAGES,
           "latent_sweep_steps": M_LATENT_SWEEP_STEPS,
           "select_walk_pages": M_SELECT_WALK_PAGES,
           "select_walk_steps": M_SELECT_WALK_STEPS,
           "ssm_state_rows": M_SSM_STATE_ROWS}
#: the stats that count paged calls by implementation or path
_TICKS = "attn_ticks_"


def prefix_hash(tokens: Sequence[int]) -> str:
    """Stable content hash for a prompt prefix (the prefix-registry key)."""
    h = hashlib.sha1()
    h.update(np.asarray(tokens, np.int64).tobytes())
    return h.hexdigest()


#: request header carrying a prefix-affinity key: clients stamp it with
#: :func:`prefix_hash` of their shared prompt prefix and the distributed
#: forwarder (serving/distributed.py) consistent-hashes it to the worker
#: whose pool already holds those pages
AFFINITY_HEADER = "X-Mmlspark-Prefix"


def affinity_headers(tokens: Sequence[int]) -> List[Tuple[str, str]]:
    """The routing header a session should attach so its requests land on
    the worker owning its shared-prefix pages — same hash the pool keys
    the prefix registry by, so routing affinity and page sharing agree."""
    return [(AFFINITY_HEADER, prefix_hash(tokens))]


class PoolExhausted(RuntimeError):
    """No free pages left — the engine sheds load or evicts prefixes."""


class PagedKVPool:
    """Page allocator + device buffer handle for one model's KV cache.

    ``buffers`` is the per-layer list of ``{"kv"}`` page arrays (plus
    ``{"k_scale","v_scale"}`` when quantized; for a hybrid decoder
    ``{"kv","ck"}``, ``{"state"}``, ``{"state","conv"}``, ``{"conv"}`` alone,
    or plain or latent ``{"kv"}`` pages, each in the shape ``models/zoo/hybrid.py`` ``pool_shapes``
    gives it) the
    engine threads through its jitted steps (reassigning after every
    dispatch, since XLA returns fresh buffers). Everything else is host
    bookkeeping: a free min-heap over pages ``[1, num_pages)``, per-page
    refcounts, and the shared-prefix registry.
    """

    def __init__(self, cfg, *, num_pages: int, page_size: int,
                 kv_dtype: Optional[str] = None, make_buffer=None,
                 residency: bool = True, sharding=None,
                 slots: int = 0, slot_positions: int = 0,
                 max_snapshots: int = 0):
        from ..ops.kv_quant import (SCALE_DTYPE, kv_store_dtype,
                                    resolve_kv_dtype)
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is reserved)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.cfg = cfg
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        #: a hybrid decoder (``cfg.mixers``) keeps two kinds of cache in
        #: this one manager: a sparse-attention layer's entry is pages plus
        #: a row of the scorer's compressed keys a SLOT, a linear-attention
        #: layer's is a float32 state row a slot, no pages; a cached prefix
        #: is then pages plus a snapshot of those rows (``register_prefix``)
        self.hybrid = bool(getattr(cfg, "mixers", ()))
        heads, hd = cfg.heads, cfg.d_model // cfg.heads
        self._layer_shapes = None
        if self.hybrid:
            if kv_dtype is not None or sharding is not None:
                raise ValueError("a hybrid decoder's pool is bf16 pages "
                                 "(K beside V of a sparse or gqa layer, or "
                                 "an mla layer's latent rows) on one device "
                                 "(no kv_dtype, no mesh)")
            self._layer_shapes = pool_shapes(
                cfg, self.num_pages, self.page_size, int(slots),
                int(slot_positions))
        #: device bytes of one prefix's state snapshot, and how many the
        #: engine's prefix store may hold (the reservation counts them)
        self.snapshot_bytes = sum(
            int(np.prod(shape[1:])) * jnp.dtype(dt).itemsize
            for layer in self._layer_shapes or ()
            for key, (shape, dt) in layer.items() if key in SLOT_KEYS)
        self.max_snapshots = int(max_snapshots)
        #: one K or V page as a session blob carries it, (H, page, hd);
        #: the pool's buffer packs the two side by side on the minor axis
        self._page_shape = (heads, self.page_size, hd)
        shape = (self.num_pages, heads, self.page_size, 2 * hd)
        #: canonical quantized-page dtype name ("int8"/"fp8") or None for
        #: bf16 pages (the byte-exact oracle representation)
        self.kv_dtype = resolve_kv_dtype(kv_dtype)
        store = kv_store_dtype(self.kv_dtype)
        #: the jnp dtype K/V VALUES are stored in — what page alignment,
        #: residency accounting and HBM byte math must all be sized to
        self.value_dtype = cfg.dtype if store is None else store
        self.scale_dtype = None if store is None else SCALE_DTYPE
        #: how the page arrays lay out on a mesh (None = single-device).
        #: Under tensor parallelism this is P(None, "tp", None, None) —
        #: heads shard, the page dimension stays a shared allocator arena,
        #: so alloc/free/block tables/CoW/compact() remain device-count-
        #: invariant host bookkeeping and defrag's permutation gathers
        #: per-shard with no resharding round-trip. Quantized pools keep
        #: (num_pages, heads, page_size) scale arrays on P(None, "tp",
        #: None) — scales shard with the heads they rescale.
        self.pool_sharding = sharding
        self._mk = make_buffer or (lambda s, d: jnp.zeros(s, d))
        self._shape = shape
        self._scale_shape = shape[:3]
        self.buffers = self._make_buffers()
        self._free: List[int] = list(range(1, self.num_pages))
        heapq.heapify(self._free)
        self._refs = np.zeros(self.num_pages, np.int32)
        # page -> monotonic time it left the free heap; feeds the cost
        # ledger's kv_page_seconds charge when the last ref drops
        self._alloc_t: Dict[int, float] = {}
        # phash -> (pages tuple, prefix length in tokens)
        self._prefixes: Dict[str, Tuple[Tuple[int, ...], int]] = {}
        # phash -> the prefix's state snapshot (a hybrid decoder): whatever
        # the engine stored with it, device arrays it restores from
        self._snapshots: Dict[str, object] = {}
        # phash -> registration count. Two engine keys whose prefixes are
        # token-identical hash to the same entry; the entry (and its page
        # refs) must survive until EVERY registering key has released it.
        self._prefix_regs: Dict[str, int] = {}
        self.high_water = 0
        #: the counters the engine and the tests read; the geometry the
        #: process serves stands beside them, set once
        self.stats = {"page_size": self.page_size,
                      "pages_per_slot": self.pages_per_slot(slot_positions),
                      "prefix_share_hits": 0, "defrag_moves": 0,
                      "prefill_chunks": 0, "prefill_chunks_riding": 0,
                      "prefill_tokens": 0, "prefix_tokens_shared": 0,
                      "latent_window_keys": 0, "latent_window_context": 0,
                      "latent_window_pairs": 0,
                      "latent_sweep_pages": 0, "latent_sweep_steps": 0,
                      "select_walk_pages": 0, "select_walk_steps": 0,
                      "ssm_state_rows": 0, "alloc_failures": 0,
                      "gather_bytes": 0, "attn_ticks_kernel": 0,
                      "attn_ticks_gather": 0, "grid_steps": 0,
                      "grid_steps_dense": 0, "quant_error_probes": 0,
                      "quant_error_last": None, "quant_error_sum": 0.0,
                      "quant_error_max": 0.0}
        M_PAGES_TOTAL.set(self.num_pages - 1)
        M_PAGES_IN_USE.set(0)
        M_PAGE_SIZE.set(self.stats["page_size"])
        M_PAGES_PER_SLOT.set(self.stats["pages_per_slot"])
        self._reservation = None
        if residency:
            mgr = get_residency_manager()
            token = mgr.reserve(self.device_bytes(), label="kv_pool")
            self._reservation = token
            self._finalizer = weakref.finalize(self, mgr.release, token)

    def _make_buffers(self):
        """Fresh per-layer page buffers through ``make_buffer`` (so mesh
        shardings apply): ``{"kv"}`` in the value dtype, plus
        ``{"k_scale","v_scale"}`` when quantized."""
        if self._layer_shapes is not None:
            return [{key: self._mk(shape, dt)
                     for key, (shape, dt) in layer.items()}
                    for layer in self._layer_shapes]
        layers = []
        for _ in range(self.cfg.layers):
            c = {"kv": self._mk(self._shape, self.value_dtype)}
            if self.scale_dtype is not None:
                c["k_scale"] = self._mk(self._scale_shape, self.scale_dtype)
                c["v_scale"] = self._mk(self._scale_shape, self.scale_dtype)
            layers.append(c)
        return layers

    def device_bytes(self) -> int:
        """Exact device bytes of the pool's buffers — K+V values in the
        (possibly quantized) value dtype plus the scale arrays. This is
        what :func:`~mmlspark_tpu.core.residency.get_residency_manager`'s
        ``reserve()`` pins, so the budget sees the QUANTIZED itemsize: a
        fixed byte budget holds ~2x the pages under int8. A hybrid
        decoder's pool counts its pages, its compressed keys, its state
        rows and the snapshots its prefix store may hold."""
        if self._layer_shapes is not None:
            return (self.max_snapshots * self.snapshot_bytes + sum(
                int(np.prod(shape)) * jnp.dtype(dt).itemsize
                for layer in self._layer_shapes
                for shape, dt in layer.values()))
        nbytes = (self.cfg.layers * int(np.prod(self._shape)) *
                  jnp.dtype(self.value_dtype).itemsize)
        if self.scale_dtype is not None:
            nbytes += (2 * self.cfg.layers *
                       int(np.prod(self._scale_shape)) *
                       jnp.dtype(self.scale_dtype).itemsize)
        return nbytes

    def bytes_per_position(self) -> int:
        """HBM bytes one cached position costs across K+V and all layers
        (values + scales) — the unit the engine's per-tick byte
        accounting multiplies out."""
        from ..ops.kv_quant import kv_bytes_per_position
        if self._layer_shapes is not None:
            # K and V on the sparse layers
            return sum(
                int(np.prod(shape[1:])) * jnp.dtype(dt).itemsize
                for layer in self._layer_shapes
                for key, (shape, dt) in layer.items()
                if key == "kv") // self.page_size
        hd = self.cfg.d_model // self.cfg.heads
        return self.cfg.layers * kv_bytes_per_position(
            self.cfg.heads, hd, self.value_dtype,
            self.scale_dtype is not None)

    def note_quant_error(self, rms: float) -> None:
        """Record one sampled write-time roundtrip error (relative RMS of
        ``dequantize(quantize(rows))`` vs the bf16 rows — exactly the
        delta between what the kernel reads and what the byte-exact
        oracle would have read). The engine forwards the same sample to
        the SLO tracker under its model label."""
        rms = float(rms)
        self.stats["quant_error_probes"] += 1
        self.stats["quant_error_last"] = rms
        self.stats["quant_error_sum"] += rms
        self.stats["quant_error_max"] = max(
            self.stats["quant_error_max"], rms)

    # -- allocation ----------------------------------------------------------

    def pages_per_slot(self, length: int) -> int:
        """Pages needed to hold ``length`` cache positions."""
        return -(-int(length) // self.page_size)

    @property
    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def alloc(self, n: int, *, count_failure: bool = True) -> List[int]:
        """Take ``n`` free pages (lowest physical index first — keeps the
        live span dense so compaction rarely triggers). Raises
        :class:`PoolExhausted` without partial effects.

        ``count_failure=False`` suppresses the failure stat/metric for
        callers that retry under prefix-eviction pressure — only the
        TERMINAL failure (nothing left to evict) should count as an
        ``alloc_failure`` (see :meth:`note_alloc_failure`)."""
        if n < 0:
            raise ValueError("alloc() needs n >= 0")
        if n > len(self._free):
            if count_failure:
                self.note_alloc_failure()
            raise PoolExhausted(
                f"need {n} pages, {len(self._free)} free "
                f"({self.pages_in_use}/{self.num_pages - 1} in use)")
        pages = [heapq.heappop(self._free) for _ in range(n)]
        self._refs[pages] += 1
        now = time.monotonic()
        for p in pages:
            self._alloc_t[p] = now
        self.high_water = max(self.high_water, self.pages_in_use)
        M_PAGES_IN_USE.set(self.pages_in_use)
        return pages

    def note_alloc_failure(self) -> None:
        """Record a terminal allocation failure — one that stood even
        after every evictable prefix was released."""
        self.stats["alloc_failures"] += 1
        M_ALLOC_FAILURES.inc()

    def incref(self, pages: Sequence[int]) -> None:
        for p in pages:
            if self._refs[p] <= 0:
                raise ValueError(f"incref of free page {p}")
        self._refs[list(pages)] += 1

    def free(self, pages: Sequence[int], *, cost_cls=None,
             cost_trace=None) -> None:
        """Drop one reference per page; refcount-0 pages return to the
        free heap. Sharing makes double-free detectable: freeing an
        already-free page raises.

        Pages whose LAST reference drops here charge their whole hold
        (pages x seconds since they left the free heap) to the cost
        ledger as ``kv_page_seconds`` — under ``cost_cls``/``cost_trace``
        when the caller knows the owning request (the decoder's slot
        release does), else the ambient trace context."""
        held = 0.0
        now = time.monotonic()
        for p in pages:
            p = int(p)
            if p <= 0 or p >= self.num_pages or self._refs[p] <= 0:
                raise ValueError(f"free of unallocated page {p}")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                heapq.heappush(self._free, p)
                held += now - self._alloc_t.pop(p, now)
        M_PAGES_IN_USE.set(self.pages_in_use)
        if held > 0.0:
            _ledger_charge("kv_page_seconds", held, cls=cost_cls,
                           trace_id=cost_trace)

    # -- prefix sharing ------------------------------------------------------

    def register_prefix(self, phash: str, pages: Sequence[int],
                        plen: int, state=None) -> None:
        """Retain ``pages`` (incref) as the cached cache-content of a
        prompt prefix of ``plen`` tokens. Registrations are COUNTED per
        hash: a re-registration keeps the existing entry's pages but
        adds a release obligation, so the entry outlives every key that
        registered it (releasing one of two token-identical keys must
        not dangle the other). ``state`` is a hybrid decoder's snapshot of
        its linear-attention states after exactly ``plen`` tokens, kept
        with the pages and dropped with them."""
        if phash in self._prefixes:
            self._prefix_regs[phash] += 1
            return
        pages = tuple(int(p) for p in pages)
        self.incref(pages)
        self._prefixes[phash] = (pages, int(plen))
        self._prefix_regs[phash] = 1
        if state is not None:
            self._snapshots[phash] = state
            self._note_snapshot("stored")

    def _note_snapshot(self, event: str) -> None:
        if not self.snapshot_bytes:
            return      # pages alone: what is kept with them is no state
        for key, metric, n in (
                ("state_snapshots_", M_STATE_SNAPSHOTS, 1),
                ("state_snapshot_bytes_", M_STATE_SNAPSHOT_BYTES,
                 self.snapshot_bytes)):
            self.stats[key + event] = self.stats.get(key + event, 0) + n
            metric.inc(n, event=event)

    def prefix_state(self, phash: str):
        """The snapshot stored with a prefix, for a hit to restore."""
        self._note_snapshot("restored")
        return self._snapshots[phash]

    def lookup_prefix(self, phash: str):
        """``(pages, plen)`` or None."""
        return self._prefixes.get(phash)

    def acquire_prefix(self, phash: str,
                       n_shared: int) -> Tuple[Tuple[int, ...], int]:
        """Share the first ``n_shared`` pages of a registered prefix into
        a request (incref — copy-on-write: the request never writes
        them). Returns the full (pages, plen) entry."""
        pages, plen = self._prefixes[phash]
        shared = pages[:n_shared]
        self.incref(shared)
        if shared:
            self.stats["prefix_share_hits"] += len(shared)
            M_PREFIX_SHARE_HITS.inc(len(shared))
            self.stats["prefix_tokens_shared"] += len(shared) * self.page_size
            M_PREFIX_TOKENS_SHARED.set(self.stats["prefix_tokens_shared"])
        return pages, plen

    def release_prefix(self, phash: str) -> None:
        """Drop one registration of a prefix (no-op for unknown hashes);
        the entry's page references fall only with the LAST one."""
        regs = self._prefix_regs.get(phash)
        if regs is None:
            return
        if regs > 1:
            self._prefix_regs[phash] = regs - 1
            return
        del self._prefix_regs[phash]
        pages, _ = self._prefixes.pop(phash)
        if self._snapshots.pop(phash, None) is not None:
            self._note_snapshot("evicted")
        self.free(pages)

    # -- defrag --------------------------------------------------------------

    def fragmentation(self) -> int:
        """Pages of dead space inside the live span: how far the highest
        live page sits past where dense packing would put it."""
        live = np.nonzero(self._refs[1:] > 0)[0]
        if live.size == 0:
            return 0
        return int(live[-1] + 1) - int(live.size)

    def should_compact(self, threshold: int) -> bool:
        return self.fragmentation() >= max(1, int(threshold))

    def compact(self) -> Optional[np.ndarray]:
        """Pack live pages down to ``[1, n_live]``. Returns ``remap``
        (old physical id -> new, a full permutation of ``[0, num_pages)``
        with ``remap[0] == 0``) for the engine to (a) gather the device
        buffers with its inverse and (b) rewrite block tables and every
        host page list it holds — or None when nothing would move.
        Internal refcounts, the free heap and the prefix registry are
        rewritten here."""
        live = (np.nonzero(self._refs > 0)[0]).astype(np.int64)
        remap = np.zeros(self.num_pages, np.int64)
        nxt = 1
        moved = 0
        for old in live:
            if old == 0:
                continue
            remap[old] = nxt
            if old != nxt:
                moved += 1
            nxt += 1
        if moved == 0:
            return None
        # dead pages fill the remainder in index order (their contents are
        # garbage either way; the permutation just has to be total)
        dead = [p for p in range(1, self.num_pages) if self._refs[p] == 0]
        for old in dead:
            remap[old] = nxt
            nxt += 1
        new_refs = np.zeros_like(self._refs)
        new_refs[remap] = self._refs
        self._refs = new_refs
        self._free = [int(remap[p]) for p in dead]
        heapq.heapify(self._free)
        self._prefixes = {
            h: (tuple(int(remap[p]) for p in pages), plen)
            for h, (pages, plen) in self._prefixes.items()}
        self._alloc_t = {int(remap[p]): t
                         for p, t in self._alloc_t.items()}
        self.stats["defrag_moves"] += moved
        M_DEFRAG_MOVES.inc(moved)
        return remap

    # -- session export / adopt ----------------------------------------------

    def export_session(self, pages: Sequence[int], *, length: int) -> dict:
        """Serialize one session's KV pages into a portable JSON-able blob.

        ``pages`` is the session's page list in *logical* (block-table)
        order — the caller runs the compact permutation first (the engine's
        ``_maybe_compact`` remap) and hands over the post-remap list, so
        the blob is position-ordered regardless of physical placement on
        this pool. The blob (version 1) carries K and V apart, as
        ``(n_pages, H, page, hd)`` each: the packed buffer is split here
        and packed again by :meth:`adopt_session`, so a blob outlives the
        pool's layout. Quant scale pools (int8/fp8) ride along per layer
        under the same page indices. ``length`` is the number of positions
        the pages actually hold (prompt + written tokens); the receiver
        uses it to rebuild the block-table row and resume mid-page."""
        import base64
        if self.hybrid:
            raise ValueError("session export does not carry a hybrid "
                             "decoder's linear-attention state yet: "
                             "restore cold")
        pages = [int(p) for p in pages]
        idx = jnp.asarray(np.asarray(pages, np.int32))
        data = []
        hd = self._page_shape[-1]
        for c in self.buffers:
            kv = np.asarray(c["kv"][idx])
            arrays = {"k": kv[..., :hd], "v": kv[..., hd:]}
            arrays.update((key, np.asarray(buf[idx]))
                          for key, buf in c.items() if key != "kv")
            data.append({key: base64.b64encode(arr.tobytes()).decode("ascii")
                         for key, arr in arrays.items()})
        self.stats["sessions_exported"] = \
            self.stats.get("sessions_exported", 0) + 1
        return {
            "v": 1,
            "page_size": self.page_size,
            "n_pages": len(pages),
            "length": int(length),
            "kv_dtype": self.kv_dtype,
            "value_dtype": np.dtype(self.value_dtype).name,
            "scale_dtype": (np.dtype(self.scale_dtype).name
                            if self.scale_dtype is not None else None),
            "layers": int(self.cfg.layers),
            "page_shape": [int(x) for x in self._page_shape],
            "data": data,
        }

    def adopt_session(self, blob: dict) -> List[int]:
        """Allocate pages on THIS pool and scatter ``blob``'s contents into
        them (the warm-handoff receive side). Returns the new page list in
        the blob's logical order — the caller rebuilds its block-table row
        from it. Raises ``ValueError`` on a layout mismatch (page size,
        layer count, head geometry, quantization mode must agree) and
        ``PoolExhausted`` — with nothing leaked — when this pool lacks the
        pages."""
        import base64
        if self.hybrid:
            raise ValueError("session adopt does not carry a hybrid "
                             "decoder's linear-attention state yet: "
                             "restore cold")
        if blob.get("v") != 1:
            raise ValueError(f"unknown session blob version {blob.get('v')}")
        want = {
            "page_size": self.page_size,
            "kv_dtype": self.kv_dtype,
            "value_dtype": np.dtype(self.value_dtype).name,
            "scale_dtype": (np.dtype(self.scale_dtype).name
                            if self.scale_dtype is not None else None),
            "layers": int(self.cfg.layers),
            "page_shape": [int(x) for x in self._page_shape],
        }
        got = {k: blob.get(k) for k in want}
        if got != want:
            raise ValueError(
                f"session blob layout mismatch: blob {got} != pool {want}")
        n = int(blob["n_pages"])
        pages = self.alloc(n)
        try:
            idx = jnp.asarray(np.asarray(pages, np.int32))
            new_buffers = []

            def decoded(entry, key):
                scale = key.endswith("_scale")
                dt = np.dtype(self.scale_dtype if scale else self.value_dtype)
                tail = self._scale_shape[1:] if scale else self._page_shape
                return np.frombuffer(base64.b64decode(entry[key]),
                                     dtype=dt).reshape((n,) + tuple(tail))

            for c, entry in zip(self.buffers, blob["data"]):
                arrays = {"kv": np.concatenate(
                    [decoded(entry, "k"), decoded(entry, "v")], axis=-1)}
                arrays.update((key, decoded(entry, key))
                              for key in c if key != "kv")
                new_buffers.append(
                    {key: buf.at[idx].set(jnp.asarray(arrays[key], buf.dtype))
                     for key, buf in c.items()})
            self.buffers = new_buffers
        except Exception:
            self.free(pages)
            raise
        self.stats["sessions_adopted"] = \
            self.stats.get("sessions_adopted", 0) + 1
        return pages

    # -- misc ----------------------------------------------------------------

    def note_prefix_miss(self) -> None:
        """A ``prefix_key`` request admitted without a stored prefix: it
        prefills whole (and registers, where the store has room)."""
        self.stats["prefix_misses"] = self.stats.get("prefix_misses", 0) + 1

    def note_prefill_chunk(self, ntok: int, riding: bool = False) -> None:
        """A prefill chunk of ``ntok`` prompt tokens; ``riding``: its window
        ran inside a decode tick's dispatch, not in one of its own."""
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_chunks_riding"] += bool(riding)
        self.stats["prefill_tokens"] += int(ntok)
        M_PREFILL_CHUNKS.inc()
        M_PREFILL_TOKENS.inc(int(ntok))
        M_PREFILL_CHUNKS_RIDING_SHARE.set(
            self.stats["prefill_chunks_riding"] / self.stats["prefill_chunks"])

    def note(self, increments: Dict[str, int]) -> None:
        """Add named increments to :attr:`stats`: the one door for counts
        made outside the pool, from the scheduler's numbers (a layer kind's
        host accounting, ``models/zoo/hybrid.py`` ``accountants``). A name
        with a gauge (:data:`_GAUGES`) sets it; ``attn_ticks_<impl>`` also
        moves the kernel-ticks counter under that label."""
        for name, n in increments.items():
            total = self.stats[name] = self.stats.get(name, 0) + n
            if name in _GAUGES:
                _GAUGES[name].set(total)
            elif name.startswith(_TICKS):
                M_KERNEL_TICKS.inc(n, impl=name[len(_TICKS):])

    def note_attn_tick(self, impl: str, *, calls: int = 1,
                       gather_bytes: int = 0) -> None:
        """Account one dispatched paged-attention batch: ``calls`` decode/
        window invocations under ``impl`` ("kernel" or "gather"), plus the
        HBM bytes the gather impl moved materializing contiguous K/V
        (always 0 under the kernel — it reads pages in place)."""
        self.note({_TICKS + impl: calls})
        if gather_bytes:
            self.stats["gather_bytes"] += gather_bytes
            M_GATHER_BYTES.inc(gather_bytes)

    def note_moe(self, counts) -> None:
        """Account the routing counts of drained decode steps: ``counts``
        (steps, 8) in ``parallel.moe.MOE_STATS``' order, as the tick carried
        them out beside its tokens (no device read of their own)."""
        for name, n in zip(MOE_STATS, np.asarray(counts).sum(axis=0)):
            key = "moe_" + name
            self.stats[key] = self.stats.get(key, 0) + int(n)
            M_MOE.inc(int(n), count=name)

    def note_grid_steps(self, positions: Sequence[int], window: int,
                        rows: int) -> None:
        """Account one call of the paged kernel's ragged sweep, from the
        scheduler's numbers: a live row at position ``pos`` sweeps the
        pages up to the one its ``window`` ends in, each of the call's other
        ``rows`` one step; ``grid_steps_dense`` is what ``rows x pages a
        slot`` would have been."""
        per = self.stats["pages_per_slot"]
        self.stats["grid_steps"] += rows - len(positions) + sum(
            min(per, (pos + window - 1) // self.page_size + 1)
            for pos in positions)
        self.stats["grid_steps_dense"] += rows * per
        M_GRID_STEPS_SHARE.set(self.stats["grid_steps"]
                               / self.stats["grid_steps_dense"])

    # -- kernel page-layout contract -----------------------------------------

    @classmethod
    def kernel_aligned_page_size(cls, page_size: int, dtype) -> int:
        """``page_size`` rounded up to the kernel-tileable multiple for
        ``dtype`` (identity when it already complies). The engine applies
        this whenever the kernel impl runs on a real TPU; interpret mode
        (CPU CI) accepts any page size."""
        from ..ops.paged_attention import aligned_page_size
        return aligned_page_size(page_size, dtype)

    def reset(self) -> None:
        """Forget every allocation and re-zero the device buffers (the
        engine's abort path). Rebuilds through the construction-time
        ``make_buffer`` so mesh shardings survive a reset."""
        self.buffers = self._make_buffers()
        self._free = list(range(1, self.num_pages))
        heapq.heapify(self._free)
        self._refs[:] = 0
        self._alloc_t.clear()
        self._prefixes.clear()
        self._prefix_regs.clear()
        self._snapshots.clear()
        M_PAGES_IN_USE.set(0)

    def close(self) -> None:
        """Release the residency reservation early (also runs at GC)."""
        if self._reservation is not None:
            self._finalizer()
            self._reservation = None


class KVAutotuner:
    """Closed-loop tuner for speculative gamma and the prefill chunk budget.

    Observations arrive once per engine tick; every ``interval`` ticks the
    tuner turns the batch into two decisions:

    * **gamma** (speculative draft length) follows the measured acceptance
      rate. Each verify round emits ``accepted + 1`` tokens per live slot,
      so ``acc = (emitted/round_slots - 1) / gamma``. High acceptance
      (>= ``acc_hi``) means drafts are cheap wins -> gamma += 1 (up to
      ``gamma_max``); low acceptance (<= ``acc_lo``) means wasted verify
      width -> gamma -= 1 (floor 1). Changing gamma between rounds keeps
      greedy output token-identical (accepted tokens are the target's own
      argmax choices) and sampled output distributionally exact per round.
    * **chunk budget** follows slot occupancy. A mostly-idle pool
      (occupancy <= ``occ_lo``) can afford bigger prefill bites -> chunk
      doubles (cap ``chunk_max``); a saturated pool (>= ``occ_hi``) needs
      decode latency bounded tighter -> chunk halves (floor ``chunk_min``).
      The power-of-two ladder keeps the window-width compile set small.
    * **pipeline depth** (in-flight decode steps before the engine drains)
      follows the same occupancy signal, in the same direction as chunk and
      for the same reason: an idle pool hides dispatch latency behind a
      deeper pipeline -> depth += 1 (cap ``depth_max``); a saturated pool
      is throughput-bound on the chip anyway and every queued step adds a
      full step-time to p99 time-to-token -> depth -= 1 (floor
      ``depth_min``). Disabled when constructed with ``depth=None`` (the
      engine keeps its static depth).
    """

    def __init__(self, *, gamma: int, gamma_max: int, chunk: int,
                 chunk_min: int = 32, chunk_max: int = 1024,
                 interval: int = 32, acc_lo: float = 0.55,
                 acc_hi: float = 0.85, occ_lo: float = 0.25,
                 occ_hi: float = 0.75, depth: Optional[int] = None,
                 depth_min: int = 1, depth_max: int = 4):
        self.gamma = int(gamma)
        self.gamma_max = int(gamma_max)
        self.chunk = int(chunk)
        self.chunk_min = int(chunk_min)
        self.chunk_max = int(chunk_max)
        self.interval = max(1, int(interval))
        self.acc_lo, self.acc_hi = float(acc_lo), float(acc_hi)
        self.occ_lo, self.occ_hi = float(occ_lo), float(occ_hi)
        self.depth = None if depth is None else int(depth)
        self.depth_min = max(0, int(depth_min))
        self.depth_max = max(self.depth_min, int(depth_max))
        self.history: List[Dict] = []
        self._ticks = 0
        self._occ_sum = 0.0
        self._emitted0 = 0
        self._rounds0 = 0
        M_AUTOTUNE_GAMMA.set(self.gamma)
        M_AUTOTUNE_CHUNK.set(self.chunk)
        if self.depth is not None:
            M_AUTOTUNE_DEPTH.set(self.depth)

    def observe(self, live: int, slots: int, spec_emitted: Optional[int] = None,
                spec_round_slots: Optional[int] = None) -> None:
        """One engine tick: ``live`` occupied of ``slots`` total, plus the
        engine's cumulative speculative counters (deltas are taken here)."""
        self._ticks += 1
        self._occ_sum += live / max(1, slots)
        if self._ticks < self.interval:
            return
        occ = self._occ_sum / self._ticks
        self._ticks = 0
        self._occ_sum = 0.0
        if spec_emitted is not None and spec_round_slots is not None:
            d_emit = spec_emitted - self._emitted0
            d_rounds = spec_round_slots - self._rounds0
            self._emitted0, self._rounds0 = spec_emitted, spec_round_slots
            if d_rounds > 0 and self.gamma > 0:
                acc = (d_emit / d_rounds - 1.0) / self.gamma
                if acc >= self.acc_hi and self.gamma < self.gamma_max:
                    self._set_gamma(self.gamma + 1, acc)
                elif acc <= self.acc_lo and self.gamma > 1:
                    self._set_gamma(self.gamma - 1, acc)
        if occ <= self.occ_lo and self.chunk * 2 <= self.chunk_max:
            self._set_chunk(self.chunk * 2, occ)
        elif occ >= self.occ_hi and self.chunk // 2 >= self.chunk_min:
            self._set_chunk(self.chunk // 2, occ)
        if self.depth is not None:
            if occ <= self.occ_lo and self.depth + 1 <= self.depth_max:
                self._set_depth(self.depth + 1, occ)
            elif occ >= self.occ_hi and self.depth - 1 >= self.depth_min:
                self._set_depth(self.depth - 1, occ)

    def _set_gamma(self, g: int, acc: float) -> None:
        self.history.append({"knob": "gamma", "from": self.gamma, "to": g,
                             "acceptance": round(acc, 4)})
        self.gamma = g
        M_AUTOTUNE_GAMMA.set(g)

    def _set_chunk(self, c: int, occ: float) -> None:
        self.history.append({"knob": "chunk", "from": self.chunk, "to": c,
                             "occupancy": round(occ, 4)})
        self.chunk = c
        M_AUTOTUNE_CHUNK.set(c)

    def _set_depth(self, d: int, occ: float) -> None:
        self.history.append({"knob": "depth", "from": self.depth, "to": d,
                             "occupancy": round(occ, 4)})
        self.depth = d
        M_AUTOTUNE_DEPTH.set(d)
