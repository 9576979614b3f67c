"""Quantized KV data plane (``ops/kv_quant.py`` + the int8/fp8 page path).

The invariants this file pins, in order of importance:

1. PARITY — the int8-page engine (kernel AND gather attention) decodes
   token-identical to the full-precision reference on short greedy
   decodes (fixed seeds); where exact parity is not the contract (fp8,
   long horizons) the dequant error is a bounded relative RMS.
2. BYTES — the quantized layout's HBM traffic is counter-asserted, not
   estimated: at hd=64 a decode tick reads >= 1.9x fewer KV bytes than
   the bf16 layout at identical geometry, ``device_bytes()`` is exact to
   the buffer arithmetic, and the residency reservation is sized to the
   QUANTIZED itemsize (values + scales), so a fixed ``kv_pages`` budget
   really holds ~2x the contexts.
3. AGREEMENT — every writer (prefill scatter, gather-impl writeback,
   mesh row write, fused in-kernel scatter) quantizes through ONE helper
   and produces bit-identical pages AND scales; CoW prefix sharing and
   ``compact()`` defrag remap scales through the same permutation as
   pages (wrong remap would corrupt the survivor's decode — asserted by
   reference-equal tokens after defrag).
4. STEADY STATE — the quantized engine path compiles once per program
   shape: zero recompiles across steady-state ticks, probed through the
   jit cache itself.
5. GOVERNANCE — the dequant-oracle probe lands in pool stats, the
   ``mmlspark_kv_quant_error`` gauge, the SLO model window and scorecard,
   and a canary whose window quant error breaches the incumbent's by
   ``quant_margin`` auto-rolls back.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.models.zoo.transformer import (
    TransformerConfig, decode_step_paged, decode_step_ragged,
    generate_cached, init_kv_cache, init_paged_cache, init_transformer,
    paged_gather, paged_scatter_rows)
from mmlspark_tpu.ops.compile_cache import jit_cache_size
from mmlspark_tpu.ops.kv_quant import (SCALE_DTYPE, dequantize_kv,
                                       kv_bytes_per_position, kv_qmax,
                                       kv_store_dtype, quantize_kv,
                                       resolve_kv_dtype, supports_fp8)
from mmlspark_tpu.ops.paged_attention import (_pool_write_rows,
                                              paged_attention_window)
from mmlspark_tpu.serving.continuous import ContinuousDecoder
from mmlspark_tpu.serving.kv_pool import PagedKVPool

CFG = TransformerConfig(vocab=128, layers=2, d_model=64, heads=4, d_ff=128,
                        max_len=64, causal=True, norm="rmsnorm",
                        position="rope", dtype=jnp.float32)

QUANT_DTYPES = ["int8"] + (["fp8"] if supports_fp8() else [])


@pytest.fixture(scope="module")
def params():
    return init_transformer(CFG, seed=0)


def _drain(eng):
    while any(r is not None for r in eng._slot_req) or eng._waiting:
        eng.step()


def _reference(params, prompt, n):
    want = generate_cached(params, prompt[None, :], CFG, max_new_tokens=n)
    return list(np.asarray(want)[0, len(prompt):])


# ---------------------------------------------------------------------------
# the quantization helper itself


class TestQuantizeKV:
    @pytest.mark.parametrize("kv_dtype", QUANT_DTYPES)
    def test_roundtrip_relative_rms_bounded(self, kv_dtype):
        store = kv_store_dtype(kv_dtype)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(64, 4, 64)), jnp.float32)
        q, s = quantize_kv(x, store)
        assert q.dtype == store and s.dtype == SCALE_DTYPE
        assert s.shape == x.shape[:-1]
        d = np.asarray(dequantize_kv(q, s)) - np.asarray(x)
        rms = np.sqrt((d * d).mean()) / np.sqrt((np.asarray(x) ** 2).mean())
        # int8 symmetric absmax on gaussians sits well under 1%; fp8's
        # 3-bit mantissa under 4%
        assert rms < (0.01 if kv_dtype == "int8" else 0.04)

    def test_absmax_element_hits_qmax_exactly(self):
        # the row max maps onto the lattice edge — no clipping loss
        x = jnp.asarray([[1.0, -4.0, 2.0]], jnp.float32)
        q, s = quantize_kv(x, jnp.int8)
        assert int(np.asarray(q)[0, 1]) == -int(kv_qmax(jnp.int8))

    def test_zero_rows_quantize_to_zero_with_unit_scale(self):
        q, s = quantize_kv(jnp.zeros((3, 5), jnp.float32), jnp.int8)
        assert np.all(np.asarray(q) == 0)
        assert np.all(np.asarray(s, np.float32) == 1.0)

    def test_division_uses_the_stored_scale(self):
        # writers divide by the bf16-ROUNDED scale, so what the kernel
        # multiplies back is exactly what the writer divided by: the
        # roundtrip of the absmax element is exact, not off by the
        # scale-rounding epsilon
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
        q, s = quantize_kv(x, jnp.int8)
        amax_idx = np.argmax(np.abs(np.asarray(x)), axis=-1)
        got = np.asarray(dequantize_kv(q, s))
        for i, j in enumerate(amax_idx):
            ref = np.float32(np.asarray(s)[i]) * np.round(
                np.asarray(x)[i, j] / np.float32(np.asarray(s)[i]))
            assert got[i, j] == pytest.approx(float(ref), abs=0.0)

    def test_resolve_kv_dtype_canonicalizes_and_rejects(self):
        assert resolve_kv_dtype(None) is None
        assert resolve_kv_dtype("bf16") is None
        assert resolve_kv_dtype("int8") == "int8"
        if supports_fp8():
            assert resolve_kv_dtype("float8_e4m3fn") == "fp8"
        with pytest.raises(ValueError):
            resolve_kv_dtype("int4")


# ---------------------------------------------------------------------------
# bytes: the >= 1.9x acceptance number, counter-asserted


class TestByteAccounting:
    def test_bytes_per_position_ratio_at_hd64(self):
        # bf16 values: 2 bytes/elem; int8 + one bf16 scale per (pos, head):
        # 128 vs 66 bytes per head-position = 1.9394x
        bf16 = kv_bytes_per_position(8, 64, jnp.bfloat16, False)
        q = kv_bytes_per_position(8, 64, jnp.int8, True)
        assert bf16 / q >= 1.9

    def test_engine_tick_bytes_ratio_at_hd64(self):
        cfg = CFG._replace(d_model=256, d_ff=256, dtype=jnp.bfloat16)
        pool_b = PagedKVPool(cfg, num_pages=8, page_size=4,
                             residency=False)
        pool_q = PagedKVPool(cfg, num_pages=8, page_size=4,
                             kv_dtype="int8", residency=False)
        ratio = pool_b.bytes_per_position() / pool_q.bytes_per_position()
        assert ratio >= 1.9
        # the engine's per-tick gather-bytes figure scales by the same
        # factor (identical S * Lc geometry)
        params = init_transformer(cfg, seed=0)
        e_b = ContinuousDecoder(params, cfg, max_slots=2, max_len=32,
                                page_size=4)
        e_q = ContinuousDecoder(params, cfg, max_slots=2, max_len=32,
                                page_size=4, kv_dtype="int8")
        assert e_b._gather_bytes_tick / e_q._gather_bytes_tick >= 1.9

    @pytest.mark.parametrize("kv_dtype", QUANT_DTYPES)
    def test_device_bytes_exact(self, kv_dtype):
        pool = PagedKVPool(CFG, num_pages=9, page_size=4,
                           kv_dtype=kv_dtype, residency=False)
        hd = CFG.d_model // CFG.heads
        vals = 9 * CFG.heads * 4 * hd * jnp.dtype(pool.value_dtype).itemsize
        scales = 9 * CFG.heads * 4 * jnp.dtype(SCALE_DTYPE).itemsize
        assert pool.device_bytes() == 2 * CFG.layers * (vals + scales)
        # ...and it is what the buffers actually hold
        nbytes = sum(int(b.nbytes) for c in pool.buffers
                     for b in c.values())
        assert pool.device_bytes() == nbytes

    def test_residency_reserve_sized_to_quantized_itemsize(self):
        from mmlspark_tpu.core.residency import get_residency_manager
        mgr = get_residency_manager()
        before = mgr.reserved_bytes()
        pool = PagedKVPool(CFG, num_pages=9, page_size=4, kv_dtype="int8")
        assert mgr.reserved_bytes() - before == pool.device_bytes()
        del pool   # finalizer releases the reservation
        assert mgr.reserved_bytes() == before

    def test_fixed_page_budget_holds_more_contexts(self):
        # the POINT of the quantized plane: same kv_pages byte budget,
        # ~2x the max_len contexts resident at hd=64
        bf16 = kv_bytes_per_position(4, 64, jnp.bfloat16, False)
        q = kv_bytes_per_position(4, 64, jnp.int8, True)
        budget = 64 * 16 * bf16            # 64 bf16 pages of 16 positions
        ctx_b = budget // (64 * bf16)      # 64-token contexts that fit
        ctx_q = budget // (64 * q)
        assert ctx_q >= int(1.9 * ctx_b)


# ---------------------------------------------------------------------------
# parity: kernel and gather vs the full-precision oracle


class TestEngineParity:
    @pytest.mark.parametrize("attn", ["kernel", "gather"])
    def test_int8_greedy_token_parity_short_decodes(self, params, attn):
        eng = ContinuousDecoder(params, CFG, max_slots=3, max_len=48,
                                page_size=4, kv_dtype="int8",
                                paged_attn=attn)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, CFG.vocab, n).astype(np.int32)
                   for n in (3, 7, 12)]
        reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
        _drain(eng)
        for p, r in zip(prompts, reqs):
            assert r.tokens == _reference(params, p, 9)
        assert eng._kv.pages_in_use == 0

    @pytest.mark.parametrize("kv_dtype", QUANT_DTYPES)
    def test_logits_relative_rms_bounded(self, params, kv_dtype):
        """Where token identity is not the contract (fp8, deeper
        contexts): the quantized paged step's logits stay within a small
        relative RMS of the full-precision paged step's."""
        B, L, page, steps = 3, 16, 4, 8
        rng = np.random.default_rng(0)
        cache = init_kv_cache(CFG, B, L)
        for t in range(steps):
            tok = jnp.asarray(rng.integers(0, CFG.vocab, B))
            _, cache = decode_step_ragged(
                params, tok, jnp.full((B,), t, jnp.int32), cache, CFG)
        n_pages = L // page
        bt = jnp.asarray(
            1 + np.arange(B)[:, None] * n_pages + np.arange(n_pages),
            jnp.int32)
        rows = [{"k": c["k"], "v": c["v"]} for c in cache]
        ref_pages = paged_scatter_rows(
            init_paged_cache(CFG, 1 + B * n_pages, page), rows, bt, page)
        q_pages = paged_scatter_rows(
            init_paged_cache(CFG, 1 + B * n_pages, page,
                             kv_dtype=kv_dtype), rows, bt, page)
        tok = jnp.asarray(rng.integers(0, CFG.vocab, B))
        pos = jnp.full((B,), steps, jnp.int32)
        want, _ = decode_step_paged(params, tok, pos, ref_pages, bt, CFG,
                                    page_size=page, length=L,
                                    impl="gather")
        got, _ = decode_step_paged(params, tok, pos, q_pages, bt, CFG,
                                   page_size=page, length=L,
                                   impl="gather")
        w, g = np.asarray(want, np.float64), np.asarray(got, np.float64)
        rms = np.sqrt(((g - w) ** 2).mean()) / np.sqrt((w ** 2).mean())
        assert rms < (0.05 if kv_dtype == "int8" else 0.15)

    @pytest.mark.skipif(not supports_fp8(), reason="no float8_e4m3fn")
    def test_fp8_engine_decodes_with_bounded_probe_error(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                page_size=4, kv_dtype="fp8",
                                quant_probe=1)
        rng = np.random.default_rng(3)
        reqs = [eng.submit(rng.integers(1, CFG.vocab, n).astype(np.int32),
                           max_new_tokens=6) for n in (4, 9)]
        _drain(eng)
        assert all(len(r.tokens) == 6 and r.error is None for r in reqs)
        assert eng._kv.stats["quant_error_probes"] >= 1
        assert eng._kv.stats["quant_error_last"] < 0.1

    def test_bf16_oracle_path_untouched(self, params):
        """kv_dtype=None is the byte-exact oracle: pool buffers carry the
        model dtype, no scale arrays exist, and the gather round-trips
        the scatter bitwise."""
        pool = PagedKVPool(CFG, num_pages=8, page_size=4,
                           residency=False)
        assert pool.kv_dtype is None and pool.scale_dtype is None
        assert set(pool.buffers[0]) == {"kv"}
        B, L, page = 2, 8, 4
        rng = np.random.default_rng(2)
        cache = init_kv_cache(CFG, B, L)
        for t in range(4):
            tok = jnp.asarray(rng.integers(0, CFG.vocab, B))
            _, cache = decode_step_ragged(
                params, tok, jnp.full((B,), t, jnp.int32), cache, CFG)
        bt = jnp.asarray(1 + np.arange(B)[:, None] * 2 + np.arange(2),
                         jnp.int32)
        pages = paged_scatter_rows(
            init_paged_cache(CFG, 1 + B * 2, page),
            [{"k": c["k"], "v": c["v"]} for c in cache], bt, page)
        for got, want in zip(paged_gather(pages, bt, L), cache):
            assert np.array_equal(np.asarray(got["k"]),
                                  np.asarray(want["k"]))


# ---------------------------------------------------------------------------
# writer agreement: one quantizer, bit-identical pages and scales


class TestWriterAgreement:
    @pytest.mark.parametrize("kv_dtype", QUANT_DTYPES)
    def test_fused_kernel_scatter_matches_pool_write_rows(self, kv_dtype):
        store = kv_store_dtype(kv_dtype)
        rng = np.random.default_rng(0)
        B, H, W, hd, page, NP = 3, 4, 2, 16, 8, 17
        q = jnp.asarray(rng.normal(size=(B, H, W, hd)), jnp.float32)
        kn = jnp.asarray(rng.normal(size=(B, H, W, hd)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(B, H, W, hd)), jnp.float32)
        kvp = jnp.zeros((NP, H, page, 2 * hd), store)
        ks = jnp.ones((NP, H, page), SCALE_DTYPE)
        vs = jnp.ones((NP, H, page), SCALE_DTYPE)
        bt = jnp.asarray(1 + 2 * np.arange(B)[:, None] + np.arange(2),
                         jnp.int32)
        pos = jnp.asarray([0, 3, 6], jnp.int32)
        active = jnp.asarray([True, True, True])
        _, kvp1, ks1, vs1 = paged_attention_window(
            q, kn, vn, kvp, bt, pos, active=active,
            k_scale=ks, v_scale=vs)
        kvp2, ks2, vs2 = _pool_write_rows(kvp, kn, vn, bt, pos, active,
                                          ks, vs)
        for a, b in ((kvp1, kvp2), (ks1, ks2), (vs1, vs2)):
            # trash page 0 is scratch for both paths — exclude it
            assert np.array_equal(np.asarray(a)[1:], np.asarray(b)[1:])

    def test_prefill_scatter_matches_writeback(self, params):
        """paged_scatter_rows (prefill) and the gather-impl writeback
        quantize through the same helper: scattering the same rows twice
        is idempotent bit-for-bit."""
        B, L, page = 2, 8, 4
        rng = np.random.default_rng(4)
        cache = init_kv_cache(CFG, B, L)
        for t in range(6):
            tok = jnp.asarray(rng.integers(0, CFG.vocab, B))
            _, cache = decode_step_ragged(
                params, tok, jnp.full((B,), t, jnp.int32), cache, CFG)
        bt = jnp.asarray(1 + np.arange(B)[:, None] * 2 + np.arange(2),
                         jnp.int32)
        rows = [{"k": c["k"], "v": c["v"]} for c in cache]
        once = paged_scatter_rows(
            init_paged_cache(CFG, 1 + B * 2, page, kv_dtype="int8"),
            rows, bt, page)
        twice = paged_scatter_rows(once, rows, bt, page)
        for a, b in zip(once, twice):
            for kk in a:
                assert np.array_equal(np.asarray(a[kk]),
                                      np.asarray(b[kk]))

    def test_quant_gather_dequantizes_through_scales(self):
        rng = np.random.default_rng(5)
        B, L, page = 2, 8, 4
        rows = [{"k": jnp.asarray(rng.normal(size=(B, CFG.heads, L, 16)),
                                  jnp.float32),
                 "v": jnp.asarray(rng.normal(size=(B, CFG.heads, L, 16)),
                                  jnp.float32)}
                for _ in range(CFG.layers)]
        bt = jnp.asarray(1 + np.arange(B)[:, None] * 2 + np.arange(2),
                         jnp.int32)
        pages = paged_scatter_rows(
            init_paged_cache(CFG, 1 + B * 2, page, kv_dtype="int8"),
            rows, bt, page)
        for got, want in zip(paged_gather(pages, bt, L), rows):
            g, w = np.asarray(got["k"]), np.asarray(want["k"])
            rms = np.sqrt(((g - w) ** 2).mean()) / np.sqrt((w ** 2).mean())
            assert rms < 0.01


# ---------------------------------------------------------------------------
# CoW + defrag: scales ride the same permutation


class TestSharingAndDefrag:
    def test_quantized_cow_prefix_sharing_token_parity(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                page_size=4, kv_dtype="int8")
        rng = np.random.default_rng(4)
        prefix = rng.integers(1, CFG.vocab, 10).astype(np.int32)
        p_b = np.concatenate(
            [prefix, rng.integers(1, CFG.vocab, 3).astype(np.int32)])
        ra = eng.submit(prefix, max_new_tokens=6, prefix_key="sys")
        while not ra.done:
            eng.step()
        shared = eng._kv.stats["prefix_share_hits"]
        rb = eng.submit(p_b, max_new_tokens=6, prefix_key="sys")
        while not rb.done:
            eng.step()
        assert eng._kv.stats["prefix_share_hits"] - shared == 2
        for p, r in ((prefix, ra), (p_b, rb)):
            assert r.tokens == _reference(params, p, 6)

    def test_quantized_defrag_remaps_scales_with_pages(self, params):
        """Retire-triggered compact(): the survivor's pages AND scales
        move through the same permutation — a scale left behind would
        rescale the survivor's keys and corrupt its (reference-equal)
        greedy decode."""
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                page_size=4, kv_dtype="int8",
                                defrag_threshold=1)
        rng = np.random.default_rng(7)
        p_short = rng.integers(1, CFG.vocab, 5).astype(np.int32)
        p_long = rng.integers(1, CFG.vocab, 9).astype(np.int32)
        rs = eng.submit(p_short, max_new_tokens=3)
        rl = eng.submit(p_long, max_new_tokens=24)
        while not (rs.done and rl.done):
            eng.step()
        assert eng._kv.stats["defrag_moves"] > 0
        assert rl.tokens == _reference(params, p_long, 24)
        assert eng._kv.pages_in_use == 0

    def test_pool_reset_rebuilds_scale_buffers(self):
        pool = PagedKVPool(CFG, num_pages=8, page_size=4,
                           kv_dtype="int8", residency=False)
        pool.alloc(3)
        pool.reset()
        assert pool.pages_in_use == 0
        assert set(pool.buffers[0]) == {"kv", "k_scale", "v_scale"}
        assert pool.buffers[0]["kv"].dtype == jnp.int8
        assert pool.buffers[0]["k_scale"].dtype == SCALE_DTYPE


# ---------------------------------------------------------------------------
# steady state: zero recompiles on the quantized path


class TestSteadyState:
    def test_zero_steadystate_recompiles_per_kv_dtype(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                page_size=4, kv_dtype="int8")
        rng = np.random.default_rng(9)
        warm = [eng.submit(rng.integers(1, CFG.vocab, n).astype(np.int32),
                           max_new_tokens=4) for n in (3, 7)]
        _drain(eng)
        size = jit_cache_size(eng._tick)
        assert size is not None and size >= 1
        more = [eng.submit(rng.integers(1, CFG.vocab, n).astype(np.int32),
                           max_new_tokens=6) for n in (4, 6)]
        _drain(eng)
        assert jit_cache_size(eng._tick) == size

    def test_program_cache_keys_split_on_kv_dtype(self, params):
        """Two engines over the same geometry but different kv_dtype get
        DIFFERENT tick programs (the quantized pytree carries scale
        leaves) — sharing one would retrace per call."""
        e_q = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                page_size=4, kv_dtype="int8")
        e_b = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                page_size=4)
        assert e_q._tick is not e_b._tick


# ---------------------------------------------------------------------------
# governance: probe -> gauge/SLO window -> scorecard -> canary rollback


class TestQuantGovernance:
    def setup_method(self):
        from mmlspark_tpu.observability.slo import reset_tracker
        reset_tracker()

    teardown_method = setup_method

    def test_probe_feeds_pool_stats_and_slo(self, params):
        from mmlspark_tpu.observability.slo import get_tracker
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                page_size=4, kv_dtype="int8",
                                quant_probe=1, slo_model="m@quant")
        rng = np.random.default_rng(3)
        r = eng.submit(rng.integers(1, CFG.vocab, 6).astype(np.int32),
                       max_new_tokens=4)
        _drain(eng)
        stats = eng._kv.stats
        assert stats["quant_error_probes"] >= 1
        assert 0.0 < stats["quant_error_last"] < 0.05
        assert stats["quant_error_max"] >= stats["quant_error_last"]
        win = get_tracker().model_window("m@quant")
        assert win["kv_quant_samples"] >= 1
        assert 0.0 < win["kv_quant_error"] < 0.05
        card = get_tracker().scorecard()
        assert "m@quant" in card["kv_quant"]
        assert card["kv_quant"]["m@quant"]["count"] >= 1

    def test_gauge_exports_last_probe(self, params):
        from mmlspark_tpu.observability.slo import _M_KV_QUANT
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=32,
                                page_size=4, kv_dtype="int8",
                                quant_probe=1, slo_model="m@g")
        rng = np.random.default_rng(5)
        eng.submit(rng.integers(1, CFG.vocab, 5).astype(np.int32),
                   max_new_tokens=3)
        _drain(eng)
        assert _M_KV_QUANT.labels(model="m@g").get() == pytest.approx(
            eng._kv.stats["quant_error_last"])

    def test_unquantized_engine_never_probes(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=32,
                                page_size=4, quant_probe=1)
        rng = np.random.default_rng(6)
        eng.submit(rng.integers(1, CFG.vocab, 5).astype(np.int32),
                   max_new_tokens=3)
        _drain(eng)
        assert eng._kv.stats["quant_error_probes"] == 0

    def test_canary_rolls_back_on_quant_error_breach(self):
        from mmlspark_tpu.observability.slo import get_tracker
        from mmlspark_tpu.serving.registry import (ModelRegistry,
                                                   reset_registry)
        reset_registry()
        tracker = get_tracker()
        reg = ModelRegistry(min_requests=5, quant_margin=0.05)
        reg.load("m", "bf16")
        reg.load("m", "quant", canary_percent=50)
        for _ in range(10):
            tracker.observe(transport="threaded", route="api",
                            model="m@bf16", seconds=0.01, error=False)
            tracker.observe(transport="threaded", route="api",
                            model="m@quant", seconds=0.01, error=False)
        # incumbent reports no quant error; the canary's dequant oracle
        # drifts past the margin
        for _ in range(4):
            tracker.note_kv_quant_error("m@quant", 0.2)
        verdicts = reg.check_canaries()
        assert "kv_quant_error" in verdicts[0]["breach"]
        assert {v.version: v.state
                for v in reg.versions("m")}["quant"] == "retired"
        assert "kv_quant_error" in reg.snapshot()["rollbacks"][-1]["reason"]
        reset_registry()

    def test_canary_within_quant_margin_stays(self):
        from mmlspark_tpu.observability.slo import get_tracker
        from mmlspark_tpu.serving.registry import (ModelRegistry,
                                                   reset_registry)
        reset_registry()
        tracker = get_tracker()
        reg = ModelRegistry(min_requests=5, quant_margin=0.05)
        reg.load("m", "bf16")
        reg.load("m", "quant", canary_percent=50)
        for _ in range(10):
            tracker.observe(transport="threaded", route="api",
                            model="m@bf16", seconds=0.01, error=False)
            tracker.observe(transport="threaded", route="api",
                            model="m@quant", seconds=0.01, error=False)
        for _ in range(4):
            tracker.note_kv_quant_error("m@quant", 0.004)   # healthy int8
        assert reg.check_canaries()[0]["breach"] is None
        assert {v.version: v.state
                for v in reg.versions("m")}["quant"] == "canary"
        assert reg.snapshot()["margins"]["quant_margin"] == 0.05
        reset_registry()
