"""Continuous-batching decoder serving (``serving/continuous.py``).

The invariant everything here pins: continuous batching changes THROUGHPUT,
never results — every request's greedy output must equal running
``generate_cached`` on its prompt alone, no matter how requests are
staggered, how slots are contended, or where prompts land in the pad
bucket. (The reference has no autoregressive serving; the stateless
analogue is replay determinism, ``HTTPSourceV2.scala:489-506``.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from mmlspark_tpu.models.zoo.transformer import (
    TransformerConfig, decode_step, decode_step_ragged,
    decode_window_ragged, generate_cached, init_kv_cache,
    init_transformer, prefill_cache)
from mmlspark_tpu.serving.continuous import ContinuousDecoder

CFG = TransformerConfig(vocab=128, layers=2, d_model=64, heads=4, d_ff=128,
                        max_len=64, causal=True, norm="rmsnorm",
                        position="rope", dtype=jnp.float32)
CFG_LEARNED = CFG._replace(position="learned", norm="layernorm")


@pytest.fixture(scope="module")
def params():
    return init_transformer(CFG, seed=0)


class TestDecodeStepRagged:
    @pytest.mark.parametrize("cfg_name", ["rope", "learned"])
    def test_uniform_pos_matches_decode_step(self, cfg_name, params):
        cfg = CFG if cfg_name == "rope" else CFG_LEARNED
        p = params if cfg_name == "rope" else init_transformer(cfg, seed=0)
        B, L, pos = 3, 16, 5
        cache = init_kv_cache(cfg, B, L)
        rng = np.random.default_rng(0)
        # warm the cache at positions 0..4 so the step attends over history
        for t in range(pos):
            tok = jnp.asarray(rng.integers(0, cfg.vocab, B))
            _, cache = decode_step(p, tok, t, cache, cfg)
        tok = jnp.asarray(rng.integers(0, cfg.vocab, B))
        want_logits, want_cache = decode_step(p, tok, pos, cache, cfg)
        got_logits, got_cache = decode_step_ragged(
            p, tok, jnp.full((B,), pos, jnp.int32), cache, cfg)
        np.testing.assert_allclose(np.asarray(got_logits),
                                   np.asarray(want_logits),
                                   rtol=1e-5, atol=1e-5)
        for gc, wc in zip(got_cache, want_cache):
            np.testing.assert_allclose(np.asarray(gc["k"]),
                                       np.asarray(wc["k"]),
                                       rtol=1e-5, atol=1e-5)

    def test_mixed_pos_matches_per_row_decode(self, params):
        """Rows at DIFFERENT depths in one ragged step == each row stepped
        alone at its own depth (the continuous-batching soundness core)."""
        B, L = 3, 32
        positions = [2, 7, 13]
        rng = np.random.default_rng(1)
        rows = []
        for pos in positions:
            cache1 = init_kv_cache(CFG, 1, L)
            hist = rng.integers(0, CFG.vocab, pos + 1)
            for t in range(pos):
                _, cache1 = decode_step(params, jnp.asarray(hist[t:t + 1]),
                                        t, cache1, CFG)
            rows.append((hist, cache1))
        # assemble the batch: per-row histories in one (B, …) cache
        cache = [{kk: jnp.concatenate([r[1][i][kk] for r in rows])
                  for kk in ("k", "v")} for i in range(CFG.layers)]
        toks = jnp.asarray([r[0][-1] for r in rows])
        got_logits, got_cache = decode_step_ragged(
            params, toks, jnp.asarray(positions, jnp.int32), cache, CFG)
        for b, pos in enumerate(positions):
            want_logits, want_cache = decode_step(
                params, toks[b:b + 1], pos, [
                    {kk: c[kk][b:b + 1] for kk in ("k", "v")}
                    for c in cache], CFG)
            np.testing.assert_allclose(np.asarray(got_logits[b]),
                                       np.asarray(want_logits[0]),
                                       rtol=1e-5, atol=1e-5)
            for gc, wc in zip(got_cache, want_cache):
                np.testing.assert_allclose(np.asarray(gc["k"][b]),
                                           np.asarray(wc["k"][0]),
                                           rtol=1e-5, atol=1e-5)

    def test_inactive_rows_keep_cache_and_position(self, params):
        B, L = 2, 16
        cache = init_kv_cache(CFG, B, L)
        rng = np.random.default_rng(2)
        for t in range(3):
            _, cache = decode_step(params, jnp.asarray(
                rng.integers(0, CFG.vocab, B)), t, cache, CFG)
        tok = jnp.asarray(rng.integers(0, CFG.vocab, B))
        active = jnp.asarray([True, False])
        _, new_cache = decode_step_ragged(
            params, tok, jnp.asarray([3, 3], jnp.int32), cache, CFG,
            active)
        # row 1 untouched everywhere, row 0 updated at position 3
        for nc, c in zip(new_cache, cache):
            np.testing.assert_array_equal(np.asarray(nc["k"][1]),
                                          np.asarray(c["k"][1]))
            assert not np.array_equal(np.asarray(nc["k"][0, :, 3]),
                                      np.asarray(c["k"][0, :, 3]))


class TestPrefillCache:
    @pytest.mark.parametrize("cfg_name", ["rope", "learned"])
    def test_matches_token_by_token_prefill(self, cfg_name):
        cfg = CFG if cfg_name == "rope" else CFG_LEARNED
        p = init_transformer(cfg, seed=3)
        P, L = 6, 24
        rng = np.random.default_rng(3)
        prompt = rng.integers(0, cfg.vocab, (1, P))
        logits, cache = prefill_cache(p, jnp.asarray(prompt),
                                      jnp.asarray([P]), cfg, L)
        want_cache = init_kv_cache(cfg, 1, L)
        for t in range(P):
            want_logits, want_cache = decode_step(
                p, jnp.asarray(prompt[:, t]), t, want_cache, cfg)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(want_logits),
                                   rtol=1e-4, atol=1e-4)
        for gc, wc in zip(cache, want_cache):
            np.testing.assert_allclose(np.asarray(gc["k"][:, :, :P]),
                                       np.asarray(wc["k"][:, :, :P]),
                                       rtol=1e-4, atol=1e-4)

    def test_right_padding_does_not_change_result(self):
        p = init_transformer(CFG, seed=4)
        P, pad_to, L = 5, 12, 24
        rng = np.random.default_rng(4)
        prompt = rng.integers(0, CFG.vocab, (1, P))
        padded = np.zeros((1, pad_to), np.int64)
        padded[0, :P] = prompt
        a, cache_a = prefill_cache(p, jnp.asarray(prompt),
                                   jnp.asarray([P]), CFG, L)
        b, cache_b = prefill_cache(p, jnp.asarray(padded),
                                   jnp.asarray([P]), CFG, L)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
        # the REAL region of the cache is pad-invariant (positions >= P
        # hold pad garbage that the ragged step's key mask never exposes
        # before it is overwritten)
        np.testing.assert_allclose(np.asarray(cache_a[0]["k"][:, :, :P]),
                                   np.asarray(cache_b[0]["k"][:, :, :P]),
                                   rtol=1e-5, atol=1e-5)


class TestDecodeWindowRagged:
    """decode_window_ragged == decode_window per row at that row's scalar
    start == W sequential ragged steps — the speculative-verify soundness
    core for the slot pool."""

    @pytest.mark.parametrize("cfg_name", ["rope", "learned"])
    def test_matches_per_row_scalar_window(self, cfg_name, params):
        from mmlspark_tpu.models.zoo.transformer import decode_window
        cfg = CFG if cfg_name == "rope" else CFG_LEARNED
        p = params if cfg_name == "rope" else init_transformer(cfg, seed=0)
        B, W, L = 3, 4, 32
        starts = [5, 2, 9]
        rng = np.random.default_rng(11)
        # warm each row's cache to its own depth with its own history
        cache = init_kv_cache(cfg, B, L)
        for t in range(max(starts)):
            tok = jnp.asarray(rng.integers(0, cfg.vocab, B))
            stepped = jnp.asarray([t < s for s in starts])
            _, cache = decode_step_ragged(
                p, tok, jnp.full((B,), t, jnp.int32), cache, cfg, stepped)
        wtoks = jnp.asarray(rng.integers(0, cfg.vocab, (B, W)))
        got, got_cache = decode_window_ragged(
            p, wtoks, jnp.asarray(starts, jnp.int32), cache, cfg)
        for b in range(B):
            row_cache = [{kk: c[kk][b:b + 1] for kk in ("k", "v")}
                         for c in cache]
            want, want_cache = decode_window(
                p, wtoks[b:b + 1], starts[b], row_cache, cfg)
            np.testing.assert_allclose(np.asarray(got[b]),
                                       np.asarray(want[0]),
                                       rtol=2e-4, atol=2e-4)
            lo, hi = starts[b], starts[b] + W
            np.testing.assert_allclose(
                np.asarray(got_cache[0]["k"][b, :, lo:hi]),
                np.asarray(want_cache[0]["k"][0, :, lo:hi]),
                rtol=2e-4, atol=2e-4)

    def test_matches_sequential_ragged_steps(self, params):
        B, W, L = 2, 3, 32
        starts = jnp.asarray([4, 7], jnp.int32)
        rng = np.random.default_rng(12)
        cache = init_kv_cache(CFG, B, L)
        for t in range(7):
            tok = jnp.asarray(rng.integers(0, CFG.vocab, B))
            stepped = starts > t
            _, cache = decode_step_ragged(
                params, tok, jnp.full((B,), t, jnp.int32), cache, CFG,
                stepped)
        wtoks = jnp.asarray(rng.integers(0, CFG.vocab, (B, W)))
        got, _ = decode_window_ragged(params, wtoks, starts, cache, CFG)
        ref_cache = cache
        for j in range(W):
            want_j, ref_cache = decode_step_ragged(
                params, wtoks[:, j], starts + j, ref_cache, CFG)
            np.testing.assert_allclose(np.asarray(got[:, j]),
                                       np.asarray(want_j),
                                       rtol=2e-4, atol=2e-4)

    def test_inactive_rows_keep_cache(self, params):
        B, W, L = 2, 3, 32
        starts = jnp.asarray([4, 6], jnp.int32)
        rng = np.random.default_rng(13)
        cache = init_kv_cache(CFG, B, L)
        for t in range(6):
            tok = jnp.asarray(rng.integers(0, CFG.vocab, B))
            _, cache = decode_step_ragged(
                params, tok, jnp.full((B,), t, jnp.int32), cache, CFG,
                starts > t)
        wtoks = jnp.asarray(rng.integers(0, CFG.vocab, (B, W)))
        active = jnp.asarray([True, False])
        _, got_cache = decode_window_ragged(params, wtoks, starts, cache,
                                            CFG, active)
        np.testing.assert_array_equal(np.asarray(got_cache[0]["k"][1]),
                                      np.asarray(cache[0]["k"][1]))
        assert not np.array_equal(np.asarray(got_cache[0]["k"][0]),
                                  np.asarray(cache[0]["k"][0]))


def _reference_tokens(params, prompt, max_new):
    ids = generate_cached(params, np.asarray(prompt)[None], CFG,
                          max_new_tokens=max_new)
    return list(np.asarray(ids)[0, len(prompt):])


class TestContinuousDecoder:
    def test_single_request_matches_generate_cached(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, CFG.vocab, 7)
        req = eng.submit(prompt, max_new_tokens=9)
        while not req.done:
            eng.step()
        assert eng.result(req) == _reference_tokens(params, prompt, 9)

    def test_staggered_requests_all_match(self, params):
        """Requests of different lengths admitted at different ticks, with
        slot contention (3 requests, 2 slots), all greedy-exact."""
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, CFG.vocab, n) for n in (3, 9, 5)]
        max_new = [6, 4, 8]
        reqs = [eng.submit(prompts[0], max_new[0])]
        eng.step()
        reqs.append(eng.submit(prompts[1], max_new[1]))
        eng.step()
        reqs.append(eng.submit(prompts[2], max_new[2]))
        for _ in range(80):
            if all(r.done for r in reqs):
                break
            eng.step()
        for prompt, mn, req in zip(prompts, max_new, reqs):
            assert req.done
            assert eng.result(req) == _reference_tokens(params, prompt, mn)

    def test_eos_stops_early_and_frees_slot(self, params):
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, CFG.vocab, 4)
        full = _reference_tokens(params, prompt, 10)
        eos = full[3]                      # force a stop after 4 tokens
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=48,
                                eos_id=eos)
        req = eng.submit(prompt, max_new_tokens=10)
        while not req.done:
            eng.step()
        got = eng.result(req)
        assert got == full[:4]
        assert eng._slot_req == [None]     # slot released

    def test_more_requests_than_slots_queue_and_finish(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        rng = np.random.default_rng(8)
        prompts = [rng.integers(0, CFG.vocab, 2 + i) for i in range(5)]
        reqs = [eng.submit(p, 5) for p in prompts]
        for _ in range(200):
            if all(r.done for r in reqs):
                break
            eng.step()
        for p, r in zip(prompts, reqs):
            assert eng.result(r) == _reference_tokens(params, p, 5)

    def test_background_thread_and_timing_fields(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        t = eng.start()
        try:
            rng = np.random.default_rng(9)
            prompt = rng.integers(0, CFG.vocab, 6)
            req = eng.submit(prompt, 5)
            got = eng.result(req, timeout=60)
            assert got == _reference_tokens(params, prompt, 5)
            assert req.first_token_at is not None
            assert req.finished_at >= req.first_token_at
        finally:
            eng.stop()
            t.join(timeout=10)
            assert not t.is_alive()

    @pytest.mark.parametrize("sampling", [
        dict(temperature=0.8, seed=7),
        dict(temperature=1.2, top_k=5, seed=11),
        dict(temperature=0.9, top_p=0.7, seed=3),
        dict(temperature=1.0, top_k=12, top_p=0.85, seed=0),
    ])
    def test_sampled_requests_match_generate_cached(self, params, sampling):
        """Sampling rides the same parity invariant as greedy: per-request
        seed + the generate_cached key schedule (fold_in by absolute emit
        position) make slot-pool sampling request-for-request identical to
        the offline generator."""
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        rng = np.random.default_rng(12)
        prompt = rng.integers(0, CFG.vocab, 6)
        req = eng.submit(prompt, max_new_tokens=8, **sampling)
        for _ in range(20):
            if req.done:
                break
            eng.step()
        ids = generate_cached(params, np.asarray(prompt)[None], CFG,
                              max_new_tokens=8, **sampling)
        assert eng.result(req) == list(np.asarray(ids)[0, 6:])

    def test_mixed_greedy_and_sampled_slots(self, params):
        """Greedy and sampled requests share one pool; each stays exact."""
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        rng = np.random.default_rng(13)
        p_greedy = rng.integers(0, CFG.vocab, 5)
        p_sampled = rng.integers(0, CFG.vocab, 7)
        r1 = eng.submit(p_greedy, max_new_tokens=6)
        r2 = eng.submit(p_sampled, max_new_tokens=6, temperature=0.9,
                        top_k=8, seed=5)
        for _ in range(30):
            if r1.done and r2.done:
                break
            eng.step()
        assert eng.result(r1) == _reference_tokens(params, p_greedy, 6)
        ids = generate_cached(params, np.asarray(p_sampled)[None], CFG,
                              max_new_tokens=6, temperature=0.9, top_k=8,
                              seed=5)
        assert eng.result(r2) == list(np.asarray(ids)[0, 7:])

    def test_two_sampled_requests_independent_seeds(self, params):
        """Two sampled requests with different seeds in the same pool each
        match their own offline run (per-slot keys don't cross-talk)."""
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        rng = np.random.default_rng(14)
        prompts = [rng.integers(0, CFG.vocab, 4),
                   rng.integers(0, CFG.vocab, 9)]
        reqs = [eng.submit(prompts[0], 7, temperature=1.1, seed=21),
                eng.submit(prompts[1], 7, temperature=1.1, seed=22)]
        for _ in range(40):
            if all(r.done for r in reqs):
                break
            eng.step()
        for prompt, req, seed in zip(prompts, reqs, (21, 22)):
            ids = generate_cached(params, np.asarray(prompt)[None], CFG,
                                  max_new_tokens=7, temperature=1.1,
                                  seed=seed)
            assert eng.result(req) == list(
                np.asarray(ids)[0, len(prompt):])

    def test_tensor_parallel_mesh_matches_unsharded(self, params):
        """Continuous decoding over a tp mesh (Megatron params, KV heads
        sharded) is token-for-token the single-device engine — GSPMD
        propagation through the ragged step, greedy AND sampled."""
        mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                mesh=mesh)
        rng = np.random.default_rng(16)
        p1 = rng.integers(0, CFG.vocab, 5)
        p2 = rng.integers(0, CFG.vocab, 8)
        r1 = eng.submit(p1, 6)
        r2 = eng.submit(p2, 6, temperature=0.9, top_k=8, seed=5)
        for _ in range(30):
            if r1.done and r2.done:
                break
            eng.step()
        assert eng.result(r1) == _reference_tokens(params, p1, 6)
        ids = generate_cached(params, np.asarray(p2)[None], CFG,
                              max_new_tokens=6, temperature=0.9, top_k=8,
                              seed=5)
        assert eng.result(r2) == list(np.asarray(ids)[0, 8:])

    def test_dp_tp_mesh_with_sharded_slots(self, params):
        """dp×tp mesh: slots shard over dp (request data parallelism),
        heads over tp; cancel_all keeps the shardings."""
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("dp", "tp"))
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                mesh=mesh)
        rng = np.random.default_rng(17)
        prompt = rng.integers(0, CFG.vocab, 6)
        req = eng.submit(prompt, 5)
        for _ in range(10):
            if req.done:
                break
            eng.step()
        assert eng.result(req) == _reference_tokens(params, prompt, 5)
        eng.cancel_all()                       # must keep mesh shardings
        req2 = eng.submit(prompt, 5)
        for _ in range(10):
            if req2.done:
                break
            eng.step()
        assert eng.result(req2) == _reference_tokens(params, prompt, 5)

    def test_dp_only_mesh_replicates_params(self, params):
        """Code-review regression: a mesh without a tp axis (pure request
        data parallelism) must work, not die in NamedSharding."""
        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                mesh=mesh)
        rng = np.random.default_rng(18)
        prompt = rng.integers(0, CFG.vocab, 5)
        req = eng.submit(prompt, 5)
        for _ in range(10):
            if req.done:
                break
            eng.step()
        assert eng.result(req) == _reference_tokens(params, prompt, 5)

    def test_mesh_heads_divisibility_rejected(self, params):
        mesh = Mesh(np.array(jax.devices()[:8]), ("tp",))
        with pytest.raises(ValueError, match="divisible"):
            ContinuousDecoder(params, CFG, max_slots=1, max_len=16,
                              mesh=mesh)          # heads=4, tp=8

    def test_submit_validation(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=16)
        with pytest.raises(ValueError, match="empty"):
            eng.submit([])
        with pytest.raises(ValueError, match="exceeds"):
            eng.submit(np.arange(10), max_new_tokens=10)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.arange(4), max_new_tokens=0)
        with pytest.raises(ValueError, match="token ids"):
            eng.submit([0, CFG.vocab], max_new_tokens=2)
        with pytest.raises(ValueError, match="token ids"):
            eng.submit([-1, 3], max_new_tokens=2)
        with pytest.raises(ValueError, match="top_p"):
            eng.submit([1, 2], max_new_tokens=2, top_p=0.0)

    def test_cancel_all_races_serve_forever_safely(self, params):
        """Code-review regression: cancel_all from another thread must not
        crash the driver thread mid-step, and the pool must be fully
        usable afterwards (all device state rebuilt)."""
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        t = eng.start()
        try:
            rng = np.random.default_rng(15)
            for _ in range(3):
                reqs = [eng.submit(rng.integers(0, CFG.vocab, 5), 40)
                        for _ in range(3)]
                import time as _t
                _t.sleep(0.02)            # let the driver get mid-stream
                eng.cancel_all()
                # every request resolved (cancelled mid-flight or finished
                # first — the race is the point); the driver survived
                for r in reqs:
                    assert r.done
                assert t.is_alive()
            # pool fully functional after repeated cancels
            prompt = rng.integers(0, CFG.vocab, 4)
            req = eng.submit(prompt, 5)
            assert eng.result(req, timeout=60) == _reference_tokens(
                params, prompt, 5)
        finally:
            eng.stop()
            t.join(timeout=10)

    def test_prompt_near_max_len_does_not_overflow_pad_bucket(self, params):
        """Code-review regression: a 40-token prompt in a 48-len cache must
        not inflate to a 64-wide prefill (bucket capped at max_len)."""
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=48)
        rng = np.random.default_rng(10)
        prompt = rng.integers(0, CFG.vocab, 40)
        req = eng.submit(prompt, max_new_tokens=8)
        for _ in range(20):
            if req.done:
                break
            eng.step()
        assert eng.result(req) == _reference_tokens(params, prompt, 8)

    def test_learned_positions_guard_max_len(self):
        """A cache longer than the learned position table would CLAMP
        gathers past the table and silently diverge — rejected up front."""
        p = init_transformer(CFG_LEARNED, seed=0)
        with pytest.raises(ValueError, match="position table"):
            ContinuousDecoder(p, CFG_LEARNED, max_slots=1,
                              max_len=CFG_LEARNED.max_len + 1)
        # at the limit it works
        eng = ContinuousDecoder(p, CFG_LEARNED, max_slots=1,
                                max_len=CFG_LEARNED.max_len)
        rng = np.random.default_rng(11)
        prompt = rng.integers(0, CFG_LEARNED.vocab, 5)
        req = eng.submit(prompt, max_new_tokens=4)
        for _ in range(10):
            if req.done:
                break
            eng.step()
        ids = generate_cached(p, np.asarray(prompt)[None], CFG_LEARNED,
                              max_new_tokens=4)
        assert eng.result(req) == list(np.asarray(ids)[0, 5:])


class TestPrefixCaching:
    def _run(self, eng, prompt, n=6, **kw):
        req = eng.submit(prompt, max_new_tokens=n, **kw)
        while not req.done:
            eng.step()
        return eng.result(req)

    def test_prefix_hit_matches_uncached(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        rng = np.random.default_rng(20)
        sys_prompt = rng.integers(0, CFG.vocab, 9)
        suffixes = [rng.integers(0, CFG.vocab, n) for n in (4, 7, 1)]
        plain = [self._run(eng, np.concatenate([sys_prompt, s]))
                 for s in suffixes]
        assert eng.stats["prefix_hits"] == 0
        cached = [self._run(eng, np.concatenate([sys_prompt, s]),
                            prefix_key="sys", prefix_len=len(sys_prompt))
                  for s in suffixes]
        assert cached == plain              # greedy outputs unchanged
        assert eng.stats["prefix_hits"] == len(suffixes) - 1

    def test_whole_prompt_hit(self, params):
        # a later request whose ENTIRE prompt is the stored prefix
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        rng = np.random.default_rng(21)
        prompt = rng.integers(0, CFG.vocab, 8)
        a = self._run(eng, prompt, prefix_key="p")
        b = self._run(eng, prompt, prefix_key="p")
        assert a == b == _reference_tokens(params, prompt, 6)
        assert (eng.stats["prefills"], eng.stats["prefix_hits"]) == (1, 1)

    def test_mismatched_prefix_fails_alone(self, params):
        # a bad request must not poison the engine: it fails with its own
        # error while concurrent requests keep decoding correctly
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        rng = np.random.default_rng(22)
        prompt = rng.integers(0, CFG.vocab, 8)
        self._run(eng, prompt, prefix_key="k")
        other = (prompt + 1) % CFG.vocab
        bad = eng.submit(other, max_new_tokens=4, prefix_key="k")
        good = eng.submit(prompt, max_new_tokens=4)
        while not (bad.done and good.done):
            eng.step()
        with pytest.raises(ValueError, match="stored"):
            eng.result(bad)
        assert eng.result(good) == _reference_tokens(params, prompt, 4)

    def test_shorter_declared_prefix_len_on_hit(self, params):
        # stored key covers the whole first prompt; a later caller reuses
        # only its declared (shorter) shared prefix
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        rng = np.random.default_rng(25)
        first = rng.integers(0, CFG.vocab, 12)
        a = self._run(eng, first, prefix_key="sys")   # stores plen=12
        second = np.concatenate([first[:6],
                                 rng.integers(0, CFG.vocab, 4)])
        b = self._run(eng, second, prefix_key="sys", prefix_len=6)
        assert b == _reference_tokens(params, second, 6)
        assert eng.stats["prefix_hits"] == 1

    def test_prefix_len_validation(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        prompt = np.arange(5) % CFG.vocab
        with pytest.raises(ValueError, match="prefix_len without"):
            eng.submit(prompt, max_new_tokens=2, prefix_len=3)
        with pytest.raises(ValueError, match="out of range"):
            eng.submit(prompt, max_new_tokens=2, prefix_key="x",
                       prefix_len=9)

    def test_store_eviction(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                prefix_cache_size=2)
        rng = np.random.default_rng(23)
        prompts = {f"k{i}": rng.integers(0, CFG.vocab, 6)
                   for i in range(3)}
        for key, p in prompts.items():
            self._run(eng, p, prefix_key=key, n=2)
        assert len(eng._prefix_store) == 2
        assert "k0" not in eng._prefix_store   # FIFO evicted

    def test_sampled_requests_with_prefix(self, params):
        # sampling composes with prefix reuse (same seed → same tokens)
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        rng = np.random.default_rng(24)
        sys_prompt = rng.integers(0, CFG.vocab, 6)
        prompt = np.concatenate([sys_prompt, rng.integers(0, CFG.vocab, 3)])
        a = self._run(eng, prompt, temperature=0.8, seed=11)
        b = self._run(eng, prompt, temperature=0.8, seed=11,
                      prefix_key="s", prefix_len=len(sys_prompt))
        c = self._run(eng, prompt, temperature=0.8, seed=11,
                      prefix_key="s", prefix_len=len(sys_prompt))
        assert a == b == c
        assert eng.stats["prefix_hits"] == 1

    def test_prefix_cache_disabled_by_cap_zero(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                prefix_cache_size=0)
        rng = np.random.default_rng(26)
        prompt = rng.integers(0, CFG.vocab, 7)
        a = self._run(eng, prompt, prefix_key="k")   # store disabled, no crash
        b = self._run(eng, prompt, prefix_key="k")
        assert a == b == _reference_tokens(params, prompt, 6)
        assert (eng.stats["prefills"], eng.stats["prefix_hits"]) == (2, 0)

    def test_unhashable_prefix_key_rejected_at_submit(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        with pytest.raises(ValueError, match="must be a string"):
            eng.submit(np.arange(4) % CFG.vocab, max_new_tokens=2,
                       prefix_key=["a"])


class TestGenerateEos:
    def test_eos_repeats_and_paths_agree(self, params):
        from mmlspark_tpu.models.zoo.transformer import generate
        rng = np.random.default_rng(60)
        prompt = jnp.asarray(rng.integers(0, CFG.vocab, (2, 5)))
        # pick the greedy first token of row 0 as the eos: it must fire
        base = np.asarray(generate_cached(params, prompt, CFG,
                                          max_new_tokens=8))
        eos = int(base[0, 5])
        a = np.asarray(generate(params, prompt, CFG, max_new_tokens=8,
                                eos_id=eos))
        b = np.asarray(generate_cached(params, prompt, CFG,
                                       max_new_tokens=8, eos_id=eos))
        np.testing.assert_array_equal(a, b)      # paths stay compatible
        assert (a[0, 5:] == eos).all()           # fired at first emit
        # rows that never hit eos match the unconstrained run
        if not (base[1, 5:] == eos).any():
            np.testing.assert_array_equal(a[1], base[1])

    def test_eos_none_unchanged(self, params):
        rng = np.random.default_rng(61)
        prompt = jnp.asarray(rng.integers(0, CFG.vocab, (1, 4)))
        a = np.asarray(generate_cached(params, prompt, CFG,
                                       max_new_tokens=6))
        b = np.asarray(generate_cached(params, prompt, CFG,
                                       max_new_tokens=6, eos_id=None))
        np.testing.assert_array_equal(a, b)


class TestBatchedAdmission:
    def test_same_bucket_prompts_prefill_once(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=4, max_len=48)
        rng = np.random.default_rng(70)
        prompts = [rng.integers(0, CFG.vocab, 6) for _ in range(3)]
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        while not all(r.done for r in reqs):
            eng.step()
        assert eng.stats["prefills"] == 1          # one batched call
        for p, r in zip(prompts, reqs):
            assert eng.result(r) == _reference_tokens(params, p, 5)

    def test_mixed_buckets_and_sampling(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=4, max_len=48)
        rng = np.random.default_rng(71)
        short = rng.integers(0, CFG.vocab, 3)       # bucket 8
        long_ = rng.integers(0, CFG.vocab, 12)      # bucket 16
        r1 = eng.submit(short, max_new_tokens=4, temperature=0.7, seed=5)
        r2 = eng.submit(long_, max_new_tokens=4)
        while not (r1.done and r2.done):
            eng.step()
        assert eng.stats["prefills"] == 2           # one per bucket
        # sampled request matches the offline generator seed-for-seed
        want = generate_cached(params, np.asarray(short)[None], CFG,
                               max_new_tokens=4, temperature=0.7, seed=5)
        assert eng.result(r1) == list(np.asarray(want)[0, 3:])
        assert eng.result(r2) == _reference_tokens(params, long_, 4)

    def test_many_instant_requests_no_recursion_blowup(self, params):
        # hundreds of instantly-finishing requests must admit in constant
        # stack (regression: tail-recursive re-admission)
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48)
        rng = np.random.default_rng(72)
        reqs = [eng.submit(rng.integers(0, CFG.vocab, 4), max_new_tokens=1)
                for _ in range(300)]
        while not all(r.done for r in reqs):
            eng.step()
        assert all(len(r.tokens) == 1 for r in reqs)


class TestMultiStepDispatch:
    """``steps_per_dispatch=k``: k ragged decode steps fused into ONE
    device dispatch (lax.scan) — behind a network-attached chip every
    dispatch pays ~RTT, so the single-step engine is RTT-bound regardless
    of chip speed. Token streams must be identical to k single-step
    ticks: retirement (remaining counter + eos) happens inside the scan."""

    def _run(self, params, k, prompts, maxnews, eos=None, **subkw):
        eng = ContinuousDecoder(params, CFG, max_slots=3, max_len=48,
                                steps_per_dispatch=k, eos_id=eos)
        reqs = [eng.submit(p, max_new_tokens=m, **subkw)
                for p, m in zip(prompts, maxnews)]
        for _ in range(300):
            if all(r.done for r in reqs):
                break
            eng.step()
        return [eng.result(r, timeout=5) for r in reqs]

    def _workload(self, seed=0):
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, CFG.vocab, int(rng.integers(3, 10)))
                   for _ in range(7)]
        return prompts, [5, 1, 9, 3, 12, 7, 2]

    def test_greedy_identical_across_k(self, params):
        prompts, maxnews = self._workload()
        a = self._run(params, 1, prompts, maxnews)
        assert self._run(params, 4, prompts, maxnews) == a
        assert self._run(params, 7, prompts, maxnews) == a
        # and each stream matches the offline generator
        for p, m, got in zip(prompts, maxnews, a):
            assert got == _reference_tokens(params, p, m)

    def test_eos_retires_mid_scan(self, params):
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, CFG.vocab, 4)
        full = _reference_tokens(params, prompt, 12)
        # an eos whose FIRST occurrence is mid-scan for k=4 (index != 3)
        stop = next(j for j in range(1, 12)
                    if full[j] not in full[:j] and j % 4 != 3)
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=48,
                                steps_per_dispatch=4, eos_id=full[stop])
        req = eng.submit(prompt, max_new_tokens=12)
        while not req.done:
            eng.step()
        assert eng.result(req) == full[:stop + 1]
        assert eng._slot_req == [None]

    def test_sampled_identical_across_k(self, params):
        prompts, maxnews = self._workload(seed=3)
        a = self._run(params, 1, prompts, maxnews,
                      temperature=0.8, top_k=10, seed=11)
        b = self._run(params, 4, prompts, maxnews,
                      temperature=0.8, top_k=10, seed=11)
        assert a == b

    def test_slot_turnover_with_queueing(self, params):
        # more requests than slots: freed slots re-admit at dispatch
        # granularity, results still exact
        rng = np.random.default_rng(9)
        prompts = [rng.integers(0, CFG.vocab, 3 + i % 5) for i in range(9)]
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                steps_per_dispatch=5)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        for _ in range(300):
            if all(r.done for r in reqs):
                break
            eng.step()
        for p, r in zip(prompts, reqs):
            assert eng.result(r) == _reference_tokens(params, p, 6)

    def test_validation(self, params):
        import pytest
        with pytest.raises(ValueError, match="steps_per_dispatch"):
            ContinuousDecoder(params, CFG, max_slots=1, max_len=16,
                              steps_per_dispatch=0)


class TestPipelinedDispatch:
    """``pipeline_depth=d``: up to d token blocks stay in flight while the
    host drains the oldest — the fetch was the only sync on the decode
    path and serialized every tick at ~RTT. Outputs must be identical at
    every depth (device-side retirement makes the host's lagged view
    safe), and ``flush()`` must surface all emitted tokens."""

    def _run(self, params, depth, prompts, maxnews, k=3, eos=None):
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                steps_per_dispatch=k, eos_id=eos,
                                pipeline_depth=depth)
        reqs = [eng.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, maxnews)]
        for _ in range(400):
            if all(r.done for r in reqs):
                break
            eng.step()
        return [eng.result(r, timeout=5) for r in reqs]

    def test_identical_across_depths(self, params):
        rng = np.random.default_rng(21)
        prompts = [rng.integers(0, CFG.vocab, int(rng.integers(3, 9)))
                   for _ in range(6)]
        maxnews = [6, 2, 9, 4, 11, 7]
        a = self._run(params, 0, prompts, maxnews)     # fully synchronous
        assert self._run(params, 2, prompts, maxnews) == a
        assert self._run(params, 4, prompts, maxnews) == a
        for p, m, got in zip(prompts, maxnews, a):
            assert got == _reference_tokens(params, p, m)

    def test_flush_drains_outstanding_blocks(self, params):
        rng = np.random.default_rng(22)
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=48,
                                steps_per_dispatch=2, pipeline_depth=3)
        req = eng.submit(rng.integers(0, CFG.vocab, 4), max_new_tokens=8)
        # step a few times WITHOUT letting the drain catch up fully
        for _ in range(3):
            eng.step()
        pending_before = len(eng._pending)
        eng.flush()
        assert not eng._pending
        # prefill emits 1 + 2 tokens per drained tick block
        assert len(req.tokens) >= min(8, 1 + 2 * pending_before)
        while not req.done:
            eng.step()
        assert eng.result(req) == _reference_tokens(
            params, np.asarray(req.prompt), 8)

    def test_negative_depth_rejected(self, params):
        import pytest
        with pytest.raises(ValueError, match="pipeline_depth"):
            ContinuousDecoder(params, CFG, max_slots=1, max_len=16,
                              pipeline_depth=-1)

    def test_saturated_pool_drains_eagerly(self, params):
        # with a backlog and a full pool, the engine drains outstanding
        # blocks to free slots NOW rather than pipeline_depth ticks later
        # (r5 sweep: depth was monotone harmful at k=8 because retiring
        # requests held slots k*depth extra steps). Deep pipelines must
        # not cost extra engine steps under saturation — and outputs stay
        # identical.
        rng = np.random.default_rng(23)
        prompts = [rng.integers(0, CFG.vocab, 4) for _ in range(3)]

        def steps_until_done(depth):
            eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=32,
                                    steps_per_dispatch=2,
                                    pipeline_depth=depth)
            reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
            n = 0
            for _ in range(200):
                if all(r.done for r in reqs):
                    break
                eng.step()
                n += 1
            assert all(r.done for r in reqs)
            return n, [eng.result(r) for r in reqs]

        n0, out0 = steps_until_done(0)
        n4, out4 = steps_until_done(4)
        assert out4 == out0
        # one depth-sized drain lag is paid once at the tail (the last
        # request has no backlog behind it to trigger the eager drain);
        # WITHOUT the eager drain every request would pay it:
        # ~len(prompts) * (depth + 1) steps ≈ 15 here
        assert n4 <= n0 + 4 + 1, (n4, n0)


class TestGridStepCounters:
    """Pool ``grid_steps`` / ``grid_steps_dense``: the paged kernel's
    ragged sweep counted from the scheduler's positions, no device read."""

    @pytest.mark.parametrize("depth,k", [(0, 1), (2, 1), (2, 3)])
    def test_counted_positions_are_the_devices(self, params, depth, k):
        eng = ContinuousDecoder(params, CFG, max_slots=3, max_len=48,
                                page_size=8, steps_per_dispatch=k,
                                pipeline_depth=depth)
        seen, noted = [], []
        dispatch, note = eng._dispatch_tick, eng._kv.note_grid_steps

        def dispatching(live):
            on = np.asarray(eng._active)
            seen.append([(j, int(np.asarray(eng._pos)[i]))
                         for j, i in enumerate(live) if on[i]])
            return dispatch(live)

        def noting(positions, window, rows):
            noted.append((list(positions), window, rows))
            return note(positions, window, rows)

        eng._dispatch_tick, eng._kv.note_grid_steps = dispatching, noting
        rng = np.random.default_rng(31)
        reqs = []
        # the second pair arrives while blocks are in flight, as a closed
        # loop's requests do: their first tokens queue behind those blocks
        for pair in (((3, 14), (11, 9)), ((6, 12), (4, 5))):
            reqs += [eng.submit(rng.integers(0, CFG.vocab, n),
                                max_new_tokens=m) for n, m in pair]
            eng.step()
            eng.step()
        while not all(r.done for r in reqs):
            eng.step()
        assert len(noted) == k * len(seen) > 0
        for t, device in enumerate(seen):
            positions, window, rows = noted[k * t]
            assert (window, rows) == (1, 3)
            # a row the device has retired ahead of the host is still
            # counted at the position the host holds for it
            assert all(positions[j] == pos for j, pos in device)
            assert noted[k * t + k - 1][0] == [p + k - 1 for p in positions]
        stats = eng._kv.stats
        assert 0 < stats["grid_steps"] < stats["grid_steps_dense"]
        assert stats["grid_steps_dense"] == len(noted) * 3 * 6

    def test_equal_when_every_row_is_full(self, params):
        """One slot of two pages, a prompt past the first page: every tick
        sweeps both, and the share's gauge reads 1."""
        from mmlspark_tpu.serving.kv_pool import M_GRID_STEPS_SHARE
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=16,
                                page_size=8)
        req = eng.submit(np.arange(1, 10), max_new_tokens=6)
        while not req.done:
            eng.step()
        stats = eng._kv.stats
        assert stats["grid_steps"] == stats["grid_steps_dense"] > 0
        assert M_GRID_STEPS_SHARE.labels().get() == 1.0

    def test_chunk_windows_and_the_gather_path(self, params):
        """A chunked prefill's windows are counted too (one row, the pages
        its window ends in); the gather path runs no grid."""
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                page_size=8, prefill_chunk=8)
        req = eng.submit(np.arange(1, 30), max_new_tokens=2)
        while not req.done:
            eng.step()
        stats = eng._kv.stats
        assert stats["prefill_chunks"] >= 3
        assert 0 < stats["grid_steps"] < stats["grid_steps_dense"]
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                page_size=8, paged_attn="gather")
        req = eng.submit(np.arange(1, 9), max_new_tokens=4)
        while not req.done:
            eng.step()
        assert eng._kv.stats["grid_steps_dense"] == 0


class TestPrefillAhead:
    """``prefill_ahead=N``: waiting prompts prefill while every slot is
    occupied and park on device; a retiring wave re-fills with one insert
    dispatch and first tokens ride the drain pipeline. THE invariant:
    outputs are identical to the unstaged engine for every request."""

    def _run(self, params, ahead, prompts, maxnews, *, slots=2, k=3,
             depth=2, eos=None, sampling=None):
        eng = ContinuousDecoder(params, CFG, max_slots=slots, max_len=48,
                                steps_per_dispatch=k, pipeline_depth=depth,
                                eos_id=eos, prefill_ahead=ahead)
        reqs = []
        for i, (p, m) in enumerate(zip(prompts, maxnews)):
            kw = dict(sampling or {})
            if sampling:
                kw["seed"] = i
            reqs.append(eng.submit(p, max_new_tokens=m, **kw))
        for _ in range(600):
            if all(r.done for r in reqs):
                break
            eng.step()
        return [eng.result(r, timeout=5) for r in reqs], eng

    def test_greedy_identical_with_and_without_staging(self, params):
        rng = np.random.default_rng(31)
        prompts = [rng.integers(0, CFG.vocab, int(rng.integers(3, 10)))
                   for _ in range(7)]
        maxnews = [5, 9, 2, 7, 4, 11, 6]
        base, _ = self._run(params, 0, prompts, maxnews)
        staged, eng = self._run(params, 6, prompts, maxnews)
        assert staged == base
        assert eng.stats.get("staged_prefills", 0) > 0  # path exercised
        for p, m, got in zip(prompts, maxnews, base):
            assert got == _reference_tokens(params, p, m)

    def test_partial_unit_insertion_across_waves(self, params):
        """A staged unit larger than the freed-slot count inserts across
        several admissions (slots=2, 5 one-bucket prompts, budget 4)."""
        rng = np.random.default_rng(32)
        prompts = [rng.integers(0, CFG.vocab, 5) for _ in range(5)]
        maxnews = [3, 3, 4, 4, 5]
        staged, eng = self._run(params, 4, prompts, maxnews)
        assert not eng._staged                      # fully consumed
        for p, m, got in zip(prompts, maxnews, staged):
            assert got == _reference_tokens(params, p, m)

    def test_sampled_requests_identical_with_staging(self, params):
        rng = np.random.default_rng(33)
        prompts = [rng.integers(0, CFG.vocab, 6) for _ in range(5)]
        maxnews = [6, 5, 7, 4, 6]
        sampling = dict(temperature=0.9, top_k=8)
        base, _ = self._run(params, 0, prompts, maxnews, sampling=sampling)
        staged, _ = self._run(params, 5, prompts, maxnews,
                              sampling=sampling)
        assert staged == base

    def test_eos_retirement_with_staging(self, params):
        rng = np.random.default_rng(34)
        prompts = [rng.integers(0, CFG.vocab, 4) for _ in range(4)]
        full = [_reference_tokens(params, p, 10) for p in prompts]
        eos = full[0][2]
        base, _ = self._run(params, 0, prompts, [10] * 4, slots=1,
                            eos=eos)
        staged, _ = self._run(params, 4, prompts, [10] * 4, slots=1,
                              eos=eos)
        assert staged == base

    def test_cancel_all_fails_staged_requests(self, params):
        rng = np.random.default_rng(35)
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=48,
                                prefill_ahead=4)
        reqs = [eng.submit(rng.integers(0, CFG.vocab, 4), 8)
                for _ in range(4)]
        eng.step()                      # admit one, stage the rest
        assert eng._staged
        cancelled = eng.cancel_all()
        assert set(map(id, cancelled)) == set(map(id, reqs))
        assert all(r.done for r in reqs)
        assert not eng._staged

    def test_prefix_requests_not_staged_and_fifo_holds(self, params):
        """Staging stops at the first prefix-cache request so FIFO order
        (and the per-request suffix path) is preserved; everything still
        matches the reference."""
        rng = np.random.default_rng(36)
        pre = rng.integers(0, CFG.vocab, 6)
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=48,
                                prefill_ahead=4)
        plain = [rng.integers(0, CFG.vocab, 4) for _ in range(2)]
        r0 = eng.submit(plain[0], 4)
        rp = eng.submit(pre, 4, prefix_key="sys")
        r1 = eng.submit(plain[1], 4)
        for _ in range(200):
            if all(r.done for r in (r0, rp, r1)):
                break
            eng.step()
        assert eng.result(r0) == _reference_tokens(params, plain[0], 4)
        assert eng.result(rp) == _reference_tokens(params, pre, 4)
        assert eng.result(r1) == _reference_tokens(params, plain[1], 4)

    def test_negative_budget_rejected(self, params):
        import pytest
        with pytest.raises(ValueError, match="prefill_ahead"):
            ContinuousDecoder(params, CFG, max_slots=1, max_len=16,
                              prefill_ahead=-1)

    def test_mixed_bucket_fifo_order_preserved(self, params):
        """Staging stops at a pad-bucket change, so a later-bucket prompt
        can never be admitted before an earlier-submitted one (first-token
        timestamps must follow submission order with slots=1)."""
        rng = np.random.default_rng(37)
        lengths = [5, 20, 5, 20]          # alternating pad buckets
        prompts = [rng.integers(0, CFG.vocab, n) for n in lengths]
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=48,
                                prefill_ahead=8)
        reqs = [eng.submit(p, 4) for p in prompts]
        for _ in range(400):
            if all(r.done for r in reqs):
                break
            eng.step()
        stamps = [r.first_token_at for r in reqs]
        assert stamps == sorted(stamps)
        for p, r in zip(prompts, reqs):
            assert eng.result(r) == _reference_tokens(params, p, 4)

    def test_budget_charges_padded_rows(self, params):
        """A staged unit holds its power-of-two padded row buffer until it
        fully drains, so the budget charges padded rows: 5 same-bucket
        prompts under prefill_ahead=5 stage 4 (padded 4 <= 5; a fifth
        would repad to 8)."""
        rng = np.random.default_rng(38)
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=48,
                                prefill_ahead=5)
        reqs = [eng.submit(rng.integers(0, CFG.vocab, 5), 6)
                for _ in range(6)]
        eng.step()          # admit 1st; stage from the remaining 5
        assert sum(len(u[0]) for u in eng._staged) == 4
        for _ in range(400):
            if all(r.done for r in reqs):
                break
            eng.step()
        for r in reqs:
            assert eng.result(r) == _reference_tokens(
                params, np.asarray(r.prompt), 6)

    def test_failed_staged_prefill_restores_waiting(self, params):
        """A background prefill that raises must put its requests back at
        the head of _waiting (order intact) so cancel_all can reach them
        — not strand them outside every queue."""
        rng = np.random.default_rng(39)
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=48,
                                prefill_ahead=4)
        reqs = [eng.submit(rng.integers(0, CFG.vocab, 4), 6)
                for _ in range(3)]
        boom = RuntimeError("device fell over")
        real = eng._prefill
        calls = {"n": 0}

        def flaky(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 2:           # the background staging call
                raise boom
            return real(*a, **kw)

        eng._prefill = flaky
        import pytest
        with pytest.raises(RuntimeError, match="fell over"):
            eng.step()
        waiting_ids = [r.rid for r in eng._waiting]
        assert waiting_ids == [reqs[1].rid, reqs[2].rid]
        cancelled = eng.cancel_all()
        assert all(r.done for r in reqs)
        assert {r.rid for r in cancelled} == {r.rid for r in reqs}


class TestSpeculativePool:
    """Speculative decoding inside the slot pool: per-slot draft→verify
    rounds. THE invariant: greedy outputs are request-identical to the
    plain engine (accepted tokens are the target's own greedy choices) —
    for a perfect draft, a garbage draft, and anything between; the draft
    only changes throughput."""

    D_CFG = TransformerConfig(vocab=128, layers=1, d_model=32, heads=2,
                              d_ff=64, max_len=64, causal=True,
                              norm="rmsnorm", position="rope",
                              dtype=jnp.float32)

    def _run(self, params, draft, prompts, maxnews, *, slots=2, k=2,
             gamma=3, depth=2, ahead=0, eos=None, d_cfg=None):
        eng = ContinuousDecoder(params, CFG, max_slots=slots, max_len=48,
                                steps_per_dispatch=k, pipeline_depth=depth,
                                prefill_ahead=ahead, eos_id=eos,
                                draft_params=draft,
                                draft_cfg=d_cfg or self.D_CFG,
                                gamma=gamma)
        reqs = [eng.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, maxnews)]
        for _ in range(600):
            if all(r.done for r in reqs):
                break
            eng.step()
        return [eng.result(r, timeout=5) for r in reqs], eng

    def test_perfect_draft_identical_and_accepts(self, params):
        """Draft == target: full acceptance, outputs still reference."""
        rng = np.random.default_rng(41)
        prompts = [rng.integers(0, CFG.vocab, int(rng.integers(3, 9)))
                   for _ in range(5)]
        maxnews = [7, 3, 9, 5, 8]
        got, eng = self._run(params, params, prompts, maxnews,
                             d_cfg=CFG)
        for p, m, g in zip(prompts, maxnews, got):
            assert g == _reference_tokens(params, p, m)
        # perfect draft: every round advances gamma+1 per live slot
        acc = (eng.stats["spec_emitted"]
               / max(eng.stats["spec_round_slots"], 1))
        assert acc > 1.5, eng.stats    # well beyond 1 token/round

    def test_weak_draft_identical(self, params):
        """A differently-initialized 1-layer draft: low acceptance, but
        outputs must not change by a single token."""
        rng = np.random.default_rng(42)
        draft = init_transformer(self.D_CFG, seed=99)
        prompts = [rng.integers(0, CFG.vocab, int(rng.integers(3, 10)))
                   for _ in range(6)]
        maxnews = [6, 2, 9, 4, 1, 7]
        got, _ = self._run(params, draft, prompts, maxnews)
        for p, m, g in zip(prompts, maxnews, got):
            assert g == _reference_tokens(params, p, m)

    def test_staggered_and_contended(self, params):
        rng = np.random.default_rng(43)
        draft = init_transformer(self.D_CFG, seed=7)
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                steps_per_dispatch=2, gamma=3,
                                draft_params=draft, draft_cfg=self.D_CFG)
        prompts = [rng.integers(0, CFG.vocab, n) for n in (3, 9, 5, 7)]
        maxnews = [6, 4, 8, 5]
        reqs = [eng.submit(prompts[0], maxnews[0])]
        eng.step()
        reqs += [eng.submit(p, m)
                 for p, m in zip(prompts[1:], maxnews[1:])]
        for _ in range(400):
            if all(r.done for r in reqs):
                break
            eng.step()
        for p, m, r in zip(prompts, maxnews, reqs):
            assert eng.result(r, timeout=5) == _reference_tokens(
                params, p, m)

    def test_eos_truncates_inside_accepted_prefix(self, params):
        rng = np.random.default_rng(44)
        prompts = [rng.integers(0, CFG.vocab, 4) for _ in range(3)]
        full = [_reference_tokens(params, p, 12) for p in prompts]
        eos = full[0][2]
        # perfect draft maximizes the chance the eos lands mid-window
        got, _ = self._run(params, params, prompts, [12] * 3, slots=2,
                           gamma=4, eos=eos, d_cfg=CFG)
        for p, g in zip(prompts, got):
            want = _reference_tokens(params, p, 12)
            stop = want.index(eos) + 1 if eos in want else 12
            assert g == want[:stop]

    def test_prefill_ahead_composes(self, params):
        rng = np.random.default_rng(45)
        draft = init_transformer(self.D_CFG, seed=3)
        prompts = [rng.integers(0, CFG.vocab, 5) for _ in range(6)]
        maxnews = [5, 7, 4, 6, 8, 3]
        base, _ = self._run(params, draft, prompts, maxnews)
        staged, eng = self._run(params, draft, prompts, maxnews, ahead=4)
        assert staged == base
        assert eng.stats.get("staged_prefills", 0) > 0

    def test_validation(self, params):
        import pytest
        draft = init_transformer(self.D_CFG, seed=1)
        with pytest.raises(ValueError, match="draft_cfg"):
            ContinuousDecoder(params, CFG, max_slots=1, max_len=16,
                              draft_params=draft)
        bad = self.D_CFG._replace(vocab=64)
        with pytest.raises(ValueError, match="vocab"):
            ContinuousDecoder(params, CFG, max_slots=1, max_len=16,
                              draft_params=init_transformer(bad, seed=1),
                              draft_cfg=bad)
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=32,
                                draft_params=draft, draft_cfg=self.D_CFG)
        # sampled submits are allowed (per-slot rejection correction);
        # only the unsupported top-k/top-p warps are rejected
        eng.submit(np.asarray([1, 2, 3]), 4, temperature=0.5)

    def test_prefix_caching_composes(self, params):
        """Prefix-cache requests work in spec mode: the target reuses the
        stored prefix (prefix_hits increments), the draft re-prefills the
        whole prompt, outputs stay reference-exact."""
        rng = np.random.default_rng(46)
        draft = init_transformer(self.D_CFG, seed=5)
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                steps_per_dispatch=2, gamma=3,
                                draft_params=draft, draft_cfg=self.D_CFG)
        sys_prefix = rng.integers(0, CFG.vocab, 6)
        prompts = [np.concatenate([sys_prefix,
                                   rng.integers(0, CFG.vocab, 3)])
                   for _ in range(3)]
        reqs = [eng.submit(p, 5, prefix_key="sys", prefix_len=6)
                for p in prompts]
        for _ in range(300):
            if all(r.done for r in reqs):
                break
            eng.step()
        for p, r in zip(prompts, reqs):
            assert eng.result(r, timeout=5) == _reference_tokens(
                params, p, 5)
        assert eng.stats["prefix_hits"] == 2   # req 1 stores; 2 and 3 hit


class TestSpeculativePoolSampled:
    """Sampled requests in the speculative pool: per-slot rejection
    correction. Contract is DISTRIBUTIONAL (exactly target-distributed;
    bit-identity to the plain engine is impossible), so the test checks
    empirical marginals against enumerated target probabilities; greedy
    requests in the same pool stay bit-exact."""

    V_CFG = TransformerConfig(vocab=32, layers=2, d_model=32, heads=4,
                              d_ff=64, max_len=64, causal=True,
                              norm="rmsnorm", position="rope",
                              dtype=jnp.float32)
    D32 = V_CFG._replace(layers=1, d_model=16, heads=2, d_ff=32)
    TEMP = 1.3

    def test_sampled_marginals_match_target(self):
        from mmlspark_tpu.models.zoo.transformer import prefill_cache
        t_params = init_transformer(self.V_CFG, seed=1)
        d_params = init_transformer(self.D32, seed=7)
        prompt = np.asarray([3, 11, 4, 17], np.int32)
        N, V = 512, self.V_CFG.vocab
        eng = ContinuousDecoder(t_params, self.V_CFG, max_slots=16,
                                max_len=32, steps_per_dispatch=2,
                                draft_params=d_params, draft_cfg=self.D32,
                                gamma=2)
        reqs = [eng.submit(prompt, 2, temperature=self.TEMP, seed=i)
                for i in range(N)]
        for _ in range(4000):
            if all(r.done for r in reqs):
                break
            eng.step()
        toks = np.asarray([r.tokens for r in reqs])          # (N, 2)
        # exact marginals by enumeration (same recipe as the zoo test)
        lengths = jnp.asarray([4], jnp.int32)
        logits, cache = prefill_cache(t_params, jnp.asarray(prompt[None]),
                                      lengths, self.V_CFG, 8)
        p1 = np.asarray(jax.nn.softmax(
            logits.astype(jnp.float32) / self.TEMP, -1))[0]
        cacheV = [{k: jnp.repeat(c[k], V, axis=0) for k in ("k", "v")}
                  for c in cache]
        l2, _ = decode_step(t_params, jnp.arange(V, dtype=jnp.int32),
                            4, cacheV, self.V_CFG)
        p2_given = np.asarray(jax.nn.softmax(
            l2.astype(jnp.float32) / self.TEMP, -1))
        p2 = p1 @ p2_given
        emp1 = np.bincount(toks[:, 0], minlength=V) / N
        emp2 = np.bincount(toks[:, 1], minlength=V) / N
        assert np.abs(emp1 - p1).max() < 0.055, np.abs(emp1 - p1).max()
        assert np.abs(emp2 - p2).max() < 0.055, np.abs(emp2 - p2).max()

    def test_mixed_pool_keeps_greedy_bit_exact(self, params):
        draft = init_transformer(
            CFG._replace(layers=1, d_model=32, heads=2, d_ff=64), seed=5)
        eng = ContinuousDecoder(
            params, CFG, max_slots=2, max_len=48, steps_per_dispatch=2,
            draft_params=draft,
            draft_cfg=CFG._replace(layers=1, d_model=32, heads=2,
                                   d_ff=64), gamma=3)
        rng = np.random.default_rng(51)
        g_prompt = rng.integers(0, CFG.vocab, 5)
        s_prompt = rng.integers(0, CFG.vocab, 6)
        g = eng.submit(g_prompt, 7)                       # greedy
        s = eng.submit(s_prompt, 7, temperature=0.9, seed=4)  # sampled
        for _ in range(200):
            if g.done and s.done:
                break
            eng.step()
        assert eng.result(g, timeout=5) == _reference_tokens(
            params, g_prompt, 7)
        assert len(eng.result(s, timeout=5)) == 7
        assert all(0 <= t < CFG.vocab for t in s.tokens)

    def test_eos_with_sampled_spec(self, params):
        draft = init_transformer(
            CFG._replace(layers=1, d_model=32, heads=2, d_ff=64), seed=5)
        eng = ContinuousDecoder(
            params, CFG, max_slots=1, max_len=48, steps_per_dispatch=2,
            eos_id=7, draft_params=draft,
            draft_cfg=CFG._replace(layers=1, d_model=32, heads=2,
                                   d_ff=64), gamma=2)
        rng = np.random.default_rng(52)
        req = eng.submit(rng.integers(0, CFG.vocab, 4), 20,
                         temperature=1.5, seed=9)
        for _ in range(200):
            if req.done:
                break
            eng.step()
        got = eng.result(req, timeout=5)
        assert 1 <= len(got) <= 20
        assert 7 not in got[:-1]          # eos only ever terminal

    def test_topk_marginals_match_warped_target(self):
        """top-k sampling under speculation: the warp applies to BOTH
        distributions before the ratio test, so outputs are exactly
        top-k-warped-target distributed — checked against enumerated
        warped marginals for the second token (the first goes through
        the plain admission sampler)."""
        from mmlspark_tpu.models.zoo.transformer import prefill_cache
        t_params = init_transformer(self.V_CFG, seed=1)
        d_params = init_transformer(self.D32, seed=7)
        prompt = np.asarray([3, 11, 4, 17], np.int32)
        N, V, TOPK = 512, self.V_CFG.vocab, 3
        eng = ContinuousDecoder(t_params, self.V_CFG, max_slots=16,
                                max_len=32, steps_per_dispatch=2,
                                draft_params=d_params, draft_cfg=self.D32,
                                gamma=2)
        reqs = [eng.submit(prompt, 2, temperature=self.TEMP, top_k=TOPK,
                           seed=i) for i in range(N)]
        for _ in range(4000):
            if all(r.done for r in reqs):
                break
            eng.step()
        toks = np.asarray([r.tokens for r in reqs])

        def warp(logits_row):
            scaled = logits_row / self.TEMP
            kth = np.sort(scaled)[::-1][TOPK - 1]
            keep = scaled >= kth
            e = np.where(keep, np.exp(scaled - scaled.max()), 0.0)
            return e / e.sum()

        lengths = jnp.asarray([4], jnp.int32)
        logits, cache = prefill_cache(t_params, jnp.asarray(prompt[None]),
                                      lengths, self.V_CFG, 8)
        p1 = warp(np.asarray(logits, np.float64)[0])
        cacheV = [{k: jnp.repeat(c[k], V, axis=0) for k in ("k", "v")}
                  for c in cache]
        l2, _ = decode_step(t_params, jnp.arange(V, dtype=jnp.int32),
                            4, cacheV, self.V_CFG)
        p2_given = np.stack([warp(row)
                             for row in np.asarray(l2, np.float64)])
        p2 = p1 @ p2_given
        emp1 = np.bincount(toks[:, 0], minlength=V) / N
        emp2 = np.bincount(toks[:, 1], minlength=V) / N
        assert np.abs(emp1 - p1).max() < 0.06, np.abs(emp1 - p1).max()
        assert np.abs(emp2 - p2).max() < 0.06, np.abs(emp2 - p2).max()
        # the warp is real: nothing outside the reachable top-k sets
        assert set(np.unique(toks[:, 0])) <= set(np.nonzero(p1)[0])
        assert set(np.unique(toks[:, 1])) <= set(np.nonzero(p2)[0])

    def test_topp_marginals_match_warped_target(self):
        """Nucleus (top-p) sampling under speculation: exact
        warped-target marginals, HF convention (cutoff over the sorted
        renormalized mass, keep through the crossing token)."""
        from mmlspark_tpu.models.zoo.transformer import prefill_cache
        t_params = init_transformer(self.V_CFG, seed=1)
        d_params = init_transformer(self.D32, seed=7)
        prompt = np.asarray([3, 11, 4, 17], np.int32)
        N, V, TOPP = 512, self.V_CFG.vocab, 0.6
        eng = ContinuousDecoder(t_params, self.V_CFG, max_slots=16,
                                max_len=32, steps_per_dispatch=2,
                                draft_params=d_params, draft_cfg=self.D32,
                                gamma=2)
        reqs = [eng.submit(prompt, 2, temperature=self.TEMP, top_p=TOPP,
                           seed=i) for i in range(N)]
        for _ in range(4000):
            if all(r.done for r in reqs):
                break
            eng.step()
        toks = np.asarray([r.tokens for r in reqs])

        def warp(logits_row):
            scaled = np.asarray(logits_row, np.float64) / self.TEMP
            probs = np.exp(scaled - scaled.max())
            probs /= probs.sum()
            order = np.argsort(-scaled)
            cum = np.cumsum(probs[order])
            keep_n = int(np.sum(cum < TOPP)) + 1   # through the crossing
            kept = order[:keep_n]
            out = np.zeros_like(probs)
            out[kept] = probs[kept] / probs[kept].sum()
            return out

        lengths = jnp.asarray([4], jnp.int32)
        logits, cache = prefill_cache(t_params, jnp.asarray(prompt[None]),
                                      lengths, self.V_CFG, 8)
        p1 = warp(np.asarray(logits)[0])
        cacheV = [{k: jnp.repeat(c[k], V, axis=0) for k in ("k", "v")}
                  for c in cache]
        l2, _ = decode_step(t_params, jnp.arange(V, dtype=jnp.int32),
                            4, cacheV, self.V_CFG)
        p2_given = np.stack([warp(row) for row in np.asarray(l2)])
        p2 = p1 @ p2_given
        emp1 = np.bincount(toks[:, 0], minlength=V) / N
        emp2 = np.bincount(toks[:, 1], minlength=V) / N
        assert np.abs(emp1 - p1).max() < 0.06, np.abs(emp1 - p1).max()
        assert np.abs(emp2 - p2).max() < 0.06, np.abs(emp2 - p2).max()
        assert set(np.unique(toks[:, 0])) <= set(np.nonzero(p1)[0])
        assert set(np.unique(toks[:, 1])) <= set(np.nonzero(p2)[0])
