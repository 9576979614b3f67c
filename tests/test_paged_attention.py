"""Pallas paged-attention kernel (``ops/paged_attention.py``).

The contract this file pins:

1. PARITY — the kernel path (``impl="kernel"``) agrees with PR 7's
   gather path to f32 accumulation-order tolerance on logits (decode
   step AND speculative windows gamma ∈ {1, 4, 16}), with ragged
   per-row positions that cross page boundaries. Greedy argmaxes are
   identical for these seeds, which is what lets the engine default to
   the kernel without perturbing token streams.
2. SCATTER — the fused variant's page writes are BITWISE identical to
   the gather path's separate ``_paged_writeback`` on the first layer
   (later layers inherit the logits' tolerance-level drift through the
   layer stack); inactive rows land in trash page 0, never in pages
   their stale block-table rows still reference.
3. MASKING — a row with zero cached keys (fully-masked fresh slot)
   yields zeros from the read-only kernel, and a ``pos == 0`` row in
   the fused kernel attends only its own window.
4. CI — the whole thing runs under ``JAX_PLATFORMS=cpu`` via Pallas
   interpret mode, and the ``ContinuousDecoder`` smoke test pays zero
   steady-state recompiles once its tick program is warm.
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from mmlspark_tpu.models.zoo.transformer import (
    TransformerConfig, decode_step_paged, decode_step_ragged,
    decode_window_paged, generate_cached, init_kv_cache,
    init_paged_cache, init_transformer, paged_gather, paged_scatter_rows)
from mmlspark_tpu.ops.compile_cache import jit_cache_size
from mmlspark_tpu.ops.kv_quant import SCALE_DTYPE, quantize_kv
from mmlspark_tpu.ops.paged_attention import (
    ENV_KNOB, _HEADS, _block_holds, _fused_schedule, _heads_of,
    _heads_query, _latent_launch, _pa_window_read_call, _pool_write_rows,
    _schedule, _scores, _select_launch, _whole_groups, aligned_page_size,
    latent_block, pack_kv, paged_attention,
    paged_attention_latent, paged_attention_selected,
    paged_attention_window, select_block, split_kv, resolve_impl,
    sublane_multiple)
from mmlspark_tpu.serving.continuous import ContinuousDecoder

CFG = TransformerConfig(vocab=128, layers=2, d_model=64, heads=4, d_ff=128,
                        max_len=96, causal=True, norm="rmsnorm",
                        position="rope", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_transformer(CFG, seed=0)


def _contig_state(params, B, L, steps, rng):
    """Decode `steps` random tokens through the contiguous ragged path."""
    cache = init_kv_cache(CFG, B, L)
    toks = jnp.asarray(rng.integers(0, CFG.vocab, (steps, B)))
    for t in range(steps):
        _, cache = decode_step_ragged(
            params, toks[t], jnp.full((B,), t, jnp.int32), cache, CFG)
    return cache


def mount(kind):
    """Keyword arguments of the kernel's mount: none on one device, the
    ``dp4 x tp2`` mesh of tier-1's eight virtual devices otherwise."""
    if kind == "single":
        return {}
    if jax.device_count() < 8:
        pytest.skip("the mesh mount needs 8 (simulated) devices")
    return dict(mesh=Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                          ("dp", "tp")), slot_axis="dp", head_axis="tp")


def _paged_state(params, B, L, page, steps, rng, kv_dtype=None):
    """Contiguous warm-up scattered into a dense page pool + block table."""
    contig = _contig_state(params, B, L, steps, rng)
    n_pages = L // page
    bt = jnp.asarray(
        1 + np.arange(B)[:, None] * n_pages + np.arange(n_pages),
        jnp.int32)
    pages = paged_scatter_rows(
        init_paged_cache(CFG, 1 + B * n_pages, page, kv_dtype=kv_dtype),
        [{"k": c["k"], "v": c["v"]} for c in contig], bt, page)
    return pages, bt


class TestResolveImpl:
    def test_default_is_kernel(self, monkeypatch):
        monkeypatch.delenv(ENV_KNOB, raising=False)
        assert resolve_impl() == "kernel"

    def test_env_knob_selects_gather(self, monkeypatch):
        for alias in ("gather", "xla", "reference", " GATHER "):
            monkeypatch.setenv(ENV_KNOB, alias)
            assert resolve_impl() == "gather"
        for alias in ("kernel", "fused", "auto", "default"):
            monkeypatch.setenv(ENV_KNOB, alias)
            assert resolve_impl() == "kernel"

    def test_override_beats_env(self, monkeypatch):
        monkeypatch.setenv(ENV_KNOB, "gather")
        assert resolve_impl("kernel") == "kernel"

    def test_unknown_impl_raises(self, monkeypatch):
        monkeypatch.delenv(ENV_KNOB, raising=False)
        with pytest.raises(ValueError):
            resolve_impl("mystery")
        monkeypatch.setenv(ENV_KNOB, "mystery")
        with pytest.raises(ValueError):
            resolve_impl()

    def test_alignment_contract(self):
        # f32 sublane tile is 8; already-compliant sizes are identity
        assert sublane_multiple(jnp.float32) == 8
        assert sublane_multiple(jnp.bfloat16) == 16
        assert aligned_page_size(4, jnp.float32) == 8
        assert aligned_page_size(16, jnp.float32) == 16


class TestOpsKernel:
    """The raw kernel vs a plain-numpy reference (no transformer around
    it) — interpret mode, which is what CI exercises."""

    def _pool(self, rng, N, H, page, hd):
        k = jnp.asarray(rng.normal(0, 1, (N, H, page, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(0, 1, (N, H, page, hd)), jnp.float32)
        return k, v

    def _reference(self, q, kc, vc, lengths):
        """(B,H,W,hd) queries over contiguous (B,H,L,hd) keys, first
        lengths[b] valid; zeros for lengths[b]==0."""
        B, H, W, hd = q.shape
        L = kc.shape[2]
        out = np.zeros_like(q)
        for b in range(B):
            n = int(lengths[b])
            if n == 0:
                continue
            s = np.einsum("hwd,hkd->hwk", q[b], kc[b, :, :n]) / np.sqrt(hd)
            s = s - s.max(-1, keepdims=True)
            p = np.exp(s)
            p /= p.sum(-1, keepdims=True)
            out[b] = np.einsum("hwk,hkd->hwd", p, vc[b, :, :n])
        return out

    def test_read_kernel_ragged_lengths_cross_pages(self):
        B, H, page, hd, P = 4, 2, 4, 8, 3
        rng = np.random.default_rng(7)
        kp, vp = self._pool(rng, 1 + B * P, H, page, hd)
        bt = jnp.asarray(
            1 + np.arange(B)[:, None] * P + np.arange(P), jnp.int32)
        # 0 = fully-masked fresh slot; 3 = mid-page; 4 = exact boundary;
        # 11 = crosses two boundaries into the last page's tail
        lengths = jnp.asarray([0, 3, 4, 11], jnp.int32)
        q = jnp.asarray(rng.normal(0, 1, (B, H, 1, hd)), jnp.float32)
        got = paged_attention(q, pack_kv(kp, vp), bt, lengths,
                              interpret=True)
        kc = np.asarray(kp)[np.asarray(bt)].transpose(0, 2, 1, 3, 4)
        kc = kc.reshape(B, H, P * page, hd)
        vc = np.asarray(vp)[np.asarray(bt)].transpose(0, 2, 1, 3, 4)
        vc = vc.reshape(B, H, P * page, hd)
        want = self._reference(np.asarray(q), kc, vc, np.asarray(lengths))
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=2e-6, atol=2e-6)
        assert np.all(np.asarray(got)[0] == 0.0)   # lengths==0 → zeros

    def test_window_kernel_scatters_and_masks_causally(self):
        B, H, page, hd, P, W = 2, 2, 4, 8, 4, 5
        rng = np.random.default_rng(8)
        kp, vp = self._pool(rng, 1 + B * P, H, page, hd)
        bt = jnp.asarray(
            1 + np.arange(B)[:, None] * P + np.arange(P), jnp.int32)
        # pos=7: window 7..11 straddles a page boundary; pos=0: fresh
        # slot, the window is the row's entire visible context
        pos = jnp.asarray([7, 0], jnp.int32)
        q = jnp.asarray(rng.normal(0, 1, (B, H, W, hd)), jnp.float32)
        kn = jnp.asarray(rng.normal(0, 1, (B, H, W, hd)), jnp.float32)
        vn = jnp.asarray(rng.normal(0, 1, (B, H, W, hd)), jnp.float32)
        ctx, kvp2 = paged_attention_window(
            q, kn, vn, pack_kv(kp, vp), bt, pos, interpret=True)
        kp2, vp2 = split_kv(kvp2)
        # reference: contiguous overlay of window rows at pos..pos+W-1
        kc = np.asarray(kp)[np.asarray(bt)].transpose(0, 2, 1, 3, 4)
        kc = kc.reshape(B, H, P * page, hd).copy()
        vc = np.asarray(vp)[np.asarray(bt)].transpose(0, 2, 1, 3, 4)
        vc = vc.reshape(B, H, P * page, hd).copy()
        for b in range(B):
            p0 = int(pos[b])
            kc[b, :, p0:p0 + W] = np.asarray(kn)[b]
            vc[b, :, p0:p0 + W] = np.asarray(vn)[b]
        for j in range(W):
            want = self._reference(
                np.asarray(q)[:, :, j:j + 1], kc, vc,
                np.asarray(pos) + j + 1)
            np.testing.assert_allclose(
                np.asarray(ctx)[:, :, j:j + 1], want, rtol=3e-6, atol=3e-6)
        # the scatter itself is bitwise: pool rows at pos..pos+W-1 now
        # hold exactly k_new/v_new
        kp2n, vp2n = np.asarray(kp2), np.asarray(vp2)
        for b in range(B):
            for j in range(W):
                t = int(pos[b]) + j
                pg, off = int(bt[b, t // page]), t % page
                assert np.array_equal(kp2n[pg, :, off], np.asarray(kn)[b, :, j])
                assert np.array_equal(vp2n[pg, :, off], np.asarray(vn)[b, :, j])

    def test_window_inactive_rows_only_touch_trash(self):
        B, H, page, hd, P, W = 2, 2, 4, 8, 2, 2
        rng = np.random.default_rng(9)
        kp, vp = self._pool(rng, 1 + B * P, H, page, hd)
        bt = jnp.asarray(
            1 + np.arange(B)[:, None] * P + np.arange(P), jnp.int32)
        pos = jnp.asarray([3, 2], jnp.int32)
        active = jnp.asarray([True, False])
        before_k = np.asarray(kp).copy()
        q = jnp.asarray(rng.normal(0, 1, (B, H, W, hd)), jnp.float32)
        kn = jnp.asarray(rng.normal(0, 1, (B, H, W, hd)), jnp.float32)
        vn = jnp.asarray(rng.normal(0, 1, (B, H, W, hd)), jnp.float32)
        _, kvp2 = paged_attention_window(
            q, kn, vn, pack_kv(kp, vp), bt, pos, active=active,
            interpret=True)
        after_k = np.asarray(split_kv(kvp2)[0])
        # row 1's pages (ids 3..4) are untouched; only row 0's pages and
        # the trash page may differ
        assert np.array_equal(after_k[1 + P:], before_k[1 + P:])
        assert not np.array_equal(after_k[1:1 + P], before_k[1:1 + P])


def _steps_of(n):
    """The sweep a Python loop builds from each row's page count."""
    return ([b for b, nb in enumerate(n) for _ in range(nb)],
            [p for nb in n for p in range(nb)])


# (page, pages a slot, bound, whi or None): hand-written, the count of pages
# each row needs beside them
SCHEDULES = {
    "one_step_for_an_empty_row": (4, 4, [0, 5, 0], None, [1, 2, 1]),
    "a_key_page_ends_on_its_boundary": (4, 4, [4, 8, 16], None, [1, 2, 4]),
    "the_write_page_lies_after_the_keys": (4, 4, [4, 8, 3], [1, 2, 0],
                                           [2, 3, 1]),
    "a_chunk_writes_five_pages": (64, 16, [32], [4], [5]),
    "clipped_to_the_slot": (4, 3, [40, 2], [9, 0], [3, 1]),
    "every_row_full": (8, 2, [16, 9, 15], [1, 1, 1], [2, 2, 2]),
}


@pytest.mark.parametrize("name", list(SCHEDULES))
def test_schedule_is_the_rows_pages_in_order(name):
    page, n_pages, bound, whi, want_n = SCHEDULES[name]
    B = len(bound)
    row_of, page_of, last_of, total = _schedule(
        jnp.asarray(bound, jnp.int32),
        -1 if whi is None else jnp.asarray(whi, jnp.int32), page, n_pages)
    assert row_of.shape == page_of.shape == (B * n_pages,)
    assert row_of.dtype == page_of.dtype == last_of.dtype == jnp.int32
    rows, pages = _steps_of(want_n)
    assert int(total) == len(rows) <= B * n_pages
    assert list(np.asarray(row_of)[:len(rows)]) == rows
    assert list(np.asarray(page_of)[:len(rows)]) == pages
    assert list(np.asarray(last_of)) == [nb - 1 for nb in want_n]
    # what lies past the sweep is never visited, and still a valid index
    assert np.all(np.asarray(row_of) < B)
    assert np.all(np.asarray(page_of) < n_pages)


def test_fused_schedule_gives_an_inactive_row_one_step():
    page, n_pages = 4, 4
    pos = jnp.asarray([9, 13, 6, 0], jnp.int32)
    active = jnp.asarray([True, False, True, False])
    wlo = jnp.where(active, pos // page, 1)
    whi = jnp.where(active, pos // page, 0)
    row_of, page_of, last_of, total = _fused_schedule(pos, wlo, whi, page,
                                                      n_pages)
    rows, pages = _steps_of([3, 1, 2, 1])
    assert int(total) == 7
    assert list(np.asarray(row_of)[:7]) == rows
    assert list(np.asarray(page_of)[:7]) == pages
    assert list(np.asarray(last_of)) == [2, 0, 1, 0]


# the edges of the ragged sweep: (page, pages a slot, W, pos, active)
SWEEPS = {
    # a chunk's first window: one step, p == 0 is window fold and write page
    "first_window_w1": (4, 4, 1, [0, 0, 5], None),
    "first_window_w8": (4, 6, 8, [0, 3, 0], None),
    "first_window_w256": (64, 8, 256, [0, 0], None),
    # pos % page == 0: the write page lies after the last page with keys
    "page_start_w1": (4, 6, 1, [4, 8, 12, 20], None),
    "page_start_w8": (4, 6, 8, [4, 8, 16], None),
    "chunk_spans_five_pages_w256": (64, 8, 256, [32, 200], None),
    "ends_at_max_len_w1": (4, 4, 1, [15, 3], None),
    "ends_at_max_len_w8": (4, 4, 8, [8, 2], None),
    "ends_at_max_len_w256": (64, 8, 256, [256, 0], None),
    # the output and trash-page blocks across a row boundary
    "inactive_between_active_w1": (4, 4, 1, [9, 13, 6, 2, 15],
                                   [True, False, True, False, True]),
    "inactive_first_and_last_w8": (4, 6, 8, [5, 9, 16, 0, 3],
                                   [False, True, False, True, False]),
    # total == B * n_pages
    "every_row_full_w1": (4, 4, 1, [15, 15, 15], None),
    "every_row_full_w8": (4, 4, 8, [8, 8], None),
}
KV = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


class TestRaggedSweep:
    """The grid visits the pages a row needs and no other: parity with a
    gather oracle on every edge of the schedule, the written pages equal to
    ``_pool_write_rows`` bit for bit, pages no row needs never read."""

    H, HD = 2, 8

    def _case(self, name, kv, poison=False, B=None):
        page, P, W, pos, active = SWEEPS[name]
        if B is not None:               # the mesh mount wants 8 rows
            pos = (pos * B)[:B]
            active = None if active is None else (active * B)[:B]
        B, H, hd = len(pos), self.H, self.HD
        rng = np.random.default_rng(sum(map(ord, name)))
        dt = jnp.float32 if kv == "f32" else jnp.bfloat16
        # rows' pages interleaved in the pool, page 0 the trash page
        bt = 1 + np.arange(P)[None, :] * B + np.arange(B)[:, None]
        vals = rng.normal(0, 1, (2, 1 + B * P, H, page, hd))
        k_f, v_f = (jnp.asarray(t, jnp.float32) for t in vals)
        if kv == "int8":
            (kq, ks), (vq, vs) = (quantize_kv(t, jnp.int8)
                                  for t in (k_f, v_f))
            pool, scales = pack_kv(kq, vq), (ks, vs)
            deq = [np.asarray(q_.astype(jnp.float32)
                              * s_.astype(jnp.float32)[..., None])
                   for q_, s_ in ((kq, ks), (vq, vs))]
        else:
            pool, scales = pack_kv(k_f, v_f).astype(KV[kv]), ()
            deq = [np.asarray(t.astype(jnp.float32))
                   for t in split_kv(pool)]
        act = np.ones(B, bool) if active is None else np.asarray(active)
        # the pages a live row needs: keys below pos, writes below pos + W
        needed = np.zeros(1 + B * P, bool)
        needed[0] = True
        for b in np.flatnonzero(act):
            needed[bt[b, :min(P, (pos[b] + W - 1) // page + 1)]] = True
        if poison:
            bad = jnp.asarray(~needed)[:, None, None]
            if kv == "int8":
                scales = tuple(jnp.where(bad, jnp.nan, s_) for s_ in scales)
            else:
                pool = jnp.where(bad[..., None], jnp.nan, pool)
        q, kn, vn = (jnp.asarray(rng.normal(0, 1, (B, H, W, hd)), dt)
                     for _ in range(3))
        return dict(page=page, P=P, W=W, pos=np.asarray(pos), act=act,
                    active=None if active is None else jnp.asarray(active),
                    bt=jnp.asarray(bt, jnp.int32), pool=pool, scales=scales,
                    deq=deq, q=q, kn=kn, vn=vn, needed=needed)

    def _oracle(self, c):
        """float32 softmax over the gathered keys below ``pos`` (as the
        pool stores them) and the window's own rows, unquantized."""
        q, kn, vn = (np.asarray(t.astype(jnp.float32))
                     for t in (c["q"], c["kn"], c["vn"]))
        B, H, W, hd = q.shape
        bt = np.asarray(c["bt"])
        out = np.zeros_like(q)
        for b in np.flatnonzero(c["act"]):
            n = int(c["pos"][b])
            kc, vc = (t[bt[b]].transpose(1, 0, 2, 3).reshape(H, -1, hd)[:, :n]
                      for t in c["deq"])
            for j in range(W):
                k = np.concatenate([kc, kn[b, :, :j + 1]], axis=1)
                v = np.concatenate([vc, vn[b, :, :j + 1]], axis=1)
                s = np.einsum("hd,hkd->hk", q[b, :, j], k) / np.sqrt(hd)
                p = np.exp(s - s.max(-1, keepdims=True))
                out[b, :, j] = np.einsum(
                    "hk,hkd->hd", p / p.sum(-1, keepdims=True), v)
        return out

    def _run(self, c, **mounted):
        kw = dict(zip(("k_scale", "v_scale"), c["scales"]))
        return paged_attention_window(
            c["q"], c["kn"], c["vn"], c["pool"], c["bt"],
            jnp.asarray(c["pos"], jnp.int32), active=c["active"],
            interpret=True, **kw, **mounted)

    def _check(self, c, kv, ctx, pools):
        tol = 2e-5 if kv == "f32" else 2e-2    # queries are bf16 but there
        got = np.asarray(ctx.astype(jnp.float32))[c["act"]]
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got, self._oracle(c)[c["act"]],
                                   rtol=tol, atol=tol)
        want = _pool_write_rows(c["pool"], c["kn"], c["vn"], c["bt"],
                                jnp.asarray(c["pos"], jnp.int32),
                                c["active"], *c["scales"])
        for g, w in zip(pools, want):
            # bit for bit off the trash page, NaN where NaN was left
            assert np.array_equal(np.asarray(g)[1:], np.asarray(w)[1:],
                                  equal_nan=True)

    @pytest.mark.parametrize("kv", list(KV))
    @pytest.mark.parametrize("name", list(SWEEPS))
    def test_sweep_matches_gather_oracle_and_writeback_bytes(self, name, kv):
        c = self._case(name, kv)
        ctx, *pools = self._run(c)
        self._check(c, kv, ctx, pools)

    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    @pytest.mark.parametrize("name", list(SWEEPS))
    def test_pages_no_row_needs_are_never_read(self, name, kv):
        """NaN in every page (every scale of a quantized page) outside the
        live rows' needed range: one read of it would reach the output
        through ``p @ v`` even under a zero weight."""
        c = self._case(name, kv, poison=True)
        assert not c["needed"].all() or name.startswith("every_row_full")
        ctx, *pools = self._run(c)
        self._check(c, kv, ctx, pools)

    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    @pytest.mark.parametrize("name", ["page_start_w1", "first_window_w8",
                                      "every_row_full_w1"])
    def test_each_shard_of_the_mesh_mount_builds_its_own_schedule(
            self, name, kv):
        """``dp4 x tp2``: two rows a shard, their own pages, one head."""
        c = self._case(name, kv, poison=True, B=8)
        ctx, *pools = self._run(c, **mount("mesh"))
        self._check(c, kv, ctx, pools)

    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    def test_read_kernel_sweeps_each_rows_own_pages(self, kv):
        """The read-only kernel: lengths 0 (one step, zeros), mid-page, a
        page's end, the whole slot; the pages past a row's length NaN."""
        lengths = [0, 3, 8, 16, 5]
        c = self._case("first_window_w1", kv, B=len(lengths))
        bt, page = np.asarray(c["bt"]), c["page"]
        bad = np.ones(c["pool"].shape[0], bool)
        for b, n in enumerate(lengths):
            bad[bt[b, :-(-n // page)]] = False
        bad = jnp.asarray(bad)[:, None, None]
        if kv == "int8":
            c["scales"] = tuple(jnp.where(bad, jnp.nan, s_)
                                for s_ in c["scales"])
        else:
            c["pool"] = jnp.where(bad[..., None], jnp.nan, c["pool"])
        got = paged_attention(
            c["q"], c["pool"], c["bt"], jnp.asarray(lengths, jnp.int32),
            interpret=True, **dict(zip(("k_scale", "v_scale"), c["scales"])))
        got = np.asarray(got.astype(jnp.float32))
        q = np.asarray(c["q"].astype(jnp.float32))
        assert np.all(got[0] == 0.0)
        for b, n in enumerate(lengths[1:], 1):
            kc, vc = (t[bt[b]].transpose(1, 0, 2, 3).reshape(
                self.H, -1, self.HD)[:, :n] for t in c["deq"])
            s = np.einsum("hd,hkd->hk", q[b, :, 0], kc) / np.sqrt(self.HD)
            p = np.exp(s - s.max(-1, keepdims=True))
            want = np.einsum("hk,hkd->hd", p / p.sum(-1, keepdims=True), vc)
            np.testing.assert_allclose(got[b, :, 0], want, rtol=2e-2,
                                       atol=2e-2)


# the five paged mounts over bf16 pages: (heads, window). A one-query window
# keeps its state a row a head, _HEADS a group: 11 heads are a whole group
# and the last eight once more
OPERAND_CASES = {"read": (3, 1), "window_w1": (11, 1), "window_w5": (3, 5),
                 "fused_w1": (11, 1), "fused_w5": (3, 5), "select": (2, 1),
                 "latent": (1, 1)}


class TestOperandRule:
    """What a live grid step computes on its page block (PR 37): the keys
    enter the scores in the page's dtype, the values are lifted to the
    float32 weights; a one-query window folds the whole packed row, heads
    as rows (the query zero over the V lanes, an accumulator ``2*hd`` wide
    whose V half is sliced once a row)."""

    PAGE, P, HD = 8, 4, 16
    # the context's own rounding to bf16 (2**-8 of values up to ~2)
    TOL = 1e-2

    def _pool(self, rng, B, H, width, huge_after=None):
        """bf16 pages, rows' pages interleaved, page 0 the trash page. With
        ``huge_after`` (B,) every position at or past a row's bound holds
        the largest finite bf16, +-: in the pages a row NEEDS, where a
        masked key's weight is an exact 0 and its K and V lanes still
        reach both products."""
        page, P = self.PAGE, self.P
        bt = 1 + np.arange(P)[None, :] * B + np.arange(B)[:, None]
        pool = rng.normal(0, 1, (1 + B * P, H, page, width))
        if huge_after is not None:
            big = float(jnp.finfo(jnp.bfloat16).max)
            for b in range(B):
                for t in range(int(huge_after[b]), P * page):
                    pool[bt[b, t // page], :, t % page] = big * (-1) ** t
        return jnp.asarray(pool, jnp.bfloat16), jnp.asarray(bt, jnp.int32)

    @staticmethod
    def _oracle(q, k, v, scale, causal=True):
        """float32 softmax attention of (H, W, d) over (H, n, d), (H, n, dv);
        under ``causal`` query j of W sees all but the last W - 1 - j keys
        (a window's own rows are the last W), else every query every key."""
        W, n = q.shape[1], k.shape[1]
        if not n:
            return np.zeros(q.shape[:2] + v.shape[2:], np.float32)
        s = jnp.einsum("hwd,hkd->hwk", q, k) * scale
        ok = jnp.arange(n)[None, :] <= (n - W + jnp.arange(W))[:, None]
        p = jax.nn.softmax(jnp.where(ok | (not causal), s, -jnp.inf), axis=-1)
        return jnp.einsum("hwk,hkd->hwd", p, v)

    def _run(self, kind, huge):
        H, W = OPERAND_CASES[kind]
        page, P, hd = self.PAGE, self.P, self.HD
        rng = np.random.default_rng(sum(map(ord, kind)))
        pos = np.asarray([0, 3, 8, 21, 26])      # empty, mid, boundary, ...
        B = len(pos)
        f32 = lambda t: np.asarray(jnp.asarray(t).astype(jnp.float32))  # noqa: E731
        cached = lambda pool, bt, b, n: f32(pool)[np.asarray(bt)[b]].transpose(  # noqa: E731
            1, 0, 2, 3).reshape(pool.shape[1], -1, pool.shape[3])[:, :n]
        if kind == "latent":
            dk, vw, Hq = 48, 32, 8
            pool, bt = self._pool(rng, B, 1, dk, pos if huge else None)
            q = jnp.asarray(rng.normal(0, 1, (B, Hq, dk)), jnp.float32)
            got = paged_attention_latent(q, pool, bt, jnp.asarray(pos),
                                         v_width=vw, scale=dk ** -0.5,
                                         interpret=True)
            want = [self._oracle(
                f32(q)[b][None], cached(pool, bt, b, n),
                cached(pool, bt, b, n)[..., :vw], dk ** -0.5, False)[0]
                for b, n in enumerate(pos)]
            return f32(got), want
        pool, bt = self._pool(rng, B, H, 2 * hd, pos + (W if kind.startswith(
            ("window", "fused")) else 0) if huge else None)
        scale = hd ** -0.5
        if kind == "select":
            hg = 4
            q = jnp.asarray(rng.normal(0, 1, (B, H, hg, hd)), jnp.bfloat16)
            # every page, in another order, and one entry that is no page
            sel = np.tile(np.asarray([2, -1, 0, 3, 1]), (B, H, 1))
            got = paged_attention_selected(q, pool, bt, jnp.asarray(sel),
                                           jnp.asarray(pos), interpret=True)
            return f32(got), [
                self._oracle(f32(q)[b], *split_kv(cached(pool, bt, b, n)),
                             scale, False) for b, n in enumerate(pos)]
        q, kn, vn = (jnp.asarray(rng.normal(0, 1, (B, H, W, hd)),
                                 jnp.bfloat16) for _ in range(3))
        if kind == "read":
            got = paged_attention(q, pool, bt, jnp.asarray(pos),
                                  interpret=True)
            new = np.zeros((B, H, 0, 2 * hd), np.float32)
        elif kind.startswith("window"):
            Wp = -(-W // 16) * 16
            padw = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, Wp - W), (0, 0)))  # noqa: E731
            got = _pa_window_read_call(
                padw(q), padw(pack_kv(kn, vn)), pool, bt,
                jnp.asarray(pos, jnp.int32), W=W, scale=scale,
                interpret=True)[:, :, :W]
            new = f32(pack_kv(kn, vn))
        else:
            got, _ = paged_attention_window(q, kn, vn, pool, bt,
                                            jnp.asarray(pos), interpret=True)
            new = f32(pack_kv(kn, vn))
        return f32(got), [
            self._oracle(f32(q)[b], *split_kv(np.concatenate(
                [cached(pool, bt, b, n), new[b]], axis=1)), scale)
            for b, n in enumerate(pos)]

    @pytest.mark.parametrize("huge", [False, True],
                             ids=["plain", "huge_past_the_bound"])
    @pytest.mark.parametrize("kind", list(OPERAND_CASES))
    def test_bf16_pages_match_the_float32_oracle(self, kind, huge):
        """Every mount over bf16 pages against float32 softmax attention of
        the same values, at the two roundings' tolerance; with the positions
        past each row's bound, in pages it needs, at the largest finite
        bf16: a masked key's weight is an exact 0 and, under a one-query
        window, the zero half of the query meets its V lanes and the wide
        accumulator its K lanes, and none of it may reach the context."""
        got, want = self._run(kind, huge)
        assert np.all(np.isfinite(got))
        for g, w in zip(got, want):         # a row with no key yields zeros
            np.testing.assert_allclose(g, np.asarray(w), rtol=self.TOL,
                                       atol=self.TOL)

    @pytest.mark.parametrize("heads", [3, 8, 11, 25])
    def test_scores_of_bf16_operands_are_the_float32_copies_scores(
            self, heads):
        """Rung 1 is no precision change: bf16 x bf16 products are exact in
        float32, so the scores of the operands as stored equal those of
        float32 copies of the same values up to the order of a float32
        sum. And the heads-as-rows query operand is window row 0 of every
        head, whole groups of ``_HEADS`` (the last eight heads once more
        where the count is no multiple, zero heads under eight), zero over
        the V lanes; ``_heads_of`` is the way back."""
        Wp, hd, K = 16, 16, 24
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.normal(0, 1, (1, heads, Wp, hd)), jnp.bfloat16)
        qo = _heads_query(q, 2 * hd)
        assert qo.shape == (-(-heads // _HEADS), _HEADS, 2 * hd)
        assert qo.dtype == jnp.bfloat16
        rows = np.asarray(_heads_of(qo, heads).astype(jnp.float32))
        assert np.array_equal(rows[:, :hd],
                              np.asarray(q.astype(jnp.float32))[0, :, 0])
        assert not np.asarray(qo.astype(jnp.float32))[..., hd:].any()
        groups = _whole_groups(jnp.arange(heads))
        assert all(g.shape[1] == _HEADS for g in groups)
        assert np.array_equal(
            np.asarray(_heads_of(jnp.concatenate(groups), heads)),
            np.arange(heads))
        kv = jnp.asarray(rng.normal(0, 1, (qo.shape[0], K, 2 * hd)),
                         jnp.bfloat16)
        got = _scores(qo, kv, hd ** -0.5)
        want = _scores(qo.astype(jnp.float32), kv.astype(jnp.float32),
                       hd ** -0.5)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                             ids=["bf16", "f32"])
    @pytest.mark.parametrize("heads,cut", [(4, 2), (16, 8), (12, 6),
                                           (25, 12)])
    def test_a_heads_context_does_not_depend_on_the_heads_beside_it(
            self, heads, cut, dtype):
        """A one-query window folds eight heads a group, and a mesh mounts
        the kernel a shard of the heads a device: the heads' contexts are
        the same BITS whichever heads share the call (every group's
        products have one shape, what crosses heads is an exact 0), so a
        tensor-parallel engine's attention is one device's."""
        page, P, hd, B = self.PAGE, self.P, self.HD, 3
        rng = np.random.default_rng(heads)
        pool, bt = self._pool(rng, B, heads, 2 * hd)
        pool = pool.astype(dtype)
        q, kn, vn = (jnp.asarray(rng.normal(0, 1, (B, heads, 1, hd)), dtype)
                     for _ in range(3))
        pos = jnp.asarray([5, 17, 30])
        run = lambda hs: paged_attention_window(  # noqa: E731
            q[:, hs], kn[:, hs], vn[:, hs], pool[:, hs], bt, pos,
            interpret=True)[0]
        whole = run(slice(None))
        shards = jnp.concatenate([run(slice(0, cut)),
                                  run(slice(cut, heads))], axis=1)
        assert np.array_equal(np.asarray(whole.astype(jnp.float32)),
                              np.asarray(shards.astype(jnp.float32)))


# what a call of the latent sweep sees and the pages a grid step folds by it:
# (rows of a page, row width, bytes a value, pages a slot) -> k
LATENT_BLOCKS = {
    # glmflash_repoctx_shared32: 328 KB pages, 128 a slot: four a step
    "glm_32k_slots": ((256, 640, 2, 128), 4),
    # lingflash_reason_closed32: the same page, sixteen a slot: one a step
    "ling_4k_slots": ((256, 640, 2, 16), 1),
    "a_slot_of_32_pages": ((256, 640, 2, 32), 2),
    "a_long_slot_is_held_by_the_bytes": ((256, 640, 2, 1024), 4),
    "a_float32_pool_halves_the_block": ((256, 640, 4, 128), 2),
    "a_page_past_the_block_is_one_a_step": ((512, 1024, 2, 128), 1),
    "tiny_pages_are_held_by_the_table": ((8, 128, 4, 64), 4),
}


@pytest.mark.parametrize("name", list(LATENT_BLOCKS))
def test_latent_block_follows_the_page_and_the_table(name):
    (rows, width, itemsize, per), want = LATENT_BLOCKS[name]
    assert latent_block(rows * width * itemsize, per) == want


# (page, pages a slot, k, lengths): what each of a block's k operands holds
# at each step, as (row, page) pairs, beside the sweep it rides
HOLDS = {
    # a two-page row moves two pages: operands 2 and 3 keep row 0's first
    "a_two_page_row_moves_two_pages": (4, 8, 4, [8, 0, 3]),
    # row 1's last block needs one page of four: the others keep row 1's
    # previous block, and row 2's first block takes over operand by operand
    "a_last_block_keeps_the_block_before": (4, 8, 4, [32, 17, 32]),
    "k2_rows_of_every_parity": (4, 6, 2, [1, 8, 9, 24, 0, 13]),
    "a_table_no_multiple_of_the_block": (4, 6, 4, [24, 5, 24]),
}


@pytest.mark.parametrize("name", list(HOLDS))
def test_block_operands_hold_their_page_until_a_step_needs_another(name):
    page, P, k, lengths = HOLDS[name]
    lens = jnp.asarray(lengths, jnp.int32)
    row_of, blk_of, last_of, total = _schedule(lens, -1, k * page,
                                               -(-P // k))
    need = [-(-n // page) for n in lengths]
    rows, blks = _steps_of([max(1, -(-n // k)) for n in need])
    total = int(total)
    assert total == len(rows)
    assert list(np.asarray(row_of)[:total]) == rows
    assert list(np.asarray(blk_of)[:total]) == blks
    holds = np.asarray(_block_holds(row_of, blk_of, lens, page, P, k))
    assert holds.shape == (k, row_of.shape[0]) and holds.dtype == np.int32
    fetched = 0
    for j in range(k):
        held = 0                        # before any need: row 0's first page
        for s, (b, blk) in enumerate(zip(rows, blks)):
            if k * blk + j < need[b]:
                held = b * P + k * blk + j
            assert holds[j, s] == held, (j, s)
        # the pipeline moves an operand's block when its index changes
        seen = holds[j, :total]
        fetched += 1 + int(np.sum(seen[1:] != seen[:-1]))
    # every needed page once, and an operand's first block where the first
    # step has no need of it
    assert sum(need) <= fetched <= sum(need) + k


class TestLatentBlocks:
    """The absorbed latent kernel a BLOCK of a row's pages a grid step
    (PR 44) against the one-page sweep (``k`` = 1, the program it was) and a
    float32 oracle: lengths at every edge of a page and of a block, rows of
    unequal length in one call so that block and row boundaries interleave,
    NaN in every page a row does not need (the unneeded pages of its last
    block and trash page 0 among them), the largest finite bf16 past a
    row's bound in the pages it needs."""

    PAGE, P, DK, DV = 8, 10, 128, 96

    def _case(self, H, k, dtype=jnp.bfloat16):
        page, P = self.PAGE, self.P
        lengths = np.asarray(
            [k * page + 1, 0, page - 1, P * page, 1, k * page, page + 1,
             k * page - 1, page, 2 * k * page + 3, P * page - 1], np.int32)
        B = len(lengths)
        rng = np.random.default_rng(17 * H + k)
        bt = 1 + rng.permutation(B * P).reshape(B, P).astype(np.int32)
        pool = rng.normal(0, 1, (1 + B * P, 1, page, self.DK))
        big = float(jnp.finfo(jnp.bfloat16).max)
        for b, n in enumerate(lengths):
            for t in range(int(n), -(-int(n) // page) * page):
                pool[bt[b, t // page], 0, t % page] = big * (-1) ** t
            pool[bt[b, -(-int(n) // page):]] = np.nan
        pool[0] = np.nan
        q = rng.normal(0, 1, (B, H, self.DK)).astype(np.float32)
        return (jnp.asarray(q), jnp.asarray(pool, dtype), jnp.asarray(bt),
                jnp.asarray(lengths))

    def _run(self, k, q, pool, bt, lengths):
        H = q.shape[1]
        qp = jnp.pad(q, ((0, 0), (0, -H % 8), (0, 0)))[:, None]
        call = jax.jit(functools.partial(
            _latent_launch, v_width=self.DV, scale=0.25, interpret=True,
            k=k))
        return np.asarray(call(qp, pool, bt, lengths))[:, 0, :H]

    def _oracle(self, q, pool, bt, lengths):
        q, pool = (np.asarray(t.astype(jnp.float32)) for t in (q, pool))
        out = np.zeros(q.shape[:2] + (self.DV,), np.float32)
        for b, n in enumerate(np.asarray(lengths)):
            if n:
                rows = np.concatenate(
                    [pool[p, 0] for p in np.asarray(bt)[b]])[:n]
                s = q[b] @ rows.T * 0.25
                p = np.exp(s - s.max(axis=1, keepdims=True))
                out[b] = (p / p.sum(axis=1, keepdims=True)) @ rows[:, :self.DV]
        return out

    @pytest.mark.parametrize("H", [20, 32, 5])
    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_blocks_against_the_one_page_sweep_and_the_oracle(self, k, H):
        """A row's last, partial block folds page by page, the one-page
        sweep's folds in its order: rows of fewer pages than a block are
        that sweep's contexts BIT FOR BIT. A whole block is one fold, the
        same mathematics under another order of float32 sums: held to the
        float32 oracle at the kernel's 2e-5, as the one-page sweep is."""
        case = self._case(H, k)
        got, one = self._run(k, *case), self._run(1, *case)
        assert np.isfinite(got).all() and np.isfinite(one).all()
        need = -(-np.asarray(case[3]) // self.PAGE)
        assert (need < k).sum() >= (2 if k > 1 else 0)
        assert np.array_equal(got[need < k], one[need < k])
        if k == 1:
            assert np.array_equal(got, one)
        want = self._oracle(*case)
        assert not got[1].any()                    # a row with no key
        assert np.abs(got - want).max() < 2e-5
        assert np.abs(one - want).max() < 2e-5

    @pytest.mark.parametrize("k", [2, 4])
    def test_float32_pages_fold_in_blocks_too(self, k):
        case = self._case(8, k, jnp.float32)
        got = self._run(k, *case)
        assert np.isfinite(got).all()
        assert np.abs(got - self._oracle(*case)).max() < 2e-5

    def test_the_call_picks_its_block_from_its_shapes(self, monkeypatch):
        """``paged_attention_latent`` through ``_pa_latent_call``: a table of
        64 pages of 8 float32 rows gives four pages a step, one of 10 gives
        one; no argument says so."""
        import mmlspark_tpu.ops.paged_attention as pa
        seen = []
        inner = pa._latent_launch

        def spy(*args, k, **kw):
            seen.append(k)
            return inner(*args, k=k, **kw)

        monkeypatch.setattr(pa, "_latent_launch", spy)
        q, pool, bt, lengths = self._case(8, 4, jnp.float32)
        wide = jnp.pad(bt, ((0, 0), (0, 64 - self.P)))
        for table, k in ((bt, 1), (wide, 4)):
            pa._pa_latent_call.clear_cache()
            got = np.asarray(paged_attention_latent(
                q, pool, table, lengths, v_width=self.DV, scale=0.25,
                interpret=True))
            assert seen[-1] == k
            assert np.abs(got - self._oracle(q, pool, bt, lengths)).max() \
                < 2e-5
        pa._pa_latent_call.clear_cache()


# what a call of the selected-block walk sees and the listed pages a grid step
# folds by it: (rows of a page, row width, bytes a value, entries a list) -> k
SELECT_BLOCKS = {
    # sala_docqa_closed8: a head's slice of a page 32 KB, top-64 blocks
    "sala_top64": ((64, 256, 2, 64), 8),
    # the same cell while a row sits under dense_len: 128 blocks listed
    "sala_dense128": ((64, 256, 2, 128), 8),
    "a_float32_pool_halves_the_block": ((64, 256, 4, 64), 4),
    "a_list_that_8_does_not_divide": ((64, 256, 2, 12), 4),
    "a_list_that_4_does_not_divide": ((8, 32, 4, 6), 2),
    "an_odd_list_walks_page_by_page": ((8, 32, 4, 5), 1),
    "a_slice_past_the_block_is_one_a_step": ((256, 1024, 2, 64), 1),
}


@pytest.mark.parametrize("name", list(SELECT_BLOCKS))
def test_select_block_follows_the_page_and_the_list(name):
    (rows, width, itemsize, n_sel), want = SELECT_BLOCKS[name]
    assert select_block(rows * width * itemsize, n_sel) == want


def _one_page_select_call(q, kv_pages, block_tables, sel, lengths, scale):
    """The selected-block kernel as it stood before PR 46, a listed page a
    grid step: the reference the walk in blocks is held to bit for bit
    wherever it folds page by page."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    import mmlspark_tpu.ops.paged_attention as pa

    B, G, hg, hd = q.shape
    page, n_sel = kv_pages.shape[2], sel.shape[1]

    def kernel(bt_ref, sel_ref, len_ref, q_ref, kv_ref, o_ref, m_scr, l_scr,
               acc_scr):
        b, g, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        pl.when(j == 0)(lambda: pa._init(m_scr, l_scr, acc_scr))
        lp = sel_ref[b * G + g, j]

        @pl.when(lp >= 0)
        def _compute():
            pa._pages_fold(m_scr, l_scr, acc_scr, q_ref[0],
                           pa._page_kv(kv_ref), lp, len_ref[b], scale, page)

        pl.when(j == n_sel - 1)(lambda: pa._finalize(o_ref, l_scr, acc_scr))

    row = pl.BlockSpec((1, 1, hg, hd), lambda b, g, j, *_: (b, g, 0, 0))

    def page_of(b, g, j, bt, sel_, *_):
        return (bt[b, jnp.maximum(sel_[b * G + g, j], 0)], g, 0, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, G, n_sel),
            in_specs=[row, pl.BlockSpec((1, 1, page, 2 * hd), page_of)],
            out_specs=row, scratch_shapes=pa._softmax_state(1, hg, hd)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=True)(block_tables, sel, lengths, q, kv_pages)


class TestSelectBlocks:
    """The selected-block kernel a BLOCK of a (row, KV head)'s listed pages
    a grid step (PR 46) against the one-page walk it was and a float32
    oracle. Rows: one past ``dense_len`` with every entry a page (whole
    blocks alone); one with fewer blocks than the list, its entries that are
    no page last, as top-k puts them (its last block with a page folds page
    by page); an idle row that lists nothing and whose table's page 0 is the
    trash page; one whose every other entry is no page (no block is whole at
    any ``k``); one that lists a single page. NaN fills the trash page and
    every page no row lists; the positions past a row's bound hold the
    largest finite bf16 in the page it lists."""

    PAGE, HD, HG, G = 8, 16, 4, 2
    SCALE = 0.25
    LONG, SHORT, IDLE, HOLES, ONE = range(5)

    @staticmethod
    @functools.lru_cache(maxsize=None)
    def _case(n_sel, dtype):
        page, G = TestSelectBlocks.PAGE, TestSelectBlocks.G
        P = n_sel + 24
        lengths = np.asarray([P * page - 3, (n_sel - 13) * page - 2, 0,
                              (n_sel + 8) * page + 1, 5], np.int32)
        B = len(lengths)
        rng = np.random.default_rng(n_sel)
        bt = 1 + rng.permutation(B * P).reshape(B, P).astype(np.int32)
        bt[TestSelectBlocks.IDLE] = 0
        pool = rng.normal(0, 1, (1 + B * P, G, page, 2 * TestSelectBlocks.HD))
        sel = np.full((B, G, n_sel), -1, np.int32)
        listed = np.zeros(1 + B * P, bool)
        big = float(jnp.finfo(jnp.bfloat16).max)
        for b, n in enumerate(lengths):
            held = -(-int(n) // page)
            for g in range(G):
                pick = rng.permutation(held)[:n_sel]
                if held:                # the page of the row's newest token
                    pick[0] = held - 1
                if b == TestSelectBlocks.HOLES:
                    pick = pick[:n_sel // 2]
                    sel[b, g, :2 * len(pick):2] = pick
                else:
                    sel[b, g, :len(pick)] = pick
                listed[bt[b, pick]] = True
            for t in range(int(n), held * page):
                pool[bt[b, t // page], :, t % page] = big * (-1) ** t
        pool[~listed] = np.nan
        q = rng.normal(0, 1, (B, G, TestSelectBlocks.HG, TestSelectBlocks.HD))
        return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
                jnp.asarray(bt), jnp.asarray(sel.reshape(B * G, n_sel)),
                jnp.asarray(lengths))

    @staticmethod
    @functools.lru_cache(maxsize=None)
    def _one_page(n_sel, dtype):
        got = _one_page_select_call(*TestSelectBlocks._case(n_sel, dtype),
                                    TestSelectBlocks.SCALE)
        return np.asarray(got.astype(jnp.float32))

    def _oracle(self, q, pool, bt, sel, lengths):
        q, pool = (np.asarray(t.astype(jnp.float32)) for t in (q, pool))
        bt, lengths = np.asarray(bt), np.asarray(lengths)
        sel = np.asarray(sel).reshape(q.shape[0], q.shape[1], -1)
        out = np.zeros_like(q)
        for b, g in np.ndindex(*q.shape[:2]):
            rows = [pool[bt[b, lp], g][:max(0, lengths[b] - lp * self.PAGE)]
                    for lp in sel[b, g] if lp >= 0]
            if rows:
                rows = np.concatenate(rows)
                s = q[b, g] @ rows[:, :self.HD].T * self.SCALE
                p = np.exp(s - s.max(axis=1, keepdims=True))
                out[b, g] = (p / p.sum(axis=1, keepdims=True)) @ \
                    rows[:, self.HD:]
        return out

    @pytest.mark.parametrize("k", [1, 2, 4, 8])
    @pytest.mark.parametrize("n_sel,dtype", [
        (64, jnp.bfloat16), (128, jnp.bfloat16), (64, jnp.float32)],
        ids=["top64_bf16", "dense128_bf16", "top64_float32"])
    def test_blocks_against_the_one_page_walk_and_the_oracle(self, k, n_sel,
                                                             dtype):
        """``k`` = 1 is the one-page walk's program, and a block with an
        entry that is no page folds its pages one by one in that walk's
        order: both return its contexts BIT FOR BIT. A whole block is one
        fold, the same mathematics under another order of float32 sums: held
        to the float32 oracle at the tolerance the one-page walk is held to
        (``TestOperandRule.TOL`` over bf16 pages, the kernels' 2e-5 over
        float32 ones). The idle row over its NaN page yields zeros."""
        case = self._case(n_sel, dtype)
        call = jax.jit(functools.partial(
            _select_launch, scale=self.SCALE, interpret=True, k=k))
        got = np.asarray(call(*case).astype(jnp.float32))
        one = self._one_page(n_sel, dtype)
        assert np.isfinite(got).all() and np.isfinite(one).all()
        by_page = [self.IDLE, self.HOLES, self.ONE]
        assert np.array_equal(got[by_page], one[by_page])
        if k == 1:
            assert np.array_equal(got, one)
        assert not got[self.IDLE].any()
        tol = TestOperandRule.TOL if dtype == jnp.bfloat16 else 2e-5
        want = self._oracle(*case)
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
        np.testing.assert_allclose(one, want, rtol=tol, atol=tol)

    def test_the_call_picks_its_block_from_its_shapes(self, monkeypatch):
        """``paged_attention_selected`` through ``_pa_select_call``: lists of
        64 float32 pages of 8 x 32 walk eight a step, of 6 two, of 5 one; no
        argument says so."""
        import mmlspark_tpu.ops.paged_attention as pa
        seen = []
        inner = pa._select_launch

        def spy(*args, k, **kw):
            seen.append(k)
            return inner(*args, k=k, **kw)

        monkeypatch.setattr(pa, "_select_launch", spy)
        q, pool, bt, sel, lengths = self._case(64, jnp.float32)
        B = q.shape[0]
        want = self._oracle(q, pool, bt, sel, lengths)
        for n, k in ((64, 8), (6, 2), (5, 1)):
            pa._pa_select_call.clear_cache()
            lists = sel.reshape(B, self.G, -1)[..., :n]
            got = np.asarray(paged_attention_selected(
                q, pool, bt, lists, lengths, scale=self.SCALE,
                interpret=True))
            assert seen[-1] == k
            if n == 64:
                assert np.abs(got - want).max() < 2e-5
            else:
                assert np.abs(got - self._oracle(
                    q, pool, bt, lists, lengths)).max() < 2e-5
        pa._pa_select_call.clear_cache()


class TestDecodeParity:
    """Kernel vs gather through the full transformer decode paths."""

    def test_decode_step_kernel_vs_gather(self, params):
        B, L, page = 3, 16, 4
        rng = np.random.default_rng(0)
        pages, bt = _paged_state(params, B, L, page, 5, rng)
        tok = jnp.asarray(rng.integers(0, CFG.vocab, B))
        # 3 = mid-page write, 4 = page-boundary write, 0 = fresh slot
        pos = jnp.asarray([3, 4, 0], jnp.int32)
        want, want_pages = decode_step_paged(
            params, tok, pos, pages, bt, CFG, page_size=page, length=L,
            impl="gather")
        got, got_pages = decode_step_paged(
            params, tok, pos, pages, bt, CFG, page_size=page, length=L,
            impl="kernel")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        assert np.array_equal(np.argmax(np.asarray(got), -1),
                              np.argmax(np.asarray(want), -1))
        # layer 0's page writes are bitwise (same projection inputs);
        # deeper layers inherit the context drift, tolerance there
        assert np.array_equal(np.asarray(got_pages[0]["kv"]),
                              np.asarray(want_pages[0]["kv"]))
        for g, w in zip(got_pages[1:], want_pages[1:]):
            np.testing.assert_allclose(np.asarray(g["kv"]),
                                       np.asarray(w["kv"]),
                                       rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("gamma", [1, 4, 16])
    def test_decode_window_kernel_vs_gather(self, params, gamma):
        """Speculative verify windows: gamma+1 query rows, ragged pos
        crossing page boundaries."""
        B, L, page = 2, 64, 4
        W = gamma + 1
        rng = np.random.default_rng(gamma)
        pages, bt = _paged_state(params, B, L, page, 20, rng)
        wtoks = jnp.asarray(rng.integers(0, CFG.vocab, (B, W)))
        pos = jnp.asarray([7, 0], jnp.int32)   # page-crossing + fresh
        want, want_pages = decode_window_paged(
            params, wtoks, pos, pages, bt, CFG, page_size=page, length=L,
            impl="gather")
        got, got_pages = decode_window_paged(
            params, wtoks, pos, pages, bt, CFG, page_size=page, length=L,
            impl="kernel")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        assert np.array_equal(np.argmax(np.asarray(got), -1),
                              np.argmax(np.asarray(want), -1))
        assert np.array_equal(np.asarray(got_pages[0]["kv"]),
                              np.asarray(want_pages[0]["kv"]))

    def test_inactive_rows_write_trash_not_pages_kernel(self, params):
        B, L, page = 2, 16, 4
        rng = np.random.default_rng(2)
        pages, bt = _paged_state(params, B, L, page, 3, rng)
        n_pages = L // page
        before = [np.asarray(c["kv"]).copy() for c in pages]
        tok = jnp.asarray(rng.integers(0, CFG.vocab, B))
        active = jnp.asarray([True, False])
        _, pages = decode_step_paged(
            params, tok, jnp.full((B,), 3, jnp.int32), pages, bt, CFG,
            page_size=page, length=L, active=active, impl="kernel")
        for lyr, b4 in zip(pages, before):
            after = np.asarray(lyr["kv"])
            assert np.array_equal(after[1 + n_pages:], b4[1 + n_pages:])


@pytest.mark.parametrize("kind", ["single", "mesh"])
@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
class TestPackedPool:
    """The pool's layout, K beside V on the minor axis of one buffer a
    layer: every kernel reads it and every writer fills it alike, plain or
    quantized pages, on one device or mounted on the mesh."""

    def test_layout_is_k_beside_v(self, params, kv_dtype, kind):
        B, L, page = 4, 16, 4
        pages, bt = _paged_state(params, B, L, page, 6,
                                 np.random.default_rng(3), kv_dtype)
        hd = CFG.d_model // CFG.heads
        assert pages[0]["kv"].shape == (1 + B * (L // page), CFG.heads,
                                        page, 2 * hd)
        assert set(pages[0]) == ({"kv"} if kv_dtype is None
                                 else {"kv", "k_scale", "v_scale"})
        k, v = split_kv(pages[0]["kv"])
        assert np.array_equal(np.asarray(pack_kv(k, v)),
                              np.asarray(pages[0]["kv"]))
        # position t of row b sits in page bt[b, t // page] at t % page,
        # its K in lanes [0, hd) and its V in [hd, 2*hd)
        for got in paged_gather(pages[:1], bt, L):
            t, b = 5, 2
            pg = int(bt[b, t // page])
            want = np.asarray(pages[0]["kv"])[pg, :, t % page]
            if kv_dtype is None:
                assert np.array_equal(np.asarray(got["k"])[b, :, t],
                                      want[:, :hd])
                assert np.array_equal(np.asarray(got["v"])[b, :, t],
                                      want[:, hd:])

    def test_kernel_matches_gather_oracle_and_writeback_bytes(
            self, params, kv_dtype, kind):
        B, L, page, W = 4, 32, 4, 3
        rng = np.random.default_rng(21)
        pages, bt = _paged_state(params, B, L, page, 14, rng, kv_dtype)
        wtoks = jnp.asarray(rng.integers(0, CFG.vocab, (B, W)))
        # a page-crossing window, a fresh slot, mid-page, a page's start
        pos = jnp.asarray([7, 0, 13, 8], jnp.int32)
        want, want_pages = decode_window_paged(
            params, wtoks, pos, pages, bt, CFG, page_size=page, length=L,
            impl="gather")
        got, got_pages = decode_window_paged(
            params, wtoks, pos, pages, bt, CFG, page_size=page, length=L,
            impl="kernel", **mount(kind))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-5, atol=5e-5)
        assert np.array_equal(np.argmax(np.asarray(got), -1),
                              np.argmax(np.asarray(want), -1))
        # layer 0 projects the same inputs on both paths: the kernel's
        # in-launch scatter (one device) and _pool_write_rows (mesh) write
        # _paged_writeback's bytes, values and scales, off the trash page
        assert set(got_pages[0]) == set(want_pages[0])
        for kk in want_pages[0]:
            assert np.array_equal(np.asarray(got_pages[0][kk])[1:],
                                  np.asarray(want_pages[0][kk])[1:]), kk
        assert not np.array_equal(np.asarray(got_pages[0]["kv"])[1:],
                                  np.asarray(pages[0]["kv"])[1:])


class TestEngineSmoke:
    def test_engine_kernel_token_parity_and_zero_recompiles(self, params):
        """The engine on the kernel impl: token-identical to the
        reference path, and same-shape batches after the first are pure
        jit-cache hits (zero steady-state recompiles)."""
        eng = ContinuousDecoder(params, CFG, max_slots=2, max_len=48,
                                page_size=8, paged_attn="kernel")
        assert eng._attn_impl == "kernel"
        rng = np.random.default_rng(11)

        def run(prompt, n=6):
            r = eng.submit(prompt, max_new_tokens=n)
            while not r.done:
                eng.step()
            assert r.error is None
            return r

        p1 = rng.integers(1, CFG.vocab, 5).astype(np.int32)
        r1 = run(p1)
        want = generate_cached(params, p1[None, :], CFG, max_new_tokens=6)
        assert r1.tokens == list(np.asarray(want)[0, len(p1):])

        warm = jit_cache_size(eng._tick)
        run(rng.integers(1, CFG.vocab, 5).astype(np.int32))
        run(rng.integers(1, CFG.vocab, 5).astype(np.int32))
        after = jit_cache_size(eng._tick)
        if warm is not None:                    # introspection available
            assert after == warm
        # every tick was accounted to the kernel impl, zero gather bytes
        assert eng._kv.stats["attn_ticks_kernel"] > 0
        assert eng._kv.stats["attn_ticks_gather"] == 0
        assert eng._kv.stats["gather_bytes"] == 0
        assert eng._kv.pages_in_use == 0

    def test_engine_gather_fallback_counts_bytes(self, params):
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=32,
                                page_size=4, paged_attn="gather")
        assert eng._attn_impl == "gather"
        rng = np.random.default_rng(12)
        r = eng.submit(rng.integers(1, CFG.vocab, 4).astype(np.int32),
                       max_new_tokens=4)
        while not r.done:
            eng.step()
        assert eng._kv.stats["attn_ticks_gather"] > 0
        assert eng._kv.stats["gather_bytes"] > 0

    def test_engine_env_knob_reaches_engine(self, params, monkeypatch):
        monkeypatch.setenv(ENV_KNOB, "gather")
        eng = ContinuousDecoder(params, CFG, max_slots=1, max_len=32,
                                page_size=4)
        assert eng._attn_impl == "gather"
