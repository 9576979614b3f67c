"""The decoder's two shared seams: every cached entry point, of the dense
block and of the hybrid one, reads the token table through ONE ``_embed`` and
makes its logits through ONE ``head`` (``models/zoo/transformer.py``;
``hybrid.py`` binds the same two functions). An edit of either function
(ROADMAP S1: the table's layout; S11: the head's precision) then reaches the
tick, both prefills, the chunk window, the gather oracle and the offline
generators at once.

Each case replaces one seam by a marked one, wherever the name is bound, and
looks for the mark in the entry point's output:

* ``head`` -> a one-hot at token ``MARK``: every logit row must be it;
* ``_embed`` -> the same read from a table whose rows are rolled by one: the
  output must equal the unpatched entry point's on parameters holding that
  table, and differ from its output on the real ones.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models.zoo import hybrid
from mmlspark_tpu.models.zoo import transformer as T

MARK = 5
B, L, PAGE = 2, 24, 8
#: GPT-2's block at toy widths: LayerNorm and learned positions
DENSE = T.TransformerConfig(vocab=61, layers=2, d_model=32, heads=4, d_ff=64,
                            max_len=L, causal=True, dtype=jnp.float32)
#: the hybrid block at tests/test_hybrid_decoder.py's widths
HYBRID = T.TransformerConfig(
    vocab=97, layers=4, d_model=64, heads=4, d_ff=128, max_len=L,
    causal=True, dtype=jnp.float32, norm="rmsnorm", position="rope",
    mixers=("sparse", "lightning", "lightning", "sparse"), kv_heads=2,
    head_dim=16, embed_scale=12.0, residual_scale=1.4 / 32 ** 0.5,
    logit_scale=0.25,
    sparse=T.SparseAttention(kernel_size=4, kernel_stride=2, block_size=8,
                             topk=6, window_size=16, init_blocks=1,
                             dense_len=64))
BT = jnp.asarray(1 + np.arange(B * (L // PAGE)).reshape(B, -1), jnp.int32)
POS = jnp.asarray([3, 5], jnp.int32)


def _tokens(cfg, *shape):
    return jnp.asarray(np.random.default_rng(1).integers(1, cfg.vocab, shape),
                       jnp.int32)


def _paged(step, impl):
    def run(p):
        pool = T.init_paged_cache(DENSE, 1 + BT.size, PAGE)
        fn = T.decode_step_paged if step else T.decode_window_paged
        toks = _tokens(DENSE, B) if step else _tokens(DENSE, B, 4)
        return fn(p, toks, POS, pool, BT, DENSE, page_size=PAGE, length=L,
                  impl=impl)[0]
    return run


def _hybrid_paged(impl, width):
    def run(p):
        pool = hybrid.init_hybrid_pool(HYBRID, 1 + BT.size, PAGE, B, L)
        return hybrid.window_paged(p, _tokens(HYBRID, B, width), POS, pool,
                                   BT, HYBRID, page_size=PAGE, impl=impl)[0]
    return run


def _dense_cache():
    return T.init_kv_cache(DENSE, B, L)


#: name -> (configuration, params -> logits, or token ids for a generator)
ENTRIES = {
    "decode_step": (DENSE, lambda p: T.decode_step(
        p, _tokens(DENSE, B), 3, _dense_cache(), DENSE)[0]),
    "decode_step_ragged": (DENSE, lambda p: T.decode_step_ragged(
        p, _tokens(DENSE, B), POS, _dense_cache(), DENSE)[0]),
    "decode_window": (DENSE, lambda p: T.decode_window(
        p, _tokens(DENSE, B, 4), 2, _dense_cache(), DENSE)[0]),
    "decode_window_ragged": (DENSE, lambda p: T.decode_window_ragged(
        p, _tokens(DENSE, B, 4), POS, _dense_cache(), DENSE)[0]),
    "prefill_cache": (DENSE, lambda p: T.prefill_cache(
        p, _tokens(DENSE, B, 6), jnp.asarray([6, 4]), DENSE, L)[0]),
    "decode_step_paged[kernel]": (DENSE, _paged(True, "kernel")),
    "decode_step_paged[gather]": (DENSE, _paged(True, "gather")),
    "decode_window_paged[kernel]": (DENSE, _paged(False, "kernel")),
    "decode_window_paged[gather]": (DENSE, _paged(False, "gather")),
    "generate_cached": (DENSE, lambda p: T.generate_cached(
        p, _tokens(DENSE, B, 3), DENSE, max_new_tokens=3)[:, 3:]),
    "hybrid.window_contiguous": (HYBRID, lambda p: hybrid.head(
        p, hybrid.window_contiguous(
            p, _tokens(HYBRID, B, 4), POS,
            hybrid.init_hybrid_cache(HYBRID, B, L), HYBRID)[0])),
    "hybrid.window_paged[gather]": (HYBRID, _hybrid_paged("gather", 4)),
    "hybrid.window_paged[kernel]": (HYBRID, _hybrid_paged("kernel", 1)),
}


@pytest.fixture(scope="module")
def weights():
    return {cfg: jax.tree.map(jnp.asarray, T.init_transformer(cfg, seed=3))
            for cfg in (DENSE, HYBRID)}


@pytest.fixture
def fresh_traces(request):
    """``generate_cached`` keeps its traced scan by configuration: a seam
    replaced under it is seen only by a new trace, and a trace made under a
    replaced seam must not outlive the test. The other entry points here
    run untraced."""
    traced = request.node.callspec.params["entry"] == "generate_cached"
    yield jax.clear_caches if traced else (lambda: None)
    if traced:
        jax.clear_caches()


def _replace(monkeypatch, name, marked):
    assert getattr(hybrid, name) is getattr(T, name)     # one function
    for module in (T, hybrid):
        monkeypatch.setattr(module, name, marked)


@pytest.mark.parametrize("seam", ["head", "_embed"])
@pytest.mark.parametrize("entry", list(ENTRIES))
def test_every_cached_entry_point_runs_the_shared_seam(
        entry, seam, weights, monkeypatch, fresh_traces):
    cfg, run = ENTRIES[entry]
    params = weights[cfg]
    if seam == "head":
        def one_hot(params, hidden):
            return jax.nn.one_hot(jnp.full(hidden.shape[:-1], MARK),
                                  cfg.vocab, dtype=jnp.float32)
        fresh_traces()
        _replace(monkeypatch, "head", one_hot)
        out = np.asarray(run(params))
        if entry == "generate_cached":
            assert (out == MARK).all()
        else:
            assert out.shape[-1] == cfg.vocab
            assert (out.argmax(-1) == MARK).all() and (out.max(-1) == 1).all()
        return
    rolled = dict(params, embed=dict(
        params["embed"], tok=jnp.roll(params["embed"]["tok"], 1, axis=0)))
    plain, want = np.asarray(run(params)), np.asarray(run(rolled))
    assert (plain != want).any()
    real = T._embed
    fresh_traces()
    _replace(monkeypatch, "_embed",
             lambda params, *a, **kw: real(rolled, *a, **kw))
    np.testing.assert_array_equal(np.asarray(run(params)), want)
