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

``_embed`` itself (PR 41): a table whose width is no multiple of the chip's
128 lanes is read in place (row slices, or a 0/1 product past
``_SLICED_ROWS`` rows) and gives ``table[tokens]`` bit for bit; a width of
whole lanes keeps the gather, its traced text as it was.
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


# ---- the table read itself -------------------------------------------------

#: a vocabulary no multiple of anything, positions enough for a 256-lane
#: window; the widths are GPT-2 XL's (12.5 registers of 128 lanes: read in
#: place) and LFM2's (16 registers: the gather)
V, POSITIONS = 997, 300
WIDTHS = {1600: "in_place", 2048: "gather"}


def _tables(width):
    rng = np.random.default_rng(width)
    cfg = T.TransformerConfig(vocab=V, layers=1, d_model=width,
                              heads=width // 64, d_ff=64, max_len=POSITIONS,
                              causal=True, dtype=jnp.bfloat16)
    draw = lambda rows: jnp.asarray(                        # noqa: E731
        rng.normal(size=(rows, width)), jnp.bfloat16)
    return cfg, {"embed": {"tok": draw(V), "pos": draw(POSITIONS)}}, rng


def _bits(a):
    return np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16))


@pytest.mark.parametrize("positions", ["leading", "wpos"])
@pytest.mark.parametrize("window", [1, 256])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_embed_reads_the_rows_the_gather_reads(width, window, positions):
    """Both tables' rows, bit for bit, in both in-place forms (3 rows: slices;
    768: the product) and through the gather: the first id, the last, and a
    row read twice among them."""
    cfg, params, rng = _tables(width)
    ids = rng.integers(0, V, (3, window))
    ids[0, 0], ids[-1, -1], ids[1, 0] = 0, V - 1, ids[2, 0]
    ids = jnp.asarray(ids, jnp.int32)
    wpos = (None if positions == "leading" else
            jnp.asarray(rng.integers(0, POSITIONS, (3, window)), jnp.int32))
    tok, pos = params["embed"]["tok"], params["embed"]["pos"]
    want = tok[ids] + (pos[:window][None] if wpos is None else pos[wpos])
    got = jax.jit(lambda p, i, w: T._embed(p, i, cfg, w))(params, ids, wpos)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert T.embed_read(width) == WIDTHS[width]


def _primitives(width, window, wpos, **kw):
    cfg, params, _ = _tables(width)
    ids = jnp.zeros((2, window), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, i: T._embed(
        p, i, cfg, None if not wpos else i, **kw))(params, ids)
    names = [eqn.primitive.name for eqn in jaxpr.jaxpr.eqns]
    return {n: names.count(n)
            for n in ("gather", "dynamic_slice", "dot_general")}


@pytest.mark.parametrize("window", [1, 256])
def test_a_width_of_whole_lanes_keeps_its_gather(window):
    """The three hybrid cells' tables (4096, 2560, 2048 wide) are read as
    they were, so their programs' text cannot move: one gather a table, no
    slice, no product; the hybrid block reads no position table."""
    assert _primitives(2048, window, wpos=False) == {
        "gather": 1, "dynamic_slice": 0, "dot_general": 0}
    assert _primitives(2048, window, wpos=True) == {
        "gather": 2, "dynamic_slice": 0, "dot_general": 0}
    for width in (4096, 2560, 2048, 768, 128):
        assert T.embed_read(width) == "gather"


def test_the_form_follows_the_row_count_and_the_caller_may_keep_the_gather():
    """At GPT-2 XL's width: a slice a row up to ``_SLICED_ROWS`` rows (a
    tick's 8-64), one product past it (a window), and the plain gather for
    the training forward, which is differentiated and sharded."""
    few = T._SLICED_ROWS // 2
    assert _primitives(1600, few, wpos=True) == {
        "gather": 0, "dynamic_slice": 4 * few, "dot_general": 0}
    assert _primitives(1600, 256, wpos=True) == {
        "gather": 0, "dynamic_slice": 0, "dot_general": 2}
    assert _primitives(1600, 256, wpos=True, gather=True) == {
        "gather": 2, "dynamic_slice": 0, "dot_general": 0}


@pytest.mark.parametrize("rows", [4, 128], ids=["slices", "product"])
def test_an_id_outside_the_table_reads_what_the_gather_reads(rows):
    """No form differs silently from the gather: a negative id counts from
    the end, one past either end reads the nearest row."""
    _, params, _ = _tables(1600)
    table = params["embed"]["tok"]
    ids = jnp.asarray(np.resize([-1, -V, -V - 3, V, V + 5, 0, V - 1, 7],
                                rows), jnp.int32)
    np.testing.assert_array_equal(_bits(T._rows(table, ids)),
                                  _bits(T._rows(table, ids, gather=True)))


def test_the_product_form_needs_a_finite_table():
    """What the docstring says of the 0/1 product: 0 x Inf is NaN in that
    column of every OTHER row and a stored -0.0 comes back +0.0; row slices
    select bits whatever they are."""
    _, params, _ = _tables(1600)
    table = params["embed"]["tok"].at[5, 3].set(jnp.inf).at[6, 4].set(-0.0)
    few, many = jnp.asarray([5, 6, 7]), jnp.arange(128) % 8
    np.testing.assert_array_equal(_bits(T._rows(table, few)),
                                  _bits(table[few]))
    got = np.asarray(T._rows(table, many).astype(jnp.float32))
    assert np.isnan(got[np.asarray(many) != 5, 3]).all()    # not its rows
    assert np.isfinite(got[:, :3]).all()
    assert not np.signbit(got[6, 4])
    clean = jnp.nan_to_num(table, posinf=1.0)
    np.testing.assert_array_equal(
        np.asarray(T._rows(clean, many).astype(jnp.float32)),
        np.asarray(clean[many].astype(jnp.float32)))


@pytest.mark.parametrize("width,form", [(32, "in_place"), (128, "gather")])
def test_a_decoder_names_the_form_it_got(width, form):
    """``stats["embed_read"]`` and the gauge, set once at construction."""
    from mmlspark_tpu.serving import continuous
    cfg = DENSE._replace(d_model=width)
    dec = continuous.ContinuousDecoder(T.init_transformer(cfg, seed=0), cfg,
                                       max_slots=2, max_len=L)
    assert dec.stats["embed_read"] == form
    for f in ("gather", "in_place"):
        assert continuous._M_EMBED_READ.labels(form=f).get() == (f == form)


# ---- the third seam: a mixer kind is one record -------------------------------

#: every kind's own configuration beside the hybrid block's, at toy widths
EVERY = HYBRID._replace(
    max_len=48, kda=T.DeltaRule(conv_kernel=3),
    latent=T.LatentAttention(latent=32, nope=16, rope=8, value=16),
    conv=T.ShortConv(taps=3),
    ssm=T.StateSpace(heads=4, head_dim=16, state=16, groups=2, taps=3,
                     chunk=8))
#: the label a kind's decode calls count under, on the Pallas decode kernels
LABELS = {"lightning": None, "sparse": "dense", "kda": "kda", "mla": "latent",
          "conv": "conv", "gqa": "gqa", "ssm": "ssm"}


def test_the_registry_names_the_kinds_in_their_order():
    assert hybrid.MIXERS == tuple(hybrid.KINDS) == tuple(LABELS)


@pytest.mark.parametrize("kind", list(LABELS))
def test_a_kind_is_one_complete_record(kind):
    """A model of the kind alone is checked, built, cached, pooled, ticked
    and counted from the kind's record: the cache and the pool hold the same
    rows a slot and pages where the cache holds K and V, and an engine's
    decode calls count under the kind's label."""
    from mmlspark_tpu.serving.continuous import ContinuousDecoder
    record = hybrid.KINDS[kind]
    for field in ("init", "cache", "pool", "contiguous", "paged"):
        assert callable(getattr(record, field)), field
    cfg = EVERY._replace(layers=2, mixers=(kind,) * 2)
    hybrid.check_config(cfg)
    cache = hybrid.init_hybrid_cache(cfg, 2, 48)
    pool = hybrid.pool_shapes(cfg, 13, 8, 2, 48)
    for c, p in zip(cache, pool):
        rows = set(c) & set(hybrid.SLOT_KEYS)
        assert rows == set(p) & set(hybrid.SLOT_KEYS)
        assert ("kv" in p) == bool(set(c) - rows)
        for key in rows - {"ck"}:       # the scorer's keys follow the length
            assert c[key].shape == p[key][0] and c[key].dtype == p[key][1]
    params = hybrid.init_hybrid(cfg, 1)
    assert all(set(record.init(cfg, np.random.default_rng(0))) <= set(lp)
               for lp in params["layers"])
    dec = ContinuousDecoder(params, cfg, max_slots=2, max_len=48,
                            prefill_chunk=16)
    assert dec._page == (hybrid.required_page(cfg) or 16)
    req = dec.submit(_tokens(cfg, 20), 3)
    while not req.done:
        dec.step()
    assert len(req.tokens) == 3
    ticks = {k: v for k, v in dec._kv.stats.items()
             if k.startswith("attn_ticks_")
             and k not in ("attn_ticks_kernel", "attn_ticks_gather")}
    label = LABELS[kind]
    assert set(ticks) == ({"attn_ticks_" + label} if label else set())
    if kind != "sparse":                # a sparse model counts windows too
        assert all(n == dec.stats["ticks"] for n in ticks.values())
    assert [type(a) for a in dec._accountants] == (
        [record.counts] if record.counts else [])


@pytest.mark.parametrize("module", ["kv_pool", "continuous"])
def test_the_server_names_no_kind(module):
    """The page pool and the scheduler read records: neither holds a kind's
    name as a string, reads a kind's configuration, or imports more of the
    model's block and its kernels than the allocation, the programs, the
    layout its weights are served in and the page-alignment helpers."""
    import ast
    import mmlspark_tpu.serving as serving
    path = f"{serving.__path__[0]}/{module}.py"
    allowed = {
        ("kv_pool", "hybrid"): {"SLOT_KEYS", "pool_shapes"},
        ("kv_pool", "paged_attention"): {"sublane_multiple",
                                         "aligned_page_size"},
        ("continuous", "hybrid"): {"SLOT_KEYS", "Geometry", "accountants",
                                   "check_config", "required_page",
                                   "serving_layout", "tick_with_window"},
        ("continuous", "paged_attention"): {"resolve_impl",
                                            "_auto_interpret"},
    }
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            assert node.value not in hybrid.KINDS, (node.lineno, node.value)
        if isinstance(node, ast.Attribute):
            assert node.attr not in ("sparse", "latent", "kda", "conv",
                                     "ssm"), (node.lineno, node.attr)
        if isinstance(node, ast.ImportFrom) and node.module:
            source = node.module.rsplit(".", 1)[-1]
            if source in ("hybrid", "paged_attention"):
                names = {a.name for a in node.names}
                assert names <= allowed[module, source], (node.lineno, names)
