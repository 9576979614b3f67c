"""Hybrid decoder block — linear-attention state beside sparse-attention pages.

``TransformerConfig.mixers`` names one mixer a layer (MiniCPM-SALA's shape:
lightning linear attention on three layers of four, InfLLM-V2 block-sparse
softmax attention on the fourth). Everything here computes ONE thing, a
window of ``W`` tokens a row continuing that row's cache, and the public
entry points of ``transformer.py`` (``transformer_apply``, ``decode_step``,
``prefill_cache``, ``decode_step_paged``, ``decode_window_paged``) reach it
when ``cfg.mixers`` is set:

* ``lightning`` — per head, in float32, ``S_t = lambda_h S_(t-1) + k_t^T
  v_t``, ``o_t = q_t S_t / sqrt(hd)``, ``lambda_h = exp(-s_h)``,
  ``s_h = 2^(-8h/H)``; q and k RMS-normed per head and rotated (RoPE). The
  cache entry is the state ``(rows, H, hd, hd)`` float32, not pages. A
  window runs the chunked form (decays taken from position differences,
  never as a ratio of powers); the decode tick runs the Pallas step of
  ``ops/lightning_attention.py``.
* ``sparse`` — grouped-query softmax attention without positions over a
  paged K/V cache plus a cache of compressed keys (the mean of
  ``kernel_size`` keys every ``kernel_stride``, entry ``f`` for the window
  that ENDS at position ``stride * f + stride - 1``), a row a slot like a
  state: the block scorer reads all of a row's entries every tick, and
  gathered through a block table that cost more than the attention did
  (PERF.md, PR 29). A query with more than
  ``dense_len`` of context attends the blocks ``sparse_select`` chooses for
  its KV group; the decode tick hands those blocks to
  ``ops.paged_attention.paged_attention_selected``, a window masks them.

Both mixers end in a sigmoid output gate; the block is bias-free with
RMSNorm and SwiGLU, and carries muP's three scalings.

Two more mixers and a second feed-forward (a decoder with routed experts,
a delta-rule linear attention and latent attention; ``cfg.kda``,
``cfg.latent``, ``cfg.ffn`` / ``cfg.routed``):

* ``kda`` - per head, float32, on a ``(d x d)`` state: ``S_t = (I - beta_t
  k_t k_t^T) diag(alpha_t) S_(t-1) + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``;
  q, k, v pass a causal depthwise convolution of ``conv_kernel`` taps and a
  SiLU, q and k are l2-normalised (q also divided by ``sqrt(d)``),
  ``alpha_t = exp(g_t)``, ``g_t = gate_floor * sigmoid(exp(A_h) (W_f x_t +
  b))`` a channel, ``beta_t = sigmoid(W_b x_t)`` a head. No positions. The
  cache entry is the state plus ``{"conv"}``: the last ``conv_kernel - 1``
  pre-convolution rows of q, k and v, a row a slot. A window runs the
  chunked form (:func:`kda_chunk`), the decode tick
  ``ops.kda_attention.kda_decode_step``. Output: per-head RMSNorm, a
  sigmoid gate a HEAD, ``W_o``.
* ``mla`` - softmax attention whose cached row a token is the normed
  ``latent``-wide key/value latent beside one rotated ``rope``-wide key all
  heads share: pages ``(pages, 1, page, latent + rope)``. A window rebuilds
  K and V from the latents (expanded) a tile of keys at a time, up to its
  row's last written key (:func:`_mla_window_call`: what it holds and what
  it costs follow the context, not ``max_len``); the decode tick folds
  ``W_kvb``'s key half into the query and applies its value half after the
  weighted sum of latents (absorbed), through
  ``ops.paged_attention.paged_attention_latent``. ``cfg.latent.q_rank``
  projects the query through a normed latent of its own; ``cfg.latent.gate``
  says whether the layer ends in a sigmoid gate a head or in ``W_o`` alone.
* feed-forward ``"moe"`` - ``parallel.moe.moe_topk_held``: top-k dropless
  routing over all experts, the held experts' part of the result, a shared
  expert.

And two mixers that end in ``W_o`` alone, no output gate (a decoder of gated
short convolutions beside grouped-query attention; ``cfg.conv``):

* ``conv`` - ``[B, C, u] = W_in x`` (thirds of ``3 d_model``), ``z = B * u``,
  ``c_t = sum_j w_j z_(t - taps + 1 + j)`` (depthwise, causal, ``cfg.conv.taps``
  taps a channel, zeros before the start, no activation), ``y = W_o(C *
  c)``. The whole cache is ``{"conv"}``: the last ``taps - 1`` rows of ``z``,
  a row a slot. No kernel: the taps fuse beside the two projections.
* ``gqa`` - full softmax attention, ``heads`` queries over ``kv_heads`` keys
  and values, q and k RMS-normed per head (``cfg.norm_eps``) and rotated
  (rotate-half RoPE over the whole head), pages ``{"kv"}`` (pages, Hkv, page,
  2 hd) as a sparse layer's and nothing a slot. A window scatters its K/V
  through the block table and attends its row's gathered pages under the
  causal mask; the decode tick is ONE fused launch,
  ``ops.paged_attention.paged_attention_gqa``. With ``cfg.qk_positions``
  False the layer is plain: no q/k norm, no rotation.

A seventh mixer, a latent form of the routed feed-forward, and layers that
are their mixer alone (a decoder of selective state-space layers, routed
experts in a latent and one grouped-query layer in a period; ``cfg.ssm``):

* ``ssm`` - Mamba-2: ``[z | xBC | dt] = W_in x``; ``xBC`` passes a causal
  depthwise convolution of ``taps`` taps with a bias and a SiLU and splits
  into ``u`` (``heads`` of ``head_dim``), ``B`` and ``C`` (``groups`` of
  ``state``); a head, in float32: ``d_t = softplus(dt_t + dt_bias_h)``,
  ``a_t = exp(d_t A_h)``, ``A_h = -exp(A_log_h)``, ``S_t = a_t S_(t-1) +
  d_t u_t B_t^T`` on a ``head_dim x state`` state (``B``, ``C`` of the
  head's group), ``y_t = S_t C_t + D_h u_t``; out ``W_out(GroupRMSNorm(y *
  silu(z)))``, the norm over each of the ``groups`` groups of channels. The
  cache entry is ``{"state", "conv"}``: the state a row a slot in the
  step's layout (``ops.ssm_step``: transposed, two heads side by side,
  ``(rows, heads / 2, state, 2 head_dim)`` float32) and the last ``taps -
  1`` pre-convolution rows of ``xBC``. A window runs the chunked scan
  (:func:`ssm_chunk`), the decode tick ``ops.ssm_step.ssm_decode_step``.
* feed-forward ``"moe"`` with ``cfg.routed.latent`` / ``form "relu2"``: the
  experts are ``relu(l W_1)^2 W_2`` on ``l = W_dn x``, the weighted sum goes
  back through ``W_up`` (``parallel.moe.moe_topk_held``); ``"none"``: the
  layer is ``x += mixer(norm(x))`` alone.

No eighth kind, but three of the model's own numbers on the kinds above (a
decoder of nine ssm layers of ONE group to one plain gqa layer with a routed
feed-forward in every layer, under muP's multipliers and a tied head):

* ``cfg.routed.score == "softmax"``: the router is a bias-free linear map,
  the ``per_token`` largest LOGITS are chosen and the weights are a softmax
  over the chosen logits alone (``parallel.moe.route_topk``); the layer
  holds no selection bias.
* ``cfg.attn_scale``: what a gqa layer's scores are multiplied by in the
  window's fold and in the decode kernel alike (0: ``head_dim ** -0.5``; a
  muP model states ``1 / head_dim``).
* ``cfg.tied_head``: the parameters hold no ``lm_head`` and
  ``transformer.head`` reads ``embed.tok``, where it lies.

Two parameter trees. The tree a caller hands in is :func:`init_hybrid`'s:
every projection a leaf ``{"w": [in, out]}``, and so does every reference
and every public entry point of ``transformer.py`` read it. The tree a
``ContinuousDecoder`` SERVES from is :func:`serving_layout` of it, made once
at construction: a ``lightning`` or ``sparse`` layer's ``q``, ``k`` and
``v`` are held as ``{"wt": [heads x hd, in]}`` (``Mixer.served``), because
the compiled product that feeds ``_heads`` reads its weight so and re-laid
an ``[in, out]`` one on its way into VMEM in every program (PERF.md, PR 50);
every other leaf is the caller's own array. :func:`_proj` reads either form
by the leaf's key, ``wt`` contracted on its second axis: the same product,
the same float32 accumulation. The caller's tree is never written.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .transformer import (TransformerConfig, _embed, _rms, _rope_tables,
                          _rot_half, head)

__all__ = ["check_config", "dims", "init_hybrid", "init_hybrid_cache",
           "init_hybrid_pool", "lightning_rates", "lightning_chunk",
           "sparse_select", "kda_chunk", "ssm_chunk", "head",
           "serving_layout", "window_contiguous",
           "window_paged", "tick_with_window", "SLOT_KEYS", "KINDS",
           "MIXERS", "Mixer", "Window", "Geometry", "accountants",
           "required_page"]

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
_NEG = -1e30
#: keys of a pool layer dict whose axis 0 is the SLOT, not the physical page:
#: a lightning or kda layer's state, a sparse layer's compressed keys, a kda
#: layer's convolution tails. A cached prefix keeps a snapshot of these rows
#: beside its pages.
SLOT_KEYS = ("state", "ck", "conv")
#: tokens a step of the chunked delta rule (:func:`kda_chunk`)
KDA_CHUNK = 64
#: keys of K/V a masked window folds at a time (a 32k context in one piece
#: would hold a gigabyte of scores)
_KEY_TILE = 2048


class Window(NamedTuple):
    """Whose cache a window of tokens continues, as a kind's window function
    reads it; past ``n_valid``, over the engine's pool alone."""
    cfg: TransformerConfig
    pos: jax.Array                      # (B,) the rows' first positions
    n_valid: jax.Array                  # (B,) their real lanes
    bt: Optional[jax.Array] = None      # (B, P) the block table
    page: int = 0
    kernel: bool = False    # a one-token window on the Pallas decode kernels
    #: the slot whose rows a one-row prefill window continues (None: row
    #: ``b`` is slot ``b``)
    slot: Optional[jax.Array] = None
    tick: bool = False      # one token a row


class Geometry(NamedTuple):
    """What a kind's host accounting reads of the engine."""
    page_size: int
    pages_a_slot: int       # the width of a slot's block table
    kernel: bool            # one-token windows run the Pallas decode kernels


class Mixer(NamedTuple):
    """What a mixer kind is, declared once (:data:`KINDS`); this module's
    functions look a layer's kind up there and the engine reads
    :func:`pool_shapes`, :func:`required_page` and :func:`accountants`, never
    a kind's name. ``contiguous`` and ``paged`` are ``(lp, x, c, wpos, w)``
    -> ``(y, c after)``: the window ``x`` (B, W, D) at ``wpos`` (B, W)
    continuing the layer's entry ``c`` of either cache under ``w``
    (:class:`Window`); the decode tick is the paged one-token window."""
    #: ``(cfg, rng)``: the parameters a layer holds beside its norms and
    #: feed-forward, drawn from the model's one generator
    init: Callable
    cache: Callable         # (cfg, batch, max_len): its contiguous entry
    #: ``(cfg, num_pages, page_size, slots, positions)``: its entry in the
    #: engine's pool, ``{key: (shape, dtype)}``; pages under ``kv``, rows a
    #: slot under :data:`SLOT_KEYS`
    pool: Callable
    contiguous: Callable
    paged: Callable
    check: Optional[Callable] = None    # (cfg): its clause of check_config
    #: what a decode call of the model counts under, once a kind: ``(on the
    #: Pallas decode kernel, on the window's form)``
    labels: Optional[Tuple[str, str]] = None
    page: Optional[Callable] = None     # (cfg): the page it requires
    #: ``(cfg, kind, geometry)``: its host accounting (:class:`TickCounts`)
    counts: Optional[Callable] = None
    #: the projections of ``init``'s a decoder holds as ``{"wt": [out, in]}``
    #: (:func:`serving_layout`): those whose product reads its weight so
    served: Tuple[str, ...] = ()


def dims(cfg: TransformerConfig):
    """(query heads, KV heads of the sparse layers, head size)."""
    return (cfg.heads, cfg.kv_heads or cfg.heads,
            cfg.head_dim or cfg.d_model // cfg.heads)


def check_config(cfg: TransformerConfig) -> None:
    if len(cfg.mixers) != cfg.layers:
        raise ValueError(f"{len(cfg.mixers)} mixers for {cfg.layers} layers")
    unknown = set(cfg.mixers) - set(MIXERS)
    if unknown:
        raise ValueError(f"unknown mixer kinds {sorted(unknown)} "
                         f"({' | '.join(MIXERS)})")
    if not cfg.causal or cfg.moe_experts or cfg.use_flash:
        raise ValueError("a hybrid decoder is causal, takes its routed "
                         "feed-forward from cfg.ffn / cfg.routed (not "
                         "moe_experts) and does not take use_flash")
    if cfg.ffn:
        if (len(cfg.ffn) != cfg.layers
                or set(cfg.ffn) - {"dense", "moe", "none"}):
            raise ValueError(f"ffn {cfg.ffn}: one of dense | moe | none a "
                             "layer")
        if "moe" in cfg.ffn:
            r = cfg.routed
            if r is None or not r.d_expert:
                raise ValueError("moe layers need cfg.routed")
            if (r.experts % r.groups or r.groups_kept > r.groups
                    or r.per_token > r.groups_kept * (r.experts // r.groups)):
                raise ValueError(f"routing {r}: groups must divide the "
                                 "experts and the kept groups hold per_token")
            if r.first < 0 or r.first + r.held > r.experts:
                raise ValueError(f"experts held [{r.first}, "
                                 f"{r.first + r.held}) of {r.experts}")
            if r.held < 8:
                raise ValueError(f"{r.held} experts held: a share of a "
                                 "routed layer is at least 8")
            if r.form not in ("swiglu", "relu2") or r.latent < 0:
                raise ValueError(f"experts of form {r.form!r} in a latent "
                                 f"of {r.latent}: swiglu | relu2, >= 0")
            if r.score not in ("sigmoid", "softmax"):
                raise ValueError(f"router score {r.score!r}: sigmoid | "
                                 "softmax")
            if any(r.swiglu_limits):
                raise ValueError(
                    f"swiglu limits {r.swiglu_limits}: a held layer names a "
                    "clamp, whose form is not built (only limit 0)")
    H, Hkv, hd = dims(cfg)
    if H % Hkv or hd % 2:
        raise ValueError(f"heads {H} / kv_heads {Hkv} / head_dim {hd}")
    for kind in dict.fromkeys(cfg.mixers):
        if KINDS[kind].check:
            KINDS[kind].check(cfg)


def _ffn_kind(cfg: TransformerConfig, i: int) -> str:
    return cfg.ffn[i] if cfg.ffn else "dense"


def _dense(rng, din, dout, scale=None):
    s = scale or np.sqrt(2.0 / (din + dout))
    return {"w": rng.normal(0, s, (din, dout)).astype(np.float32)}


def _ones(n):
    return {"scale": np.ones(n, np.float32)}


def _moe_init(cfg, rng):
    r = cfg.routed
    D, F = cfg.d_model, r.d_expert
    L = r.latent or D           # the width the experts read and write
    wide = F if r.form == "relu2" else 2 * F
    s = np.sqrt(2.0 / (L + F))
    p = {"router": _dense(rng, D, r.experts)}
    if r.score == "sigmoid":        # a softmax router selects by its logits
        p["bias"] = rng.normal(0, 0.01, r.experts).astype(np.float32)
    p["experts"] = {
        "up" if r.form == "relu2" else "gate_up":
            rng.normal(0, s, (r.held, L, wide)).astype(np.float32),
        "down": rng.normal(0, s, (r.held, F, L)).astype(np.float32)}
    if r.latent:
        p["to_latent"] = _dense(rng, D, L)
        p["from_latent"] = _dense(rng, L, D)
    if r.d_shared:
        p["shared"] = dict(
            {"gate": _dense(rng, D, r.d_shared)} if r.form != "relu2" else {},
            up=_dense(rng, D, r.d_shared), down=_dense(rng, r.d_shared, D))
    return p


def init_hybrid(cfg: TransformerConfig, seed: int = 0) -> Dict:
    """Random parameters in the pytree the hybrid block reads: a layer's
    norms, what its mixer's kind holds (``Mixer.init``), its feed-forward."""
    check_config(cfg)
    rng = np.random.default_rng(seed)
    D = cfg.d_model
    layers = []
    for i, kind in enumerate(cfg.mixers):
        lp = {"ln1": _ones(D)}
        lp.update(KINDS[kind].init(cfg, rng))
        feed = _ffn_kind(cfg, i)
        if feed != "none":
            lp["ln2"] = _ones(D)
        if feed == "moe":
            lp["moe"] = _moe_init(cfg, rng)
        elif feed == "dense":
            lp.update({"gate": _dense(rng, D, cfg.d_ff),
                       "up": _dense(rng, D, cfg.d_ff),
                       "down": _dense(rng, cfg.d_ff, D)})
        layers.append(lp)
    params = {"embed": {"tok": _dense(rng, cfg.vocab, D, 0.02)["w"]},
              "layers": layers, "final_ln": _ones(D)}
    if not cfg.tied_head:       # a tied head is the token table
        params["lm_head"] = _dense(rng, D, cfg.vocab, 0.02)
    return params


def serving_layout(cfg: TransformerConfig, params: Dict) -> Dict:
    """The tree a decoder serves from: ``params`` with every projection a
    layer's kind declares (``Mixer.served``) held as ``{"wt": w.T}``, laid
    out ONCE as its product reads it (:func:`_proj`). Every other leaf, and
    the whole tree of a model whose kinds declare nothing, is the caller's
    own object; the caller's tree is not written. Shapes alone will do
    (``jax.eval_shape``)."""
    served = [KINDS[kind].served for kind in cfg.mixers]
    if not any(served):
        return params
    return dict(params, layers=[
        dict(lp, **{name: {"wt": lp[name]["w"].T} for name in names})
        if names else lp for lp, names in zip(params["layers"], served)])


# ---- caches -----------------------------------------------------------------

def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _zeros(shapes):
    return {key: jnp.zeros(*sd) for key, sd in shapes.items()}


def _slot_kind(rows, layer):
    """The entries and the windows of a kind that holds rows a slot and no
    page: ``rows(cfg, n)`` for the batch's rows or the engine's slots, and
    one ``layer`` function over either."""
    return dict(
        cache=lambda cfg, batch, max_len: _zeros(rows(cfg, batch)),
        pool=lambda cfg, num_pages, page_size, slots, positions:
            rows(cfg, slots), contiguous=layer, paged=layer)


def _kv_pages(cfg, num_pages, page_size, slots, positions):
    """Plain pages, K beside V, as every pool."""
    _, Hkv, hd = dims(cfg)
    return {"kv": ((num_pages, Hkv, page_size, 2 * hd), cfg.dtype)}


def init_hybrid_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """Contiguous per-layer cache, each layer's entry its kind's
    (``Mixer.cache``): what a kind holds a slot of the pool it holds a row
    of the batch here, and pages are ``{"k", "v"}`` (or an mla layer's
    ``{"kv"}`` latent rows) ``max_len`` long."""
    return [KINDS[kind].cache(cfg, batch, max_len) for kind in cfg.mixers]


def pool_shapes(cfg: TransformerConfig, num_pages: int, page_size: int,
                slots: int, positions: int):
    """Per layer ``{key: (shape, dtype)}`` of the engine's cache, each
    layer's entry its kind's (``Mixer.pool``). What a layer holds is read
    from these keys (``kv``: pages; :data:`SLOT_KEYS`: rows a slot, for
    ``positions`` positions), not from its mixer's name."""
    return [KINDS[kind].pool(cfg, num_pages, page_size, slots, positions)
            for kind in cfg.mixers]


def init_hybrid_pool(cfg, num_pages: int, page_size: int, slots: int,
                     positions: int):
    return [_zeros(layer) for layer in pool_shapes(
        cfg, num_pages, page_size, slots, positions)]


def required_page(cfg: TransformerConfig) -> Optional[int]:
    """The page a model's kinds require of the engine (``Mixer.page``), or
    None where every kind takes the page it is given."""
    for kind in dict.fromkeys(cfg.mixers):
        if KINDS[kind].page:
            return KINDS[kind].page(cfg)
    return None


class TickCounts:
    """A kind's host accounting, built once an engine (:func:`accountants`).
    Its calls return plain ``{pool stat: increment}`` dicts from the
    scheduler's numbers, no device read: this one counts the decode calls
    under the kind's label; a kind whose decode kernel walks a grid adds the
    count of the walk beside the kernel's rule."""

    def __init__(self, cfg, kind: str, geometry: Geometry):
        self.layers = cfg.mixers.count(kind)
        self.kernel = geometry.kernel
        labels = KINDS[kind].labels
        self.label = labels and "attn_ticks_" + labels[not geometry.kernel]

    def decode(self, positions, rows: int, context: int):
        """A decode call of ``rows`` rows, the live ones at ``positions``;
        the longest ``context`` they have served."""
        return {self.label: 1}

    def window(self, offset: int, lanes: int):
        """A prefill window of ``lanes`` real tokens at ``offset``."""
        return {}


def accountants(cfg: TransformerConfig, geometry: Geometry):
    """The host accounting of a model's kinds, in :data:`KINDS`' order: one
    for each kind the model has that counts anything."""
    return [kind.counts(cfg, name, geometry) for name, kind in KINDS.items()
            if name in cfg.mixers and kind.counts]


# ---- shared pieces ----------------------------------------------------------

def _proj(x, p, dt):
    """``x W``, by what the leaf holds: ``w`` is ``[in, out]`` as
    :func:`init_hybrid` drew it, ``wt`` is ``[out, in]`` as
    :func:`serving_layout` laid it for this product, contracted on its
    second axis. The same product either way."""
    if "wt" in p:
        return jnp.tensordot(x, p["wt"].astype(dt),
                             axes=((x.ndim - 1,), (1,)))
    return x @ p["w"].astype(dt)


def _heads(t, n, hd):
    B, W, _ = t.shape
    return t.reshape(B, W, n, hd).transpose(0, 2, 1, 3)


def _head_rms(t, p, eps=1e-6):
    t = t.astype(F32)
    return (t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)
            * p["scale"])


def _gated_out(lp, x, o, cfg, norm: bool):
    """``W_o(sigmoid(W_g x) * o)``, ``o`` (B, H, W, hd) float32, RMS-normed
    over all heads first on a lightning layer."""
    dt = cfg.dtype
    B, H, W, hd = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(B, W, H * hd)
    if norm:
        o = _rms(o, lp["o_norm"])
    gate = jax.nn.sigmoid(_proj(x, lp["g"], dt).astype(F32))
    return (gate * o).astype(dt) @ lp["o"]["w"].astype(dt)


def _swiglu(lp, x, dt):
    y = jax.nn.silu(_proj(x, lp["gate"], dt)) * _proj(x, lp["up"], dt)
    return _proj(y, lp["down"], dt)


def _slot_rows(c, slot):
    """A layer's rows a slot as a window continues them: every row (row
    ``b`` is slot ``b``), or the one row of a prefill window's ``slot``."""
    return c if slot is None else {
        kk: jax.lax.dynamic_slice_in_dim(c[kk], slot, 1, axis=0) for kk in c}


def _slot_rows_back(c, new, slot):
    """The layer's entry with the window's rows ``new`` where
    :func:`_slot_rows` read them."""
    return new if slot is None else {
        kk: jax.lax.dynamic_update_slice_in_dim(c[kk], new[kk], slot, axis=0)
        for kk in c}


def _gated_attention_init(cfg, rng, kv):
    """A lightning or sparse layer: ``kv`` key/value heads, q and k normed a
    head, a sigmoid gate a channel."""
    H, _, hd = dims(cfg)
    D = cfg.d_model
    return {"q": _dense(rng, D, H * hd), "k": _dense(rng, D, kv * hd),
            "v": _dense(rng, D, kv * hd), "g": _dense(rng, D, H * hd),
            "o": _dense(rng, H * hd, D),
            "q_norm": _ones(hd), "k_norm": _ones(hd)}


#: what a lightning and a sparse layer declare as ``Mixer.served``: the
#: product that feeds :func:`_heads` reads its weight ``[heads x hd, in]``
#: (the module's docstring, "Two parameter trees")
_HEADS_MAJOR = ("q", "k", "v")


# ---- lightning --------------------------------------------------------------

def _lightning_init(cfg, rng):
    H, _, hd = dims(cfg)
    return dict(_gated_attention_init(cfg, rng, H), o_norm=_ones(H * hd))


def _lightning_rows(cfg, rows: int):
    H, _, hd = dims(cfg)
    return {"state": ((rows, H, hd, hd), F32)}


def lightning_rates(H: int):
    """``s_h = 2^(-8h/H)``, h = 1..H: head h decays by ``exp(-s_h)`` a
    position (the Lightning Attention-2 convention)."""
    return 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=F32) / H)


def _lightning_qkv(lp, x, wpos, cfg):
    H, _, hd = dims(cfg)
    dt = cfg.dtype
    q = _head_rms(_heads(_proj(x, lp["q"], dt), H, hd), lp["q_norm"])
    k = _head_rms(_heads(_proj(x, lp["k"], dt), H, hd), lp["k_norm"])
    v = _heads(_proj(x, lp["v"], dt), H, hd).astype(F32)
    cos, sin = _rope_tables(wpos, hd, cfg.rope_theta, F32)   # (B, W, hd/2)
    cos, sin = cos[:, None], sin[:, None]
    return _rot_half(q, cos, sin), _rot_half(k, cos, sin), v


def lightning_chunk(q, k, v, state, n_valid):
    """The chunked form over one window: ``q``, ``k``, ``v`` (B, H, W, hd)
    float32, ``state`` (B, H, hd, hd) the state before the window,
    ``n_valid`` (B,) the real lanes of each row (the rest is padding and
    neither attends nor reaches the state; 0 leaves a row's state as it
    was). Returns ``(o (B, H, W, hd), state after lane n_valid - 1)``.
    Every decay is ``exp(-s_h * (a difference of positions))``."""
    B, H, W, hd = q.shape
    s = lightning_rates(H)
    j = jnp.arange(W)
    valid = j[None] < n_valid[:, None]                          # (B, W)
    k = jnp.where(valid[:, None, :, None], k, 0.0)
    diff = (j[:, None] - j[None, :]).astype(F32)
    decay = jnp.where(diff >= 0,
                      jnp.exp(-s[:, None, None] * jnp.maximum(diff, 0.0)),
                      0.0)                                      # (H, W, W)
    scores = jnp.einsum("bhtd,bhsd->bhts", q, k, precision=HI) * decay
    o = jnp.einsum("bhts,bhsd->bhtd", scores, v, precision=HI)
    carried = jnp.exp(-s[:, None] * (j + 1.0))                  # (H, W)
    o = o + carried[None, :, :, None] * jnp.einsum(
        "bhtd,bhde->bhte", q, state, precision=HI)
    n = n_valid.astype(F32)[:, None, None]                      # (B, 1, 1)
    left = jnp.where(valid[:, None], jnp.exp(
        -s[None, :, None] * jnp.maximum(n - 1.0 - j, 0.0)), 0.0)  # (B, H, W)
    new = (jnp.exp(-s[None, :, None] * n)[..., None] * state
           + jnp.einsum("bhsd,bhse->bhde", k * left[..., None], v,
                        precision=HI))
    return o * hd ** -0.5, new


def _lightning_layer(lp, x, c, wpos, w):
    """A lightning layer over its states ``c``: the decode tick
    (``w.kernel``: one token a row) runs the Pallas step, a window the
    chunked form from a state zeroed at position 0."""
    from ...ops.lightning_attention import lightning_decode_step
    q, k, v = _lightning_qkv(lp, x, wpos, w.cfg)
    state = _slot_rows(c, w.slot)["state"]
    if w.kernel:
        # a decoding row is never at position 0 (a prompt has a token),
        # so the tick needs no reset and no pass over the states for one
        o, st = lightning_decode_step(q[:, :, 0], k[:, :, 0], v[:, :, 0],
                                      state, w.n_valid > 0)
        o = o[:, :, None]
    else:
        o, st = lightning_chunk(q, k, v, _fresh(state, w.pos, w.n_valid),
                                w.n_valid)
    new = _slot_rows_back(c, {"state": st}, w.slot)
    return _gated_out(lp, x, o, w.cfg, norm=True), new


# ---- sparse -----------------------------------------------------------------

def _sparse_check(cfg):
    sp = cfg.sparse
    if sp is None:
        raise ValueError("sparse layers need cfg.sparse")
    if (sp.kernel_size % sp.kernel_stride
            or sp.block_size % sp.kernel_stride):
        raise ValueError("kernel_size and block_size must be multiples "
                         "of kernel_stride")
    forced = sp.init_blocks + sp.window_size // sp.block_size + 1
    if forced > sp.topk:
        raise ValueError(f"first blocks and window force {forced} "
                         f"blocks, more than topk {sp.topk}")


def _sparse_cache(cfg, batch, max_len):
    """``L`` is ``max_len`` in whole blocks."""
    _, Hkv, hd = dims(cfg)
    sp = cfg.sparse
    L = _round_up(max_len, sp.block_size)
    kv = jnp.zeros((batch, Hkv, L, hd), cfg.dtype)
    return {"k": kv, "v": kv, "ck": jnp.zeros(
        (batch, Hkv, L // sp.kernel_stride, hd), cfg.dtype)}


def _sparse_pool(cfg, num_pages, page_size, slots, positions):
    _, Hkv, hd = dims(cfg)
    if cfg.sparse.block_size % page_size:
        raise ValueError(f"page_size {page_size} must divide the sparse "
                         f"block size {cfg.sparse.block_size}")
    return dict(
        _kv_pages(cfg, num_pages, page_size, slots, positions),
        ck=((slots, Hkv, -(-positions // cfg.sparse.kernel_stride), hd),
            cfg.dtype))


def _sparse_qkv(lp, x, cfg):
    H, Hkv, hd = dims(cfg)
    dt = cfg.dtype
    q = _head_rms(_heads(_proj(x, lp["q"], dt), H, hd), lp["q_norm"])
    k = _head_rms(_heads(_proj(x, lp["k"], dt), Hkv, hd), lp["k_norm"])
    v = _heads(_proj(x, lp["v"], dt), Hkv, hd)
    return q.astype(dt), k.astype(dt), v.astype(dt)


def _ck_windows(pos, n_valid, W, sp):
    """End positions ``e`` (B, n) of the compressed-key windows that can
    complete while ``pos .. pos + n_valid - 1`` are written, and which of
    them do. Window ``f`` ends at ``stride * f + stride - 1``."""
    s = sp.kernel_stride
    n = W // s + 1
    e0 = jnp.maximum((pos + s) // s * s - 1, sp.kernel_size - 1)
    e = e0[:, None] + s * jnp.arange(n, dtype=jnp.int32)[None]
    ok = e < (pos + n_valid)[:, None]
    return e, ok


def sparse_select(q, ck, t, sp):
    """The blocks each query's KV group attends once its context passes
    ``dense_len``. ``q`` (B, Hq, W, hd), ``ck`` (B, Hkv, F, hd) compressed
    keys (entry ``f`` = the window ending at ``stride * f + stride - 1``),
    ``t`` (B, W) the queries' positions. Returns ``(idx, ok)``, both
    (B, Hkv, W, K), K = min(topk, blocks): logical block ids and whether
    each entry is a block at all (a short context has fewer than K)."""
    B, Hq, W, hd = q.shape
    G, Fn = ck.shape[1], ck.shape[2]
    s, ks, bs = sp.kernel_stride, sp.kernel_size, sp.block_size
    r = bs // s
    nb = -(-Fn // r)
    qg = q.reshape(B, G, Hq // G, W, hd)
    logits = jnp.einsum("bghwd,bgfd->bghwf", qg, ck,
                        preferred_element_type=F32) * hd ** -0.5
    ends = s * jnp.arange(Fn) + s - 1
    fvalid = ((ends[None, None] <= t[..., None])
              & (ends >= ks - 1))[:, None, None]               # (B,1,1,W,F)
    p = jax.nn.softmax(jnp.where(fvalid, logits, _NEG), axis=-1)
    p = jnp.where(fvalid, p, 0.0).sum(axis=2)                   # (B, G, W, F)
    # a block's score: the best window that overlaps it, windows
    # r*b .. r*b + r + ks/s - 2
    extra = ks // s - 1
    p = jnp.pad(p, ((0, 0),) * 3 + ((0, nb * r + extra - Fn),))
    score = jax.lax.reduce_window(
        p, -jnp.inf, jax.lax.max, (1, 1, 1, r + extra), (1, 1, 1, r),
        "VALID")                                                # (B, G, W, nb)
    b = jnp.arange(nb)
    first = jnp.maximum(t - sp.window_size + 1, 0) // bs
    forced = (b < sp.init_blocks) | (b >= first[..., None])     # (B, W, nb)
    live = b <= (t // bs)[..., None]
    score = jnp.where(forced[:, None], 1e9, score)
    score = jnp.where(live[:, None], score, -jnp.inf)
    vals, idx = jax.lax.top_k(score, min(sp.topk, nb))
    return idx.astype(jnp.int32), vals > -jnp.inf


def _allowed_keys(idx, ok, t, sp, L):
    """(B, G, W, L) bool: the keys each query may attend — causal, and past
    ``dense_len`` of context only inside its selected blocks."""
    bs = sp.block_size
    nb = -(-L // bs)
    sel = jnp.put_along_axis(
        jnp.zeros(idx.shape[:-1] + (max(nb, idx.shape[-1]),), bool),
        idx, ok, axis=-1, inplace=False)
    sel = jnp.repeat(sel, bs, axis=-1)[..., :L]
    dense = (t + 1 <= sp.dense_len)[:, None, :, None]
    causal = (jnp.arange(L)[None, None] <= t[..., None])[:, None]
    return causal & (dense | sel)


def _fold_keys(carry, qg, ks_, vs_, al, scale=None):
    """One online-softmax update of ``carry = (m, l, acc)``: the queries
    ``qg`` (B, G, hg, W, hd) meet a tile of keys ``ks_`` (B, G, T, hd) and
    values ``vs_`` (B, G, T, dv) under ``al`` (B, G or 1, W, T); scores
    times ``scale`` (None: ``hd ** -0.5``)."""
    m, l, acc = carry
    # a key no query may read can hold anything (the trash page behind
    # a block table's unassigned entries takes whatever the fused decode
    # kernel's idle output block held): its weight is 0, and 0 x NaN is
    # NaN, so its value is 0 too
    vs_ = jnp.where(al.any(axis=2)[..., None], vs_,
                    jnp.zeros((), vs_.dtype))
    s = jnp.einsum("bghwd,bgud->bghwu", qg, ks_,
                   preferred_element_type=F32) * (scale
                                                  or qg.shape[-1] ** -0.5)
    al = al[:, :, None]
    s = jnp.where(al, s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None]) * al
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    acc = acc * corr[..., None] + jnp.einsum(
        "bghwu,bgud->bghwd", p.astype(vs_.dtype), vs_,
        preferred_element_type=F32)
    return m_new, l, acc


def _fold_start(qg, dv):
    shape = qg.shape[:-1]
    return (jnp.full(shape, _NEG, F32), jnp.zeros(shape, F32),
            jnp.zeros(shape + (dv,), F32))


def _fold_end(carry, shape):
    _, l, acc = carry
    return (acc / jnp.where(l == 0.0, 1.0, l)[..., None]).reshape(shape)


def _masked_attention(q, k, v, allowed, t_max, scale=None):
    """Softmax attention of ``q`` (B, Hq, W, hd) over ``k`` (B, Hkv, L,
    hd) and ``v`` (B, Hkv, L, dv) under ``allowed`` (B, Hkv or 1, W, L),
    grouped-query, folded a tile of keys at a time up to position
    ``t_max``; scores times ``scale`` (None: ``hd ** -0.5``). float32
    out."""
    B, Hq, W, hd = q.shape
    G, L, dv = k.shape[1], k.shape[2], v.shape[-1]
    qg = q.reshape(B, G, Hq // G, W, hd)
    T = min(L, _KEY_TILE)
    init = _fold_start(qg, dv)
    if L == T:
        out = _fold_keys(init, qg, k, v, allowed, scale)
    else:
        short = -L % T                      # whole tiles (none at 32k)
        k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, short), (0, 0)))
                for a in (k, v))
        allowed = jnp.pad(allowed, ((0, 0),) * 3 + ((0, short),))

        def body(i, carry):
            def tile(a, axis):
                return jax.lax.dynamic_slice_in_dim(a, i * T, T, axis=axis)
            return _fold_keys(carry, qg, tile(k, 2), tile(v, 2),
                              tile(allowed, 3), scale)
        out = jax.lax.fori_loop(0, t_max // T + 1, body, init)
    return _fold_end(out, (B, Hq, W, dv))


def _put(buf, val, idx):
    """One row's ``(heads, L, .) <- (heads, W, .)`` at positions ``idx``; an
    index of ``L`` or more is dropped."""
    return buf.at[:, idx].set(val, mode="drop")


def _put_window(c, k, v, wpos, n_valid):
    """A contiguous cache's ``k`` and ``v`` with the window's real lanes
    written at ``wpos`` (padding lanes are dropped)."""
    W, L = k.shape[2], c["k"].shape[2]
    dest = jnp.where(jnp.arange(W)[None] < n_valid[:, None], wpos, L)
    return (jax.vmap(_put)(c["k"], k, dest), jax.vmap(_put)(c["v"], v, dest))


def _scatter_pages(pool, k, v, bt, wpos, n_valid, page):
    """The page pool with a window's K/V rows ``(B, Hkv, W, hd)`` written
    through the block table (padding lanes and idle rows to trash page 0).
    Every index names (page, head, offset) and the window is the minor axis
    alone: a scatter over the page and offset axes with the heads sliced
    makes the chip lay the whole pool out anew around it."""
    from ...ops.paged_attention import pack_kv
    B, Hkv, W, hd = k.shape
    lane_ok = jnp.arange(W)[None] < n_valid[:, None]
    pg = jnp.take_along_axis(
        bt, jnp.clip(wpos // page, 0, bt.shape[1] - 1), axis=1)
    rows = pack_kv(k, v).transpose(0, 2, 1, 3).reshape(B * W, Hkv, 2 * hd)
    return pool.at[jnp.where(lane_ok, pg, 0).reshape(-1, 1),
                   jnp.arange(Hkv)[None],
                   (wpos % page).reshape(-1, 1)].set(rows)


def _sparse_contiguous(lp, x, c, wpos, w):
    """A sparse layer over a contiguous cache: write the window's K/V and
    the compressed keys it completes, select, attend under the mask."""
    cfg, pos, n_valid = w.cfg, w.pos, w.n_valid
    sp = cfg.sparse
    s, ks = sp.kernel_stride, sp.kernel_size
    W = x.shape[1]
    q, k, v = _sparse_qkv(lp, x, cfg)
    L = c["k"].shape[2]
    kc, vc = _put_window(c, k, v, wpos, n_valid)
    put = jax.vmap(_put)
    e, ok = _ck_windows(pos, n_valid, W, sp)
    src = jnp.clip(e[..., None] - (ks - 1) + jnp.arange(ks), 0, L - 1)
    rows = jax.vmap(lambda kb, ib: kb[:, ib])(kc, src)   # (B,Hkv,n,ks,hd)
    means = rows.astype(F32).mean(axis=3).astype(cfg.dtype)
    Fn = c["ck"].shape[2]
    f = jnp.where(ok, (e + 1) // s - 1, Fn)
    ck = put(c["ck"], means, f)
    idx, sel_ok = sparse_select(q, ck, wpos, sp)
    allowed = _allowed_keys(idx, sel_ok, wpos, sp, L)
    o = _masked_attention(q, kc, vc, allowed, jnp.max(wpos))
    return _gated_out(lp, x, o, cfg, norm=False), {"k": kc, "v": vc,
                                                   "ck": ck}


def _sparse_paged(lp, x, c, wpos, w):
    """A sparse layer over the page pool. K/V writes go through the block
    table (padding lanes and idle rows to trash page 0); the compressed keys
    are the rows' own (row ``slot`` for a one-row prefill window). The
    decode tick (``kernel``: one query a row) hands the selected blocks to
    the Pallas kernel, which reads them in place; a window gathers its
    row's pages and masks."""
    from ...ops.paged_attention import split_kv
    cfg, pos, n_valid, bt, page, kernel, slot, _ = w
    sp = cfg.sparse
    s, ks = sp.kernel_stride, sp.kernel_size
    B, W, _ = x.shape
    H, Hkv, hd = dims(cfg)
    P = bt.shape[1]
    q, k, v = _sparse_qkv(lp, x, cfg)
    heads_ = jnp.arange(Hkv)[None]
    kv = _scatter_pages(c["kv"], k, v, bt, wpos, n_valid, page)
    # the compressed keys this write completes. Their keys lie in the few
    # pages over [pos - ks + 1, pos + W + s): whole pages gathered, one
    # slice a row, sums by stride (a gather row by row is a sequential loop
    # on the chip, 2.4 us a row: 2.5 ms of an 11.9 ms tick, PERF.md PR 29)
    e, ok = _ck_windows(pos, n_valid, W, sp)
    n, m = e.shape[1], ks // s
    n_pg = -(-(page + W + ks + s) // page)
    first = jnp.maximum(pos - ks + 1, 0) // page
    near = kv[jnp.take_along_axis(
        bt, jnp.clip(first[:, None] + jnp.arange(n_pg), 0, P - 1), axis=1)]
    near = near[..., :hd].transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, n_pg * page, hd)
    start = e[:, 0] - (ks - 1) - first * page
    seg = jax.vmap(lambda a, r: jax.lax.dynamic_slice_in_dim(
        a, r, (n - 1 + m) * s, axis=1))(near, start)
    strides = seg.astype(F32).reshape(B, Hkv, n - 1 + m, s, hd).sum(axis=3)
    means = sum(strides[:, :, i:i + n] for i in range(m)) / ks
    ck = (c["ck"] if slot is None else
          jax.lax.dynamic_slice_in_dim(c["ck"], slot, 1, axis=0))
    f = jnp.where(ok, (e + 1) // s - 1, ck.shape[2])    # dropped: no window
    ck = ck.at[jnp.arange(B)[:, None, None], heads_[..., None],
               f[:, None, :]].set(means.astype(cfg.dtype), mode="drop")
    idx, sel_ok = sparse_select(q, ck, wpos, sp)
    if kernel:
        o = _selected_decode(q, kv, bt, pos, n_valid, idx, sel_ok, cfg, page)
    else:
        L = P * page
        kc, vc = split_kv(kv[bt].transpose(0, 2, 1, 3, 4).reshape(
            B, Hkv, L, 2 * hd))
        allowed = _allowed_keys(idx, sel_ok, wpos, sp, L)
        o = _masked_attention(q, kc, vc, allowed, jnp.max(wpos))
    if slot is not None:
        ck = jax.lax.dynamic_update_slice_in_dim(c["ck"], ck, slot, axis=0)
    return _gated_out(lp, x, o, cfg, norm=False), {"kv": kv, "ck": ck}


def _dense_walk(sp, page: int, pages_a_slot: int):
    """``(pages a block, blocks of the dense walk)``: a row still under
    ``dense_len`` lists every block up to its own, ``dense_len``'s blocks at
    most and no more than a slot's block table holds."""
    pp = sp.block_size // page
    return pp, min(-(-sp.dense_len // sp.block_size), -(-pages_a_slot // pp))


def _selected_decode(q, kv, bt, pos, n_valid, idx, sel_ok, cfg, page):
    """One query a row over the blocks chosen for each (row, KV group), read
    in place. A row still under ``dense_len`` lists every block up to its
    own; the kernel walks ``topk`` blocks unless such a row has more."""
    from ...ops.paged_attention import paged_attention_selected
    sp = cfg.sparse
    B, Hq, _, hd = q.shape
    G = idx.shape[1]
    K = idx.shape[-1]
    pp, n_dense = _dense_walk(sp, page, bt.shape[1])
    cur = pos // sp.block_size
    dense_row = (pos + 1 <= sp.dense_len) & (n_valid > 0)

    def pages_of(blocks, okay):                 # (B, G, n) -> (B, G, n*pp)
        pages = blocks[..., None] * pp + jnp.arange(pp)
        return jnp.where(okay[..., None], pages, -1).reshape(B, G, -1)

    def walk(n):
        every = jnp.broadcast_to(jnp.arange(n)[None, None], (B, G, n))
        chosen = jnp.pad(idx[:, :, 0, :n], ((0, 0), (0, 0),
                                             (0, max(0, n - K))))
        chosen_ok = jnp.pad(sel_ok[:, :, 0, :n], ((0, 0), (0, 0),
                                                  (0, max(0, n - K))))
        d = dense_row[:, None, None]
        blocks = jnp.where(d, every, chosen)
        okay = jnp.where(d, every <= cur[:, None, None], chosen_ok)
        okay = okay & (n_valid > 0)[:, None, None]
        out = paged_attention_selected(
            q[:, :, 0].reshape(B, G, Hq // G, hd), kv, bt,
            pages_of(blocks, okay), pos + 1)
        return out.reshape(B, Hq, 1, hd).astype(F32)

    if n_dense <= K:
        return walk(K)
    wide = jnp.any(dense_row & (cur >= K))
    return jax.lax.cond(wide, lambda: walk(n_dense), lambda: walk(K))


class _SelectCounts(TickCounts):
    """A model with sparse layers counts each paged call by path, ``sparse``
    when the longest context it served was past ``dense_len`` (blocks were
    selected), else ``dense``; and ``select_walk_pages`` / ``_steps``, the
    selected-block kernel's walk of a decode call by
    :func:`_selected_decode`'s own rule (docs/observability.md)."""

    def __init__(self, cfg, kind, geometry):
        from ...ops.paged_attention import select_block
        super().__init__(cfg, kind, geometry)
        _, Hkv, hd = dims(cfg)
        self.sp = sp = cfg.sparse
        page = geometry.page_size
        # the two lists a call walks for a (row, KV head), in pages: the
        # top-k walk's (K is sparse_select's, from the compressed keys a
        # slot holds) and the dense walk's, the longer
        self.pp, n_dense = _dense_walk(sp, page, geometry.pages_a_slot)
        scored = -(-geometry.pages_a_slot * page // sp.kernel_stride)
        K = min(sp.topk, -(-scored // (sp.block_size // sp.kernel_stride)))
        self.walks = (K * self.pp, max(K, n_dense) * self.pp)
        head_slice = page * 2 * hd * jnp.dtype(cfg.dtype).itemsize
        self.steps = tuple(n // select_block(head_slice, n)
                           for n in self.walks)
        self.lists = self.layers * Hkv          # a call's lists a row

    def _path(self, context):
        return {"attn_ticks_sparse" if context > self.sp.dense_len
                else "attn_ticks_dense": 1}

    def window(self, offset, lanes):
        return self._path(offset + lanes)

    def decode(self, positions, rows, context):
        out = self._path(context)
        if self.kernel:
            sp, short = self.sp, self.walks[0]
            listed = [(pos // sp.block_size + 1) * self.pp
                      for pos in positions]
            dense = [pos + 1 <= sp.dense_len for pos in positions]
            widened = any(d and n > short for d, n in zip(dense, listed))
            out["select_walk_pages"] = self.lists * sum(
                n if d else min(n, short) for d, n in zip(dense, listed))
            out["select_walk_steps"] = (self.lists * rows
                                        * self.steps[widened])
        return out


# ---- kda --------------------------------------------------------------------

def _kda_check(cfg):
    if cfg.kda is None:
        raise ValueError("kda layers need cfg.kda")


def _kda_init(cfg, rng):
    H, _, hd = dims(cfg)
    D, K = cfg.d_model, cfg.kda.conv_kernel
    return {"q": _dense(rng, D, H * hd), "k": _dense(rng, D, H * hd),
            "v": _dense(rng, D, H * hd), "f": _dense(rng, D, H * hd),
            "b": _dense(rng, D, H), "z": _dense(rng, D, H),
            "o": _dense(rng, H * hd, D),
            "dt_bias": rng.normal(0, 0.5, H * hd).astype(np.float32),
            "a_log": rng.normal(0, 0.5, H).astype(np.float32),
            "conv": {n: rng.normal(0, K ** -0.5, (K, H * hd)).astype(
                np.float32) for n in "qkv"},
            "o_norm": _ones(hd)}


def _kda_rows(cfg, rows: int):
    H, _, hd = dims(cfg)
    return {"state": ((rows, H, hd, hd), F32),
            "conv": ((rows, cfg.kda.conv_kernel - 1, 3 * H * hd), cfg.dtype)}


def _next_tail(ext, n_valid, keep: int, tick: bool):
    """The ``keep`` rows before a row's next token, of ``ext`` (B, keep + W,
    C) = the tails before the window beside the window's rows: the rows that
    end at lane ``n_valid - 1`` (padding lanes never enter a tail). A
    ``tick`` (one token a row) shifts every row by its one lane or leaves it:
    a slice a row there is a gather, a sequential loop on the chip (PERF.md,
    PR 35)."""
    if tick:
        return jnp.where((n_valid > 0)[:, None, None], ext[:, 1:],
                         ext[:, :keep])
    return jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(
        e, n, keep, axis=0))(ext, n_valid)


def _l2norm(t, eps=1e-6):
    return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + eps)


def _kda_inputs(lp, x, tail, n_valid, cfg):
    """What the delta rule reads of a window ``x`` (B, W, D) continuing the
    convolution tails ``tail`` (B, K - 1, 3 H d): ``(q, k, v, g)`` (B, H, W,
    d) float32, ``beta`` (B, H, W), and the tails after lane ``n_valid - 1``.
    Padding lanes get ``beta = 0`` and ``g = 0``: they neither correct nor
    decay a state."""
    H, _, hd = dims(cfg)
    dt = cfg.dtype
    K = cfg.kda.conv_kernel
    B, W, _ = x.shape
    pre = jnp.concatenate([_proj(x, lp[n], dt) for n in "qkv"], axis=-1)
    ext = jnp.concatenate([tail.astype(dt), pre], axis=1)   # (B, K-1+W, 3C)
    taps = jnp.concatenate([lp["conv"][n] for n in "qkv"],
                           axis=-1).astype(F32)             # (K, 3C)
    mixed = jax.nn.silu(sum(ext[:, j:j + W].astype(F32) * taps[j]
                            for j in range(K)))
    new_tail = _next_tail(ext, n_valid, K - 1, W == 1)
    q, k, v = (_heads(t, H, hd) for t in jnp.split(mixed, 3, axis=-1))
    q = _l2norm(q) * hd ** -0.5
    k = _l2norm(k)
    f = (_proj(x, lp["f"], dt).astype(F32) + lp["dt_bias"].astype(F32))
    g = cfg.kda.gate_floor * jax.nn.sigmoid(
        jnp.exp(lp["a_log"].astype(F32))[None, :, None, None]
        * _heads(f, H, hd))
    beta = jax.nn.sigmoid(_proj(x, lp["b"], dt).astype(F32)).transpose(
        0, 2, 1)                                            # (B, H, W)
    live = (jnp.arange(W)[None] < n_valid[:, None])[:, None]    # (B, 1, W)
    return (q, k, v, jnp.where(live[..., None], g, 0.0),
            jnp.where(live, beta, 0.0), new_tail)


def _unit_lower_inverse(n):
    """``(I + n)^-1`` for a strictly lower triangular ``n`` (.., C, C):
    ``n`` is nilpotent, so the inverse is the finite product ``(I - n)(I +
    n^2)(I + n^4)..``; matrix products only, at full precision."""
    C = n.shape[-1]
    eye = jnp.eye(C, dtype=n.dtype)
    m = -n
    inv = eye + m
    reach = 2
    while reach < C:
        m = jnp.matmul(m, m, precision=HI)
        inv = jnp.matmul(inv, eye + m, precision=HI)
        reach *= 2
    return inv


def kda_chunk(q, k, v, g, beta, state):
    """The chunked delta rule over one window: ``q``, ``k``, ``v``, ``g``
    (B, H, W, d) float32 (``g <= 0`` the channels' log-decays), ``beta``
    (B, H, W), ``state`` (B, H, d, d) the state before the window. Returns
    ``(o (B, H, W, d), state after the window)``.

    :data:`KDA_CHUNK` tokens a step (the WY / UT-transform form). With ``G``
    the log-decays cumulated inside the chunk, ``A[t, i] = sum_c k_t[c]
    k_i[c] exp(G_t[c] - G_i[c])`` for ``i < t`` and ``B[t, i]`` the same
    with ``q_t`` for ``i <= t``, the chunk's corrections are ``U = (I +
    diag(beta) A)^-1 diag(beta) (V - (K * exp(G)) S)``, its outputs ``(Q *
    exp(G)) S + B U`` and its closing state ``diag(exp(G_C)) S + (K *
    exp(G_C - G))^T U``. Every decay is the exponential of a DIFFERENCE of
    cumulated ``g`` that is at most 0, never a ratio of two powers (``exp(-G)``
    overflows float32 inside one chunk at ``g = -5``)."""
    B, H, W, d = q.shape
    C = min(KDA_CHUNK, W)
    short = -W % C
    if short:
        pad = ((0, 0), (0, 0), (0, short))
        q, k, v, g = (jnp.pad(t, pad + ((0, 0),)) for t in (q, k, v, g))
        beta = jnp.pad(beta, pad)
    n = (W + short) // C

    def chunks(t):          # (B, H, n*C, ..) -> (n, B, H, C, ..)
        return jnp.moveaxis(t.reshape(B, H, n, C, *t.shape[3:]), 2, 0)

    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    # between[t, i, s]: step s lies after i and not after t. G_t - G_i is
    # summed from the steps between them, not subtracted from two cumulated
    # sums: those reach -320 in a chunk, where float32 resolves 3e-5, and
    # the decays that matter are the ones whose exponent is small
    j = jnp.arange(C)
    between = ((j[None, :, None] < j[None, None, :])
               & (j[None, None, :] <= j[:, None, None])).astype(F32)

    def step(S, xs):
        qc, kc, vc, gc, bc = xs
        diff = jnp.einsum("tis,bhsd->bhtid", between, gc, precision=HI)
        decay = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
        kk = kc[:, :, None] * decay                          # (B,H,C,C,d)
        A = jnp.where(strict, jnp.sum(kc[:, :, :, None] * kk, axis=-1), 0.0)
        Bm = jnp.sum(qc[:, :, :, None] * kk, axis=-1)        # (B, H, C, C)
        eG = jnp.exp(jnp.cumsum(gc, axis=2))                 # (B, H, C, d)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "bhtd,bhde->bhte", kc * eG, S, precision=HI))
        U = jnp.matmul(_unit_lower_inverse(bc[..., None] * A), rhs,
                       precision=HI)
        o = (jnp.einsum("bhtd,bhde->bhte", qc * eG, S, precision=HI)
             + jnp.matmul(Bm, U, precision=HI))
        S = (eG[:, :, -1, :, None] * S
             + jnp.einsum("bhid,bhie->bhde", kc * decay[:, :, -1], U,
                          precision=HI))
        return S, o

    state, o = jax.lax.scan(step, state,
                            tuple(chunks(t) for t in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, n * C, d)
    return o[:, :, :W], state


def _head_gated_out(lp, x, o, cfg, norm: bool):
    """``W_o(o * sigmoid(W_z x))`` with one gate a HEAD, ``o`` (B, H, W, dv)
    float32, RMS-normed a head first on a kda layer."""
    dt = cfg.dtype
    B, H, W, dv = o.shape
    if norm:
        o = _head_rms(o, lp["o_norm"])
    gate = jax.nn.sigmoid(_proj(x, lp["z"], dt).astype(F32))    # (B, W, H)
    o = o.transpose(0, 2, 1, 3) * gate[..., None]
    return o.reshape(B, W, H * dv).astype(dt) @ lp["o"]["w"].astype(dt)


def _kda_layer(lp, x, c, wpos, w):
    """A kda layer over its rows of the cache ``c`` (``state``, ``conv``):
    the decode tick (``w.kernel``: one token a row, none at position 0) runs
    the Pallas step, a window the chunked form from a state and tails zeroed
    at position 0."""
    from ...ops.kda_attention import kda_decode_step
    cfg, pos, n_valid, kernel = w.cfg, w.pos, w.n_valid, w.kernel
    rows = _slot_rows(c, w.slot)
    state, tail = rows["state"], rows["conv"]
    if not kernel:
        state, tail = _fresh(state, pos, n_valid), _fresh(tail, pos, n_valid)
    q, k, v, g, beta, tail = _kda_inputs(lp, x, tail, n_valid, cfg)
    if kernel:
        o, state = kda_decode_step(q[:, :, 0], k[:, :, 0], v[:, :, 0],
                                   jnp.exp(g[:, :, 0]), beta[:, :, 0],
                                   state, n_valid > 0)
        o = o[:, :, None]
    else:
        o, state = kda_chunk(q, k, v, g, beta, state)
    return (_head_gated_out(lp, x, o, cfg, norm=True),
            _slot_rows_back(c, {"state": state, "conv": tail}, w.slot))


# ---- ssm --------------------------------------------------------------------

def _ssm_check(cfg):
    m = cfg.ssm
    if m is None or m.taps < 2:
        raise ValueError("ssm layers need cfg.ssm (taps >= 2)")
    if m.heads % (2 * m.groups):
        raise ValueError(f"ssm: {m.heads} heads in {m.groups} groups "
                         "(the state holds heads in pairs inside a group)")


def _ssm_init(cfg, rng):
    m = cfg.ssm
    D = cfg.d_model
    inner, bc = m.heads * m.head_dim, 2 * m.groups * m.state
    # the family's initialisation: A in [1, 16], the step log-uniform
    # in [1e-3, 1e-1] (stored as its inverse softplus), D = 1
    step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), m.heads))
    return {"in": _dense(rng, D, 2 * inner + bc + m.heads),
            "conv": {"w": rng.normal(0, m.taps ** -0.5,
                                     (m.taps, inner + bc)).astype(np.float32),
                     "b": rng.normal(0, 0.1, inner + bc).astype(np.float32)},
            "dt_bias": (step + np.log(-np.expm1(-step))).astype(np.float32),
            "a_log": np.log(rng.uniform(1, 16, m.heads)).astype(np.float32),
            "d": np.ones(m.heads, np.float32),
            "o_norm": _ones(inner), "o": _dense(rng, inner, D)}


def _ssm_rows(cfg, rows: int):
    """The state as the step holds it (``ops.ssm_step``): heads in pairs,
    ``(rows, H / 2, N, 2 P)``."""
    m = cfg.ssm
    return {"state": ((rows, m.heads // 2, m.state, 2 * m.head_dim), F32),
            "conv": ((rows, m.taps - 1,
                      m.heads * m.head_dim + 2 * m.groups * m.state),
                     cfg.dtype)}


def _ssm_inputs(lp, x, tail, n_valid, cfg):
    """What the state-space recurrence reads of a window ``x`` (B, W, D)
    continuing the convolution tails ``tail`` (B, taps - 1, C): ``z`` (B, W,
    H P) the gate, ``u`` (B, H, W, P), ``b``, ``c`` (B, G, W, N) and the
    step ``d`` (B, H, W), all float32, and the tails after lane ``n_valid -
    1``. A padding lane's step is 0: it neither decays nor feeds a state."""
    m = cfg.ssm
    dt = cfg.dtype
    K, H, P, G, N = m.taps, m.heads, m.head_dim, m.groups, m.state
    inner = H * P
    B, W, _ = x.shape
    zxd = _proj(x, lp["in"], dt)
    z, pre, step = (zxd[..., :inner], zxd[..., inner:zxd.shape[-1] - H],
                    zxd[..., zxd.shape[-1] - H:])
    ext = jnp.concatenate([tail.astype(dt), pre], axis=1)   # (B, K-1+W, C)
    taps = lp["conv"]["w"].astype(F32)
    mixed = jax.nn.silu(sum(ext[:, j:j + W].astype(F32) * taps[j]
                            for j in range(K)) + lp["conv"]["b"].astype(F32))
    new_tail = _next_tail(ext, n_valid, K - 1, W == 1)
    u = _heads(mixed[..., :inner], H, P)
    b = _heads(mixed[..., inner:inner + G * N], G, N)
    c = _heads(mixed[..., inner + G * N:], G, N)
    d = jax.nn.softplus(step.astype(F32) + lp["dt_bias"].astype(F32))
    live = jnp.arange(W)[None] < n_valid[:, None]               # (B, W)
    d = jnp.where(live[..., None], d, 0.0).transpose(0, 2, 1)   # (B, H, W)
    return z.astype(F32), u, b, c, d, new_tail


def ssm_chunk(u, b, c, d, a_rate, state, chunk: int):
    """The chunked scan over one window: ``u`` (B, H, W, P), ``b``, ``c``
    (B, G, W, N), ``d`` (B, H, W) the steps (0 on a padding lane), ``a_rate``
    (H,) the heads' ``A < 0``, all float32, ``state`` (B, H, P, N) the state
    before the window. Returns ``(y (B, H, W, P) = S_t C_t, the state after
    the window)``.

    ``chunk`` tokens a step (the state-space duality's form): with ``L`` the
    log-decays ``d A`` cumulated inside the chunk, token ``t`` reads ``sum_(s
    <= t) exp(L_t - L_s) d_s (C_t . B_s) u_s`` of its own chunk and ``exp(L_t)
    S C_t`` of the state before it; the chunk closes on ``exp(L_end) S +
    sum_s exp(L_end - L_s) d_s u_s B_s^T``. Every decay is the exponential
    of a DIFFERENCE of cumulated log-decays that is at most 0, never a ratio
    of two powers."""
    B, H, W, P = u.shape
    G = b.shape[1]
    C = min(chunk, W)
    short = -W % C
    if short:
        pad = ((0, 0), (0, 0), (0, short))
        u, b, c = (jnp.pad(t, pad + ((0, 0),)) for t in (u, b, c))
        d = jnp.pad(d, pad)
    n = (W + short) // C

    def chunks(t):          # (B, X, n*C, ..) -> (n, B, X, C, ..)
        return jnp.moveaxis(t.reshape(*t.shape[:2], n, C, *t.shape[3:]), 2, 0)

    lower = jnp.tril(jnp.ones((C, C), bool))

    def step(S, xs):
        uc, bc, cc, dc = xs
        L = jnp.cumsum(dc * a_rate[None, :, None], axis=2)      # (B, H, C)
        decay = jnp.exp(jnp.where(lower, L[..., :, None] - L[..., None, :],
                                  -jnp.inf))                    # (B,H,C,C)
        cb = jnp.einsum("bgtn,bgsn->bgts", cc, bc, precision=HI)
        w = jnp.repeat(cb, H // G, axis=1) * decay * dc[:, :, None, :]
        ch = jnp.repeat(cc, H // G, axis=1)                     # (B,H,C,N)
        y = (jnp.einsum("bhts,bhsp->bhtp", w, uc, precision=HI)
             + jnp.exp(L)[..., None] * jnp.einsum(
                 "bhpn,bhtn->bhtp", S, ch, precision=HI))
        left = jnp.exp(L[..., -1:] - L) * dc                    # (B, H, C)
        S = (jnp.exp(L[..., -1])[..., None, None] * S
             + jnp.einsum("bhsp,bhsn->bhpn", uc * left[..., None],
                          jnp.repeat(bc, H // G, axis=1), precision=HI))
        return S, y

    state, y = jax.lax.scan(step, state,
                            tuple(chunks(t) for t in (u, b, c, d)))
    y = jnp.moveaxis(y, 0, 2).reshape(B, H, n * C, P)
    return y[:, :, :W], state


def _ssm_layer(lp, x, c, wpos, w):
    """An ssm layer over its rows of the cache ``c`` (``state``, ``conv``):
    the decode tick (``w.kernel``: one token a row, none at position 0) runs
    the Pallas step, a window the chunked scan from a state and tails zeroed
    at position 0. Out: the gate BEFORE the norm, the norm a group of
    channels, ``W_o``."""
    from ...ops.ssm_step import pack_state, ssm_decode_step, unpack_state
    cfg, pos, n_valid, kernel = w.cfg, w.pos, w.n_valid, w.kernel
    m = cfg.ssm
    rows = _slot_rows(c, w.slot)
    state, tail = rows["state"], rows["conv"]
    if not kernel:
        state, tail = _fresh(state, pos, n_valid), _fresh(tail, pos, n_valid)
    z, u, b, cc, d, tail = _ssm_inputs(lp, x, tail, n_valid, cfg)
    a_rate = -jnp.exp(lp["a_log"].astype(F32))
    if kernel:
        d1 = d[:, :, 0]
        y, state = ssm_decode_step(u[:, :, 0] * d1[..., None],
                                   jnp.exp(d1 * a_rate), b[:, :, 0],
                                   cc[:, :, 0], state, n_valid > 0)
        y = y[:, :, None]
    else:
        y, st = ssm_chunk(u, b, cc, d, a_rate, unpack_state(state), m.chunk)
        state = pack_state(st)
    y = y + lp["d"].astype(F32)[None, :, None, None] * u
    B, H, W, P = y.shape
    y = y.transpose(0, 2, 1, 3).reshape(B, W, H * P) * jax.nn.silu(z)
    g = y.reshape(B, W, m.groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    y = g.reshape(B, W, H * P) * lp["o_norm"]["scale"].astype(F32)
    return (_proj(y.astype(cfg.dtype), lp["o"], cfg.dtype),
            _slot_rows_back(c, {"state": state, "conv": tail}, w.slot))


class _StateCounts(TickCounts):
    """``ssm_state_rows``: the states the decode calls' state-space step had
    to read and write, each live row's once an ssm layer a call."""

    def decode(self, positions, rows, context):
        out = super().decode(positions, rows, context)
        if self.kernel:
            out["ssm_state_rows"] = self.layers * len(positions)
        return out


# ---- mla --------------------------------------------------------------------

def _mla_check(cfg):
    if cfg.latent is None:
        raise ValueError("mla layers need cfg.latent")
    if cfg.latent.rope % 2:
        raise ValueError(f"rope width {cfg.latent.rope}")


def _mla_init(cfg, rng):
    la, H, D = cfg.latent, cfg.heads, cfg.d_model
    hq = H * (la.nope + la.rope)
    q = ({"q_a": _dense(rng, D, la.q_rank), "q_norm": _ones(la.q_rank),
          "q_b": _dense(rng, la.q_rank, hq)} if la.q_rank
         else {"q": _dense(rng, D, hq)})
    return dict(q, kva=_dense(rng, D, la.latent + la.rope),
                c_norm=_ones(la.latent),
                kvb=_dense(rng, la.latent, H * (la.nope + la.value)),
                o=_dense(rng, H * la.value, D),
                **({"z": _dense(rng, D, H)} if la.gate else {}))


def latent_row(cfg) -> int:
    """Values a cached row of an mla layer holds: ``latent + rope`` rounded
    up to whole 128-lane registers (zeros). Unpadded, the chip keeps the pool
    with the page offset minor-most to save the padding and relays it, in
    and out, around every call of the kernel, which takes row-major
    operands only: two pool-sized copies a tick (PERF.md, PRs 28 and 35)."""
    return _round_up(cfg.latent.latent + cfg.latent.rope, 128)


def _mla_pool(cfg, num_pages, page_size, slots, positions):
    return {"kv": ((num_pages, 1, page_size, latent_row(cfg)), cfg.dtype)}


def _mla_inputs(lp, x, wpos, cfg):
    """``(q_n (B, H, W, nope), q_r (B, H, W, rope) rotated, row (B, W,
    latent_row))``: the heads' queries (one product, or with ``q_rank``
    through their own normed latent) and the one row a token caches, the
    normed latent beside the rotated shared key (and zeros up to whole
    registers), in the compute dtype."""
    H = cfg.heads
    la = cfg.latent
    dt = cfg.dtype
    xq = x
    if la.q_rank:
        xq = _rms(_proj(x, lp["q_a"], dt).astype(F32), lp["q_norm"],
                  cfg.norm_eps).astype(dt)
    q = _heads(_proj(xq, lp["q_b" if la.q_rank else "q"], dt), H,
               la.nope + la.rope)
    ckr = _proj(x, lp["kva"], dt).astype(F32)
    cos, sin = _rope_tables(wpos, la.rope, cfg.rope_theta, F32)
    q_r = _rot_half(q[..., la.nope:].astype(F32), cos[:, None], sin[:, None])
    row = jnp.concatenate(
        [_rms(ckr[..., :la.latent], lp["c_norm"], cfg.norm_eps),
         _rot_half(ckr[..., la.latent:], cos, sin)], axis=-1).astype(dt)
    row = jnp.pad(row, ((0, 0), (0, 0),
                        (0, latent_row(cfg) - la.latent - la.rope)))
    return q[..., :la.nope], q_r.astype(dt), row


def _mla_kvb(lp, cfg):
    """``W_kvb`` (latent, H, nope + value) in the compute dtype."""
    la = cfg.latent
    return lp["kvb"]["w"].astype(cfg.dtype).reshape(
        la.latent, cfg.heads, la.nope + la.value)


def _mla_expanded(lp, q_n, q_r, rows, wpos, cfg):
    """Causal attention of queries at ``wpos`` (B, W) over cached rows
    ``rows`` (B, L, latent_row) with K and V REBUILT from the latents: what
    a window runs."""
    k, v = _mla_keys_values(rows, _mla_kvb(lp, cfg), cfg.latent)
    q = jnp.concatenate([q_n, q_r], axis=-1)
    return _masked_attention(q, k, v, _causal(wpos, rows.shape[1]),
                             jnp.max(wpos))


def _mla_keys_values(rows, w, la):
    """``(K (B, H, L, nope + rope), V (B, H, L, value))`` REBUILT from cached
    rows ``rows`` (B, L, latent_row) by ``w`` (:func:`_mla_kvb`): a head's
    keys beside the rotated key all heads share."""
    B, L, _ = rows.shape
    kv = jnp.einsum("bld,dhn->bhln", rows[..., :la.latent], w)
    k = jnp.concatenate(
        [kv[..., :la.nope], jnp.broadcast_to(
            rows[:, None, :, la.latent:la.latent + la.rope],
            (B, w.shape[1], L, la.rope))], axis=-1)
    return k, kv[..., la.nope:]


def window_tile(page: int, pages: int) -> int:
    """Whole pages a tile of :func:`_mla_window_call`: ``_KEY_TILE`` keys, a
    page at least, the slot's block table at most."""
    return min(max(1, _KEY_TILE // page), pages)


@functools.partial(jax.jit, static_argnames=("la",))
def _mla_window_call(q, kv_pages, bt, wpos, n_valid, w, *, la):
    """Causal attention of a window's queries ``q`` (B, H, W, nope + rope) at
    ``wpos`` (B, W) over their rows' LATENT pages ``kv_pages`` (N, 1, page,
    latent_row) through ``bt`` (B, P), K and V rebuilt by ``w``
    (:func:`_mla_kvb`) a tile of :func:`window_tile` pages at a time inside
    the fold, up to the tile that holds the last real lane's position
    (``n_valid`` (B,) real lanes a row). The temporaries are a tile's
    whatever ``P`` is, and the work follows the context. One jitted name, so
    that a trace shows the window's attention apart. float32 (B, H, W,
    value) out."""
    B, H, W, _ = q.shape
    page, P = kv_pages.shape[2], bt.shape[1]
    per = window_tile(page, P)
    T = per * page
    qg = q[:, :, None]
    real = jnp.arange(W)[None] < n_valid[:, None]
    last = jnp.max(jnp.where(real, wpos, 0))
    # entries past the table read trash page 0, at positions no lane reaches
    bt = jnp.pad(bt, ((0, 0), (0, -P % per)))

    def body(i, carry):
        pages = jax.lax.dynamic_slice_in_dim(bt, i * per, per, axis=1)
        k, v = _mla_keys_values(
            kv_pages[pages][:, :, 0].reshape(B, T, -1), w, la)
        t = i * T + jnp.arange(T)
        return _fold_keys(carry, qg, k, v,
                          (t[None, None] <= wpos[..., None])[:, None])

    init = _fold_start(qg, la.value)
    out = (body(0, init) if per == P else
           jax.lax.fori_loop(0, last // T + 1, body, init))
    return _fold_end(out, (B, H, W, la.value))


def _mla_out(lp, x, o, cfg):
    """The layer's end: a sigmoid gate a head (``cfg.latent.gate``) or
    ``W_o`` alone."""
    return (_head_gated_out(lp, x, o, cfg, norm=False) if cfg.latent.gate
            else _heads_out(lp, o, cfg))


def _mla_absorbed(lp, q_n, q_r, kv_pages, bt, lengths, cfg):
    """One query a row over the latent pages in place: ``W_kvb``'s key half
    folded into the query, its value half applied to the weighted sum of
    latents. ``q_n``, ``q_r`` (B, H, 1, .)."""
    from ...ops.paged_attention import paged_attention_latent
    la = cfg.latent
    w = _mla_kvb(lp, cfg)
    q_abs = jnp.einsum("bhn,lhn->bhl", q_n[:, :, 0], w[..., :la.nope],
                       preferred_element_type=F32)
    q_lat = jnp.concatenate([q_abs, q_r[:, :, 0].astype(F32)], axis=-1)
    q_lat = jnp.pad(q_lat, ((0, 0), (0, 0),
                            (0, kv_pages.shape[-1] - q_lat.shape[-1])))
    ctx = paged_attention_latent(
        q_lat, kv_pages, bt, lengths, v_width=la.latent,
        scale=(la.nope + la.rope) ** -0.5)                  # (B, H, latent)
    o = jnp.einsum("bhl,lhv->bhv", ctx.astype(cfg.dtype), w[..., la.nope:],
                   preferred_element_type=F32)
    return o[:, :, None]


def _mla_cache(cfg, batch, max_len):
    return {"kv": jnp.zeros((batch, 1, max_len, latent_row(cfg)), cfg.dtype)}


def _mla_contiguous(lp, x, c, wpos, w):
    cfg, n_valid = w.cfg, w.n_valid
    q_n, q_r, row = _mla_inputs(lp, x, wpos, cfg)
    W = x.shape[1]
    L = c["kv"].shape[2]
    dest = jnp.where(jnp.arange(W)[None] < n_valid[:, None], wpos, L)
    rows = jax.vmap(lambda buf, val, idx: buf.at[idx].set(
        val, mode="drop"))(c["kv"][:, 0], row, dest)
    o = _mla_expanded(lp, q_n, q_r, rows, wpos, cfg)
    return _mla_out(lp, x, o, cfg), {"kv": rows[:, None]}


def _mla_paged(lp, x, c, wpos, w):
    """An mla layer over its latent pages: the window's rows are written
    through the block table (padding lanes and idle rows to trash page 0);
    the decode tick then attends absorbed, in place; a window attends
    expanded, a tile of its row's pages at a time
    (:func:`_mla_window_call`)."""
    cfg, pos, n_valid, bt, page, kernel = w[:6]
    B, W, _ = x.shape
    P = bt.shape[1]
    q_n, q_r, row = _mla_inputs(lp, x, wpos, cfg)
    lane_ok = jnp.arange(W)[None] < n_valid[:, None]
    pg = jnp.take_along_axis(bt, jnp.clip(wpos // page, 0, P - 1), axis=1)
    kv = c["kv"].at[jnp.where(lane_ok, pg, 0).reshape(-1, 1),
                    jnp.zeros((1, 1), jnp.int32),
                    (wpos % page).reshape(-1, 1)].set(
                        row.reshape(B * W, 1, -1))
    if kernel:
        o = _mla_absorbed(lp, q_n, q_r, kv, bt,
                          jnp.where(n_valid > 0, pos + 1, 0), cfg)
    else:
        o = _mla_window_call(jnp.concatenate([q_n, q_r], axis=-1), kv, bt,
                             wpos, n_valid, _mla_kvb(lp, cfg), la=cfg.latent)
    return _mla_out(lp, x, o, cfg), {"kv": kv}


class _LatentCounts(TickCounts):
    """The prefill windows' fold, :func:`window_tile` pages of keys at a time
    (``latent_window_keys``: whole tiles up to a window's last key;
    ``_context``: the keys it had to rebuild; ``_pairs``: the (query, key)
    pairs its causal mask lets through, what the mathematics needs), and
    ``latent_sweep_pages`` / ``_steps``, the absorbed kernel's sweep of a
    decode call by the rule the call reads its shapes with
    (docs/observability.md)."""

    def __init__(self, cfg, kind, geometry):
        from ...ops.paged_attention import latent_block
        super().__init__(cfg, kind, geometry)
        self.page, per = geometry.page_size, geometry.pages_a_slot
        self.tile = self.page * window_tile(self.page, per)
        self.block = latent_block(
            self.page * latent_row(cfg) * jnp.dtype(cfg.dtype).itemsize, per)

    def window(self, offset, lanes):
        last = offset + lanes - 1
        return {"latent_window_keys": (last // self.tile + 1) * self.tile,
                "latent_window_context": offset + lanes,
                "latent_window_pairs": (lanes * offset
                                        + lanes * (lanes + 1) // 2)}

    def decode(self, positions, rows, context):
        out = super().decode(positions, rows, context)
        if self.kernel:
            pages = [-(-(pos + 1) // self.page) for pos in positions]
            out["latent_sweep_pages"] = self.layers * sum(pages)
            out["latent_sweep_steps"] = self.layers * (
                rows - len(pages)
                + sum(max(1, -(-p // self.block)) for p in pages))
        return out


# ---- conv and gqa: the mixers that end in W_o alone ---------------------------

def _conv_check(cfg):
    if cfg.conv is None or cfg.conv.taps < 2:
        raise ValueError("conv layers need cfg.conv (taps >= 2: the cache "
                         "is the taps - 1 rows before a token)")


def _conv_init(cfg, rng):
    D, K = cfg.d_model, cfg.conv.taps
    return {"in": _dense(rng, D, 3 * D), "o": _dense(rng, D, D),
            "taps": rng.normal(0, K ** -0.5, (K, D)).astype(np.float32)}


def _conv_rows(cfg, rows: int):
    return {"conv": ((rows, cfg.conv.taps - 1, cfg.d_model), cfg.dtype)}


def _conv_layer(lp, x, c, wpos, w):
    """A gated short convolution over a window ``x`` (B, W, D) continuing the
    tails ``c["conv"]`` (B, taps - 1, D), the rows of ``z = B * u`` before
    the window (zeroed for a window that starts at position 0; the decode
    tick, ``w.tick``, never does). Returns ``(W_o(C * c), the tails after
    lane n_valid - 1)``: padding lanes never enter a tail."""
    cfg, pos, n_valid, tick = w.cfg, w.pos, w.n_valid, w.tick
    dt = cfg.dtype
    K = cfg.conv.taps
    W = x.shape[1]
    tail = _slot_rows(c, w.slot)["conv"]
    if not tick:
        tail = _fresh(tail, pos, n_valid)
    b, gate, u = jnp.split(_proj(x, lp["in"], dt), 3, axis=-1)
    ext = jnp.concatenate([tail.astype(dt), b * u], axis=1)  # (B, K-1+W, D)
    taps = lp["taps"].astype(F32)
    mixed = sum(ext[:, j:j + W].astype(F32) * taps[j] for j in range(K))
    new_tail = _next_tail(ext, n_valid, K - 1, tick)
    return (_proj((gate.astype(F32) * mixed).astype(dt), lp["o"], dt),
            _slot_rows_back(c, {"conv": new_tail}, w.slot))


def _gqa_check(cfg):
    H, Hkv, _ = dims(cfg)
    if H // Hkv not in (1, 2, 4, 8, 16):
        raise ValueError(f"gqa layers: {H} heads over {Hkv} KV heads (the "
                         "decode kernel folds 1, 2, 4, 8 or 16 queries a KV "
                         "head)")


def _gqa_init(cfg, rng):
    H, Hkv, hd = dims(cfg)
    D = cfg.d_model
    lp = {"q": _dense(rng, D, H * hd), "k": _dense(rng, D, Hkv * hd),
          "v": _dense(rng, D, Hkv * hd), "o": _dense(rng, H * hd, D)}
    if cfg.qk_positions:
        lp.update({"q_norm": _ones(hd), "k_norm": _ones(hd)})
    return lp


def _gqa_cache(cfg, batch, max_len):
    _, Hkv, hd = dims(cfg)
    kv = jnp.zeros((batch, Hkv, max_len, hd), cfg.dtype)
    return {"k": kv, "v": kv}


def _gqa_qkv(lp, x, wpos, cfg):
    """``(q (B, H, W, hd), k, v (B, Hkv, W, hd))`` in the compute dtype: q
    and k RMS-normed a head, then rotated; as projected where the model's
    attention takes no positions (``cfg.qk_positions`` False)."""
    H, Hkv, hd = dims(cfg)
    dt = cfg.dtype
    if not cfg.qk_positions:
        return (_heads(_proj(x, lp["q"], dt), H, hd),
                _heads(_proj(x, lp["k"], dt), Hkv, hd),
                _heads(_proj(x, lp["v"], dt), Hkv, hd))
    q = _head_rms(_heads(_proj(x, lp["q"], dt), H, hd), lp["q_norm"],
                  cfg.norm_eps)
    k = _head_rms(_heads(_proj(x, lp["k"], dt), Hkv, hd), lp["k_norm"],
                  cfg.norm_eps)
    v = _heads(_proj(x, lp["v"], dt), Hkv, hd)
    cos, sin = _rope_tables(wpos, hd, cfg.rope_theta, F32)   # (B, W, hd/2)
    cos, sin = cos[:, None], sin[:, None]
    return (_rot_half(q, cos, sin).astype(dt),
            _rot_half(k, cos, sin).astype(dt), v.astype(dt))


def _heads_out(lp, o, cfg):
    """``W_o`` of the heads' contexts ``o`` (B, H, W, hd), no gate."""
    B, H, W, hd = o.shape
    return _proj(o.transpose(0, 2, 1, 3).reshape(B, W, H * hd).astype(
        cfg.dtype), lp["o"], cfg.dtype)


def _causal(wpos, L):
    """(B, 1, W, L) bool: key ``l`` is at or before the query's position."""
    return (jnp.arange(L)[None, None] <= wpos[..., None])[:, None]


def _gqa_contiguous(lp, x, c, wpos, w):
    cfg, n_valid = w.cfg, w.n_valid
    q, k, v = _gqa_qkv(lp, x, wpos, cfg)
    kc, vc = _put_window(c, k, v, wpos, n_valid)
    o = _masked_attention(q, kc, vc, _causal(wpos, kc.shape[2]),
                          jnp.max(wpos), cfg.attn_scale or None)
    return _heads_out(lp, o, cfg), {"k": kc, "v": vc}


def _gqa_paged(lp, x, c, wpos, w):
    """A gqa layer over its pages. The decode tick (``kernel``) is one fused
    launch: the token's K/V row scattered and the live pages folded, four
    query heads a KV head's block. A window writes its rows through the
    block table (padding lanes and idle rows to trash page 0), gathers its
    row's pages and masks."""
    from ...ops.paged_attention import paged_attention_gqa, split_kv
    cfg, pos, n_valid, bt, page, kernel = w[:6]
    B = x.shape[0]
    _, Hkv, hd = dims(cfg)
    q, k, v = _gqa_qkv(lp, x, wpos, cfg)
    if kernel:
        o, kv = paged_attention_gqa(q[:, :, 0], k[:, :, 0], v[:, :, 0],
                                    c["kv"], bt, pos, active=n_valid > 0,
                                    scale=cfg.attn_scale or None)
        return _heads_out(lp, o[:, :, None], cfg), {"kv": kv}
    kv = _scatter_pages(c["kv"], k, v, bt, wpos, n_valid, page)
    L = bt.shape[1] * page
    kc, vc = split_kv(kv[bt].transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, L, 2 * hd))
    o = _masked_attention(q, kc, vc, _causal(wpos, L), jnp.max(wpos),
                          cfg.attn_scale or None)
    return _heads_out(lp, o, cfg), {"kv": kv}


# ---- the kinds ---------------------------------------------------------------

#: the mixer kinds ``cfg.mixers`` may name, a record each (:class:`Mixer`)
KINDS: Dict[str, Mixer] = {
    "lightning": Mixer(
        init=_lightning_init, served=_HEADS_MAJOR,
        **_slot_kind(_lightning_rows, _lightning_layer)),
    "sparse": Mixer(
        check=_sparse_check, served=_HEADS_MAJOR,
        init=lambda cfg, rng: _gated_attention_init(cfg, rng, dims(cfg)[1]),
        cache=_sparse_cache, pool=_sparse_pool,
        contiguous=_sparse_contiguous, paged=_sparse_paged,
        # selection and the compressed keys are laid out by the block
        page=lambda cfg: cfg.sparse.block_size, counts=_SelectCounts),
    "kda": Mixer(
        check=_kda_check, init=_kda_init,
        **_slot_kind(_kda_rows, _kda_layer),
        labels=("kda", "kda_window"), counts=TickCounts),
    "mla": Mixer(
        check=_mla_check, init=_mla_init, cache=_mla_cache, pool=_mla_pool,
        contiguous=_mla_contiguous, paged=_mla_paged,
        labels=("latent", "latent_window"), counts=_LatentCounts),
    "conv": Mixer(
        check=_conv_check, init=_conv_init,
        **_slot_kind(_conv_rows, _conv_layer),
        labels=("conv", "conv"), counts=TickCounts),
    "gqa": Mixer(
        check=_gqa_check, init=_gqa_init, cache=_gqa_cache, pool=_kv_pages,
        contiguous=_gqa_contiguous, paged=_gqa_paged,
        labels=("gqa", "gqa_window"), counts=TickCounts),
    "ssm": Mixer(
        check=_ssm_check, init=_ssm_init,
        **_slot_kind(_ssm_rows, _ssm_layer),
        labels=("ssm", "ssm_window"), counts=_StateCounts),
}
MIXERS = tuple(KINDS)


# ---- the window -------------------------------------------------------------

def _finish(params, h, cfg, n_valid, last_only):
    """Final norm with muP's logit scaling folded in: hidden states of every
    lane, or with ``last_only`` of lane ``n_valid - 1`` alone."""
    hidden = (_rms(h.astype(F32), params["final_ln"], cfg.norm_eps)
              * cfg.logit_scale).astype(cfg.dtype)
    if last_only:
        last = jnp.maximum(n_valid - 1, 0)[:, None, None]
        hidden = jnp.take_along_axis(hidden, last, axis=1)[:, 0]
    return hidden


def _routed(lp, x32, cfg, n_valid):
    """A layer's routed feed-forward on the window's normed rows ``x32``
    (B, W, D) float32: the held experts' part plus the shared expert, and
    the layer's routing counts (``parallel.moe.MOE_STATS``)."""
    from ...parallel.moe import moe_topk_held
    B, W, D = x32.shape
    valid = (jnp.arange(W)[None] < n_valid[:, None]).reshape(B * W)
    y, counts = moe_topk_held(x32.astype(cfg.dtype).reshape(B * W, D),
                              x32.reshape(B * W, D), lp["moe"], cfg.routed,
                              valid)
    return y.reshape(B, W, D), counts


def _window(params, tokens, pos, cfg, n_valid, mixer, last_only, stats=None):
    """The layer loop shared by both cache forms; ``mixer(kind, lp, x, wpos,
    layer index)`` returns the mixer's output and records its new cache.
    The feed-forward is the layer's ``cfg.ffn`` kind, resolved here at trace
    time; a caller that passes a dict as ``stats`` finds the routed layers'
    counts under ``"moe"``: int32 in ``MOE_STATS``' order, summed over the
    layers, the largest expert's load their maximum."""
    dt = cfg.dtype
    W = tokens.shape[1]
    wpos = pos[:, None] + jnp.arange(W, dtype=jnp.int32)
    h = _embed(params, tokens, cfg) * jnp.asarray(cfg.embed_scale, dt)
    rs = jnp.asarray(cfg.residual_scale, dt)
    counts = []
    for i, (kind, lp) in enumerate(zip(cfg.mixers, params["layers"])):
        x = _rms(h.astype(F32), lp["ln1"], cfg.norm_eps).astype(dt)
        h = h + rs * mixer(i, kind, lp, x, wpos).astype(dt)
        feed = _ffn_kind(cfg, i)
        if feed == "moe":
            y, c = _routed(lp, _rms(h.astype(F32), lp["ln2"], cfg.norm_eps),
                           cfg, n_valid)
            counts.append(c)
            h = h + rs * y
        elif feed == "dense":
            x = _rms(h.astype(F32), lp["ln2"], cfg.norm_eps).astype(dt)
            h = h + rs * _swiglu(lp, x, dt)
    if stats is not None and counts:
        c = jnp.stack(counts)
        stats["moe"] = jnp.concatenate([c[:, :-1].sum(axis=0),
                                        c[:, -1:].max(axis=0)])
    return _finish(params, h, cfg, n_valid, last_only)


def _lanes(tokens, pos, n_valid, active):
    B, W = tokens.shape
    pos = pos.astype(jnp.int32)
    n_valid = (jnp.full((B,), W, jnp.int32) if n_valid is None
               else n_valid.astype(jnp.int32))
    if active is not None:
        n_valid = jnp.where(active, n_valid, 0)
    return pos, n_valid


def _fresh(state, pos, n_valid):
    """A row whose window starts at position 0 starts from a zero state
    (and zero convolution tails): what resets a reused slot."""
    new = ((pos == 0) & (n_valid > 0)).reshape((-1,) + (1,) * (state.ndim - 1))
    return jnp.where(new, jnp.zeros((), state.dtype), state)


def window_contiguous(params: Dict, tokens, pos, cache, cfg, *,
                      n_valid=None, active=None, last_only=False):
    """``W`` tokens a row at positions ``pos[b] ..`` continuing a contiguous
    cache (:func:`init_hybrid_cache`): the full forward (``pos`` 0 over an
    empty cache), ``prefill_cache``, ``decode_step``. Returns ``(final
    hidden states, new cache)``; :func:`head` makes logits of them."""
    check_config(cfg)
    pos, n_valid = _lanes(tokens, pos, n_valid, active)
    new_cache = [None] * cfg.layers
    w = Window(cfg, pos, n_valid)

    def mixer(i, kind, lp, x, wpos):
        y, new_cache[i] = KINDS[kind].contiguous(lp, x, cache[i], wpos, w)
        return y

    hidden = _window(params, tokens, pos, cfg, n_valid, mixer, last_only)
    return hidden, new_cache


def _paged_mixer(cfg, bufs, new_bufs, block_tables, pos, n_valid, page_size,
                 kernel, slot, tick):
    """The ``mixer`` :func:`_window` calls for rows of the engine's pool:
    layer ``i`` reads its buffers as the walk's last writer left them
    (``new_bufs[i]``, else ``bufs[i]``) and leaves them in ``new_bufs[i]``.
    ``kernel``: the Pallas decode kernels (one token a row); ``slot``: the
    state row of a one-row prefill window; ``tick``: one token a row, so a
    conv layer shifts its tails with no slice a row."""
    w = Window(cfg, pos, n_valid, block_tables, page_size, kernel, slot, tick)

    def mixer(i, kind, lp, x, wpos):
        c = bufs[i] if new_bufs[i] is None else new_bufs[i]
        y, new_bufs[i] = KINDS[kind].paged(lp, x, c, wpos, w)
        return y

    return mixer


def window_paged(params: Dict, tokens, pos, bufs, block_tables, cfg, *,
                 page_size: int, impl: str = "kernel", n_valid=None,
                 active=None, slot=None, last_only=False, stats=None):
    """The engine's window over its pool (:func:`pool_shapes`): pages
    through ``block_tables`` for the sparse layers' K/V; their compressed
    keys and the lightning layers' states are rows a slot — row ``b`` of
    the batch is slot ``b`` (the decode tick, every slot a row), or with
    ``slot`` the one row of a prefill chunk is that slot's. ``impl="kernel"`` runs the two Pallas
    decode kernels when the window is one token; a longer window, and
    ``impl="gather"`` always, gather and mask. Returns ``(logits, bufs)``;
    ``stats``: :func:`_window`."""
    check_config(cfg)
    pos, n_valid = _lanes(tokens, pos, n_valid, active)
    one = tokens.shape[1] == 1
    new_bufs = [None] * cfg.layers
    mixer = _paged_mixer(cfg, bufs, new_bufs, block_tables, pos, n_valid,
                         page_size, impl == "kernel" and one, slot, one)
    hidden = _window(params, tokens, pos, cfg, n_valid, mixer, last_only,
                     stats)
    return head(params, hidden), new_bufs


def tick_with_window(params: Dict, tokens, pos, bufs, block_tables, cfg, *,
                     page_size: int, chunk, impl: str = "kernel",
                     active=None, stats=None):
    """The decode tick with ONE prefill window riding it: ``tokens``, ``pos``
    (S,), a row a slot, as :func:`window_paged`'s one-token window, and
    ``chunk = (ids (1, W), start (1,), bt_row (1, P), slot, n_valid (1,))``,
    that function's one-row window of a slot the tick holds inactive. ONE
    layer walk over the ``S + W`` tokens, each its own row of one lane: the
    token table, every feed-forward (a routed layer's experts among them)
    and the head are read once for both; a layer's mixer runs the window's
    lanes in their gathered and masked form and then the tick's rows on the
    decode kernels, each on its rows of the pool. Routing is per token and
    dropless, so a token's feed-forward does not depend on the rows beside
    it. Returns ``(the tick's logits (S, vocab), the logits (1, vocab) of
    the window's lane n_valid - 1, bufs)``; ``stats`` counts every row the
    step routed."""
    check_config(cfg)
    ids, start, bt_row, slot, n_chunk = chunk
    S, W = tokens.shape[0], ids.shape[1]
    pos, n_tick = _lanes(tokens[:, None], pos, None, active)
    start, n_chunk = _lanes(ids, start, n_chunk, None)
    wpos = start[:, None] + jnp.arange(W, dtype=jnp.int32)
    new_bufs = [None] * cfg.layers
    window = _paged_mixer(cfg, bufs, new_bufs, bt_row, start, n_chunk,
                          page_size, False, slot, False)
    tick = _paged_mixer(cfg, bufs, new_bufs, block_tables, pos, n_tick,
                        page_size, impl == "kernel", None, True)

    def mixer(i, kind, lp, x, _):
        # the window first, as the chunk program ran before the tick did
        yw = window(i, kind, lp, x[S:].reshape(1, W, -1), wpos)
        yt = tick(i, kind, lp, x[:S], pos[:, None])
        return jnp.concatenate([yt, yw.reshape(W, 1, -1)], axis=0)

    real = (jnp.arange(W) < n_chunk[0]).astype(jnp.int32)
    hidden = _window(params, jnp.concatenate([tokens, ids[0]])[:, None],
                     jnp.concatenate([pos, wpos[0]]), cfg,
                     jnp.concatenate([n_tick, real]), mixer, False,
                     stats)[:, 0]
    last = jax.lax.dynamic_slice_in_dim(
        hidden, S + jnp.maximum(n_chunk[0] - 1, 0), 1, axis=0)
    logits = head(params, jnp.concatenate([hidden[:S], last], axis=0))
    return logits[:S], logits[S:], new_bufs
