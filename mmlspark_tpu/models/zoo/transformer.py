"""Transformer encoder — native JAX, mesh-sharded (dp × tp with Megatron-style
sequence parallelism), plus a full training step.

The reference has **no** intra-model sharding anywhere (SURVEY.md §2.8) — its
largest models run whole-per-executor through ONNX/CNTK sessions. This module
is where the TPU rebuild goes past parity: a BERT-class encoder whose weights
and activations are laid out over a ``Mesh(('dp','tp'))``:

* batch sharded over ``dp``;
* attention heads and MLP hidden dim sharded over ``tp`` (Megatron split:
  QKV/W1 column-parallel, O/W2 row-parallel — XLA inserts the psum);
* activations outside attention/MLP sharded over the sequence axis on ``tp``
  (sequence parallelism), so layernorm/residual memory scales with 1/tp;
* ring attention over long sequences lives in ``parallel/ring.py`` and mounts
  on the same mesh (axis ``sp``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["TransformerConfig", "SparseAttention", "RoutedExperts",
           "LatentAttention", "DeltaRule", "ShortConv", "StateSpace",
           "init_transformer",
           "transformer_apply",
           "train_step", "param_shardings", "BERT_BASE", "BERT_MINI",
           "DECODER_MINI", "generate", "generate_cached",
           "decode_step", "init_kv_cache", "decode_window_ragged",
           "init_paged_cache", "paged_gather", "paged_scatter_rows",
           "decode_step_paged", "decode_window_paged"]


class SparseAttention(NamedTuple):
    """InfLLM-V2 block-sparse attention's sizes (the ``sparse`` mixer):
    compressed keys are means over ``kernel_size`` positions every
    ``kernel_stride``; a query past ``dense_len`` of context attends the
    first ``init_blocks`` blocks of ``block_size`` positions, the blocks
    over its last ``window_size`` positions and the best-scored of the
    rest, ``topk`` in all, chosen once per KV group."""
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    window_size: int = 2048
    init_blocks: int = 1
    dense_len: int = 8192


class RoutedExperts(NamedTuple):
    """A routed feed-forward's sizes (``ffn`` kind ``"moe"``): a router over
    ``experts`` scores every token (sigmoid, float32, a learned selection
    bias), the experts stand in ``groups`` equal groups of which the
    ``groups_kept`` best stay (a group scores the sum of its two best), the
    ``per_token`` best experts among them are chosen and their scores,
    normalised over all chosen, times ``scale`` weigh the experts' outputs.
    THIS process holds experts ``first .. first + count - 1`` (its share of
    an expert-parallel layer; ``count`` 0 = all): it routes over all of
    them and adds up only what its own give. ``d_shared`` > 0 adds one
    always-on expert of that width, computed on every share alike.
    ``swiglu_limits`` are the published clamps of the layers held (expert
    and shared, a layer after a layer): a non-zero one is refused, its form
    is not built. ``latent`` > 0 puts the experts in a latent of that width
    all experts of a layer share (``l = W_dn x``; the weighted sum of the
    experts' outputs goes back through ``W_up``; the router and the shared
    expert read the model's row); ``form`` is an expert's body, ``"swiglu"``
    (``(silu(x W_g) * x W_u) W_d``) or ``"relu2"`` (``relu(x W_1)^2 W_2``,
    no gate; the shared expert takes the same form). ``score`` is the
    router's form: ``"sigmoid"`` (the above) or ``"softmax"``, a bias-free
    linear map whose ``per_token`` largest LOGITS are chosen and whose
    weights are ``scale`` times a softmax over the chosen logits alone."""
    experts: int = 8
    first: int = 0
    count: int = 0
    per_token: int = 2
    groups: int = 1
    groups_kept: int = 1
    scale: float = 1.0
    d_expert: int = 0
    d_shared: int = 0
    swiglu_limits: tuple = ()
    latent: int = 0
    form: str = "swiglu"
    score: str = "sigmoid"

    @property
    def held(self) -> int:
        return self.count or self.experts


class LatentAttention(NamedTuple):
    """Multi-head latent attention's sizes (the ``mla`` mixer): a token
    caches ONE row of ``latent + rope`` values (the normed key/value latent
    and a rotated key every head shares); a head's query is ``nope + rope``
    wide, its value ``value``. ``q_rank`` > 0 projects the query through a
    normed latent of that width (``q_a``, RMSNorm, ``q_b``) instead of one
    product; ``gate`` ends the layer in a sigmoid gate a head before
    ``W_o``, False in ``W_o`` alone."""
    latent: int = 512
    nope: int = 128
    rope: int = 64
    value: int = 128
    q_rank: int = 0
    gate: bool = True


class DeltaRule(NamedTuple):
    """The ``kda`` mixer's sizes: a causal depthwise convolution of
    ``conv_kernel`` taps on q, k and v, and a per-channel log-decay held in
    ``(gate_floor, 0)`` a token."""
    conv_kernel: int = 4
    gate_floor: float = -5.0


class ShortConv(NamedTuple):
    """The ``conv`` mixer's size (a gated short convolution): a causal
    depthwise convolution of ``taps`` taps a channel over ``d_model``
    channels (the published ``conv_L_cache``); a sequence caches the
    ``taps - 1`` rows before its next token."""
    taps: int = 3


class StateSpace(NamedTuple):
    """The ``ssm`` mixer's sizes (a Mamba-2 selective state-space layer):
    ``heads`` heads of ``head_dim`` channels on a state ``state`` wide a
    channel (``S`` is ``head_dim x state`` a head, not square), ``groups``
    groups of heads sharing one ``B`` and one ``C`` a token, a causal
    depthwise convolution of ``taps`` taps over ``heads * head_dim + 2 *
    groups * state`` channels, and ``chunk`` tokens a step of the chunked
    scan a window runs."""
    heads: int = 8
    head_dim: int = 64
    state: int = 128
    groups: int = 1
    taps: int = 4
    chunk: int = 128


class TransformerConfig(NamedTuple):
    vocab: int = 30522
    layers: int = 12
    d_model: int = 768
    heads: int = 12
    d_ff: int = 3072
    max_len: int = 512
    dtype: Any = jnp.bfloat16
    #: >0 turns every ``moe_every``-th FFN into a mixture-of-experts block
    #: (experts sharded over dp — the GShard deployment; parallel/moe.py)
    moe_experts: int = 0
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    #: weight of the Switch/GShard load-balance loss (keeps the router from
    #: collapsing onto one expert, which silently drops tokens)
    moe_aux_weight: float = 0.01
    #: route attention through the Pallas flash kernel (``ops/flash_attention``)
    #: — O(S) memory streaming softmax instead of the (B, H, S, S) score
    #: matrix; on a mesh it mounts per-shard via shard_map (heads on tp).
    #: Semantics differ from the dense path only for a row whose mask is
    #: all-False (a fully-padded sequence): dense -1e9 bias degenerates to
    #: uniform attention (mean of v), flash yields exact zeros — the
    #: better-defined output, but flip-sensitive if a consumer pools padded
    #: rows without masking
    use_flash: bool = False
    #: decoder (Llama-family) switches: causal attention, RMSNorm instead
    #: of LayerNorm, rotary position embeddings instead of the learned
    #: position table
    causal: bool = False
    norm: str = "layernorm"        # "layernorm" | "rmsnorm"
    position: str = "learned"      # "learned" | "rope"
    rope_theta: float = 10000.0
    #: a HYBRID decoder (``models/zoo/hybrid.py``): one mixer kind a layer,
    #: ``"lightning"`` (linear attention over a recurrent (hd x hd) float32
    #: state a head, RoPE) or ``"sparse"`` (grouped-query block-sparse
    #: softmax attention, no positions). Non-empty selects that block:
    #: bias-free projections, per-head QK RMSNorm, a sigmoid output gate,
    #: SwiGLU, RMSNorm, and the three scalings below. Empty keeps the dense
    #: multi-head block above, unchanged.
    mixers: tuple = ()
    #: two more kinds: ``"kda"`` (a delta-rule linear attention with a
    #: per-channel decay gate and short convolutions, sizes in ``kda``) and
    #: ``"mla"`` (latent attention, sizes in ``latent``). ``ffn`` names one
    #: feed-forward kind a layer of a hybrid decoder, ``"dense"`` (SwiGLU of
    #: ``d_ff``) or ``"moe"`` (``routed``); empty = dense everywhere.
    ffn: tuple = ()
    routed: Optional[RoutedExperts] = None
    latent: Optional[LatentAttention] = None
    kda: Optional[DeltaRule] = None
    #: and two that end in ``W_o`` alone, no output gate: ``"conv"`` (a gated
    #: short convolution, size in ``conv``: its whole cache is the
    #: convolution's tail, a row a slot) and ``"gqa"`` (full grouped-query
    #: softmax attention with per-head QK RMSNorm and RoPE over plain pages)
    conv: Optional[ShortConv] = None
    #: a seventh kind, ``"ssm"`` (a selective state-space layer, sizes in
    #: ``ssm``: a float32 state and the convolution's tail, a row a slot).
    #: ``ffn`` may also name ``"none"``: the layer is its mixer alone (no
    #: second norm, no feed-forward). ``qk_positions`` False makes a gqa
    #: layer plain: no per-head q/k norm and no rotation
    ssm: Optional[StateSpace] = None
    qk_positions: bool = True
    #: the epsilon of a hybrid decoder's RMSNorms (the block's, the final
    #: one, a gqa layer's per-head ones)
    norm_eps: float = 1e-6
    #: KV heads of the sparse and gqa layers (0 = ``heads``) and an explicit
    #: head size (0 = ``d_model // heads``)
    kv_heads: int = 0
    head_dim: int = 0
    sparse: Optional[SparseAttention] = None
    #: what a gqa layer's scores are multiplied by (0: ``head_dim ** -0.5``;
    #: a muP model states its own, 1 / head_dim), window and decode kernel
    #: alike
    attn_scale: float = 0.0
    #: the head is the token table (``tie_word_embeddings``): a hybrid
    #: model's parameters then hold no ``lm_head`` and :func:`head` reads
    #: ``embed.tok``
    tied_head: bool = False
    #: muP: the embedding is multiplied by ``embed_scale``, every residual
    #: branch by ``residual_scale``, the final hidden state by
    #: ``logit_scale`` before the head
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0

    def is_moe_layer(self, i: int) -> bool:
        return (self.moe_experts > 0 and self.moe_every > 0
                and (i % self.moe_every) == (self.moe_every - 1))


BERT_BASE = TransformerConfig()
#: Llama-style decoder shape (causal + RMSNorm + RoPE); small enough to test
DECODER_MINI = TransformerConfig(vocab=1024, layers=4, d_model=256, heads=8,
                                 d_ff=1024, max_len=128, causal=True,
                                 norm="rmsnorm", position="rope")
BERT_MINI = TransformerConfig(vocab=1024, layers=4, d_model=256, heads=8,
                              d_ff=1024, max_len=128)


def init_transformer(cfg: TransformerConfig, seed: int = 0) -> Dict:
    if cfg.mixers:
        from .hybrid import init_hybrid
        return init_hybrid(cfg, seed)
    rng = np.random.default_rng(seed)

    def dense(din, dout, scale=None):
        s = scale or np.sqrt(2.0 / (din + dout))
        return rng.normal(0, s, (din, dout)).astype(np.float32)

    def norm_p():
        p = {"scale": np.ones(cfg.d_model, np.float32)}
        if cfg.norm != "rmsnorm":       # RMSNorm has no bias
            p["bias"] = np.zeros(cfg.d_model, np.float32)
        return p

    params: Dict = {
        "embed": {"tok": dense(cfg.vocab, cfg.d_model, 0.02)},
        "layers": [],
        "final_ln": norm_p(),
        "lm_head": {"w": dense(cfg.d_model, cfg.vocab, 0.02)},
    }
    if cfg.position == "learned":
        params["embed"]["pos"] = dense(cfg.max_len, cfg.d_model, 0.02)
    for i in range(cfg.layers):
        layer = {
            "ln1": norm_p(),
            "qkv": {"w": dense(cfg.d_model, 3 * cfg.d_model),
                    "b": np.zeros(3 * cfg.d_model, np.float32)},
            "out": {"w": dense(cfg.d_model, cfg.d_model),
                    "b": np.zeros(cfg.d_model, np.float32)},
            "ln2": norm_p(),
        }
        if cfg.is_moe_layer(i):
            from ...parallel.moe import init_moe_params
            layer["moe"] = init_moe_params(cfg.d_model, cfg.d_ff,
                                           cfg.moe_experts,
                                           seed=seed * 1000 + i)
        else:
            layer["w1"] = {"w": dense(cfg.d_model, cfg.d_ff),
                           "b": np.zeros(cfg.d_ff, np.float32)}
            layer["w2"] = {"w": dense(cfg.d_ff, cfg.d_model),
                           "b": np.zeros(cfg.d_model, np.float32)}
        params["layers"].append(layer)
    return params


def param_shardings(mesh: Mesh) -> Dict:
    """PartitionSpec pytree matching ``init_transformer`` (Megatron layout)."""
    def norm_spec(lp):
        return {k: P() for k in lp}

    def layer_spec(is_moe: bool = False, lp=None):
        lp = lp or {}
        spec = {
            "ln1": norm_spec(lp.get("ln1", {"scale": 0, "bias": 0})),
            "qkv": {"w": P(None, "tp"), "b": P("tp")},      # column-parallel
            "out": {"w": P("tp", None), "b": P()},          # row-parallel
            "ln2": norm_spec(lp.get("ln2", {"scale": 0, "bias": 0})),
        }
        if is_moe:
            # experts over dp (GShard: ep == dp), expert hidden over tp
            spec["moe"] = {"gate": P(),
                           "w1": P("dp", None, "tp"),
                           "b1": P("dp", "tp"),
                           "w2": P("dp", "tp", None),
                           "b2": P("dp", None)}
        else:
            spec["w1"] = {"w": P(None, "tp"), "b": P("tp")}
            spec["w2"] = {"w": P("tp", None), "b": P()}
        return spec

    return {
        "embed": {"tok": P(None, "tp"), "pos": P(None, "tp")},
        "layers": [],  # filled dynamically by tree mapping below
        "final_ln": {"scale": P(), "bias": P()},
        "lm_head": {"w": P(None, "tp")},
        "_layer_template": layer_spec,
        "_norm_template": norm_spec,
    }


def shardings_for(params: Dict, mesh: Mesh) -> Dict:
    spec = param_shardings(mesh)
    template = spec.pop("_layer_template")
    norm_template = spec.pop("_norm_template")
    spec["layers"] = [template(is_moe="moe" in lp, lp=lp)
                      for lp in params["layers"]]
    spec["embed"] = {k: spec["embed"][k] for k in params["embed"]}
    spec["final_ln"] = norm_template(params["final_ln"])
    return jax.tree.map(lambda p: NamedSharding(mesh, p), spec,
                        is_leaf=lambda x: isinstance(x, P))


def _ln(x, p, eps=1e-5):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.var(x, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * p["scale"] + p["bias"]


def _rms(x, p, eps=1e-6):
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * inv * p["scale"]


def _norm(x, p, cfg):
    return _rms(x, p) if cfg.norm == "rmsnorm" else _ln(x, p)


def _rope_tables(positions, D: int, theta: float, dtype):
    """cos/sin tables for split-half rotation at the given positions
    (any shape); shared by the full forward and the cached decode step."""
    if D % 2:
        raise ValueError(f"rotary embeddings need an even head dim, got {D} "
                         f"(d_model/heads)")
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def _rot_half(t, cos, sin):
    half = t.shape[-1] // 2
    t0, t1 = t[..., :half], t[..., half:]
    return jnp.concatenate([t0 * cos - t1 * sin,
                            t0 * sin + t1 * cos], axis=-1)


def _rope(q, k, theta: float):
    """Rotary position embeddings on (B, H, S, D) q/k (split-half form)."""
    cos, sin = _rope_tables(jnp.arange(q.shape[2]), q.shape[-1], theta,
                            q.dtype)
    cos, sin = cos[None, None], sin[None, None]
    return _rot_half(q, cos, sin), _rot_half(k, cos, sin)


#: the most rows :func:`_rows` fetches one ``dynamic_slice`` each; past it
#: one 0/1 product reads the table once (PERF.md §6, PR 41: the crossing
#: on the chip, and the programs' text grows ten lines a sliced row)
_SLICED_ROWS = 64


def embed_read(width: int) -> str:
    """How :func:`_embed` reads a table ``width`` columns wide on the chip:
    ``"gather"`` or ``"in_place"`` (what a decoder's ``stats`` name)."""
    return "gather" if width % 128 == 0 else "in_place"


def _rows(table, idx, gather=False):
    """``table[idx]`` for a (V, D) table, bit for bit, read where the table
    lies. The chip keeps a table whose width is no multiple of its 128 lanes
    column-major, a gather wants it row-major, and the compiler then relays
    the WHOLE table in front of the gather in every program (GPT-2 XL:
    161 MB read and written to fetch eight rows). So such a table is read in
    a form the compiler takes in place: up to ``_SLICED_ROWS`` rows a
    ``dynamic_slice`` each, more as a 0/1 product under a float32 sum (one
    read of the table, nothing written). A width of whole lanes stays the
    gather it was, as does a caller that says ``gather``: the training
    forward (a row slice's transpose is a table-sized update a token).

    An id outside the table reads what the gather reads, in every form:
    a negative one counts from the end, one past the end (or under ``-V``)
    reads the nearest row. The product selects exactly while the table is
    finite (0 x Inf is NaN, in every other row) and gives a stored -0.0 as
    +0.0."""
    V, D = table.shape
    if gather or embed_read(D) == "gather":
        return table[idx]
    idx = jnp.clip(jnp.where(idx < 0, idx + V, idx), 0, V - 1)
    if idx.size <= _SLICED_ROWS:
        rows = [jax.lax.dynamic_slice(table, (i, 0), (1, D))
                for i in idx.reshape(-1)]
        return jnp.concatenate(rows).reshape(*idx.shape, D)
    hot = jax.nn.one_hot(idx, V, dtype=table.dtype)
    return jnp.einsum("...v,vd->...d", hot, table,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32).astype(table.dtype)


def _embed(params, tokens, cfg: TransformerConfig, wpos=None, gather=False):
    """Rows of the token table for ``tokens`` (B, W), in ``cfg.dtype``: the
    one read of the table every forward makes, both blocks' and the CPU
    oracles' (:func:`_rows`: in place where the chip would relay the table).
    The dense block adds its learned position rows, at ``wpos`` (B, W) or,
    with None, at 0..W-1 (a slice, not a gather); the hybrid block has none
    and scales the rows itself. ``gather`` keeps the plain gather whatever
    the width: for the differentiated, mesh-sharded forward."""
    dt = cfg.dtype
    h = _rows(params["embed"]["tok"].astype(dt), tokens, gather)
    if cfg.position == "learned" and not cfg.mixers:
        rows = params["embed"]["pos"].astype(dt)
        h = h + (rows[:tokens.shape[1]][None] if wpos is None
                 else _rows(rows, wpos, gather))
    return h


def head(params, hidden):
    """float32 logits of final hidden states: the one output product of
    cached decoding, both blocks'. Parameters without an ``lm_head`` are a
    tied model's: the head is the token table, read where it lies (a
    product over its columns, no transposed copy)."""
    if "lm_head" not in params:
        return jnp.einsum("...d,vd->...v", hidden.astype(jnp.float32),
                          params["embed"]["tok"])
    return hidden.astype(jnp.float32) @ params["lm_head"]["w"]


def _softmax_attend(q, k, v, ok, dt):
    """Softmax attention of (B, H, W, hd) queries over (B, H, L, hd) keys
    where ``ok`` (broadcast to (B, H, W, L)) allows: float32 scores,
    weights and context in ``dt``."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / np.sqrt(q.shape[-1])
    s = jnp.where(ok, s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, preferred_element_type=dt)


def _dense_window(params, h, wpos, cfg: TransformerConfig, attend):
    """The dense block's layer walk for every cached entry point: embedded
    rows ``h`` (B, W, D) at absolute positions ``wpos`` -> final hidden states
    (B, W, D). ``attend(i, q, k, v)`` owns the cache form: it takes layer
    ``i``'s (B, H, W, hd) heads (rotated under RoPE), records the layer's
    new cache and returns the context. The caller embeds (:func:`_embed`)
    and builds its masks before the walk: the order of a program's
    operations, and with it its lowered text, stays the entry point's."""
    if cfg.moe_experts:
        raise ValueError("cached decoding does not support MoE layers")
    dt = cfg.dtype
    B, W, _ = h.shape
    hd = cfg.d_model // cfg.heads
    if cfg.position == "rope":
        cos, sin = _rope_tables(wpos, hd, cfg.rope_theta, dt)  # (B, W, h/2)
        cos, sin = cos[:, None], sin[:, None]                  # (B,1,W,·)

    def heads(t):
        return t.reshape(B, W, cfg.heads, hd).transpose(0, 2, 1, 3)

    for i, lp in enumerate(params["layers"]):
        x = _norm(h.astype(jnp.float32), lp["ln1"], cfg).astype(dt)
        qkv = x @ lp["qkv"]["w"].astype(dt) + lp["qkv"]["b"].astype(dt)
        q, k, v = (heads(t) for t in jnp.split(qkv, 3, axis=-1))
        if cfg.position == "rope":
            q = _rot_half(q, cos, sin)
            k = _rot_half(k, cos, sin)
        ctx = attend(i, q, k, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, W, cfg.d_model)
        h = h + ctx @ lp["out"]["w"].astype(dt) + lp["out"]["b"].astype(dt)
        x = _norm(h.astype(jnp.float32), lp["ln2"], cfg).astype(dt)
        y = jax.nn.gelu(x @ lp["w1"]["w"].astype(dt) + lp["w1"]["b"].astype(dt))
        y = y @ lp["w2"]["w"].astype(dt) + lp["w2"]["b"].astype(dt)
        h = h + y
    return _norm(h.astype(jnp.float32), params["final_ln"], cfg).astype(dt)


def transformer_apply(params: Dict, ids: jnp.ndarray,
                      cfg: TransformerConfig,
                      mesh: Optional[Mesh] = None,
                      mask: Optional[jnp.ndarray] = None,
                      return_aux: bool = False):
    """Encoder forward → final hidden states (B, S, D) in cfg.dtype.

    ``return_aux=True`` additionally returns the accumulated MoE
    auxiliaries {``balance``: load-balance loss the trainer must add,
    ``dropped``: over-capacity token count} — a functional return, not an
    out-parameter, so it survives jit (a mutated-dict argument would be a
    trace-local copy)."""
    if cfg.mixers:
        # a hybrid decoder (models/zoo/hybrid.py): the window over an empty
        # cache, one device; its hidden states carry muP's logit scaling
        if mesh is not None or mask is not None:
            raise ValueError("a hybrid decoder takes no mesh and no mask")
        from .hybrid import init_hybrid_cache, window_contiguous
        B, S = ids.shape
        hidden, _ = window_contiguous(
            params, ids, jnp.zeros((B,), jnp.int32),
            init_hybrid_cache(cfg, B, S), cfg)
        aux = {"balance": jnp.float32(0.0), "dropped": jnp.float32(0.0)}
        return (hidden, aux) if return_aux else hidden
    if cfg.norm not in ("layernorm", "rmsnorm"):
        raise ValueError(f"cfg.norm {cfg.norm!r} (layernorm | rmsnorm)")
    if cfg.position not in ("learned", "rope"):
        raise ValueError(f"cfg.position {cfg.position!r} (learned | rope)")
    dt = cfg.dtype
    B, S = ids.shape

    def constrain(x, spec):
        if mesh is not None:
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
        return x

    moe_aux = {"balance": jnp.float32(0.0), "dropped": jnp.float32(0.0)}
    h = _embed(params, ids, cfg, gather=True)   # differentiated, sharded
    # sequence-parallel region: activations sharded (dp, tp) on (B, S)
    h = constrain(h, P("dp", "tp", None))

    if mask is not None:
        bias = jnp.where(mask[:, None, None, :], 0.0, -1e9).astype(jnp.float32)
    else:
        bias = None

    for lp in params["layers"]:
        x = _norm(h.astype(jnp.float32), lp["ln1"], cfg).astype(dt)
        x = constrain(x, P("dp", None, None))  # gather sequence for attention
        qkv = x @ lp["qkv"]["w"].astype(dt) + lp["qkv"]["b"].astype(dt)
        qkv = constrain(qkv, P("dp", None, "tp"))
        q, k, v = jnp.split(qkv, 3, axis=-1)
        hd = cfg.d_model // cfg.heads

        def heads(t):
            return t.reshape(B, S, cfg.heads, hd).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        if cfg.position == "rope":
            q, k = _rope(q, k, cfg.rope_theta)
        if cfg.use_flash:
            from ...ops.flash_attention import (flash_attention,
                                                flash_attention_sharded)
            if mesh is not None:
                ctx = flash_attention_sharded(q, k, v, mesh, kv_mask=mask,
                                              causal=cfg.causal)
            else:
                ctx = flash_attention(q, k, v, kv_mask=mask,
                                      causal=cfg.causal)
            ctx = ctx.astype(dt)
        else:
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                                preferred_element_type=jnp.float32) / np.sqrt(hd)
            if bias is not None:
                scores = scores + bias
            if cfg.causal:
                tri = jnp.tril(jnp.ones((S, S), bool))
                scores = jnp.where(tri[None, None], scores,
                                   jnp.float32(-1e9))
            attn = jax.nn.softmax(scores, axis=-1).astype(dt)
            ctx = jnp.einsum("bhqk,bhkd->bhqd", attn, v,
                             preferred_element_type=dt)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, cfg.d_model)
        proj = ctx @ lp["out"]["w"].astype(dt) + lp["out"]["b"].astype(dt)
        h = h + constrain(proj, P("dp", "tp", None))  # back to sequence-parallel

        x = _norm(h.astype(jnp.float32), lp["ln2"], cfg).astype(dt)
        x = constrain(x, P("dp", None, None))
        if "moe" in lp:
            from ...parallel.moe import moe_capacity, moe_ffn_gspmd
            cap = moe_capacity(S, cfg.moe_experts, cfg.moe_capacity_factor)
            y, aux = moe_ffn_gspmd(x, lp["moe"], cfg.moe_experts, cap,
                                   mesh=mesh, ep_axis="dp",
                                   tp_axis="tp")
            moe_aux["balance"] = moe_aux["balance"] + aux["balance_loss"]
            moe_aux["dropped"] = moe_aux["dropped"] + aux["dropped"]
        else:
            y = jax.nn.gelu(x @ lp["w1"]["w"].astype(dt)
                            + lp["w1"]["b"].astype(dt))
            y = constrain(y, P("dp", None, "tp"))
            y = y @ lp["w2"]["w"].astype(dt) + lp["w2"]["b"].astype(dt)
        h = h + constrain(y, P("dp", "tp", None))

    hidden = _norm(h.astype(jnp.float32), params["final_ln"], cfg).astype(dt)
    return (hidden, moe_aux) if return_aux else hidden


def loss_fn(params, ids, labels, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None):
    hidden, moe_aux = transformer_apply(params, ids, cfg, mesh,
                                        return_aux=True)
    logits = (hidden.astype(jnp.float32) @ params["lm_head"]["w"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.mean() + cfg.moe_aux_weight * moe_aux["balance"]


def train_step(params, opt_state, ids, labels, cfg: TransformerConfig,
               mesh: Optional[Mesh] = None, lr: float = 1e-4):
    """One SGD-with-momentum step; grads/opt-state shard like params."""
    loss, grads = jax.value_and_grad(loss_fn)(params, ids, labels, cfg, mesh)
    new_m = jax.tree.map(lambda m, g: 0.9 * m + g, opt_state, grads)
    new_p = jax.tree.map(lambda p, m: p - lr * m, params, new_m)
    return new_p, new_m, loss


def _warp_scaled_rows(scaled, top_k, top_p):
    """Top-k then nucleus filtering on temperature-scaled (S, V) logit
    rows with PER-ROW parameters (-inf outside the kept set) — the HF
    convention ``transformer._sample_logits`` follows. Neutral values
    (top_k=0 → k=V, top_p≥1 → cutoff at the sorted tail) reduce every
    filter to a no-op. Shared by the continuous
    engine's per-slot sampler and both speculative-sampling ratio
    tests (zoo + pool), which must warp the TARGET and the DRAFT
    with the same function to stay distribution-exact."""
    S, V = scaled.shape
    sorted_l = jnp.sort(scaled, axis=-1)[:, ::-1]
    k = jnp.where(top_k > 0, jnp.minimum(top_k, V), V)          # (S,)
    kth = jnp.take_along_axis(sorted_l, (k - 1)[:, None], axis=-1)
    filtered = jnp.where(scaled < kth, -jnp.inf, scaled)
    # nucleus mass over the k-filtered renormalized distribution
    posn = jnp.arange(V)[None]
    sorted_f = jnp.where(posn >= k[:, None], -jnp.inf, sorted_l)
    probs = jax.nn.softmax(sorted_f, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    eff_p = jnp.where((top_p > 0.0) & (top_p < 1.0), top_p, 1.0)
    cutoff_idx = jnp.sum(cum < eff_p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(sorted_f, cutoff_idx[:, None], axis=-1)
    return jnp.where(filtered < cutoff, -jnp.inf, filtered)



def _sample_logits(logits, key, temperature: float, top_k: int,
                   top_p: float):
    """Greedy (temperature 0) or filtered sampling shared by both
    generators: optional top-k truncation then nucleus (top-p) truncation,
    applied to (B, V) float32 logits."""
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    need_k = top_k > 0
    need_p = 0.0 < top_p < 1.0
    if need_k or need_p:
        # ONE descending sort serves both filters (per emitted token,
        # inside the decode scan — worth not doing twice)
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        if need_k:
            k = min(int(top_k), logits.shape[-1])   # oversized k = no-op
            logits = jnp.where(logits < sorted_l[:, k - 1][:, None],
                               -jnp.inf, logits)
        if need_p:
            # nucleus mass comes from the top-k-FILTERED renormalized
            # distribution (the HF convention) — mask the sorted tail
            # before the softmax/cumsum; renormalized mass reaches top_p
            # at an equal-or-earlier rank, so pre-filter mass would keep
            # MORE tokens inside the top-k set than callers expect
            if need_k:
                pos = jnp.arange(sorted_l.shape[-1])[None, :]
                sorted_l = jnp.where(pos >= k, -jnp.inf, sorted_l)
            probs = jax.nn.softmax(sorted_l, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep the smallest prefix with mass >= top_p (always >= 1)
            cutoff_idx = jnp.sum(cum < top_p, axis=-1)
            cutoff = jnp.take_along_axis(sorted_l, cutoff_idx[:, None],
                                         axis=-1)
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def generate(params: Dict, prompt_ids, cfg: TransformerConfig,
             max_new_tokens: int = 32, temperature: float = 0.0,
             seed: int = 0, top_k: int = 0, top_p: float = 1.0,
             eos_id: Optional[int] = None):
    """Autoregressive generation from a causal config (greedy when
    ``temperature == 0``, else softmax sampling). ``eos_id``: rows that
    emit it keep repeating it (static shapes — the convention the
    continuous engine's per-request truncation builds on).

    One jitted program: the sequence is padded to prompt+new length and the
    whole forward runs each step — causality guarantees position ``t``'s
    logits never see the not-yet-generated tail, so no KV-cache machinery
    is needed for correctness (the cache is a latency optimization this
    zoo model omits; cost is O(steps · full-forward)).
    """
    if not cfg.causal:
        raise ValueError("generate() needs cfg.causal=True")
    # numpy params indexed by a traced token array would force a tracer
    # →numpy conversion inside the scan
    params = jax.tree.map(jnp.asarray, params)
    prompt_ids = jnp.asarray(prompt_ids)
    B, P_len = prompt_ids.shape
    if P_len < 1:
        raise ValueError("generate() needs at least one prompt token "
                         "(an empty prompt would condition on padding)")
    L = P_len + max_new_tokens
    if L > cfg.max_len and cfg.position == "learned":
        raise ValueError(f"prompt+new = {L} exceeds max_len {cfg.max_len}")
    ids0 = jnp.pad(prompt_ids, ((0, 0), (0, max_new_tokens)))
    key0 = jax.random.PRNGKey(seed)

    def step(carry, t):
        ids, done = carry
        hidden = transformer_apply(params, ids, cfg)
        logits = (hidden[:, t - 1].astype(jnp.float32)
                  @ params["lm_head"]["w"])
        # fold_in by position: the cached generator derives the same key
        # at the same emit position, keeping the two paths seed-compatible
        nxt = _sample_logits(logits, jax.random.fold_in(key0, t),
                             temperature, top_k, top_p)
        if eos_id is not None:
            nxt = jnp.where(done, jnp.full_like(nxt, eos_id), nxt)
            done = done | (nxt == eos_id)
        ids = jax.lax.dynamic_update_slice(
            ids, nxt[:, None].astype(ids.dtype), (0, t))
        return (ids, done), nxt

    (ids, _), _ = jax.lax.scan(step, (ids0, jnp.zeros(B, bool)),
                               jnp.arange(P_len, L))
    return ids


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int):
    """Per-layer (B, H, L, D) key/value buffers for incremental decoding
    (a hybrid decoder's entries: ``hybrid.init_hybrid_cache``)."""
    if cfg.mixers:
        from .hybrid import init_hybrid_cache
        return init_hybrid_cache(cfg, batch, max_len)
    hd = cfg.d_model // cfg.heads
    shape = (batch, cfg.heads, max_len, hd)
    return [{"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}
            for _ in range(cfg.layers)]


def decode_step(params: Dict, token: jnp.ndarray, pos, cache,
                cfg: TransformerConfig):
    """One incremental decode step: ``token`` (B,) int at position ``pos``
    → (logits (B, vocab), updated cache). The KV-cache latency path of
    :func:`generate` — O(L) attention per step instead of a full forward.

    The shared-``pos`` special case of :func:`decode_step_ragged` (one
    layer-loop implementation keeps the two bit-identical — the continuous
    batching engine's parity invariant depends on it)."""
    B = token.shape[0]
    return decode_step_ragged(
        params, token, jnp.full((B,), pos, jnp.int32), cache, cfg)


def decode_step_ragged(params: Dict, tokens: jnp.ndarray, pos: jnp.ndarray,
                       cache, cfg: TransformerConfig,
                       active: Optional[jnp.ndarray] = None):
    """:func:`decode_step` with PER-ROW positions — the continuous-batching
    step (``serving/continuous.py``): each cache slot advances at its own
    position, so requests at different depths share one compiled program.

    ``tokens`` (B,) int, ``pos`` (B,) int32 per-row write positions,
    ``active`` (B,) bool (inactive rows keep their cache untouched and
    their logits are don't-care) → (logits (B, vocab), updated cache).

    The ``W = 1`` window of :func:`decode_window_ragged`.
    """
    logits, cache = decode_window_ragged(params, tokens[:, None], pos, cache,
                                         cfg, active)
    return logits[:, 0], cache


def prefill_cache(params: Dict, ids: jnp.ndarray, length,
                  cfg: TransformerConfig, max_len: int):
    """Batched prompt prefill for continuous batching: ONE causal forward
    over the (padded) prompt, capturing every layer's K/V into ``max_len``
    cache buffers, plus the logits at the last real token.

    ``ids`` (B, P) right-padded prompts, ``length`` (B,) real lengths
    (1 ≤ length ≤ P) → (logits (B, vocab), cache list of (B, H, max_len,
    hd) k/v). O(P) attention per token instead of :func:`generate_cached`'s
    token-by-token prefill — the standard serving split (prefill batched,
    decode incremental).
    """
    if cfg.mixers:
        from .hybrid import init_hybrid_cache, window_contiguous
        B = ids.shape[0]
        hidden, cache = window_contiguous(
            params, ids, jnp.zeros((B,), jnp.int32),
            init_hybrid_cache(cfg, B, max_len), cfg, n_valid=length,
            last_only=True)
        return head(params, hidden), cache
    dt = cfg.dtype
    B, P = ids.shape
    if P > max_len:
        raise ValueError(f"prompt {P} exceeds cache max_len {max_len}")
    length = length.astype(jnp.int32)
    wpos = jnp.arange(P)[None]                  # every row starts at 0
    valid = wpos < length[:, None]                              # (B, P)
    h = _embed(params, ids, cfg)
    tri = jnp.tril(jnp.ones((P, P), bool))
    # causal AND key-valid: padded key columns never attend anywhere
    attn_ok = tri[None, None] & valid[:, None, None, :]
    pad = ((0, 0), (0, 0), (0, max_len - P), (0, 0))
    cache = [None] * len(params["layers"])

    def attend(i, q, k, v):
        # fresh K/V: attended as they are, recorded padded to max_len
        cache[i] = {"k": jnp.pad(k.astype(dt), pad),
                    "v": jnp.pad(v.astype(dt), pad)}
        return _softmax_attend(q, k, v, attn_ok, dt)

    hidden = _dense_window(params, h, wpos, cfg, attend)
    last = jnp.take_along_axis(hidden, (length - 1)[:, None, None], axis=1)
    return head(params, last[:, 0]), cache


def decode_window(params: Dict, tokens: jnp.ndarray, pos, cache,
                  cfg: TransformerConfig):
    """Cached forward over a WINDOW of W tokens at positions
    ``pos..pos+W-1``: the chunk-sized middle ground between
    :func:`decode_step` (W=1) and :func:`prefill_cache` (fresh cache).

    ``tokens`` (B, W) int, ``pos`` scalar start (traced ok) →
    (logits (B, W, vocab), cache with the window's K/V written). Queries
    attend causally within the window and to everything cached before it —
    the verify primitive of speculative decoding, and a chunked-prefill
    building block.

    Delegates to :func:`decode_window_ragged` with a uniform position
    vector — one layer-loop implementation keeps the scalar and per-row
    paths bit-identical (the decode_step / decode_step_ragged pattern;
    the speculative-verify parity invariant depends on it).
    """
    B = tokens.shape[0]
    pos = jnp.full((B,), pos, jnp.int32)
    return decode_window_ragged(params, tokens, pos, cache, cfg)


def decode_window_ragged(params: Dict, tokens: jnp.ndarray,
                         pos: jnp.ndarray, cache, cfg: TransformerConfig,
                         active: Optional[jnp.ndarray] = None):
    """:func:`decode_window` with PER-ROW start positions — the verify
    primitive for speculative decoding inside the continuous-batching slot
    pool (``serving/continuous.py``): every slot scores its own gamma+1
    proposal window at its own depth in ONE compiled forward.

    ``tokens`` (B, W) int, ``pos`` (B,) int32 per-row window starts,
    ``active`` (B,) bool (inactive rows keep their cache untouched,
    logits are don't-care) → (logits (B, W, vocab), updated cache).
    Row b's query at window index j sits at absolute position
    ``pos[b] + j``, attends cached keys ``<= pos[b] + j``, and the
    window's K/V land at ``pos[b]..pos[b]+W-1`` in that row's cache —
    exactly :func:`decode_window` per row with a scalar start.
    """
    if cfg.mixers:
        from .hybrid import window_contiguous
        hidden, cache = window_contiguous(params, tokens, pos, cache, cfg,
                                          active=active)
        return head(params, hidden), cache
    dt = cfg.dtype
    W = tokens.shape[1]
    L = cache[0]["k"].shape[2]
    pos = pos.astype(jnp.int32)
    wpos = pos[:, None] + jnp.arange(W, dtype=jnp.int32)       # (B, W)
    h = _embed(params, tokens, cfg, wpos)
    # row b, query j sees cached keys at positions <= pos[b] + j
    key_ok = (jnp.arange(L)[None, None, :]
              <= wpos[:, :, None])[:, None]                    # (B,1,W,L)
    # decode_step's shared-pos path passes active=None: skip the masking
    # entirely so the delegation costs nothing
    keep = None if active is None else active[:, None, None, None]

    def scatter_row(buf, val, p):
        # (H, L, hd) ← (H, W, hd) at key-position p; vmapped over rows
        return jax.lax.dynamic_update_slice(buf, val, (0, p, 0))

    row_scatter = jax.vmap(scatter_row)
    new_cache = [None] * len(cache)

    def attend(i, q, k, v):
        c = cache[i]
        kc = row_scatter(c["k"], k.astype(dt), pos)
        vc = row_scatter(c["v"], v.astype(dt), pos)
        if keep is not None:
            kc = jnp.where(keep, kc, c["k"])
            vc = jnp.where(keep, vc, c["v"])
        new_cache[i] = {"k": kc, "v": vc}
        return _softmax_attend(q, kc, vc, key_ok, dt)

    hidden = _dense_window(params, h, wpos, cfg, attend)
    return head(params, hidden), new_cache


# ---- paged KV cache (vLLM-style PagedAttention) ----------------------------
# The physical cache is a pool of fixed-size PAGES, per layer one
# (num_pages, H, page_size, 2*hd) buffer, K beside V; a BLOCK TABLE row maps
# each batch row's logical pages to physical ones, and page 0 is the TRASH
# page that unallocated entries and inactive rows' writes point at. The one
# layer loop is ``_dense_window``; ``decode_window_paged`` gives it the
# closure that reads and writes pages in place (ops/paged_attention.py).
# ``impl="gather"`` is the parity oracle: ``paged_gather`` -> the contiguous
# window -> ``_paged_writeback``, bitwise equal to the contiguous path.

def init_paged_cache(cfg: TransformerConfig, num_pages: int,
                     page_size: int, kv_dtype=None):
    """Per-layer page pools (page 0 is the trash page — allocators must
    never hand it out): one ``(num_pages, H, page_size, 2*hd)`` buffer
    ``"kv"`` a layer, K in ``[..., :hd]`` and V in ``[..., hd:]``
    (``ops.paged_attention.pack_kv``; why: that module's docstring). With
    ``kv_dtype`` ("int8"/"fp8") pages store quantized values and each
    layer dict gains ``(num_pages, H, page_size)`` ``k_scale``/
    ``v_scale`` arrays (see ``ops/kv_quant.py``)."""
    from ...ops.kv_quant import SCALE_DTYPE, kv_store_dtype
    if cfg.mixers:
        raise ValueError("a hybrid decoder's pool also holds a state row a "
                         "slot: hybrid.init_hybrid_pool")
    hd = cfg.d_model // cfg.heads
    shape = (num_pages, cfg.heads, page_size, 2 * hd)
    store = kv_store_dtype(kv_dtype)
    if store is None:
        return [{"kv": jnp.zeros(shape, cfg.dtype)}
                for _ in range(cfg.layers)]
    sshape = shape[:3]
    return [{"kv": jnp.zeros(shape, store),
             "k_scale": jnp.ones(sshape, SCALE_DTYPE),
             "v_scale": jnp.ones(sshape, SCALE_DTYPE)}
            for _ in range(cfg.layers)]


def _is_quant_cache(c) -> bool:
    """A quantized page-pool layer dict carries its scale arrays."""
    return "k_scale" in c


def paged_gather(cache_pages, block_tables, length: int, out_dtype=None):
    """Assemble each row's pages into contiguous (B, H, length, hd) k/v.

    ``block_tables`` (B, P) int32 physical page ids per logical page;
    ``length`` trims the last page's tail so the result has EXACTLY the
    contiguous cache's key length — attention reductions then run over
    the same number of lanes, which is what keeps the paged step bitwise
    equal to the contiguous one. Quantized pools dequantize through
    their gathered scales (in ``out_dtype``, default f32) — this is the
    oracle path the quant-error gauge measures the kernel against."""
    from ...ops.kv_quant import dequantize_kv
    from ...ops.paged_attention import split_kv
    out = []
    for c in cache_pages:
        quant = _is_quant_cache(c)
        halves = split_kv(c["kv"][block_tables])  # 2 x (B, P, H, page, hd)
        row = {}
        for kk, g in zip(("k", "v"), halves):
            B, Pp, H, pg, hd = g.shape
            if quant:
                s = c[kk + "_scale"][block_tables]   # (B, P, H, page)
                g = dequantize_kv(g, s, out_dtype or jnp.float32)
            elif out_dtype is not None:
                g = g.astype(out_dtype)
            g = g.transpose(0, 2, 1, 3, 4).reshape(B, H, Pp * pg, hd)
            row[kk] = g[:, :, :length]
        out.append(row)
    return out


def _pool_rows(c, k, v):
    """``{key: rows}`` ready to store in the page-pool layer ``c`` from K
    and V rows ``(..., hd)``: packed, and for a quantized pool quantized
    with their scale rows alongside (``ops.paged_attention.stored_kv``)."""
    from ...ops.paged_attention import stored_kv
    keys = ("kv", "k_scale", "v_scale") if _is_quant_cache(c) else ("kv",)
    return dict(zip(keys, stored_kv(k, v, *(c[kk] for kk in keys))))


def paged_scatter_rows(cache_pages, rows, block_tables, page_size: int):
    """Write full contiguous (B, H, L, hd) k/v rows (a prefill output)
    into the pool through each row's block table. Logical pages past a
    row's allocation must map to the trash page in ``block_tables`` —
    their writes collide harmlessly there. Quantized pools quantize each
    position through the sanctioned ``quantize_kv`` and scatter the
    per-head scales alongside."""
    n_pages = (rows[0]["k"].shape[2] + page_size - 1) // page_size
    dest = block_tables[:, :n_pages].reshape(-1)         # (B*n_pages,)

    def paged(r):                # (B, H, L, hd) -> (B*n_pages, H, page, hd)
        B, H, L, hd = r.shape
        r = jnp.pad(r, ((0, 0), (0, 0),
                        (0, n_pages * page_size - L), (0, 0)))
        r = r.reshape(B, H, n_pages, page_size, hd)
        return r.transpose(0, 2, 1, 3, 4).reshape(
            B * n_pages, H, page_size, hd)

    return [{kk: c[kk].at[dest].set(new) for kk, new in _pool_rows(
                c, paged(rc["k"]), paged(rc["v"])).items()}
            for c, rc in zip(cache_pages, rows)]


def _paged_writeback(cache_pages, new_cache, block_tables, wpos,
                     page_size: int, active):
    """Scatter the freshly-written positions ``wpos`` (B, W) of an updated
    gathered cache back into the physical pages. Inactive rows (and only
    they) are redirected to trash page 0 — their "new" values are the old
    ones decode_step_ragged preserved, but their block-table rows may
    reference pages that were freed and reallocated to another request.
    Quantized pools write ``quantize_kv``'d bytes plus scales — the same
    helper every other writer uses, so the bytes agree bit-for-bit."""
    B, W = wpos.shape
    phys = jnp.take_along_axis(block_tables, wpos // page_size, axis=1)
    if active is not None:
        phys = jnp.where(active[:, None], phys, 0)
    pf = phys.reshape(-1)
    of = (wpos % page_size).reshape(-1)

    def written(t):              # (B, H, L, hd) -> (B*W, H, hd)
        vals = jnp.take_along_axis(t, wpos[:, None, :, None], axis=2)
        H, hd = vals.shape[1], vals.shape[3]
        return vals.transpose(0, 2, 1, 3).reshape(B * W, H, hd)

    return [{kk: c[kk].at[pf, :, of].set(new) for kk, new in _pool_rows(
                c, written(nc["k"]), written(nc["v"])).items()}
            for c, nc in zip(cache_pages, new_cache)]


def decode_step_paged(params: Dict, tokens: jnp.ndarray, pos: jnp.ndarray,
                      cache_pages, block_tables, cfg: TransformerConfig, *,
                      page_size: int, length: int,
                      active: Optional[jnp.ndarray] = None,
                      impl: Optional[str] = None,
                      mesh=None, slot_axis=None, head_axis=None,
                      stats: Optional[dict] = None):
    """One paged decode step: the ``W = 1`` window of
    :func:`decode_window_paged`, which describes the two implementations
    ``impl`` selects. ``tokens`` (B,), ``pos`` (B,), every ``pos`` <
    ``length`` → (logits (B, vocab), updated pages)."""
    logits, pages = decode_window_paged(
        params, tokens[:, None], pos, cache_pages, block_tables, cfg,
        page_size=page_size, length=length, active=active, impl=impl,
        mesh=mesh, slot_axis=slot_axis, head_axis=head_axis, stats=stats)
    return logits[:, 0], pages


def decode_window_paged(params: Dict, tokens: jnp.ndarray,
                        pos: jnp.ndarray, cache_pages, block_tables,
                        cfg: TransformerConfig, *, page_size: int,
                        length: int,
                        active: Optional[jnp.ndarray] = None,
                        impl: Optional[str] = None,
                        mesh=None, slot_axis=None, head_axis=None,
                        n_valid=None, slot=None, last_only: bool = False,
                        stats: Optional[dict] = None):
    """Paged window decode — the decode tick (``W = 1``), the speculative
    verify and the chunked-prefill primitive. Row b's window writes
    positions ``pos[b]..pos[b]+W-1`` into its pages; every such position
    must be < ``length``, the logical cache length (the engine sizes
    allocations so windows never clamp). Two implementations, selected by
    ``impl`` (``None`` → the ``MMLSPARK_TPU_PAGED_ATTN`` env knob, default
    ``"kernel"``):

    * ``"kernel"`` — the Pallas paged-attention kernel attends over the page
      pool IN PLACE through the block table and scatters the window's fresh
      K/V rows in the same launch
      (:func:`~mmlspark_tpu.ops.paged_attention.paged_attention_window`).
      Page writes are bit-identical to the gather path's; logits agree to
      f32 online-softmax accumulation order.
    * ``"gather"`` — the parity oracle: gather through the block table to
      the contiguous ``(B, H, length, hd)`` layout, run
      :func:`decode_window_ragged` on it, scatter the window's positions
      back to their pages. Logits are bitwise equal to the contiguous path
      on the same cache contents (masked lanes contribute exactly 0).

    A hybrid decoder (``cfg.mixers``) also carries state rows in
    ``cache_pages`` and takes three more arguments, which the dense block
    refuses: ``n_valid`` (B,), the real lanes of each row (padding must not
    reach a state); ``slot``, the state row of a one-row prefill window;
    ``last_only``, logits (B, vocab) of lane ``n_valid - 1`` alone. A dict
    passed as ``stats`` receives what the traced window counts of itself
    (``"moe"``: a routed decoder's pairs, ``hybrid._window``)."""
    from ...ops.paged_attention import paged_attention_window, resolve_impl
    if cfg.mixers:
        if mesh is not None:
            raise ValueError("a hybrid decoder takes no mesh")
        from .hybrid import window_paged
        return window_paged(params, tokens, pos, cache_pages, block_tables,
                            cfg, page_size=page_size,
                            impl=resolve_impl(impl), n_valid=n_valid,
                            active=active, slot=slot, last_only=last_only,
                            stats=stats)
    if n_valid is not None or slot is not None or last_only:
        raise ValueError("n_valid, slot and last_only belong to a hybrid "
                         "decoder's window")
    dt = cfg.dtype
    W = tokens.shape[1]
    pos = pos.astype(jnp.int32)
    wpos = pos[:, None] + jnp.arange(W, dtype=jnp.int32)       # (B, W)
    if resolve_impl(impl) != "kernel":
        gathered = paged_gather(cache_pages, block_tables, length,
                                out_dtype=dt)
        logits, new = decode_window_ragged(params, tokens, pos, gathered,
                                           cfg, active)
        return logits, _paged_writeback(cache_pages, new, block_tables,
                                        wpos, page_size, active)
    h = _embed(params, tokens, cfg, wpos)
    new_pages = [None] * len(cache_pages)

    def attend(i, q, k, v):
        c = cache_pages[i]
        scales = ({"k_scale": c["k_scale"], "v_scale": c["v_scale"]}
                  if _is_quant_cache(c) else {})
        ctx, *pools = paged_attention_window(
            q, k.astype(dt), v.astype(dt), c["kv"], block_tables, pos,
            active=active, mesh=mesh, slot_axis=slot_axis,
            head_axis=head_axis, **scales)
        new_pages[i] = dict(zip(("kv", *scales), pools))
        return ctx

    hidden = _dense_window(params, h, wpos, cfg, attend)
    return head(params, hidden), new_pages


def generate_cached(params: Dict, prompt_ids, cfg: TransformerConfig,
                    max_new_tokens: int = 32, temperature: float = 0.0,
                    seed: int = 0, top_k: int = 0, top_p: float = 1.0,
                    eos_id: Optional[int] = None):
    """KV-cached :func:`generate`: O(L) attention per emitted token.

    The prompt prefills the cache token-by-token through the same
    ``decode_step`` (a zoo model: simplicity over a batched prefill).
    ``eos_id`` repeats after firing, token-compatible with
    :func:`generate` (the key schedule is consumed identically)."""
    if not cfg.causal:
        raise ValueError("generate_cached() needs cfg.causal=True")
    params = jax.tree.map(jnp.asarray, params)
    prompt_ids = jnp.asarray(prompt_ids)
    B, P_len = prompt_ids.shape
    if P_len < 1:
        raise ValueError("generate_cached() needs at least one prompt token")
    L = P_len + max_new_tokens
    if L > cfg.max_len and cfg.position == "learned":
        raise ValueError(f"prompt+new = {L} exceeds max_len {cfg.max_len}")
    key0 = jax.random.PRNGKey(seed)
    # module-level cached jit: a per-call closure would RETRACE and
    # RECOMPILE the whole scan on every generation — seconds per call that
    # a bench would mistake for decode cost
    return _generate_cached_impl(params, prompt_ids, key0, cfg=cfg,
                                 max_new_tokens=int(max_new_tokens),
                                 temperature=float(temperature),
                                 top_k=int(top_k), top_p=float(top_p),
                                 eos_id=eos_id)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_new_tokens", "temperature",
                                    "top_k", "top_p", "eos_id"))
def _generate_cached_impl(params, prompt_ids, key0, cfg, max_new_tokens,
                          temperature, top_k, top_p, eos_id):
    B, P_len = prompt_ids.shape
    L = P_len + max_new_tokens
    cache = init_kv_cache(cfg, B, L)
    ids0 = jnp.pad(prompt_ids, ((0, 0), (0, max_new_tokens)))

    def step(carry, t):
        ids, cache, done = carry
        token = jax.lax.dynamic_slice_in_dim(ids, t, 1, axis=1)[:, 0]
        logits, cache = decode_step(params, token, t, cache, cfg)
        # keyed by EMIT position (t+1), matching generate() exactly —
        # prefill steps consume no randomness
        nxt = _sample_logits(logits.astype(jnp.float32),
                             jax.random.fold_in(key0, t + 1),
                             temperature, top_k, top_p)
        # scan covers t = 0..L-2, so t+1 is always a valid position; only
        # emit past the prompt (prompt positions keep their tokens)
        keep = t + 1 >= P_len
        if eos_id is not None:
            # post-sampling override keeps the key schedule identical to
            # the no-eos run (and to generate())
            nxt = jnp.where(done & keep, jnp.full_like(nxt, eos_id), nxt)
            done = done | (keep & (nxt == eos_id))
        cur = jax.lax.dynamic_slice_in_dim(ids, t + 1, 1, axis=1)[:, 0]
        upd = jnp.where(keep, nxt.astype(ids.dtype), cur)
        ids = jax.lax.dynamic_update_slice(ids, upd[:, None], (0, t + 1))
        return (ids, cache, done), None

    (ids, _, _), _ = jax.lax.scan(step, (ids0, cache, jnp.zeros(B, bool)),
                                  jnp.arange(L - 1))
    # the final position's token comes from the last step's write; the scan
    # covers t = 0..L-2, emitting into positions P_len..L-1
    return ids


def generate_beam(params: Dict, prompt_ids, cfg: TransformerConfig,
                  max_new_tokens: int = 32, num_beams: int = 4,
                  length_penalty: float = 1.0,
                  eos_id: Optional[int] = None):
    """Beam search over the cached decoder — one jitted program.

    Standard HF-convention semantics with fully static shapes: the
    prompt prefills once (:func:`prefill_cache`), beams fold into the
    batch axis (B·W cache rows), and every step is (1) one ragged-free
    ``decode_step``, (2) a (B, W·V) top-2W candidate scan — 2W because at
    most W of them can be eos-extensions, so W live beams always survive
    (the HF rationale) — and (3) a per-layer cache row gather to reorder
    beams. Finished hypotheses bank into a static (B, W) pool scored by
    ``sum_logprob / len**length_penalty``; the final answer is the best
    of banked + still-live beams. With ``num_beams=1`` and no eos this
    reduces exactly to greedy :func:`generate_cached`.

    Returns ``(ids (B, P+max_new), scores (B,))`` — the best hypothesis
    per batch row, prompt included, padded with ``eos_id`` (or the last
    token) past each hypothesis' end.
    """
    if not cfg.causal:
        raise ValueError("generate_beam() needs cfg.causal=True")
    if num_beams < 1:
        raise ValueError("num_beams must be >= 1")
    if cfg.mixers:
        raise ValueError("generate_beam() reorders K/V rows; a hybrid "
                         "decoder's cache is not rows of K/V alone")
    if num_beams > cfg.vocab:
        raise ValueError(f"num_beams {num_beams} exceeds vocab {cfg.vocab} "
                         "(only vocab distinct first tokens exist)")
    params = jax.tree.map(jnp.asarray, params)
    prompt_ids = jnp.asarray(prompt_ids)
    B, P_len = prompt_ids.shape
    if P_len < 1:
        raise ValueError("generate_beam() needs at least one prompt token")
    W, V, M = int(num_beams), cfg.vocab, int(max_new_tokens)
    L = P_len + M
    if L > cfg.max_len and cfg.position == "learned":
        raise ValueError(f"prompt+new = {L} exceeds max_len {cfg.max_len}")

    def penalize(score, length):
        return score / (length.astype(jnp.float32) ** jnp.float32(
            length_penalty))

    # prefill once per batch row, then replicate every cache row W times
    logits0, cache = prefill_cache(
        params, prompt_ids, jnp.full((B,), P_len, jnp.int32), cfg, L)
    cache = [{k: jnp.repeat(c[k], W, axis=0) for k in ("k", "v")}
             for c in cache]
    logp0 = jax.nn.log_softmax(logits0.astype(jnp.float32), axis=-1)
    batch_ix = jnp.arange(B)[:, None]                       # (B, 1)
    # first step follows the same top-2W discipline as the loop: an eos
    # among the top-W banks AND its live slot refills from the next-best
    # non-eos token (taking only top-W here would let a first-step eos
    # permanently narrow the beam). k0 caps at V; when W == V and eos
    # ranks, one live slot legitimately dies (-inf) — V-1 non-eos first
    # tokens exist.
    k0 = min(2 * W, V)
    c_scores, c_tok = jax.lax.top_k(logp0, k0)              # (B, k0)
    c_seqs = jnp.zeros((B, k0, M), jnp.int32).at[:, :, 0].set(c_tok)
    fin_scores = jnp.full((B, W), -jnp.inf)
    fin_seqs = jnp.zeros((B, W, M), jnp.int32)
    if eos_id is not None:
        c_eos = c_tok == eos_id
        bank = jnp.where(c_eos, penalize(c_scores, jnp.int32(1)), -jnp.inf)
        fin_scores, keep = jax.lax.top_k(bank, W)           # W <= k0 always
        fin_seqs = c_seqs[batch_ix, keep]
        live_key0 = jnp.where(c_eos, -jnp.inf, c_scores)
    else:
        live_key0 = c_scores
    scores, pick0 = jax.lax.top_k(live_key0, W)             # W <= k0
    tok0 = c_tok[batch_ix, pick0]
    seqs = c_seqs[batch_ix, pick0]
    tok = tok0.reshape(B * W)

    def step(carry, t):
        seqs, scores, fin_scores, fin_seqs, tok, cache = carry
        logits, cache = decode_step(params, tok, P_len + t - 1, cache, cfg)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        cand = scores[:, :, None] + logp.reshape(B, W, V)   # (B, W, V)
        c_scores, c_idx = jax.lax.top_k(cand.reshape(B, W * V), 2 * W)
        c_parent = c_idx // V                               # (B, 2W)
        c_tok = (c_idx % V).astype(jnp.int32)
        c_seqs = seqs[batch_ix, c_parent]                   # (B, 2W, M)
        c_seqs = jnp.where(jnp.arange(M)[None, None] == t,
                           c_tok[:, :, None], c_seqs)
        if eos_id is not None:
            c_eos = c_tok == eos_id
            # bank eos candidates (penalized), keep the best W of old+new
            pool_s = jnp.concatenate(
                [fin_scores,
                 jnp.where(c_eos, penalize(c_scores, t + 1), -jnp.inf)],
                axis=1)                                     # (B, 3W)
            pool_q = jnp.concatenate([fin_seqs, c_seqs], axis=1)
            fin_scores, keep = jax.lax.top_k(pool_s, W)
            fin_seqs = pool_q[batch_ix, keep]
            live_key = jnp.where(c_eos, -jnp.inf, c_scores)
        else:
            live_key = c_scores
        # top-W live (non-eos) continuations — ≥ W exist among the 2W
        scores, pick = jax.lax.top_k(live_key, W)
        parent = c_parent[batch_ix, pick]                   # (B, W)
        seqs = c_seqs[batch_ix, pick]
        tok = c_tok[batch_ix, pick].reshape(B * W)
        # reorder the cache rows onto the surviving beams
        rows = (jnp.arange(B)[:, None] * W + parent).reshape(B * W)
        cache = [{k: c[k][rows] for k in ("k", "v")} for c in cache]
        return (seqs, scores, fin_scores, fin_seqs, tok, cache), None

    if M > 1:
        (seqs, scores, fin_scores, fin_seqs, tok, cache), _ = jax.lax.scan(
            step, (seqs, scores, fin_scores, fin_seqs, tok, cache),
            jnp.arange(1, M))

    # final pool: banked hypotheses + live beams at full length
    all_s = jnp.concatenate(
        [fin_scores, penalize(scores, jnp.int32(M))], axis=1)  # (B, 2W)
    all_q = jnp.concatenate([fin_seqs, seqs], axis=1)
    best = jnp.argmax(all_s, axis=1)
    best_seq = all_q[jnp.arange(B), best]                   # (B, M)
    best_score = all_s[jnp.arange(B), best]
    if eos_id is not None:
        # pad past each hypothesis' eos with eos (generate()'s convention)
        hit = jnp.cumsum(
            (best_seq == eos_id).astype(jnp.int32), axis=1) > 0
        after = jnp.pad(hit, ((0, 0), (1, 0)))[:, :-1]      # strictly after
        best_seq = jnp.where(after, eos_id, best_seq)
    ids = jnp.concatenate([prompt_ids.astype(jnp.int32), best_seq], axis=1)
    return ids, best_score
