"""Plain reference for LFM2-24B-A2B's language model (``LiquidAI/LFM2-24B-A2B``
``config.json``, ``model_type`` ``lfm2_moe``): a pre-norm decoder whose layers
mix tokens by a gated short convolution or by grouped-query softmax attention
with rotary positions, under a dense SwiGLU (the leading layers) or routed
experts. float32 ``jax.numpy`` at ``HIGHEST`` matmul precision, no cache, no
kernels, no batching, one sequence at a time. Imports nothing of the program
under test.

``x <- x + Op(RMSNorm(x))``, then ``x <- x + FF(RMSNorm(x))``; RMSNorm with a
learned scale and eps ``norm_eps``; no biases (``conv_bias`` false). A held
layer's kind is ``layer_types[j]`` (the ``j``-th layer HELD, published index
``layers_held[j]``); the first ``num_dense_layers`` held layers are dense
(width ``intermediate_size``), the rest routed.

* ``conv``: ``[B, C, u] = W_in x`` (thirds of ``3 * hidden``); ``z = B * u``;
  ``c_t = sum_j w_j z_(t - K + 1 + j)``, ``K = conv_L_cache`` taps a channel,
  zeros before the start: ``K`` shifted multiply-adds over the whole
  sequence; ``y = W_out(C * c)``.
* ``full_attention``: ``q = W_q x`` as ``num_attention_heads`` heads of ``d``,
  ``k = W_k x``, ``v = W_v x`` as ``num_key_value_heads``; q and k pass an
  RMSNorm over a head's ``d`` values with a learned scale; rotate-half RoPE
  over all ``d`` dims at ``rope_theta``; causal softmax, scores over
  ``sqrt(d)``, KV head ``h // (heads / kv heads)`` serves query head ``h``;
  ``y = W_o o``. A block of ``QUERIES`` queries at a time.
* routed feed-forward: ``s = sigmoid(W_r x)``; the ``num_experts_per_tok``
  best by ``s + b`` are chosen; weights ``s`` (without ``b``) over their sum
  (``norm_topk_prob``) times ``routed_scaling_factor``; ``FF = sum_e w_e W2_e
  (silu(W1_e x) * W3_e x)``: a loop over ALL experts with a mask, ``EXPERTS``
  at a time. An expert's gate and up projections are stored side by side
  (``gate_up``: ``[W1, W3]``). No groups, no shared expert.
* the final RMSNorm (the family's ``embedding_norm``), then the head.

Weights are made on the device from the seed, a layer at a time, in the
pytree the program's decoder takes and in ``param_dtype``; the reference
reads those same values as float32, a layer and ``EXPERTS`` experts at a
time.

``cast`` is applied to both operands of every matrix product, the router's
included: the identity for the reference, a scaled round trip through a lower
precision for the control that the comparison must reject.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references._control import lower_precision

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QUERIES = 512          # queries of an attention layer attended at once
EXPERTS = 16           # experts upcast and multiplied at a time
PAD = 1024             # a sequence is padded to a multiple (fewer programs)


def head_dim(sizes):
    return sizes.get("head_dim") or (sizes["hidden_size"]
                                     // sizes["num_attention_heads"])


def rope_theta(sizes):
    return float(sizes["rope_parameters"]["rope_theta"])


def layer_kinds(sizes):
    """``[(mixer, feed-forward)]`` of the layers held."""
    return [(kind, "dense" if j < sizes["num_dense_layers"] else "moe")
            for j, kind in enumerate(sizes["layer_types"])]


def make_weights(sizes, seed):
    D, H, Hkv = (sizes["hidden_size"], sizes["num_attention_heads"],
                 sizes["num_key_value_heads"])
    d, K = head_dim(sizes), sizes["conv_L_cache"]
    F, E = sizes["moe_intermediate_size"], sizes["num_experts"]
    vocab = sizes["vocab_size"]
    dtype = jnp.dtype(sizes["param_dtype"])
    # the device's own bit generator: billions of normals from threefry are
    # most of a run's set-up on the chip
    key = jax.random.key(
        int(np.random.SeedSequence(seed).generate_state(1)[0]), impl="rbg")

    def normal(k, shape, scale):
        return (scale * jax.random.normal(k, shape, F32)).astype(dtype)

    def glorot(k, din, dout):
        return {"w": normal(k, (din, dout), (2.0 / (din + dout)) ** 0.5)}

    def ones(n):
        return {"scale": jnp.ones(n, dtype)}

    def mixer(k, kind):
        k = jax.random.split(k, 4)
        if kind == "conv":
            return {"in": glorot(k[0], D, 3 * D), "o": glorot(k[1], D, D),
                    "taps": normal(k[2], (K, D), K ** -0.5)}
        return {"q": glorot(k[0], D, H * d), "k": glorot(k[1], D, Hkv * d),
                "v": glorot(k[2], D, Hkv * d), "o": glorot(k[3], H * d, D),
                "q_norm": ones(d), "k_norm": ones(d)}

    def ffn(k, kind):
        k = jax.random.split(k, 4)
        if kind == "dense":
            ff = sizes["intermediate_size"]
            return {"gate": glorot(k[0], D, ff), "up": glorot(k[1], D, ff),
                    "down": glorot(k[2], ff, D)}
        s = (2.0 / (D + F)) ** 0.5
        return {"moe": {
            "router": glorot(k[0], D, E),
            "bias": 0.01 * jax.random.normal(k[1], (E,), F32),
            "experts": {"gate_up": normal(k[2], (E, D, 2 * F), s),
                        "down": normal(k[3], (E, F, D), s)}}}

    @functools.partial(jax.jit, static_argnames=("kind", "feed"))
    def layer(k, kind, feed):
        km, kf = jax.random.split(k)
        return dict({"ln1": ones(D), "ln2": ones(D)}, **mixer(km, kind),
                    **ffn(kf, feed))

    @jax.jit
    def ends(k):
        k = jax.random.split(k, 2)
        return (normal(k[0], (vocab, D), 0.02), normal(k[1], (D, vocab), 0.02))

    kinds = layer_kinds(sizes)
    keys = jax.random.split(key, 1 + len(kinds))
    tok, head = ends(keys[0])
    out = {"embed": {"tok": tok}, "final_ln": ones(D),
           "lm_head": {"w": head},
           "layers": [layer(k, kind, feed)
                      for k, (kind, feed) in zip(keys[1:], kinds)]}
    return jax.block_until_ready(out)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(t, theta):
    """Rotate-half rotary embedding of ``t`` (.., S, d) at positions 0..S-1."""
    S, d = t.shape[-2:]
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = t[..., :d // 2], t[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def short_conv(x, lp, sizes, cast):
    """The gated short convolution on one sequence ``x`` (S, hidden)."""
    K = sizes["conv_L_cache"]
    S = x.shape[0]

    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)

    b, c, u = jnp.split(mm(x, lp["in"]["w"]), 3, axis=-1)
    z = jnp.pad(b * u, ((K - 1, 0), (0, 0)))
    conv = sum(z[j:j + S] * lp["taps"][j] for j in range(K))
    return mm(c * conv, lp["o"]["w"])


def attention(x, lp, sizes, cast):
    """Grouped-query attention on one sequence, a block of queries at a
    time."""
    H, Hkv, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 head_dim(sizes))
    S = x.shape[0]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]     # :func:`logits`

    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)

    def heads(t, n):
        return t.reshape(S, n, d).transpose(1, 0, 2)

    q = _rope(_rms(heads(mm(x, lp["q"]["w"]), H), lp["q_norm"]["scale"], eps),
              theta).reshape(Hkv, H // Hkv, S, d)
    kc = cast(_rope(_rms(heads(mm(x, lp["k"]["w"]), Hkv),
                         lp["k_norm"]["scale"], eps), theta))
    vc = cast(heads(mm(x, lp["v"]["w"]), Hkv))
    Q = min(S, QUERIES)

    def block(i):
        t = i * Q + jnp.arange(Q)
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q, Q, axis=2)
        s_ = jnp.einsum("gjqd,gkd->gjqk", cast(qb), kc,
                        precision=HI) / math.sqrt(d)
        ok = jnp.arange(S)[None] <= t[:, None]
        a = jax.nn.softmax(jnp.where(ok[None, None], s_, -jnp.inf), axis=-1)
        return jnp.einsum("gjqk,gkd->gjqd", cast(a), vc, precision=HI)

    o = jax.lax.map(block, jnp.arange(S // Q))          # (n, Hkv, G, Q, d)
    o = o.transpose(0, 3, 1, 2, 4).reshape(S, H * d)
    return mm(o, lp["o"]["w"])


def route(x, router_w, bias, sizes, cast):
    """``(chosen (S, k) expert ids, weights (S, k))``."""
    s = jax.nn.sigmoid(jnp.dot(cast(x), cast(router_w), precision=HI))
    _, chosen = jax.lax.top_k(s + bias, sizes["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, w / w.sum(axis=-1, keepdims=True) \
        * sizes["routed_scaling_factor"]


def _swiglu(x, gate, up, down, cast):
    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


@functools.partial(jax.jit, static_argnames=("control",))
def _experts_block(x, weight, gate_up, down, control):
    """``sum_e weight[:, e] * E_e(x)`` over one block of experts: every
    expert on every token, masked by its weight (0 where not chosen)."""
    cast = lower_precision(control)
    F = down.shape[1]

    def one(y, e):
        gu, dn, w = e
        gu, dn = gu.astype(F32), dn.astype(F32)
        return y + w[:, None] * _swiglu(x, gu[:, :F], gu[:, F:], dn,
                                        cast), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (gate_up, down, weight.T))
    return y


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _expert_weights(x, router_w, bias, sizes, control):
    """(S, experts): an expert's weight for each token, 0 where the token
    did not choose it."""
    chosen, w = route(x, router_w.astype(F32), bias.astype(F32), sizes,
                      lower_precision(control))
    return jnp.where(
        chosen[:, :, None] == jnp.arange(router_w.shape[1])[None, None],
        w[:, :, None], 0.0).sum(axis=1)


def routed_ffn(x, p, sizes, control=None):
    """The routed feed-forward on ``x`` (S, hidden) float32; ``p`` the
    layer's ``moe`` entry in ``param_dtype``; ``sizes`` hashable
    (:class:`_static`)."""
    weight = _expert_weights(x, p["router"]["w"], p["bias"], sizes, control)
    y = jnp.zeros_like(x)
    for lo in range(0, weight.shape[1], EXPERTS):
        y = y + _experts_block(x, weight[:, lo:lo + EXPERTS],
                               p["experts"]["gate_up"][lo:lo + EXPERTS],
                               p["experts"]["down"][lo:lo + EXPERTS], control)
    return y


class _static(dict):
    """A configuration's sizes as a jitted function's static argument."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))

    def __eq__(self, other):
        return json.dumps(self, sort_keys=True) == json.dumps(
            other, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("kind", "sizes", "control"))
def _mixer_layer(h, lp, kind, sizes, control):
    cast = lower_precision(control)
    lp = _f32(lp)
    x = _rms(h, lp["ln1"]["scale"], sizes["norm_eps"])
    h = h + (short_conv if kind == "conv" else attention)(x, lp, sizes, cast)
    return h, _rms(h, lp["ln2"]["scale"], sizes["norm_eps"])


@functools.partial(jax.jit, static_argnames=("control",))
def _dense_ffn(x, lp, control):
    lp = _f32(lp)
    return _swiglu(x, lp["gate"]["w"], lp["up"]["w"], lp["down"]["w"],
                   lower_precision(control))


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(h, final_ln, w, eps, control):
    cast = lower_precision(control)
    return jnp.dot(cast(_rms(h, final_ln["scale"].astype(F32), eps)),
                   cast(w.astype(F32)), precision=HI)


SHAPE_KEYS = ("num_attention_heads", "num_key_value_heads", "hidden_size",
              "conv_L_cache", "norm_eps", "num_experts_per_tok",
              "routed_scaling_factor")


def logits(params, sizes, ids, rows, control=None):
    """float32 logits of one sequence ``ids`` (1-D) at positions ``rows``: a
    full causal forward layer by layer, the head on those rows only. A
    sequence longer than ``QUERIES`` is padded on the right to a multiple
    (never seen: causal)."""
    if len(ids) > QUERIES:
        ids = np.pad(np.asarray(ids), (0, -len(ids) % QUERIES))
    ids = jnp.asarray(ids, jnp.int32)
    shape = _static({k: sizes[k] for k in SHAPE_KEYS},
                    head_dim=head_dim(sizes), rope_theta=rope_theta(sizes))
    h = params["embed"]["tok"][ids].astype(F32)
    for lp, (kind, feed) in zip(params["layers"], layer_kinds(sizes)):
        mixer = {k: v for k, v in lp.items()
                 if k not in ("moe", "gate", "up", "down")}
        h, x = _mixer_layer(h, mixer, kind, shape, control)
        if feed == "moe":
            h = h + routed_ffn(x, lp["moe"], shape, control)
        else:
            h = h + _dense_ffn(x, {k: lp[k] for k in ("gate", "up", "down")},
                               control)
    return _head(h[jnp.asarray(rows)], params["final_ln"],
                 params["lm_head"]["w"], sizes["norm_eps"], control)


def served_token_gaps(params, sizes, prompt, served, pad_to, control=None):
    """Teacher forcing over ``prompt + served``, padded on the right (causal,
    so the padding is never seen) to a multiple of PAD positions, at most
    ``pad_to``. For each served token the amount by which its reference logit
    lies below the row's best, in standard deviations of the row: 0 where it
    is the float32 argmax.

    With ``control``, the tokens judged are not the served ones but those the
    lower precision puts first at each of the same positions."""
    n, m = len(prompt), len(served)
    ids = np.zeros(min(pad_to, -(-(n + m) // PAD) * PAD), np.int32)
    ids[:n] = prompt
    ids[n:n + m] = served
    rows = np.arange(n - 1, n - 1 + m)
    ref = np.asarray(logits(params, sizes, ids, rows))
    if control:
        judged = np.asarray(logits(params, sizes, ids, rows,
                                   control=control)).argmax(axis=1)
    else:
        judged = np.asarray(served)
    short = ref.max(axis=1) - ref[np.arange(m), judged]
    return short / ref.std(axis=1)
