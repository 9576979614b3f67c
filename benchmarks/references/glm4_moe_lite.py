"""Plain reference for GLM-4.7-Flash's language model (``zai-org/GLM-4.7-Flash``
``config.json``, ``model_type`` ``glm4_moe_lite``): a pre-norm decoder whose
every layer mixes tokens by multi-head latent attention (MLA) with a low-rank
query, under a dense SwiGLU (the leading layer) or routed experts beside one
shared expert. float32 ``jax.numpy`` at ``HIGHEST`` matmul precision, no
cache, no kernels, no batching, one sequence at a time. Imports nothing of the
program under test.

``h <- h + MLA(RMSNorm(h))``, then ``h <- h + FF(RMSNorm(h))``; RMSNorm with a
learned scale and eps ``rms_norm_eps``; no biases (``attention_bias`` false).
The first ``first_k_dense_replace`` held layers are dense (width
``intermediate_size``), the rest routed.

* MLA (``H`` heads): ``c_q = RMSNorm(W_qa x)`` (``q_lora_rank`` wide); ``q =
  W_qb c_q`` as ``H`` heads of ``[q_n (qk_nope_head_dim) | q_r
  (qk_rope_head_dim)]``; ``[c_kv (kv_lora_rank) | k_r] = W_kva x``; ``c =
  RMSNorm(c_kv)``; rotate-half RoPE on each head's ``q_r`` and on the one
  ``k_r`` the heads share (theta ``rope_theta``, all ``qk_rope_head_dim``
  dims, no scaling); ``[k_n | v (v_head_dim)] = W_kvb c`` a head; scores
  ``(q_n . k_n + q_r . k_r) / sqrt(qk_nope_head_dim + qk_rope_head_dim)``,
  causal softmax; ``y = W_o concat_h(sum p v)``: no gate. Expanded: K and V of
  every position are built; a block of ``QUERIES`` queries at a time.
* routed feed-forward: ``s = sigmoid(W_r x)`` over ``n_routed_experts``; the
  ``num_experts_per_tok`` best by ``s + b`` are chosen (``noaux_tc``: the
  bias selects, it does not weigh; one group); weights ``s`` (without ``b``)
  over their sum (``norm_topk_prob``) times ``routed_scaling_factor``; ``FF =
  sum_e w_e E_e(x) + E_shared(x)``, ``E(x) = W_d (silu(W_g x) * W_u x)``: a
  loop over ALL experts with a mask, ``EXPERTS`` at a time. An expert's gate
  and up projections are stored side by side (``gate_up``: ``[W_g, W_u]``).
* the final RMSNorm, then the untied head.

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
QUERIES = 512          # queries of a layer attended at once
EXPERTS = 16           # experts upcast and multiplied at a time
PAD = 1024             # a sequence is padded to a multiple (fewer programs)


def layer_kinds(sizes):
    """The feed-forward of each layer held: ``dense`` | ``moe``."""
    return ["dense" if j < sizes["first_k_dense_replace"] else "moe"
            for j in range(len(sizes["layers_held"]))]


def make_weights(sizes, seed):
    D, H = sizes["hidden_size"], sizes["num_attention_heads"]
    rq, latent = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                      sizes["v_head_dim"])
    F, E = sizes["moe_intermediate_size"], sizes["n_routed_experts"]
    Fs = F * sizes["n_shared_experts"]
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

    def mixer(k):
        k = jax.random.split(k, 5)
        return {"q_a": glorot(k[0], D, rq), "q_norm": ones(rq),
                "q_b": glorot(k[1], rq, H * (nope + rope)),
                "kva": glorot(k[2], D, latent + rope),
                "c_norm": ones(latent),
                "kvb": glorot(k[3], latent, H * (nope + dv)),
                "o": glorot(k[4], H * dv, D)}

    def ffn(k, kind):
        k = jax.random.split(k, 7)
        if kind == "dense":
            ff = sizes["intermediate_size"]
            return {"gate": glorot(k[0], D, ff), "up": glorot(k[1], D, ff),
                    "down": glorot(k[2], ff, D)}
        s = (2.0 / (D + F)) ** 0.5
        return {"moe": {
            "router": glorot(k[0], D, E),
            "bias": 0.01 * jax.random.normal(k[1], (E,), F32),
            "experts": {"gate_up": normal(k[2], (E, D, 2 * F), s),
                        "down": normal(k[3], (E, F, D), s)},
            "shared": {"gate": glorot(k[4], D, Fs), "up": glorot(k[5], D, Fs),
                       "down": glorot(k[6], Fs, D)}}}

    @functools.partial(jax.jit, static_argnames=("feed",))
    def layer(k, feed):
        km, kf = jax.random.split(k)
        return dict({"ln1": ones(D), "ln2": ones(D)}, **mixer(km),
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
           "layers": [layer(k, feed) for k, feed in zip(keys[1:], kinds)]}
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


def mla(x, lp, sizes, cast):
    """The MLA mixer on one sequence ``x`` (S, hidden), expanded, a block of
    queries at a time."""
    H = sizes["num_attention_heads"]
    nope, rope, dv, latent = (sizes["qk_nope_head_dim"],
                              sizes["qk_rope_head_dim"], sizes["v_head_dim"],
                              sizes["kv_lora_rank"])
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    S = x.shape[0]

    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)

    c_q = _rms(mm(x, lp["q_a"]["w"]), lp["q_norm"]["scale"], eps)
    q = mm(c_q, lp["q_b"]["w"]).reshape(S, H, nope + rope).transpose(1, 0, 2)
    ckr = mm(x, lp["kva"]["w"])
    c = _rms(ckr[:, :latent], lp["c_norm"]["scale"], eps)
    k_r = _rope(ckr[:, latent:], theta)                      # (S, rope)
    kv = mm(c, lp["kvb"]["w"]).reshape(S, H, nope + dv).transpose(1, 0, 2)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r[None], (H, S, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
    kc, vc = cast(k), cast(kv[..., nope:])
    Q = min(S, QUERIES)

    def block(i):
        t = i * Q + jnp.arange(Q)
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q, Q, axis=1)
        s_ = jnp.einsum("hqd,hkd->hqk", cast(qb), kc,
                        precision=HI) / math.sqrt(nope + rope)
        ok = jnp.arange(S)[None] <= t[:, None]
        a = jax.nn.softmax(jnp.where(ok[None], s_, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", cast(a), vc, precision=HI)

    o = jax.lax.map(block, jnp.arange(S // Q))               # (n, H, Q, dv)
    return mm(o.transpose(0, 2, 1, 3).reshape(S, H * dv), lp["o"]["w"])


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


@functools.partial(jax.jit, static_argnames=("control",))
def _dense_ffn(x, lp, control):
    lp = _f32(lp)
    return _swiglu(x, lp["gate"]["w"], lp["up"]["w"], lp["down"]["w"],
                   lower_precision(control))


def routed_ffn(x, p, sizes, control=None):
    """The routed feed-forward plus the shared expert on ``x`` (S, hidden)
    float32; ``p`` the layer's ``moe`` entry in ``param_dtype``; ``sizes``
    hashable (:class:`_static`)."""
    weight = _expert_weights(x, p["router"]["w"], p["bias"], sizes, control)
    y = _dense_ffn(x, p["shared"], control)
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


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _mixer_layer(h, lp, sizes, control):
    lp = _f32(lp)
    x = _rms(h, lp["ln1"]["scale"], sizes["rms_norm_eps"])
    h = h + mla(x, lp, sizes, lower_precision(control))
    return h, _rms(h, lp["ln2"]["scale"], sizes["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(h, final_ln, w, eps, control):
    cast = lower_precision(control)
    return jnp.dot(cast(_rms(h, final_ln["scale"].astype(F32), eps)),
                   cast(w.astype(F32)), precision=HI)


SHAPE_KEYS = ("num_attention_heads", "hidden_size", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "rms_norm_eps", "rope_theta", "num_experts_per_tok",
              "routed_scaling_factor")


def shape_of(sizes):
    return _static({k: sizes[k] for k in SHAPE_KEYS})


def logits(params, sizes, ids, rows, control=None):
    """float32 logits of one sequence ``ids`` (1-D) at positions ``rows``: a
    full causal forward layer by layer, the head on those rows only. A
    sequence longer than ``QUERIES`` is padded on the right to a multiple
    (never seen: causal)."""
    if len(ids) > QUERIES:
        ids = np.pad(np.asarray(ids), (0, -len(ids) % QUERIES))
    ids = jnp.asarray(ids, jnp.int32)
    shape = shape_of(sizes)
    h = params["embed"]["tok"][ids].astype(F32)
    for lp, feed in zip(params["layers"], layer_kinds(sizes)):
        mixer = {k: v for k, v in lp.items()
                 if k not in ("moe", "gate", "up", "down")}
        h, x = _mixer_layer(h, mixer, shape, control)
        if feed == "moe":
            h = h + routed_ffn(x, lp["moe"], shape, control)
        else:
            h = h + _dense_ffn(x, {k: lp[k] for k in ("gate", "up", "down")},
                               control)
    return _head(h[jnp.asarray(rows)], params["final_ln"],
                 params["lm_head"]["w"], sizes["rms_norm_eps"], control)


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
