"""Plain reference for Ling-3.0-flash's language model
(``inclusionAI/Ling-3.0-flash-VL`` ``config.json``): a pre-norm decoder whose
layers mix tokens by KDA (a delta-rule linear attention with a per-channel
decay gate and short convolutions) or by multi-head latent attention (MLA),
under a dense SwiGLU (the leading layers) or routed experts with one shared
expert. float32 ``jax.numpy`` at ``HIGHEST`` matmul precision, no cache, no
kernels, no chunking of the recurrence, no batching, one sequence at a time.
Imports nothing of the program under test.

``x = RMSNorm(h)`` (eps ``rms_norm_eps``), no biases. A held layer's kind is
read from its PUBLISHED index ``i`` (``layers_held``): MLA where ``(i + 1) %
layer_group_size == 0``, else KDA; the first ``first_k_dense_replace`` held
layers are dense, the rest routed.

* KDA (``H`` heads of ``d``): ``q~, k~, v~ = W_q x, W_k x, W_v x``; a causal
  depthwise convolution of ``short_conv_kernel_size`` taps over each, then
  SiLU; ``q = l2norm(q') / sqrt(d)``, ``k = l2norm(k')`` a head; ``g_t =
  kda_lower_bound * sigmoid(exp(A_h) (W_f x_t + b))`` a channel, ``beta_t =
  sigmoid(W_b x_t)`` a head; ``S_t = (I - beta_t k_t k_t^T) diag(exp(g_t))
  S_(t-1) + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``: a ``lax.scan`` over
  positions. ``y = W_o(RMSNorm_d(o) * sigmoid(W_z x))``, one gate a head.
* MLA: ``q = W_q x`` as ``H`` heads of ``nope + rope``; ``[c, k_r] = W_kva
  x``, ``c = RMSNorm(c)``; rotate-half RoPE on each head's ``q_r`` and on the
  shared ``k_r``; ``[k_n, v] = W_kvb c`` a head; scores ``(q_n . k_n + q_r .
  k_r) / sqrt(nope + rope)``, causal softmax; ``y = W_o(o * sigmoid(W_z
  x))``. Expanded: K and V of every position are built.
* routed feed-forward: ``s = sigmoid(W_r x)`` over all ``published
  num_experts``; selection by ``s + b``: groups of equal size score the sum
  of their two best, the ``topk_group`` best groups stay, the
  ``num_experts_per_tok`` best experts among them are chosen; weights ``s``
  (without ``b``) over their sum times ``routed_scaling_factor``. **The
  share**: only experts ``experts_held[0] .. experts_held[1] - 1`` exist
  here; the sum runs over the chosen experts that are held (a loop over the
  held experts with a mask), what the others would add is left out, the
  normalisation stays over all chosen. Plus the shared expert. ``E(x) = W_d
  (silu(W_g x) * W_u x)``; an expert's gate and up projections are stored
  side by side (``gate_up``: ``[W_g, W_u]``).

Weights are made on the device from the seed, a layer at a time, in the
pytree the program's decoder takes and in ``param_dtype``; the reference
reads those same values as float32, a layer and ``EXPERTS`` experts at a
time (the share is 20.7 GB in float32).

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
QUERIES = 512          # queries of an MLA layer attended at once
EXPERTS = 16           # experts upcast and multiplied at a time
PAD = 1024             # a sequence is padded to a multiple (fewer programs)


def layer_kinds(sizes):
    """``[(mixer, feed-forward)]`` of the layers held."""
    return [("mla" if (i + 1) % sizes["layer_group_size"] == 0 else "kda",
             "dense" if j < sizes["first_k_dense_replace"] else "moe")
            for j, i in enumerate(sizes["layers_held"])]


def make_weights(sizes, seed):
    D, H, d = (sizes["hidden_size"], sizes["num_attention_heads"],
               sizes["head_dim"])
    nope, rope, dv, latent = (sizes["qk_nope_head_dim"],
                              sizes["qk_rope_head_dim"], sizes["v_head_dim"],
                              sizes["kv_lora_rank"])
    K = sizes["short_conv_kernel_size"]
    F, Fs = (sizes["moe_intermediate_size"],
             sizes["moe_shared_expert_intermediate_size"])
    E_all = sizes["published"]["num_experts"]
    held = sizes["experts_held"][1] - sizes["experts_held"][0]
    vocab = sizes["vocab_size"]
    dtype = jnp.dtype(sizes["param_dtype"])
    # the device's own bit generator: 5.2B normals from threefry are most of
    # a run's set-up on the chip
    key = jax.random.key(
        int(np.random.SeedSequence(seed).generate_state(1)[0]), impl="rbg")

    def normal(k, shape, scale):
        return (scale * jax.random.normal(k, shape, F32)).astype(dtype)

    def glorot(k, din, dout):
        return {"w": normal(k, (din, dout), (2.0 / (din + dout)) ** 0.5)}

    def ones(n):
        return {"scale": jnp.ones(n, dtype)}

    def mixer(k, kind):
        k = jax.random.split(k, 12)
        if kind == "mla":
            return {"q": glorot(k[0], D, H * (nope + rope)),
                    "kva": glorot(k[1], D, latent + rope),
                    "c_norm": ones(latent),
                    "kvb": glorot(k[2], latent, H * (nope + dv)),
                    "z": glorot(k[3], D, H), "o": glorot(k[4], H * dv, D)}
        return {"q": glorot(k[0], D, H * d), "k": glorot(k[1], D, H * d),
                "v": glorot(k[2], D, H * d), "f": glorot(k[3], D, H * d),
                "b": glorot(k[4], D, H), "z": glorot(k[5], D, H),
                "o": glorot(k[6], H * d, D),
                "dt_bias": normal(k[7], (H * d,), 0.5),
                "a_log": normal(k[8], (H,), 0.5),
                "conv": {n: normal(kk, (K, H * d), K ** -0.5)
                         for n, kk in zip("qkv", k[9:12])},
                "o_norm": ones(d)}

    def ffn(k, kind):
        k = jax.random.split(k, 8)
        if kind == "dense":
            ff = sizes["intermediate_size"]
            return {"gate": glorot(k[0], D, ff), "up": glorot(k[1], D, ff),
                    "down": glorot(k[2], ff, D)}
        s = (2.0 / (D + F)) ** 0.5
        return {"moe": {
            "router": glorot(k[0], D, E_all),
            "bias": 0.01 * jax.random.normal(k[1], (E_all,), F32),
            "experts": {"gate_up": normal(k[2], (held, D, 2 * F), s),
                        "down": normal(k[3], (held, F, D), s)},
            "shared": {"gate": glorot(k[4], D, Fs), "up": glorot(k[5], D, Fs),
                       "down": glorot(k[6], Fs, D)}}}

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


def _l2norm(x, eps=1e-6):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _rope(t, theta):
    """Rotate-half rotary embedding of ``t`` (.., S, d) at positions 0..S-1."""
    S, d = t.shape[-2:]
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = t[..., :d // 2], t[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _head_gate(x, o, lp, mm):
    """``W_o(o * sigmoid(W_z x))``, ``o`` (S, H, dv), one gate a head."""
    S = x.shape[0]
    gate = jax.nn.sigmoid(mm(x, lp["z"]["w"]))               # (S, H)
    return mm((o * gate[..., None]).reshape(S, -1), lp["o"]["w"])


def kda(x, lp, sizes, cast):
    """The KDA mixer on one sequence ``x`` (S, hidden), token by token."""
    H, d = sizes["num_attention_heads"], sizes["head_dim"]
    K = sizes["short_conv_kernel_size"]
    S = x.shape[0]

    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)

    def conv(name):
        pre = jnp.pad(mm(x, lp[name]["w"]), ((K - 1, 0), (0, 0)))
        taps = lp["conv"][name]
        return jax.nn.silu(sum(pre[j:j + S] * taps[j]
                               for j in range(K))).reshape(S, H, d)

    q = _l2norm(conv("q")) / math.sqrt(d)
    k = _l2norm(conv("k"))
    v = conv("v")
    g = sizes["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(lp["a_log"])[None, :, None]
        * (mm(x, lp["f"]["w"]) + lp["dt_bias"]).reshape(S, H, d))
    beta = jax.nn.sigmoid(mm(x, lp["b"]["w"]))               # (S, H)

    def step(state, t):
        qt, kt, vt, gt, bt = t
        state = jnp.exp(gt)[:, :, None] * state              # (H, d, d)
        u = vt - jnp.einsum("hd,hde->he", cast(kt), cast(state),
                            precision=HI)
        state = state + jnp.einsum("hd,he->hde", cast(bt[:, None] * kt),
                                   cast(u), precision=HI)
        return state, jnp.einsum("hd,hde->he", cast(qt), cast(state),
                                 precision=HI)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), F32),
                        (q, k, v, g, beta))                  # (S, H, d)
    o = _rms(o, lp["o_norm"]["scale"], sizes["rms_norm_eps"])
    return _head_gate(x, o, lp, mm)


def mla(x, lp, sizes, cast):
    """The MLA mixer on one sequence, expanded, a block of queries at a
    time."""
    H = sizes["num_attention_heads"]
    nope, rope, dv, latent = (sizes["qk_nope_head_dim"],
                              sizes["qk_rope_head_dim"], sizes["v_head_dim"],
                              sizes["kv_lora_rank"])
    S = x.shape[0]

    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)

    q = mm(x, lp["q"]["w"]).reshape(S, H, nope + rope).transpose(1, 0, 2)
    ckr = mm(x, lp["kva"]["w"])
    c = _rms(ckr[:, :latent], lp["c_norm"]["scale"], sizes["rms_norm_eps"])
    k_r = _rope(ckr[:, latent:], sizes["rope_theta"])        # (S, rope)
    kv = mm(c, lp["kvb"]["w"]).reshape(S, H, nope + dv).transpose(1, 0, 2)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r[None], (H, S, rope))], axis=-1)
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], sizes["rope_theta"])], axis=-1)
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
    return _head_gate(x, o.transpose(0, 2, 1, 3).reshape(S, H, dv), lp, mm)


def route(x, router_w, bias, sizes, cast):
    """``(chosen (S, k) expert ids over ALL experts, weights (S, k))``."""
    s = jax.nn.sigmoid(jnp.dot(cast(x), cast(router_w), precision=HI))
    S, E = s.shape
    G, kept, k = (sizes["n_group"], sizes["topk_group"],
                  sizes["num_experts_per_tok"])
    sel = s + bias
    per = E // G
    best2 = jax.lax.top_k(sel.reshape(S, G, per), min(2, per))[0].sum(-1)
    _, groups = jax.lax.top_k(best2, kept)
    stay = (groups[:, :, None] == jnp.arange(G)[None, None]).any(axis=1)
    _, chosen = jax.lax.top_k(
        jnp.where(jnp.repeat(stay, per, axis=1), sel, -jnp.inf), k)
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
def _held_weights(x, router_w, bias, sizes, control):
    """(S, held): a held expert's weight for each token, 0 where the token
    did not choose it."""
    first, end = sizes["experts_held"]
    chosen, w = route(x, router_w.astype(F32), bias.astype(F32), sizes,
                      lower_precision(control))
    return jnp.where(
        chosen[:, :, None] == (first + jnp.arange(end - first))[None, None],
        w[:, :, None], 0.0).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("control",))
def _shared_expert(x, sh, control):
    sh = _f32(sh)
    return _swiglu(x, sh["gate"]["w"], sh["up"]["w"], sh["down"]["w"],
                   lower_precision(control))


def routed_ffn(x, p, sizes, control=None):
    """The held experts' part of the routed feed-forward plus the shared
    expert, on ``x`` (S, hidden) float32; ``p`` the layer's ``moe`` entry in
    ``param_dtype``; ``sizes`` hashable (:class:`_static`)."""
    weight = _held_weights(x, p["router"]["w"], p["bias"], sizes, control)
    y = jnp.zeros_like(x)
    for lo in range(0, weight.shape[1], EXPERTS):
        y = y + _experts_block(x, weight[:, lo:lo + EXPERTS],
                               p["experts"]["gate_up"][lo:lo + EXPERTS],
                               p["experts"]["down"][lo:lo + EXPERTS], control)
    return y + _shared_expert(x, p["shared"], control)


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
    x = _rms(h, lp["ln1"]["scale"], sizes["rms_norm_eps"])
    h = h + (mla if kind == "mla" else kda)(x, lp, sizes, cast)
    return h, _rms(h, lp["ln2"]["scale"], sizes["rms_norm_eps"])


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


SHAPE_KEYS = ("num_attention_heads", "head_dim", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
              "short_conv_kernel_size", "kda_lower_bound", "rms_norm_eps",
              "rope_theta", "n_group", "topk_group", "num_experts_per_tok",
              "routed_scaling_factor", "experts_held")


def logits(params, sizes, ids, rows, control=None):
    """float32 logits of one sequence ``ids`` (1-D) at positions ``rows``: a
    full causal forward layer by layer, the head on those rows only. A
    sequence longer than ``QUERIES`` is padded on the right to a multiple
    (never seen: causal)."""
    if len(ids) > QUERIES:
        ids = np.pad(np.asarray(ids), (0, -len(ids) % QUERIES))
    ids = jnp.asarray(ids, jnp.int32)
    shape = _static({k: sizes[k] for k in SHAPE_KEYS})
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
