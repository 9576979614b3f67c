"""Plain reference for Nemotron 3 Super's language model
(``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16`` ``config.json``,
``model_type`` ``nemotron_h``): a pre-norm decoder whose every published
layer is ONE of three kinds under ONE RMSNorm, ``x <- x + F_i(RMSNorm_i(x))``,
the kind named by a character of ``hybrid_override_pattern``. float32
``jax.numpy`` at ``HIGHEST`` matmul precision, no cache, no kernels, no
chunking, no batching, one sequence at a time. Imports nothing of the program
under test. No biases but the convolution's; eps ``layer_norm_epsilon``.

* ``M``, Mamba-2 (``H = mamba_num_heads`` heads of ``P = mamba_head_dim``,
  state ``N = ssm_state_size``, ``G = n_groups``, ``K = conv_kernel``):
  ``[z (H P) | xBC (H P + 2 G N) | dt (H)] = W_in x``; ``xBC' = silu(conv(xBC)
  + b)``, depthwise, causal, ``K`` taps a channel, zeros before the start;
  ``[u | B | C] = xBC'``; a head, a token at a time (the PLAIN RECURRENCE, a
  ``lax.scan`` over the sequence): ``d_t = softplus(dt_t + dt_bias_h)``,
  ``a_t = exp(d_t A_h)``, ``A_h = -exp(A_log_h)``, ``S_t = a_t S_(t-1) + d_t
  u_t B_t^T`` on ``S`` (P x N), ``B``, ``C`` of group ``h // (H / G)``; ``y_t
  = S_t C_t + D_h u_t``; out ``W_out(GroupRMSNorm(y * silu(z)))``: the gate
  BEFORE the norm, the norm over each of the ``G`` groups of ``H P / G``
  channels with a learned scale.
* ``*``, attention: ``q = W_q x`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k = W_k x``, ``v = W_v x`` as ``num_key_value_heads``;
  NO rotation and NO q/k norm; causal softmax, scores over ``sqrt(head_dim)``,
  KV head ``h // (heads / kv heads)`` serves query head ``h``; ``y = W_o o``.
* ``E``, routed feed-forward in a latent: ``s = sigmoid(W_r x)`` over
  ``published.n_routed_experts``; the ``num_experts_per_tok`` best by ``s +
  b`` are chosen (one group); weights ``routed_scaling_factor x s_e / sum of
  the chosen s_e``; ``l = W_dn x`` (``moe_latent_size``); ``E_e(l) = W2_e
  relu(W1_e l)^2``; ``y = W_up(sum_e w_e E_e(l)) + W2_s relu(W1_s x)^2``.
  **The share**: only experts ``experts_held[0] .. experts_held[1] - 1``
  exist here; routing and the weights' normalisation run over ALL experts,
  the held ones' outputs are added up, then ``W_up``; what the others would
  add is left out.
* the final RMSNorm, then the untied head over the vocabulary slice.

The parameters are the pytree the program's decoder takes: a mixer layer
(``*`` or ``M``) and the ``E`` layer that follows it are ONE entry of
``layers`` (``ln1`` and the mixer's arrays; ``ln2`` and ``moe`` where an ``E``
follows), which is the same mathematics as two layers of one norm each; an
``M`` with no ``E`` after it is an entry without ``ln2``. Weights are made on
the device from the seed, an entry at a time, in ``param_dtype`` (``A_log``,
``dt_bias``, ``D`` and the selection bias stay float32); the reference reads
those same values as float32, ``EXPERTS`` experts at a time.

``cast`` is applied to both operands of every matrix product, the router's,
the recurrence's outer product and its read-out included: the identity for
the reference, a scaled round trip through a lower precision for the control
that the comparison must reject.
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
QUERIES = 512          # queries of the attention layer attended at once
EXPERTS = 16           # experts upcast and multiplied at a time
PAD = 512              # a sequence is padded to a multiple (fewer programs)
MIXERS = {"*": "attention", "M": "mamba"}


def block_layers(pattern):
    """``[(mixer, routed)]``: the pattern's published layers as the entries
    of ``layers``, a mixer (``*`` | ``M``) with the ``E`` that follows it,
    if one does. An ``E`` with no mixer before it has no entry to join."""
    out = []
    for j, ch in enumerate(pattern):
        if ch in MIXERS:
            out.append([MIXERS[ch], False])
        elif ch == "E" and out and not out[-1][1]:
            out[-1][1] = True
        else:
            raise ValueError(f"pattern {pattern!r}: layer {j} ({ch!r}) is "
                             "no mixer and follows none")
    return [tuple(e) for e in out]


def mamba_dims(sizes):
    H, P = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    return H, P, sizes["ssm_state_size"], sizes["n_groups"]


def make_weights(sizes, seed):
    D, A = sizes["hidden_size"], sizes["num_attention_heads"]
    Akv, hd = sizes["num_key_value_heads"], sizes["head_dim"]
    H, P, N, G = mamba_dims(sizes)
    K = sizes["conv_kernel"]
    inner, bc = H * P, 2 * G * N
    E = sizes["published"]["n_routed_experts"]
    held = sizes["experts_held"][1] - sizes["experts_held"][0]
    L, F = sizes["moe_latent_size"], sizes["moe_intermediate_size"]
    Fs = sizes["moe_shared_expert_intermediate_size"]
    vocab = sizes["vocab_size"]
    dtype = jnp.dtype(sizes["param_dtype"])
    lo, hi, floor = (sizes["time_step_min"], sizes["time_step_max"],
                     sizes["time_step_floor"])
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
        k = jax.random.split(k, 6)
        if kind == "attention":
            return {"q": glorot(k[0], D, A * hd), "k": glorot(k[1], D, Akv * hd),
                    "v": glorot(k[2], D, Akv * hd), "o": glorot(k[3], A * hd, D)}
        # the family's initialisation: A in [1, 16]; the step log-uniform in
        # [time_step_min, time_step_max], floored, kept as its inverse
        # softplus; D = 1
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            k[4], (H,), F32, math.log(lo), math.log(hi))), floor)
        return {"in": glorot(k[0], D, 2 * inner + bc + H),
                "conv": {"w": normal(k[1], (K, inner + bc), K ** -0.5),
                         "b": normal(k[2], (inner + bc,), 0.1)},
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "a_log": jnp.log(jax.random.uniform(k[5], (H,), F32, 1.0,
                                                    16.0)),
                "d": jnp.ones(H, F32),
                "o_norm": ones(inner), "o": glorot(k[3], inner, D)}

    def routed(k):
        k = jax.random.split(k, 8)
        s = (2.0 / (L + F)) ** 0.5
        return {"ln2": ones(D), "moe": {
            "router": glorot(k[0], D, E),
            "bias": 0.01 * jax.random.normal(k[1], (E,), F32),
            "to_latent": glorot(k[2], D, L),
            "from_latent": glorot(k[3], L, D),
            "experts": {"up": normal(k[4], (held, L, F), s),
                        "down": normal(k[5], (held, F, L), s)},
            "shared": {"up": glorot(k[6], D, Fs),
                       "down": glorot(k[7], Fs, D)}}}

    @functools.partial(jax.jit, static_argnames=("kind", "feed"))
    def layer(k, kind, feed):
        km, kf = jax.random.split(k)
        return dict({"ln1": ones(D)}, **mixer(km, kind),
                    **(routed(kf) if feed else {}))

    @jax.jit
    def ends(k):
        k = jax.random.split(k, 2)
        return (normal(k[0], (vocab, D), 0.02), normal(k[1], (D, vocab), 0.02))

    kinds = block_layers(sizes["hybrid_override_pattern"])
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


def mamba(x, lp, sizes, cast):
    """The Mamba-2 layer on one sequence ``x`` (S, hidden): the recurrence a
    token at a time."""
    H, P, N, G = mamba_dims(sizes)
    K, eps = sizes["conv_kernel"], sizes["layer_norm_epsilon"]
    S = x.shape[0]
    inner = H * P

    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)

    zxd = mm(x, lp["in"]["w"])
    z, pre, dt = (zxd[:, :inner], zxd[:, inner:zxd.shape[1] - H],
                  zxd[:, zxd.shape[1] - H:])
    pre = jnp.pad(pre, ((K - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(pre[j:j + S] * lp["conv"]["w"][j]
                            for j in range(K)) + lp["conv"]["b"])
    u = mixed[:, :inner].reshape(S, H, P)
    b = jnp.repeat(mixed[:, inner:inner + G * N].reshape(S, G, N), H // G,
                   axis=1)                                      # (S, H, N)
    c = jnp.repeat(mixed[:, inner + G * N:].reshape(S, G, N), H // G, axis=1)
    d = jax.nn.softplus(dt + lp["dt_bias"])                     # (S, H)
    a = jnp.exp(d * -jnp.exp(lp["a_log"]))

    def step(state, t):
        a_t, du_t, b_t, c_t = t
        state = (a_t[:, None, None] * state
                 + cast(du_t)[:, :, None] * cast(b_t)[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", cast(state), cast(c_t),
                                 precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32),
                        (a, u * d[..., None], b, c))
    y = (y + lp["d"][None, :, None] * u).reshape(S, inner) * jax.nn.silu(z)
    g = y.reshape(S, G, inner // G)
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return mm(g.reshape(S, inner) * lp["o_norm"]["scale"], lp["o"]["w"])


def attention(x, lp, sizes, cast):
    """Grouped-query attention without positions on one sequence, a block of
    queries at a time."""
    H, Hkv, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    S = x.shape[0]

    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)

    def heads(t, n):
        return t.reshape(S, n, d).transpose(1, 0, 2)

    q = heads(mm(x, lp["q"]["w"]), H).reshape(Hkv, H // Hkv, S, d)
    kc = cast(heads(mm(x, lp["k"]["w"]), Hkv))
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


def _relu2(x, up, down, cast):
    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)
    return mm(jnp.square(jax.nn.relu(mm(x, up))), down)


@functools.partial(jax.jit, static_argnames=("control",))
def _experts_block(latent, weight, up, down, control):
    """``sum_e weight[:, e] * E_e(latent)`` over one block of experts: every
    expert on every token, masked by its weight (0 where not chosen)."""
    cast = lower_precision(control)

    def one(y, e):
        w1, w2, w = e
        return y + w[:, None] * _relu2(latent, w1.astype(F32),
                                       w2.astype(F32), cast), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(latent), (up, down, weight.T))
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
def _project(x, w, control):
    cast = lower_precision(control)
    return jnp.dot(cast(x), cast(w.astype(F32)), precision=HI)


@functools.partial(jax.jit, static_argnames=("control",))
def _shared_expert(x, sh, control):
    sh = _f32(sh)
    return _relu2(x, sh["up"]["w"], sh["down"]["w"], lower_precision(control))


def routed_part(x, p, sizes, control=None):
    """The held experts' part of the layer, back in the model's width:
    ``W_up(sum over held e of w_e E_e(W_dn x))``."""
    weight = _held_weights(x, p["router"]["w"], p["bias"], sizes, control)
    latent = _project(x, p["to_latent"]["w"], control)
    y = jnp.zeros_like(latent)
    for lo in range(0, weight.shape[1], EXPERTS):
        y = y + _experts_block(latent, weight[:, lo:lo + EXPERTS],
                               p["experts"]["up"][lo:lo + EXPERTS],
                               p["experts"]["down"][lo:lo + EXPERTS], control)
    return _project(y, p["from_latent"]["w"], control)


def routed_ffn(x, p, sizes, control=None):
    """The held experts' part plus the shared expert, on ``x`` (S, hidden)
    float32; ``p`` the entry's ``moe`` in ``param_dtype``; ``sizes`` hashable
    (:class:`_static`)."""
    return (routed_part(x, p, sizes, control)
            + _shared_expert(x, p["shared"], control))


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
    x = _rms(h, lp["ln1"]["scale"], sizes["layer_norm_epsilon"])
    return h + (mamba if kind == "mamba" else attention)(x, lp, sizes, cast)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(h, ln, eps):
    return _rms(h, ln["scale"].astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(h, final_ln, w, eps, control):
    cast = lower_precision(control)
    return jnp.dot(cast(_rms(h, final_ln["scale"].astype(F32), eps)),
                   cast(w.astype(F32)), precision=HI)


SHAPE_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
              "hidden_size", "mamba_num_heads", "mamba_head_dim",
              "ssm_state_size", "n_groups", "conv_kernel",
              "layer_norm_epsilon", "num_experts_per_tok",
              "routed_scaling_factor", "experts_held")


def shape_of(sizes):
    return _static({k: sizes[k] for k in SHAPE_KEYS})


def logits(params, sizes, ids, rows, control=None):
    """float32 logits of one sequence ``ids`` (1-D) at positions ``rows``: a
    full causal forward entry by entry, the head on those rows only. A
    sequence longer than ``QUERIES`` is padded on the right to a multiple
    (never seen: causal)."""
    if len(ids) > QUERIES:
        ids = np.pad(np.asarray(ids), (0, -len(ids) % QUERIES))
    ids = jnp.asarray(ids, jnp.int32)
    shape = shape_of(sizes)
    eps = sizes["layer_norm_epsilon"]
    h = params["embed"]["tok"][ids].astype(F32)
    kinds = block_layers(sizes["hybrid_override_pattern"])
    for lp, (kind, feed) in zip(params["layers"], kinds):
        mixer = {k: v for k, v in lp.items() if k not in ("moe", "ln2")}
        h = _mixer_layer(h, mixer, kind, shape, control)
        if feed:
            h = h + routed_ffn(_norm(h, lp["ln2"], eps), lp["moe"], shape,
                               control)
    return _head(h[jnp.asarray(rows)], params["final_ln"],
                 params["lm_head"]["w"], eps, control)


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
