"""Plain reference for Granite 4.0-H-Small's language model
(``ibm-granite/granite-4.0-h-small`` ``config.json``, ``model_type``
``granitemoehybrid``): a pre-norm decoder whose every layer is a mixer AND a
routed feed-forward beside a shared expert, under muP's four multipliers.
float32 ``jax.numpy`` at ``HIGHEST`` matmul precision, no cache, no kernels,
no chunked scan, no batching, one sequence at a time. Imports nothing of the
program under test. No biases but the convolution's; eps ``rms_norm_eps``.

    h_0 = embedding_multiplier x E[id]
    h  += residual_multiplier x M_i(RMSNorm(h))         (layer_types[i])
    h  += residual_multiplier x (R(n) + Sh(n)),  n = RMSNorm(h)
    logits = E^T RMSNorm(h_L) / logits_scaling          (tied head)

* ``mamba``, Mamba-2 (``H = mamba_n_heads`` heads of ``P = mamba_d_head``,
  state ``N = mamba_d_state``, ``G = mamba_n_groups``, ``K = mamba_d_conv``):
  ``[z (H P) | xBC (H P + 2 G N) | dt (H)] = W_in x``; ``xBC' = silu(conv(xBC)
  + b)``, depthwise, causal, ``K`` taps a channel, zeros before the start;
  ``[u | B | C] = xBC'``; a head, a token at a time (the PLAIN RECURRENCE, a
  ``lax.scan`` over the tokens): ``d_t = softplus(dt_t + dt_bias_h)``, ``a_t
  = exp(d_t A_h)``, ``A_h = -exp(A_log_h)``, ``S_t = a_t S_(t-1) + d_t u_t
  B_t^T`` on ``S`` (P x N), ``B``, ``C`` of group ``h // (H / G)`` (ONE group
  as published), ``y_t = S_t C_t + D_h u_t``; out ``W_out(GroupRMSNorm(y *
  silu(z)))``: the gate BEFORE the norm, the norm over each group's channels
  (all ``H P`` of them at one group) with a learned scale.
* ``attention``: ``q = W_q x`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k``, ``v`` as ``num_key_value_heads``; NO rotation and NO
  q/k norm (``position_embedding_type`` ``nope``); causal softmax of scores
  times ``attention_multiplier`` (1 / head_dim, not its root); KV head ``h //
  (heads / kv heads)`` serves query head ``h``; ``y = W_o o``.
* ``R``, routed: ``l = W_r n`` over ``published.num_local_experts`` logits
  (no bias); the ``num_experts_per_tok`` largest ``l`` are chosen; ``w =
  softmax(l[chosen])``; ``E_e(n) = W_d,e (silu(W_g,e n) * W_u,e n)`` at
  ``intermediate_size``; ``R = sum_e w_e E_e(n)``. ``Sh``: the same form at
  ``shared_intermediate_size``, every token. **The share**: only experts
  ``experts_held[0] .. experts_held[1] - 1`` exist here; routing and the
  softmax run over ALL experts' logits, the held ones' outputs are added up;
  what the others would add is left out.

**In blocks, so that 53k tokens fit and every length runs one set of
programs.** A sequence longer than ``TOKENS`` is padded on the right (causal:
never seen) to whole blocks of ``TOKENS`` tokens and every layer runs a block
at a time with fixed shapes: a Mamba layer carries its convolution's last
``K - 1`` rows and its state from block to block (the same recurrence, token
after token); an attention layer computes every block's keys and values
first, into buffers ``keys`` positions long, then attends ``QUERIES`` queries
at a time over ALL of them under the causal mask (a whole row of scores, a
plain softmax); the feed-forward and the norms are per token. An expert reads
the rows that chose it, gathered ``TOKENS / FEW`` at a time, and not every
row under a weight of 0: the same sum. Products that share an input share
one call (``[W_k | W_v]``, ``[W_g | W_u]``), and the programs are compiled at
once, on a pool of threads (:func:`_programs`): each float32 product at full
precision costs the chip's compiler 5-15 s, most of a first check.

The parameters are the pytree the program's decoder takes (no ``lm_head``:
the head is ``embed.tok``). Weights are made on the device from the seed, a
layer at a time, in ``param_dtype`` (``A_log``, ``dt_bias``, ``D`` stay
float32); the reference reads those same values as float32, ``EXPERTS``
experts at a time.

``cast`` is applied to both operands of every matrix product, the router's,
the recurrence's outer product's and its read-out's ``C`` included; the
STATE stays float32 from token to token, as a quantised product's
accumulator does (rounding 4 MB a token a layer is the whole cost of a 53k
check). The identity for the reference, a scaled round trip through a lower
precision for the control that the comparison must reject.
"""

from concurrent import futures
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references._control import lower_precision

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
TOKENS = 2048          # tokens a block of a long sequence
QUERIES = 128          # queries of the attention layer attended at once
EXPERTS = 12           # experts upcast and multiplied at a time
FEW = 4                # an expert gathers TOKENS / FEW of its rows at a time
ROWS = 512             # rows the head is given at a time
ATTENTION = ("attention", "full_attention")     # published | readers' name
#: the token table's standard deviation. The table is the head too, so a
#: row's own input token meets itself there: under ``embedding_multiplier``
#: 12 its logit stands ``12 s D / |h|`` row deviations above the others
#: (``|h|`` ~ 50 after ten layers of seeded branches: 19 at the customary
#: 0.02), greedy decoding then repeats its last prompt token whatever the
#: precision, and the comparison that decides ``correct`` is blind. At
#: 0.0025 it stands ~2.5: one logit among the others. The artefact is the
#: seed's (a trained table is not orthogonal noise), not the model's.
TABLE_SCALE = 0.0025


def layer_kinds(sizes):
    """``mamba`` | ``attention`` a layer held, from ``layer_types``."""
    kinds = ["attention" if k in ATTENTION else k
             for k in sizes["layer_types"]]
    if set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {sizes['layer_types']}")
    return kinds


def head_dim(sizes):
    return sizes.get("head_dim") or (sizes["hidden_size"]
                                     // sizes["num_attention_heads"])


def mamba_dims(sizes):
    return (sizes["mamba_n_heads"], sizes["mamba_d_head"],
            sizes["mamba_d_state"], sizes["mamba_n_groups"])


def make_weights(sizes, seed):
    D, A = sizes["hidden_size"], sizes["num_attention_heads"]
    Akv, hd = sizes["num_key_value_heads"], head_dim(sizes)
    H, P, N, G = mamba_dims(sizes)
    K = sizes["mamba_d_conv"]
    inner, bc = H * P, 2 * G * N
    E = sizes["published"]["num_local_experts"]
    held = sizes["experts_held"][1] - sizes["experts_held"][0]
    F, Fs = sizes["intermediate_size"], sizes["shared_intermediate_size"]
    vocab = sizes["vocab_size"]
    dtype = jnp.dtype(sizes["param_dtype"])
    if inner != sizes["mamba_expand"] * D or not sizes["tie_word_embeddings"]:
        raise ValueError("mamba_expand x hidden_size is the heads' channels, "
                         "and the head is the token table")
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
        # [1e-3, 1e-1], kept as its inverse softplus; D = 1
        step = jnp.exp(jax.random.uniform(k[4], (H,), F32, math.log(1e-3),
                                          math.log(1e-1)))
        return {"in": glorot(k[0], D, 2 * inner + bc + H),
                "conv": {"w": normal(k[1], (K, inner + bc), K ** -0.5),
                         "b": normal(k[2], (inner + bc,), 0.1)},
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "a_log": jnp.log(jax.random.uniform(k[5], (H,), F32, 1.0,
                                                    16.0)),
                "d": jnp.ones(H, F32),
                "o_norm": ones(inner), "o": glorot(k[3], inner, D)}

    def routed(k):
        k = jax.random.split(k, 6)
        s = (2.0 / (D + F)) ** 0.5
        return {"router": glorot(k[0], D, E),
                "experts": {"gate_up": normal(k[1], (held, D, 2 * F), s),
                            "down": normal(k[2], (held, F, D), s)},
                "shared": {"gate": glorot(k[3], D, Fs),
                           "up": glorot(k[4], D, Fs),
                           "down": glorot(k[5], Fs, D)}}

    @functools.partial(jax.jit, static_argnames=("kind",))
    def layer(k, kind):
        km, kf = jax.random.split(k)
        return dict({"ln1": ones(D), "ln2": ones(D), "moe": routed(kf)},
                    **mixer(km, kind))

    kinds = layer_kinds(sizes)
    keys = jax.random.split(key, 1 + len(kinds))
    out = {"embed": {"tok": jax.jit(normal, static_argnums=(1, 2))(
               keys[0], (vocab, D), TABLE_SCALE)},
           "final_ln": ones(D),
           "layers": [layer(k, kind) for k, kind in zip(keys[1:], kinds)]}
    return jax.block_until_ready(out)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def recurrence_step(state, a_t, du_t, b_t, c_t):
    """One token of the recurrence on ``state`` (H, P, N): decays ``a_t``
    (H,), inputs times steps ``du_t`` (H, P), ``b_t``, ``c_t`` (H, N) the
    heads' ``B`` and ``C``. Returns ``(S_t, y_t = S_t C_t (H, P))``."""
    state = (a_t[:, None, None] * state
             + du_t[:, :, None] * b_t[:, None, :])
    return state, jnp.einsum("hpn,hn->hp", state, c_t, precision=HI)


def mamba(x, lp, carry, sizes, cast):
    """The Mamba-2 layer on a block ``x`` (S, hidden) continuing ``carry =
    (the K - 1 pre-convolution rows before it, the state (H, P, N))``: the
    recurrence a token at a time. Returns ``(y (S, hidden), carry after)``."""
    H, P, N, G = mamba_dims(sizes)
    K, eps = sizes["mamba_d_conv"], sizes["rms_norm_eps"]
    S = x.shape[0]
    inner = H * P
    tail, state = carry

    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)

    zxd = mm(x, lp["in"]["w"])
    z, pre, dt = (zxd[:, :inner], zxd[:, inner:zxd.shape[1] - H],
                  zxd[:, zxd.shape[1] - H:])
    pre = jnp.concatenate([tail, pre])
    mixed = jax.nn.silu(sum(pre[j:j + S] * lp["conv"]["w"][j]
                            for j in range(K)) + lp["conv"]["b"])
    u = mixed[:, :inner].reshape(S, H, P)
    b = jnp.repeat(mixed[:, inner:inner + G * N].reshape(S, G, N), H // G,
                   axis=1)                                      # (S, H, N)
    c = jnp.repeat(mixed[:, inner + G * N:].reshape(S, G, N), H // G, axis=1)
    d = jax.nn.softplus(dt + lp["dt_bias"])                     # (S, H)
    a = jnp.exp(d * -jnp.exp(lp["a_log"]))
    state, y = jax.lax.scan(lambda s, t: recurrence_step(s, *t), state,
                            (a, cast(u * d[..., None]), cast(b), cast(c)))
    y = (y + lp["d"][None, :, None] * u).reshape(S, inner) * jax.nn.silu(z)
    g = y.reshape(S, G, inner // G)
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (mm(g.reshape(S, inner) * lp["o_norm"]["scale"], lp["o"]["w"]),
            (pre[S:], state))


def keys_values(x, lp, sizes, cast):
    """``(K, V)`` (Hkv, S, d) of a block ``x`` (S, hidden), as the products
    read them (``cast`` applied); ``W_k`` and ``W_v`` side by side are one
    product (a float32 product at full precision is what compiles
    slowly)."""
    Hkv, d = sizes["num_key_value_heads"], sizes["head_dim"]
    kv = jnp.dot(cast(x), cast(jnp.concatenate(
        [lp["k"]["w"], lp["v"]["w"]], axis=1)), precision=HI)
    kv = cast(kv.reshape(x.shape[0], 2, Hkv, d).transpose(1, 2, 0, 3))
    return kv[0], kv[1]


def attention(x, lp, kc, vc, start, sizes, cast):
    """Grouped-query attention without positions of a block ``x`` (S,
    hidden) whose first token is at ``start``, over the keys and values
    ``kc``, ``vc`` (Hkv, L, d) of the whole sequence, ``QUERIES`` queries at
    a time; key ``l`` is seen by the queries at or after position ``l``."""
    H, Hkv, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    S, L = x.shape[0], kc.shape[1]

    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)

    q = mm(x, lp["q"]["w"]).reshape(S, H, d).transpose(1, 0, 2).reshape(
        Hkv, H // Hkv, S, d)
    Q = S if S % QUERIES else QUERIES   # a short sequence: every query

    def block(i):
        t = start + i * Q + jnp.arange(Q)
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q, Q, axis=2)
        s_ = jnp.einsum("gjqd,gkd->gjqk", cast(qb), kc,
                        precision=HI) * sizes["attention_multiplier"]
        ok = jnp.arange(L)[None] <= t[:, None]
        a = jax.nn.softmax(jnp.where(ok[None, None], s_, -jnp.inf), axis=-1)
        return jnp.einsum("gjqk,gkd->gjqd", cast(a), vc, precision=HI)

    o = jax.lax.map(block, jnp.arange(S // Q))          # (n, Hkv, G, Q, d)
    o = o.transpose(0, 3, 1, 2, 4).reshape(S, H * d)
    return mm(o, lp["o"]["w"])


def route(x, router_w, sizes, cast):
    """``(chosen (S, k) expert ids, weights (S, k))``: the largest logits,
    a softmax over them alone."""
    logit = jnp.dot(cast(x), cast(router_w), precision=HI)
    top, chosen = jax.lax.top_k(logit, sizes["num_experts_per_tok"])
    return chosen, jax.nn.softmax(top, axis=-1)


def _swiglu(x, gate_up, down, cast):
    """``(silu(x W_g) * x W_u) W_d`` with ``[W_g | W_u]`` side by side, one
    product for both."""
    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)
    h = mm(x, gate_up)
    F = down.shape[0]
    return mm(jax.nn.silu(h[:, :F]) * h[:, F:], down)


@functools.partial(jax.jit, static_argnames=("control",))
def _experts_block(x, weight, gate_up, down, control):
    """``sum_e weight[:, e] * E_e(x)`` over one block of experts. An expert
    reads the rows that chose it, gathered ``TOKENS / FEW`` at a time (the
    rows that chose it first, then as many pieces as they fill: a piece's
    other rows weigh 0), not every row of a long block under a weight of 0:
    the same sum, a seventh of the products at 10 of 72."""
    cast = lower_precision(control)
    T = x.shape[0]
    cap = T if T < TOKENS else T // FEW

    def one(y, e):
        gu, dn, w = e
        gu, dn = gu.astype(F32), dn.astype(F32)
        order = jnp.argsort(w == 0, stable=True)    # its rows first

        def piece(i, y):
            at = jax.lax.dynamic_slice_in_dim(order, i * cap, cap)
            return y.at[at].add(w[at][:, None] * _swiglu(x[at], gu, dn, cast))

        pieces = (jnp.sum(w != 0) + cap - 1) // cap
        return jax.lax.fori_loop(0, pieces, piece, y), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (gate_up, down, weight.T))
    return y


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _held_weights(x, router_w, sizes, control):
    """(S, held): a held expert's weight for each token, 0 where the token
    did not choose it."""
    first, end = sizes["experts_held"]
    chosen, w = route(x, router_w.astype(F32), sizes,
                      lower_precision(control))
    return jnp.where(
        chosen[:, :, None] == (first + jnp.arange(end - first))[None, None],
        w[:, :, None], 0.0).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("control",))
def _shared_expert(x, sh, control):
    sh = _f32(sh)
    return _swiglu(x, jnp.concatenate([sh["gate"]["w"], sh["up"]["w"]],
                                      axis=1), sh["down"]["w"],
                   lower_precision(control))


def routed_part(x, p, sizes, control=None, run=None):
    """The held experts' part of the layer on ``x`` (S, hidden) float32;
    ``run``: the layer programs (:func:`_programs`; default: the jitted
    functions themselves)."""
    run = run or _jitted(sizes, control)
    weight = run["weights"](x, p["router"]["w"])
    y = jnp.zeros_like(x)
    for lo in range(0, weight.shape[1], EXPERTS):
        y = y + run["experts"](x, weight[:, lo:lo + EXPERTS],
                               p["experts"]["gate_up"][lo:lo + EXPERTS],
                               p["experts"]["down"][lo:lo + EXPERTS])
    return y


def routed_ffn(x, p, sizes, control=None, run=None):
    """The held experts' part plus the shared expert; ``p`` the layer's
    ``moe`` in ``param_dtype``; ``sizes`` hashable (:class:`_static`)."""
    run = run or _jitted(sizes, control)
    return routed_part(x, p, sizes, control, run) + run["shared"](x,
                                                                  p["shared"])


class _static(dict):
    """A configuration's sizes as a jitted function's static argument."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))

    def __eq__(self, other):
        return json.dumps(self, sort_keys=True) == json.dumps(
            other, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _mamba_block(h, lp, carry, sizes, control):
    """``(h after the mixer, the feed-forward's normed input, carry)``."""
    lp = _f32(lp)
    eps, rm = sizes["rms_norm_eps"], sizes["residual_multiplier"]
    y, carry = mamba(_rms(h, lp["ln1"]["scale"], eps), lp, carry, sizes,
                     lower_precision(control))
    h = h + rm * y
    return h, _rms(h, lp["ln2"]["scale"], eps), carry


@functools.partial(jax.jit, static_argnames=("sizes", "control"),
                   donate_argnums=(2, 3))
def _keys_block(h, lp, kc, vc, start, sizes, control):
    """The sequence's keys and values with a block's written at ``start``."""
    lp = _f32(lp)
    k, v = keys_values(_rms(h, lp["ln1"]["scale"], sizes["rms_norm_eps"]),
                       lp, sizes, lower_precision(control))
    return (jax.lax.dynamic_update_slice_in_dim(kc, k, start, axis=1),
            jax.lax.dynamic_update_slice_in_dim(vc, v, start, axis=1))


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _attention_block(h, lp, kc, vc, start, sizes, control):
    lp = _f32(lp)
    eps, rm = sizes["rms_norm_eps"], sizes["residual_multiplier"]
    h = h + rm * attention(_rms(h, lp["ln1"]["scale"], eps), lp, kc, vc,
                           start, sizes, lower_precision(control))
    return h, _rms(h, lp["ln2"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "control"))
def _head(h, final_ln, table, eps, scaling, control):
    cast = lower_precision(control)
    return jnp.dot(cast(_rms(h, final_ln["scale"].astype(F32), eps)),
                   cast(table.astype(F32)).T, precision=HI) / scaling


def _jitted(shape, control):
    """The layer programs as the jitted functions, their static arguments
    bound: each compiles at its first call, one after another."""
    eps, scaling = shape["rms_norm_eps"], shape["logits_scaling"]
    return {
        "mamba": lambda h, lp, c: _mamba_block(h, lp, c, shape, control),
        "keys": lambda h, lp, k, v, at: _keys_block(h, lp, k, v, at, shape,
                                                    control),
        "attention": lambda h, lp, k, v, at: _attention_block(
            h, lp, k, v, at, shape, control),
        "weights": lambda x, w: _held_weights(x, w, shape, control),
        "experts": lambda x, w, gu, dn: _experts_block(x, w, gu, dn, control),
        "shared": lambda x, sh: _shared_expert(x, sh, control),
        "head": lambda h, ln, tok: _head(h, ln, tok, eps, scaling, control)}


_COMPILED = {}


def _programs(params, shape, control, T, L):
    """The same programs for blocks of ``T`` tokens and key buffers ``L``
    long, lowered from shapes and compiled AT ONCE on a pool of threads, kept
    a process: a float32 product at full precision costs the chip's compiler
    5-15 s, and a first check that compiled its seven programs one after
    another spent a minute there."""
    struct = jax.ShapeDtypeStruct
    table = params["embed"]["tok"]
    key = (shape, control, T, L, str(table.dtype))
    if key in _COMPILED:
        return _COMPILED[key]

    def like(tree):
        return jax.tree.map(lambda a: struct(a.shape, a.dtype), tree)

    D = shape["hidden_size"]
    H, P, N, G = mamba_dims(shape)
    x, at = struct((T, D), F32), struct((), jnp.int32)
    kv = struct((shape["num_key_value_heads"], L, shape["head_dim"]), F32)
    carry = (struct((shape["mamba_d_conv"] - 1, H * P + 2 * G * N), F32),
             struct((H, P, N), F32))
    kinds = layer_kinds(shape)
    mixer = {kind: like({n: v for n, v in params["layers"][
        kinds.index(kind)].items() if n != "moe"}) for kind in set(kinds)}
    moe = like(params["layers"][0]["moe"])
    gate_up, down = moe["experts"]["gate_up"], moe["experts"]["down"]
    held = down.shape[0]
    low = {"weights": _held_weights.lower(x, moe["router"]["w"], shape,
                                          control),
           "shared": _shared_expert.lower(x, moe["shared"], control),
           "head": _head.lower(struct((ROWS, D), F32),
                               like(params["final_ln"]), like(table),
                               shape["rms_norm_eps"],
                               shape["logits_scaling"], control)}
    for n in {min(EXPERTS, held - lo) for lo in range(0, held, EXPERTS)}:
        low["experts", n] = _experts_block.lower(
            x, struct((T, n), F32), struct((n,) + gate_up.shape[1:],
                                           gate_up.dtype),
            struct((n,) + down.shape[1:], down.dtype), control)
    if "mamba" in mixer:
        low["mamba"] = _mamba_block.lower(x, mixer["mamba"], carry, shape,
                                          control)
    if "attention" in mixer:
        low["keys"] = _keys_block.lower(x, mixer["attention"], kv, kv, at,
                                        shape, control)
        low["attention"] = _attention_block.lower(
            x, mixer["attention"], kv, kv, at, shape, control)
    with futures.ThreadPoolExecutor(len(low)) as pool:
        run = dict(zip(low, pool.map(lambda lo: lo.compile(), low.values())))
    run["experts"] = lambda x, w, gu, dn: run["experts", w.shape[1]](
        x, w, gu, dn)
    _COMPILED[key] = run
    return run


SHAPE_KEYS = ("num_attention_heads", "num_key_value_heads", "hidden_size",
              "mamba_n_heads", "mamba_d_head", "mamba_d_state",
              "mamba_n_groups", "mamba_d_conv", "rms_norm_eps",
              "num_experts_per_tok", "experts_held", "attention_multiplier",
              "residual_multiplier", "logits_scaling", "layer_types")


def shape_of(sizes):
    return _static({k: sizes[k] for k in SHAPE_KEYS},
                   head_dim=head_dim(sizes))


def logits(params, sizes, ids, rows, control=None, keys=None):
    """float32 logits of one sequence ``ids`` (1-D) at positions ``rows``: a
    full causal forward, layer by layer and (past ``TOKENS`` tokens) block
    by block, the head on those rows only, ``ROWS`` at a time. ``keys``: the
    length of the attention layers' key buffers (default: the padded
    sequence's), so that sequences of every length run the same programs."""
    n, m = len(ids), len(rows)
    T = n if n <= TOKENS else TOKENS
    ids = jnp.asarray(np.pad(np.asarray(ids), (0, -n % T)), jnp.int32)
    L = max(keys or 0, len(ids))
    shape = shape_of(sizes)
    run = _programs(params, shape, control, T, L)
    H, P, N, G = mamba_dims(sizes)
    Hkv, hd = sizes["num_key_value_heads"], shape["head_dim"]
    rm = sizes["residual_multiplier"]
    h = [params["embed"]["tok"][ids[lo:lo + T]].astype(F32)
         * sizes["embedding_multiplier"] for lo in range(0, len(ids), T)]
    for lp, kind in zip(params["layers"], layer_kinds(sizes)):
        mixer = {k: v for k, v in lp.items() if k != "moe"}
        if kind == "mamba":
            carry = (jnp.zeros((sizes["mamba_d_conv"] - 1,
                                H * P + 2 * G * N), F32),
                     jnp.zeros((H, P, N), F32))
        else:
            kc, vc = (jnp.zeros((Hkv, L, hd), F32) for _ in "kv")
            for j, hb in enumerate(h):
                kc, vc = run["keys"](hb, mixer, kc, vc, np.int32(j * T))
        for j, hb in enumerate(h):
            if kind == "mamba":
                hb, x, carry = run["mamba"](hb, mixer, carry)
            else:
                hb, x = run["attention"](hb, mixer, kc, vc, np.int32(j * T))
            h[j] = hb + rm * routed_ffn(x, lp["moe"], shape, control, run)
    h = jnp.concatenate(h)[jnp.asarray(np.pad(np.asarray(rows),
                                              (0, -m % ROWS), mode="edge"))]
    return jnp.concatenate([
        run["head"](h[lo:lo + ROWS], params["final_ln"],
                    params["embed"]["tok"])
        for lo in range(0, len(h), ROWS)])[:m]


def served_token_gaps(params, sizes, prompt, served, pad_to, control=None):
    """Teacher forcing over ``prompt + served``, padded on the right (causal,
    so the padding is never seen) to whole blocks; the attention layers' key
    buffers are ``pad_to`` long whatever the sequence holds, so one set of
    programs judges every length. For each served token the amount by which
    its reference logit lies below the row's best, in standard deviations of
    the row: 0 where it is the float32 argmax.

    With ``control``, the tokens judged are not the served ones but those the
    lower precision puts first at each of the same positions."""
    n, m = len(prompt), len(served)
    ids = np.concatenate([prompt, served]).astype(np.int32)
    rows = np.arange(n - 1, n - 1 + m)
    keys = -(-pad_to // TOKENS) * TOKENS if n + m > TOKENS else None
    ref = np.asarray(logits(params, sizes, ids, rows, keys=keys))
    if control:
        judged = np.asarray(logits(params, sizes, ids, rows, control=control,
                                   keys=keys)).argmax(axis=1)
    else:
        judged = np.asarray(served)
    short = ref.max(axis=1) - ref[np.arange(m), judged]
    return short / ref.std(axis=1)
