"""Plain reference for MiniCPM-SALA (``openbmb/MiniCPM-SALA``): a decoder whose
layers mix tokens in one of two ways, ``lightning-attn`` (linear attention
over a recurrent state) or ``minicpm4`` (InfLLM-V2 block-sparse softmax
attention), under RMSNorm, SwiGLU and muP's scalings. float32 ``jax.numpy``
at ``HIGHEST`` matmul precision, no cache, no kernels, no chunking, one
sequence at a time. Imports nothing of the program under test.

The equations (``x`` the block's input after its RMSNorm, ``d`` the head
size, ``L`` the PUBLISHED depth, kept under a cut in depth):

* model: ``h0 = scale_emb * E[ids]``; a block adds ``scale_depth / sqrt(L)``
  times its mixer's output, then as much of ``W_down(silu(W_gate x) * W_up
  x)``; logits ``= W_head(RMSNorm(h) / (hidden_size / dim_model_base))``.
* ``lightning-attn``: ``q = RoPE(RMSNorm_d(W_q x))``, ``k`` alike, ``v = W_v
  x``; per head ``S_t = lambda_h S_(t-1) + k_t^T v_t``, ``o_t = q_t S_t /
  sqrt(d)``, ``lambda_h = exp(-2^(-8h/H))``, h = 1..H; ``y = W_o(sigmoid(W_g
  x) * RMSNorm_(H d)(o))``. A ``lax.scan`` over positions.
* ``minicpm4``: ``q = RMSNorm_d(W_q x)``, ``k`` alike, no positions, 16 query
  heads a KV head. A query with at most ``dense_len`` of context attends every
  key before it and its own. A later one, at position ``t``: compressed keys
  ``c_j = mean(k_(s j .. s j + ks - 1))`` over the windows complete by ``t``;
  ``p = softmax_j(q . c_j / sqrt(d))`` a head, summed over the group's heads;
  a block's score is the largest ``p`` of a window that overlaps it; it
  attends the first block, the blocks over its last ``window_size``
  positions and the best-scored others, ``topk`` in all, the same for the
  group's heads; ``y = W_o(sigmoid(W_g x) * o)``. Taken a block of queries at
  a time so that a 32k context fits.

Weights are made on the device from the seed, a layer at a time, in the
pytree the program's decoder takes and in the configuration's ``param_dtype``;
the reference reads those same values as float32.

``cast`` is applied to both operands of every matrix product: the identity
for the reference, a scaled round trip through a lower precision for the
control that the comparison must reject.
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
KIND = {"lightning-attn": "lightning", "minicpm4": "sparse"}
ROWS = 2048            # rows of a sequence a matrix product takes at a time
QUERIES = 128          # queries of a sparse layer scored and attended at once
PAD = 2048             # a sequence is padded to a multiple (fewer programs)


def heads(sizes):
    """(query heads, KV heads, head size) of the ``minicpm4`` layers; the
    lightning layers have ``lightning_nh`` heads of ``lightning_head_dim``
    for queries, keys and values alike."""
    return (sizes["num_attention_heads"], sizes["num_key_value_heads"],
            sizes["head_dim"])


def make_weights(sizes, seed):
    d, ff, vocab = (sizes["hidden_size"], sizes["intermediate_size"],
                    sizes["vocab_size"])
    H, Hkv, hd = heads(sizes)
    Hl, hdl = sizes["lightning_nh"], sizes["lightning_head_dim"]
    dtype = jnp.dtype(sizes["param_dtype"])
    key = jax.random.PRNGKey(
        int(np.random.SeedSequence(seed).generate_state(1)[0]))

    def normal(k, shape, scale):
        return (scale * jax.random.normal(k, shape, F32)).astype(dtype)

    def glorot(k, din, dout):
        return {"w": normal(k, (din, dout), (2.0 / (din + dout)) ** 0.5)}

    def ones(n):
        return {"scale": jnp.ones(n, dtype)}

    @functools.partial(jax.jit, static_argnames="kind")
    def layer(k, kind):
        k = jax.random.split(k, 8)
        nq, nkv, n = ((Hl, sizes["lightning_nkv"], hdl)
                      if kind == "lightning" else (H, Hkv, hd))
        lp = {"ln1": ones(d), "ln2": ones(d),
              "q": glorot(k[0], d, nq * n), "k": glorot(k[1], d, nkv * n),
              "v": glorot(k[2], d, nkv * n), "g": glorot(k[3], d, nq * n),
              "o": glorot(k[4], nq * n, d),
              "q_norm": ones(n), "k_norm": ones(n),
              "gate": glorot(k[5], d, ff), "up": glorot(k[6], d, ff),
              "down": glorot(k[7], ff, d)}
        if kind == "lightning":
            lp["o_norm"] = ones(nq * n)
        return lp

    @jax.jit
    def ends(k):
        k = jax.random.split(k, 2)
        return (normal(k[0], (vocab, d), 0.02), normal(k[1], (d, vocab), 0.02))

    keys = jax.random.split(key, 1 + len(sizes["mixer_types"]))
    tok, head = ends(keys[0])
    out = {"embed": {"tok": tok}, "final_ln": ones(d),
           "lm_head": {"w": head},
           "layers": [layer(k, KIND[m])
                      for k, m in zip(keys[1:], sizes["mixer_types"])]}
    return jax.block_until_ready(out)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _by_rows(fn, *xs):
    """``fn`` over arrays (S, ...) a slab of ROWS rows at a time (a longer S
    is a multiple): bounds the float32 intermediates of a 32k sequence."""
    S = xs[0].shape[0]
    if S <= ROWS:
        return fn(*xs)
    out = jax.lax.map(lambda slab: fn(*slab), tuple(
        x.reshape(S // ROWS, ROWS, *x.shape[1:]) for x in xs))
    return out.reshape(S, *out.shape[2:])


def _rope(t, theta):
    """Rotate-half rotary embedding of ``t`` (H, S, d) at positions 0..S-1,
    over all d dims."""
    H, S, d = t.shape
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = t[..., :d // 2], t[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _lightning(x, lp, sizes, cast):
    H, d = sizes["lightning_nh"], sizes["lightning_head_dim"]
    eps = sizes["rms_norm_eps"]
    S = x.shape[0]

    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)

    def split(t):
        return t.reshape(S, H, d).transpose(1, 0, 2)

    q = _rope(_rms(split(_by_rows(lambda r: mm(r, lp["q"]["w"]), x)),
                   lp["q_norm"]["scale"], eps), sizes["rope_theta"])
    k = _rope(_rms(split(_by_rows(lambda r: mm(r, lp["k"]["w"]), x)),
                   lp["k_norm"]["scale"], eps), sizes["rope_theta"])
    v = split(_by_rows(lambda r: mm(r, lp["v"]["w"]), x))
    lam = jnp.exp(-(2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=F32) / H)))

    def step(state, qkv):
        qt, kt, vt = qkv                                    # (H, d) each
        state = lam[:, None, None] * state + jnp.einsum(
            "hd,he->hde", cast(kt), cast(vt), precision=HI)
        return state, jnp.einsum("hd,hde->he", cast(qt), cast(state),
                                 precision=HI)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), F32),
                        (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                         v.transpose(1, 0, 2)))              # (S, H, d)
    o = _rms(o.reshape(S, H * d) / math.sqrt(d), lp["o_norm"]["scale"], eps)

    return _by_rows(lambda xr, orow: mm(
        jax.nn.sigmoid(mm(xr, lp["g"]["w"])) * orow, lp["o"]["w"]), x, o)


def selected_blocks(q, k, t, sp):
    """(G, Q, blocks) bool: the blocks the queries ``q`` (Hq, Q, d) at
    positions ``t`` (Q,) attend, from the keys ``k`` (G, S, d) of the whole
    sequence. What a query with at most ``dense_len`` of context gets here
    is not used."""
    Hq, Q, d = q.shape
    G, S, _ = k.shape
    s, ks, bs = sp["kernel_stride"], sp["kernel_size"], sp["block_size"]
    nw = (S - ks) // s + 1
    starts = s * jnp.arange(nw)
    c = jnp.mean(k[:, starts[:, None] + jnp.arange(ks)[None]], axis=2)
    logit = jnp.einsum("ghqd,gjd->ghqj", q.reshape(G, Hq // G, Q, d), c,
                       precision=HI) / math.sqrt(d)
    done = (starts + ks - 1)[None] <= t[:, None]             # (Q, nw)
    p = jax.nn.softmax(jnp.where(done, logit, -jnp.inf), axis=-1)
    p = jnp.where(done, p, 0.0).sum(axis=1)                  # (G, Q, nw)
    # the best window over each stride of tokens (a stride lies in ks / s
    # windows), then the best stride of each block
    n_str = S // s
    per_stride = jnp.full((G, Q, n_str), 0.0)
    for back in range(ks // s):
        lo = back
        hi = min(n_str, nw + back)
        per_stride = per_stride.at[..., lo:hi].max(p[..., :hi - lo])
    nb = S // bs
    score = per_stride[..., :nb * (bs // s)].reshape(
        G, Q, nb, bs // s).max(axis=-1)
    b = jnp.arange(nb)
    live = b[None] <= (t // bs)[:, None]                     # (Q, nb)
    forced = live & ((b[None] < sp["init_blocks"]) | (
        b[None] >= (jnp.maximum(t - sp["window_size"] + 1, 0) // bs)[:, None]))
    rest = live & ~forced
    room = sp["topk"] - forced.sum(axis=-1)                  # (Q,)
    order = jnp.argsort(-jnp.where(rest[None], score, -jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return forced[None] | (rest[None] & (rank < room[None, :, None]))


def _sparse(x, lp, sizes, cast):
    H, G, d = heads(sizes)
    sp = sizes["sparse_config"]
    eps = sizes["rms_norm_eps"]
    S = x.shape[0]
    bs = sp["block_size"]

    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)

    def split(t, n):
        return t.reshape(S, n, d).transpose(1, 0, 2)

    q = _rms(split(_by_rows(lambda r: mm(r, lp["q"]["w"]), x), H),
             lp["q_norm"]["scale"], eps)
    k = _rms(split(_by_rows(lambda r: mm(r, lp["k"]["w"]), x), G),
             lp["k_norm"]["scale"], eps)
    v = split(_by_rows(lambda r: mm(r, lp["v"]["w"]), x), G)
    Q = min(S, QUERIES)
    kc, vc = cast(k), cast(v)

    def block(i):
        t = i * Q + jnp.arange(Q)
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q, Q, axis=1)
        sel = selected_blocks(cast(qb), kc, t, sp)           # (G, Q, nb)
        keys = jnp.repeat(sel, bs, axis=-1)                  # (G, Q, S)
        dense = (t + 1 <= sp["dense_len"])[None, :, None]
        ok = (jnp.arange(S)[None, None] <= t[None, :, None]) & (dense | keys)
        s_ = jnp.einsum("ghqd,gkd->ghqk", cast(qb).reshape(G, H // G, Q, d),
                        kc, precision=HI) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(ok[:, None], s_, -jnp.inf), axis=-1)
        return jnp.einsum("ghqk,gkd->ghqd", cast(a), vc, precision=HI)

    o = jax.lax.map(block, jnp.arange(S // Q))               # (n, G, hg, Q, d)
    o = o.transpose(0, 3, 1, 2, 4).reshape(S, H * d)

    return _by_rows(lambda xr, orow: mm(
        jax.nn.sigmoid(mm(xr, lp["g"]["w"])) * orow, lp["o"]["w"]), x, o)


@functools.partial(jax.jit, static_argnames=("kind", "shape", "control"))
def _layer(h, lp, kind, shape, control):
    """One block on one sequence ``h`` (S, hidden); ``shape`` is the
    configuration's sizes as JSON (a jitted function's static argument)."""
    sizes = json.loads(shape)
    cast = lower_precision(control)
    lp = _f32(lp)
    eps = sizes["rms_norm_eps"]
    depth = sizes["scale_depth"] / math.sqrt(
        sizes["published"]["num_hidden_layers"])
    mixer = _lightning if kind == "lightning" else _sparse
    h = h + depth * mixer(_rms(h, lp["ln1"]["scale"], eps), lp, sizes, cast)

    def ffn(r):
        return jnp.dot(cast(
            jax.nn.silu(jnp.dot(cast(r), cast(lp["gate"]["w"]), precision=HI))
            * jnp.dot(cast(r), cast(lp["up"]["w"]), precision=HI)),
            cast(lp["down"]["w"]), precision=HI)
    return h + depth * _by_rows(ffn, _rms(h, lp["ln2"]["scale"], eps))


@functools.partial(jax.jit, static_argnames=("eps", "scale", "control"))
def _head(h, final_ln, w, eps, scale, control):
    cast = lower_precision(control)
    return jnp.dot(cast(_rms(h, final_ln["scale"].astype(F32), eps) * scale),
                   cast(w.astype(F32)), precision=HI)


SHAPE_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
              "lightning_nh", "lightning_head_dim", "rms_norm_eps",
              "rope_theta", "scale_depth", "sparse_config", "published")


def logits(params, sizes, ids, rows, control=None):
    """float32 logits of one sequence ``ids`` (1-D) at positions ``rows``:
    a full causal forward layer by layer, the head on those rows only. The
    sequence is padded on the right to whole blocks (never seen: causal)."""
    unit = sizes["sparse_config"]["block_size"]
    if len(ids) > QUERIES:
        unit = max(unit, QUERIES)
    if len(ids) > ROWS:
        unit = max(unit, ROWS)
    ids = jnp.pad(jnp.asarray(ids, jnp.int32), (0, -len(ids) % unit))
    shape = json.dumps({k: sizes[k] for k in SHAPE_KEYS}, sort_keys=True)
    h = sizes["scale_emb"] * params["embed"]["tok"][ids].astype(F32)
    for lp, m in zip(params["layers"], sizes["mixer_types"]):
        h = _layer(h, lp, KIND[m], shape, control)
    return _head(h[jnp.asarray(rows)], params["final_ln"],
                 params["lm_head"]["w"], sizes["rms_norm_eps"],
                 sizes["dim_model_base"] / sizes["hidden_size"], control)


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
