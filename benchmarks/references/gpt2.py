"""Plain reference for GPT-2 (Radford et al. 2019; the block of
``openai-community/gpt2-xl``): pre-LayerNorm decoder, fused biased QKV,
causal softmax attention, tanh-GELU feed-forward, learned positions, final
LayerNorm, an output head. float32 ``jax.numpy`` at ``HIGHEST`` matmul
precision, no cache, no kernels, one sequence at a time. Imports nothing of
the program under test.

Weights are made on the device from the seed in one jitted call, in the
pytree the program's decoder takes (``embed.tok/pos``, ``layers[i].ln1/qkv/
out/ln2/w1/w2``, ``final_ln``, ``lm_head.w``) and in the configuration's
``param_dtype``; the reference reads those same values as float32.

``cast`` is applied to both operands of every matrix product: the identity
for the reference, a scaled round trip through a lower precision for the
control that the comparison must reject.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references._control import lower_precision

HI = jax.lax.Precision.HIGHEST


def make_weights(sizes, seed):
    d, ff = sizes["n_embd"], sizes["n_inner"]
    vocab, layers, npos = (sizes["vocab_size"], sizes["n_layer"],
                           sizes["n_positions"])
    dtype = jnp.dtype(sizes["param_dtype"])
    key = jax.random.PRNGKey(
        int(np.random.SeedSequence(seed).generate_state(1)[0]))

    @jax.jit
    def build(key):
        def normal(k, shape, scale):
            return scale * jax.random.normal(k, shape, jnp.float32)

        def glorot(k, din, dout):
            return normal(k, (din, dout), (2.0 / (din + dout)) ** 0.5)

        def ln():
            return {"scale": jnp.ones(d, jnp.float32),
                    "bias": jnp.zeros(d, jnp.float32)}

        keys = jax.random.split(key, 3 + 4 * layers)
        out = {"embed": {"tok": normal(keys[0], (vocab, d), 0.02),
                         "pos": normal(keys[1], (npos, d), 0.02)},
               "final_ln": ln(),
               "lm_head": {"w": normal(keys[2], (d, vocab), 0.02)},
               "layers": []}
        for i in range(layers):
            k = keys[3 + 4 * i:7 + 4 * i]
            out["layers"].append({
                "ln1": ln(), "ln2": ln(),
                "qkv": {"w": glorot(k[0], d, 3 * d),
                        "b": jnp.zeros(3 * d, jnp.float32)},
                "out": {"w": glorot(k[1], d, d),
                        "b": jnp.zeros(d, jnp.float32)},
                "w1": {"w": glorot(k[2], d, ff),
                       "b": jnp.zeros(ff, jnp.float32)},
                "w2": {"w": glorot(k[3], ff, d),
                       "b": jnp.zeros(d, jnp.float32)}})
        return jax.tree.map(lambda a: a.astype(dtype), out)

    return jax.block_until_ready(build(key))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _ln(x, p, eps):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        (2.0 / jnp.pi) ** 0.5 * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("heads", "eps", "control"))
def _layer(h, lp, heads, eps, control):
    """One block on one sequence ``h`` of shape (S, D)."""
    cast = lower_precision(control)
    lp = _f32(lp)

    def mm(a, b):
        return jnp.dot(cast(a), cast(b), precision=HI)

    S, D = h.shape
    x = _ln(h, lp["ln1"], eps)
    qkv = mm(x, lp["qkv"]["w"]) + lp["qkv"]["b"]
    q, k, v = (t.reshape(S, heads, D // heads).transpose(1, 0, 2)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("hqd,hkd->hqk", cast(q), cast(k), precision=HI) \
        / jnp.sqrt(jnp.float32(D // heads))
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], scores,
                       -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("hqk,hkd->hqd", cast(attn), cast(v), precision=HI)
    h = h + mm(ctx.transpose(1, 0, 2).reshape(S, D), lp["out"]["w"]) \
        + lp["out"]["b"]
    x = _ln(h, lp["ln2"], eps)
    y = _gelu_new(mm(x, lp["w1"]["w"]) + lp["w1"]["b"])
    return h + mm(y, lp["w2"]["w"]) + lp["w2"]["b"]


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(h, final_ln, w, eps, control):
    cast = lower_precision(control)
    return jnp.dot(cast(_ln(h, _f32(final_ln), eps)),
                   cast(w.astype(jnp.float32)), precision=HI)


def logits(params, sizes, ids, rows, control=None):
    """float32 logits of one sequence ``ids`` (1-D) at positions ``rows``:
    a full causal forward layer by layer, the head on those rows only."""
    ids = jnp.asarray(ids, jnp.int32)
    h = (params["embed"]["tok"][ids].astype(jnp.float32)
         + params["embed"]["pos"][:ids.shape[0]].astype(jnp.float32))
    for lp in params["layers"]:
        h = _layer(h, lp, sizes["n_head"], sizes["layer_norm_epsilon"],
                   control)
    return _head(h[jnp.asarray(rows)], params["final_ln"],
                 params["lm_head"]["w"], sizes["layer_norm_epsilon"], control)


def served_token_gaps(params, sizes, prompt, served, pad_to, control=None):
    """Teacher forcing over ``prompt + served``, padded on the right to
    ``pad_to`` (causal, so the padding is never seen). For each served token
    the amount by which its reference logit lies below the row's best, in
    standard deviations of the row: 0 where it is the float32 argmax.

    With ``control``, the tokens judged are not the served ones but those the
    lower precision puts first at each of the same positions."""
    n, m = len(prompt), len(served)
    ids = np.zeros(pad_to, np.int32)
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
