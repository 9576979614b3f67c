"""LM training + draft distillation for the zoo decoder.

Speculative decoding (``speculative.py``) only pays off when the draft's
greedy choices agree with the target's — an untrained draft accepts ~0
proposals and the machinery slows generation down. This module supplies the missing piece as a first-class
capability:

* :func:`train_lm` — next-token cross-entropy training of any zoo
  ``TransformerConfig`` model (one jitted ``optax`` step, scan-free host
  loop: the batch iterator is a plain callable).
* :func:`distill_draft` — knowledge distillation of a small draft from a
  frozen target: KL(target ‖ draft) on teacher logits over sampled
  prompts. This is the "draft model" production recipe the speculative
  literature assumes; the reference has no serving-side analog (its
  deep-learning module is stateless batch ONNX inference,
  ``deep-learning/.../onnx/ONNXModel.scala:305-355``).

Both run as compiled-per-step programs on whatever backend JAX has; at
zoo scale a few hundred steps take seconds on a TPU chip.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import optax

from .transformer import TransformerConfig, init_transformer, transformer_apply

__all__ = ["train_lm", "distill_draft", "markov_sampler"]


def _lm_logits(params: Dict, ids: jnp.ndarray,
               cfg: TransformerConfig) -> jnp.ndarray:
    """(B, S) ids → (B, S, V) next-token logits (f32 head like the
    generators, so training and serving argmax see the same numerics)."""
    h = transformer_apply(params, ids, cfg)
    return h.astype(jnp.float32) @ params["lm_head"]["w"].astype(jnp.float32)


def train_lm(params: Dict, cfg: TransformerConfig,
             batch_fn: Callable[[int], np.ndarray], steps: int,
             learning_rate: float = 3e-4,
             log_every: int = 0) -> Tuple[Dict, list]:
    """Next-token CE training; returns (trained params, loss history).

    ``batch_fn(step) -> (B, S) int32`` supplies token batches (host side —
    corpora are the caller's business). One ``jax.jit`` step: loss grad +
    adamw update; the loop never fetches anything but the scalar loss.
    """
    params = jax.tree.map(jnp.asarray, params)
    opt = optax.adamw(learning_rate)
    opt_state = opt.init(params)

    @jax.jit
    def step_fn(params, opt_state, ids):
        def loss_fn(p):
            logits = _lm_logits(p, ids[:, :-1], cfg)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, ids[:, 1:]).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # losses stay ON DEVICE during the loop (a float() per step would cost
    # one host round-trip each — serialized dead time on the device);
    # one stacked fetch at the end returns the whole history
    dev_losses = []
    for s in range(int(steps)):
        ids = jnp.asarray(np.asarray(batch_fn(s), dtype=np.int32))
        params, opt_state, loss = step_fn(params, opt_state, ids)
        if log_every and (s + 1) % log_every == 0:
            dev_losses.append(loss)
    history = ([float(x) for x in np.asarray(jnp.stack(dev_losses))]
               if dev_losses else [])
    return params, history


def distill_draft(t_params: Dict, t_cfg: TransformerConfig,
                  d_cfg: TransformerConfig,
                  batch_fn: Callable[[int], np.ndarray], steps: int,
                  learning_rate: float = 1e-3, tau: float = 1.0,
                  seed: int = 0,
                  d_params: Optional[Dict] = None) -> Tuple[Dict, list]:
    """Distill a draft for speculative decoding from a frozen target.

    Minimizes KL(softmax(target/τ) ‖ softmax(draft/τ)) over ``batch_fn``
    prompts. The objective is exactly what acceptance measures: the
    draft's greedy choice matching the target's. Returns (draft params,
    loss history). Vocabularies must match (the verifier compares ids).
    """
    if t_cfg.vocab != d_cfg.vocab:
        raise ValueError("draft and target must share a vocabulary")
    if d_params is None:
        d_params = init_transformer(d_cfg, seed=seed)
    t_params = jax.tree.map(jnp.asarray, t_params)
    d_params = jax.tree.map(jnp.asarray, d_params)
    opt = optax.adamw(learning_rate)
    opt_state = opt.init(d_params)
    inv_tau = 1.0 / float(tau)

    @jax.jit
    def step_fn(t_params, d_params, opt_state, ids):
        # teacher passed as an ARG: a closure-captured 100M-param tree
        # would be baked into the program as constants (and blow up
        # the compiled program)
        t_logits = _lm_logits(t_params, ids, t_cfg) * inv_tau
        t_prob = jax.nn.softmax(t_logits, axis=-1)
        t_ent = -(t_prob * jax.nn.log_softmax(t_logits, axis=-1)).sum(-1)

        def loss_fn(p):
            d_logits = _lm_logits(p, ids, d_cfg) * inv_tau
            ce = -(t_prob * jax.nn.log_softmax(d_logits, axis=-1)).sum(-1)
            return (ce - t_ent).mean()          # KL, >= 0
        loss, grads = jax.value_and_grad(loss_fn)(d_params)
        updates, opt_state = opt.update(grads, opt_state, d_params)
        return optax.apply_updates(d_params, updates), opt_state, loss

    # same device-side loss accumulation as train_lm: zero per-step syncs
    dev_losses = []
    for s in range(int(steps)):
        ids = jnp.asarray(np.asarray(batch_fn(s), dtype=np.int32))
        d_params, opt_state, loss = step_fn(t_params, d_params, opt_state,
                                            ids)
        dev_losses.append(loss)
    history = ([float(x) for x in np.asarray(jnp.stack(dev_losses))]
               if dev_losses else [])
    return d_params, history


def markov_sampler(vocab: int, batch: int, seq: int, seed: int = 0,
                   branching: int = 4):
    """A low-entropy first-order Markov language: every token has
    ``branching`` plausible successors with a dominant mode. Structured
    enough that a trained model's greedy continuations are confident and
    predictable — the regime speculative decoding exists for — while
    synthetic (zero-egress image: no downloadable corpus).

    Returns ``batch_fn(step) -> (batch, seq) int32`` for the trainers.
    """
    rng = np.random.default_rng(seed)
    succ = rng.integers(0, vocab, (vocab, branching))
    probs = np.full(branching, 0.1 / max(branching - 1, 1))
    probs[0] = 0.9
    probs = probs / probs.sum()

    def batch_fn(step: int) -> np.ndarray:
        r = np.random.default_rng(seed * 1_000_003 + step)
        out = np.empty((batch, seq), np.int32)
        out[:, 0] = r.integers(0, vocab, batch)
        for t in range(1, seq):
            choice = r.choice(branching, size=batch, p=probs)
            out[:, t] = succ[out[:, t - 1], choice]
        return out

    return batch_fn
