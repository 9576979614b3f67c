"""Bytes the mechanisms of a state-space decoder with experts in a latent
need for a decode step, from shapes alone: ``costs.py``'s rule (what the
published mathematics must move, whatever implements it). Both are memory
bound in a decode tick: a state a row against two operations a byte, a few
rows against many experts' weights."""


def ssm_state_bytes(row_steps, heads, head_dim, state, bytes_per_value=4):
    """Least bytes the state-space decode step moves: each live row's
    ``(head_dim x state)`` state a head, read once and written once, a
    state-space layer a step. ``row_steps`` is that count summed over the
    layers and the steps (the pool's ``ssm_state_rows``)."""
    return row_steps * 2 * heads * head_dim * state * bytes_per_value


def latent_expert_bytes(latent, width, bytes_per_value=2):
    """One non-gated expert's parameters in a latent: ``W_1`` (latent,
    width) and ``W_2`` (width, latent)."""
    return 2 * latent * width * bytes_per_value


def latent_experts_touched_bytes(experts_touched, latent, width,
                                 bytes_per_value=2):
    """Least bytes the routed experts' product reads: every DISTINCT expert
    that got a (token, expert) pair is read once a layer a step.
    ``experts_touched`` is that count summed over layers and steps."""
    return experts_touched * latent_expert_bytes(latent, width,
                                                 bytes_per_value)
