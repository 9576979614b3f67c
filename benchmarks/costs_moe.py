"""Bytes the mechanisms of a routed, delta-rule and latent decoder need for a
decode step, from shapes alone: ``costs.py``'s rule (what the mathematics
must move, whatever implements it). All three are memory bound in a decode
tick: a few rows against many weights, a state, a cache."""


def expert_bytes(hidden, width, bytes_per_value=2):
    """One SwiGLU expert's parameters: gate, up and down."""
    return 3 * hidden * width * bytes_per_value


def experts_touched_bytes(experts_touched, hidden, width, bytes_per_value=2):
    """Least bytes the routed experts' product reads: every DISTINCT expert
    that got a (token, expert) pair is read once a layer a step.
    ``experts_touched`` is that count summed over layers and steps."""
    return experts_touched * expert_bytes(hidden, width, bytes_per_value)


def expected_experts_touched(held, pairs):
    """Distinct experts ``pairs`` pairs reach when each lands on one of
    ``held`` experts evenly and independently: the plan a routing counter
    is read against."""
    return held * (1.0 - (1.0 - 1.0 / held) ** pairs)


def latent_decode_bytes(contexts, layers, latent, rope, bytes_per_value=2):
    """Least bytes latent attention reads to emit one token for each
    context length in ``contexts``: ONE ``latent + rope`` row a cached
    position a layer, whatever the number of heads (the row is key and
    value at once)."""
    return layers * (latent + rope) * bytes_per_value * sum(contexts)
