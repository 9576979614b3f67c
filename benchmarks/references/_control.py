"""The control's arithmetic, shared by the references: a round trip through
the nearest precision below the one a configuration states."""

import jax.numpy as jnp

DTYPES = {"float8_e4m3fn": jnp.float8_e4m3fn}


def identity(x):
    return x


def lower_precision(name):
    """Per tensor, scaled so that the largest magnitude lands on the type's
    largest finite value, as a quantised matrix product would be fed;
    ``None`` is the identity (the reference itself)."""
    if not name:
        return identity
    dtype = DTYPES[name]
    top = float(jnp.finfo(dtype).max)

    def cast(x):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return (x / scale).astype(dtype).astype(jnp.float32) * scale
    return cast
