"""Operations a prefill window over LATENT pages needs, from shapes alone:
``costs.py``'s rule (what the published mathematics must do, whatever
implements it). A window in the expanded form rebuilds K and V of every
cached position of its row from the latents, then scores its queries against
them and weighs the values: compute bound (hundreds of operations a latent
byte). The decode step's bytes are ``costs_moe.latent_decode_bytes``."""


def latent_window_flops(context_keys, pairs, layers, heads, latent, nope,
                        rope, value):
    """Least operations the windows' expanded latent attention needs:
    ``context_keys`` cached positions (each window's ``offset + lanes``,
    summed) rebuilt into a head's ``nope`` key values and ``value`` values
    (``2 * latent`` operations each), and ``pairs`` (query, key) pairs the
    causal mask lets through, each a ``nope + rope`` score and a ``value``
    weighted sum, a head a layer. Masked keys, padded lanes and keys past a
    window's last position are what a program may spend, not what the
    mathematics needs: not counted."""
    rebuild = 2 * latent * heads * (nope + value) * context_keys
    attend = 2 * heads * (nope + rope + value) * pairs
    return layers * (rebuild + attend)
