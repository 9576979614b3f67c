"""Bytes the grouped-query attention of a short-convolution hybrid decoder
needs for a decode step, from shapes alone: ``costs.py``'s rule (what the
mathematics must move, whatever implements it). Memory bound: a decode step
does ``2 x (query heads a KV head)`` operations a byte. The gated short
convolution has no kernel of its own (its taps fuse beside its projections,
whose weights are the layer's bytes) and the routed feed-forward's bytes are
``costs_moe.py``'s."""


def gqa_decode_bytes(contexts, layers, kv_heads, head_dim, bytes_per_value=2):
    """Least bytes grouped-query decode attention reads to emit one token for
    each context length in ``contexts``: K and V of every cached position,
    once a KV head a layer, however many query heads share the head."""
    return layers * kv_heads * head_dim * 2 * bytes_per_value * sum(contexts)
