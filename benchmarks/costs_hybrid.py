"""Bytes the two mixers of a linear/sparse hybrid decoder need for a decode
step, from shapes alone: ``costs.py``'s rule (what the mathematics requires,
not what a program happens to do) for the kernels a hybrid configuration
brings. Both steps are memory bound: two operations a byte."""


def lightning_state_bytes(slot_steps, layers, heads, head_dim,
                          bytes_per_value=4):
    """Least bytes the linear-attention decode step moves: each live slot's
    ``(head_dim x head_dim)`` state a head, read once and written once, a
    lightning layer a step. ``slot_steps`` is the sum over steps of the
    slots that got a token."""
    return slot_steps * layers * 2 * heads * head_dim * head_dim \
        * bytes_per_value


def sparse_selected_keys(context, sp):
    """Keys a query with ``context`` cached positions (its own included)
    attends on a block-sparse layer: all of them up to ``dense_len``; past
    it ``topk`` blocks, of which the newest holds only the positions
    written so far."""
    if context <= sp["dense_len"]:
        return context
    blocks = -(-context // sp["block_size"])
    whole = min(sp["topk"], blocks) - 1
    return whole * sp["block_size"] + (context - 1) % sp["block_size"] + 1


def compressed_keys_scanned(context, sp):
    """Compressed keys the block scorer reads for that query: the windows of
    ``kernel_size`` positions, one every ``kernel_stride``, complete by its
    position; none while attention is dense."""
    if context <= sp["dense_len"] or context < sp["kernel_size"]:
        return 0
    return (context - sp["kernel_size"]) // sp["kernel_stride"] + 1


def sparse_decode_bytes(contexts, layers, kv_heads, head_dim, sp,
                        bytes_per_value=2):
    """Least bytes the sparse layers' decode attention reads to emit one
    token for each context length in ``contexts``: K and V of the selected
    keys and one key of each compressed window scanned, a KV head a layer."""
    row = kv_heads * head_dim * bytes_per_value
    return layers * row * sum(
        2 * sparse_selected_keys(c, sp) + compressed_keys_scanned(c, sp)
        for c in contexts)
