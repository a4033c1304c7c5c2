"""Bytes and operations of a decode step of a LOOPED stack (Ouro), for its
share of the memory roofline: what one step has to move whatever the
batch. The stack's weights cross HBM once a PASS (the passes depend on
each other, so nothing of one is kept for the next: 4.9 GB does not stay
in 128 MiB of fast memory), the head and the gathered embedding rows
once; every page a live slot lists is read by every cache layer (a (pass,
layer) pair each) and every live slot writes one row into each. The
page-list read itself counts as ``peaks_mellum.slot_list_call``."""


def layer_matmul_params(d_model: int, d_ff: int, heads: int, kv_heads: int,
                        head_dim: int) -> int:
    """The entries of one layer's matrices: q, k, v and the projection
    out, the gated feed-forward's three."""
    return (d_model * (heads + 2 * kv_heads) * head_dim
            + heads * head_dim * d_model + 3 * d_model * d_ff)


def decode_step(pages: float, live: float, slots: int, passes: int,
                layers: int, d_model: int, d_ff: int, heads: int,
                kv_heads: int, head_dim: int, vocab: int, block_size: int,
                itemsize: int) -> dict:
    """One decode step whose ``live`` slots list ``pages`` pages in all.
    Bytes: ``passes`` x the stack's matrices and its four norms a layer,
    the head once, a row of the embedding a slot; ``pages`` pages of
    ``block_size`` keys and values of every key head, ``passes x layers``
    cache layers deep; a new row a live slot in each of them. Operations:
    two an entry of every matrix, a slot and a pass; the head; the
    attention's two products over the listed keys."""
    stack = layers * (layer_matmul_params(d_model, d_ff, heads, kv_heads,
                                          head_dim) + 4 * d_model)
    deep = passes * layers
    row = 2 * kv_heads * head_dim * itemsize * deep     # K and V, all layers
    nbytes = itemsize * (passes * stack + d_model * vocab + slots * d_model) \
        + pages * block_size * row + live * row
    flops = 2.0 * slots * (passes * stack + d_model * vocab) \
        + 4.0 * pages * block_size * heads * head_dim * deep
    return {"flops": flops, "bytes": float(nbytes)}
