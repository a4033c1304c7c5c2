"""Operations and bytes of what Mellum 2 brought to the device, for the
shares of the roofline: the grouped product of the routed experts (JAX's
grouped matmul for TPU, ``gmm``, through ``ops/pallas/grouped_matmul``)
and the page-list read of a stack of two cache rules
(``paged_sparse_attn_slots``: a full layer over a slot's pages of every
key, a window layer over the whole pages of its ring). The least a call
needs: an expert's weights are read once if any row is assigned to it and
not at all otherwise; padded rows, revisited tiles and pages past a count
do not count. The chunk program's attention is XLA's and has no roofline
of its own (its milliseconds are in PERF.md section 5)."""


def experts_product(rows: float, touched: float, k: int, n: int,
                    itemsize: int) -> dict:
    """One grouped product ``(rows, k) @ (experts, k, n)`` in which
    ``touched`` experts have any row: two operations a row, an entry of k
    and of n; every touched expert's ``k x n`` weights once, the rows in
    and the result out."""
    return {"flops": 2.0 * rows * k * n,
            "bytes": itemsize * (touched * k * n + rows * (k + n))}


def slot_list_call(pages: float, slots: int, heads: int, kv_heads: int,
                   head_dim: int, block_size: int, itemsize: int) -> dict:
    """One call of ``paged_sparse_attn_slots`` (a layer of a decode step)
    whose slots' lists count ``pages`` pages in all, a page ``block_size``
    keys and as many values of EVERY key head: each key meets its key
    head's ``heads / kv_heads`` queries in two products (2 x 2 operations
    a key, a query head and an entry of the head). Beside the pages a slot
    brings its queries in and its output out (the pool's dtype) and what
    it had attended over already (float32: the unnormalised output, and
    the running maximum and sum, each held over 128 lanes)."""
    flops = 4.0 * pages * block_size * heads * head_dim
    nbytes = pages * 2 * kv_heads * block_size * head_dim * itemsize \
        + slots * heads * (2 * head_dim * itemsize + head_dim * 4
                           + 2 * 128 * 4)
    return {"flops": flops, "bytes": nbytes}


def pool_bytes(slots: int, positions: int, block_size: int, ring: int,
               full_layers: int, window_layers: int, kv_heads: int,
               head_dim: int, itemsize: int) -> tuple:
    """Bytes of the two pools sized so that ``slots`` slots of
    ``positions`` positions fit, a null page each: (the pool of every
    key, the rings' pool). A page is ``block_size`` rows of K and V of
    every key head, as deep as the layers that read it."""
    page = 2 * kv_heads * block_size * head_dim * itemsize
    full = (slots * -(-positions // block_size) + 1) * page * full_layers
    rings = (slots * (ring // block_size) + 1) * page * window_layers
    return full, rings
