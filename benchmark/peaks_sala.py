"""Operations and bytes of the kernels MiniCPM-SALA brought
(``ops/pallas/paged_sparse_attn``, ``ops/pallas/lightning_chunk``), for
their shares of the roofline. The least a call needs: recomputed or padded
work does not count, and neither do the pages a chunked copy fetches past
the last that counts."""


def paged_sparse_call(pages: float, rows: int, heads_per_row: int,
                      head_dim: int, block_size: int, itemsize: int) -> dict:
    """One call of ``paged_sparse_attn`` whose rows' lists name ``pages``
    pages in all. A page is ``block_size`` keys and as many values of one
    key head; each is read once and meets the row's ``heads_per_row``
    queries in two products (scores, output): 2 x 2 operations a key, a
    query head and an entry of the head. Beside the pages a row brings
    its queries in and its output out (the pool's dtype) and what it had
    attended over already (float32: the unnormalised output, and the
    running maximum and sum, each held over 128 lanes)."""
    G, Dh, bs = heads_per_row, head_dim, block_size
    flops = 4.0 * pages * bs * G * Dh
    nbytes = pages * 2 * bs * Dh * itemsize \
        + rows * (2 * G * Dh * itemsize + G * Dh * 4 + 2 * G * 128 * 4)
    return {"flops": flops, "bytes": nbytes}


def lightning_chunk_call(chunk: int, heads: int, head_dim: int, block: int,
                         itemsize: int) -> dict:
    """One call of ``lightning_chunk`` over ``chunk`` positions in blocks
    of ``block``: per head and block the scores Q K^T and their product
    with V (2 x 2 B^2 Dh), what the carried state adds, Q S, and the
    state's update, K^T V (2 x 2 B Dh^2). q, k, v come in once (the
    served dtype), o goes out in float32, the state in and out in
    float32."""
    B, Dh = block, head_dim
    per_block = 4.0 * B * B * Dh + 4.0 * B * Dh * Dh
    flops = heads * (chunk // B) * per_block
    nbytes = 3 * chunk * heads * Dh * itemsize + chunk * heads * Dh * 4 \
        + 2 * heads * Dh * Dh * 4
    return {"flops": flops, "bytes": nbytes}


def chunk_pages_read(chunk: int, block_size: int, topk: int, kv_heads: int,
                     sparse_layers: int = 1) -> int:
    """Pages the selections of one prompt chunk beyond ``dense_len`` name
    in the pool: every query and key head ``topk`` blocks, less those of
    the chunk itself (the query in the chunk's b-th block has b + 1 of its
    forced local blocks there), which it attends over densely."""
    n = chunk // block_size
    return sparse_layers * kv_heads * (chunk * topk
                                       - block_size * n * (n + 1) // 2)
