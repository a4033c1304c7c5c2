"""Operations and bytes of what Solar Open 2 brought to the device, for
the shares of the roofline: the chunkwise gated delta rule of a prompt
chunk (``kda_chunk``) and a decode step's update of the state rows
(``kda_row_update``). The least a call needs: every operand crosses HBM
once, the state once each way; the operations are the rule's own matrix
products (the scores inside a block, the solve, the products with the
state), not what the kernel spends on masks and shifts. The routed
experts' grouped product and the page-list read count as in
``peaks_mellum`` (``experts_product``, ``slot_list_call``)."""


def kda_chunk_call(chunk: int, heads: int, head_k: int, head_v: int,
                   block: int) -> dict:
    """One call of ``kda_chunk``: ``chunk`` positions of ``heads`` heads in
    blocks of ``block``. A block and head: the two score matrices K K^T
    and Q K^T (2 x 2 B B dk), the solve's products with the right-hand
    side (2 B B dv), K S, Q S and K^T V' with the state (3 x 2 B dk dv)
    and the scores times V' (2 B B dv). Bytes: q, k, beta k, beta v and
    the log-decays in (float32), the output out, the state in and out."""
    blocks = heads * chunk // block
    flops = blocks * (4.0 * block * block * head_k
                      + 4.0 * block * block * head_v
                      + 6.0 * block * head_k * head_v)
    nbytes = 4.0 * chunk * heads * (4 * head_k + 2 * head_v) \
        + 2 * 4.0 * heads * head_k * head_v
    return {"flops": flops, "bytes": nbytes}


def kda_row_update_call(slots: int, heads: int, head_k: int,
                        head_v: int) -> dict:
    """One call of ``kda_row_update`` (a layer of a decode step): every
    slot's rows in and out (float32; an idle slot's too: the kernel moves
    them all), the columns (the decay, k, beta k, q) and beta v in, the
    output out; a row's entries meet the decay, the prediction, the
    rank-one update and the output (2 operations each)."""
    rows = slots * heads
    return {"flops": 8.0 * rows * head_k * head_v,
            "bytes": 4.0 * rows * (2 * head_k * head_v + 4 * head_k
                                   + 2 * head_v)}
