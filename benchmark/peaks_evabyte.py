"""Operations and bytes of the kernel calls EvaByte makes, for their
shares of the roofline. The model brought no kernel of its own: its decode
step reads the two-role page list (the summary pages of the windows left
behind, then the window's pages) through ``ops/pallas/paged_sparse_attn``
with ONE query a key head, whose count is ``peaks_sala.paged_sparse_call``
(``metrics/paged_attn_roofline.eva.json`` hands it 1 head a row and 512
rows a call). What is here is the arithmetic of what a live slot's list
names, for the cell's notes and the tests; the pooling of 16 rows and the
chunk program's attention are XLA's and have no roofline of their own."""

from .peaks_sala import paged_sparse_call


def listed_pages(n: int, window: int, chunk: int, block_size: int) -> int:
    """Pages the list of a query at position ``n`` (``n`` positions
    cached before it) counts: the summary pages of the ``n // window``
    windows left behind and the window's pages that hold a row below
    ``n mod window``."""
    w, r = divmod(n, window)
    return w * (window // chunk // block_size) + -(-r // block_size)


def eva_decode_call(positions, heads: int, head_dim: int, window: int,
                    chunk: int, block_size: int, itemsize: int) -> dict:
    """One call (a layer of a decode step) of the page-list kernel for
    live slots at ``positions``: every one of a slot's ``heads`` key heads
    is a row of one query over the slot's listed pages."""
    pages = heads * sum(listed_pages(n, window, chunk, block_size)
                        for n in positions)
    return paged_sparse_call(pages, heads * len(positions), 1, head_dim,
                             block_size, itemsize)
