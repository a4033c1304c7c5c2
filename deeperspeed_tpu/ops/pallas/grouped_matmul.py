"""The grouped product of routed experts: ``x[rows of group g] @ w[g]`` for
rows sorted by group.

``jax.lax.ragged_dot`` is the form that runs everywhere (and the oracle).
On one TPU it takes 2.0 ms for 256 rows over 64 experts of 2,304 x 896 in
bf16, 132 GB/s of the weights it touches (a v5e, PR 39), and wants its
weights as one array of their own: a layer's experts sliced out of the
layers' stack by a traced index are COPIED for it, every call. The kernel
here is JAX's own grouped matmul for TPU (``jax.experimental.pallas.ops.
tpu.megablox``: Mosaic, a group's weights brought to VMEM a whole ``(tk,
tn)`` block at a time, a group with no rows not visited at all) under
tiles found on the chip for the decode step's and the prompt chunk's
shapes: 0.45 ms for the same product, 70% of what reading the touched
weights once allows. It reads ``w`` where it lies, so a caller hands the
WHOLE stack ``(layers x experts, k, n)`` with the sizes of the other
layers' groups zero, and nothing is copied.
"""

import jax
import jax.numpy as jnp

from .. import kernel_config

LANES = 128
# elements of one (tk, tn) block of the weights: 2,304 x 896 (4 MiB in
# bf16, double-buffered beside a tile of rows and the accumulator)
_BLOCK = 2304 * 896
ROWS = 128      # rows a tile: the fewest the sweep found no slower


def _tile(dim: int, most: int) -> int:
    """The largest multiple of 128 that divides ``dim``, at most ``most``."""
    return next((t for t in range(min(dim, most) // LANES * LANES, 0, -LANES)
                 if dim % t == 0), 0)


def tiling(m: int, k: int, n: int):
    """(tm, tk, tn) for ``(m, k) @ (g, k, n)``, or None where the kernel
    cannot tile the shapes: the whole of k a block where it fits, then as
    much of n as the block allows."""
    tk = _tile(k, 2304)
    tn = _tile(n, max(LANES, _BLOCK // tk)) if tk else 0
    return (ROWS, tk, tn) if tk and tn and m % ROWS == 0 else None


def is_available(x, w) -> bool:
    """Whether the compiled kernel can take these operands: one TPU, bf16
    or float32, shapes ``tiling`` can tile."""
    if not kernel_config.on_tpu():
        return False
    return (x.dtype == w.dtype and x.dtype.itemsize in (2, 4)
            and tiling(x.shape[0], *w.shape[1:]) is not None)


def grouped_matmul(x, w, sizes):
    """``jax.lax.ragged_dot(x, w, sizes)`` in x's dtype: x (m, k) with its
    rows sorted by group, w (g, k, n), sizes (g,) int32. Rows past the
    sizes' sum are left as they are (whatever the buffer held)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    return gmm(x, w, sizes.astype(jnp.int32), x.dtype,
               tiling(x.shape[0], *w.shape[1:]))


def grouped_matmul_for(x, w, mesh=None):
    """The grouped product a program takes, from what it can see when it
    is traced: the kernel on one TPU at shapes it can tile, else
    ``jax.lax.ragged_dot`` (which GSPMD shards)."""
    if (mesh is None or mesh.size == 1) and is_available(x, w):
        return grouped_matmul
    return jax.lax.ragged_dot
