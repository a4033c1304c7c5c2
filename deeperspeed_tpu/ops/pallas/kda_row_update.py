"""A decode step's update of the gated-delta-rule state rows, in place.

A ``kda`` layer keeps, for every slot and head, a state ``S`` of ``d_k x
d_v`` float32 (4 MiB a slot and layer at 64 x 128 x 128). One token a slot
moves every row: ``S <- Diag(a) S``, ``S <- S + k (b (v - S^T k))^T``, and
reads it once more for ``o = S^T q``. XLA computes that in several fusions
that each read the rows; here a row crosses HBM once each way: the grid is
(slot, block of ``hb`` heads), a step takes the block's rows ``(hb, d_k,
d_v)`` into VMEM, decays them, takes the prediction ``S^T (b k)`` and the
output as sums over the sublanes, and writes the rows back to where they
came from (the whole stacked ``(layers, slots, heads, d_k, d_v)`` array is
aliased in and out, the layer a prefetched scalar: nothing of it is sliced
or copied).

Small operands: what multiplies a row of S (the decay ``a = exp g``, k, ``b
k`` and q, each ``d_k`` long) comes as COLUMNS, ``(slots, head blocks,
d_k, 4 hb)``, so that a head's column broadcasts over the state's lanes;
``b v`` and the output are rows of ``d_v`` lanes, ``(slots, head blocks,
hb, d_v)``. A slot that is not live (idle, or its prompt still entering
in chunks) keeps its rows: they are read and written back as they were
(every slot's rows move every step, as in ``ssm_row_update``: a list of
the live slots would save the idle ones' bytes, a fifth of the rows at
the cell's load, for an index map that follows it). The oracle is
models/mixers.kda_rows_xla.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernel_config

HEADS_PER_STEP = 8       # 8 x 128 x 128 float32 = 512 KiB in and out a step


def heads_per_step(n_heads: int) -> int:
    return next(b for b in (HEADS_PER_STEP, 4, 2, 1) if n_heads % b == 0)


def is_available(rows) -> bool:
    """rows: (L, N, H, dk, dv) float32. A head's state must be whole
    tiles (``dk`` whole sublanes, ``dv`` whole lanes)."""
    if not kernel_config.on_tpu():
        return False
    dk, dv = rows.shape[-2:]
    return rows.dtype == jnp.float32 and dk % 8 == 0 and dv % 128 == 0


def _kernel(layer_ref, live_ref, cols_ref, bv_ref, h_ref, h_out_ref, o_ref,
            *, hb):
    live = live_ref[pl.program_id(0)] > 0
    cols, bv = cols_ref[0, 0], bv_ref[0, 0]         # (dk, 4 hb), (hb, dv)
    sub = jax.lax.broadcasted_iota(jnp.int32, bv.shape, 0)
    o = jnp.zeros(bv.shape, jnp.float32)
    col = lambda j, i: cols[:, j * hb + i:j * hb + i + 1]   # (dk, 1)
    for i in range(hb):
        h = h_ref[0, 0, i]                                  # (dk, dv)
        Sd = col(0, i) * h
        pred = jnp.sum(col(2, i) * Sd, axis=0, keepdims=True)
        new = Sd + col(1, i) * (bv[i:i + 1] - pred)
        o = jnp.where(sub == i,
                      jnp.sum(col(3, i) * new, axis=0, keepdims=True), o)
        h_out_ref[0, 0, i] = jnp.where(live, new, h)
    o_ref[0, 0] = o


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_row_update(rows, layer, q, k, v, g, beta, live, interpret=False):
    """models/mixers.kda_rows_xla as a kernel (its docstring has the
    contract): rows (L, N, H, dk, dv) float32, updated in place at
    ``layer``; q, k, g (N, H, dk); v (N, H, dv); beta (N, H); live (N,)
    bool. Returns (rows', o (N, H, dv))."""
    L, N, H, dk, dv = rows.shape
    hb = heads_per_step(H)
    nb = H // hb
    f32 = jnp.float32
    b = beta.astype(f32)[..., None]
    k = k.astype(f32)
    # (N, nb, dk, 4 hb): the decays of a block's heads, their k, b k and q
    cols = jnp.concatenate(
        [jnp.swapaxes(a.astype(f32).reshape(N, nb, hb, dk), 2, 3)
         for a in (jnp.exp(g.astype(f32)), k, k * b, q)], -1)
    small = lambda *shape: pl.BlockSpec(
        (1, 1) + shape, lambda n, j, *_: (n, j, 0, 0))
    block = pl.BlockSpec((1, 1, hb, dk, dv),
                         lambda n, j, layer_ref, *_: (layer_ref[0], n, j, 0, 0))
    rows, o = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        name="kda_row_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N, nb),
            in_specs=[small(dk, 4 * hb), small(hb, dv), block],
            out_specs=[block, small(hb, dv)],
        ),
        out_shape=[jax.ShapeDtypeStruct(rows.shape, f32),
                   jax.ShapeDtypeStruct((N, nb, hb, dv), f32)],
        # the rows: argument 4 (after two prefetched scalars, cols, b v)
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live.astype(jnp.int32),
      cols, (v.astype(f32) * b).reshape(N, nb, hb, dv), rows)
    return rows, o.reshape(N, H, dv)
