"""A decode step's update of the state-space rows, in place.

A ``mamba_attn`` layer keeps, for every slot and state-space head, a state
``H`` of ``head_dim x d_state`` float32 (4 MiB a slot and layer at 32 x
128 x 256). One token a slot moves every row: ``H <- a H + dx (x) B`` and
reads it once more for ``y = H C``. XLA computes the two in two fusions
and reads each row twice; here a row crosses HBM once each way: the grid
is (slot, block of ``hb`` heads), a step takes the block's rows ``(hb,
head_dim, d_state)`` into VMEM, updates them, sums ``y`` over the state's
lanes and writes the rows back to where they came from (the whole stacked
``(layers, slots, heads, head_dim, d_state)`` array is aliased in and
out, the layer a prefetched scalar: nothing of it is sliced or copied).

Small operands: the decay ``a`` (slots x heads) is a prefetched scalar a
head; ``dx = delta x`` comes transposed, ``(slots, head blocks, head_dim,
hb)``, so that a head's column broadcasts over the state's lanes and
``y`` leaves in the same shape; B and C are a group's rows of ``d_state``.
A slot that is not live (idle, or its prompt still entering in chunks)
keeps its rows: they are read and written back as they were. The oracle
is models/mixers.ssm_rows_xla.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernel_config

HEADS_PER_STEP = 8       # 8 x 128 x 256 float32 = 1 MiB in, 1 MiB out a step


def heads_per_step(n_heads: int, n_groups: int) -> int:
    """Heads one grid step takes: they share a group's B and C."""
    per_group = n_heads // n_groups
    return next(b for b in (HEADS_PER_STEP, 4, 2, 1) if per_group % b == 0)


def is_available(rows, n_groups: int) -> bool:
    """rows: (L, N, Hs, P, Nst) float32. A head's state must be whole
    tiles (``P`` whole sublanes, ``Nst`` whole lanes)."""
    if not kernel_config.on_tpu():
        return False
    _, _, Hs, P, Nst = rows.shape
    return (rows.dtype == jnp.float32 and P % 8 == 0 and Nst % 128 == 0
            and Hs % n_groups == 0)


def _kernel(layer_ref, live_ref, a_ref, dx_ref, b_ref, c_ref, h_ref,
            h_out_ref, y_ref, *, hb, Hs):
    n, j = pl.program_id(0), pl.program_id(1)
    live = live_ref[n] > 0
    Bv, Cv = b_ref[0, 0], c_ref[0, 0]                       # (1, Nst)
    dx = dx_ref[0, 0]                                       # (P, hb)
    lane = jax.lax.broadcasted_iota(jnp.int32, dx.shape, 1)
    y = jnp.zeros(dx.shape, jnp.float32)
    for i in range(hb):
        h = h_ref[0, 0, i]                                  # (P, Nst)
        new = a_ref[n * Hs + j * hb + i] * h + dx[:, i:i + 1] * Bv
        y = jnp.where(lane == i, jnp.sum(new * Cv, axis=1, keepdims=True), y)
        h_out_ref[0, 0, i] = jnp.where(live, new, h)
    y_ref[0, 0] = y


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_row_update(rows, layer, decay, dx, Bm, Cm, live, interpret=False):
    """models/mixers.ssm_rows_xla as a kernel (its docstring has the
    contract): rows (L, N, Hs, P, Nst) float32, updated in place at
    ``layer``; decay (N, Hs); dx (N, Hs, P); Bm, Cm (N, G, Nst); live
    (N,) bool. Returns (rows', y (N, Hs, P))."""
    L, N, Hs, P, Nst = rows.shape
    G = Bm.shape[1]
    hb = heads_per_step(Hs, G)
    nb, per_group = Hs // hb, (Hs // G) // hb
    f32 = jnp.float32
    dx_t = jnp.swapaxes(dx.astype(f32).reshape(N, nb, hb, P), 2, 3)
    small = lambda width: pl.BlockSpec(
        (1, 1, P, width), lambda n, j, *_: (n, j, 0, 0))
    group = pl.BlockSpec((1, 1, 1, Nst),
                         lambda n, j, *_: (n, j // per_group, 0, 0))
    block = pl.BlockSpec((1, 1, hb, P, Nst),
                         lambda n, j, layer_ref, *_: (layer_ref[0], n, j, 0, 0))
    rows, y = pl.pallas_call(
        functools.partial(_kernel, hb=hb, Hs=Hs),
        name="ssm_row_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N, nb),
            in_specs=[small(hb), group, group, block],
            out_specs=[block, small(hb)],
        ),
        out_shape=[jax.ShapeDtypeStruct(rows.shape, f32),
                   jax.ShapeDtypeStruct((N, nb, P, hb), f32)],
        # the rows: argument 6 (after three prefetched scalars, dx, B, C)
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live.astype(jnp.int32),
      decay.astype(f32).reshape(-1), dx_t,
      Bm.astype(f32)[:, :, None, :], Cm.astype(f32)[:, :, None, :], rows)
    return rows, jnp.swapaxes(y, 2, 3).reshape(N, Hs, P)
