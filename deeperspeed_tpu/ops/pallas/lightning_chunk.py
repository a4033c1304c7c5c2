"""Lightning (decayed linear) attention over one prompt chunk, chunkwise.

Per head h with decay ``lam = exp(-s_h)``: ``S_t = lam S_{t-1} + k_t^T
v_t`` and ``o_t = (q_t / sqrt(Dh)) S_t``. Over a block of B positions that
sum splits into the masked product inside the block, ``((Q K^T) * D) V``
with ``D[i, j] = lam^(i-j)`` for ``j <= i``, and what the state carried in
adds, ``lam^(i+1) Q S``; the state leaves the block as ``lam^B S + (K *
lam^(B-1-j))^T V``. The grid is (head, block): a head's blocks run in order
with its ``(Dh, Dh)`` float32 state in VMEM between them, so q, k, v and o
cross HBM once and the state twice a chunk.

Of the C positions the first ``n_valid`` are real (the last chunk of a
prompt is padded): the others neither decay the state nor add to it, and
their outputs mean nothing. Inputs keep the projection's layout ``(C, H *
Dh)``: a head's block is a lane-aligned ``(B, Dh)`` window of it. Products
of q and k are taken in their dtype with float32 accumulation; the masked
scores, the state and everything they multiply stay float32 (4% of a
chunk's operations, next to the projections). The oracle is
models/mixers.lightning_chunk_xla.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernel_config

BLOCK = 256


def block_for(C: int) -> int:
    return next(b for b in (BLOCK, 128, 64, 32, 16, 8) if C % b == 0) \
        if C % 8 == 0 else 0


def is_available(q) -> bool:
    """q: (C, H, Dh). Whole lanes a head, whole sublane tiles a block."""
    if not kernel_config.on_tpu():
        return False
    C, _, Dh = q.shape
    return Dh % 128 == 0 and block_for(C) >= 32 // q.dtype.itemsize


def _kernel(nv_ref, slopes_ref, q_ref, k_ref, v_ref, s_in_ref, o_ref,
            s_out_ref, S, *, B, scale):
    h, i = pl.program_id(0), pl.program_id(1)
    s = slopes_ref[h]

    @pl.when(i == 0)
    def _():
        S[...] = s_in_ref[0]

    nvl = jnp.clip(nv_ref[0] - i * B, 0, B)
    row = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)
    cnt_r = jnp.minimum(row + 1, nvl).astype(jnp.float32)   # decays so far
    cnt_c = jnp.minimum(col + 1, nvl).astype(jnp.float32)
    q, k = q_ref[...], k_ref[...]
    v = v_ref[...].astype(jnp.float32)
    a = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    keep = (col <= row) & (col < nvl)
    a = jnp.where(keep, a * jnp.exp(-s * (cnt_r - cnt_c)), 0.0)
    o = jnp.dot(a, v, preferred_element_type=jnp.float32)
    o = o + jnp.exp(-s * cnt_r) * jnp.dot(
        q.astype(jnp.float32) * scale, S[...],
        preferred_element_type=jnp.float32)
    o_ref[...] = o.astype(o_ref.dtype)
    kd = jnp.where(row < nvl, k.astype(jnp.float32)
                   * jnp.exp(-s * (nvl.astype(jnp.float32) - cnt_r)), 0.0)
    S[...] = jnp.exp(-s * nvl.astype(jnp.float32)) * S[...] \
        + jax.lax.dot_general(kd, v, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        s_out_ref[0] = S[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def lightning_chunk(q, k, v, s_in, slopes, n_valid, block=None,
                    interpret=False):
    """models/mixers.lightning_chunk_xla as a kernel: q, k, v (C, H, Dh);
    s_in (H, Dh, Dh) float32; -> (o (C, H, Dh) float32, state out)."""
    C, H, Dh = q.shape
    B = block or block_for(C)
    flat = lambda t: t.reshape(C, H * Dh)
    tok = pl.BlockSpec((B, Dh), lambda h, i, *_: (i, h))
    state = pl.BlockSpec((1, Dh, Dh), lambda h, i, *_: (h, 0, 0))
    o, s_out = pl.pallas_call(
        functools.partial(_kernel, B=B, scale=1.0 / math.sqrt(Dh)),
        name="lightning_chunk",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H, C // B),
            in_specs=[tok, tok, tok, state],
            out_specs=[tok, state],
            scratch_shapes=[pltpu.VMEM((Dh, Dh), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((C, H * Dh), jnp.float32),
                   jax.ShapeDtypeStruct((H, Dh, Dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(n_valid, (1,)).astype(jnp.int32),
      slopes.astype(jnp.float32), flat(q), flat(k), flat(v),
      s_in.astype(jnp.float32))
    return o.reshape(C, H, Dh), s_out
