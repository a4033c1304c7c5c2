"""Single-pass Pallas Adam/AdamW update for the flat-shard hot path.

The XLA update in ops/adam.py is already fused into a few elementwise
kernels, but each still streams p/g/m/v through HBM separately and the
fp32->bf16 master-weight cast is one more full-param pass. This kernel
does the whole per-leaf update — m/v moment update, bias correction,
weight decay, parameter step, dtype cast-back, and (optionally) the
compute-dtype cast of the new params — in ONE read of (p, g, m, v) and
one write of the outputs, with `input_output_aliases` donating the p/m/v
buffers so XLA can update in place inside the engine's donated train
step. Reference capability: csrc/adam/multi_tensor_adam.cu (the
multi-tensor apply over flattened shards).

Math is bit-compatible with FusedAdam.leaf: all arithmetic in fp32,
storage dtypes preserved. Static hyperparameters (betas, eps, weight
decay, mode) are baked into the kernel; the traced scalars (lr and the
two bias corrections, which depend on the step counter) ride in one SMEM
row so no scalar ever forces a recompile.

Leaves are viewed as (rows, last_dim) and the grid tiles rows; leaves
whose geometry finds no legal row block (or that are too small to be
worth a kernel launch) fall back to the XLA path per-leaf — a pytree may
mix both freely.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _compiler_params, _vmem_spec, pltpu

# per-block working set is ~10 arrays of the block (4 in + up to 4 out +
# fp32 temporaries); 128K elements keeps the worst case (all-fp32) ~6.5MB
_BUDGET_ELEMS = 128 * 1024
# below this, per-launch overhead beats the saved HBM passes (auto mode)
MIN_AUTO_SIZE = 16384


def _smem_spec(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape),
                        memory_space=pltpu.SMEM)


def _leaf_2d(shape):
    if len(shape) == 0:
        return None
    if len(shape) == 1:
        return (1, shape[0])
    rows = 1
    for d in shape[:-1]:
        rows *= d
    return (rows, shape[-1])


def _row_block(R, C):
    if C > _BUDGET_ELEMS:
        return None
    for br in (512, 256, 128, 64, 32, 16, 8):
        if br <= R and R % br == 0 and br * C <= _BUDGET_ELEMS:
            return br
    if R * C <= _BUDGET_ELEMS:
        return R
    return None


def supports(shape) -> bool:
    two_d = _leaf_2d(tuple(shape))
    return two_d is not None and _row_block(*two_d) is not None


def _adam_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref,
                 op_ref, om_ref, ov_ref, oc_ref=None, *,
                 b1, b2, eps, wd, adam_w):
    lr = scal_ref[0, 0]
    bc1 = scal_ref[0, 1]
    bc2 = scal_ref[0, 2]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    if wd and not adam_w:
        g = g + wd * p
    m_ = b1 * m + (1.0 - b1) * g
    v_ = b2 * v + (1.0 - b2) * (g * g)
    denom = jnp.sqrt(v_ / bc2) + eps
    upd = (m_ / bc1) / denom
    if wd and adam_w:
        upd = upd + wd * p
    p_ = p - lr * upd
    op_ref[...] = p_.astype(op_ref.dtype)
    om_ref[...] = m_.astype(om_ref.dtype)
    ov_ref[...] = v_.astype(ov_ref.dtype)
    if oc_ref is not None:
        oc_ref[...] = p_.astype(oc_ref.dtype)


def fused_adam_leaf(p, g, m, v, lr, bc1, bc2, *, b1, b2, eps, wd, adam_w,
                    cast_dtype=None, interpret=False):
    """One fused update for one pytree leaf.

    Returns (new_p, new_m, new_v) — plus new_p cast to ``cast_dtype`` as a
    fourth element when requested — or None when the leaf geometry has no
    legal row block (caller must fall back to the XLA leaf math).
    ``lr``/``bc1``/``bc2`` may be traced scalars.
    """
    two_d = _leaf_2d(p.shape)
    if two_d is None:
        return None
    R, C = two_d
    br = _row_block(R, C)
    if br is None:
        return None
    scal = jnp.stack([
        jnp.asarray(lr, jnp.float32).reshape(()),
        jnp.asarray(bc1, jnp.float32).reshape(()),
        jnp.asarray(bc2, jnp.float32).reshape(()),
        jnp.zeros((), jnp.float32),
    ]).reshape(1, 4)
    rows = _vmem_spec((br, C), lambda i: (i, 0))
    out_shape = [
        jax.ShapeDtypeStruct((R, C), p.dtype),
        jax.ShapeDtypeStruct((R, C), m.dtype),
        jax.ShapeDtypeStruct((R, C), v.dtype),
    ]
    if cast_dtype is not None:
        out_shape.append(jax.ShapeDtypeStruct((R, C), cast_dtype))
    kernel = functools.partial(
        _adam_kernel, b1=b1, b2=b2, eps=eps, wd=wd, adam_w=adam_w
    )
    out = pl.pallas_call(
        kernel,
        name="fused_adam",
        grid=(R // br,),
        in_specs=[_smem_spec((1, 4)), rows, rows, rows, rows],
        out_specs=[rows] * len(out_shape),
        out_shape=out_shape,
        # p/m/v are read once and fully overwritten: let XLA reuse the
        # buffers (the engine's donated train step makes them dead after
        # this op). scal is input 0, so p/g/m/v are inputs 1..4.
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret,
        **_compiler_params(interpret, 1),
    )(scal, p.reshape(R, C), g.reshape(R, C), m.reshape(R, C),
      v.reshape(R, C))
    return tuple(o.reshape(p.shape) for o in out)
