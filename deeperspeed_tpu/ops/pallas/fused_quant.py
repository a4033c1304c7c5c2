"""Fused blockwise quantize/dequantize kernels for the comm wire formats.

PR 6's reducer lowered its int8/compressed wire math as a chain of
separate XLA ops (abs-max, scale, divide, round, cast, multiply, sum) —
each a full pass over the gradient bucket, all serialized on the
critical path after backward: int8 cuts the wire bytes 3.69x (the HLO
audit of scripts/comm_bench.py) and pays for it in passes. This module is the
EQuARX-style answer (PAPERS.md, arXiv 2506.17615): single-pass Pallas
kernels that read each gradient block once and emit everything the wire
needs —

  * quantize: per-block abs-max scale, round-to-nearest int8, and the
    error-feedback residual, in one VMEM pass (three outputs, one read);
  * unpack+dequant+accumulate: the post-collective ``sum_w q_w * s_w``
    contraction without materializing W dequantized copies;
  * dequant: the final scale-and-average rebuild.

Routing follows the PR 3 kernel layer: :func:`routing` consults
``kernel_config.resolve("fused_quant")`` —

  off    — reducer keeps its original unfused chains (byte-identical
           graphs to PR 6, the safe fallback);
  auto   — Pallas on TPU when :func:`supports` passes; elsewhere the
           single-expression XLA forms below (same math fused by XLA,
           fewer materialized temporaries than the reference chain);
  fused  — force the Pallas kernels, interpret mode off-TPU so CPU CI
           tests the real kernel graphs.

The XLA fallback forms are arranged to be **bit-identical** to the
reference ``quantize_int8_blocks``/``dequantize_int8_blocks`` chain
(same op order; the reference's clip is dropped because it is provably
a no-op: ``|x| <= 127*s`` by construction of ``s``), so flipping the
kernels knob cannot move a loss curve on CPU.

Scale transport: collectives ship ONE packed int8 payload per phase
(:func:`pack_wire`), the f32 block scales bitcast into 4 trailing bytes
per block, instead of PR 6's separate value/scale collectives — half
the collective launches per bucket for the same wire bytes.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _compiler_params, _vmem_spec

__all__ = [
    "routing", "supports", "quantize_rows", "dequant_sum_rows",
    "dequant_rows", "quantize_blocks", "dequantize_blocks",
    "pack_wire", "unpack_wire",
]

# largest tile (in rows of `block` lanes) a single kernel program handles
_MAX_TILE_ROWS = 128


def routing():
    """Wire-format kernel decision: ``("off"|"xla"|"pallas", interpret)``.

    Called by the reducer at trace time; process-global like the rest of
    the kernel layer (ops/kernel_config.py).
    """
    from ..kernel_config import get, resolve

    st = get()
    if st.mode == "off" or not st.fused_quant:
        return "off", False
    use_pallas, interpret = resolve("fused_quant")
    if use_pallas:
        return "pallas", interpret
    return "xla", False


def supports(block: int) -> bool:
    """Geometry gate for the compiled (Mosaic) path: the block is the
    lane dimension of every tile, so it must fill 128-lane registers."""
    return block >= 128 and block % 128 == 0


_SUBLANES = 8


def _pad_rows(x, axis: int):
    """Zero-pad the tiled row axis to a multiple of 8 sublanes: the TPU
    lowering refuses a block whose second-to-last dim is neither a
    multiple of 8 nor the full extent, and the row counts here (world
    size x blocks per chunk) are arbitrary. Zero rows quantize to zeros
    and dequantize to zeros; callers slice them off."""
    pad = -x.shape[axis] % _SUBLANES
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _tile_rows(n_rows: int) -> int:
    """Largest multiple-of-8 divisor of ``n_rows`` (itself a multiple of
    8, see :func:`_pad_rows`) that is <= _MAX_TILE_ROWS."""
    cap = min(n_rows, _MAX_TILE_ROWS)
    return max(d for d in range(_SUBLANES, cap + 1, _SUBLANES)
               if n_rows % d == 0)


def _use_pallas(choice: str, interpret: bool, block: int) -> bool:
    return choice == "pallas" and (interpret or supports(block))


# --------------------------------------------------------------------------
# quantize + scale (+ residual): one pass over the bucket
# --------------------------------------------------------------------------


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    s = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)  # all-zero block: scale 1 -> q == 0
    q_ref[...] = jnp.rint(x / s).astype(jnp.int8)
    s_ref[...] = s


def _quant_residual_kernel(x_ref, q_ref, s_ref, r_ref):
    x = x_ref[...].astype(jnp.float32)
    s = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    q = jnp.rint(x / s)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = s
    r_ref[...] = x - q * s  # error feedback, same read


def _quantize_rows_xla(x, block, want_residual):
    R, C = x.shape
    nb = C // block
    xb = x.astype(jnp.float32).reshape(R, nb, block)
    s = jnp.max(jnp.abs(xb), axis=2) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    qf = jnp.rint(xb / s[:, :, None])
    q = qf.astype(jnp.int8)
    r = (xb - qf * s[:, :, None]).reshape(R, C) if want_residual else None
    return q.reshape(R, C), s, r


def quantize_rows(x, block, *, want_residual=True, choice="xla",
                  interpret=False):
    """Blockwise int8 quantization of ``(R, C)`` rows (``block | C``).

    Returns ``(q (R, C) int8, s (R, C//block) f32, residual | None)``
    where ``residual = x - dequant(q, s)`` (the error-feedback term,
    emitted by the same kernel pass that produced ``q``).
    """
    R, C = x.shape
    nb = C // block
    if not _use_pallas(choice, interpret, block):
        return _quantize_rows_xla(x, block, want_residual)
    NB = R * nb
    x2 = _pad_rows(x.astype(jnp.float32).reshape(NB, block), 0)
    NBp = x2.shape[0]
    br = _tile_rows(NBp)
    spec = _vmem_spec((br, block), lambda i: (i, 0))
    sspec = _vmem_spec((br, 1), lambda i: (i, 0))
    outs = [jax.ShapeDtypeStruct((NBp, block), jnp.int8),
            jax.ShapeDtypeStruct((NBp, 1), jnp.float32)]
    out_specs = [spec, sspec]
    kernel = _quant_kernel
    if want_residual:
        kernel = _quant_residual_kernel
        outs.append(jax.ShapeDtypeStruct((NBp, block), jnp.float32))
        out_specs.append(spec)
    got = pl.pallas_call(
        kernel,
        name="quantize_rows",
        grid=(NBp // br,),
        in_specs=[spec],
        out_specs=out_specs,
        out_shape=outs,
        interpret=interpret,
        **_compiler_params(interpret, 1),
    )(x2)
    q, s = got[0][:NB].reshape(R, C), got[1][:NB].reshape(R, nb)
    r = got[2][:NB].reshape(R, C) if want_residual else None
    return q, s, r


# --------------------------------------------------------------------------
# unpack + dequant + accumulate: sum_w q_w * s_w without W f32 copies
# --------------------------------------------------------------------------


def _dequant_sum_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)  # (R, bn, block)
    s = s_ref[...].astype(jnp.float32)  # (R, bn, 1): one scale per sublane
    o_ref[...] = jnp.sum(q * s, axis=0)


def dequant_sum_rows(q, s, block, *, choice="xla", interpret=False):
    """``sum_r dequant(q[r], s[r])`` -> ``(C,) f32``.

    ``q`` is ``(R, C)`` int8 (or f16 mantissas for the compressed wire),
    ``s`` is ``(R, C//block)`` f32 per-block scales. This is the
    post-all_to_all partial-sum / post-all_gather rebuild contraction.
    """
    R, C = q.shape
    nb = C // block
    if not _use_pallas(choice, interpret, block):
        vals = q.astype(jnp.float32).reshape(R, nb, block) * s[:, :, None]
        return jnp.sum(vals, axis=0).reshape(-1)
    q3 = _pad_rows(q.reshape(R, nb, block), 1)
    s3 = _pad_rows(s.reshape(R, nb, 1), 1)
    nbp = q3.shape[1]
    bn = _tile_rows(nbp)
    out = pl.pallas_call(
        _dequant_sum_kernel,
        name="dequant_sum_rows",
        grid=(nbp // bn,),
        in_specs=[_vmem_spec((R, bn, block), lambda j: (0, j, 0)),
                  _vmem_spec((R, bn, 1), lambda j: (0, j, 0))],
        out_specs=_vmem_spec((bn, block), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, block), jnp.float32),
        interpret=interpret,
        **_compiler_params(interpret, 1),
    )(q3, s3)
    return out[:nb].reshape(-1)


# --------------------------------------------------------------------------
# dequant (+ divide): the final rebuild of every shard's chunk
# --------------------------------------------------------------------------


def _dequant_kernel(q_ref, s_ref, o_ref, *, divisor):
    q = q_ref[...].astype(jnp.float32)  # (1, bn, block)
    s = s_ref[...].astype(jnp.float32)  # (1, bn, 1)
    o_ref[...] = q * s / divisor


def dequant_rows(q, s, block, *, divisor=1.0, choice="xla",
                 interpret=False):
    """``dequant(q, s) / divisor`` -> ``(R, C) f32`` (divisor = world
    size for the mean)."""
    R, C = q.shape
    nb = C // block
    if not _use_pallas(choice, interpret, block):
        vals = q.astype(jnp.float32).reshape(R, nb, block) * s[:, :, None]
        return (vals / divisor).reshape(R, C)
    q3 = _pad_rows(q.reshape(R, nb, block), 1)
    s3 = _pad_rows(s.reshape(R, nb, 1), 1)
    nbp = q3.shape[1]
    bn = _tile_rows(nbp)
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, divisor=float(divisor)),
        name="dequant_rows",
        grid=(R, nbp // bn),
        in_specs=[_vmem_spec((1, bn, block), lambda i, j: (i, j, 0)),
                  _vmem_spec((1, bn, 1), lambda i, j: (i, j, 0))],
        out_specs=_vmem_spec((1, bn, block), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((R, nbp, block), jnp.float32),
        interpret=interpret,
        **_compiler_params(interpret, 2),
    )(q3, s3)
    return out[:, :nb].reshape(R, C)


# --------------------------------------------------------------------------
# flat convenience API (parity tests, tpu_smoke) — pads like the plan does
# --------------------------------------------------------------------------


def quantize_blocks(x, block, *, choice="pallas", interpret=True):
    """Fused counterpart of ``reducer.quantize_int8_blocks`` accepting
    any-length (and bf16) input: pads to a whole block like the bucket
    plan, returns ``((nb, block) int8, (nb,) f32)``."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    nb = -(-n // block)
    flat = jnp.pad(flat, (0, nb * block - n))
    q, s, _ = quantize_rows(flat.reshape(1, -1), block,
                            want_residual=False, choice=choice,
                            interpret=interpret)
    return q.reshape(nb, block), s.reshape(-1)


def dequantize_blocks(q, s, *, choice="pallas", interpret=True):
    """Fused counterpart of ``reducer.dequantize_int8_blocks``."""
    nb, block = q.shape
    return dequant_rows(q.reshape(1, -1), s.reshape(1, -1), block,
                        choice=choice, interpret=interpret).reshape(-1)


# --------------------------------------------------------------------------
# packed wire layout: values + bitcast scales in ONE int8 payload
# --------------------------------------------------------------------------


def pack_wire(q, s):
    """``(R, C) int8`` values + ``(R, nb) f32`` scales -> one
    ``(R, C + 4*nb) int8`` collective payload (scales bitcast to 4
    trailing bytes per block)."""
    sb = jax.lax.bitcast_convert_type(s, jnp.int8)  # (R, nb, 4)
    return jnp.concatenate([q, sb.reshape(s.shape[0], -1)], axis=1)


def unpack_wire(w, values, block):
    """Inverse of :func:`pack_wire` for a ``(R, values + 4*values//block)``
    payload -> ``(q (R, values) int8, s (R, values//block) f32)``."""
    nb = values // block
    q = w[:, :values]
    s = jax.lax.bitcast_convert_type(
        w[:, values:].reshape(w.shape[0], nb, 4), jnp.float32)
    return q, s
