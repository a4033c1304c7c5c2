"""24-bit compressed allreduce (fork extra; reference
/root/reference/deepspeed/runtime/comm/compressed_ar.py:34,42).

The reference decomposes fp32 into an fp16 mantissa + int8 exponent via
frexp (24 bits/element on the wire instead of 32) and allreduces both
pieces. Summing exponents only reconstructs the true sum when world==1 (the
file ships as a single-process demo), so this rebuild keeps the
decompose/reconstruct API for parity but implements the collective with
correct mathematics: block-exponent compression. Each shard normalizes
fixed-size blocks by their max exponent (int8) and quantizes the residual
mantissa to fp16 — 24 bits/element shipped — then every shard rebuilds and
sums the gathered contributions exactly.

Wire cost per element over the mesh axis: 24 bits x world (all_gather),
vs 64 bits (2x fp32) for a ring allreduce; the relative error is bounded by
the fp16 mantissa, ~2^-11 per contribution.
"""

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

BLOCK = 128


# --------------------------------------------------------------------------
# reference-compatible frexp/ldexp pieces (compressed_ar.py:22,29)
# --------------------------------------------------------------------------


def decompose(t) -> Tuple[jax.Array, jax.Array]:
    """fp32 -> (fp16 mantissa in [0.5,1), int8 exponent)."""
    m, e = jnp.frexp(t.astype(jnp.float32))
    return m.astype(jnp.float16), e.astype(jnp.int8)


def reconstruct(mantissa, exponent, original_dtype=jnp.float32):
    return jnp.ldexp(mantissa.astype(jnp.float32),
                     exponent.astype(jnp.int32)).astype(original_dtype)


# --------------------------------------------------------------------------
# block-exponent compression (the correct-sum wire format)
# --------------------------------------------------------------------------


def _compress_blocks(x32, block):
    """(n,) fp32 -> ((nb, block) fp16 mantissas, (nb,) int8 exponents)."""
    n = x32.shape[0]
    nb = (n + block - 1) // block
    pad = nb * block - n
    xb = jnp.pad(x32, (0, pad)).reshape(nb, block)
    # per-block max exponent; ldexp by -e brings the block into [-1, 1]
    _, e = jnp.frexp(jnp.max(jnp.abs(xb), axis=1))
    e = jnp.clip(e, -126, 127).astype(jnp.int8)
    m = jnp.ldexp(xb, -e[:, None].astype(jnp.int32)).astype(jnp.float16)
    return m, e


def _decompress_blocks(m, e, n):
    xb = jnp.ldexp(m.astype(jnp.float32), e[:, None].astype(jnp.int32))
    return xb.reshape(-1)[:n]


def compress(x, block: int = BLOCK):
    """Flatten + block-compress any-shape fp tensor. Returns (m, e, meta)."""
    flat = x.reshape(-1).astype(jnp.float32)
    m, e = _compress_blocks(flat, block)
    return m, e, (x.shape, flat.shape[0])


def decompress(m, e, meta, dtype=jnp.float32):
    shape, n = meta
    return _decompress_blocks(m, e, n).reshape(shape).astype(dtype)


@jax.named_scope("ds.comm/compressed")
def compressed_all_reduce(x, axis_name: str = "data", block: int = BLOCK,
                          average: bool = False):
    """SUM (or mean) allreduce over ``axis_name`` shipping 24 bits/element.

    Traced inside shard_map/pmap. Each shard compresses its contribution,
    all_gathers the (fp16 mantissa, int8 exponent) pair, and rebuilds the
    exact sum of quantized contributions locally — unlike the reference's
    exponent-summing demo, this is correct for any world size.
    """
    m, e, meta = compress(x, block)
    ms = jax.lax.all_gather(m, axis_name)  # (W, nb, block) fp16
    es = jax.lax.all_gather(e, axis_name)  # (W, nb) int8
    world = ms.shape[0]
    vals = jax.vmap(lambda mm, ee: _decompress_blocks(mm, ee, meta[1]))(ms, es)
    total = jnp.sum(vals, axis=0)
    if average:
        total = total / world
    return total.reshape(meta[0]).astype(x.dtype)


def compressed_all_reduce_tree(tree, axis_name: str = "data",
                               block: int = BLOCK, average: bool = False):
    """Apply the compressed allreduce to every leaf of a grad pytree."""
    return jax.tree.map(
        partial(compressed_all_reduce, axis_name=axis_name, block=block,
                average=average),
        tree,
    )


# --------------------------------------------------------------------------
# 1-bit wire format (reference comm/nccl.py:47 compressed_allreduce packs
# sign bits with cupy packbits; here signs pack into uint8 on device)
# --------------------------------------------------------------------------


def _pack_signs(x32):
    """(n,) fp32 -> ((ceil(n/8),) uint8 sign bits, padded length).

    CHUNK-SPLIT bit layout: bit b of byte i carries element b*nb + i —
    the reshape keeps the vector's MINOR dim at nb instead of a trailing
    dim of 8, which the TPU tiled layout pads to the 128-lane width (a
    16x relayout blow-up measured as the 1-bit compressed step running
    ~9x slower than its warmup twin at 162M params; same class of
    hazard as streaming.py's u8->bf16 trailing-dim-2 note)."""
    n = x32.shape[0]
    nb = (n + 7) // 8
    bits = (jnp.pad(x32, (0, nb * 8 - n)) >= 0).astype(jnp.uint8)
    rows = bits.reshape(8, nb)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))[:, None]
    return jnp.sum(rows * weights, axis=0, dtype=jnp.uint8), n


def _unpack_signs(packed, n):
    """uint8 bit rows -> (n,) +-1.0 fp32 (chunk-split layout, see
    _pack_signs)."""
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))[:, None]
    bits = (packed[None, :] & weights) > 0  # (8, nb)
    return jnp.where(bits.reshape(-1)[:n], 1.0, -1.0).astype(jnp.float32)


def onebit_compress(x, error):
    """Error-compensated 1-bit quantization of a flat fp32 tensor.

    Returns (packed uint8 signs, per-tensor scale, new error feedback).
    scale = mean(|corrected|) preserves expected magnitude (reference
    OnebitAdam server scale)."""
    corrected = x.astype(jnp.float32) + error
    scale = jnp.mean(jnp.abs(corrected))
    packed, _ = _pack_signs(corrected)
    # same `>= 0` predicate as the pack — bit-identical to unpacking, but
    # skips the bit-test matrix on the gradient hot path
    quantized = jnp.where(corrected >= 0, scale, -scale)
    return packed, scale, corrected - quantized


@jax.named_scope("ds.comm/onebit")
def onebit_all_reduce(x, axis_name: str = "data", error=None):
    """Average `x` over the mesh axis shipping ~1 bit/element + one scale.

    Traced inside shard_map. Each shard quantizes its contribution with
    error feedback, all_gathers (packed signs, scale), and rebuilds the
    mean of the quantized contributions — the single-phase analog of the
    reference's worker->server->all 1-bit allreduce (comm/nccl.py:47).
    Returns (average, new_error); thread the error back in next step."""
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    if error is None:
        error = jnp.zeros_like(flat)
    packed, scale, new_error = onebit_compress(flat, error.reshape(-1))
    all_packed = jax.lax.all_gather(packed, axis_name)  # (W, nb) u8
    all_scales = jax.lax.all_gather(scale, axis_name)  # (W,)
    n = flat.shape[0]
    vals = jax.vmap(lambda p, s: _unpack_signs(p, n) * s)(
        all_packed, all_scales
    )
    avg = jnp.mean(vals, axis=0)
    return avg.reshape(shape).astype(x.dtype), new_error.reshape(shape)
