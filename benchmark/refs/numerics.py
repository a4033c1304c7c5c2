"""The arithmetic a reference multiplies matrices in.

``f32`` is the reference proper: float32 operands, ``highest`` precision
(on a TPU a float32 matmul otherwise runs in bfloat16 passes). The others
are controls: the same mathematics with both operands of every matrix
multiplication rounded to a precision below the bfloat16 that the
configurations state, gradients passing straight through the rounding:

``int8``   8-bit integers, one scale per row of the left operand and per
           column of the right: the most careful 8-bit form
``int8t``  8-bit integers, one scale per tensor: the plain form
``fp8``    float8 e4m3, one scale per tensor

Which of them a cell's check is held against is in its workload file
(``check.control_numerics``), with the readings in PERF.md.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def _round_fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


# how each numerics rounds (the left operand, the right operand)
_ROUNDERS = {
    "f32": None,
    "int8": (lambda a: _round_int8(a, -1),
             lambda b: _round_int8(b, -2 if b.ndim > 1 else 0)),
    "int8t": (lambda a: _round_int8(a, None), lambda b: _round_int8(b, None)),
    "fp8": (_round_fp8, _round_fp8),
}


class Numerics:
    def __init__(self, name: str):
        if name not in _ROUNDERS:
            raise ValueError(f"unknown numerics {name!r}; known {sorted(_ROUNDERS)}")
        self.name = name

    def dot(self, a, b):
        """``a @ b``: a's last axis against b's second-to-last."""
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        if _ROUNDERS[self.name] is not None:
            left, right = _ROUNDERS[self.name]
            a, b = left(a), right(b)
        return jnp.matmul(a, b, precision=HIGHEST)


F32 = Numerics("f32")
