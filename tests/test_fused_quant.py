"""Interpret-mode parity tests for the fused wire-format kernels
(ops/pallas/fused_quant) against the reducer's unfused reference path.

Two bars, matching the two routes :func:`fused_quant.routing` can pick
off-TPU:

* the XLA route (``kernels: auto`` on CPU) must be **bit-identical** to
  the reference ``quantize_int8_blocks`` chain — the only formal
  difference is the reference's clip, which is a provable no-op;
* the Pallas route (``kernels: fused`` -> interpret mode on CPU) may
  differ by compiler rounding (interpret lowers the scale division as a
  reciprocal multiply), so it gets max-rel-err bounds: scales within an
  ulp, values within one quantization quantum.

Shapes cover the ISSUE 11 checklist: non-block-divisible lengths (the
flat API pads like the bucket plan), all-zero blocks (scale must clamp
to 1, q to 0), and bf16 inputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.ops import kernel_config
from deeperspeed_tpu.ops.pallas import fused_quant as fq
from deeperspeed_tpu.runtime.comm.reducer import (
    dequantize_int8_blocks,
    quantize_int8_blocks,
)

BLOCK = 8


def _rows(seed, r, c, zero_block=None):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((r, c)).astype(np.float32)
    if zero_block is not None:
        i, j = zero_block
        x[i, j * BLOCK:(j + 1) * BLOCK] = 0.0
    return x


def _ref_rows(x):
    """Reference (unfused) quantization applied row by row."""
    qs = [quantize_int8_blocks(jnp.asarray(r), BLOCK) for r in x]
    q = np.stack([np.asarray(q).reshape(-1) for q, _ in qs])
    s = np.stack([np.asarray(s) for _, s in qs])
    dq = np.stack([
        np.asarray(dequantize_int8_blocks(jnp.asarray(qr.reshape(-1, BLOCK)),
                                          jnp.asarray(sr)))
        for qr, sr in zip(q, s)])
    return q, s, dq


# --------------------------------------------------------------------- #
# XLA route: bit-identical to the reference chain
# --------------------------------------------------------------------- #


def test_xla_route_bit_identical_to_reference():
    x = _rows(0, 4, 64, zero_block=(1, 2))
    qr, sr, dqr = _ref_rows(x)
    q, s, r = fq.quantize_rows(jnp.asarray(x), BLOCK, want_residual=True,
                               choice="xla")
    np.testing.assert_array_equal(np.asarray(q), qr)
    np.testing.assert_array_equal(np.asarray(s), sr)
    np.testing.assert_array_equal(np.asarray(r), x - dqr)
    # dequant-accumulate == jnp.sum of the reference dequantized rows
    ds = fq.dequant_sum_rows(q, s, BLOCK, choice="xla")
    ref = np.asarray(jnp.sum(jnp.asarray(dqr), axis=0))
    np.testing.assert_array_equal(np.asarray(ds), ref)
    # final rebuild with the mean divisor
    d = fq.dequant_rows(q, s, BLOCK, divisor=4, choice="xla")
    np.testing.assert_array_equal(
        np.asarray(d), np.asarray(jnp.asarray(dqr) / 4))


def test_all_zero_input_quantizes_to_zero():
    x = np.zeros((2, 32), np.float32)
    for choice, interp in [("xla", False), ("pallas", True)]:
        q, s, r = fq.quantize_rows(jnp.asarray(x), BLOCK,
                                   want_residual=True, choice=choice,
                                   interpret=interp)
        assert np.asarray(q).max() == 0 and np.asarray(q).min() == 0
        np.testing.assert_array_equal(np.asarray(s), np.ones((2, 4)))
        np.testing.assert_array_equal(np.asarray(r), x)


# --------------------------------------------------------------------- #
# Pallas route (interpret): max-rel-err bounds vs the reference
# --------------------------------------------------------------------- #


def _assert_quant_close(q, s, qr, sr):
    """Scales within an ulp, values within one quantization quantum."""
    np.testing.assert_allclose(np.asarray(s), sr, rtol=2e-7)
    dq = np.abs(np.asarray(q).astype(np.int32) - qr.astype(np.int32))
    assert dq.max() <= 1, f"q differs by {dq.max()} quanta"
    assert (dq > 0).mean() < 0.01  # rounding-edge flips only


def test_pallas_interpret_parity():
    x = _rows(1, 4, 64, zero_block=(0, 3))
    qr, sr, dqr = _ref_rows(x)
    q, s, r = fq.quantize_rows(jnp.asarray(x), BLOCK, want_residual=True,
                               choice="pallas", interpret=True)
    _assert_quant_close(q, s, qr, sr)
    # residual: x - q*s for THIS (q, s); off from the reference residual
    # by at most one quantum per element
    np.testing.assert_allclose(
        np.asarray(r), x - np.asarray(q).astype(np.float32).reshape(
            4, -1, BLOCK).reshape(4, 64) * np.repeat(np.asarray(s), BLOCK,
                                                     axis=1),
        rtol=0, atol=1e-6)
    ds = fq.dequant_sum_rows(jnp.asarray(qr), jnp.asarray(sr), BLOCK,
                             choice="pallas", interpret=True)
    ref = np.asarray(jnp.sum(jnp.asarray(dqr), axis=0))
    np.testing.assert_allclose(np.asarray(ds), ref, rtol=1e-6, atol=1e-7)
    d = fq.dequant_rows(jnp.asarray(qr), jnp.asarray(sr), BLOCK, divisor=4,
                        choice="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(d), dqr / 4, rtol=1e-6,
                               atol=1e-7)


@pytest.mark.parametrize("n", [45, 63, 129])  # none divisible by 16
def test_flat_api_pads_non_block_divisible(n):
    x = _rows(2, 1, n + 3)[0, :n]
    nb = -(-n // 16)
    pad = np.pad(x, (0, nb * 16 - n))
    q0, s0 = quantize_int8_blocks(jnp.asarray(pad), 16)
    for choice, interp in [("xla", False), ("pallas", True)]:
        q, s = fq.quantize_blocks(jnp.asarray(x), 16, choice=choice,
                                  interpret=interp)
        assert q.shape == (nb, 16) and s.shape == (nb,)
        _assert_quant_close(q.reshape(1, -1), s[None],
                            np.asarray(q0).reshape(1, -1),
                            np.asarray(s0)[None])


def test_bf16_input_parity():
    x = _rows(3, 1, 64)[0]
    xb = jnp.asarray(x, dtype=jnp.bfloat16)
    # reference on the f32 view of the SAME bf16 values
    q0, s0 = quantize_int8_blocks(xb.astype(jnp.float32), BLOCK)
    for choice, interp in [("xla", False), ("pallas", True)]:
        q, s = fq.quantize_blocks(xb, BLOCK, choice=choice,
                                  interpret=interp)
        _assert_quant_close(q.reshape(1, -1), s[None],
                            np.asarray(q0).reshape(1, -1),
                            np.asarray(s0)[None])
        # reconstruction tracks the bf16 input within the quantization
        # error bound (half a quantum per element)
        dq = np.asarray(fq.dequantize_blocks(q, s, choice=choice,
                                             interpret=interp))
        bound = np.repeat(np.asarray(s), BLOCK) * 0.5000001
        assert (np.abs(dq - np.asarray(xb, np.float32)) <= bound).all()


# --------------------------------------------------------------------- #
# wire packing + routing
# --------------------------------------------------------------------- #


def test_pack_unpack_wire_roundtrip():
    x = _rows(4, 8, 128)
    q, s, _ = fq.quantize_rows(jnp.asarray(x), BLOCK, want_residual=False,
                               choice="xla")
    w = fq.pack_wire(q, s)
    assert w.shape == (8, 128 + 4 * 16) and w.dtype == jnp.int8
    q2, s2 = fq.unpack_wire(w, 128, BLOCK)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(q))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s))


def test_routing_follows_kernel_config():
    with kernel_config.override(mode="off"):
        assert fq.routing() == ("off", False)
    with kernel_config.override(mode="auto"):
        # off-TPU auto -> the fused XLA formulation, not Pallas
        assert fq.routing() == ("xla", False)
    with kernel_config.override(mode="fused"):
        choice, interpret = fq.routing()
        assert choice == "pallas"
        assert interpret or jax.devices()[0].platform == "tpu"
    with kernel_config.override(mode="auto", fused_quant=False):
        assert fq.routing() == ("off", False)


def test_supports_gate_and_tiling():
    assert fq.supports(128) and fq.supports(256)
    assert not fq.supports(8) and not fq.supports(130)
    # row axes are zero-padded to 8 sublanes before tiling (the TPU
    # lowering refuses blocks of 13 or 65 rows): 13 -> 16, 260 -> 264
    assert fq._pad_rows(jnp.ones((13, 128)), 0).shape == (16, 128)
    assert fq._pad_rows(jnp.ones((2, 260, 1)), 1).shape == (2, 264, 1)
    assert fq._tile_rows(104) == 104  # fits one tile, multiple of 8
    assert fq._tile_rows(16) == 16
    assert fq._tile_rows(1024) == 128
    assert fq._tile_rows(264) == 88   # largest multiple-of-8 divisor
