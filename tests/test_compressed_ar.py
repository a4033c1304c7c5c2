"""24-bit compressed allreduce tests (reference tests/onebit scripts +
comm/compressed_ar.py analog): compressed collective must track the exact
psum within fp16-mantissa error over the 8-device mesh."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deeperspeed_tpu.runtime.comm.compressed import (
    compress,
    compressed_all_reduce,
    compressed_all_reduce_tree,
    decompose,
    decompress,
    reconstruct,
)

shard_map = partial(jax.shard_map, check_vma=False)


def _mesh():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


def test_decompose_reconstruct_round_trip():
    x = jnp.asarray(np.random.RandomState(0).randn(1024).astype(np.float32) * 100)
    m, e = decompose(x)
    assert m.dtype == jnp.float16 and e.dtype == jnp.int8
    out = reconstruct(m, e)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-3)


def test_compress_decompress_round_trip_odd_sizes():
    for n in (1, 127, 128, 129, 1000):
        x = jnp.asarray(np.random.RandomState(n).randn(n).astype(np.float32))
        m, e, meta = compress(x)
        out = decompress(m, e, meta)
        assert out.shape == x.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                                   rtol=2e-3, atol=1e-6)


def test_compress_wide_dynamic_range():
    # per-block exponents must handle blocks of wildly different scales
    x = np.zeros(256, np.float32)
    x[:128] = np.random.RandomState(0).randn(128) * 1e-6
    x[128:] = np.random.RandomState(1).randn(128) * 1e6
    m, e, meta = compress(jnp.asarray(x))
    out = np.asarray(decompress(m, e, meta))
    np.testing.assert_allclose(out, x, rtol=2e-3)


def test_compressed_all_reduce_matches_psum():
    mesh = _mesh()
    data = np.random.RandomState(0).randn(8, 4096).astype(np.float32)

    @jax.jit
    def run(x):
        def body(x):
            x = x.reshape(-1)
            return (
                compressed_all_reduce(x, "data"),
                jax.lax.psum(x, "data"),
            )

        return shard_map(
            body, mesh=mesh, in_specs=P("data", None),
            out_specs=(P(None), P(None)),
        )(x)

    with mesh:
        comp, exact = run(jnp.asarray(data))
    # abs tolerance = 8 contributions x fp16 mantissa quantum at |x|~4
    np.testing.assert_allclose(np.asarray(comp), np.asarray(exact),
                               rtol=5e-3, atol=5e-3)


def test_compressed_all_reduce_average_and_tree():
    mesh = _mesh()
    data = {
        "w": np.random.RandomState(1).randn(8, 64, 4).astype(np.float32),
        "b": np.random.RandomState(2).randn(8, 10).astype(np.float32),
    }

    @jax.jit
    def run(tree):
        def body(tree):
            tree = jax.tree.map(lambda x: x[0], tree)  # drop shard dim
            return compressed_all_reduce_tree(tree, "data", average=True)

        return shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("data"), data),),
            out_specs=jax.tree.map(lambda _: P(None), data),
        )(tree)

    with mesh:
        out = run(jax.tree.map(jnp.asarray, data))
    for k in data:
        np.testing.assert_allclose(
            np.asarray(out[k]), data[k].mean(axis=0), rtol=5e-3, atol=1e-3
        )


def test_onebit_pack_round_trip():
    from deeperspeed_tpu.runtime.comm.compressed import (
        _pack_signs,
        _unpack_signs,
        onebit_compress,
    )

    x = jnp.asarray(np.random.RandomState(0).randn(1000).astype(np.float32))
    packed, n = _pack_signs(x)
    assert packed.dtype == jnp.uint8 and packed.shape == (125,)
    signs = _unpack_signs(packed, n)
    np.testing.assert_array_equal(np.asarray(signs), np.sign(np.asarray(x)))

    err0 = jnp.zeros_like(x)
    packed, scale, err = onebit_compress(x, err0)
    # quantized + error reconstructs the input exactly (error feedback)
    recon = _unpack_signs(packed, 1000) * scale + err
    np.testing.assert_allclose(np.asarray(recon), np.asarray(x),
                               rtol=1e-5, atol=1e-6)


def test_onebit_all_reduce_error_feedback_converges():
    """Repeatedly reducing the same tensors with error feedback converges
    to the true mean (the EF-SGD property the 1-bit optimizers rely on)."""
    from deeperspeed_tpu.runtime.comm.compressed import onebit_all_reduce

    mesh = _mesh()
    data = np.random.RandomState(0).randn(8, 512).astype(np.float32)
    true_mean = data.mean(axis=0)

    @jax.jit
    def run(x, err):
        def body(x, err):
            return onebit_all_reduce(x.reshape(-1), "data", err.reshape(-1))

        return shard_map(
            body, mesh=mesh,
            in_specs=(P("data", None), P("data", None)),
            out_specs=(P(None), P("data")),
        )(x, err)

    rounds = 60
    err = jnp.zeros_like(jnp.asarray(data))
    with mesh:
        accum = np.zeros_like(true_mean)
        for i in range(rounds):
            avg, err_flat = run(jnp.asarray(data), err)
            err = err_flat.reshape(8, 512)
            accum += np.asarray(avg)
    # the RUNNING MEAN of EF-compressed reductions approaches the true mean
    # (the error-feedback guarantee, O(1/T) in mean absolute error; a
    # per-tensor scale leaves the few largest coordinates oscillating, so
    # the max-norm converges much more slowly — assert on the mean)
    running = accum / rounds
    assert np.abs(running - true_mean).mean() < 0.05
    # and is much closer than any single compressed round
    single = np.asarray(run(jnp.asarray(data),
                            jnp.zeros_like(jnp.asarray(data)))[0])
    assert (np.abs(running - true_mean).mean()
            < 0.3 * np.abs(single - true_mean).mean())


def test_compressed_preserves_dtype():
    mesh = _mesh()
    data = np.random.RandomState(0).randn(8, 256).astype(np.float32)

    @jax.jit
    def run(x):
        def body(x):
            return compressed_all_reduce(x.reshape(-1).astype(jnp.bfloat16), "data")

        return shard_map(body, mesh=mesh, in_specs=P("data", None),
                         out_specs=P(None))(x)

    with mesh:
        out = run(jnp.asarray(data))
    assert out.dtype == jnp.bfloat16


# ---------------------------------------------------------------------- #
# edge cases: non-block-divisible lengths, zeros, bf16, single elements
# ---------------------------------------------------------------------- #


@pytest.mark.parametrize("n", [1, 100, 129, 8 * 128 + 3])
def test_compressed_all_reduce_non_block_divisible(n):
    """The collective must pad/crop correctly when the per-shard length is
    not a multiple of the 128-element block."""
    mesh = _mesh()
    data = np.random.RandomState(n).randn(8, n).astype(np.float32)

    @jax.jit
    def run(x):
        def body(x):
            x = x.reshape(-1)
            return compressed_all_reduce(x, "data"), jax.lax.psum(x, "data")

        return shard_map(body, mesh=mesh, in_specs=P("data", None),
                         out_specs=(P(None), P(None)))(x)

    with mesh:
        comp, exact = run(jnp.asarray(data))
    assert comp.shape == (n,)
    np.testing.assert_allclose(np.asarray(comp), np.asarray(exact),
                               rtol=5e-3, atol=5e-3)


def test_compress_all_zero_tensor():
    """All-zero input: frexp(0) = (0, 0); the round trip must return exact
    zeros with no NaN/inf from the block normalization."""
    x = jnp.zeros(300, jnp.float32)
    m, e, meta = compress(x)
    out = np.asarray(decompress(m, e, meta))
    assert out.shape == (300,)
    np.testing.assert_array_equal(out, np.zeros(300, np.float32))


def test_onebit_compress_all_zero_tensor():
    """Zero gradient + zero error: the mean-|x| scale is 0, the quantized
    output must be exact zeros (not NaN from 0/0) and the error stays 0."""
    from deeperspeed_tpu.runtime.comm.compressed import (
        _unpack_signs, onebit_compress)

    x = jnp.zeros(64, jnp.float32)
    packed, scale, err = onebit_compress(x, jnp.zeros_like(x))
    recon = np.asarray(_unpack_signs(packed, 64) * scale)
    assert np.isfinite(recon).all()
    np.testing.assert_array_equal(recon, np.zeros(64, np.float32))
    np.testing.assert_array_equal(np.asarray(err), np.zeros(64, np.float32))


def test_compress_bf16_input_round_trip():
    """bf16 inputs flow through the fp32 block compressor; the round trip
    must be exact at bf16 resolution (bf16 -> fp32 is lossless, fp16
    mantissas cover bf16's 8 bits)."""
    x32 = np.random.RandomState(3).randn(257).astype(np.float32)
    x = jnp.asarray(x32).astype(jnp.bfloat16)
    m, e, meta = compress(x)
    out = decompress(m, e, meta, dtype=jnp.bfloat16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(out.astype(jnp.float32)),
        np.asarray(x.astype(jnp.float32)))


@pytest.mark.parametrize("n", [1, 2, 3, 7, 8, 9, 15])
def test_pack_signs_odd_sizes_round_trip(n):
    """Single-element and sub-byte lengths: the chunk-split bit layout
    pads to whole bytes; unpack must crop back to exactly n signs."""
    from deeperspeed_tpu.runtime.comm.compressed import (
        _pack_signs, _unpack_signs)

    x = np.random.RandomState(n).randn(n).astype(np.float32)
    x[0] = 0.0  # sign convention: >= 0 packs as +1
    packed, padded = _pack_signs(jnp.asarray(x))
    assert packed.shape == ((n + 7) // 8,)
    assert padded == n
    signs = np.asarray(_unpack_signs(packed, n))
    np.testing.assert_array_equal(signs, np.where(x >= 0, 1.0, -1.0))
