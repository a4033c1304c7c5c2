"""Sparse (CSR) gradient tests — reference tests/unit/test_csr.py analog,
plus the DP allreduce equivalence the engine path relies on."""

import jax
import jax.numpy as jnp
import numpy as np
from functools import partial

from jax.sharding import Mesh, PartitionSpec as P

shard_map = partial(jax.shard_map, check_vma=False)

from deeperspeed_tpu.runtime.csr_tensor import (
    CSRTensor,
    csr_allreduce,
    sparse_embedding_grad_allreduce,
)


def _sparse_dense(rows=32, cols=8, touched=(1, 5, 7, 20), seed=0):
    g = np.zeros((rows, cols), np.float32)
    r = np.random.RandomState(seed)
    for t in touched:
        g[t] = r.randn(cols)
    return jnp.asarray(g)


def test_from_dense_round_trip():
    g = _sparse_dense()
    csr = CSRTensor.from_dense(g, capacity=8)
    np.testing.assert_allclose(np.asarray(csr.to_dense()), np.asarray(g))
    sparse, dense = csr.sparse_size()
    assert sparse < dense


def test_from_dense_cancelling_rows_kept():
    # a row whose entries sum to zero must not be dropped (abs-mass keying)
    g = np.zeros((8, 2), np.float32)
    g[3] = [1.0, -1.0]
    csr = CSRTensor.from_dense(jnp.asarray(g), capacity=4)
    np.testing.assert_allclose(np.asarray(csr.to_dense()), g)


def test_add_concatenates_and_scatter_adds():
    a = CSRTensor.from_dense(_sparse_dense(seed=0), capacity=8)
    b = CSRTensor.from_dense(_sparse_dense(seed=1), capacity=8)
    merged = a.add(b)
    np.testing.assert_allclose(
        np.asarray(merged.to_dense()),
        np.asarray(a.to_dense() + b.to_dense()),
        rtol=1e-6,
    )


def test_repr_and_type():
    csr = CSRTensor.from_dense(_sparse_dense(), capacity=8)
    assert CSRTensor.type() == "deepspeed.CSRTensor"
    assert "reduction_factor" in repr(csr)


def _mesh():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


def test_csr_allreduce_matches_dense_mean():
    mesh = _mesh()
    rows, cols = 64, 4
    # per-shard dense grads, each touching a few rows
    shards = np.zeros((8, rows, cols), np.float32)
    r = np.random.RandomState(0)
    for d in range(8):
        for t in r.choice(rows, size=5, replace=False):
            shards[d, t] = r.randn(cols)
    expect = shards.mean(axis=0)

    @jax.jit
    def run(x):
        def body(g):
            g = g.reshape(rows, cols)
            return sparse_embedding_grad_allreduce(g, capacity=8, axis_name="data")

        return shard_map(
            body, mesh=mesh,
            in_specs=P("data", None, None), out_specs=P(None, None),
        )(x)

    with mesh:
        out = run(jnp.asarray(shards.reshape(8 * 1, rows, cols)))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-6)


def test_csr_allreduce_union_of_indices():
    mesh = _mesh()
    rows, cols = 16, 2
    shards = np.zeros((8, rows, cols), np.float32)
    for d in range(8):
        shards[d, d] = 1.0  # each shard touches exactly row d

    @jax.jit
    def run(x):
        def body(g):
            csr = CSRTensor.from_dense(g.reshape(rows, cols), capacity=2)
            red = csr_allreduce(csr, axis_name="data")
            return red.to_dense()

        return shard_map(
            body, mesh=mesh,
            in_specs=P("data", None, None), out_specs=P(None, None),
        )(x)

    with mesh:
        out = np.asarray(run(jnp.asarray(shards)))
    for d in range(8):
        np.testing.assert_allclose(out[d], [1.0 / 8, 1.0 / 8], rtol=1e-6)
    assert np.allclose(out[8:], 0)
