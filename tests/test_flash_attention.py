"""Pallas flash attention vs XLA reference (interpret mode on CPU; the same
kernels run compiled on TPU). Parity with the reference's kernel tests
tests/unit/test_cuda_forward.py / test_cuda_backward.py methodology: compare
fused kernel against a dense reference over shape grids with tolerances."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.ops.pallas.flash_attention import flash_attention


def reference_attention(q, k, v, causal=True):
    dh = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / np.sqrt(dh)
    if causal:
        mask = np.tril(np.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def make_qkv(b=2, s=256, h=2, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = make_qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_block_not_dividing_masked_tail(causal):
    """Blocks that do not divide S run a masked tail (clamped final window
    + overlap mask) instead of rejecting the geometry."""
    q, k, v = make_qkv(s=200)
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=128, block_k=128)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_backward_block_not_dividing_masked_tail():
    q, k, v = make_qkv(b=1, s=200, h=2, d=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=True,
                                       block_q=128, block_k=128) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, rtol=5e-3)


def test_small_seq_uses_smaller_blocks():
    q, k, v = make_qkv(s=64)
    out = flash_attention(q, k, v, interpret=True)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_backward_matches_reference(causal):
    q, k, v = make_qkv(b=1, s=128, h=2, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-3,
            err_msg=f"d{name} mismatch",
        )


def test_bf16_inputs():
    q, k, v = make_qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True)
    ref = reference_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2, rtol=3e-2
    )


def test_model_uses_flash_in_interpret_mode():
    """GPT forward with attn_impl=pallas_interpret == xla impl."""
    from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt

    kw = dict(
        vocab_size=128, n_layer=2, n_head=2, d_model=64, max_seq=128,
        dtype=jnp.float32, remat=False,
    )
    batch = np.random.default_rng(0).integers(0, 128, size=(2, 129), dtype=np.int32)
    losses = {}
    for impl in ("xla", "pallas_interpret"):
        init_fn, _, loss_fn, _ = make_gpt(GPTConfig(attn_impl=impl, **kw))
        params = init_fn(jax.random.PRNGKey(0))
        losses[impl] = float(loss_fn(params, batch))
    assert abs(losses["xla"] - losses["pallas_interpret"]) < 1e-3, losses


def test_mismatched_block_sizes():
    """block_q != block_k must still be correct under causal masking."""
    q, k, v = make_qkv(s=256)
    for bq, bk in ((64, 128), (128, 64)):
        out = flash_attention(q, k, v, causal=True, interpret=True,
                              block_q=bq, block_k=bk)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3,
            err_msg=f"bq={bq} bk={bk}",
        )


def test_auto_block_is_lane_legal():
    """Auto blocks must be 128-multiples (block_q becomes the LANE dim of
    the lse/delta BlockSpecs) or span the whole sequence — regression guard
    for the S=640 Mosaic lowering failure scripts/tpu_smoke.py caught
    (interpret mode does not enforce the lane rule, so this must be a
    pure-Python check)."""
    from deeperspeed_tpu.ops.pallas.flash_attention import _auto_block

    assert _auto_block(640, 512) == 128
    assert _auto_block(1024, 512) == 512
    # 8*127: no 128-multiple divisor and too long for a whole-S block —
    # picks the default-sized block and the kernels run a masked tail
    assert _auto_block(1016, 512) == 512
    assert _auto_block(384, 512) == 384  # short whole-S fallback still wins
    for S in range(128, 4097, 8):
        for default in (128, 256, 512):
            b = _auto_block(S, default)
            assert b % 128 == 0 or b == S, (S, default, b)
            assert b <= S, (S, default, b)


def test_flash_under_a_mesh_scope_shards_batch_and_heads():
    """Traced under a multi-device mesh, flash attention wraps itself in a
    shard_map over batch and heads (XLA cannot partition a Mosaic kernel);
    the result and the gradient are those of the unwrapped call."""
    from jax.sharding import PartitionSpec as P

    from deeperspeed_tpu.ops import kernel_config
    from deeperspeed_tpu.ops.pallas.flash_attention import (
        _mesh_spec_bhsd, flash_attention_bhsd)
    from deeperspeed_tpu.parallel import build_mesh

    mesh = build_mesh({"data": 4, "model": 2})
    assert _mesh_spec_bhsd(mesh, 8, 4) == P(("data",), ("model",), None, None)
    # an axis that does not divide is left out (gathered), never an error
    assert _mesh_spec_bhsd(mesh, 2, 3) == P(None, None, None, None)

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (8, 4, 128, 32),
                                 jnp.float32) for i in range(3))

    def loss(q, k, v):
        return (flash_attention_bhsd(q, k, v, causal=True,
                                     interpret=True) ** 2).sum()

    ref, ref_g = jax.value_and_grad(loss)(q, k, v)

    def scoped(q, k, v):
        with kernel_config.mesh_scope(mesh):
            return loss(q, k, v)

    jaxpr = str(jax.make_jaxpr(scoped)(q, k, v))
    assert "shard_map" in jaxpr
    got, got_g = jax.jit(jax.value_and_grad(scoped))(q, k, v)
    np.testing.assert_allclose(got, ref, rtol=1e-5)
    np.testing.assert_allclose(got_g, ref_g, rtol=1e-4, atol=1e-5)
