"""Cross-lowering: every Pallas entry point lowers for TPU from the CPU.

``jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))`` runs the
Pallas->Mosaic lowering rules without a chip, at the geometries the
configs use. It catches what interpret-mode tests cannot see and what
would otherwise cost chip time to find: block shapes the TPU lowering
refuses (the three fused_blocks backwards shipped that way), unsupported
ops and layouts. It does NOT replace the chip run (``chip_smoke.py``,
``scripts/tpu_smoke.py``): Mosaic's own compile — VMEM limits, scheduling,
numerics — still needs hardware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeperspeed_tpu.ops import kernel_config
from deeperspeed_tpu.ops.pallas import fused_blocks, fused_quant
from deeperspeed_tpu.ops.pallas.flash_attention import (attention_dispatch,
                                                        flash_attention_bhsd)
from deeperspeed_tpu.ops.pallas.flash_static import (
    flash_attention_static_bhsd, flash_attention_supertile_bhsd)
from deeperspeed_tpu.ops.pallas.fused_adam import fused_adam_leaf
from deeperspeed_tpu.ops.sparse_attention.kernels import (
    make_block_sparse_attention, resident_ok)
from deeperspeed_tpu.ops.sparse_attention.sparsity_config import (
    FixedSparsityConfig)

BF16 = jnp.bfloat16


@pytest.fixture(autouse=True)
def as_if_on_tpu(monkeypatch):
    """The kernel gates ask kernel_config.on_tpu(); answer as the chip
    would, so the lowered graph is the one the chip compiles."""
    monkeypatch.setattr(kernel_config, "on_tpu", lambda: True)


def _sds(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _mosaic_calls(fn, *args):
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    return text.count("tpu_custom_call")


def _sq_grad(fn):
    """fwd+bwd of fn w.r.t. its first argument."""
    return jax.grad(lambda x, *rest: (fn(x, *rest).astype(jnp.float32)
                                      ** 2).sum())


# (B, H, S, Dh), causal — NeoX-1.3B, BERT-large seq 512 and seq 128
NEOX = ((2, 16, 1024, 128), True)
BERT512 = ((8, 16, 512, 64), False)
BERT128 = ((64, 16, 128, 64), False)


@pytest.mark.parametrize("shape,causal", [NEOX, BERT512])
def test_flash_static_lowers(shape, causal):
    assert attention_dispatch(shape, 2, causal=causal) == "static"
    fn = _sq_grad(lambda q: flash_attention_static_bhsd(q, q, q,
                                                        causal=causal))
    assert _mosaic_calls(fn, _sds(shape)) >= 2  # fwd + one-kernel bwd


@pytest.mark.parametrize("shape,causal", [NEOX, BERT512,
                                          ((1, 2, 4096, 64), True)])
def test_flash_stream_lowers(shape, causal):
    # explicit blocks keep the v1 streaming kernel (what S > 2048 gets)
    fn = _sq_grad(lambda q: flash_attention_bhsd(q, q, q, causal=causal,
                                                 block_q=512, block_k=512))
    assert _mosaic_calls(fn, _sds(shape)) >= 3  # fwd + dkdv + dq


def test_flash_supertile_lowers():
    shape, causal = BERT128
    with kernel_config.override(mode="auto"):
        assert attention_dispatch(shape, 2, causal=causal) == "supertile"
    fn = _sq_grad(lambda q: flash_attention_supertile_bhsd(q, q, q,
                                                           causal=causal))
    assert _mosaic_calls(fn, _sds(shape)) >= 2


@pytest.mark.parametrize("dtype", [jnp.float32, BF16])
def test_fused_blocks_lower_fwd_and_bwd(dtype):
    """The backwards' per-block partial outputs must be (8, D) tiles: the
    TPU lowering refuses a (1, D) block over an (nb, D) array."""
    R, D, F = 2048, 768, 3072  # gpt-125m rows x d_model / d_ff
    w, b = _sds((D,), jnp.float32), _sds((D,), jnp.float32)
    with kernel_config.override(mode="auto"):
        ln = jax.grad(lambda x, w, b: (fused_blocks.layer_norm(
            x, w, b, 1e-5).astype(jnp.float32) ** 2).sum(), argnums=(0, 1, 2))
        assert _mosaic_calls(ln, _sds((R, D), dtype), w, b) >= 2
        aln = jax.grad(lambda x, r, w, b: (fused_blocks.add_layer_norm(
            x, r, w, b, 1e-5).astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2, 3))
        assert _mosaic_calls(aln, _sds((R, D), dtype), _sds((R, D), dtype),
                             w, b) >= 2
        for approx in (True, False):
            bg = jax.grad(lambda h, hb: (fused_blocks.bias_gelu(
                h, hb, approx).astype(jnp.float32) ** 2).sum(),
                argnums=(0, 1))
            assert _mosaic_calls(bg, _sds((R, F), dtype),
                                 _sds((F,), dtype)) >= 2


def test_fused_adam_lowers():
    p = _sds((512, 2048), jnp.float32)
    fn = lambda p, g, m, v: fused_adam_leaf(
        p, g, m, v, 1e-3, 0.9, 0.95, b1=0.9, b2=0.95, eps=1e-8, wd=0.01,
        adam_w=True, cast_dtype=BF16)
    assert _mosaic_calls(fn, p, p, p, p) >= 1


@pytest.mark.parametrize("rows,blocks", [(8, 16), (4, 200), (1, 13)])
def test_fused_quant_lowers(rows, blocks):
    """rows = world size, blocks = blocks per chunk: both arbitrary, so
    the wire kernels pad their tiled row axis to 8 sublanes."""
    def roundtrip(x):
        q, s, r = fused_quant.quantize_rows(x, 128, want_residual=True,
                                            choice="pallas", interpret=False)
        tot = fused_quant.dequant_sum_rows(q, s, 128, choice="pallas",
                                           interpret=False)
        back = fused_quant.dequant_rows(q, s, 128, divisor=8.0,
                                        choice="pallas", interpret=False)
        return tot, back, r

    assert _mosaic_calls(roundtrip,
                         _sds((rows, blocks * 128), jnp.float32)) >= 3


@pytest.mark.parametrize("S", [1024, 4096])
@pytest.mark.parametrize("impl", ["resident", "stream"])
def test_block_sparse_lowers(S, impl):
    H, Dh = 4, 64
    assert resident_ok(S, Dh)
    cfg = FixedSparsityConfig(num_heads=H, block=128, num_local_blocks=4,
                              num_global_blocks=1,
                              attention="unidirectional")
    attn = make_block_sparse_attention(np.asarray(cfg.make_layout(S)), 128,
                                       causal=True, impl=impl)
    fn = _sq_grad(lambda q: attn(q, q, q))
    assert _mosaic_calls(fn, _sds((1, S, H, Dh))) >= 2


def test_neox_1p3b_micro_step_lowers_with_mosaic_attention():
    """The flagship value_and_grad micro-step (what chip_smoke.py trains):
    attn_impl='auto' must reach the Pallas kernels, not the XLA reference."""
    from deeperspeed_tpu.models.gpt import get_preset, make_gpt

    cfg = get_preset("neox-1.3b", remat_policy="matmuls", ce_chunk=0,
                     max_seq=1024)
    init_fn, _, loss_fn, _ = make_gpt(cfg)
    params = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    batch = _sds((2, cfg.max_seq + 1), jnp.int32)
    assert _mosaic_calls(jax.value_and_grad(loss_fn), params, batch) >= 2


@pytest.mark.parametrize("comm", [None, {"mode": "int8", "block": 128}])
def test_engine_train_step_lowers_on_a_multi_device_mesh(comm):
    """XLA cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"): under the engine's data-parallel jit the
    flash kernels must sit inside a shard_map over the engine's mesh. This
    is what stopped the first four-chip run. With a "comm" block the engine
    already computes grads inside its own shard_map, where the kernels must
    NOT wrap themselves again."""
    import deeperspeed_tpu as deepspeed
    from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt

    assert len(jax.devices()) >= 4
    cfg = GPTConfig(vocab_size=256, n_layer=2, n_head=4, d_model=256,
                    max_seq=512, ce_chunk=0)
    init_fn, _, loss_fn, _ = make_gpt(cfg)
    engine, _, _, _ = deepspeed.initialize(
        model=loss_fn, model_parameters=init_fn(jax.random.PRNGKey(0)),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 2,
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 1},
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                **({"comm": comm} if comm else {})})
    assert engine.mesh.size == len(jax.devices())
    rows = 2 * engine.data_parallel_size
    batch = engine._place_batch(np.zeros((rows, cfg.max_seq + 1), np.int32))
    args = (engine.state, batch, np.float32(1e-4), engine._rng_args())
    if comm:
        args = (engine.state, engine._comm_state) + args[1:]
    text = engine._train_batch_fn().trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 2


def test_single_device_surfaces_leave_a_multi_device_mesh_to_xla():
    """fused_blocks / fused_adam have no shard_map wrapper: under a
    multi-device mesh `auto` keeps them on XLA and `fused` says why not."""
    from deeperspeed_tpu.sharding import default_mesh

    mesh = default_mesh()
    assert mesh.size > 1
    with kernel_config.override(mode="auto"):
        assert kernel_config.resolve("fused_blocks") == (True, False)
        with kernel_config.mesh_scope(mesh):
            assert kernel_config.resolve("fused_blocks") == (False, False)
            assert kernel_config.resolve("fused_adam") == (False, False)
            with kernel_config.mesh_scope(None):  # a shard_map body
                assert kernel_config.resolve("fused_adam") == (True, False)
    with kernel_config.override(mode="fused"), \
            kernel_config.mesh_scope(mesh):
        with pytest.raises(NotImplementedError, match="single-device"):
            kernel_config.resolve("fused_blocks")


def _decode_step_text(mesh=None, **cfg_kw):
    """``ds_decode_step`` lowered for TPU at 16 key heads of 128 (the
    widths the kernel's gate admits), from shapes alone."""
    from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
    from deeperspeed_tpu.serving import ServingConfig
    from deeperspeed_tpu.serving.engine import idle_slots, make_decode_step

    cfg = GPTConfig(vocab_size=256, n_layer=2, n_head=16, d_model=2048,
                    max_seq=256, **cfg_kw)
    scfg = ServingConfig(num_slots=4, block_size=16, num_blocks=65,
                         max_seq_len=256)
    params = jax.eval_shape(make_gpt(cfg)[0], jax.random.PRNGKey(0))
    N = scfg.num_slots
    pool = _sds((cfg.n_layer, scfg.num_blocks, scfg.block_size,
                 cfg.kv_heads, cfg.head_dim))
    slots = idle_slots(N, scfg.blocks_per_slot)
    args = (params, pool, pool, _sds(slots.shape, slots.dtype),
            _sds((N,), jnp.int32))
    return make_decode_step(cfg, scfg, mesh).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("H,Hkv,dtype", [(16, 16, BF16), (32, 16, BF16),
                                         (8, 8, jnp.float32)])
def test_paged_decode_attn_lowers(H, Hkv, dtype):
    from deeperspeed_tpu.ops.pallas.paged_decode_attn import (
        is_available, paged_decode_attn)

    N, bs, bps, Dh = 16, 16, 128, 128       # the serving cell's geometry
    pool = _sds((2, 129, bs, Hkv, Dh), dtype)
    tables = _sds((N, bps), jnp.int32)
    assert is_available(pool, tables, H)
    assert _mosaic_calls(
        paged_decode_attn, pool, pool, _sds((), jnp.int32),
        _sds((N, 1, H, Dh), dtype), _sds((N, Hkv, Dh), dtype),
        _sds((N, Hkv, Dh), dtype), tables, _sds((N,), jnp.int32)) == 1


def test_decode_step_reads_the_pool_through_the_kernel_on_one_tpu():
    text = _decode_step_text()
    assert text.count("tpu_custom_call") == 1       # once, in the layer scan
    assert "paged_decode_attn" in text


def test_decode_step_keeps_the_xla_form_on_a_multi_device_mesh():
    """XLA cannot partition a Mosaic kernel and this one has no shard_map
    wrapper: dp x tp serving keeps the gather GSPMD can shard."""
    from deeperspeed_tpu.sharding import default_mesh

    assert "tpu_custom_call" not in _decode_step_text(default_mesh())
