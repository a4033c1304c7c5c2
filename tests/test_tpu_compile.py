"""Compiled for a described TPU v5e, no chip attached: what only the
chip's own compiler can say of the main serving path, at its real widths.

``tests/test_tpu_lowering.py`` stops at the Pallas->Mosaic lowering; this
file runs the TPU compiler itself (Mosaic's compile of the kernel, XLA's
buffer assignment), which refuses unaligned slices and too much VMEM and
says how often a program holds the KV pool. Nothing runs: no result, no
time. The topology is described inside a fixture and every test of the
kind lives in this one file, so that one test worker loads the TPU's
library and every worker collects the same tests.
"""

import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeperspeed_tpu.analysis import count_alias_pairs
from deeperspeed_tpu.monitor import extract_memory_analysis
from deeperspeed_tpu.ops import kernel_config

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_if_on_tpu(monkeypatch):
    monkeypatch.setattr(kernel_config, "on_tpu", lambda: True)


def runs_kernel(text, name):
    """Whether the compiled program CALLS the Mosaic kernel ``name``. Its
    name anywhere in the text does not say so: the text lists the files
    and functions of every trace it reuses, and a helper first traced
    inside a kernel's module brings that module's name into a program
    that never calls the kernel (which tests ran before decides it)."""
    return any("tpu_custom_call" in ln and name in ln
               for ln in text.splitlines())


@pytest.mark.parametrize("H,Hkv", [(16, 16), (32, 16)])
def test_paged_decode_attn_compiles_at_the_serving_cells_geometry(
        one_chip, H, Hkv):
    from deeperspeed_tpu.ops.pallas.paged_decode_attn import paged_decode_attn

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    N, bs, bps, Dh = 16, 16, 128, 128
    pool = sds((24, 1793, bs, Hkv, Dh))
    compiled = paged_decode_attn.lower(
        pool, pool, sds((), jnp.int32), sds((N, 1, H, Dh)),
        sds((N, Hkv, Dh)), sds((N, Hkv, Dh)), sds((N, bps), jnp.int32),
        sds((N,), jnp.int32)).compile()
    assert "paged_decode_attn" in compiled.as_text()


def _idle_slots(N, bps):
    """Shape and dtype of the decode step's packed slot array, from the
    function that packs it."""
    from deeperspeed_tpu.serving.engine import idle_slots

    slots = idle_slots(N, bps)
    return slots.shape, slots.dtype


def test_neox_1p3b_decode_step_holds_the_pool_once(one_chip, as_if_on_tpu):
    """The serving cell's decode program (NeoX-1.3B widths, 16 slots, a
    28,672-token pool): the donated pools are its outputs in place, no
    layer is sliced out of them, nothing copies them, and the compiler
    asks for one pool and the weights (it asked 14.14 GiB while the pool
    went through the layer scan; 7.9 GiB now)."""
    from deeperspeed_tpu.models.gpt import get_preset, init_params
    from deeperspeed_tpu.serving import ServingConfig
    from deeperspeed_tpu.serving.engine import make_decode_step

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg = get_preset("neox-1.3b")
    scfg = ServingConfig(num_slots=16, block_size=16, num_blocks=1793,
                         max_seq_len=2048)
    params = jax.tree.map(
        lambda a: sds(a.shape),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    N = scfg.num_slots
    shape = (cfg.n_layer, scfg.num_blocks, scfg.block_size, cfg.kv_heads,
             cfg.head_dim)
    compiled = make_decode_step(cfg, scfg).lower(
        params, sds(shape), sds(shape),
        sds(*_idle_slots(N, scfg.blocks_per_slot)),
        sds((N,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "paged_decode_attn" in text
    assert count_alias_pairs(text) == 2
    whole = "bf16[" + ",".join(map(str, shape)) + "]"
    layer = "bf16[" + ",".join(map(str, shape[1:])) + "]"
    moved = [ln.strip()[:160] for ln in text.splitlines()
             if layer in ln or (whole in ln and " copy(" in ln)]
    assert moved == []
    pool = 2 * 2 * math.prod(shape)
    weights = sum(2 * math.prod(a.shape) for a in jax.tree.leaves(params))
    ask = extract_memory_analysis(compiled)["peak_bytes"]
    assert ask < 1.5 * pool + weights, (ask, pool, weights)


# ------------------------------------------------------------------ #
# MiniCPM-SALA: the kernels and the two serving programs at the cell's size
# ------------------------------------------------------------------ #


def _sala():
    import os

    from benchmark import manifest as mf
    from benchmark.adapters import minicpm_sala as adapter
    from deeperspeed_tpu.serving import ServingConfig

    man = mf.Manifest()
    cfg = adapter.model_config(man.config("minicpm-sala"))
    scfg = ServingConfig.from_dict(
        man.workload_file("minicpm-sala.serve-longdoc")["serving"])
    return cfg, scfg


@pytest.mark.parametrize("R,P", [(24, 128), (2048, 64), (2048, 32)],
                         ids=["a_decode_step", "a_prompt_chunk_by_topk",
                              "a_prompt_chunk_by_its_chosen_pages"])
def test_paged_sparse_attn_compiles_at_the_longdoc_cells_geometry(
        one_chip, R, P):
    """The chunk program lists a row's CHOSEN pages (32 wide, in calls of
    1,024 rows); the 64-wide list is what it handed on until PR 36."""
    from deeperspeed_tpu.ops.pallas.paged_sparse_attn import (
        _ROW_VMEM, _pages_per_row_chunk, paged_sparse_attn, rows_per_call)

    assert rows_per_call(2048, 32) == 1024 and rows_per_call(2048, 64) == 512

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    G, Dh = 16, 128
    pool = sds((4, 6241, 2, 64, Dh))
    # a copy-chunk is 32 pages of one key head at every width of the cell
    # (a chunk row's whole list): K and V, two buffers each, fill the room
    # the row form's rule names and no more
    pp = _pages_per_row_chunk(P, pool)
    assert pp == 32 and 2 * 2 * pp * 64 * Dh * 2 <= _ROW_VMEM
    f32 = jnp.float32
    compiled = paged_sparse_attn.lower(
        pool, pool, sds((), jnp.int32), sds((R, G, Dh)), sds((R,), jnp.int32),
        sds((R, P), jnp.int32), sds((R,), jnp.int32), sds((R, G), f32),
        sds((R, G), f32), sds((R, G, Dh), f32)).compile()
    assert "paged_sparse_attn" in compiled.as_text()


def test_lightning_chunk_compiles_at_the_longdoc_cells_geometry(one_chip):
    from deeperspeed_tpu.ops.pallas.lightning_chunk import lightning_chunk

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    C, H, Dh = 1024, 32, 128
    compiled = lightning_chunk.lower(
        sds((C, H, Dh)), sds((C, H, Dh)), sds((C, H, Dh)),
        sds((H, Dh, Dh), jnp.float32), sds((H,), jnp.float32),
        sds((), jnp.int32)).compile()
    assert "lightning_chunk" in compiled.as_text()


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_sala_programs_move_neither_the_pool_nor_a_weight_stack(
        one_chip, as_if_on_tpu, program):
    """The decode step and the prompt-chunk program of the long-document
    cell (16 layers at the published widths, 12 slots, 399,360 tokens of
    pages): the donated pools, pooled keys and state rows are outputs in
    place; nothing copies a pool (XLA re-laid the WHOLE pool out for each
    gather or scatter that indexed inside a page, 3 GiB and five passes a
    step) or a stack of weights (it transposed the lightning layers' q, k
    and v stacks every call while they were three projections); the
    compiler's ask stays inside the chip."""
    from deeperspeed_tpu.models import mixers
    from deeperspeed_tpu.serving.engine import (make_chunk_step,
                                                make_decode_step)

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg, scfg = _sala()
    params = jax.tree.map(
        lambda a: sds(a.shape),
        jax.eval_shape(lambda: mixers.init_params(jax.random.PRNGKey(0), cfg)))
    N, nb, bps = scfg.num_slots, scfg.num_blocks, scfg.blocks_per_slot
    i32 = jnp.int32
    pool = sds((4, nb, 2, 64, 128))
    kc = sds((4, nb, 8, 128))
    state = sds((12, N, 32, 128, 128), jnp.float32)
    if program == "decode":
        compiled = make_decode_step(cfg, scfg).lower(
            params, pool, pool, sds(*_idle_slots(N, bps)), sds((N,), i32), kc,
            state).compile()
    else:
        compiled = make_chunk_step(cfg, scfg).lower(
            params, pool, pool, kc, state, sds((1, 1024), i32), sds((bps,), i32),
            sds((), i32), sds((), i32), sds((), i32)).compile()
    text = compiled.as_text()
    assert runs_kernel(text, "paged_sparse_attn")
    assert runs_kernel(text, "lightning_chunk") == (program == "chunk")
    assert count_alias_pairs(text) == 4        # k, v, pooled keys, state rows
    big = ("bf16[4,6241,2,64,128]", "bf16[4,6241,8,128]",
           "bf16[12,4096,", "bf16[12,16384,", "bf16[4,4096,", "bf16[4,16384,")
    moved = [ln.strip()[:140] for ln in text.splitlines()
             if " copy(" in ln and ln.split(" = ")[1].startswith(big)]
    assert moved == []
    weights = sum(2 * math.prod(a.shape) for a in jax.tree.leaves(params))
    caches = 2 * (2 * math.prod(pool.shape) + math.prod(kc.shape)) \
        + 4 * math.prod(state.shape)
    ask = extract_memory_analysis(compiled)["peak_bytes"]
    assert weights + caches < ask < weights + caches + 2 ** 30, (
        ask, weights, caches)
    assert ask < 12.5 * 2 ** 30


# ------------------------------------------------------------------ #
# Falcon-H1: the kernels and the two serving programs at the cell's size
# ------------------------------------------------------------------ #


def _falcon_h1():
    from benchmark import manifest as mf
    from benchmark.adapters import falcon_h1 as adapter
    from deeperspeed_tpu.serving import ServingConfig

    man = mf.Manifest()
    cfg = adapter.model_config(man.config("falcon-h1-34b"))
    scfg = ServingConfig.from_dict(
        man.workload_file("falcon-h1-34b.serve-chat")["serving"])
    return cfg, scfg


def test_page_list_kernel_compiles_at_five_queries_a_key_head(one_chip):
    """The chat cell's decode call: 48 slots x 4 key heads = 192 rows of 5
    queries, each row's list the slot's whole table of 48 pages."""
    from deeperspeed_tpu.ops.pallas.paged_sparse_attn import paged_sparse_attn

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, G, P, Dh = 192, 5, 48, 128
    pool = sds((6, 2305, 4, 64, Dh))
    f32 = jnp.float32
    compiled = paged_sparse_attn.lower(
        pool, pool, sds((), jnp.int32), sds((R, G, Dh)), sds((R,), jnp.int32),
        sds((R, P), jnp.int32), sds((R,), jnp.int32), sds((R, G), f32),
        sds((R, G), f32), sds((R, G, Dh), f32)).compile()
    assert "paged_sparse_attn" in compiled.as_text()


def test_ssm_row_update_compiles_at_the_chat_cells_geometry(one_chip):
    from deeperspeed_tpu.ops.pallas.ssm_row_update import ssm_row_update

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = ssm_row_update.lower(
        sds((6, 48, 32, 128, 256)), sds((), jnp.int32), sds((48, 32)),
        sds((48, 32, 128)), sds((48, 2, 256)), sds((48, 2, 256)),
        sds((48,), jnp.bool_)).compile()
    assert "ssm_row_update" in compiled.as_text()


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_falcon_h1_programs_move_neither_pool_rows_nor_a_weight_stack(
        one_chip, as_if_on_tpu, program):
    """The decode step and the prompt-chunk program of the chat cell (6
    layers at the published widths, 48 slots, 147,520 tokens of pages,
    1.13 GiB of state rows): the donated pools, state rows and convolution
    tails are outputs in place, nothing copies a pool, the rows or a stack
    of weights, and the compiler's ask stays inside the chip."""
    from deeperspeed_tpu.models import mixers
    from deeperspeed_tpu.serving.engine import (make_chunk_step,
                                                make_decode_step)

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg, scfg = _falcon_h1()
    params = jax.tree.map(
        lambda a: sds(a.shape),
        jax.eval_shape(lambda: mixers.init_params(jax.random.PRNGKey(0), cfg)))
    N, nb, bps = scfg.num_slots, scfg.num_blocks, scfg.blocks_per_slot
    i32, m = jnp.int32, cfg.ssm
    pool = sds((6, nb, 4, 64, 128))
    state = {"ssm": sds((6, N, m.n_heads, m.head_dim, m.d_state), jnp.float32),
             "conv": sds((6, N, m.d_conv - 1, m.conv_dim))}
    if program == "decode":
        compiled = make_decode_step(cfg, scfg).lower(
            params, pool, pool, sds(*_idle_slots(N, bps)), sds((N,), i32),
            None, state).compile()
    else:
        compiled = make_chunk_step(cfg, scfg).lower(
            params, pool, pool, None, state, sds((1, 512), i32),
            sds((bps,), i32), sds((), i32), sds((), i32), sds((), i32)).compile()
    text = compiled.as_text()
    # the decode step reads pages and rows through kernels; the chunk
    # attends over the slot's gathered pages and scans in XLA
    assert runs_kernel(text, "paged_sparse_attn") == (program == "decode")
    # ... in the form whose row is a slot: its key heads share the list
    assert runs_kernel(text, "paged_sparse_attn_slots") == (
        program == "decode")
    assert runs_kernel(text, "ssm_row_update") == (program == "decode")
    assert count_alias_pairs(text) == 4        # k, v, state rows, tails
    big = ("bf16[6,2305,4,64,128]", "f32[6,48,32,128,256]", "bf16[6,5120,",
           "bf16[6,21504,", "bf16[6,4096,", "bf16[6,2560,")
    moved = [ln.strip()[:140] for ln in text.splitlines()
             if " copy(" in ln and ln.split(" = ")[1].startswith(big)]
    assert moved == []
    weights = sum(2 * math.prod(a.shape) for a in jax.tree.leaves(params))
    assert weights == 2 * 5_254_594_112
    caches = 2 * 2 * math.prod(pool.shape) + 4 * math.prod(
        state["ssm"].shape) + 2 * math.prod(state["conv"].shape)
    ask = extract_memory_analysis(compiled)["peak_bytes"]
    assert weights + caches < ask < weights + caches + 2 ** 29, (
        ask, weights, caches)
    assert ask < 12.9 * 2 ** 30


# ------------------------------------------------------------------ #
# EvaByte: the page-list kernel at one query a key head, and the two
# serving programs at the cell's size
# ------------------------------------------------------------------ #


def _evabyte():
    from benchmark import manifest as mf
    from benchmark.adapters import evabyte as adapter
    from deeperspeed_tpu.serving import ServingConfig
    from deeperspeed_tpu.serving.kv_cache import page_rule_for

    man = mf.Manifest()
    cfg = adapter.model_config(man.config("evabyte-6.5b"))
    scfg = ServingConfig.from_dict(
        man.workload_file("evabyte-6.5b.serve-bytes")["serving"])
    return cfg, scfg.for_cache(page_rule_for(cfg))


def test_page_list_kernel_compiles_at_one_query_a_key_head(one_chip):
    """The byte cell's decode call: 16 slots x 32 key heads = 512 rows of
    ONE query, each row's list the slot's 64 entries (summary pages, then
    the window's): 128 KiB of lists in scalar memory."""
    from deeperspeed_tpu.ops.pallas.paged_sparse_attn import paged_sparse_attn

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, G, P, Dh = 512, 1, 64, 128
    pool = sds((8, 1025, 32, 64, Dh))
    f32 = jnp.float32
    compiled = paged_sparse_attn.lower(
        pool, pool, sds((), jnp.int32), sds((R, G, Dh)), sds((R,), jnp.int32),
        sds((R, P), jnp.int32), sds((R,), jnp.int32), sds((R, G), f32),
        sds((R, G), f32), sds((R, G, Dh), f32)).compile()
    assert "paged_sparse_attn" in compiled.as_text()


@pytest.mark.parametrize("pool,N,G,P", [
    pytest.param((8, 1025, 32, 64, 128), 16, 1, 64, id="the_byte_cell"),
    pytest.param((6, 2305, 4, 64, 128), 48, 5, 48, id="the_chat_cell")])
def test_page_list_kernel_compiles_with_a_slot_a_row(one_chip, pool, N, G, P):
    """The decode call of the two cells whose key heads share a slot's
    list: a row a SLOT, a copy a whole page (512 KiB of 32 key heads, 2 a
    copy-chunk; 64 KiB of 4, 8 a copy-chunk)."""
    from deeperspeed_tpu.ops.pallas.paged_sparse_attn import (
        paged_sparse_attn_slots, slots_available)

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    Hkv, Dh = pool[2], pool[4]
    f32 = jnp.float32
    compiled = paged_sparse_attn_slots.lower(
        sds(pool), sds(pool), sds((), jnp.int32), sds((N, Hkv, G, Dh)),
        sds((N, P), jnp.int32), sds((N,), jnp.int32), sds((N, Hkv, G), f32),
        sds((N, Hkv, G), f32), sds((N, Hkv, G, Dh), f32)).compile()
    assert runs_kernel(compiled.as_text(), "paged_sparse_attn_slots")


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_evabyte_programs_move_neither_the_pool_nor_a_weight_stack(
        one_chip, as_if_on_tpu, program):
    """The decode step and the prompt-chunk program of the byte cell (8
    layers at the published widths, 16 slots, 1,025 pages of 64 rows x 32
    heads): the donated pools are outputs in place, nothing copies a pool
    or a stack of weights, the table is 64 entries wide, and the
    compiler's ask stays inside the chip."""
    from deeperspeed_tpu.models import mixers
    from deeperspeed_tpu.serving.engine import (make_chunk_step,
                                                make_decode_step)

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg, scfg = _evabyte()
    params = jax.tree.map(
        lambda a: sds(a.shape),
        jax.eval_shape(lambda: mixers.init_params(jax.random.PRNGKey(0), cfg)))
    N, nb, bps = scfg.num_slots, scfg.num_blocks, scfg.blocks_per_slot
    assert (N, nb, bps, scfg.table_widths) == (16, 1025, 64, (32, 32))
    i32 = jnp.int32
    pool = sds((8, nb, 32, 64, 128))
    if program == "decode":
        compiled = make_decode_step(cfg, scfg).lower(
            params, pool, pool, sds(*_idle_slots(N, bps)), sds((N,), i32),
            None, None).compile()
    else:
        compiled = make_chunk_step(cfg, scfg).lower(
            params, pool, pool, None, None, sds((1, 1024), i32),
            sds((bps,), i32), sds((), i32), sds((), i32), sds((), i32)).compile()
    text = compiled.as_text()
    # the decode step reads the two-role list through the page-list
    # kernel; the chunk attends over the same list, a count of its rows
    # and its own keys in the chunk kernel, the pages read where they lie
    assert runs_kernel(text, "paged_sparse_attn") == (program == "decode")
    # ... in the form whose row is a slot: its key heads share the list
    assert runs_kernel(text, "paged_sparse_attn_slots") == (
        program == "decode")
    assert runs_kernel(text, "chunk_past_attn") == (program == "chunk")
    assert count_alias_pairs(text) == 2        # k, v
    big = ("bf16[8,1025,32,64,128]", "bf16[8,4096,", "bf16[8,11008,")
    moved = [ln.strip()[:140] for ln in text.splitlines()
             if " copy(" in ln and ln.split(" = ")[1].startswith(big)]
    assert moved == []
    weights = sum(2 * math.prod(a.shape) for a in jax.tree.leaves(params))
    assert weights == 2 * 1_630_932_992
    caches = 2 * 2 * math.prod(pool.shape)
    ask = extract_memory_analysis(compiled)["peak_bytes"]
    assert weights + caches < ask < weights + caches + 2 ** 30, (
        ask, weights, caches)
    assert ask < 12.5 * 2 ** 30


# ------------------------------------------------------------------ #
# Mellum 2: two pools, the page-list kernel at 8 queries a key head, the
# routed experts' grouped product, and the two serving programs at the
# cell's size
# ------------------------------------------------------------------ #


def _mellum():
    from benchmark import manifest as mf
    from benchmark.adapters import mellum as adapter
    from deeperspeed_tpu.serving import ServingConfig
    from deeperspeed_tpu.serving.kv_cache import page_rule_for

    man = mf.Manifest()
    cfg = adapter.model_config(man.config("mellum2-12b-a2.5b"))
    scfg = ServingConfig.from_dict(
        man.workload_file("mellum2-12b-a2.5b.serve-code")["serving"])
    return cfg, scfg.for_cache(page_rule_for(cfg))


@pytest.mark.parametrize("rows", [256, 8192])
def test_grouped_matmul_compiles_at_the_experts_shapes(one_chip, rows):
    """A layer's experts read in the layers' stack: 6 x 64 groups of which
    one layer's 64 have rows, at a decode step's 256 rows and a prompt
    chunk's 8,192, both products' shapes."""
    from deeperspeed_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                           tiling)

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for k, n in ((2304, 896), (896, 2304)):
        assert tiling(rows, k, n) == (128, k, n)
        compiled = jax.jit(grouped_matmul).lower(
            sds((rows, k)), sds((384, k, n)), sds((384,), jnp.int32)).compile()
        assert runs_kernel(compiled.as_text(), "gmm")
    assert tiling(100, 2304, 896) is None and tiling(256, 100, 896) is None


@pytest.mark.parametrize("pool,P,band", [
    pytest.param((2, 16385, 4, 64, 128), 528, False, id="every_key"),
    pytest.param((6, 513, 4, 64, 128), 16, True, id="the_ring"),
    pytest.param((8, 1025, 32, 64, 128), 48, False, id="two_roles")])
def test_chunk_past_attn_compiles_at_the_code_cells_geometry(
        one_chip, as_if_on_tpu, pool, P, band):
    """A prompt chunk's attention over its past in the cell of two cache
    rules: 1,024 queries of 32 heads over 4 key heads, the list the
    slot's 512 pages of every key and the chunk's own 16 (null), or the
    ring's 16 pages under the band rule; a grid step 256 queries of all
    heads, a step of the walk 16 whole pages of 64 KiB. And in the byte
    cell: ONE query a key head, 32 of them, the list of two roles 48
    pages of 512 KiB; a grid step 512 queries, so that a key head's
    block has 512 rows where the code cell's has 2,048."""
    from deeperspeed_tpu.ops.pallas import chunk_past_attn as kernel

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, Hkv = jnp.int32, pool[2]
    assert kernel.is_available(sds(pool), 32, 1024)
    assert kernel.tiles(1024, 64, 32 // Hkv) == (
        (512, 16) if Hkv == 32 else (256, 16))
    compiled = kernel.chunk_past_attn.lower(
        sds(pool), sds(pool), sds((), i32), sds((1024, 32, 128)),
        sds((1024, Hkv, 128)), sds((1024, Hkv, 128)), sds((P,), i32),
        sds((), i32), sds((), i32), band=band).compile()
    assert runs_kernel(compiled.as_text(), "chunk_past_attn")


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_mellum_programs_move_neither_a_pool_nor_a_weight_stack(
        one_chip, as_if_on_tpu, program):
    """The decode step and the prompt-chunk program of the cell of two
    cache rules (8 layers at the published widths, 32 slots, 16,385 pages
    of every key 2 layers deep and 513 of the rings 6 deep): the donated
    pools are outputs in place, nothing copies a pool or an experts'
    stack (the grouped product reads a layer's experts where they lie),
    and the compiler's ask stays inside the chip."""
    from deeperspeed_tpu.models import mixers
    from deeperspeed_tpu.serving.engine import (make_chunk_step,
                                                make_decode_step)

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg, scfg = _mellum()
    params = jax.tree.map(
        lambda a: sds(a.shape),
        jax.eval_shape(lambda: mixers.init_params(jax.random.PRNGKey(0), cfg)))
    N, bps = scfg.num_slots, scfg.blocks_per_slot
    assert (N, scfg.pool_blocks, bps, scfg.table_widths) == (
        32, (16385, 513), 528, (512, 16))
    i32 = jnp.int32
    pools = (sds((2, 16385, 4, 64, 128)), sds((6, 513, 4, 64, 128)))
    if program == "decode":
        compiled = make_decode_step(cfg, scfg).lower(
            params, pools, pools, sds(*_idle_slots(N, bps)),
            sds((N + 3,), i32), None, None).compile()
    else:
        compiled = make_chunk_step(cfg, scfg).lower(
            params, pools, pools, None, None, sds((1, 1024), i32),
            sds((bps,), i32), sds((), i32), sds((), i32), sds((), i32)).compile()
    text = compiled.as_text()
    # both kinds of layer read their lists through the kernel whose row is
    # a slot in a decode step; a chunk attends over its past and itself in
    # the chunk kernel, under both band rules; the experts' three products
    # a layer are the grouped-matmul kernel's in both
    assert runs_kernel(text, "paged_sparse_attn_slots") == (
        program == "decode")
    assert runs_kernel(text, "chunk_past_attn") == (program == "chunk")
    assert runs_kernel(text, "gmm")
    assert count_alias_pairs(text) == 4        # k, v of both pools
    big = ("bf16[2,16385,", "bf16[6,513,", "bf16[6,64,", "bf16[2,64,",
           "bf16[64,2304,896]", "bf16[64,896,2304]")
    moved = [ln.strip()[:140] for ln in text.splitlines()
             if (" copy(" in ln or "dynamic-slice_bitcast_fusion" in ln)
             and ln.split(" = ")[1].startswith(big)]
    assert moved == []
    weights = sum(2 * math.prod(a.shape) for a in jax.tree.leaves(params))
    assert weights == 2 * 3_794_968_832
    caches = 2 * 2 * sum(math.prod(p.shape) for p in pools)
    assert caches == 2 * (16385 * 2 + 513 * 6) * 65536
    ask = extract_memory_analysis(compiled)["peak_bytes"]
    assert weights + caches < ask < weights + caches + 2 ** 29, (
        ask, weights, caches)
    assert ask < 11.8 * 2 ** 30


def _solar():
    from benchmark import manifest as mf
    from benchmark.adapters import solar_open2 as adapter
    from deeperspeed_tpu.serving import ServingConfig
    from deeperspeed_tpu.serving.kv_cache import page_rule_for

    man = mf.Manifest()
    cfg = adapter.model_config(man.config("solar-open2-250b"))
    scfg = ServingConfig.from_dict(
        man.workload_file("solar-open2-250b.serve-reason")["serving"])
    return cfg, scfg.for_cache(page_rule_for(cfg))


def test_kda_kernels_compile_at_the_reasoning_cells_geometry(one_chip):
    """The chunkwise delta rule over a 1,024-token chunk of 64 heads of
    128 x 128 (a grid step a head and a block of 64 positions, the state
    in VMEM between them) and a decode step's update of 48 slots' rows of
    one of three layers, in place."""
    from deeperspeed_tpu.ops.pallas.kda_chunk import kda_chunk
    from deeperspeed_tpu.ops.pallas.kda_row_update import kda_row_update

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    C, H, N = 1024, 64, 48
    compiled = kda_chunk.lower(
        sds((C, H, 128)), sds((C, H, 128)), sds((C, H, 128)),
        sds((C, H, 128)), sds((C, H)), sds((H, 128, 128))).compile()
    assert runs_kernel(compiled.as_text(), "kda_chunk")
    compiled = kda_row_update.lower(
        sds((3, N, H, 128, 128)), sds((), jnp.int32), sds((N, H, 128)),
        sds((N, H, 128)), sds((N, H, 128)), sds((N, H, 128)), sds((N, H)),
        sds((N,), jnp.bool_)).compile()
    assert runs_kernel(compiled.as_text(), "kda_row_update")


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_solar_programs_move_neither_the_pool_nor_the_rows_nor_a_weight_stack(
        one_chip, as_if_on_tpu, program):
    """The decode step and the prompt-chunk program of the cell of pages
    and state rows (one period at the published widths: a GQA layer and
    three KDA layers, 40 held experts of 320 and a shared one a layer, 48
    slots, 15,361 pages 1 layer deep, 3 x 48 state rows of 4 MiB): the
    donated pool, rows and tails are outputs in place, nothing copies
    them or an experts' stack, every kernel of the path engages and the
    compiler's ask stays inside the chip."""
    from deeperspeed_tpu.models import mixers
    from deeperspeed_tpu.serving.engine import (make_chunk_step,
                                                make_decode_step)

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg, scfg = _solar()
    params = jax.tree.map(
        lambda a: sds(a.shape),
        jax.eval_shape(lambda: mixers.init_params(jax.random.PRNGKey(0), cfg)))
    N, bps = scfg.num_slots, scfg.blocks_per_slot
    assert (N, scfg.pool_blocks, bps, scfg.table_widths) == (
        48, (15361,), 320, (320,))
    i32 = jnp.int32
    pool = sds((1, 15361, 8, 64, 128))
    state = {"kda": sds((3, N, 64, 128, 128), jnp.float32),
             "conv": sds((3, N, 3, 3 * 64 * 128))}
    if program == "decode":
        compiled = make_decode_step(cfg, scfg).lower(
            params, pool, pool, sds(*_idle_slots(N, bps)),
            sds((N + 4,), i32), None, state).compile()
    else:
        compiled = make_chunk_step(cfg, scfg).lower(
            params, pool, pool, None, state, sds((1, 1024), i32),
            sds((bps,), i32), sds((), i32), sds((), i32), sds((), i32)).compile()
    text = compiled.as_text()
    assert runs_kernel(text, "paged_sparse_attn_slots") == (
        program == "decode")
    assert runs_kernel(text, "kda_row_update") == (program == "decode")
    assert runs_kernel(text, "chunk_past_attn") == (program == "chunk")
    assert runs_kernel(text, "kda_chunk") == (program == "chunk")
    assert runs_kernel(text, "gmm")
    assert count_alias_pairs(text) == 4        # k, v, the rows, the tails
    big = ("bf16[1,15361,", "f32[3,48,", "bf16[3,48,", "f32[48,64,128,128]",
           "bf16[3,40,", "bf16[1,40,", "bf16[40,4096,1280]",
           "bf16[40,1280,4096]")
    moved = [ln.strip()[:140] for ln in text.splitlines()
             if (" copy(" in ln or "dynamic-slice_bitcast_fusion" in ln)
             and ln.split(" = ")[1].startswith(big)]
    assert moved == []
    weights = sum(2 * math.prod(a.shape) for a in jax.tree.leaves(params))
    assert weights == 2 * 3_308_353_344
    caches = 2 * 2 * math.prod(pool.shape) + 4 * math.prod(
        state["kda"].shape) + 2 * math.prod(state["conv"].shape)
    ask = extract_memory_analysis(compiled)["peak_bytes"]
    assert weights + caches < ask < weights + caches + 2 ** 30, (
        ask, weights, caches)
    assert ask < 11.2 * 2 ** 30


def _ouro():
    from benchmark import manifest as mf
    from benchmark.adapters import ouro as adapter
    from deeperspeed_tpu.serving import ServingConfig
    from deeperspeed_tpu.serving.kv_cache import page_rule_for

    man = mf.Manifest()
    cfg = adapter.model_config(man.config("ouro-2.6b"))
    scfg = ServingConfig.from_dict(
        man.workload_file("ouro-2.6b.serve-solve")["serving"])
    return cfg, scfg.for_cache(page_rule_for(cfg))


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_ouro_programs_loop_in_place_inside_the_chip(one_chip, as_if_on_tpu,
                                                     program):
    """The decode step and the prompt-chunk program of the looped cell at
    the published size, whole (48 layers run 4 times, 8 slots, 73 pages of
    64 positions 192 cache layers deep = 6.84 GiB beside 4.97 GiB of
    weights): the donated pools are outputs in place, nothing copies the
    pool or a weight stack, the passes are ONE loop (the page-list kernel
    and the chunk kernel each appear once in the text, not four times),
    and the compiler's ask stays inside the chip's 15.75 GiB with the
    margin the other cells keep."""
    from deeperspeed_tpu.models import mixers
    from deeperspeed_tpu.serving.engine import (make_chunk_step,
                                                make_decode_step)

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg, scfg = _ouro()
    params = jax.tree.map(
        lambda a: sds(a.shape),
        jax.eval_shape(lambda: mixers.init_params(jax.random.PRNGKey(0), cfg)))
    N, bps = scfg.num_slots, scfg.blocks_per_slot
    assert (N, scfg.pool_blocks, bps, scfg.table_widths) == (
        8, (73,), 20, (20,))
    assert (cfg.loop_steps, cfg.cache_layers("full_attn")) == (4, 192)
    i32 = jnp.int32
    pool = sds((192, 73, 16, 64, 128))
    if program == "decode":
        compiled = make_decode_step(cfg, scfg).lower(
            params, pool, pool, sds(*_idle_slots(N, bps)),
            sds((N + 4,), i32), None, None).compile()
    else:
        compiled = make_chunk_step(cfg, scfg).lower(
            params, pool, pool, None, None, sds((1, 256), i32),
            sds((bps,), i32), sds((), i32), sds((), i32), sds((), i32)).compile()
    text = compiled.as_text()
    kernel = ("paged_sparse_attn_slots" if program == "decode"
              else "chunk_past_attn")
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and kernel in ln]
    assert len(calls) == 1, len(calls)
    assert count_alias_pairs(text) == 2        # k, v
    big = ("bf16[192,73,", "bf16[48,2048,", "bf16[48,5632,", "bf16[49152,",
           "bf16[2048,49152]")
    moved = [ln.strip()[:140] for ln in text.splitlines()
             if (" copy(" in ln or "dynamic-slice_bitcast_fusion" in ln)
             and ln.split(" = ")[1].startswith(big)]
    assert moved == []
    weights = sum(2 * math.prod(a.shape) for a in jax.tree.leaves(params))
    assert weights == 2 * 2_667_974_657
    caches = 2 * 2 * math.prod(pool.shape)
    assert caches == 73 * 64 * 1_572_864
    ask = extract_memory_analysis(compiled)["peak_bytes"]
    assert weights + caches < ask < weights + caches + 2 ** 30, (
        ask, weights, caches)
    assert ask < 12.8 * 2 ** 30
