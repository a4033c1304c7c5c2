"""The seam between the serving programs and the layer kinds: ONE table
(``serving/kinds.py``), two program builders that name no kind, and a
block adapter a kind under one signature and one return shape. What the
programs compute is the other serving tests' to guard; these fail when the
seam goes."""

import functools
import inspect
import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark import device, manifest as mf, run as brun
from benchmark.runners import serve
from deeperspeed_tpu.models import mixers
from deeperspeed_tpu.models.gpt import LAYER_KINDS
from deeperspeed_tpu.serving import engine, kinds, kv_cache

EVERY_NAME = sorted(set(LAYER_KINDS) | set(kinds.KINDS)
                    | set(mixers.STACK_KEY))
# the toy serving cell whose stack has the kind
CELL_OF = {"attention": "toy-neox.serve",
           "minicpm4": "toy-sala.serve-longdoc",
           "lightning": "toy-sala.serve-longdoc",
           "mamba_attn": "toy-h1.serve-chat",
           "eva": "toy-eva.serve-bytes",
           "full_attn": "toy-mellum.serve-code",
           "window_attn": "toy-mellum.serve-code",
           "kda": "toy-solar.serve-reason"}


@pytest.mark.parametrize("kind", EVERY_NAME)
def test_a_kind_is_a_name_a_record_and_a_stack(kind):
    """A kind ``GPTConfig`` accepts has a record, a record names a kind
    ``GPTConfig`` accepts, and the model says where both keep their
    weights."""
    assert kind in LAYER_KINDS and kind in mixers.STACK_KEY
    record = kinds.KINDS[kind]
    assert callable(record.block) and record.decode is not None
    assert isinstance(record.prefix_reuse, bool)
    assert kind in CELL_OF, "a kind no toy cell serves is a kind no test runs"


def between_the_builders():
    """``serving/engine.py`` from ``make_decode_step`` to the end of
    ``make_chunk_step``, less ``prefill_chunk_for`` (which may keep the
    kinds' constraints on a chunk's length)."""
    src = inspect.getsource(engine)
    src = src[src.index("def make_decode_step"):]
    src = src[:src.index("return ds_prefill_chunk")]
    return src.replace(inspect.getsource(engine.prefill_chunk_for), "")


@pytest.mark.parametrize("source", [
    pytest.param(lambda: inspect.getsource(engine.make_decode_step),
                 id="make_decode_step"),
    pytest.param(lambda: inspect.getsource(engine.make_chunk_step),
                 id="make_chunk_step"),
    pytest.param(between_the_builders, id="all_that_lies_between_them")])
def test_the_program_builders_name_no_kind(source):
    """Code, comments and docstrings: the frame of a program knows nothing
    of what a kind reads, keeps or writes."""
    names = "|".join(map(re.escape, LAYER_KINDS))
    named = re.findall(
        rf"\b(?:{names})\b|GROUPED_KINDS|SLOT_LIST_KINDS"
        r"|cfg\.(?:sparse|eva|ssm|kda|gqa)\b", source())
    assert not named, named


@functools.lru_cache(maxsize=None)
def toy_engine(cell):
    data = os.path.join(mf.ROOT, "tests", "bench", "data")
    for name in sorted(os.listdir(data)):
        if not (name.startswith("BENCHMARK.toy") and name.endswith(".json")):
            continue
        man = mf.Manifest(os.path.join(data, name),
                          extra_dirs=[os.path.join(mf.ROOT, "benchmark")])
        if cell in man.cells():
            devs = jax.devices()[:1]
            return serve.build_engine(brun.build_context(
                man, cell, 7, 1.0, 0, devs, device.describe(devs),
                lambda m: None))
    raise KeyError(cell)


@pytest.mark.parametrize("kind,program", [
    (kind, program) for kind in sorted(kinds.KINDS)
    for program in ("decode", "chunk")
    if getattr(kinds.KINDS[kind], program) is not None])
def test_a_block_adapter_returns_x_rows_kept(kind, program):
    """One layer of the kind inside each program, shapes alone
    (``jax.eval_shape`` at the toy cell's sizes): the stream comes back as
    it went in; the rows the loop carries come back in the shape they
    went in (a decode step's whole state; None from a prompt chunk, which
    carries none); where the kind hands the experts' counts out, what is
    kept is the pair (what the write takes, the counts)."""
    eng = toy_engine(CELL_OF[kind])
    cfg, scfg, kv = eng.cfg, eng.scfg, eng.kv
    record = kinds.KINDS[kind]
    N, bps = scfg.num_slots, scfg.blocks_per_slot
    C = None if cfg.classic else engine.prefill_chunk_for(cfg, scfg)

    def one_layer(params, k_pool, v_pool, kc_pool, state):
        p = jax.tree.map(lambda a: a[0], params[mixers.STACK_KEY[kind]])
        if program == "decode":
            lengths = jnp.zeros((N,), jnp.int32)
            view = kv_cache.decode_view(cfg, scfg, None)(
                params, k_pool, v_pool, kc_pool, state,
                jnp.zeros((N, bps), jnp.int32), lengths, lengths[:, None])
            x = mixers.embed_tokens(cfg, params, lengths, lengths)[:, None]
            rows = state
        else:
            zero = jnp.int32(0)
            view = kv_cache.chunk_view(cfg, scfg, None)(
                params, k_pool, v_pool, kc_pool, state,
                jnp.zeros((bps,), jnp.int32), zero, zero, jnp.int32(C),
                jnp.arange(C, dtype=jnp.int32))
            x = mixers.embed_tokens(cfg, params,
                                    jnp.zeros((1, C), jnp.int32))
            rows = None
        out = record.block(view, getattr(record, program), x, p, 0, rows, 0)
        assert len(out) == 3
        return x, rows, out

    x, rows, (x_out, rows_out, kept) = jax.eval_shape(
        one_layer, eng.params, kv.k, kv.v, kv.kc, kv.state)
    assert (x_out.shape, x_out.dtype) == (x.shape, x.dtype)
    shapes = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    assert shapes(rows_out) == shapes(rows)
    if record.counts_experts:
        for_the_write, counts = kept
        assert counts.shape == (mixers.expert_counts_width(cfg),)


@pytest.mark.parametrize("kind", ["minicpm4", "mamba_attn", "kda"])
def test_a_cache_refuses_a_page_rule_that_is_not_its_own(kind):
    """Since PR 47 ``PagedKVCache.__init__`` compares the serving
    configuration's page rule with the model's once, for every mixed
    stack (the eva and the ring stacks each made the check themselves
    before): a table sized by another stack's rule is refused by name."""
    eng = toy_engine(CELL_OF[kind])
    own = kv_cache.page_rule_for(eng.cfg)
    assert eng.scfg.page_rule == own
    other = kv_cache.PageRule(ring=eng.scfg.block_size)
    assert other != own
    with pytest.raises(ValueError, match="page rule"):
        kv_cache.PagedKVCache(eng.cfg, eng.scfg.for_cache(other))
