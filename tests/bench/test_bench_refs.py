"""The two plain references against ``models/gpt.py`` and
``models/bert.py`` at a toy size on the CPU: same seeded weights, same
rows, loss and every gradient leaf.

Tolerance: the program computes in float32 here, the reference in float32
at ``highest``; both sum in different orders, so losses agree to 1e-5 and
the norm of each gradient leaf's DIFFERENCE stays under 1e-4 of the leaf's
norm (read: 3e-7 NeoX, 6e-7 BERT). bfloat16 rounds at 2**-8 = 4e-3 per
operation, so the same program in bfloat16, the nearest precision below,
has to fail that limit: it reads 1.6e-2 and 6.5e-2, over a hundred times it."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark import generator as tg
from benchmark.adapters import bert as bert_ad
from benchmark.adapters import gpt_neox as neox_ad
from benchmark.refs import init as rinit
from benchmark.refs import layerwise as lw

DATA = os.path.join(os.path.dirname(__file__), "data")
LIMIT = 1e-4


@pytest.fixture(scope="module")
def toy():
    return mf.Manifest(os.path.join(DATA, "BENCHMARK.toy.json"),
                       extra_dirs=[mf.BENCH_DIR])


def _worst_leaf(prog_grads, ref_grads):
    rel = jax.tree.map(
        lambda a, b: float(jnp.linalg.norm((a.astype(jnp.float32) - b).ravel())
                           / (jnp.linalg.norm(b.ravel()) + 1e-12)),
        prog_grads, ref_grads)
    norms = jax.tree.map(lambda b: float(jnp.linalg.norm(b.ravel())), ref_grads)
    big = float(np.median(jax.tree.leaves(norms)))
    # leaves with all but no gradient (the unused pooler) have no ratio
    return max(r for r, n in zip(jax.tree.leaves(rel), jax.tree.leaves(norms))
               if n > 1e-3 * big)


@pytest.mark.parametrize("adapter,cell,change", [
    (neox_ad, "toy-neox.train", {}),
    (neox_ad, "toy-neox.train", {"use_parallel_residual": True, "rotary_pct": 0.25}),
    (bert_ad, "toy-bert.train", {})])
def test_reference_agrees_with_the_program_and_bf16_does_not(toy, adapter, cell,
                                                             change):
    cfg = dict(toy.config(toy.cell(cell)["config"]), **change)
    mix = toy.traffic(toy.cell(cell)["traffic"])
    specs = adapter.reference.leaf_specs(cfg)
    params = rinit.init_tree(2**32 + 17, specs, jnp.float32)
    (batch,) = tg.train_batches(mix, 5, 1, 4, cfg["vocab_size"])

    trainer = lw.Layerwise(adapter.reference.make(cfg), cfg["num_hidden_layers"], 2)
    ref_loss, ref_grads = trainer.loss_and_grad(params, batch)

    def program(dtype):
        c = dict(cfg, program=dict(cfg["program"], dtype=dtype))
        loss_fn = adapter.train_loss_fn(c, mix["seq"])
        return jax.value_and_grad(loss_fn)(params, adapter.feed(batch))

    loss32, grads32 = program(jnp.float32)
    assert abs(float(loss32) - ref_loss) / ref_loss < 1e-5
    assert _worst_leaf(grads32, ref_grads) < LIMIT
    loss16, grads16 = program(jnp.bfloat16)
    assert _worst_leaf(grads16, ref_grads) > 10 * LIMIT


def test_weights_from_any_seed_repeat_and_round_once():
    cfg = {"hidden_size": 16, "num_hidden_layers": 2, "num_attention_heads": 2,
           "intermediate_size": 32, "vocab_size": 40, "rotary_pct": 1.0,
           "layer_norm_eps": 1e-5, "hidden_act": "gelu_tanh",
           "use_parallel_residual": False}
    specs = neox_ad.reference.leaf_specs(cfg)
    a = rinit.init_tree(3_000_000_019, specs, jnp.float32)
    b = rinit.init_tree(3_000_000_019, specs, jnp.bfloat16)
    c = rinit.init_tree(3_000_000_020, specs, jnp.float32)
    assert jax.tree.all(jax.tree.map(lambda x, y: bool((x.astype(jnp.bfloat16) == y).all()), a, b))
    assert not bool((a["lm_head"] == c["lm_head"]).all())
    moved = rinit.moved_norms(3_000_000_019, specs, jnp.float32,
                              jax.tree.map(lambda x: x + 1.0, a))
    assert moved["['lm_head']"] == pytest.approx(np.sqrt(16 * 40))
