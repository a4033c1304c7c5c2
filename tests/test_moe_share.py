"""One chip's share of a layer of routed experts (``moe.gated_experts``
told which experts it holds): the router's rule against the reference's,
the shares adding up to the uncut layer, and the path of a program that
holds every expert left as it was."""

import copy
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark.refs import init as rinit
from benchmark.refs import mellum as mellum_ref
from benchmark.refs import solar_open2 as ref
from deeperspeed_tpu.models import mixers, moe

DATA = os.path.join(mf.ROOT, "tests", "bench", "data", "configs")
TOY = mf.load_json(os.path.join(DATA, "toy-solar.json"))
E, K, D = 16, 2, 64


def uncut():
    """The toy's layer with all 16 experts on one chip."""
    cfg = copy.deepcopy(TOY)
    cfg["n_routed_experts"] = E
    cfg["share"]["first_expert"] = 0
    return cfg


@pytest.fixture(scope="module")
def layer():
    """One layer's ``mlp`` tree of the uncut toy, and 40 normed tokens."""
    p = rinit.init_tree(11, ref.leaf_specs(uncut())["kda"]["mlp"],
                        jnp.float32)
    mlp = jax.tree.map(lambda a: a[1], p)
    m = jax.random.normal(jax.random.PRNGKey(2), (40, D))
    return mlp, m / jnp.sqrt(jnp.mean(m * m, -1, keepdims=True))


def share_of(mlp, first, count):
    """What the chip that holds ``first .. first + count - 1`` is handed:
    its experts' weights, the whole router and bias, the shared expert."""
    cut = dict(mlp)
    for name in ("w_gate", "w_up", "w_down"):
        cut[name] = mlp[name][first:first + count]
    return cut


def test_the_shares_add_up_to_the_uncut_layer(layer):
    """(c) The four shares' routed parts, with what every chip computes
    alike (the shared expert) counted ONCE, equal the reference's uncut
    layer; every assignment is computed on exactly one chip."""
    mlp, m = layer
    with jax.default_matmul_precision("highest"):
        want = ref.make(uncut()).experts(mlp, m)
        total = mixers.gated_ffn(m, mlp["shared"], jnp.float32)
        computed = away = 0
        for first in range(0, E, 4):
            y, counts = moe.gated_experts(
                share_of(mlp, first, 4), m, K, True, held=(first, 4),
                rule="sigmoid_bias")
            total = total + y
            computed += int(counts[1])
            away += int(counts[3])
            assert int(counts[1] + counts[3]) == 40 * K
    assert computed == 40 * K and away == 3 * 40 * K
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-6)
    # one share alone is what the reference gives for that share
    cfg = uncut()
    cfg["n_routed_experts"], cfg["share"]["first_expert"] = 4, 8
    with jax.default_matmul_precision("highest"):
        one = ref.make(cfg).experts(share_of(mlp, 8, 4), m)
        y, _ = moe.gated_experts(share_of(mlp, 8, 4), m, K, True, held=(8, 4),
                                 shared=mlp["shared"], rule="sigmoid_bias")
    np.testing.assert_allclose(np.asarray(y), np.asarray(one), atol=2e-6)


@pytest.mark.parametrize("case", ["plain", "ties", "bias_changes_the_winners"])
def test_the_router_rule_is_the_references(layer, case):
    """(d) sigmoid scores, the largest ``score + bias`` win (equal ones
    to the lower index), the gates are the winners' UNBIASED scores over
    their sum."""
    mlp, m = layer
    router, bias = mlp["router"], mlp["router_bias"]
    if case == "ties":      # experts 3, 9 and 12 score alike for every token
        router = router.at[:, 9].set(router[:, 3]).at[:, 12].set(router[:, 3])
        bias = jnp.zeros_like(bias)
    if case == "bias_changes_the_winners":
        bias = bias.at[5].set(3.0)
    p = dict(mlp, router=router, router_bias=bias)
    with jax.default_matmul_precision("highest"):
        got_e, got_g = moe.route_top_k(m, router, K, True, "sigmoid_bias", bias)
        want_e, want_g = ref.make(uncut()).route(p, m)
        s = np.asarray(jax.nn.sigmoid(m @ router))
    np.testing.assert_array_equal(np.asarray(got_e), np.asarray(want_e))
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g), atol=1e-6)
    rows = np.arange(40)[:, None]
    np.testing.assert_allclose(
        np.asarray(got_g), s[rows, got_e] / s[rows, got_e].sum(-1, keepdims=True),
        atol=1e-6)
    if case == "ties":
        got = np.asarray(got_e)
        # of the three equal experts the lower indices win, in order
        assert not (got == 12).any() or ((got == 3).any(1) & (got == 9).any(1))[
            (got == 12).any(1)].all()
        assert ((got == 9).any(1) <= (got == 3).any(1)).all()
    if case == "bias_changes_the_winners":
        plain, _ = moe.route_top_k(m, router, K, True, "sigmoid_bias",
                                   jnp.zeros_like(bias))
        assert (np.asarray(got_e) == 5).any(1).all()
        assert not (np.asarray(plain) == 5).any(1).all()
        # ... and its gate is its own score, not the biased one
        assert float(got_g.max()) <= 1.0


def test_a_program_that_holds_every_expert_is_left_as_it_was():
    """(e) Mellum's toy layer: ``held`` unset is the path every earlier
    cell runs (softmax, no shared expert, three counts); told that it
    holds ALL the experts the function gives the same bits and counts no
    assignment away."""
    cfg = mf.load_json(os.path.join(DATA, "toy-mellum.json"))
    stack = rinit.init_tree(
        7, mellum_ref.leaf_specs(cfg)["window_attn"]["mlp"], jnp.float32)
    mlp = jax.tree.map(lambda a: a[2], stack)
    m = jax.random.normal(jax.random.PRNGKey(4), (24, cfg["hidden_size"]))
    live = jnp.arange(24) % 5 != 0
    y0, c0 = moe.gated_experts(mlp, m, 2, True, live)
    y1, c1 = moe.gated_experts(mlp, m, 2, True, live, held=(0, 8))
    assert c0.shape == (3,) and c1.shape == (4,)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    assert np.asarray(c1).tolist() == np.asarray(c0).tolist() + [0]
    # the stacked form (a traced layer index) alike
    y2, c2 = jax.jit(lambda i: moe.gated_experts(stack, m, 2, True, live,
                                                 layer=i))(jnp.int32(2))
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y0), atol=1e-6)
    assert np.asarray(c2).tolist() == np.asarray(c0).tolist()


def test_dead_lanes_and_absent_experts_add_nothing(layer):
    """An idle lane is routed nowhere and counts as no assignment, away
    or here; a token whose experts are all absent gets the shared expert
    alone."""
    mlp, m = layer
    live = jnp.arange(40) < 30
    cut = share_of(mlp, 4, 4)
    y, counts = moe.gated_experts(cut, m, K, True, live, held=(4, 4),
                                  shared=mlp["shared"], rule="sigmoid_bias")
    assert int(counts[1] + counts[3]) == 30 * K
    assert not np.asarray(y[30:]).any()
    experts, _ = moe.route_top_k(m, mlp["router"], K, True, "sigmoid_bias",
                                 mlp["router_bias"])
    absent = ~(((np.asarray(experts) >= 4) & (np.asarray(experts) < 8)).any(1))
    absent[30:] = False
    assert absent.any()
    alone = mixers.gated_ffn(m, mlp["shared"], jnp.float32)
    np.testing.assert_allclose(np.asarray(y)[absent], np.asarray(alone)[absent],
                               atol=1e-6)
