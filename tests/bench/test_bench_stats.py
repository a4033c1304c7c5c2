"""Percentile, due-time latency, generator-lateness and traffic arithmetic
on hand-made inputs."""

import numpy as np
import pytest

from benchmark import generator as traffic
from benchmark import stats

SERVE = {"arrivals": {"process": "poisson", "rate_per_s": 5.0, "stretches": 8},
         "prompt_tokens": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                           "min": 32, "max": 1536},
         "output_tokens": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                           "min": 8, "max": 384}}


def test_percentile_matches_numpy_and_hand_values():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    rng = np.random.default_rng(0).random(101).tolist()
    for q in (0, 5, 50, 95, 100):
        assert stats.percentile(rng, q) == pytest.approx(np.percentile(rng, q))
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    assert stats.samples_beyond(200, 95) == 10


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx((5.25 - 1.75) / 3.5)


def test_latency_counts_from_when_a_request_was_due():
    due, sent, first = [0.0, 1.0, 2.0], [0.0, 1.4, 2.1], [0.5, 1.9, 2.2]
    assert stats.due_latencies(due, first) == pytest.approx([0.5, 0.9, 0.2])
    late = stats.lateness(due, sent)
    assert late["n"] == 3 and late["max_ms"] == pytest.approx(400.0)
    assert late["median_ms"] == pytest.approx(100.0)
    assert stats.token_gaps([[1.0, 1.5, 1.75], [3.0]]) == pytest.approx([0.5, 0.25])


def test_every_seed_offers_the_same_sizes_and_gaps_in_another_order():
    a = traffic.serve_requests(SERVE, 3, 40.0, 1000)
    b = traffic.serve_requests(SERVE, 3_000_000_019, 40.0, 1000)
    assert len(a) == len(b) == 200
    sizes = lambda rs, k: [len(r[k]) if k == "prompt" else r[k] for r in rs]
    for k in ("prompt", "max_new_tokens"):
        assert sorted(sizes(a, k)) == sorted(sizes(b, k))    # same work
        assert sizes(a, k) != sizes(b, k)                    # another order
    assert a[-1]["due_s"] != b[-1]["due_s"]
    assert a[0]["prompt"] != b[0]["prompt"]      # other token ids
    assert a == traffic.serve_requests(SERVE, 3, 40.0, 1000)   # same seed, same inputs
    assert all(0.0 <= r["due_s"] < 40.0 for r in a)
    assert sorted(r["due_s"] for r in a) == [r["due_s"] for r in a]
    assert max(len(r["prompt"]) + r["max_new_tokens"] for r in a) <= 2048
    # the sizes are the quantiles of the mix, not draws: median as stated
    assert abs(np.median([len(r["prompt"]) for r in a]) - 512) < 8
    assert abs(np.median([r["max_new_tokens"] for r in a]) - 64) < 2
    # another rate keeps the distribution and changes the count
    assert len(traffic.serve_requests(SERVE, 3, 40.0, 1000, rate=2.0, tag="x")) == 80


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_the_order_is_dealt_so_that_every_stretch_of_the_window_is_alike(seed):
    """With 8 stretches each eighth of the schedule holds one of every 8
    neighbouring quantiles: its output tokens stay near an eighth of all."""
    n, k = 200, SERVE["arrivals"]["stretches"]
    order = traffic.dealt_order(traffic.rng_for(seed, 1), n, k)
    assert sorted(order) == list(range(n))
    for s in range(k):
        part = np.sort(order[s * n // k:(s + 1) * n // k])
        assert (part // k == np.arange(n // k)).all()
    rs = traffic.serve_requests(SERVE, seed, 40.0, 1000)
    out = np.array([r["max_new_tokens"] for r in rs]).reshape(k, -1).sum(axis=1)
    assert out.max() / out.min() < 1.25
    free = traffic.dealt_order(traffic.rng_for(seed, 1), n, 1)
    assert sorted(free) == list(range(n))


def test_training_rows_all_differ_and_masks_are_exact():
    mix = {"objective": "mlm", "seq": 64, "mask_share": 0.15, "mask_token_id": 3}
    (ids, labels), = traffic.train_batches(mix, 9, 1, 8, 100)
    assert ids.shape == labels.shape == (8, 64)
    assert ((labels != -100).sum(axis=1) == 10).all()
    assert (ids[labels != -100] == 3).all()
    (tok,), (tok2,) = traffic.train_batches(
        {"objective": "causal", "seq": 16}, 2**33 + 5, 2, 4, 50)
    assert tok.shape == (4, 17) and len({r.tobytes() for r in np.vstack([tok, tok2])}) == 8
