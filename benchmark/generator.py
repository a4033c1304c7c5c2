"""One general generator of traffic from a data file of parameters.

The sizes and arrival gaps of a serving mix are the quantiles of the file's
distributions (not random draws): every seed offers the same set of
prompts, outputs and gaps, so the work of a run does not depend on the
seed. The run's seed ORDERS them (three independent orders, so it also
pairs them anew), picks every token id and, elsewhere, the weights. The
order is dealt, not free: the window is cut into the file's ``stretches``
and each stretch gets one of every ``stretches`` neighbouring quantiles, so
no seed puts all the long answers at the close or all the short gaps in
one burst (a free shuffle at 0.8 of the knee moved the 95th percentile of
the time to the first token between 263 and 3301 ms, PR 23).
"""

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

MAX_SEED = 2**32


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, purpose); any whole-number seed."""
    return np.random.default_rng([int(seed) % MAX_SEED, int(seed) // MAX_SEED,
                                  int(stream)])


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    nd = NormalDist()
    qs = [(i + 0.5) / n for i in range(n)]
    xs = [median * math.exp(sigma * nd.inv_cdf(q)) for q in qs]
    return np.clip(np.rint(xs), lo, hi).astype(np.int64)


def _lengths(spec: dict, n: int) -> np.ndarray:
    if spec["dist"] == "lognormal":
        return _lognormal_quantiles(n, spec["median"], spec["sigma"],
                                    spec["min"], spec["max"])
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _gaps(spec: dict, n: int, seconds: float) -> np.ndarray:
    """n inter-arrival gaps that sum to ``seconds``: the quantiles of the
    arrival process's gap distribution, rescaled."""
    if spec["process"] == "poisson":
        g = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    elif spec["process"] == "uniform":
        g = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    return g * (seconds / g.sum())


def dealt_order(rng: np.random.Generator, n: int, stretches: int) -> np.ndarray:
    """An order of ``n`` sorted quantiles, by stretch of the window: each
    run of ``stretches`` neighbours is dealt one to a stretch, and each
    stretch is shuffled. ``stretches`` 1 is a free shuffle."""
    k = max(1, min(int(stretches), n))
    hands = [[] for _ in range(k)]
    for g in range(0, n, k):
        group = rng.permutation(np.arange(g, min(g + k, n)))
        for j, s in zip(group, rng.permutation(k)):
            hands[s].append(j)
    return np.array([j for h in hands for j in rng.permutation(h)], np.int64)


def serve_requests(traffic: dict, seed: int, seconds: float, vocab: int,
                   rate: float = None, tag: str = "r") -> List[Dict]:
    """The open-loop schedule of one run: a list of requests, each with
    the time it is due (seconds from the window's start), its prompt
    token ids and the number of tokens to generate. ``rate`` overrides
    the file's (the knee sweep uses that)."""
    rate = float(traffic["arrivals"]["rate_per_s"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    k = int(traffic["arrivals"].get("stretches", 1))
    order = rng_for(seed, 1)
    prompts = _lengths(traffic["prompt_tokens"], n)[dealt_order(order, n, k)]
    outputs = _lengths(traffic["output_tokens"], n)[dealt_order(order, n, k)]
    gaps = _gaps(traffic["arrivals"], n, seconds)[dealt_order(order, n, k)]
    due = np.cumsum(gaps) - gaps[0]
    tok = rng_for(seed, 2)
    return [{"rid": f"{tag}{i}", "due_s": float(due[i]),
             "prompt": tok.integers(0, vocab, int(prompts[i])).tolist(),
             "max_new_tokens": int(outputs[i])} for i in range(n)]


def train_batches(traffic: dict, seed: int, n_batches: int, rows: int,
                  vocab: int) -> List[tuple]:
    """``n_batches`` global batches of ``rows`` rows that all differ.

    ``objective: causal`` gives (tokens,) of seq+1 ids; ``objective: mlm``
    gives (input_ids, labels): exactly ``round(mask_share * seq)``
    positions of every row are scored (label = the original id, input =
    the mask id), the rest carry label -100."""
    seq = int(traffic["seq"])
    rng = rng_for(seed, 3)
    out = []
    for _ in range(n_batches):
        if traffic["objective"] == "causal":
            out.append((rng.integers(0, vocab, (rows, seq + 1), dtype=np.int32),))
        elif traffic["objective"] == "mlm":
            ids = rng.integers(0, vocab, (rows, seq), dtype=np.int32)
            k = int(round(traffic["mask_share"] * seq))
            pos = np.argsort(rng.random((rows, seq)), axis=1)[:, :k]
            labels = np.full((rows, seq), -100, np.int32)
            np.put_along_axis(labels, pos, np.take_along_axis(ids, pos, 1), 1)
            inputs = ids.copy()
            np.put_along_axis(inputs, pos, np.int32(traffic["mask_token_id"]), 1)
            out.append((inputs, labels))
        else:
            raise ValueError(f"unknown objective {traffic['objective']!r}")
    return out
