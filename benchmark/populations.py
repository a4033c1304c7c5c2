#!/usr/bin/env python3
"""Which population of token gaps a serving cell's 95th percentile sits in.

A cell's ``tpot_p95_ms`` is one number off a mixture: a gap between two
tokens of one request holds a decode step and whatever prompt work ran
beside it, and the kinds of prompt work differ by tens of milliseconds.
Where one kind is 5% of a window's gaps to within a few gaps, the
percentile interpolates between two populations and reads anywhere
between them from seed to seed (PERF.md section 6, PR 44). This tool
counts the kinds. It builds the cell's engine ONCE, serves each seed's
window through ``runners/serve.py``'s ``offer`` as a run does, and
prints a line a seed: ``tpot_p95_ms`` as the run reduces it, the 88th to
the 100th percentile of the window's gaps, and the share of gaps by what
ran between their two tokens (``KINDS``). A last line has the extremes
over the seeds. No cell runs it and nothing it prints is a metric.

    python benchmark/populations.py --workload minicpm-sala.serve-longdoc \\
        --seeds 1,2,3 [--rate 0.25] [--seconds 40]

What a ``step()`` dispatched is read off the engine around the call: the
prompts in prefill and their positions (``engine._chunking``, the one
private thing read here), whether a decode step was in flight, and the
counters of ``ServingMetrics``. The loop runs one step ahead, so a chunk
dispatched in call ``k`` runs on the device between the decode steps
read in calls ``k`` and ``k + 1``: it falls into the gap that BEGINS at
the return of call ``k``. Work the host waits out inside a call (a
bucketed prefill; a prompt's last chunk with nothing in flight, whose
first token is picked at once) falls into the gap that ENDS there.
"""

import argparse
import json
import os
import sys
import time

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import generator as tg      # noqa: E402
from benchmark import stats                # noqa: E402

# what ran between the two tokens of a gap, beside the decode step
KINDS = ("decode", "chunk_below", "chunk_beyond", "two_chunks", "prefill")
TOP = tuple(range(88, 101))


class Watched:
    """An engine whose ``step()`` leaves a record of what it dispatched:
    ``steps[k]`` has the host's clock at the return of call ``k``, the
    prompt chunks it dispatched as ``(offset, waited_out)``, the bucketed
    prefills it ran and every live request's count of tokens after it."""

    def __init__(self, engine):
        self._engine = engine
        self.steps = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def _prefilling(self) -> dict:
        return {st["req"].rid: (st.get("m", 0), st["chunk"], st["next"], st["n"])
                for st in self._engine._chunking.values()}

    def step(self):
        e = self._engine
        before, in_flight = self._prefilling(), bool(e._inflight)
        prefills, n_chunks = e.metrics.prefills, e.metrics.prefill_chunks
        finished = e.step()
        t = time.perf_counter()
        after = self._prefilling()
        chunks, picked = [], 0
        for rid in {**before, **after}:     # those in prefill before, then the admitted
            base, size, _, n = after.get(rid) or before[rid]
            first = before[rid][2] if rid in before else 0
            # a prompt that left the table had its first token picked in
            # this call: left over from the call before (nothing was
            # dispatched for it here), or at once behind its last chunk,
            # which the host then waited out (with a step in flight the
            # pick is left to the next call and the prompt stays listed)
            gone = rid not in after
            last = n if gone else after[rid][2]
            picked += gone
            chunks += [(base + c * size, gone and c == n - 1 and not in_flight)
                       for c in range(first, last)]
        unlisted = e.metrics.prefill_chunks - n_chunks - len(chunks)
        if unlisted > 0:    # a prompt that came and went inside this call
            chunks += [(c * e.scfg.prefill_chunk, c == unlisted - 1)
                       for c in range(unlisted)]
            picked += 1
        live = [q for q in e.sched.slots if q is not None] + list(finished)
        self.steps.append({
            "t": t, "chunks": chunks,
            # first tokens picked off a prompt's logits, less the chunked ones
            "prefills": max(0, e.metrics.prefills - prefills - picked),
            "tokens": {q.rid: len(q.generated) for q in live}})
        return finished


def classify(steps, dense_len=None):
    """Every gap between successive tokens of one request as ``(seconds,
    kind)``, from a record of steps as ``Watched`` keeps it. A request's
    tokens are timed at the return of the call that showed them, as
    ``offer`` times them."""
    seen, at = {}, {}
    for k, s in enumerate(steps):
        for rid, n in s["tokens"].items():
            at.setdefault(rid, []).extend([k] * (n - seen.get(rid, 0)))
            seen[rid] = n
    gaps = []
    for calls in at.values():
        for a, b in zip(calls, calls[1:]):
            held = [c for s in steps[a:b] for c in s["chunks"] if not c[1]]
            held += [c for s in steps[a + 1:b + 1] for c in s["chunks"] if c[1]]
            if any(s["prefills"] for s in steps[a + 1:b + 1]):
                kind = "prefill"
            elif not held:
                kind = "decode"
            elif len(held) > 1:
                kind = "two_chunks"
            elif dense_len is not None and held[0][0] >= dense_len:
                kind = "chunk_beyond"
            else:
                kind = "chunk_below"
            gaps.append((steps[b]["t"] - steps[a]["t"], kind))
    return gaps


def shares(gaps) -> dict:
    """The share of gaps (%) and the median gap (ms) of every kind."""
    out = {}
    for kind in KINDS:
        xs = [g for g, k in gaps if k == kind]
        out[kind] = {"share_pct": 100.0 * len(xs) / len(gaps) if gaps else 0.0,
                     "median_ms": 1e3 * stats.percentile(xs, 50) if xs else None}
    return out


def warm_cell(ctx, engine, requests):
    """Warm the engine as the cell's runner does before its window."""
    from benchmark.runners import serve, serve_long

    vocab = ctx.config["vocab_size"]
    if ctx.cell_file["runner"] == "serve_long":
        return serve_long.warm(engine, ctx.config, vocab, ctx.seed)
    if ctx.cell_file["runner"] == "serve":
        return serve.warm(engine, requests, vocab, ctx.seed)
    raise ValueError(f"no warm-up here for the runner {ctx.cell_file['runner']!r}")


def window(ctx, engine, seed, rate=None) -> dict:
    """Serve one seed's window on the warmed engine and count its gaps;
    what is still decoding at the close is finished before returning."""
    from benchmark.runners import serve

    requests = tg.serve_requests(ctx.traffic, seed, ctx.seconds,
                                 ctx.config["vocab_size"], rate, tag=f"s{seed}-")
    watched = Watched(engine)
    m = engine.metrics
    before = (m.gaps, m.chunk_gaps)
    recs, _, took = serve.offer(watched, requests, ctx.seconds,
                                ctx.traffic["first_token_cap_s"], ctx.spans)
    w = serve.reduce_window(recs, ctx.seconds)
    runner_gaps = stats.token_gaps([r["tokens"] for r in recs])
    counted = (m.gaps - before[0], m.chunk_gaps - before[1])
    engine.run()
    dense_len = (ctx.config.get("sparse_config") or {}).get("dense_len")
    gaps = classify(watched.steps, dense_len)
    return {"seed": seed, "requests": w["attempted"], "failed": w["failed"],
            "cut_by_close": w["cut_by_close"], "took_s": took,
            "n_gaps": w["n_gaps"], "n_classified": len(gaps),
            "tpot_p50_ms": w["tpot_p50_ms"], "tpot_p95_ms": w["tpot_p95_ms"],
            "top_ms": {q: 1e3 * stats.percentile(runner_gaps, q) for q in TOP}
            if runner_gaps else {},
            "kinds": shares(gaps),
            "engine_chunk_gap_share_pct": (100.0 * counted[1] / counted[0]
                                           if counted[0] else 0.0),
            "lateness_p95_ms": w["lateness"]["p95_ms"]}


def extremes(rows) -> dict:
    """Over the seeds: the judged number's spread as a check takes it
    (quartile distance and the whole range over the median) and the
    least and the largest share of every kind."""
    p95 = [r["tpot_p95_ms"] for r in rows]
    med = stats.percentile(p95, 50)
    out = {"seeds": len(rows), "tpot_p95_ms": [min(p95), med, max(p95)],
           "range_over_median": (max(p95) - min(p95)) / med,
           "quartile_spread": stats.spread(p95) if len(p95) > 1 else 0.0}
    for kind in KINDS:
        xs = [r["kinds"][kind]["share_pct"] for r in rows]
        out[kind + "_share_pct"] = [min(xs), max(xs)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rate", type=float, default=None,
                    help="requests/s in place of the traffic file's")
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args(argv)
    from benchmark import run as brun
    from benchmark.runners import serve

    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = brun.open_context(args.workload, seeds[0], args.seconds, 0, print)
    engine = serve.build_engine(ctx)
    # every seed offers the same set of sizes, so one seed's warms them all
    warm_cell(ctx, engine, tg.serve_requests(
        ctx.traffic, seeds[0], args.seconds, ctx.config["vocab_size"], args.rate))
    rows = []
    for seed in seeds:
        rows.append(window(ctx, engine, seed, args.rate))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"over_seeds": extremes(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
