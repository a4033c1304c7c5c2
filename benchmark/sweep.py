#!/usr/bin/env python3
"""Find the knee of a serving cell once: offer the cell's traffic at each
of a few fixed rates to ONE engine (drained between rates) and print, for
each, what was completed and whether a backlog grew. The highest rate the
system sustains is read off the table by hand and four fifths of it are
written into the traffic file. The benchmark's own runs never search.

    python benchmark/sweep.py --workload neox-1.3b.serve --rates 2,3,4 --seconds 25 --seed 5
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    import numpy as np

    from benchmark import run as brun
    from benchmark import generator as tg
    from benchmark import populations
    from benchmark.runners import serve

    ctx = brun.open_context(args.workload, args.seed, args.seconds, 0, print)
    vocab = ctx.config["vocab_size"]
    rates = [float(r) for r in args.rates.split(",")]
    engine = serve.build_engine(ctx)
    widest = tg.serve_requests(ctx.traffic, args.seed, args.seconds, vocab, max(rates))
    populations.warm_cell(ctx, engine, widest)
    rows = []
    for rate in rates:
        reqs = tg.serve_requests(ctx.traffic, args.seed, args.seconds, vocab, rate,
                                 tag=f"rate{rate:g}-")
        n_occ = len(engine.metrics.occupancy)
        ctx.spans.durations["serve_step"].clear()
        recs, qd, took = serve.offer(engine, reqs, args.seconds, 60.0, ctx.spans,
                                     drain=True)
        w = serve.reduce_window(recs, args.seconds)
        half = [q for t, q in qd if args.seconds / 2 <= t <= args.seconds]
        first = [q for t, q in qd if t < args.seconds / 2]
        occ = engine.metrics.occupancy[n_occ:]
        row = {"rate": rate, "requests": w["attempted"], "failed": w["failed"],
               "offered_tokens_per_s": sum(r["max_new_tokens"] for r in reqs) / args.seconds,
               "serve_tokens_per_s": w["serve_tokens_per_s"],
               "ttft_p50_ms": w["ttft_p50_ms"], "ttft_p95_ms": w["ttft_p95_ms"],
               "tpot_p50_ms": w["tpot_p50_ms"], "tpot_p95_ms": w["tpot_p95_ms"],
               "queue_mean_first_half": float(np.mean(first)) if first else 0.0,
               "queue_mean_second_half": float(np.mean(half)) if half else 0.0,
               "queue_at_close": half[-1] if half else 0,
               "drained_after_s": took, "slot_occupancy": float(np.mean(occ)),
               "step_ms_median": 1e3 * float(np.median(ctx.spans.durations["serve_step"])),
               "lateness_p95_ms": w["lateness"]["p95_ms"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
