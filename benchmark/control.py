#!/usr/bin/env python3
"""The control of the check that decides ``correct``: the plain reference,
put in the program's place and computed in the nearest precision below the
one the configuration states (8-bit integer matrix multiplications for
bfloat16). It has to come out NOT correct under the cell's own limits.

    python benchmark/control.py --workload <name> --seeds 1,2,3 [--seconds s]

A training cell needs no engine: both references follow the first two
steps on the seed's rows. A serving cell serves a short window at the
cell's own load, then compares the tokens the control puts first at the
positions of the served ones. The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def control_train(ctx) -> dict:
    """Gaps of the control against the reference, by the cell's limits."""
    from benchmark import generator as tg
    from benchmark.runners import train

    mix, cfg = ctx.traffic, ctx.config
    rows = mix["micro_batch"] * mix["accumulation_steps"] * len(ctx.devices)
    batches = tg.train_batches(mix, ctx.seed, 2, rows, cfg["vocab_size"])
    ref = train.reference_numbers(ctx, batches, "f32")
    out = {}
    for num in ctx.control_numerics:
        low = train.reference_numbers(ctx, batches, num)
        lines = []
        out[num] = train.compare(low, ref, ctx.cell_file["check"]["limits"],
                                 lines.append)
        for s in lines:
            ctx.say(f"control[{num}] " + s)
    return {"correct": any(out.values()), "by_numerics": out}


def control_serve(ctx) -> dict:
    import gc

    import jax

    from benchmark import generator as tg
    from benchmark.runners import serve

    cfg, cell, mix = ctx.config, ctx.cell_file, ctx.traffic
    requests = tg.serve_requests(mix, ctx.seed, ctx.seconds, cfg["vocab_size"])
    engine = serve.build_engine(ctx)
    serve.warm(engine, requests, cfg["vocab_size"], ctx.seed)
    recs, _, _ = serve.offer(engine, requests, ctx.seconds,
                             mix["first_token_cap_s"], ctx.spans)
    w = serve.reduce_window(recs, ctx.seconds)
    sample = serve.sample_finished(w["done"], ctx.seed,
                                   cell["check"]["min_served_tokens"])
    del engine, recs, w
    gc.collect()
    jax.clear_caches()
    limit = cell["check"]["limits"]["served_logit_gap"]
    out = {}
    for num in ctx.control_numerics:
        g = serve.check_served(ctx, sample, control=num)
        ctx.say(f"program served_logit_gap {g['widest_gap']:.6g}; control[{num}] "
                f"{g['control_widest_gap']:.6g} (limit {limit:g}); "
                f"{g['tokens']} tokens")
        out[num] = g["control_widest_gap"] <= limit
    return {"correct": any(out.values()), "by_numerics": out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--numerics", default=None,
                    help="comma-separated lower precisions to read beside the "
                         "cell's own control (int8, int8t, fp8)")
    args = ap.parse_args(argv)
    from benchmark import run as brun

    out = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        say = lambda m, seed=seed: print(f"[control {args.workload} seed={seed}] {m}",
                                         flush=True)
        ctx = brun.open_context(args.workload, seed, args.seconds, 0, say)
        ctx.control_numerics = (args.numerics.split(",") if args.numerics
                                else [ctx.cell_file["check"]["control_numerics"]])
        fn = control_train if ctx.cell_file["runner"] == "train" else control_serve
        r = fn(ctx)
        say(f"control correct: {r['correct']} (must be False); "
            f"{time.perf_counter() - t0:.1f} s")
        out[seed] = r["correct"]
    print(json.dumps({"control_correct": out}))
    return 0 if not any(out.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
