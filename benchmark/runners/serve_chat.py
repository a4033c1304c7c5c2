"""A serving cell of short chat over a model whose every layer keeps pages
AND a state row (Falcon-H1's ``mamba_attn``): many short prompts at a high
rate, dozens of slots decoding at once. The loop and the window's numbers
are ``serve.py``'s (``offer``, ``reduce_window``, ``build_engine``,
``sample_finished``), the traced run's extra metrics ``serve_long.py``'s
(``read_also``); what differs is the warm-up, the program's counters and
the check.

Warm-up: every prompt enters through ONE chunk program, whatever its
length, so one prompt of more than one chunk with a ragged last chunk,
followed by a few decode steps, has run every program of the window.

Check: the reference (``refs/falcon_h1.py``) casts ONE LAYER at a time to
float32 and the output head in blocks of columns, and follows each sampled
request as the plain forward, the recurrence token by token.
``served_logit_gap`` is ``serve.py``'s number.

    python benchmark/runners/serve_chat.py --workload <cell> --seeds 1,2 \\
        [--seconds 20]

serves a short window and then reads the controls the cell's file names
(``check.controls``): the reference in float8 put in the program's place,
the reference without the state-space branch, the reference without the
attention branch. Each has to read OVER the cell's limit. The benchmark's
own runs never do this.
"""

import gc
import os
import sys
import time

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark import device, generator as tg           # noqa: E402
from benchmark.refs import init as rinit                # noqa: E402
from benchmark.refs.numerics import Numerics            # noqa: E402
from benchmark.runners import serve, serve_long         # noqa: E402

COUNTED = ("chunk_gaps", "gaps", "decode_steps", "kv_live_pages",
           "state_bytes_moved", "state_resets", "prefill_chunks")


def warm(engine, vocab, seed) -> int:
    """One prompt of two chunks, the second ragged, and a few decode
    steps; returns its length."""
    n = engine.scfg.prefill_chunk + 37
    rng = tg.rng_for(seed, 4)
    engine.submit(rng.integers(0, vocab, n).tolist(), max_new_tokens=4,
                  request_id="warm")
    engine.run()
    return n


def snapshot(engine) -> dict:
    return {k: getattr(engine.metrics, k, 0) for k in COUNTED}


def program_counts(engine, before: dict) -> dict:
    """What the program counted of itself over the window, for the
    per-layer metrics: ``before`` is ``snapshot`` at the window's start."""
    m = engine.metrics
    d = {k: getattr(m, k, 0) - v for k, v in before.items()}
    steps = max(d["decode_steps"], 1)
    return {"chunk_gap_share_pct": (100.0 * d["chunk_gaps"] / d["gaps"]
                                    if d["gaps"] else 0.0),
            "state_gib_per_step": d["state_bytes_moved"] / steps / 2**30,
            "state_bytes": float(getattr(m, "state_bytes", 0)),
            "state_resets": float(d["state_resets"]),
            "chunks": float(d["prefill_chunks"]),
            # the page-list kernel, one call a layer and decode step: every
            # key head of a live slot reads the slot's live pages
            "paged_pages_per_decode_call": (engine.cfg.kv_heads
                                            * d["kv_live_pages"] / steps)}


def check_served(ctx, sample, controls=()) -> dict:
    """Reference gaps of the sampled requests and, for each of
    ``controls``, of the tokens the control puts first."""
    import jax.numpy as jnp

    cfg, ref = ctx.config, ctx.adapter.reference
    params = rinit.init_tree(ctx.seed, ref.leaf_specs(cfg),
                             jnp.dtype(ctx.cell_file["weights_dtype"]))
    make = {"fp8": lambda: ref.make(cfg, Numerics("fp8")),
            "nossm": lambda: ref.make(cfg, skip="ssm"),
            "noattn": lambda: ref.make(cfg, skip="attn")}
    return ref.served_gaps(ref.Forward(ref.make(cfg)), params, sample,
                           {c: ref.Forward(make[c]()) for c in controls})


def first_finished(recs, n_tokens):
    """The schedule's first requests, in the order they were offered,
    until ``n_tokens`` served tokens are in the sample: for a drained
    window, where which requests finished does not depend on the clock."""
    pick = []
    for r in recs:
        if sum(len(p["output"]) for p in pick) >= n_tokens:
            break
        if r["req"] is not None and r["req"].state == "finished" \
                and r["req"].finish_reason in ("length", "eos"):
            pick.append({"prompt": list(r["req"].prompt),
                         "output": list(r["req"].generated)})
    return pick


def serve_window(ctx, drain=False):
    """Build, warm and serve the cell's window; returns the engine, the
    per-request records and the window's numbers."""
    cfg, mix, say = ctx.config, ctx.traffic, ctx.say
    requests = tg.serve_requests(mix, ctx.seed, ctx.seconds, cfg["vocab_size"])
    engine = serve.build_engine(ctx)
    n_warm = warm(engine, cfg["vocab_size"], ctx.seed)
    lowered = device.LoweringCounter.get()
    compiles = lowered.count
    n_occ = len(engine.metrics.occupancy)
    say(f"warmed the chunk program and the decode step with one prompt of "
        f"{n_warm} tokens; {len(requests)} requests of "
        f"{min(len(r['prompt']) for r in requests)}-"
        f"{max(len(r['prompt']) for r in requests)} tokens offered over "
        f"{ctx.seconds:g} s; weights {cfg.get('weights')}; in use "
        f"{device.bytes_in_use(ctx.devices) / 2**30:.2f} GiB")
    ctx.spans.durations["serve_step"].clear()
    before = snapshot(engine)
    setup_s = time.perf_counter() - ctx.t_start
    recs, queue_depth, took = serve.offer(
        engine, requests, ctx.seconds, mix["first_token_cap_s"], ctx.spans,
        ctx.profiler, drain=drain)
    w = serve.reduce_window(recs, ctx.seconds)
    w["setup_s"] = setup_s
    w["peak"] = device.memory_peak_bytes(ctx.devices)
    w["compiled_inside"] = lowered.count - compiles
    counts = program_counts(engine, before)
    occ = engine.metrics.occupancy[n_occ:]
    ctx.spans.counters.update(counts)
    ctx.spans.counters["slot_occupancy"] = float(np.mean(occ)) if occ else 0.0
    ctx.spans.counters["hbm_peak_bytes"] = w["peak"]
    qd = [q for _, q in queue_depth]
    say(f"window: {w['attempted']} requests, {w['failed']} failed, "
        f"{w['cut_by_close']} still decoding when the run stopped at {took:.2f} s; "
        f"ttft mean {w['ttft_mean_ms']:.1f} p50 {w['ttft_p50_ms']:.1f} p95 {w['ttft_p95_ms']:.1f} ms "
        f"(n={w['attempted']}); tpot p50 {w['tpot_p50_ms']:.2f} p95 {w['tpot_p95_ms']:.2f} ms "
        f"(n={w['n_gaps']}); {w['serve_tokens_per_s']:.1f} tokens/s in the window")
    say(f"chunk-gap share {counts['chunk_gap_share_pct']:.1f}% of the window's "
        f"decoded tokens (must stay far from 5%); {counts['chunks']:.0f} chunks; "
        f"state {counts['state_bytes'] / 2**30:.3f} GiB, "
        f"{counts['state_gib_per_step']:.3f} GiB of it moved a decode step, "
        f"{counts['state_resets']:.0f} rows entered as zeros; generator lateness "
        f"{w['lateness']}; queue depth mean {np.mean(qd) if qd else 0:.2f} "
        f"max {max(qd) if qd else 0}; decode steps {len(occ)}; slot occupancy "
        f"{ctx.spans.counters['slot_occupancy']:.3f}; preemptions "
        f"{engine.metrics.summary().get('preemptions')}; compiles inside the "
        f"window: {w['compiled_inside']} (must be 0); peak {w['peak'] / 2**30:.2f} GiB")
    return engine, recs, w


def run(ctx, controls=(), drain=False) -> dict:
    import jax

    cell, say = ctx.cell_file, ctx.say
    engine, recs, w = serve_window(ctx, drain)
    if ctx.trace and cell.get("also_read"):
        serve_long.read_also(ctx, cell["also_read"])
    n_tokens = cell["check"]["min_served_tokens"]
    sample = (first_finished(recs, n_tokens) if drain
              else serve.sample_finished(w["done"], ctx.seed, n_tokens))
    del engine, recs, w["done"]
    gc.collect()
    jax.clear_caches()
    say(f"program freed: {device.bytes_in_use(ctx.devices) / 2**30:.2f} GiB in use")
    t_ref = time.perf_counter()
    limit = cell["check"]["limits"]["served_logit_gap"]
    correct, g = False, None
    if sample:
        g = check_served(ctx, sample, controls)
        correct = g["widest_gap"] <= limit
        say(f"check served_logit_gap: {g['widest_gap']:.6g} (limit {limit:g}) "
            f"{'ok' if correct else 'OVER'}; {g['tokens']} served tokens of "
            f"{len(sample)} requests, the longest of {len(sample[0]['prompt'])}"
            f"+{len(sample[0]['output'])} tokens; the reference's logits "
            f"spread {g['logit_std']:.4g} over the vocabulary")
        for name, gap in g["controls"].items():
            say(f"control[{name}] served_logit_gap {gap:.6g} (limit {limit:g}) "
                f"{'OVER, as it must be' if gap > limit else 'INSIDE THE LIMIT'}")
    else:
        say("check served_logit_gap: no request finished, nothing to compare")
    say(f"reference took {time.perf_counter() - t_ref:.1f} s")
    return {
        "correct": bool(correct and w["failed"] == 0
                        and w["compiled_inside"] == 0),
        "attempted": w["attempted"], "failed": w["failed"],
        "end_to_end": {k: w[k] for k in serve.E2E} | {"setup_s": w["setup_s"]},
        "memory_peak_bytes": w["peak"],
        "check": g,
    }


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description="the controls of a serve_chat cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    from benchmark import run as brun

    out = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        say = lambda m, seed=seed: print(
            f"[control {args.workload} seed={seed}] {m}", flush=True)
        ctx = brun.open_context(args.workload, seed, args.seconds, 0, say)
        r = run(ctx, ctx.cell_file["check"]["controls"])
        limit = ctx.cell_file["check"]["limits"]["served_logit_gap"]
        out[seed] = {"program": r["check"]["widest_gap"], "limit": limit,
                     **r["check"]["controls"]}
    print(json.dumps({"controls": out}))
    return 0 if all(v[c] > v["limit"] for v in out.values()
                    for c in v if c not in ("program", "limit")) else 1


if __name__ == "__main__":
    sys.exit(main())
