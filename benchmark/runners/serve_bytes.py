"""A serving cell of long byte prompts over a model whose every layer
keeps the exact keys of the current window alone, in pages a slot reuses
window after window, and a page of pooled summaries for every 1,024 bytes
behind them (EvaByte's ``eva``): every prompt reaches beyond two windows,
so the summaries are always attended to. The loop and the window's numbers
are ``serve.py``'s (``offer``, ``reduce_window``, ``build_engine``,
``sample_finished``), the traced run's extra metrics ``serve_long.py``'s
(``read_also``); what differs is the schedule's order, the warm-up, the
program's counters and the check.

Schedule: the generator's, dealt in ONE order for every seed (the mix's
own ``arrivals.deal``); the run's seed picks every byte and the weights.
Every other serving cell's decode step is the weights' read and costs the
same whoever is live. Here a step reads every live slot's pages (0.76 ms a
slot), so which answers overlap, which a seed's order decides, is part of
the WORK of a run: with a seed's own order ``tpot_p95_ms`` moved 45.7 to
50.2 ms over 18 seeds, two runs of one seed alike (PERF.md section 6).

Warm-up: every prompt enters through ONE chunk program and every token
through ONE decode program, whatever the length. One prompt of more than
two windows with a ragged last chunk, and decode steps that complete a
chunk of 16 and write its summary, have run both, with every branch a
length can take: no program compiles inside a window.

Check: the reference (``refs/evabyte.py``) casts ONE LAYER at a time to
float32 and builds every query's exact keys and summaries outright from
the whole sequence. ``served_logit_gap`` is ``serve.py``'s number over the
head's first block (the served byte's).

    python benchmark/runners/serve_bytes.py --workload <cell> --seeds 1,2 \\
        [--seconds 20]

serves a short window and then reads the controls the cell's file names
(``check.controls``): the reference in float8 put in the program's place,
the reference that forgot its summaries, the reference that pools by plain
means. Each has to read OVER the cell's limit. The benchmark's own runs
never do this.
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
from benchmark.runners import serve, serve_chat, serve_long   # noqa: E402

COUNTED = ("chunk_gaps", "gaps", "decode_steps", "kv_live_pages",
           "kv_window_pages", "kv_summary_pages", "kv_held_rows",
           "summary_rows_decode", "summary_rows_chunk", "window_wraps",
           "prefill_chunks")


def warm(engine, vocab, seed) -> int:
    """One prompt of two windows and a ragged chunk, and decode steps
    across a chunk of 16; returns its length."""
    ev = engine.cfg.eva
    n = 2 * ev.window + 37
    rng = tg.rng_for(seed, 4)
    engine.submit(rng.integers(0, vocab, n).tolist(),
                  max_new_tokens=ev.chunk + 4, request_id="warm")
    engine.run()
    return n


def schedule(mix, seed, seconds, vocab):
    """The generator's schedule as ``arrivals.deal`` orders it, whatever
    the seed: the same lengths and gaps in the same order, so every seed
    is the same work; ``seed`` draws the bytes, as the generator would."""
    requests = tg.serve_requests(mix, mix["arrivals"]["deal"], seconds, vocab)
    tok = tg.rng_for(seed, 2)
    for r in requests:
        r["prompt"] = tok.integers(0, vocab, len(r["prompt"])).tolist()
    return requests


def snapshot(engine) -> dict:
    return {k: getattr(engine.metrics, k, 0) for k in COUNTED}


def program_counts(engine, before: dict) -> dict:
    """What the program counted of itself over the window, for the
    per-layer metrics: ``before`` is ``snapshot`` at the window's start."""
    d = {k: getattr(engine.metrics, k, 0) - v for k, v in before.items()}
    steps, rows = max(d["decode_steps"], 1), max(d["kv_held_rows"], 1)
    return {"chunk_gap_share_pct": (100.0 * d["chunk_gaps"] / d["gaps"]
                                    if d["gaps"] else 0.0),
            "chunks": float(d["prefill_chunks"]),
            # pages a live slot HELD, over the window's decode rows
            "kv_pages_per_slot": ((d["kv_window_pages"]
                                   + d["kv_summary_pages"]) / rows),
            "kv_window_pages_per_slot": d["kv_window_pages"] / rows,
            "kv_summary_pages_per_slot": d["kv_summary_pages"] / rows,
            "summary_rows_decode": float(d["summary_rows_decode"]),
            "summary_rows_chunk": float(d["summary_rows_chunk"]),
            "window_wraps": float(d["window_wraps"]),
            # the page-list kernel, one call a layer and decode step: every
            # key head of a live slot reads the pages its list counts
            "paged_pages_per_decode_call": (engine.cfg.kv_heads
                                            * d["kv_live_pages"] / steps)}


def check_served(ctx, sample, controls=()) -> dict:
    """Reference gaps of the sampled requests and, for each of
    ``controls``, of the bytes the control puts first."""
    import jax.numpy as jnp

    cfg, ref = ctx.config, ctx.adapter.reference
    params = rinit.init_tree(ctx.seed, ref.leaf_specs(cfg),
                             jnp.dtype(ctx.cell_file["weights_dtype"]))
    make = {"fp8": lambda: ref.make(cfg, Numerics("fp8")),
            "nosum": lambda: ref.make(cfg, control="nosum"),
            "flatpool": lambda: ref.make(cfg, control="flatpool")}
    return ref.served_gaps(ref.Forward(ref.make(cfg)), params, sample,
                           {c: ref.Forward(make[c]()) for c in controls})


def serve_window(ctx, drain=False):
    """Build, warm and serve the cell's window; returns the engine, the
    per-request records and the window's numbers."""
    cfg, mix, say = ctx.config, ctx.traffic, ctx.say
    requests = schedule(mix, ctx.seed, ctx.seconds, cfg["vocab_size"])
    engine = serve.build_engine(ctx)
    n_warm = warm(engine, cfg["vocab_size"], ctx.seed)
    lowered = device.LoweringCounter.get()
    compiles = lowered.count
    n_occ = len(engine.metrics.occupancy)
    say(f"warmed the chunk program and the decode step with one prompt of "
        f"{n_warm} bytes; {len(requests)} requests of "
        f"{min(len(r['prompt']) for r in requests)}-"
        f"{max(len(r['prompt']) for r in requests)} bytes offered over "
        f"{ctx.seconds:g} s in the order of deal {mix['arrivals']['deal']}; "
        f"weights {cfg.get('weights')}; a slot's table "
        f"{engine.scfg.table_widths} entries; in use "
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
    longest = max(len(r["prompt"]) + r["max_new_tokens"] for r in requests)
    say(f"window: {w['attempted']} requests, {w['failed']} failed, "
        f"{w['cut_by_close']} still decoding when the run stopped at {took:.2f} s; "
        f"ttft mean {w['ttft_mean_ms']:.1f} p50 {w['ttft_p50_ms']:.1f} p95 {w['ttft_p95_ms']:.1f} ms "
        f"(n={w['attempted']}); tpot p50 {w['tpot_p50_ms']:.2f} p95 {w['tpot_p95_ms']:.2f} ms "
        f"(n={w['n_gaps']}); {w['serve_tokens_per_s']:.1f} tokens/s in the window")
    say(f"chunk-gap share {counts['chunk_gap_share_pct']:.1f}% of the window's "
        f"decoded bytes (must stay far from 5%); {counts['chunks']:.0f} chunks; "
        f"a live slot held {counts['kv_pages_per_slot']:.1f} pages "
        f"({counts['kv_window_pages_per_slot']:.1f} of the window, "
        f"{counts['kv_summary_pages_per_slot']:.1f} of summaries; a page for "
        f"every {engine.scfg.block_size} positions would be up to "
        f"{-(-longest // engine.scfg.block_size)}); summary "
        f"rows written: {counts['summary_rows_chunk']:.0f} by chunks, "
        f"{counts['summary_rows_decode']:.0f} by decode steps; "
        f"{counts['window_wraps']:.0f} windows started over; generator lateness "
        f"{w['lateness']}; queue depth mean {np.mean(qd) if qd else 0:.2f} "
        f"max {max(qd) if qd else 0}; decode steps {len(occ)}; slot occupancy "
        f"{ctx.spans.counters['slot_occupancy']:.3f}; preemptions "
        f"{engine.metrics.summary().get('preemptions')}; compiles inside the "
        f"window: {w['compiled_inside']} (must be 0); peak {w['peak'] / 2**30:.2f} GiB")
    return engine, recs, w


def run(ctx, controls=(), drain=False) -> dict:
    """``drain`` (the tests' toy window): serve every request to its end
    and check the schedule's first ones, whatever the machine's load."""
    import jax

    cell, say = ctx.cell_file, ctx.say
    engine, recs, w = serve_window(ctx, drain)
    if ctx.trace and cell.get("also_read"):
        serve_long.read_also(ctx, cell["also_read"])
    n_tokens = cell["check"]["min_served_tokens"]
    sample = (serve_chat.first_finished(recs, n_tokens) if drain
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
            f"{'ok' if correct else 'OVER'}; {g['tokens']} served bytes of "
            f"{len(sample)} requests, the longest of {len(sample[0]['prompt'])}"
            f"+{len(sample[0]['output'])} bytes; the reference's logits "
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

    ap = argparse.ArgumentParser(description="the controls of a serve_bytes cell")
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
                     "e2e": r["end_to_end"], **r["check"]["controls"]}
        print(json.dumps({"controls": {seed: out[seed]}}), flush=True)
    print(json.dumps({"controls": out}))
    return 0 if all(v[c] > v["limit"] for v in out.values()
                    for c in v if c not in ("program", "limit", "e2e")) else 1


if __name__ == "__main__":
    sys.exit(main())
