"""A serving cell of short problems and worked answers over a LOOPED stack:
48 layers run four times a token with one set of weights, every (pass,
layer) pair with a cache layer of its own (Ouro). The loop and the
window's numbers are ``serve.py``'s (``offer``, ``reduce_window``,
``build_engine``, ``sample_finished``), the schedule and the check's two
statistics ``serve_code.py``'s (``schedule``, ``check_served``: the
reference scores the program's own context at every served step of a
sample of finished requests), the traced run's extra metrics
``serve_long.py``'s (``read_also``); what differs is the warm-up, the
program's counters (the pages a slot holds through 192 cache layers, the
exit gate's read-out) and the gaps' histogram by what each held, which
says where the 95th rank sits.

Warm-up: every prompt enters through ONE chunk program (the length of the
past it reads is a traced trip count) and every token through ONE decode
program. One prompt of two chunks and a ragged third, and decode steps
behind it, have run both: no program compiles inside a window.

    python benchmark/runners/serve_solve.py --workload <cell> --seeds 1,2 \\
        [--seconds 20]

serves a short window and then reads the controls the cell's file names
(``check.controls``): the reference in float8, with a pass fewer, with one
cache for all passes, without the norms on the sublayers' outputs, with
the final norm after the last pass alone. Each has to read OVER one of the
cell's limits. The benchmark's own runs never do this.
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
from benchmark.runners import (serve, serve_chat, serve_code,    # noqa: E402
                               serve_long)

COUNTED = ("chunk_gaps", "gaps", "decode_steps", "kv_live_pages",
           "kv_full_pages", "kv_held_rows", "prefill_chunks", "exit_tokens")


def warm(engine, vocab, seed) -> int:
    """One prompt of two chunks and a ragged third, and decode steps
    behind it; returns its length."""
    from deeperspeed_tpu.serving.engine import prefill_chunk_for

    n = 2 * prefill_chunk_for(engine.cfg, engine.scfg) + 37
    rng = tg.rng_for(seed, 4)
    engine.submit(rng.integers(0, vocab, n).tolist(), max_new_tokens=8,
                  request_id="warm")
    engine.run()
    return n


def snapshot(engine) -> dict:
    m = engine.metrics
    out = {k: getattr(m, k, 0) for k in COUNTED}
    out["exit_mass"] = np.array(getattr(m, "exit_mass", ()), np.float64)
    out["itl"] = {held: list(h.counts) for held, h in m.token_gaps.items()}
    return out


def program_counts(engine, before: dict) -> dict:
    """What the program counted of itself over the window, for the
    per-layer metrics: ``before`` is ``snapshot`` at the window's start."""
    now = snapshot(engine)
    d = {k: now[k] - before[k] for k in COUNTED}
    rows, steps = max(d["kv_held_rows"], 1), max(d["decode_steps"], 1)
    mass = now["exit_mass"] - (before["exit_mass"]
                               if before["exit_mass"].size else 0.0)
    p = mass / max(d["exit_tokens"], 1)
    return {"chunk_gap_share_pct": (100.0 * d["chunk_gaps"] / d["gaps"]
                                    if d["gaps"] else 0.0),
            "chunks": float(d["prefill_chunks"]),
            # pages a live slot held, each as deep as the cache's layers
            "kv_pages_per_slot": d["kv_full_pages"] / rows,
            "live_slots_per_step": d["kv_held_rows"] / steps,
            "pages_per_step": d["kv_full_pages"] / steps,
            "exit_p": [float(x) for x in p],
            "exit_step_expected": float(1.0 + np.dot(np.arange(p.size), p))}


def gaps_by_held(engine, before: dict) -> dict:
    """The window's gaps between two tokens by what each held
    (``ServingMetrics.token_gaps``): {held: (count, p50 ms, p95 ms)}."""
    from deeperspeed_tpu.serving.metrics import GapHistogram

    out = {}
    for held, h in engine.metrics.token_gaps.items():
        counts = [a - b for a, b in zip(h.counts, before["itl"][held])]
        if sum(counts):
            q = GapHistogram.percentiles(counts, (50, 95))
            out[held] = (q["n"], 1e3 * q["p50"], 1e3 * q["p95"])
    return out


def serve_window(ctx, drain=False):
    """Build, warm and serve the cell's window; returns the engine, the
    per-request records and the window's numbers."""
    from deeperspeed_tpu.serving.kv_cache import pool_bytes

    cfg, mix, say = ctx.config, ctx.traffic, ctx.say
    requests = serve_code.schedule(mix, ctx.seed, ctx.seconds,
                                   cfg["vocab_size"])
    engine = serve.build_engine(ctx)
    say(f"weights made and the engine built "
        f"{time.perf_counter() - ctx.t_start:.1f} s after the chip was claimed")
    n_warm = warm(engine, cfg["vocab_size"], ctx.seed)
    lowered = device.LoweringCounter.get()
    compiles = lowered.count
    n_occ = len(engine.metrics.occupancy)
    pools = pool_bytes(engine.kv)
    s = engine.metrics.summary()
    deal = mix["arrivals"].get("deal")
    say(f"warmed the chunk program and the decode step with one prompt of "
        f"{n_warm} tokens; {len(requests)} requests of "
        f"{min(len(r['prompt']) for r in requests)}-"
        f"{max(len(r['prompt']) for r in requests)} tokens offered over "
        f"{ctx.seconds:g} s"
        + (f" in the order of deal {deal}" if deal is not None else "")
        + f"; {s['loop_steps']} passes over {engine.cfg.n_layer} layers, a "
        f"pool {engine.kv.k.shape[0]} cache layers deep, "
        f"{s['kv_bytes_per_position']} B a position; pool "
        f"{engine.scfg.pool_blocks} pages = {sum(pools) / 2**30:.3f} GiB; a "
        f"prompt chunk attends as {engine._chunk_attn}; in use "
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
    ctx.spans.counters.update(
        {k: v for k, v in counts.items() if k != "exit_p"})
    ctx.spans.counters["slot_occupancy"] = float(np.mean(occ)) if occ else 0.0
    ctx.spans.counters["hbm_peak_bytes"] = w["peak"]
    ctx.spans.counters["kv_pool_bytes"] = float(sum(pools))
    qd = [q for _, q in queue_depth]
    say(f"window: {w['attempted']} requests, {w['failed']} failed, "
        f"{w['cut_by_close']} still decoding when the run stopped at {took:.2f} s; "
        f"ttft mean {w['ttft_mean_ms']:.1f} p50 {w['ttft_p50_ms']:.1f} p95 {w['ttft_p95_ms']:.1f} ms "
        f"(n={w['attempted']}); tpot p50 {w['tpot_p50_ms']:.2f} p95 {w['tpot_p95_ms']:.2f} ms "
        f"(n={w['n_gaps']}); {w['serve_tokens_per_s']:.1f} tokens/s in the window")
    say(f"chunk-gap share {counts['chunk_gap_share_pct']:.2f}% of the window's "
        f"decoded tokens (must stay a point or more under 5%); gaps by what "
        f"they held (n, p50 ms, p95 ms): "
        + "; ".join(f"{held} {n}, {p50:.2f}, {p95:.2f}" for held, (n, p50, p95)
                    in gaps_by_held(engine, before).items())
        + f"; {counts['chunks']:.0f} chunks; a decode step ran "
        f"{counts['live_slots_per_step']:.2f} live slots listing "
        f"{counts['pages_per_step']:.1f} pages, each slot holding "
        f"{counts['kv_pages_per_slot']:.2f} pages ({engine.kv.k.shape[0]} "
        f"cache layers deep); the exit gate's distribution over the passes "
        f"{[round(x, 4) for x in counts['exit_p']]}, expected exit "
        f"{counts['exit_step_expected']:.3f} (read out, not acted on); "
        f"generator lateness {w['lateness']}; queue depth mean "
        f"{np.mean(qd) if qd else 0:.2f} max {max(qd) if qd else 0}; decode "
        f"steps {len(occ)}; slot occupancy "
        f"{ctx.spans.counters['slot_occupancy']:.3f}; preemptions "
        f"{engine.metrics.summary().get('preemptions')} (must be 0); compiles "
        f"inside the window: {w['compiled_inside']} (must be 0); peak "
        f"{w['peak'] / 2**30:.2f} GiB")
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
    limits = cell["check"]["limits"]
    limit, limit_mean = (limits["served_logit_gap"],
                         limits["served_logit_gap_request_mean"])
    correct, g = False, None
    if sample:
        g = serve_code.check_served(ctx, sample, controls)
        correct = (g["widest_gap"] <= limit
                   and g["request_mean_gap"] <= limit_mean)
        say(f"check served_logit_gap: {g['widest_gap']:.6g} (limit {limit:g}), "
            f"the widest of a request's means {g['request_mean_gap']:.6g} "
            f"(limit {limit_mean:g}) {'ok' if correct else 'OVER'}; "
            f"{g['tokens']} served tokens of {len(sample)} requests, the "
            f"longest of {len(sample[0]['prompt'])}+{len(sample[0]['output'])} "
            f"tokens, their mean gap {g['mean_gap']:.4g}; the reference's "
            f"logits spread {g['logit_std']:.4g} over the vocabulary")
        for name, gap in g["controls"].items():
            mean = g["controls_request_mean"][name]
            over = gap > limit or mean > limit_mean
            say(f"control[{name}] over {g['control_tokens']} served tokens: "
                f"served_logit_gap {gap:.6g} (limit {limit:g}), the widest of "
                f"a request's means {mean:.6g} (limit {limit_mean:g}) "
                f"{'OVER, as it must be' if over else 'INSIDE BOTH LIMITS'}")
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

    ap = argparse.ArgumentParser(description="the controls of a serve_solve cell")
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
        limits, c = ctx.cell_file["check"]["limits"], r["check"]
        out[seed] = {
            "program": [c["widest_gap"], c["request_mean_gap"]],
            "limits": [limits["served_logit_gap"],
                       limits["served_logit_gap_request_mean"]],
            "e2e": r["end_to_end"], "tokens": c["tokens"],
            "controls": {name: [c["controls"][name],
                                c["controls_request_mean"][name]]
                         for name in c["controls"]}}
        print(json.dumps({"controls": {seed: out[seed]}}), flush=True)
    print(json.dumps({"controls": out}))
    # a control is told from the sound program by ONE of the cell's limits
    return 0 if all(any(x > l for x, l in zip(pair, v["limits"]))
                    for v in out.values()
                    for pair in v["controls"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
