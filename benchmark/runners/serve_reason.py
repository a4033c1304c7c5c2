"""A serving cell of long answers over a stack that mixes ONE layer of
pages (gated grouped-query attention, no rotary) with THREE layers that
keep a float32 state row and three convolution tails a slot (Kimi Delta
Attention), every feed-forward routed over 320 experts of which this chip
holds 40 beside a shared one (Solar Open 2, one chip's share of an 8-chip
layer). The loop and the window's numbers are ``serve.py``'s (``offer``,
``reduce_window``, ``build_engine``, ``sample_finished``), the schedule,
the check's two statistics and its controls ``serve_code.py``'s
(``schedule``, ``check_served``), the traced run's extra metrics
``serve_long.py``'s (``read_also``); what differs is the warm-up and the
program's counters (state bytes a step, the assignments that left for
experts held elsewhere, the share of the HELD experts a step touched).

Warm-up: every prompt enters through ONE chunk program (the length of the
past it reads is a traced trip count) and every token through ONE decode
program. One prompt of two chunks and a ragged third, and decode steps
behind it, have run both: no program compiles inside a window.

    python benchmark/runners/serve_reason.py --workload <cell> --seeds 1,2 \\
        [--seconds 20]

serves a short window and then reads the controls the cell's file names
(``check.controls``), each of which has to read OVER one of the cell's
limits. The benchmark's own runs never do this.
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
           "kv_full_pages", "kv_held_rows", "prefill_chunks",
           "kda_chunks_kernel", "state_bytes_moved")
BY_PROGRAM = ("moe_experts_touched", "moe_assignments", "moe_max_load",
              "moe_layer_calls", "moe_calls", "moe_assignments_away")


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
    for k in BY_PROGRAM:
        for prog, v in getattr(m, k, {}).items():
            out[f"{k}.{prog}"] = v
    return out


def program_counts(engine, before: dict) -> dict:
    """What the program counted of itself over the window, for the
    per-layer metrics: ``before`` is ``snapshot`` at the window's start."""
    now = snapshot(engine)
    d = {k: now[k] - v for k, v in before.items()}
    held = engine.cfg.moe_held[1]
    rows, steps = max(d["kv_held_rows"], 1), max(d["decode_steps"], 1)
    calls = max(d["moe_layer_calls.decode"], 1)
    routed = d["moe_assignments.decode"] + d["moe_assignments_away.decode"]
    mean_load = d["moe_assignments.decode"] / (calls * held)
    chunk_routed = (d["moe_assignments.chunk"]
                    + d["moe_assignments_away.chunk"])
    return {"chunk_gap_share_pct": (100.0 * d["chunk_gaps"] / d["gaps"]
                                    if d["gaps"] else 0.0),
            "chunks": float(d["prefill_chunks"]),
            "chunks_kernel_scan": float(d["kda_chunks_kernel"]),
            # pages a live slot held (the full_attn layers' alone)
            "kv_pages_per_slot": d["kv_full_pages"] / rows,
            # state rows and tails a decode step read and wrote
            "state_gib_per_step": d["state_bytes_moved"] / steps / 2**30,
            # of the experts HELD HERE, those a decode step touched
            "experts_touched_pct": (100.0 * d["moe_experts_touched.decode"]
                                    / (calls * held)),
            "experts_touched_pct_chunk": (
                100.0 * d["moe_experts_touched.chunk"]
                / (max(d["moe_layer_calls.chunk"], 1) * held)),
            # of a step's assignments, those that left for absent experts
            "experts_away_pct": (100.0 * d["moe_assignments_away.decode"]
                                 / routed if routed else 0.0),
            "experts_away_pct_chunk": (
                100.0 * d["moe_assignments_away.chunk"] / chunk_routed
                if chunk_routed else 0.0),
            "expert_load_max_over_mean": (
                d["moe_max_load.decode"] / max(d["moe_calls.decode"], 1)
                / mean_load if mean_load else 0.0),
            "live_slots_per_step": d["kv_held_rows"] / steps}


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
    state = engine.metrics.state_bytes
    deal = mix["arrivals"].get("deal")
    say(f"warmed the chunk program and the decode step with one prompt of "
        f"{n_warm} tokens; {len(requests)} requests of "
        f"{min(len(r['prompt']) for r in requests)}-"
        f"{max(len(r['prompt']) for r in requests)} tokens offered over "
        f"{ctx.seconds:g} s"
        + (f" in the order of deal {deal}" if deal is not None else "")
        + f"; a slot's table {engine.scfg.table_widths} entries; pool "
        f"{engine.scfg.pool_blocks} pages = {sum(pools) / 2**30:.3f} GiB, "
        f"state rows and tails {state / 2**30:.3f} GiB; the chunks' delta "
        f"rule runs as {engine._kda_scan}; in use "
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
    ctx.spans.counters["kv_pool_bytes"] = float(sum(pools))
    qd = [q for _, q in queue_depth]
    say(f"window: {w['attempted']} requests, {w['failed']} failed, "
        f"{w['cut_by_close']} still decoding when the run stopped at {took:.2f} s; "
        f"ttft mean {w['ttft_mean_ms']:.1f} p50 {w['ttft_p50_ms']:.1f} p95 {w['ttft_p95_ms']:.1f} ms "
        f"(n={w['attempted']}); tpot p50 {w['tpot_p50_ms']:.2f} p95 {w['tpot_p95_ms']:.2f} ms "
        f"(n={w['n_gaps']}); {w['serve_tokens_per_s']:.1f} tokens/s in the window")
    say(f"chunk-gap share {counts['chunk_gap_share_pct']:.1f}% of the window's "
        f"decoded tokens (must stay far from 5%); {counts['chunks']:.0f} chunks "
        f"({counts['chunks_kernel_scan']:.0f} through the delta-rule kernel); "
        f"a decode step ran {counts['live_slots_per_step']:.1f} live slots, "
        f"each holding {counts['kv_pages_per_slot']:.1f} pages (1 layer "
        f"deep), moved {counts['state_gib_per_step']:.3f} GiB of state rows "
        f"and tails, touched {counts['experts_touched_pct']:.1f}% of a "
        f"layer's {engine.cfg.moe_held[1]} held experts (a chunk "
        f"{counts['experts_touched_pct_chunk']:.1f}%), "
        f"{counts['experts_away_pct']:.1f}% of its assignments left for "
        f"absent experts (a chunk's {counts['experts_away_pct_chunk']:.1f}%), "
        f"the largest held expert {counts['expert_load_max_over_mean']:.2f} x "
        f"the mean load; generator lateness {w['lateness']}; queue depth mean "
        f"{np.mean(qd) if qd else 0:.2f} max {max(qd) if qd else 0}; decode "
        f"steps {len(occ)}; slot occupancy "
        f"{ctx.spans.counters['slot_occupancy']:.3f}; preemptions "
        f"{engine.metrics.summary().get('preemptions')}; compiles inside the "
        f"window: {w['compiled_inside']} (must be 0); peak {w['peak'] / 2**30:.2f} GiB")
    return engine, recs, w


def run(ctx, controls=(), drain=False, short_only=False) -> dict:
    """``serve_code.run`` over this module's window: ``drain`` (the
    tests' toy window) serves every request to its end and checks the
    schedule's first ones; ``short_only`` (the controls' own runs) checks
    up to four finished requests of at most ``check.control_max_tokens``
    tokens, the sound program's readings on them too."""
    import jax

    cell, say = ctx.cell_file, ctx.say
    engine, recs, w = serve_window(ctx, drain)
    if ctx.trace and cell.get("also_read"):
        serve_long.read_also(ctx, cell["also_read"])
    n_tokens = cell["check"]["min_served_tokens"]
    sample = (serve_chat.first_finished(recs, n_tokens) if drain
              else serve.sample_finished(w["done"], ctx.seed, n_tokens))
    most = cell["check"].get("control_max_tokens")
    if short_only and most:
        short = [{"prompt": list(r["req"].prompt),
                  "output": list(r["req"].generated)} for r in w["done"]
                 if len(r["req"].prompt) + len(r["req"].generated) <= most]
        sample = short[:4] or sample[-1:]
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

    ap = argparse.ArgumentParser(description="the controls of a serve_reason cell")
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
        r = run(ctx, ctx.cell_file["check"]["controls"], short_only=True)
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
