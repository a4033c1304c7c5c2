"""A serving cell of short and long prompts in one queue over a model
whose stack holds layers of TWO cache rules (three layers that keep the
last 1,024 keys in a ring, then one that keeps every key, a period) and
whose every feed-forward is 64 gated experts routed 8 a token (Mellum 2).
The loop and the window's numbers are ``serve.py``'s (``offer``,
``reduce_window``, ``build_engine``, ``sample_finished``), the traced run's
extra metrics ``serve_long.py``'s (``read_also``); what differs is the
warm-up, the program's counters, the check's two statistics and the
controls.

Warm-up: every prompt enters through ONE chunk program (the length of the
past it reads is a traced trip count) and every token through ONE decode
program. One prompt of more than two windows with a ragged last chunk,
and decode steps behind it, have run both with every branch a length can
take (a ring that has wrapped, a chunk that keeps the ring's rows beyond
its last token): no program compiles inside a window.

Check: the reference (``refs/mellum.py``) casts ONE LAYER at a time to
float32, masks every query's keys outright and computes every expert for
every token, weighted by a gate that is zero where it was not chosen; the
routing is its own. Two numbers of how far the served tokens' reference
logits lie under the reference's best, each under its own limit:
``served_logit_gap``, ``serve.py``'s (the widest over every served token:
the tail of the few tokens whose 8th and 9th expert bfloat16 swaps), and
the widest of a request's MEANS (what the arithmetic does to every
token).

    python benchmark/runners/serve_code.py --workload <cell> --seeds 1,2 \\
        [--seconds 20]

serves a short window and then reads the controls the cell's file names
(``check.controls``): the reference in float8 put in the program's place,
the reference that forgot the window, that turned the full layers by the
sliding layers' frequencies, that left the gates unnormalised, that did
not norm q and k a head. Each has to read OVER one of the cell's limits.
The benchmark's own runs never do this.
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
from benchmark.runners import (serve, serve_bytes, serve_chat,   # noqa: E402
                               serve_long)

COUNTED = ("chunk_gaps", "gaps", "decode_steps", "kv_live_pages",
           "kv_full_pages", "kv_window_pages", "kv_held_rows", "window_wraps",
           "prefill_chunks")
BY_PROGRAM = ("moe_experts_touched", "moe_assignments", "moe_max_load",
              "moe_layer_calls", "moe_calls")


def warm(engine, vocab, seed) -> int:
    """One prompt of two windows and a ragged chunk, and decode steps
    behind it; returns its length."""
    n = 2 * engine.cfg.gqa.window + 37
    rng = tg.rng_for(seed, 4)
    engine.submit(rng.integers(0, vocab, n).tolist(), max_new_tokens=8,
                  request_id="warm")
    engine.run()
    return n


def schedule(mix, seed, seconds, vocab):
    """The generator's schedule: in the seed's own order, or, where the
    mix deals one (``arrivals.deal``), the byte cell's way: the same
    lengths and gaps in that ONE order for every seed, the seed drawing
    the token ids alone."""
    if mix["arrivals"].get("deal") is None:
        return tg.serve_requests(mix, seed, seconds, vocab)
    return serve_bytes.schedule(mix, seed, seconds, vocab)


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
    E, rows = engine.cfg.moe_num_experts, max(d["kv_held_rows"], 1)
    calls = max(d["moe_layer_calls.decode"], 1)
    steps = max(d["moe_calls.decode"], 1)
    mean_load = d["moe_assignments.decode"] / (calls * E)
    return {"chunk_gap_share_pct": (100.0 * d["chunk_gaps"] / d["gaps"]
                                    if d["gaps"] else 0.0),
            "chunks": float(d["prefill_chunks"]),
            # pages a live slot HELD, rule by rule, over the decode rows
            "kv_pages_per_slot": ((d["kv_full_pages"] + d["kv_window_pages"])
                                  / rows),
            "kv_full_pages_per_slot": d["kv_full_pages"] / rows,
            "kv_window_pages_per_slot": d["kv_window_pages"] / rows,
            "window_wraps": float(d["window_wraps"]),
            # of a layer's experts, those a decode step touched
            "experts_touched_pct": (100.0 * d["moe_experts_touched.decode"]
                                    / (calls * E)),
            "experts_touched_pct_chunk": (
                100.0 * d["moe_experts_touched.chunk"]
                / (max(d["moe_layer_calls.chunk"], 1) * E)),
            # the largest expert's assignments of a step over the mean's
            "expert_load_max_over_mean": (
                d["moe_max_load.decode"] / steps / mean_load
                if mean_load else 0.0),
            "live_slots_per_step": d["kv_held_rows"] / max(d["decode_steps"], 1)}


def check_served(ctx, sample, controls=()) -> dict:
    """Reference gaps of the sampled requests and, for each of
    ``controls``, of the tokens the control puts first."""
    import jax.numpy as jnp

    cfg, ref = ctx.config, ctx.adapter.reference
    params = rinit.init_tree(ctx.seed, ref.leaf_specs(cfg),
                             jnp.dtype(ctx.cell_file["weights_dtype"]))
    make = lambda c: (ref.make(cfg, Numerics("fp8")) if c == "fp8"
                      else ref.make(cfg, control=c))
    return ref.served_gaps(ref.Forward(ref.make(cfg)), params, sample,
                           {c: ref.Forward(make(c)) for c in controls},
                           ctx.cell_file["check"].get("control_max_tokens"))


def serve_window(ctx, drain=False):
    """Build, warm and serve the cell's window; returns the engine, the
    per-request records and the window's numbers."""
    from deeperspeed_tpu.serving.kv_cache import pool_bytes

    cfg, mix, say = ctx.config, ctx.traffic, ctx.say
    requests = schedule(mix, ctx.seed, ctx.seconds, cfg["vocab_size"])
    engine = serve.build_engine(ctx)
    say(f"weights made and the engine built "
        f"{time.perf_counter() - ctx.t_start:.1f} s after the chip was claimed")
    n_warm = warm(engine, cfg["vocab_size"], ctx.seed)
    lowered = device.LoweringCounter.get()
    compiles = lowered.count
    n_occ = len(engine.metrics.occupancy)
    pools = pool_bytes(engine.kv)
    deal = mix["arrivals"].get("deal")
    say(f"warmed the chunk program and the decode step with one prompt of "
        f"{n_warm} tokens; {len(requests)} requests of "
        f"{min(len(r['prompt']) for r in requests)}-"
        f"{max(len(r['prompt']) for r in requests)} tokens offered over "
        f"{ctx.seconds:g} s"
        + (f" in the order of deal {deal}" if deal is not None else "")
        + f"; weights {cfg.get('weights')}; a slot's table "
        f"{engine.scfg.table_widths} entries; pools {engine.scfg.pool_blocks} "
        f"pages = {' + '.join(f'{b / 2**30:.3f}' for b in pools)} GiB; in use "
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
    longest = max(len(r["prompt"]) + r["max_new_tokens"] for r in requests)
    say(f"window: {w['attempted']} requests, {w['failed']} failed, "
        f"{w['cut_by_close']} still decoding when the run stopped at {took:.2f} s; "
        f"ttft mean {w['ttft_mean_ms']:.1f} p50 {w['ttft_p50_ms']:.1f} p95 {w['ttft_p95_ms']:.1f} ms "
        f"(n={w['attempted']}); tpot p50 {w['tpot_p50_ms']:.2f} p95 {w['tpot_p95_ms']:.2f} ms "
        f"(n={w['n_gaps']}); {w['serve_tokens_per_s']:.1f} tokens/s in the window")
    say(f"chunk-gap share {counts['chunk_gap_share_pct']:.1f}% of the window's "
        f"decoded tokens (must stay far from 5%); {counts['chunks']:.0f} chunks; "
        f"a live slot held {counts['kv_full_pages_per_slot']:.1f} pages of "
        f"every key (2 layers deep) and {counts['kv_window_pages_per_slot']:.1f} "
        f"of its ring (6 deep); every layer keeping every key would be up to "
        f"{-(-longest // engine.scfg.block_size)} pages 8 deep; "
        f"{counts['window_wraps']:.0f} rings started over; a decode step ran "
        f"{counts['live_slots_per_step']:.1f} live slots and touched "
        f"{counts['experts_touched_pct']:.1f}% of a layer's experts (a chunk "
        f"{counts['experts_touched_pct_chunk']:.1f}%), the largest expert "
        f"{counts['expert_load_max_over_mean']:.2f} x the mean load; generator "
        f"lateness {w['lateness']}; queue depth mean "
        f"{np.mean(qd) if qd else 0:.2f} max {max(qd) if qd else 0}; decode "
        f"steps {len(occ)}; slot occupancy "
        f"{ctx.spans.counters['slot_occupancy']:.3f}; preemptions "
        f"{engine.metrics.summary().get('preemptions')}; compiles inside the "
        f"window: {w['compiled_inside']} (must be 0); peak {w['peak'] / 2**30:.2f} GiB")
    return engine, recs, w


def run(ctx, controls=(), drain=False, short_only=False) -> dict:
    """``drain`` (the tests' toy window): serve every request to its end
    and check the schedule's first ones, whatever the machine's load.
    ``short_only`` (the controls' own runs): check, in place of the
    sample, up to four finished requests the controls are read on
    (``check.control_max_tokens``), the sound program's readings on them
    too."""
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
        g = check_served(ctx, sample, controls)
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

    ap = argparse.ArgumentParser(description="the controls of a serve_code cell")
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
