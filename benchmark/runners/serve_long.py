"""A serving cell of long prompts over a model of mixed layers: the loop
and the window's numbers are ``serve.py``'s (``offer``, ``reduce_window``);
what differs is the warm-up and the check.

Warm-up: such a model's prompts all enter through ONE chunk program with
one page scatter inside it, whatever their length, so one prompt warms
every prefill shape (``serve.warm`` would send a long prompt for every
page count and put half a minute of prefill into ``setup_s``). It is long
enough to pass ``dense_len``, so both branches of the chunk program and of
the decode step have run before the window opens.

Check: the reference casts ONE LAYER at a time to float32 (the whole tree
would be 20 GB) and follows each sampled request as the plain forward of
``refs/<family>.py``. ``served_logit_gap`` is ``serve.py``'s number. The
run also prints how many (position, key head) selections the program's
selector makes differently from the reference's on the reference's own
queries and pooled keys.

    python benchmark/runners/serve_long.py --workload <cell> --seeds 1,2 \\
        [--seconds 20]

serves a short window and then reads the controls the cell's file names
(``check.controls``): the reference in float8 put in the program's place,
and the reference that skips the selection. Each has to read OVER the
cell's limit. The benchmark's own runs never do this.
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
from benchmark.runners import serve                     # noqa: E402


def warm(engine, config, vocab, seed) -> int:
    """One prompt a chunk beyond ``dense_len`` and a few decode steps;
    returns its length."""
    n = config["sparse_config"]["dense_len"] + engine.scfg.prefill_chunk + 5
    rng = tg.rng_for(seed, 4)
    engine.submit(rng.integers(0, vocab, n).tolist(), max_new_tokens=4,
                  request_id="warm")
    engine.run()
    return n


def program_counts(engine, before: dict) -> dict:
    """What the program counted of itself over the window, for the
    per-layer metrics: ``before`` is ``snapshot`` at the window's start."""
    m = engine.metrics
    d = {k: getattr(m, k) - v for k, v in before.items()}
    out = {"kv_selected_page_frac": (d["kv_selected_pages"] / d["kv_live_pages"]
                                     if d["kv_live_pages"] else 0.0),
           "chunk_gap_share_pct": (100.0 * d["chunk_gaps"] / d["gaps"]
                                   if d["gaps"] else 0.0),
           "state_bytes": float(m.state_bytes)}
    cfg, sp = engine.cfg, engine.cfg.sparse
    if sp is not None and d["decode_steps"]:
        from benchmark import peaks_sala
        from deeperspeed_tpu.ops.pallas import paged_sparse_attn as kernel

        C = engine.scfg.prefill_chunk
        calls = (C * cfg.kv_heads) // kernel.rows_per_call(
            C * cfg.kv_heads, sp.topk)
        out["sparse_pages_per_chunk_call"] = peaks_sala.chunk_pages_read(
            C, sp.block_size, sp.topk, cfg.kv_heads) / calls
        out["sparse_pages_per_decode_call"] = (
            cfg.kv_heads * d["kv_selected_pages"] / d["decode_steps"])
    return out


COUNTED = ("kv_selected_pages", "kv_live_pages", "chunk_gaps", "gaps",
           "decode_steps")


def snapshot(engine) -> dict:
    return {k: getattr(engine.metrics, k, 0) for k in COUNTED}


def check_served(ctx, sample, controls=()) -> dict:
    """Reference gaps of the sampled requests and, for each of
    ``controls``, of the tokens the control puts first."""
    import jax.numpy as jnp

    cfg, ref = ctx.config, ctx.adapter.reference
    params = rinit.init_tree(ctx.seed, ref.leaf_specs(cfg),
                             jnp.dtype(ctx.cell_file["weights_dtype"]))
    make = {"fp8": lambda: ref.make(cfg, Numerics("fp8")),
            "noselect": lambda: ref.make(cfg, selection="first")}
    forward = ref.Forward(ref.make(cfg, probe=ctx.adapter.selector_probe(cfg)))
    return ref.served_gaps(forward, params, sample,
                           {c: ref.Forward(make[c]()) for c in controls})


def serve_window(ctx):
    """Build, warm and serve the cell's window; returns the engine, the
    window's numbers and what is needed to report them."""
    cfg, mix, say = ctx.config, ctx.traffic, ctx.say
    requests = tg.serve_requests(mix, ctx.seed, ctx.seconds, cfg["vocab_size"])
    engine = serve.build_engine(ctx)
    n_warm = warm(engine, cfg, cfg["vocab_size"], ctx.seed)
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
    recs, queue_depth, took = serve.offer(engine, requests, ctx.seconds,
                                          mix["first_token_cap_s"], ctx.spans,
                                          ctx.profiler)
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
        f"decoded tokens (must stay far from 5%); selected pages "
        f"{counts['kv_selected_page_frac']:.3f} of the live ones; state "
        f"{counts['state_bytes'] / 2**20:.0f} MiB; generator lateness "
        f"{w['lateness']}; queue depth mean {np.mean(qd) if qd else 0:.2f} "
        f"max {max(qd) if qd else 0}; decode steps {len(occ)}; slot occupancy "
        f"{ctx.spans.counters['slot_occupancy']:.3f}; preemptions "
        f"{engine.metrics.summary().get('preemptions')}; compiles inside the "
        f"window: {w['compiled_inside']} (must be 0); peak {w['peak'] / 2**30:.2f} GiB")
    return engine, recs, w


def read_also(ctx, names) -> dict:
    """The metrics of the cell's file that BENCHMARK.json does not list
    yet (``also_read``: files under ``metrics/`` only, as
    ``queue_wait_ms`` is), read from the traced slice and printed."""
    import importlib

    from benchmark import profiling

    run = {"spans": ctx.spans, "device": ctx.device, "notes": ctx.notes,
           "cell": ctx.cell_file,
           "trace": profiling.traced_run(ctx.profiler.events(),
                                         len(ctx.devices))}
    out = {}
    for name in names:
        spec = ctx.manifest.metric_file(name)
        reader = importlib.import_module(f"benchmark.reducers.{spec['reducer']}")
        out[name] = reader.read(run, spec.get("params", {}))
        ctx.say(f"metric {name}: {out[name]}")
    return out


def run(ctx, controls=()) -> dict:
    import jax

    cell, say = ctx.cell_file, ctx.say
    engine, recs, w = serve_window(ctx)
    if ctx.trace and cell.get("also_read"):
        read_also(ctx, cell["also_read"])
    sample = serve.sample_finished(w["done"], ctx.seed,
                                   cell["check"]["min_served_tokens"])
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
            f"+{len(sample[0]['output'])} tokens; the program's selector and "
            f"the reference's differ in {g['selections_differ']} of "
            f"{g['selections']} (position, key head) selections")
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

    ap = argparse.ArgumentParser(description="the controls of a serve_long cell")
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
