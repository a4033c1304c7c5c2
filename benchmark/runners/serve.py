"""A serving cell: ``ServingEngine`` under an open loop.

One thread offers the schedule (a request is submitted as soon as it is
due and the loop comes round) and drives ``engine.step()``. A token's time
is the host's clock when the step that produced it returned; latencies
count from when the request was DUE. Every request of the schedule is due
inside the window. When the window closes the loop goes on only until each
of them has its first token (bounded by the mix's ``first_token_cap_s``):
a request still decoding then is cut by the close, which is no failure; one
without a first token by the cap, or ended by anything but its length, is.
"""

import gc
import time

import numpy as np

from .. import device, stats, generator as tg
from ..refs import init as rinit
from ..refs import layerwise as lw
from ..refs import serve_check
from ..refs.numerics import Numerics

TRACE_AT, TRACE_SECONDS = 0.4, 4.0
E2E = ("ttft_mean_ms", "ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms",
       "serve_tokens_per_s")


def offer(engine, requests, seconds, first_token_cap_s, spans, profiler=None,
          drain=False):
    """Offer ``requests`` (sorted by due time) and drive the engine through
    the window and on until every request has its first token (with
    ``drain``: until every request is finished) or the cap passes. Returns
    per-request records and per-step queue depths; times are seconds from
    the window's start."""
    recs = [{"due": r["due_s"], "sent": None, "tokens": [], "req": None}
            for r in requests]
    by_rid = {}
    queue_depth = []
    t_open = time.perf_counter()
    i, n = 0, len(requests)
    trace_at = TRACE_AT * seconds
    while True:
        now = time.perf_counter() - t_open
        while i < n and requests[i]["due_s"] <= now:
            r = requests[i]
            engine.submit(r["prompt"], max_new_tokens=r["max_new_tokens"],
                          request_id=r["rid"])
            recs[i]["sent"] = now
            recs[i]["req"] = engine.get(r["rid"])
            by_rid[r["rid"]] = recs[i]
            i += 1
        if profiler is not None:
            if not profiler.active and not profiler.done and now >= trace_at:
                profiler.start()
            elif profiler.active and now >= trace_at + TRACE_SECONDS:
                profiler.stop()
                profiler.done = True
        if engine.has_work():
            with spans.span("serve_step"):
                finished = engine.step()
            t = time.perf_counter() - t_open
            live = [q for q in engine.sched.slots if q is not None] + list(finished)
            for q in live:
                rec = by_rid.get(q.rid)
                if rec is not None:
                    rec["tokens"].extend([t] * (len(q.generated) - len(rec["tokens"])))
            queue_depth.append((t, len(engine.sched.queue)))
        elif i < n:
            time.sleep(max(0.0, min(0.005, requests[i]["due_s"] - now)))
        else:
            break
        if now >= seconds and i == n and not drain \
                and all(r["tokens"] for r in recs):
            break
        if now > seconds + first_token_cap_s:
            break
    if profiler is not None and profiler.active:
        profiler.stop()
        profiler.done = True
    return recs, queue_depth, time.perf_counter() - t_open


def reduce_window(recs, seconds):
    """End-to-end numbers of one window from the per-request records."""
    done = [r for r in recs if r["req"] is not None
            and r["req"].state == "finished"
            and r["req"].finish_reason in ("length", "eos")]
    bad = [r for r in recs if not r["tokens"] or (
        r["req"].state == "finished"
        and r["req"].finish_reason not in ("length", "eos"))]
    started = [r for r in recs if r["tokens"]]
    ttft = stats.due_latencies([r["due"] for r in started],
                               [r["tokens"][0] for r in started])
    gaps = stats.token_gaps([r["tokens"] for r in recs])
    in_window = sum(1 for r in recs for t in r["tokens"] if t <= seconds)
    late = stats.lateness([r["due"] for r in recs if r["sent"] is not None],
                          [r["sent"] for r in recs if r["sent"] is not None])
    return {"attempted": len(recs), "failed": len(bad),
            "cut_by_close": len(recs) - len(done) - len(bad),
            "ttft_mean_ms": 1e3 * sum(ttft) / len(ttft) if ttft else float("nan"),
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95) if ttft else float("nan"),
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50) if ttft else float("nan"),
            "tpot_p95_ms": 1e3 * stats.percentile(gaps, 95) if gaps else float("nan"),
            "tpot_p50_ms": 1e3 * stats.percentile(gaps, 50) if gaps else float("nan"),
            "serve_tokens_per_s": in_window / seconds, "n_gaps": len(gaps),
            "lateness": late, "done": done}


def build_engine(ctx):
    import jax.numpy as jnp

    ref = ctx.adapter.reference
    params = rinit.init_tree(ctx.seed, ref.leaf_specs(ctx.config),
                             jnp.dtype(ctx.cell_file["weights_dtype"]))
    return ctx.adapter.serving_engine(ctx.config, params, ctx.cell_file["serving"])


def schedule(mix, seed, seconds, vocab):
    """The generator's schedule: in the seed's own order or, where the mix
    deals one (``arrivals.deal``), the same lengths and gaps in that ONE
    order for every seed, the seed drawing the token ids alone. The order
    decides who decodes beside whom, and a decode step reads the pages of
    whoever is live: dealt by each seed this cell's ``tpot_p95_ms`` moved
    4.41 to 4.66 ms over nine seeds, two runs of one seed 0.01 apart
    (PERF.md section 6, PR 44)."""
    deal = mix["arrivals"].get("deal")
    if deal is None:
        return tg.serve_requests(mix, seed, seconds, vocab)
    requests = tg.serve_requests(mix, deal, seconds, vocab)
    tok = tg.rng_for(seed, 2)
    for r in requests:
        r["prompt"] = tok.integers(0, vocab, len(r["prompt"])).tolist()
    return requests


def warm(engine, requests, vocab, seed):
    """One request for every prompt shape of the schedule, then decode, so
    that every program the window uses is compiled or loaded. The program
    compiles its prefill once a length bucket and the scatter of the
    prefilled pages into the pool once a NUMBER OF PAGES (found by PR 23:
    2 s stalls inside the window), so a shape is (bucket, pages)."""
    scfg = engine.scfg
    rng = tg.rng_for(seed, 4)
    shapes = {}
    for r in requests:
        n = len(r["prompt"])
        shapes.setdefault((scfg.bucket_for(n), -(-n // scfg.block_size)), n)
    for (b, pages), n in sorted(shapes.items()):
        engine.submit(rng.integers(0, vocab, n).tolist(), max_new_tokens=2,
                      request_id=f"warm-{b}-{pages}")
    engine.run()
    return sorted(shapes)


def sample_finished(done, seed, min_tokens):
    """The longest finished request and others drawn from the seed until
    ``min_tokens`` served tokens are in the sample."""
    if not done:
        return []
    order = sorted(done, key=lambda r: -(len(r["req"].prompt) + len(r["req"].generated)))
    pick, rest = [order[0]], order[1:]
    rng = tg.rng_for(seed, 5)
    rng.shuffle(rest)
    for r in rest:
        if sum(len(p["req"].generated) for p in pick) >= min_tokens:
            break
        pick.append(r)
    return [{"prompt": list(r["req"].prompt), "output": list(r["req"].generated)}
            for r in pick]


def check_served(ctx, sample, control=None) -> dict:
    """Reference gaps of the sampled requests (and those of the control,
    the reference in the lower precision ``control`` names)."""
    import jax
    import jax.numpy as jnp

    cfg, ref = ctx.config, ctx.adapter.reference
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        rinit.init_tree(ctx.seed, ref.leaf_specs(cfg),
                        jnp.dtype(ctx.cell_file["weights_dtype"])))
    L = cfg["num_hidden_layers"]
    model = ref.make(cfg, Numerics("f32"))
    trainer = lw.Layerwise(model, L, 1)
    kw = {}
    if control:
        low = ref.make(cfg, Numerics(control))
        kw = {"control_model": low, "control_trainer": lw.Layerwise(low, L, 1)}
    return serve_check.served_gaps(model, trainer, params, sample, **kw)


def run(ctx) -> dict:
    import jax

    cfg, cell, mix, say = ctx.config, ctx.cell_file, ctx.traffic, ctx.say
    requests = schedule(mix, ctx.seed, ctx.seconds, cfg["vocab_size"])
    engine = build_engine(ctx)
    shapes = warm(engine, requests, cfg["vocab_size"], ctx.seed)
    lowered = device.LoweringCounter.get()
    compiles = lowered.count
    n_occ = len(engine.metrics.occupancy)
    deal = mix["arrivals"].get("deal")
    order = "the seed" if deal is None else f"deal {deal}"
    say(f"warmed decode and {len(shapes)} prefill shapes in buckets "
        f"{sorted({b for b, _ in shapes})}; {len(requests)} requests "
        f"offered over {ctx.seconds:g} s in the order of {order}; in use "
        f"{device.bytes_in_use(ctx.devices) / 2**30:.2f} GiB")
    ctx.spans.durations["serve_step"].clear()
    setup_s = time.perf_counter() - ctx.t_start
    recs, queue_depth, took = offer(engine, requests, ctx.seconds,
                                    mix["first_token_cap_s"], ctx.spans,
                                    ctx.profiler)
    w = reduce_window(recs, ctx.seconds)
    peak = device.memory_peak_bytes(ctx.devices)
    compiled_inside = lowered.count - compiles
    occ = engine.metrics.occupancy[n_occ:]
    ctx.spans.counters["slot_occupancy"] = float(np.mean(occ)) if occ else 0.0
    ctx.spans.counters["hbm_peak_bytes"] = peak
    summ = engine.metrics.summary()
    qd = [q for _, q in queue_depth]
    say(f"window: {w['attempted']} requests, {w['failed']} failed, "
        f"{w['cut_by_close']} still decoding when the run stopped at {took:.2f} s; "
        f"ttft mean {w['ttft_mean_ms']:.1f} p50 {w['ttft_p50_ms']:.1f} p95 {w['ttft_p95_ms']:.1f} ms "
        f"(n={w['attempted']}, {stats.samples_beyond(w['attempted'], 95)} beyond); "
        f"tpot p50 {w['tpot_p50_ms']:.2f} p95 {w['tpot_p95_ms']:.2f} ms "
        f"(n={w['n_gaps']}); {w['serve_tokens_per_s']:.1f} tokens/s in the window")
    say(f"generator lateness {w['lateness']}; queue depth mean "
        f"{np.mean(qd) if qd else 0:.2f} max {max(qd) if qd else 0}; decode steps "
        f"{len(occ)}; slot occupancy {ctx.spans.counters['slot_occupancy']:.3f}; "
        f"preemptions {summ.get('preemptions')}; compiles inside the window: "
        f"{compiled_inside} (must be 0); peak {peak / 2**30:.2f} GiB")

    sample = sample_finished(w["done"], ctx.seed, cell["check"]["min_served_tokens"])
    del engine, recs, w["done"]
    gc.collect()
    jax.clear_caches()
    say(f"program freed: {device.bytes_in_use(ctx.devices) / 2**30:.2f} GiB in use")
    t_ref = time.perf_counter()
    limit = cell["check"]["limits"]["served_logit_gap"]
    correct = False
    if sample:
        g = check_served(ctx, sample)
        correct = g["widest_gap"] <= limit
        say(f"check served_logit_gap: {g['widest_gap']:.6g} (limit {limit:g}) "
            f"{'ok' if correct else 'OVER'}; {g['tokens']} served tokens of "
            f"{len(sample)} requests, the longest of {len(sample[0]['prompt'])}"
            f"+{len(sample[0]['output'])} tokens")
    else:
        say("check served_logit_gap: no request finished, nothing to compare")
    say(f"reference took {time.perf_counter() - t_ref:.1f} s")
    return {
        "correct": bool(correct and w["failed"] == 0 and compiled_inside == 0),
        "attempted": w["attempted"], "failed": w["failed"],
        # every statistic of the window; the manifest says which are judged
        "end_to_end": {k: w[k] for k in E2E} | {"setup_s": setup_s},
        "memory_peak_bytes": peak,
    }
