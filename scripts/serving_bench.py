"""Continuous-batching serving benchmark on the forced-CPU mesh.

Drives ServingEngine with a synthetic open-loop Poisson arrival trace
(exponential inter-arrival times, mixed prompt/output lengths) and writes
BENCH_serving.json: tokens/s, p50/p99 TTFT and TPOT, slot occupancy,
preemptions. The model is a tiny random-weight GPT — the benchmark
measures the ENGINE (scheduling, paged-cache writes, one-compile decode),
not model quality, so it runs anywhere (CI included) in seconds.

``--shared-prefix`` switches to production-shaped traffic: a Zipf-ish
mix over K shared system prompts plus a long-prompt tail, replayed TWICE
on the same arrival schedule — once with prefix caching and chunked
prefill off (baseline) and once with both on — and emits a
``prefix_reuse`` block comparing TTFT p99 and head-of-line blocking
across the two passes alongside the radix-cache hit counters.

Usage:
  python scripts/serving_bench.py [--requests 32] [--rate 8.0] \
      [--num-slots 4] [--num-blocks 64] [--out BENCH_serving.json] \
      [--slo] [--shared-prefix] [--prefill-chunk N] [--prefill-budget N]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the benchmark targets the host CPU mesh by design (the acceptance
# surface for serving work without a chip); export JAX_PLATFORMS=tpu to
# override before invoking
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# shared-prefix traffic shape: K distinct system prompts, popularity
# ~ 1/rank (Zipf-ish — one prompt dominates, the rest are a long tail
# of tenants), short per-request user suffixes, and a slice of
# long-prompt requests that stress chunked prefill
SHARED_PREFIX_K = 4
SHARED_PREFIX_LEN = (96, 144)        # system-prompt token lengths
SHARED_SUFFIX_LEN = (8, 32)          # per-request user suffix
SHARED_LONG_FRAC = 0.15              # long-tail request fraction
SHARED_LONG_TOTAL = (160, 220)       # total prompt length of the tail


def make_scfg(args, mode: str):
    """Serving config for one bench pass. ``plain`` honors the CLI knobs
    as given; ``baseline`` forces reuse AND chunking off (the
    shared-prefix comparison floor); ``reuse`` turns prefix caching on
    and defaults chunking/budget when the CLI left them unset; ``spec``
    is ``plain`` plus the speculative sub-block (truncated drafter)."""
    from deeperspeed_tpu.serving import ServingConfig

    chunk, budget = args.prefill_chunk, args.prefill_budget
    if mode == "baseline":
        chunk = budget = None
    elif mode == "reuse":
        chunk = chunk if chunk is not None else 4 * args.block_size
        budget = budget if budget is not None else 8 * args.block_size
    speculative = None
    if mode == "spec":
        speculative = {"draft_k": args.draft_k,
                       "drafter": {"n_layer": args.drafter_layers}}
    return ServingConfig(num_slots=args.num_slots,
                         block_size=args.block_size,
                         num_blocks=args.num_blocks,
                         max_seq_len=args.max_seq_len,
                         prefix_caching=(mode == "reuse"),
                         prefill_chunk=chunk,
                         prefill_token_budget=budget,
                         speculative=speculative,
                         slo=({"ttft_p99_ms": 250.0, "tpot_p99_ms": 50.0,
                               "e2e_p99_ms": 2500.0}
                              if args.slo else None))


def run_pass(args, cfg, params, scfg, prompts, arrivals, news,
             sys_prompts, trace_path, metrics_port):
    """One warmed, measured replay of the arrival schedule. Returns the
    metrics summary and the compile counters."""
    from deeperspeed_tpu.serving import ServingEngine

    monitor_config = None
    if trace_path is not None or metrics_port is not None:
        monitor_config = {
            "trace_path": trace_path,
            "trace_enabled": trace_path is not None,
            "metrics_port": metrics_port,
            "watchdog": "warn",
        }
    eng = ServingEngine(cfg, params, scfg, monitor_config=monitor_config)

    # warm the compiled paths so the measured run is steady-state (one
    # decode program + the prefill buckets the trace will hit); doctor
    # mode warms EVERY bucket — measured requests must pay zero compile,
    # so the tail the doctor reads is scheduling, not XLA
    wrng = np.random.default_rng(args.seed + 1)
    warmed = False
    if args.slo:
        for b in scfg.prefill_buckets:
            eng.submit(wrng.integers(0, cfg.vocab_size,
                                     max(1, b - 2)).tolist(),
                       max_new_tokens=2, request_id=f"warm-{b}")
        eng.run()
        warmed = True
    if sys_prompts is not None:
        # warm each system prompt serially at the suffix lengths the
        # measured traffic draws from: the first prefill indexes the
        # prompt in the radix cache (when caching is on), the rest
        # exercise — and compile — every suffix-prefill shape (s_pad
        # bucket × staging cache bucket, plus the per-page-count gather)
        # the measured pass will hit, so the measured pass starts with a
        # warm cache in BOTH senses and the TTFT/HOL comparison reads
        # scheduling, not XLA. The baseline pass runs the identical
        # warmup for a fair comparison.
        for k, sp in enumerate(sys_prompts):
            # first run misses and indexes the prompt; the rest are HITS
            # covering both short-suffix pad buckets plus the long tail
            # (chunked, or the full-prefill fallback when no staging
            # bucket covers it) — exactly the shapes measured hits take
            suffixes = (SHARED_SUFFIX_LEN[0],
                        SHARED_SUFFIX_LEN[0],
                        SHARED_SUFFIX_LEN[1],
                        max(SHARED_LONG_TOTAL[1] - len(sp),
                            SHARED_SUFFIX_LEN[0]))
            for j, n in enumerate(suffixes):
                eng.submit(sp + wrng.integers(0, cfg.vocab_size,
                                              int(n)).tolist(),
                           max_new_tokens=2, request_id=f"warm-sys{k}-{j}")
                eng.run()
        warmed = True
    if not warmed:
        if scfg.speculative is not None:
            # warm all three decode-path programs (draft, verify,
            # fallback) AND the drafter-sync suffix shapes (pad bucket
            # × page count) the measured prompts will hit — drafter
            # sync compiles are per bucket combination, and one landing
            # mid-measurement would charge XLA to some request's TPOT
            for j, b in enumerate(scfg.prefill_buckets):
                plen = min(max(1, b - 2), scfg.max_seq_len - 8)
                eng.submit(wrng.integers(0, cfg.vocab_size,
                                         plen).tolist(),
                           max_new_tokens=8,
                           request_id=f"warm-spec{j}")
                eng.run()
        else:
            eng.submit(prompts[0], max_new_tokens=2)
            eng.run()
    assert all(r.state == "finished" for r in eng.sched.finished)
    # drop warmup stats (Prometheus counters, being cumulative, keep the
    # warmup requests — the trace marks the measured-run boundary instead)
    eng.metrics.__init__(scfg.num_slots, eng.clock,
                         registry=eng.metrics.registry, slo=scfg.slo)

    t0 = time.monotonic()
    submitted = 0
    while submitted < args.requests or eng.has_work():
        now = time.monotonic() - t0
        while submitted < args.requests and arrivals[submitted] <= now:
            eng.submit(prompts[submitted],
                       max_new_tokens=int(news[submitted]))
            submitted += 1
        if eng.has_work():
            eng.step()
        elif submitted < args.requests:
            time.sleep(min(arrivals[submitted] - now, 0.01))

    s = eng.metrics.summary()
    assert s["requests_finished"] == args.requests, s
    compiles = {
        "decode_compiles": eng.decode_compile_count,
        "prefill_compiles": eng.prefill_compile_count,
        "chunk_prefill_compiles": eng.chunk_prefill_compile_count,
    }
    if scfg.speculative is not None:
        compiles["draft_compiles"] = eng.draft_compile_count
        compiles["verify_compiles"] = eng.verify_compile_count
    if eng.telemetry is not None:
        from deeperspeed_tpu.monitor import shutdown_monitor
        from deeperspeed_tpu.monitor.validate import validate_file

        shutdown_monitor(save=True)  # writes the trace
        if trace_path is not None:
            errors = validate_file(trace_path)
            assert not errors, errors[:5]
    return s, compiles


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=None,
                    help="Poisson arrival rate, requests/s (default 8; "
                         "80 in --slo mode, where the doctor needs real "
                         "admission contention to attribute)")
    ap.add_argument("--num-slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size in blocks (default 64; 192 with "
                         "--shared-prefix, where the radix cache keeps "
                         "warm prefixes resident ALONGSIDE live traffic "
                         "— a pool sized for exclusive ownership would "
                         "measure reclaim churn, not reuse)")
    ap.add_argument("--max-seq-len", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(8, 48),
                    metavar=("LO", "HI"))
    ap.add_argument("--max-new", type=int, nargs=2, default=(16, 64),
                    metavar=("LO", "HI"))
    ap.add_argument("--n-layer", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=None,
                    help="model width (default 64; 256 with "
                         "--shared-prefix, where prefill compute must "
                         "dominate launch overhead for the reuse "
                         "comparison to measure the cache, not the "
                         "dispatch path)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON of the run (open in "
                         "Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics on this port for the "
                         "duration of the run (0 = ephemeral)")
    ap.add_argument("--slo", action="store_true",
                    help="request-path-doctor mode: set SLO targets, warm "
                         "EVERY prefill bucket (so measured requests pay "
                         "no compile), skew the prompt mix long-tailed, "
                         "and emit an attribution breakdown ('slo' block) "
                         "from the trace via monitor/reqledger")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="production-shaped traffic over K shared system "
                         "prompts (Zipf-ish popularity + long-prompt "
                         "tail), replayed twice — baseline vs prefix "
                         "caching + chunked prefill — and compared in a "
                         "'prefix_reuse' output block")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill slab size in tokens (default: "
                         "off; 2*block_size in the --shared-prefix reuse "
                         "pass)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="per-step prefill token budget (default: "
                         "unbounded; 4*block_size in the --shared-prefix "
                         "reuse pass)")
    ap.add_argument("--speculative", action="store_true",
                    help="dual-pass speculative-decoding comparison: "
                         "replay the same arrival schedule with plain "
                         "decode (baseline) and with a truncated-drafter "
                         "speculative engine, and emit a 'speculative' "
                         "block (accept_rate, tpot_ms vs baseline, "
                         "e2e_p99_ms). The target's upper layers are "
                         "down-scaled by --spec-alpha so the truncated "
                         "drafter is a FAITHFUL approximation — the CPU "
                         "bench measures the engine at a realistic "
                         "acceptance rate, not drafter quality")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens per speculative round")
    ap.add_argument("--drafter-layers", type=int, default=None,
                    help="truncated-drafter depth (default "
                         "max(1, n_layer//4))")
    ap.add_argument("--merge-out", action="store_true",
                    help="with --speculative: merge the 'speculative' "
                         "block and its compile counters into an "
                         "existing --out file (the corpus "
                         "BENCH_serving.json is written by the "
                         "--slo --shared-prefix run) instead of "
                         "overwriting it")
    ap.add_argument("--spec-alpha", type=float, default=0.3,
                    help="down-scale factor applied to the target's "
                         "layers above the drafter cut in --speculative "
                         "mode (makes drafter/target agreement high, as "
                         "a distilled drafter's would be)")
    args = ap.parse_args()
    if args.speculative and args.shared_prefix:
        ap.error("--speculative and --shared-prefix are separate "
                 "comparisons; run them as two bench invocations")
    if args.merge_out and not args.speculative:
        ap.error("--merge-out only applies to --speculative runs")
    if args.drafter_layers is None:
        args.drafter_layers = max(1, args.n_layer // 4)
    if args.rate is None:
        args.rate = 80.0 if args.slo else 8.0
    if args.num_blocks is None:
        args.num_blocks = 192 if args.shared_prefix else 64
    if args.d_model is None:
        args.d_model = 256 if args.shared_prefix else 64
    if (args.slo or args.shared_prefix or args.speculative) \
            and args.trace is None:
        # attribution needs the trace; default it next to the other
        # committed drill traces (the spec pass gets its own file so
        # the corpus keeps both drill traces side by side)
        args.trace = os.path.join(
            "traces", "serving_spec_trace.json" if args.speculative
            else "serving_bench_trace.json")

    from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt

    cfg = GPTConfig(vocab_size=256, n_layer=args.n_layer, n_head=2,
                    d_model=args.d_model, max_seq=args.max_seq_len,
                    remat=False, dtype=jnp.float32, attn_impl="xla")
    init_fn, _, _, _ = make_gpt(cfg)
    params = init_fn(jax.random.PRNGKey(args.seed))
    if args.speculative:
        # make the first --drafter-layers layers dominate the target's
        # computation: random upper layers would make the truncated
        # drafter a coin flip (sub-1% acceptance), which benchmarks
        # nothing — a production drafter is distilled to agree. Scaling
        # the layers ABOVE the cut by alpha keeps one weight set serving
        # both passes (plain decode is bit-identical either way).
        nd = args.drafter_layers
        layers = params["layers"]
        scale = jax.tree.map(
            lambda x: x * np.where(
                np.arange(x.shape[0]) < nd, 1.0,
                args.spec_alpha).reshape(
                    (x.shape[0],) + (1,) * (x.ndim - 1)).astype(x.dtype),
            layers)
        params = dict(params)
        params["layers"] = scale

    # open-loop Poisson trace: arrival offsets + per-request lengths,
    # all drawn up front so the trace is reproducible from --seed
    rng = np.random.default_rng(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    plens = rng.integers(args.prompt_len[0], args.prompt_len[1] + 1,
                         args.requests)
    news = rng.integers(args.max_new[0], args.max_new[1] + 1, args.requests)
    if args.slo:
        # heavy-tailed prompt mix, short generations: half the traffic
        # carries near-max-bucket prompts and every request finishes in
        # a few decode steps, so slots churn through admission waves of
        # expensive prefills — the TTFT tail is genuine head-of-line
        # blocking behind long prefills (the thing the doctor
        # attributes), not compile noise or decode occupancy
        long_mask = rng.random(args.requests) < 0.5
        plens = np.where(long_mask,
                         rng.integers(160, 221, args.requests),
                         rng.integers(32, 97, args.requests))
        news = rng.integers(4, 9, args.requests)
    prompts = [rng.integers(0, cfg.vocab_size, p).tolist() for p in plens]
    sys_prompts = None
    if args.shared_prefix:
        # overrides the --slo prompt mix (the long tail lives in the
        # suffix draw below instead); arrivals and the slo block keep
        # their --slo semantics
        lo, hi = SHARED_PREFIX_LEN
        sys_lens = rng.integers(lo, hi + 1, SHARED_PREFIX_K)
        sys_prompts = [rng.integers(0, cfg.vocab_size, int(n)).tolist()
                       for n in sys_lens]
        ranks = np.arange(1, SHARED_PREFIX_K + 1, dtype=np.float64)
        popularity = (1.0 / ranks) / (1.0 / ranks).sum()
        picks = rng.choice(SHARED_PREFIX_K, size=args.requests,
                           p=popularity)
        long_mask = rng.random(args.requests) < SHARED_LONG_FRAC
        total = rng.integers(SHARED_LONG_TOTAL[0], SHARED_LONG_TOTAL[1] + 1,
                             args.requests)
        suffix_lens = np.where(
            long_mask,
            np.maximum(total - sys_lens[picks], SHARED_SUFFIX_LEN[0]),
            rng.integers(SHARED_SUFFIX_LEN[0], SHARED_SUFFIX_LEN[1] + 1,
                         args.requests))
        prompts = [sys_prompts[int(k)]
                   + rng.integers(0, cfg.vocab_size, int(n)).tolist()
                   for k, n in zip(picks, suffix_lens)]
        news = rng.integers(4, 9, args.requests)

    s_base = None
    if args.shared_prefix:
        # replay the same schedule twice: baseline (no reuse, no
        # chunking) into a throwaway trace, then the measured pass with
        # the radix cache + chunked prefill on into --trace. BENCH
        # numbers come from the measured pass; the baseline exists only
        # for the before/after columns of the prefix_reuse block.
        base_trace = args.trace + ".baseline"
        s_base, _ = run_pass(args, cfg, params,
                             make_scfg(args, "baseline"), prompts,
                             arrivals, news, sys_prompts, base_trace,
                             None)
        scfg = make_scfg(args, "reuse")
        s, compiles = run_pass(args, cfg, params, scfg, prompts,
                               arrivals, news, sys_prompts, args.trace,
                               args.metrics_port)
    elif args.speculative:
        # same dual-pass discipline as --shared-prefix: plain decode
        # (the TPOT floor speculative must beat) into a throwaway
        # trace, then the speculative pass into --trace. Same weights,
        # same schedule — greedy outputs are token-identical by the
        # engine's determinism contract, so the comparison is pure
        # engine mechanics.
        base_trace = args.trace + ".baseline"
        s_base, _ = run_pass(args, cfg, params, make_scfg(args, "plain"),
                             prompts, arrivals, news, None, base_trace,
                             None)
        scfg = make_scfg(args, "spec")
        s, compiles = run_pass(args, cfg, params, scfg, prompts,
                               arrivals, news, None, args.trace,
                               args.metrics_port)
    else:
        scfg = make_scfg(args, "plain")
        s, compiles = run_pass(args, cfg, params, scfg, prompts,
                               arrivals, news, None, args.trace,
                               args.metrics_port)

    out = {
        "bench": "serving",
        "platform": jax.devices()[0].platform,
        "config": {
            "requests": args.requests,
            "rate_rps": args.rate,
            "num_slots": args.num_slots,
            "block_size": args.block_size,
            "num_blocks": args.num_blocks,
            "max_seq_len": args.max_seq_len,
            "n_layer": args.n_layer,
            "d_model": args.d_model,
            "seed": args.seed,
            "shared_prefix": args.shared_prefix,
            "speculative": args.speculative,
            "prefix_caching": scfg.prefix_caching,
            "prefill_chunk": scfg.prefill_chunk,
            "prefill_token_budget": scfg.prefill_token_budget,
        },
        "requests_finished": s["requests_finished"],
        "tokens_generated": s["tokens_generated"],
        "tokens_per_sec": round(s["tokens_per_sec"], 2),
        "ttft_p50_s": round(s["ttft_s"]["p50"], 4),
        "ttft_p99_s": round(s["ttft_s"]["p99"], 4),
        "tpot_p50_s": round(s["tpot_s"]["p50"], 4),
        "tpot_p99_s": round(s["tpot_s"]["p99"], 4),
        "slot_occupancy": round(s["slot_occupancy"], 3),
        "queue_depth_max": s["queue_depth_max"],
        "preemptions": s["preemptions"],
        **compiles,
    }
    if args.trace is not None:
        out["trace"] = args.trace
    report = None
    if args.slo or args.shared_prefix or args.speculative:
        # offline attribution over the trace just written: where every
        # request's TTFT went, who blocked whom, and what a kilotoken
        # costs — the keys monitor/ledger.py gates (serving.ttft_p99_ms,
        # serving.cost_per_1k_tokens)
        from deeperspeed_tpu.monitor.reqledger import build_ledger

        report = build_ledger(args.trace)
    if args.shared_prefix:
        # before/after columns on the SAME arrival schedule: the radix
        # cache + chunked prefill must show up as fewer prefill tokens,
        # a shorter TTFT tail, and strictly less head-of-line blocking
        report_base = build_ledger(base_trace)
        os.remove(base_trace)
        pr = dict(s["prefix_reuse"])
        pr["reuse_hit_rate"] = round(pr["reuse_hit_rate"], 4)
        pr["tokens_saved_frac"] = round(pr["tokens_saved_frac"], 4)
        pr.update({
            "ttft_p99_s_baseline": round(s_base["ttft_s"]["p99"], 4),
            "ttft_p99_s": round(s["ttft_s"]["p99"], 4),
            "hol_blocking_ms_baseline":
                report_base["buckets_total_ms"]["hol_blocking"],
            "hol_blocking_ms": report["buckets_total_ms"]["hol_blocking"],
        })
        out["prefix_reuse"] = pr
    if args.speculative:
        # before/after columns on the SAME arrival schedule and the SAME
        # target weights: acceptance comes from the engine's own round
        # accounting, the TPOT/e2e columns from the two passes' metrics
        # and trace ledgers — the drafter must buy back more decode
        # steps than its own draft+verify overhead costs
        report_base = build_ledger(base_trace)
        os.remove(base_trace)
        sp = dict(s["speculative"])
        for k in ("accept_rate", "tokens_per_round",
                  "draft_time_s", "verify_time_s"):
            sp[k] = round(sp[k], 4)
        tpot_base_ms = s_base["tpot_s"]["p50"] * 1e3
        tpot_ms = s["tpot_s"]["p50"] * 1e3
        sp.update({
            "draft_k": scfg.speculative.draft_k,
            "n_layer": args.n_layer,
            "drafter_layers": args.drafter_layers,
            "spec_alpha": args.spec_alpha,
            "tpot_ms_baseline": round(tpot_base_ms, 3),
            "tpot_ms": round(tpot_ms, 3),
            "tpot_reduction": (round(1.0 - tpot_ms / tpot_base_ms, 4)
                               if tpot_base_ms > 0 else 0.0),
            "e2e_p99_ms_baseline": report_base["e2e"]["p99_ms"],
            "e2e_p99_ms": report["e2e"]["p99_ms"],
        })
        out["speculative"] = sp
    if args.slo:
        out["slo"] = {
            "targets": s["slo"],
            "ttft_p99_ms": report["ttft"]["p99_ms"],
            "e2e_p99_ms": report["e2e"]["p99_ms"],
            "cost_per_1k_tokens": report["cost_per_1k_tokens"],
            "buckets_total_ms": report["buckets_total_ms"],
            "p99_victim": report["p99_victim"],
            "top_blockers": report["top_blockers"],
            "worst_residual_fraction": report["worst_residual_fraction"],
        }
    if args.merge_out and os.path.exists(args.out):
        # corpus mode: BENCH_serving.json is written by the
        # --slo --shared-prefix run; the speculative pass (mutually
        # exclusive with it) contributes only its own headline block
        # plus its compile counters, leaving every other row intact
        with open(args.out) as f:
            prev = json.load(f)
        prev["speculative"] = out["speculative"]
        for k in ("draft_compiles", "verify_compiles"):
            prev[k] = out[k]
        out = prev
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
