"""Serving fleet kill drill: SIGKILL + stall under a live Poisson trace.

Three subprocess replicas serve an open-loop Poisson request trace
through the FleetRouter. Mid-trace, fault injection inside the children
(resilience/faults.py, counter-based so runs are reproducible) SIGKILLs
replica 1 and wedges replica 2 (alive and heartbeating, emitting no
tokens — the failure mode only the decode-progress watchdog catches).
The router must notice both, requeue their in-flight requests onto the
healthy replica, and restart the casualties.

Acceptance, audited from router state (not replica claims):

  * ZERO lost accepted requests — every rid admission control accepted
    reaches a clean terminal outcome (``length``/``eos``); ``failed`` or
    a missing outcome is a drill failure.
  * p99 TTFT under failure is reported next to an identically-shaped
    healthy baseline run (the cost of failover, in numbers).
  * a shed-rate curve over increasing offered load (thread-replica
    fleet with a tight queue cap): admission control degrades by
    rejecting loudly, not by queueing unboundedly.
  * the drill's Chrome trace — carrying ``serving/shed``,
    ``serving/retry``, ``serving/replica_down``, ``serving/finish``
    instants — passes ``python -m deeperspeed_tpu.monitor.validate``.

Writes its report where ``--out`` says (default: the git-ignored
BENCH_fleet.json).

Usage:
  python scripts/fleet_drill.py [--quick] [--out BENCH_fleet.json]
"""

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# one tiny GPT spec shared by every replica (subprocess AND thread):
# identical weights from init_seed is what makes failover retries
# token-identical
MODEL_SPEC = {
    "gpt": {"vocab_size": 97, "n_layer": 2, "n_head": 2, "d_model": 32,
            "max_seq": 256, "remat": False, "attn_impl": "xla"},
    "init_seed": 0,
    "serving": {"num_slots": 4, "block_size": 8, "num_blocks": 128,
                "max_seq_len": 256, "max_new_tokens": 64,
                "prefill_buckets": [16, 256]},
    "warm": True,
}


def make_trace(rng, n, rate, vocab):
    """Reproducible open-loop Poisson trace: arrival offsets, prompts,
    generation budgets, temperatures (half greedy, half sampled)."""
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    plens = rng.integers(6, 13, n)
    prompts = [rng.integers(1, vocab, p).tolist() for p in plens]
    news = rng.integers(24, 49, n)
    temps = np.where(rng.random(n) < 0.5, 0.0, 0.7)
    return arrivals, prompts, news, temps


def run_poisson(router, arrivals, prompts, news, temps,
                timeout_s=300.0):
    """Drive the trace open-loop: submit on schedule (sheds counted,
    never retried — the curve wants the raw rejection rate), step the
    router, then run to idle."""
    from deeperspeed_tpu.serving import ShedError

    accepted, shed = [], 0
    t0 = time.monotonic()
    i = 0
    while i < len(prompts):
        now = time.monotonic() - t0
        while i < len(prompts) and arrivals[i] <= now:
            try:
                rid = router.submit(prompts[i],
                                    max_new_tokens=int(news[i]),
                                    temperature=float(temps[i]),
                                    request_id=f"t{i}")
                accepted.append(rid)
            except ShedError:
                shed += 1
            i += 1
        router.step()
        time.sleep(router.rcfg.poll_interval_s)
        if time.monotonic() - t0 > timeout_s:
            break
    router.run_until_idle(timeout_s=timeout_s)
    return accepted, shed


def drill_failover(n_requests: int, sigkill_at: int, stall_at: int):
    """Healthy baseline run, then the same trace with replica 1
    SIGKILLed and replica 2 stalled mid-trace (trigger points are
    decode-step counts inside each child, scaled to the trace size so
    they land while requests are in flight)."""
    from deeperspeed_tpu.serving import FleetRouter, RouterConfig
    from deeperspeed_tpu.serving.fleet import build_subprocess_fleet

    rcfg = RouterConfig(
        num_replicas=3, max_queue_depth=256, retry_max=4,
        retry_backoff_base_s=0.02, retry_backoff_max_s=0.5,
        heartbeat_timeout_s=30.0, progress_timeout_s=3.0,
        replica_restart=True, replica_max_restarts=2,
        poll_interval_s=0.005)
    vocab = MODEL_SPEC["gpt"]["vocab_size"]
    # one-shot flag files: each fault fires once, so the RESTARTED
    # replica rejoins healthy instead of dying on schedule forever
    flags = tempfile.mkdtemp(prefix="fleet-drill-flags-")
    runs = {}
    for phase, faults in (
            ("healthy", None),
            ("fault", {1: {"replica_sigkill_at_decode": sigkill_at,
                           "flag_file": os.path.join(flags, "kill")},
                       2: {"replica_stall_at_decode": stall_at,
                           "flag_file": os.path.join(flags, "stall")}})):
        fleet = build_subprocess_fleet(3, MODEL_SPEC, faults=faults)
        router = FleetRouter(fleet, rcfg)
        rng = np.random.default_rng(0)   # same trace both phases
        arrivals, prompts, news, temps = make_trace(
            rng, n_requests, rate=25.0, vocab=vocab)
        t0 = time.monotonic()
        accepted, shed = run_poisson(router, arrivals, prompts, news,
                                     temps)
        wall = time.monotonic() - t0
        s = router.metrics.summary()
        outcomes = router.outcomes()
        lost = [r for r in accepted
                if outcomes.get(r) not in ("length", "eos")]
        runs[phase] = {
            "accepted": len(accepted), "shed": shed,
            "lost_accepted": lost,
            "outcomes": s["outcomes"],
            "retries": s["retries"],
            "replica_downs": s["replica_downs"],
            "p50_ttft_s": s["router_ttft_s"]["p50"],
            "p99_ttft_s": s["router_ttft_s"]["p99"],
            "p99_e2e_s": s["router_e2e_s"]["p99"],
            "wall_s": wall,
        }
        router.shutdown()
        print(f"[failover/{phase}] accepted={len(accepted)} shed={shed} "
              f"lost={len(lost)} retries={s['retries']} "
              f"downs={[d['cause'] for d in s['replica_downs']]} "
              f"p99_ttft={s['router_ttft_s']['p99'] * 1e3:.1f}ms "
              f"wall={wall:.1f}s", flush=True)
    causes = {d["cause"] for d in runs["fault"]["replica_downs"]}
    runs["pass"] = bool(
        not runs["healthy"]["lost_accepted"]
        and not runs["fault"]["lost_accepted"]
        and runs["fault"]["retries"] >= 1
        and "dead" in causes and "stalled" in causes)
    return runs


def drill_shed_curve(n_requests: int):
    """Offered-load sweep against a deliberately small fleet (2 thread
    replicas, queue cap 8): shed rate must rise with load instead of
    latency rising without bound."""
    import jax
    import jax.numpy as jnp

    from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
    from deeperspeed_tpu.serving import (FleetRouter, RouterConfig,
                                         ServingConfig, ServingEngine,
                                         build_thread_fleet)

    gpt = dict(MODEL_SPEC["gpt"])
    cfg = GPTConfig(dtype=jnp.float32, **gpt)
    init_fn, _, _, _ = make_gpt(cfg)
    params = init_fn(jax.random.PRNGKey(MODEL_SPEC["init_seed"]))
    scfg = ServingConfig.from_dict(MODEL_SPEC["serving"])

    def factory():
        eng = ServingEngine(cfg, params, scfg)
        eng.submit([1, 2, 3], max_new_tokens=2, request_id="_warm")
        eng.submit([4, 5, 6], max_new_tokens=2, temperature=0.5,
                   request_id="_warm2")   # sampled path compiles too
        eng.run()
        return eng

    rcfg = RouterConfig(num_replicas=2, max_queue_depth=8,
                        heartbeat_timeout_s=60.0,
                        progress_timeout_s=60.0,
                        poll_interval_s=0.002)
    points = []
    for rate in (5.0, 20.0, 80.0, 320.0):
        fleet = build_thread_fleet(2, factory)
        router = FleetRouter(fleet, rcfg)
        rng = np.random.default_rng(1)   # same requests, faster clock
        arrivals, prompts, news, temps = make_trace(
            rng, n_requests, rate=rate,
            vocab=MODEL_SPEC["gpt"]["vocab_size"])
        accepted, shed = run_poisson(router, arrivals, prompts, news,
                                     temps)
        offered = len(accepted) + shed
        rate_pt = {"offered_rate_rps": rate, "accepted": len(accepted),
                   "shed": shed,
                   "shed_rate": shed / offered if offered else 0.0}
        points.append(rate_pt)
        router.shutdown()
        print(f"[shed] rate={rate:g}/s accepted={len(accepted)} "
              f"shed={shed} shed_rate={rate_pt['shed_rate']:.2f}",
              flush=True)
    rates = [p["shed_rate"] for p in points]
    # monotone within noise, and the top load must actually shed
    ok = all(b >= a - 0.05 for a, b in zip(rates, rates[1:])) \
        and rates[-1] > 0.0
    return {"points": points, "pass": bool(ok)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "BENCH_fleet.json"))
    ap.add_argument("--trace", default=os.path.join(
        REPO, "traces", "fleet_drill_trace.json"))
    ap.add_argument("--quick", action="store_true",
                    help="smaller trace (CI wrapper)")
    args = ap.parse_args()

    from deeperspeed_tpu.monitor import init_monitor, shutdown_monitor
    from deeperspeed_tpu.monitor.validate import validate_file

    os.makedirs(os.path.dirname(args.trace), exist_ok=True)
    init_monitor({"trace_path": args.trace, "trace_enabled": True,
                  "watchdog": "warn"})

    n_fail = 12 if args.quick else 24
    n_shed = 12 if args.quick else 20
    sigkill_at = 15 if args.quick else 30
    stall_at = 25 if args.quick else 50
    t0 = time.time()
    failover = drill_failover(n_fail, sigkill_at, stall_at)
    shed = drill_shed_curve(n_shed)
    shutdown_monitor(save=True)
    problems = validate_file(args.trace)
    for p in problems:
        print(f"trace: {p}", file=sys.stderr)

    result = {
        "drill": "serving_fleet",
        "quick": bool(args.quick),
        "failover": failover,
        "shed_curve": shed,
        "trace_path": os.path.relpath(args.trace, REPO),
        "trace_valid": not problems,
        "wall_s": time.time() - t0,
        "pass": bool(failover["pass"] and shed["pass"]
                     and not problems),
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(f"wrote {args.out} pass={result['pass']}")
    if not result["pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
