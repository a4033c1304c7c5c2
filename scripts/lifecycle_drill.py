"""Lifecycle drill: zero-downtime train->serve under continuous load.

One tiny GPT trains under the supervisor while a two-replica subprocess
fleet serves an open-loop Poisson trace of the SAME model. The run
exercises the whole ``lifecycle/`` control plane end to end:

  * **two weight pushes** — interval autosaves commit checkpoint tags;
    the trainer's :class:`VersionPublisher` mints them as WeightVersion
    records in ``VERSIONS.json``; the drill's :class:`RolloutDriver`
    rolling-restarts the fleet onto each (drain -> stage weights ->
    restart, mixed-version routing in between).
  * **one pool shrink, handled LIVE** — the drill rewrites the pool
    file; the supervisor's watcher debounces it and sends ``SIGUSR1``
    to the RUNNING trainer; the ``RemeshHook`` flips the topology in
    process at a step boundary (``jax.device_put`` re-placement + the
    PR 7 reshard math for comm residuals, no checkpoint round trip, no
    re-exec).

Acceptance, audited from artifacts (not participant claims):

  * every live per-step loss is BIT-IDENTICAL to a kill-restart
    reference (train to the flip step at W1, exit, resume the
    checkpoint at W2) — the re-mesh is provably the restart path minus
    the restart;
  * ZERO lost accepted requests across both rollouts and the shrink;
  * the restart log shows ONE launch, one ``remesh`` transition and a
    clean exit — goodput's ``restart`` bucket is ~0 and the flip cost
    lands in the new ``remesh`` bucket instead;
  * both Chrome traces (trainer + serving) pass the strict validator.

Writes its report where ``--out`` says (default: the git-ignored
BENCH_lifecycle.json at the repo root).

Usage:
  python scripts/lifecycle_drill.py [--quick] [--out BENCH_lifecycle.json]
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SEQ_LEN = 32
GLOBAL_BATCH = 16
TOTAL_STEPS = 8
FLIP_AT = 4          # optimizer-step boundary where the topology flips
WORLD_FROM, WORLD_TO = 4, 2
SAVE_EVERY = 4       # -> committed tags (= weight versions) at steps 4, 8

# the trainer trains EXACTLY the model the fleet serves: same GPT
# kwargs, same init seed — that is what makes a published tag loadable
# by a serving replica
GPT = {"vocab_size": 97, "n_layer": 2, "n_head": 2, "d_model": 32,
       "max_seq": 256, "remat": False, "attn_impl": "xla"}
SERVE_SPEC = {
    "gpt": GPT,
    "init_seed": 0,
    "serving": {"num_slots": 4, "block_size": 8, "num_blocks": 128,
                "max_seq_len": 256, "max_new_tokens": 64,
                "prefill_buckets": [16, 256]},
    "warm": True,
}

# elasticity pins global batch 16 / micro 4 -> valid worlds {1, 2, 4}
# (gas 4/2/1); canonical_shards=4 fixes the reduction tree so the loss
# is bit-identical on every admissible topology. int8 + error feedback
# puts real residual state on the line for the re-mesh reshard.
DRILL_CONFIG = {
    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    "zero_optimization": {"stage": 0},
    "steps_per_print": 10000,
    "comm": {"mode": "int8", "bucket_mb": 0.01, "error_feedback": True},
    "elasticity": {
        "enabled": True,
        "max_train_batch_size": GLOBAL_BATCH,
        "micro_batch_sizes": [4],
        "min_gpus": 1,
        "max_gpus": 8,
        "version": 0.1,
        "canonical_shards": 4,
    },
    "checkpoint": {"sharded_io": False},
    "resilience": {
        "save_interval_steps": SAVE_EVERY,
        "async_save": False,
        "preemption_guard": False,
    },
    "lifecycle": {"enabled": True, "remesh_debounce_s": 0.0,
                  "keep_live_versions": 2},
    "monitor": {"trace_enabled": True, "watchdog": "warn"},
    "_gpt": GPT, "_seq": SEQ_LEN, "_gb": GLOBAL_BATCH,
}

_TRAINER = """\
import json, os, sys, time
ckpt_dir, steps_s, cfg_path, out_path = sys.argv[1:5]
W = int(os.environ.get("DS_TPU_WORLD_SIZE", "4"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={W}")
import numpy as np
import jax
import jax.numpy as jnp
import deeperspeed_tpu as deepspeed
from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
from deeperspeed_tpu.monitor import shutdown_monitor
from deeperspeed_tpu.resilience import shutdown_resilience

with open(cfg_path) as f:
    cfg = json.load(f)
gpt_kw = cfg.pop("_gpt")
SEQ, GB = int(cfg.pop("_seq")), int(cfg.pop("_gb"))
cfg["resilience"]["save_dir"] = ckpt_dir
cfg["monitor"]["trace_path"] = out_path + ".trace.json"
VOCAB = gpt_kw["vocab_size"]
FLIP_AT = int(os.environ.get("DRILL_FLIP_AT", "-1"))
FLIP_TO = int(os.environ.get("DRILL_FLIP_TO", "0"))

gptc = GPTConfig(dtype=jnp.float32, **gpt_kw)
init_fn, _, loss_fn, _ = make_gpt(gptc)
params = init_fn(jax.random.PRNGKey(0))
engine, _, _, _ = deepspeed.initialize(
    model=loss_fn, model_parameters=params, config=cfg)
engine.load_checkpoint(ckpt_dir)

def batch(i):
    rng = np.random.default_rng(100000 + i)
    return rng.integers(1, VOCAB, size=(GB, SEQ + 1)).astype(np.int32)

steps = int(steps_s)
out = open(out_path, "a")
while engine.global_steps < steps:
    i = engine.global_steps
    if i == FLIP_AT and FLIP_TO and engine.data_parallel_size != FLIP_TO:
        # hold this boundary until the supervisor's re-mesh signal
        # lands; polling applies the latched flip HERE, so the live
        # schedule matches the kill-restart reference step for step
        deadline = time.time() + 120.0
        while (engine.data_parallel_size != FLIP_TO
               and time.time() < deadline):
            engine._lifecycle.poll(engine)
            time.sleep(0.02)
        assert engine.data_parallel_size == FLIP_TO, \\
            "re-mesh signal never arrived"
    loss = engine.train_batch(batch(i))
    out.write(json.dumps({"step": i, "loss": "%.17e" % float(loss),
                          "world": engine.data_parallel_size}) + "\\n")
    out.flush()
    os.fsync(out.fileno())
lc = getattr(engine, "_lifecycle", None)
out.write(json.dumps({
    "event": "done",
    "world": engine.data_parallel_size,
    "remeshes": getattr(getattr(lc, "remesh", None), "remeshes", 0),
    "published": getattr(getattr(lc, "publisher", None),
                         "published", 0)}) + "\\n")
out.flush()
os.fsync(out.fileno())
out.close()
shutdown_resilience()
shutdown_monitor(save=True)
"""


def _write_atomic(path: str, text: str) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(text)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def parse_losses(path):
    """The trainer's JSONL stream -> ({step: loss_repr}, {step: world},
    done record or None). Tolerates a torn trailing line."""
    losses, worlds, done = {}, {}, None
    try:
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if "step" in rec:
                    losses[int(rec["step"])] = rec["loss"]
                    worlds[int(rec["step"])] = int(rec["world"])
                elif rec.get("event") == "done":
                    done = rec
    except OSError:
        pass
    return losses, worlds, done


def _progress(path) -> int:
    losses, _, _ = parse_losses(path)
    return max(losses) if losses else -1


def _base_env():
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    return env


def run_reference(work: str, cfg_path: str):
    """The kill-restart baseline on the SAME schedule as the live run:
    train to the flip boundary at W1, exit cleanly, relaunch at W2 and
    resume from the committed tag. Returns ({step: loss}, {step: world})
    stitched across both incarnations."""
    ckpt = os.path.join(work, "ckpt_ref")
    losses, worlds = {}, {}
    for phase, world, steps in (("save", WORLD_FROM, FLIP_AT),
                                ("resume", WORLD_TO, TOTAL_STEPS)):
        out = os.path.join(work, f"ref_{phase}.jsonl")
        env = dict(_base_env(), DS_TPU_WORLD_SIZE=str(world),
                   JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(work, "trainer.py"),
             ckpt, str(steps), cfg_path, out],
            env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, (
            f"reference phase {phase} failed:\n{proc.stdout}\n"
            f"{proc.stderr[-3000:]}")
        ls, ws, done = parse_losses(out)
        assert done is not None, f"reference phase {phase} never finished"
        losses.update(ls)
        worlds.update(ws)
        print(f"[ref/{phase}] world={world} steps={sorted(ls)}",
              flush=True)
    assert sorted(losses) == list(range(TOTAL_STEPS)), sorted(losses)
    return losses, worlds


def run_live(work: str, cfg_path: str, n_max: int, rate: float,
             timeout_s: float):
    """The tentpole: supervised trainer (pool watch + live re-mesh) and
    the serving fleet (Poisson load + version rollouts), concurrently."""
    from deeperspeed_tpu.lifecycle import (LifecycleConfig, RolloutDriver,
                                           VersionRegistry)
    from deeperspeed_tpu.resilience import Supervisor, SupervisorPolicy
    from deeperspeed_tpu.serving import (FleetRouter, RouterConfig,
                                         ShedError)
    from deeperspeed_tpu.serving.fleet import build_subprocess_fleet

    ckpt = os.path.join(work, "ckpt_live")
    pool_file = os.path.join(work, "pool")
    restart_log = os.path.join(work, "restarts.jsonl")
    losses_out = os.path.join(work, "live.jsonl")
    _write_atomic(pool_file, f"{WORLD_FROM}\n")

    # fleet first (sequential cold starts), then the trainer alongside
    fleet = build_subprocess_fleet(2, SERVE_SPEC)
    rcfg = RouterConfig(
        num_replicas=2, max_queue_depth=512, retry_max=4,
        retry_backoff_base_s=0.02, retry_backoff_max_s=0.5,
        heartbeat_timeout_s=60.0, progress_timeout_s=60.0,
        replica_restart=True, replica_max_restarts=4,
        poll_interval_s=0.005)
    router = FleetRouter(fleet, rcfg)
    registry = VersionRegistry(ckpt)
    rollout = RolloutDriver(router, registry,
                            LifecycleConfig(drain_timeout_s=60.0))

    sup = Supervisor(
        [sys.executable, os.path.join(work, "trainer.py"),
         ckpt, str(TOTAL_STEPS), cfg_path, losses_out],
        SupervisorPolicy(
            max_restarts=2, backoff_base=0.1, backoff_max=0.5,
            checkpoint_dir=ckpt, elastic_config=cfg_path,
            pool_file=pool_file, watch_pool=True,
            pool_poll_interval_s=0.05, pool_debounce_s=0.15,
            restart_log=restart_log, simulate_cpu_devices=True))
    # the supervisor builds the child env from os.environ
    os.environ.update(_base_env())
    os.environ["DRILL_FLIP_AT"] = str(FLIP_AT)
    os.environ["DRILL_FLIP_TO"] = str(WORLD_TO)
    holder = {}

    def _sup_run():
        holder["rc"] = sup.run()

    sup_thread = threading.Thread(target=_sup_run, daemon=True)
    sup_thread.start()

    # open-loop Poisson load for the WHOLE run: requests are in flight
    # across both rollouts and the shrink, so drains and mixed-version
    # routing are exercised for real
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_max))
    prompts = [rng.integers(1, GPT["vocab_size"], p).tolist()
               for p in rng.integers(6, 13, n_max)]
    news = rng.integers(12, 33, n_max)
    temps = np.where(rng.random(n_max) < 0.5, 0.0, 0.7)

    accepted, shed = [], 0
    pool_written = False
    t0 = time.monotonic()
    i = 0
    while True:
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
        if not pool_written and _progress(losses_out) >= FLIP_AT - 1:
            # the boundary before the flip has completed (and with it
            # the save + publish); shrink the pool NOW — the supervisor
            # watcher signals the running trainer, no restart
            _write_atomic(pool_file, f"{WORLD_TO}\n")
            pool_written = True
            print(f"[live] pool {WORLD_FROM} -> {WORLD_TO} "
                  f"(file rewrite, t={now:.1f}s)", flush=True)
        rollout.poll_once()
        trained = not sup_thread.is_alive()
        if trained and rollout.rollouts >= 2 and i >= len(prompts):
            break
        if now > timeout_s:
            print(f"[live] TIMEOUT after {now:.0f}s (trained={trained} "
                  f"rollouts={rollout.rollouts})", file=sys.stderr,
                  flush=True)
            break
        time.sleep(0.005)
    sup_thread.join(timeout=30.0)
    outcomes = router.run_until_idle(timeout_s=300.0)
    lost = [r for r in accepted
            if outcomes.get(r) not in ("length", "eos")]
    versions = {}
    for rid in accepted:
        try:
            v = getattr(router.result(rid), "version", None)
        except KeyError:
            v = None
        versions[str(v)] = versions.get(str(v), 0) + 1
    summary = router.metrics.summary()
    router.shutdown()

    losses, worlds, done = parse_losses(losses_out)
    return {
        "sup": sup, "rc": holder.get("rc"),
        "losses": losses, "worlds": worlds, "done": done,
        "restart_log": restart_log,
        "trainer_trace": losses_out + ".trace.json",
        "accepted": len(accepted), "shed": shed, "lost": lost,
        "versions_served": versions,
        "rollouts": rollout.rollouts, "applied": rollout.applied,
        "registry": [vars(v) for v in registry.list()],
        "p99_ttft_s": summary["router_ttft_s"]["p99"],
        "p99_e2e_s": summary["router_e2e_s"]["p99"],
    }


def audit(ref_losses, live) -> dict:
    """Everything the drill promises, checked from artifacts."""
    from deeperspeed_tpu.monitor.goodput import compute_goodput

    losses, worlds = live["losses"], live["worlds"]
    covered = sorted(losses) == list(range(TOTAL_STEPS))
    max_delta, mismatches = 0.0, []
    for s, loss in losses.items():
        want = ref_losses.get(s)
        if want is None:
            continue
        d = abs(float(loss) - float(want))
        max_delta = max(max_delta, d)
        if loss != want:
            mismatches.append({"step": s, "live": loss, "ref": want})
    worlds_ok = all(
        worlds.get(s) == (WORLD_FROM if s < FLIP_AT else WORLD_TO)
        for s in range(TOTAL_STEPS))

    recs = []
    try:
        with open(live["restart_log"]) as f:
            recs = [json.loads(x) for x in f if x.strip()]
    except OSError:
        pass
    launches = [r for r in recs if r.get("event") == "launch"]
    remesh_events = [r for r in recs if r.get("event") == "remesh"]
    clean_exit = any(r.get("event") == "exit" and r.get("code") == 0
                     for r in recs)

    gp = compute_goodput(live["restart_log"], [live["trainer_trace"]],
                         emit_trace=False)
    stall_s = 0.0
    try:
        with open(live["trainer_trace"]) as f:
            doc = json.load(f)
        for ev in doc.get("traceEvents", doc if isinstance(doc, list)
                          else []):
            if (isinstance(ev, dict)
                    and ev.get("name") == "lifecycle/remesh"
                    and ev.get("ph") == "X"):
                stall_s += float(ev.get("dur", 0)) / 1e6
    except (OSError, ValueError):
        pass

    done = live["done"] or {}
    return {
        "remesh": {
            "max_loss_delta": max_delta,
            "loss_steps_covered": covered,
            "loss_mismatches": mismatches[:10],
            "worlds_ok": worlds_ok,
            "flip_step": FLIP_AT,
            "world_from": WORLD_FROM,
            "world_to": WORLD_TO,
            "remeshes": done.get("remeshes", 0),
            "signals_sent": live["sup"].remesh_signals,
            "stall_s": round(stall_s, 6),
        },
        "serving": {
            "lost_accepted": len(live["lost"]),
            "lost_rids": live["lost"][:10],
            "accepted": live["accepted"],
            "shed": live["shed"],
            "versions_served": live["versions_served"],
            "p99_ttft_s": live["p99_ttft_s"],
            "p99_e2e_s": live["p99_e2e_s"],
        },
        "weight_pushes": live["rollouts"],
        "versions": live["registry"],
        "goodput": {
            "restart_s": gp["buckets"]["restart"],
            "remesh_s": gp["buckets"]["remesh"],
            "fraction": gp["goodput"],
            "wall_s": gp["wall_s"],
        },
        "supervisor": {
            "rc": live["rc"],
            "launches": len(launches),
            "remesh_transitions": len(remesh_events),
            "clean_exit": clean_exit,
            "restarts": live["sup"].restarts,
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out",
                    default=os.path.join(REPO, "BENCH_lifecycle.json"))
    ap.add_argument("--trace", default=os.path.join(
        REPO, "traces", "lifecycle_drill_trace.json"))
    ap.add_argument("--trainer-trace", default=os.path.join(
        REPO, "traces", "lifecycle_trainer_trace.json"))
    ap.add_argument("--quick", action="store_true",
                    help="lighter request load (CI wrapper)")
    args = ap.parse_args()

    from deeperspeed_tpu.monitor import init_monitor, shutdown_monitor
    from deeperspeed_tpu.monitor.validate import validate_file

    os.makedirs(os.path.dirname(args.trace), exist_ok=True)
    init_monitor({"trace_path": args.trace, "trace_enabled": True,
                  "watchdog": "warn"})

    n_max = 120 if args.quick else 240
    rate = 4.0 if args.quick else 6.0
    timeout_s = 420.0 if args.quick else 540.0

    work = tempfile.mkdtemp(prefix="lifecycle_drill_")
    cfg_path = os.path.join(work, "ds_config.json")
    with open(os.path.join(work, "trainer.py"), "w") as f:
        f.write(_TRAINER)
    with open(cfg_path, "w") as f:
        json.dump(DRILL_CONFIG, f, indent=1)

    t0 = time.time()
    try:
        ref_losses, _ = run_reference(work, cfg_path)
        live = run_live(work, cfg_path, n_max, rate, timeout_s)
        report = audit(ref_losses, live)
        shutil.copy(live["trainer_trace"], args.trainer_trace)
    finally:
        shutdown_monitor(save=True)
        shutil.rmtree(work, ignore_errors=True)

    problems = []
    for path in (args.trace, args.trainer_trace):
        for p in validate_file(path, strict=True):
            problems.append(f"{os.path.basename(path)}: {p}")
    for p in problems:
        print(f"trace: {p}", file=sys.stderr)

    r, s, g, sv = (report["remesh"], report["serving"],
                   report["goodput"], report["supervisor"])
    ok = bool(
        r["max_loss_delta"] == 0.0 and r["loss_steps_covered"]
        and not r["loss_mismatches"] and r["worlds_ok"]
        and r["remeshes"] == 1 and r["stall_s"] < 5.0
        and s["lost_accepted"] == 0
        and report["weight_pushes"] >= 2
        and g["restart_s"] < 0.5 and g["remesh_s"] > 0.0
        and sv["rc"] == 0 and sv["launches"] == 1
        and sv["remesh_transitions"] == 1 and sv["clean_exit"]
        and sv["restarts"] == 0
        and not problems)
    result = dict(report)
    result.update({
        "drill": "lifecycle",
        "quick": bool(args.quick),
        "trace_valid": not problems,
        "trace_problems": problems[:10],
        "wall_s": round(time.time() - t0, 1),
        "pass": ok,
    })
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(f"[lifecycle] pushes={report['weight_pushes']} "
          f"remeshes={r['remeshes']} stall={r['stall_s'] * 1e3:.1f}ms "
          f"max_loss_delta={r['max_loss_delta']:.3e} "
          f"lost={s['lost_accepted']} restart_s={g['restart_s']:.3f} "
          f"remesh_s={g['remesh_s']:.3f}", flush=True)
    print(f"wrote {args.out} pass={result['pass']}")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
