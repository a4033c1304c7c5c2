"""Observability drill: flight recovery, trace merge, goodput ledger.

Two phases exercise the run-scoped observability stack end to end and
audit the ISSUE's acceptance criteria:

**Phase 1 — fleet kill + cross-process merge.** Three subprocess
replicas (each with a flight recorder via the spec's ``monitor`` block)
serve a request trace through the FleetRouter while the drill's own
monitor traces the router lane. Mid-trace, fault injection SIGKILLs
replica 1; the router retries its in-flight work elsewhere and restarts
it. Afterwards the drill merges the router trace, the surviving
replicas' traces, and the KILLED replica's ``flight.bin`` into one
timeline and audits:

  * >= 1 event recovered from the SIGKILLed replica's flight file is
    present in the merged trace (including its ``serving/admit``
    instants — the proof the kill didn't erase the replica's story);
  * 100% of accepted rids are traceable ``serving/dispatch`` (router)
    -> ``serving/admit`` (replica) -> terminal ``serving/finish``;
  * the merged trace passes the schema validator in **strict** mode.

**Phase 2 — supervised trainer + goodput ledger.** A supervisor runs a
tiny trainer (checkpointing every 2 steps, datapipe input, a
``monitor`` block pointing at the shared obs dir); fault injection
SIGKILLs it mid-run, the supervisor relaunches it, and it resumes from
the newest checkpoint. The goodput ledger then classifies the measured
wall-clock from the restart log plus the per-incarnation traces (the
killed incarnation contributes its flight file) and the drill audits
that the buckets sum to the independently measured wall time within 5%.

Writes its report where ``--out`` says (default: the git-ignored
BENCH_obs.json).

Usage:
  python scripts/obs_drill.py [--quick] [--out BENCH_obs.json]
"""

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

TERMINAL_OK = ("length", "eos")

MODEL_SPEC = {
    "gpt": {"vocab_size": 97, "n_layer": 2, "n_head": 2, "d_model": 32,
            "max_seq": 256, "remat": False, "attn_impl": "xla"},
    "init_seed": 0,
    "serving": {"num_slots": 4, "block_size": 8, "num_blocks": 128,
                "max_seq_len": 256, "max_new_tokens": 64,
                "prefill_buckets": [16, 256]},
    "warm": True,
}


def _pick_sources(obs_dir: str):
    """Per (role, incarnation) stem: the saved trace when the process
    exited cleanly, its flight.bin when it was killed (crash path)."""
    stems = {}
    for p in sorted(glob.glob(os.path.join(obs_dir, "*.trace.json"))):
        stems[p[: -len(".trace.json")]] = p
    for p in sorted(glob.glob(os.path.join(obs_dir, "*.flight.bin"))):
        stems.setdefault(p[: -len(".flight.bin")], p)
    return [stems[s] for s in sorted(stems)]


# --------------------------------------------------------------------- #
# phase 1: fleet kill + merge
# --------------------------------------------------------------------- #


def drill_fleet_merge(work: str, n_requests: int, sigkill_at: int):
    from deeperspeed_tpu.monitor import (init_monitor, shutdown_monitor,
                                         trace_instant)
    from deeperspeed_tpu.monitor.aggregate import merge_files
    from deeperspeed_tpu.monitor.runctx import ROLE_ENV, ensure_run_id
    from deeperspeed_tpu.monitor.validate import validate_events
    from deeperspeed_tpu.serving import FleetRouter, RouterConfig
    from deeperspeed_tpu.serving.fleet import build_subprocess_fleet

    obs = os.path.join(work, "obs_fleet")
    run_id = ensure_run_id()
    os.environ[ROLE_ENV] = "router"
    init_monitor({"obs_dir": obs, "watchdog": "warn"})

    spec = dict(MODEL_SPEC)
    spec["monitor"] = {"obs_dir": obs, "watchdog": "off"}
    faults = {1: {"replica_sigkill_at_decode": sigkill_at,
                  "flag_file": os.path.join(work, "kill-flag")}}
    rcfg = RouterConfig(
        num_replicas=3, max_queue_depth=256, retry_max=4,
        retry_backoff_base_s=0.02, retry_backoff_max_s=0.5,
        heartbeat_timeout_s=30.0, progress_timeout_s=3.0,
        replica_restart=True, replica_max_restarts=2,
        poll_interval_s=0.005)
    fleet = build_subprocess_fleet(3, spec, faults=faults)
    router = FleetRouter(fleet, rcfg)

    rng = np.random.default_rng(0)
    vocab = MODEL_SPEC["gpt"]["vocab_size"]
    accepted = []
    t0 = time.monotonic()
    for i in range(n_requests):
        plen = int(rng.integers(6, 13))
        rid = router.submit(rng.integers(1, vocab, plen).tolist(),
                            max_new_tokens=int(rng.integers(24, 49)),
                            temperature=0.0 if i % 2 else 0.7,
                            request_id=f"t{i}")
        accepted.append(rid)
        for _ in range(3):
            router.step()
            time.sleep(rcfg.poll_interval_s)
    router.run_until_idle(timeout_s=300.0)
    wall = time.monotonic() - t0
    outcomes = router.outcomes()
    retries = router.metrics.summary()["retries"]
    # per-replica handshake offsets, applied to every file of that
    # replica (one host per replica in real fleets)
    offsets = {}
    for rep in fleet:
        if rep.clock_offset_s is None:
            continue
        for inc in range(rep.restarts + 1):
            for ext in ("trace.json", "flight.bin"):
                offsets[f"replica-{rep.name}.i{inc}.{ext}"] = \
                    rep.clock_offset_s
    trace_instant("goodput/report", lane="run", wall_s=round(wall, 3),
                  goodput=0.0)   # router lane: wall accounting marker
    router.shutdown()
    time.sleep(0.2)              # replicas flush their traces on stop
    shutdown_monitor(save=True)

    sources = _pick_sources(obs)
    merged_path = os.path.join(REPO, "traces", "obs_drill_merged.json")
    doc, stats = merge_files(sources, out=merged_path, offsets_s=offsets)

    flight_pids = {i + 1 for i, s in enumerate(stats["sources"])
                   if s["kind"] == "flight"}
    dispatched, admitted, finished = set(), set(), set()
    flight_admits = set()
    for ev in doc["traceEvents"]:
        rid = (ev.get("args") or {}).get("rid")
        name = ev.get("name")
        if rid is None or rid not in set(accepted):
            continue
        if name == "serving/dispatch":
            dispatched.add(rid)
        elif name == "serving/admit":
            admitted.add(rid)
            if ev.get("pid") in flight_pids:
                flight_admits.add(rid)
        elif name == "serving/finish":
            if (ev.get("args") or {}).get("reason") in TERMINAL_OK:
                finished.add(rid)
    acc = set(accepted)
    traceable = dispatched & admitted & finished & acc
    problems = validate_events(doc["traceEvents"], strict=True)
    for p in problems[:20]:
        print(f"merged strict: {p}", file=sys.stderr)

    result = {
        "run_id": run_id,
        "accepted": len(accepted),
        "outcomes_ok": sum(1 for r in accepted
                           if outcomes.get(r) in TERMINAL_OK),
        "retries": retries,
        "merged_events": stats["events"],
        "recovered_events": stats["recovered_events"],
        "flight_sources": len(flight_pids),
        "flow_arrows": stats["flow_arrows"],
        "rids_traceable": len(traceable),
        "rids_admitted_via_flight": sorted(flight_admits),
        "strict_problems": len(problems),
        "merged_trace": os.path.relpath(merged_path, REPO),
        "sources": [s["label"] for s in stats["sources"]],
        "wall_s": round(wall, 2),
        "pass": bool(stats["recovered_events"] >= 1
                     and len(flight_pids) >= 1
                     and traceable == acc
                     and retries >= 1
                     and not problems),
    }
    print(f"[fleet] accepted={len(accepted)} traceable={len(traceable)} "
          f"recovered={stats['recovered_events']} "
          f"flows={stats['flow_arrows']} retries={retries} "
          f"strict_problems={len(problems)} pass={result['pass']}",
          flush=True)
    return result


# --------------------------------------------------------------------- #
# phase 2: supervised trainer + goodput ledger
# --------------------------------------------------------------------- #

SEQ_LEN = 16

TRAIN_CONFIG = {
    "train_batch_size": 32,
    "train_micro_batch_size_per_gpu": 4,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    "zero_optimization": {"stage": 0},
    "steps_per_print": 10000,
    "datapipe": {
        "enabled": True,
        "seq_len": SEQ_LEN,
        "seed": 7,
        "shuffle": True,
        "prefetch": False,
        "stage_to_device": False,
    },
    "checkpoint": {"sharded_io": True},
    "resilience": {
        "save_interval_steps": 2,
        "async_save": False,
        "preemption_guard": False,
    },
    # obs_dir is filled in by the drill; every incarnation derives its
    # own trace/flight paths from DS_TPU_ROLE/DS_TPU_INCARNATION
    "monitor": {"watchdog": "warn"},
}

_TRAINER = """\
import os, sys, time
ckpt_dir, steps, data_src, cfg_path = sys.argv[1:5]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax.numpy as jnp
import deeperspeed_tpu as deepspeed
from deeperspeed_tpu.resilience import shutdown_resilience
from deeperspeed_tpu.monitor import shutdown_monitor

with open(cfg_path) as f:
    cfg = json.load(f)
cfg["resilience"]["save_dir"] = ckpt_dir
cfg["datapipe"]["source"] = data_src
SEQ = cfg["datapipe"]["seq_len"]

def loss_fn(p, b):
    t = b.astype(jnp.float32) / 997.0
    x, y = t[:, :-1], t[:, 1:]
    return jnp.mean((x @ p["w"] - y) ** 2)

params = {"w": jnp.eye(SEQ, dtype=jnp.float32) * 0.5}
engine, _, _, _ = deepspeed.initialize(
    model=loss_fn, model_parameters=params, config=cfg)
engine.load_checkpoint(ckpt_dir)
steps = int(steps)
while engine.global_steps < steps:
    i = engine.global_steps
    loss = engine.train_batch()
    print(f"STEP {i} LOSS {float(loss):.9e}", flush=True)
shutdown_resilience()
shutdown_monitor(save=True)
"""


def drill_goodput(work: str, steps: int, kill_at: int):
    from deeperspeed_tpu.monitor.goodput import compute_goodput
    from deeperspeed_tpu.resilience import (FAULTS_ENV_VAR, Supervisor,
                                            SupervisorPolicy)

    obs = os.path.join(work, "obs_train")
    script = os.path.join(work, "trainer.py")
    cfg_path = os.path.join(work, "ds_config.json")
    data = os.path.join(work, "corpus.npy")
    ckpt = os.path.join(work, "ckpt")
    restart_log = os.path.join(work, "restarts.jsonl")
    cfg = json.loads(json.dumps(TRAIN_CONFIG))
    cfg["monitor"]["obs_dir"] = obs
    with open(script, "w") as f:
        f.write(_TRAINER)
    with open(cfg_path, "w") as f:
        json.dump(cfg, f, indent=1)
    rs = np.random.RandomState(1234)
    np.save(data, rs.randint(0, 997, size=40000).astype(np.int32))

    base_env = dict(os.environ,
                    PYTHONPATH=REPO + os.pathsep
                    + os.environ.get("PYTHONPATH", ""))
    base_env.pop("XLA_FLAGS", None)
    base_env[FAULTS_ENV_VAR] = json.dumps({
        "sigkill_at_step": kill_at,
        "flag_file": os.path.join(work, "train-kill-flag")})

    def run_child(cmd, env):
        merged = dict(base_env)
        merged.update({k: v for k, v in env.items()
                       if k.startswith("DS_TPU_")})
        proc = subprocess.run(cmd, env=merged, capture_output=True,
                              text=True, timeout=600)
        if proc.returncode not in (0, -9):
            sys.stderr.write(proc.stderr[-3000:] + "\n")
        return (proc.returncode if proc.returncode >= 0
                else 128 - proc.returncode)

    sup = Supervisor(
        [sys.executable, script, ckpt, str(steps), data, cfg_path],
        SupervisorPolicy(max_restarts=3, backoff_base=0.1,
                         backoff_max=0.5, checkpoint_dir=ckpt,
                         restart_log=restart_log),
        run_fn=run_child)
    t0 = time.time()
    rc = sup.run()
    wall = time.time() - t0

    traces = _pick_sources(obs)
    report = compute_goodput(restart_log, traces, wall_s=wall,
                             emit_trace=False)
    err = abs(report["accounted_s"] - wall) / wall if wall else 1.0
    flight_incarnations = sum(1 for t in traces
                              if t.endswith(".flight.bin"))
    result = {
        "supervisor_rc": rc,
        "restarts": sup.restarts,
        "steps": steps,
        "kill_at_step": kill_at,
        "traces": [os.path.basename(t) for t in traces],
        "flight_incarnations": flight_incarnations,
        "measured_wall_s": round(wall, 3),
        "goodput": report["goodput"],
        "buckets": report["buckets"],
        "accounting_error": round(err, 4),
        "pass": bool(rc == 0 and sup.restarts == 1
                     and err <= 0.05
                     and report["buckets"]["productive"] > 0
                     and flight_incarnations >= 1),
    }
    print(f"[goodput] rc={rc} restarts={sup.restarts} "
          f"goodput={report['goodput']:.3f} err={err:.4f} "
          f"buckets={ {k: round(v, 2) for k, v in report['buckets'].items()} } "
          f"pass={result['pass']}", flush=True)
    return result


# --------------------------------------------------------------------- #


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_obs.json"))
    ap.add_argument("--quick", action="store_true",
                    help="smaller trace / fewer steps (CI wrapper)")
    ap.add_argument("--keep", action="store_true",
                    help="keep the drill workdir (for post-mortems)")
    args = ap.parse_args()

    work = tempfile.mkdtemp(prefix="obs_drill_")
    n_requests = 8 if args.quick else 12
    sigkill_at = 12 if args.quick else 20
    steps = 10 if args.quick else 14
    kill_at = 5 if args.quick else 7
    t0 = time.time()
    try:
        fleet = drill_fleet_merge(work, n_requests, sigkill_at)
        goodput = drill_goodput(work, steps, kill_at)
    finally:
        if args.keep:
            print(f"workdir kept at {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)

    result = {
        "drill": "observability",
        "quick": bool(args.quick),
        "fleet_merge": fleet,
        "goodput": goodput,
        "wall_s": round(time.time() - t0, 1),
        "pass": bool(fleet["pass"] and goodput["pass"]),
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(f"wrote {args.out} pass={result['pass']}")
    if not result["pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
