"""Multi-host drill: a 2-process localhost fleet, killed, healed, grown.

One tiny GPT trains under the :class:`FleetSupervisor` as a REAL
``jax.distributed`` fleet — two localhost processes with two simulated
CPU devices each, rendezvousing through the gloo coordinator exactly
like two TPU hosts would. The run exercises the whole ``distributed/``
subsystem end to end:

  * **bit-identical multi-host math** — every per-step loss of the
    fleet (across every incarnation) must equal, byte for byte, a
    single-process 4-device reference run of the same schedule. The
    canonical-slot reduction (``elasticity.canonical_shards``) plus the
    layout-invariant ``exact_slot_mean`` make the loss independent of
    both the device->process mapping AND the world size.
  * **one host SIGKILLed mid-run** — the supervisor's coordinated
    restart barrier tears down the survivor, backs off, and relaunches
    the fleet; it resumes from the last committed tag and recomputes
    the same losses.
  * **cross-host pool growth, 2 -> 3 processes** — the drill rewrites
    the pool file; the supervisor performs a planned re-mesh (coherent
    stop + relaunch at the new process count, ZERO crash-restarts);
    the world-6 fleet resumes the world-4 checkpoint and its losses
    still match the reference (the elastic cross-world guarantee).
  * **observability survives all of it** — per-host, per-epoch trace
    files merge (clock offsets from the rendezvous handshake) into ONE
    strict-validator-clean timeline.

Writes its report where ``--out`` says (default: the git-ignored
BENCH_multihost.json at the repo root).

Usage:
  python scripts/multihost_drill.py [--quick] [--out BENCH_multihost.json]
"""

import argparse
import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SEQ_LEN = 32
GLOBAL_BATCH = 24
TOTAL_STEPS = 9
SAVE_EVERY = 3             # committed tags at global_steps 3, 6, 9
PROCS_FROM, PROCS_TO = 2, 3
LOCAL_DEVICES = 2          # world 4 -> world 6 across the growth
KILL_AFTER_STEP = 4        # epoch-0 progress that triggers the SIGKILL
GROW_AFTER_STEP = 5        # epoch-1 progress that triggers the pool write

GPT = {"vocab_size": 97, "n_layer": 2, "n_head": 2, "d_model": 32,
       "max_seq": 256, "remat": False, "attn_impl": "xla"}

# micro 2 / global 24 admits worlds {2, 4, 6, 12}; canonical_shards=12
# fixes the reduction tree (12 slots of 2 rows) so the loss is
# bit-identical on every admissible topology AND every device->process
# mapping. int8 + error feedback puts real residual state on the line
# for the crash resume and the cross-world growth resume.
DRILL_CONFIG = {
    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    "zero_optimization": {"stage": 0},
    "steps_per_print": 10000,
    "comm": {"mode": "int8", "bucket_mb": 0.01, "error_feedback": True,
             "hierarchical": "off"},
    "elasticity": {
        "enabled": True,
        "max_train_batch_size": GLOBAL_BATCH,
        "micro_batch_sizes": [2],
        "min_gpus": 1,
        "max_gpus": 12,
        "version": 0.1,
        "canonical_shards": 12,
    },
    "checkpoint": {"sharded_io": False},
    "resilience": {
        "save_interval_steps": SAVE_EVERY,
        "async_save": False,
        "preemption_guard": False,
    },
    "monitor": {"trace_enabled": True, "watchdog": "warn"},
    "_gpt": GPT, "_seq": SEQ_LEN, "_gb": GLOBAL_BATCH,
}

_TRAINER = """\
import json, os, signal, sys, time
ckpt_dir, steps_s, cfg_path, out_dir = sys.argv[1:5]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from deeperspeed_tpu.distributed import bootstrap as bs
topo = bs.bootstrap()  # env-discovered under the fleet; 1-proc for ref

import numpy as np
import jax
import jax.numpy as jnp
import deeperspeed_tpu as deepspeed
from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
from deeperspeed_tpu.monitor import shutdown_monitor
from deeperspeed_tpu.parallel import build_mesh
from deeperspeed_tpu.resilience import shutdown_resilience

pid, nproc = topo.process_id, topo.process_count
epoch = int(os.environ.get("DS_TPU_FLEET_EPOCH", "0"))
role = os.environ.get("DS_TPU_ROLE", f"trainer.h{pid}")
SLEEP = float(os.environ.get("DRILL_STEP_SLEEP", "0"))

with open(cfg_path) as f:
    cfg = json.load(f)
gpt_kw = cfg.pop("_gpt")
SEQ, GB = int(cfg.pop("_seq")), int(cfg.pop("_gb"))
cfg["resilience"]["save_dir"] = ckpt_dir
# per-host, per-epoch obs lane: a SIGKILLed incarnation must not
# clobber the trace of the one that replaces it
cfg["monitor"]["trace_path"] = os.path.join(
    out_dir, "obs", f"{role}.e{epoch}.trace.json")
VOCAB = gpt_kw["vocab_size"]

gptc = GPTConfig(dtype=jnp.float32, **gpt_kw)
init_fn, _, loss_fn, _ = make_gpt(gptc)
params = init_fn(jax.random.PRNGKey(0))
engine, _, _, _ = deepspeed.initialize(
    model=loss_fn, model_parameters=params, config=cfg,
    mesh=build_mesh({"data": jax.device_count()}))
engine.load_checkpoint(ckpt_dir)

# the supervisor's coherent stop is SIGTERM-first: exit through the
# finally block so this incarnation's trace reaches the obs dir
signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))

rows = GB // nproc

def batch(i):
    rng = np.random.default_rng(100000 + i)
    gb = rng.integers(1, VOCAB, size=(GB, SEQ + 1)).astype(np.int32)
    # multi-host data contract (sharding.place_batch): each process
    # feeds its own contiguous slice of the global batch, process order
    return gb[pid * rows:(pid + 1) * rows]

steps = int(steps_s)
out = open(os.path.join(out_dir, f"losses_h{pid}.jsonl"), "a")
try:
    while engine.global_steps < steps:
        i = engine.global_steps
        loss = engine.train_batch(batch(i))
        out.write(json.dumps({
            "step": i, "loss": "%.17e" % float(jax.device_get(loss)),
            "world": int(engine.data_parallel_size), "epoch": epoch,
            "host": pid, "wall": time.time()}) + "\\n")
        out.flush()
        os.fsync(out.fileno())
        if SLEEP:
            time.sleep(SLEEP)
    out.write(json.dumps({"event": "done", "host": pid, "epoch": epoch,
                          "world": int(engine.data_parallel_size)})
              + "\\n")
    out.flush()
    os.fsync(out.fileno())
finally:
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


def parse_lines(out_dir):
    """All loss records across every host's JSONL stream, plus done
    events. Tolerates torn trailing lines from killed incarnations."""
    recs, dones = [], []
    for path in sorted(glob.glob(os.path.join(out_dir, "losses_h*.jsonl"))):
        try:
            with open(path) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if "step" in rec:
                        recs.append(rec)
                    elif rec.get("event") == "done":
                        dones.append(rec)
        except OSError:
            pass
    return recs, dones


def _base_env():
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    for k in ("DS_COORDINATOR_ADDRESS", "DS_NUM_PROCESSES",
              "DS_PROCESS_ID"):
        env.pop(k, None)
    return env


def run_reference(work: str, cfg_path: str):
    """Single process x 4 devices, 9 straight steps, no restarts: the
    timeline every fleet incarnation must reproduce byte for byte."""
    ref_dir = os.path.join(work, "ref")
    os.makedirs(os.path.join(ref_dir, "obs"), exist_ok=True)
    env = dict(_base_env(), JAX_PLATFORMS="cpu",
               DS_TPU_WORLD_SIZE=str(PROCS_FROM * LOCAL_DEVICES),
               XLA_FLAGS="--xla_force_host_platform_device_count="
               f"{PROCS_FROM * LOCAL_DEVICES}")
    proc = subprocess.run(
        [sys.executable, os.path.join(work, "trainer.py"),
         os.path.join(ref_dir, "ckpt"), str(TOTAL_STEPS), cfg_path,
         ref_dir],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        f"reference run failed:\n{proc.stdout}\n{proc.stderr[-4000:]}")
    recs, dones = parse_lines(ref_dir)
    losses = {r["step"]: r["loss"] for r in recs}
    assert sorted(losses) == list(range(TOTAL_STEPS)), sorted(losses)
    assert dones, "reference never finished"
    print(f"[ref] world={PROCS_FROM * LOCAL_DEVICES} "
          f"steps={sorted(losses)}", flush=True)
    return losses


def run_live(work: str, cfg_path: str, step_sleep: float,
             timeout_s: float):
    """The tentpole: a supervised 2-process fleet, one host SIGKILLed,
    then grown to 3 processes through the pool file."""
    from deeperspeed_tpu.distributed import rendezvous
    from deeperspeed_tpu.distributed.fleet import FleetPolicy, FleetSupervisor

    live = os.path.join(work, "live")
    obs = os.path.join(live, "obs")
    ckpt = os.path.join(live, "ckpt")
    rdzv = os.path.join(live, "rdzv")
    pool_file = os.path.join(live, "pool")
    restart_log = os.path.join(live, "restarts.jsonl")
    for d in (obs, ckpt, rdzv):
        os.makedirs(d, exist_ok=True)
    _write_atomic(pool_file, f"{PROCS_FROM}\n")

    os.environ.update(_base_env())
    sup = FleetSupervisor(
        [sys.executable, os.path.join(work, "trainer.py"),
         ckpt, str(TOTAL_STEPS), cfg_path, live],
        FleetPolicy(
            procs=PROCS_FROM, local_devices=LOCAL_DEVICES,
            checkpoint_dir=ckpt, rendezvous_dir=rdzv,
            restart_log=restart_log, max_restarts=3,
            backoff_base=0.1, backoff_factor=2.0, backoff_max=0.5,
            pool_file=pool_file, watch_pool=True,
            pool_poll_interval_s=0.05, pool_debounce_s=0.2,
            term_grace_s=3.0, simulate_cpu_devices=True,
            extra_env={"DRILL_STEP_SLEEP": str(step_sleep)}))
    holder = {}

    def _sup_run():
        holder["rc"] = sup.run()

    sup_thread = threading.Thread(target=_sup_run, daemon=True)
    sup_thread.start()

    t0 = time.monotonic()
    killed_pid, t_kill, pool_written = None, None, False
    while sup_thread.is_alive():
        now = time.monotonic() - t0
        if now > timeout_s:
            print(f"[live] TIMEOUT after {now:.0f}s", file=sys.stderr,
                  flush=True)
            break
        recs, _ = parse_lines(live)
        if killed_pid is None:
            if any(r["epoch"] == 0 and r["step"] >= KILL_AFTER_STEP
                   for r in recs):
                rec = rendezvous.read_record(rdzv, 1)
                assert rec is not None and rec.pid, rec
                killed_pid = int(rec.pid)
                t_kill = time.time()
                os.kill(killed_pid, signal.SIGKILL)
                print(f"[live] SIGKILL host 1 (pid {killed_pid}, "
                      f"t={now:.1f}s)", flush=True)
        elif not pool_written:
            if any(r["epoch"] >= 1 and r["step"] >= GROW_AFTER_STEP
                   for r in recs):
                # the step-6 tag is committed: grow the pool NOW — a
                # planned re-mesh, not a crash
                _write_atomic(pool_file, f"{PROCS_TO}\n")
                pool_written = True
                print(f"[live] pool {PROCS_FROM} -> {PROCS_TO} "
                      f"(file rewrite, t={now:.1f}s)", flush=True)
        time.sleep(0.05)
    sup_thread.join(timeout=60.0)

    recs, dones = parse_lines(live)
    restart_wall = min((r["wall"] for r in recs if r["epoch"] >= 1),
                       default=None)
    return {
        "sup": sup, "rc": holder.get("rc"),
        "recs": recs, "dones": dones,
        "obs": obs, "rdzv": rdzv, "restart_log": restart_log,
        "killed_pid": killed_pid, "t_kill": t_kill,
        "restart_s": (restart_wall - t_kill
                      if restart_wall and t_kill else None),
        "pool_written": pool_written,
    }


def audit(ref_losses, live, merged_path) -> dict:
    """Everything the drill promises, checked from artifacts."""
    from deeperspeed_tpu.distributed import rendezvous
    from deeperspeed_tpu.monitor.aggregate import merge_files
    from deeperspeed_tpu.monitor.validate import validate_file

    # ---- bit-identical parity: every line of every incarnation ----
    max_delta, mismatches = 0.0, []
    for r in live["recs"]:
        want = ref_losses.get(r["step"])
        if want is None:
            continue
        d = abs(float(r["loss"]) - float(want))
        max_delta = max(max_delta, d)
        if r["loss"] != want:
            mismatches.append({"step": r["step"], "epoch": r["epoch"],
                               "host": r["host"], "live": r["loss"],
                               "ref": want})
    steps_covered = (set(r["step"] for r in live["recs"])
                     == set(range(TOTAL_STEPS)))
    final_epoch = max((r["epoch"] for r in live["recs"]), default=-1)
    final = [r for r in live["recs"] if r["epoch"] == final_epoch]
    worlds_ok = (
        all(r["world"] == PROCS_FROM * LOCAL_DEVICES
            for r in live["recs"] if r["epoch"] < final_epoch)
        and all(r["world"] == PROCS_TO * LOCAL_DEVICES for r in final))
    hosts_final = sorted(set(r["host"] for r in final))

    # ---- restart log: barrier taxonomy + growth without crashes ----
    events = []
    try:
        with open(live["restart_log"]) as f:
            events = [json.loads(x) for x in f if x.strip()]
    except OSError:
        pass
    barriers = [e for e in events if e.get("event") == "barrier"]
    remeshes = [e for e in events if e.get("event") == "fleet_remesh"]
    dones = [e for e in events if e.get("event") == "done"]
    remesh_idx = (events.index(remeshes[0]) if remeshes else -1)
    barriers_after_growth = [
        e for e in events[remesh_idx:] if e.get("event") == "barrier"
    ] if remesh_idx >= 0 else []
    done = dones[0] if dones else {}

    # ---- merged multi-host trace, clock-aligned, strict-clean ----
    offsets = rendezvous.read_offsets(live["rdzv"])
    doc, stats = merge_files([live["obs"]], out=merged_path,
                             offsets_s=offsets)
    problems = validate_file(merged_path, strict=True)

    # ---- cross-host wire pricing for the grown fleet ----
    import jax

    from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt
    from deeperspeed_tpu.runtime.comm import bucketing
    from deeperspeed_tpu.runtime.comm.config import CommConfig
    from deeperspeed_tpu.runtime.comm.wiremodel import (hier_wire_split,
                                                        plan_wire_bytes)
    import jax.numpy as jnp

    init_fn, _, _, _ = make_gpt(GPTConfig(dtype=jnp.float32, **GPT))
    params = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    world = PROCS_TO * LOCAL_DEVICES
    wire = {}
    for mode in ("int8", "lossless"):
        ccfg = CommConfig(mode=mode, bucket_mb=0.01,
                          error_feedback=(mode == "int8"),
                          hierarchical="on", intra_size=LOCAL_DEVICES)
        plan = bucketing.build_plan(params, ccfg.bucket_bytes,
                                    ccfg.block * world)
        split = hier_wire_split(plan, ccfg, world, LOCAL_DEVICES)
        wire[mode] = {"flat_bytes": plan_wire_bytes(plan, ccfg, world),
                      **split}

    return {
        "parity": {
            "max_loss_delta": max_delta,
            "mismatches": mismatches[:10],
            "lines_checked": len(live["recs"]),
            "steps_covered": steps_covered,
            "worlds_ok": worlds_ok,
            "hosts_final": hosts_final,
        },
        "restart": {
            "restart_s": (round(live["restart_s"], 3)
                          if live["restart_s"] is not None else None),
            "barriers": len(barriers),
            "cause": (barriers[0].get("cause") if barriers else None),
            "crashes": done.get("crashes"),
            "preemptions": done.get("preemptions"),
        },
        "growth": {
            "remeshes": done.get("remeshes"),
            "procs_from": (remeshes[0].get("procs_from")
                           if remeshes else None),
            "procs_to": (remeshes[0].get("procs_to")
                         if remeshes else None),
            "world_to": world,
            "crash_restarts_after_growth": len(barriers_after_growth),
        },
        "trace": {
            "merged_valid": not problems,
            "problems": problems[:10],
            "sources": stats.get("sources"),
            "unaligned_sources": stats.get("unaligned_sources"),
            "clock_offsets": {k: round(v, 6)
                              for k, v in sorted(offsets.items())},
        },
        "wire": wire,
        "supervisor": {
            "rc": live["rc"],
            "done": bool(done),
            "trainer_dones": len(live["dones"]),
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out",
                    default=os.path.join(REPO, "BENCH_multihost.json"))
    ap.add_argument("--trace", default=os.path.join(
        REPO, "traces", "multihost_drill_trace.json"))
    ap.add_argument("--quick", action="store_true",
                    help="shorter step sleeps (CI wrapper)")
    args = ap.parse_args()

    from deeperspeed_tpu.distributed.bootstrap import multiprocess_cpu_probe

    if not multiprocess_cpu_probe():
        print("multihost drill: no multiprocess CPU collectives in this "
              "jaxlib; cannot run", file=sys.stderr)
        sys.exit(2)

    step_sleep = 0.25 if args.quick else 0.4
    timeout_s = 360.0 if args.quick else 480.0
    os.makedirs(os.path.dirname(args.trace), exist_ok=True)

    work = tempfile.mkdtemp(prefix="multihost_drill_")
    cfg_path = os.path.join(work, "ds_config.json")
    with open(os.path.join(work, "trainer.py"), "w") as f:
        f.write(_TRAINER)
    with open(cfg_path, "w") as f:
        json.dump(DRILL_CONFIG, f, indent=1)

    t0 = time.time()
    merged = os.path.join(work, "merged_trace.json")
    try:
        ref_losses = run_reference(work, cfg_path)
        live = run_live(work, cfg_path, step_sleep, timeout_s)
        report = audit(ref_losses, live, merged)
        shutil.copy(merged, args.trace)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    p, r, g, tr, sv = (report["parity"], report["restart"],
                       report["growth"], report["trace"],
                       report["supervisor"])
    ok = bool(
        p["max_loss_delta"] == 0.0 and not p["mismatches"]
        and p["steps_covered"] and p["worlds_ok"]
        and p["hosts_final"] == list(range(PROCS_TO))
        and r["barriers"] == 1 and r["cause"] == "crashed"
        and r["crashes"] == 1 and r["preemptions"] == 0
        and r["restart_s"] is not None and r["restart_s"] < 120.0
        and g["remeshes"] == 1 and g["procs_from"] == PROCS_FROM
        and g["procs_to"] == PROCS_TO
        and g["crash_restarts_after_growth"] == 0
        and tr["merged_valid"] and tr["unaligned_sources"] == 0
        and sv["rc"] == 0 and sv["trainer_dones"] >= PROCS_TO)
    result = dict(report)
    result.update({
        "drill": "multihost",
        "quick": bool(args.quick),
        "wall_s": round(time.time() - t0, 1),
        "pass": ok,
    })
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(f"[multihost] max_loss_delta={p['max_loss_delta']:.3e} "
          f"lines={p['lines_checked']} restart_s={r['restart_s']} "
          f"remeshes={g['remeshes']} "
          f"trace_valid={tr['merged_valid']} rc={sv['rc']}", flush=True)
    print(f"wrote {args.out} pass={result['pass']}")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
