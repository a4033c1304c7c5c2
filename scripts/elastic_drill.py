"""Elastic fleet drill: spot-pool kills with world-size flips.

A supervised trainer runs on a simulated spot pool of CPU devices.
The SpotPoolSimulator SIGKILLs it on a fixed schedule and changes the
surviving pool size; before every restart the supervisor re-reads the
pool file, picks the largest admissible elastic world size, and
re-execs the trainer on the new topology. The checkpoint written at
world size W is resharded onto W' — partitioned optimizer state via
the sharded loader, comm error-feedback residuals via
resilience/reshard.py, and the datapipe cursor by exact-stream remap.

Default schedule (24 steps): start on 8 devices, SIGKILL at step 8 ->
pool shrinks to 4, SIGKILL at step 16 -> pool grows to 16, finish at
16. Acceptance: every per-step loss across all phases is BIT-IDENTICAL
to an uninterrupted 8-device reference run (canonical-slot reduction
makes the loss world-size invariant), and the post-run datapipe batch
digest matches (no token skipped or repeated).

Writes its report where ``--out`` says (default: the git-ignored
BENCH_elastic.json): per-flip resume latency + loss delta.

Usage:
  python scripts/elastic_drill.py [--steps 24] [--out BENCH_elastic.json]
"""

import argparse
import hashlib  # noqa: F401 - mirrored in the trainer template
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SEQ_LEN = 16

# elasticity solves the batch geometry per world size: final batch 64,
# micro 4 -> valid worlds {4, 8, 16} (gas 4/2/1). canonical_shards=16
# fixes the reduction tree at 16 slots so the loss is bit-identical on
# every admissible topology.
DRILL_CONFIG = {
    "elasticity": {
        "enabled": True,
        "max_train_batch_size": 64,
        "micro_batch_sizes": [4],
        "min_gpus": 4,
        "max_gpus": 16,
        "version": 0.1,
        "ignore_non_elastic_batch_info": True,
        "canonical_shards": 16,
    },
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    "zero_optimization": {"stage": 0},
    "steps_per_print": 10000,
    "comm": {"mode": "int8", "bucket_mb": 0.01, "error_feedback": True},
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
}

_TRAINER = """\
import os, sys, time
ckpt_dir, steps, data_src, cfg_path = sys.argv[1:5]
W = int(os.environ.get("DS_TPU_WORLD_SIZE", "8"))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={W}"
import json
import hashlib
import numpy as np
import jax
import jax.numpy as jnp
import deeperspeed_tpu as deepspeed
from deeperspeed_tpu.resilience import shutdown_resilience

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
t0 = time.perf_counter()
path, _ = engine.load_checkpoint(ckpt_dir)
print(f"RESUME_S {time.perf_counter() - t0:.4f} "
      f"FROM {engine.global_steps if path is not None else 0} "
      f"WORLD {W}", flush=True)
steps = int(steps)
while engine.global_steps < steps:
    i = engine.global_steps
    loss = engine.train_batch()
    print(f"STEP {i} LOSS {float(loss):.17e}", flush=True)
batch, _ = engine.datapipe.next_global_batch()
digest = hashlib.sha1(
    np.ascontiguousarray(jax.device_get(batch)).tobytes()).hexdigest()
print(f"NEXT_BATCH_DIGEST {digest}", flush=True)
shutdown_resilience()
"""


def _write_corpus(path: str, n_tokens: int = 40000) -> None:
    import numpy as np

    rs = np.random.RandomState(1234)
    tokens = rs.randint(0, 997, size=n_tokens).astype(np.int32)
    np.save(path, tokens)


def parse_stream(text: str):
    losses, resume, digest = {}, None, None
    for line in text.splitlines():
        if line.startswith("STEP "):
            _, i, _, loss = line.split()
            losses[int(i)] = loss
        elif line.startswith("RESUME_S "):
            parts = line.split()
            resume = {"resume_s": float(parts[1]), "from_step": int(parts[3]),
                      "world": int(parts[5])}
        elif line.startswith("NEXT_BATCH_DIGEST "):
            digest = line.split()[1]
    return losses, resume, digest


def run_drill(steps: int, kills=((8, 4), (16, 16)), initial_pool: int = 8):
    from deeperspeed_tpu.resilience import (
        FAULTS_ENV_VAR, PoolEvent, SpotPoolSimulator, Supervisor,
        SupervisorPolicy,
    )

    work = tempfile.mkdtemp(prefix="elastic_drill_")
    script = os.path.join(work, "trainer.py")
    cfg_path = os.path.join(work, "ds_config.json")
    data = os.path.join(work, "corpus.npy")
    ckpt = os.path.join(work, "ckpt")
    pool_file = os.path.join(work, "pool")
    with open(script, "w") as f:
        f.write(_TRAINER)
    with open(cfg_path, "w") as f:
        json.dump(DRILL_CONFIG, f, indent=1)
    _write_corpus(data)

    base_env = dict(os.environ,
                    PYTHONPATH=REPO + os.pathsep
                    + os.environ.get("PYTHONPATH", ""))
    base_env.pop("XLA_FLAGS", None)
    base_env.pop(FAULTS_ENV_VAR, None)

    outputs = []
    try:
        # reference: uninterrupted run at the initial world size
        ref_env = dict(base_env, DS_TPU_WORLD_SIZE=str(initial_pool))
        ref = subprocess.run(
            [sys.executable, script, os.path.join(work, "ref"), str(steps),
             data, cfg_path],
            env=ref_env, capture_output=True, text=True, timeout=600)
        assert ref.returncode == 0, ref.stderr[-3000:]
        ref_losses, _, ref_digest = parse_stream(ref.stdout)
        assert sorted(ref_losses) == list(range(steps)), sorted(ref_losses)

        sim = SpotPoolSimulator(
            pool_file, initial_pool,
            [PoolEvent(kill_at_step=k, pool_after=p) for k, p in kills])

        def run_child(cmd, env):
            merged = dict(base_env)
            merged.update({k: v for k, v in env.items()
                           if k.startswith("DS_TPU_")})
            faults = sim.child_faults()
            if faults is not None:
                merged[FAULTS_ENV_VAR] = json.dumps(faults)
            else:
                merged.pop(FAULTS_ENV_VAR, None)
            t0 = time.perf_counter()
            proc = subprocess.run(cmd, env=merged, capture_output=True,
                                  text=True, timeout=600)
            outputs.append((proc, time.perf_counter() - t0))
            rc = (proc.returncode if proc.returncode >= 0
                  else 128 - proc.returncode)
            sim.on_child_exit(rc)
            return rc

        sup = Supervisor(
            [sys.executable, script, ckpt, str(steps), data, cfg_path],
            SupervisorPolicy(
                max_restarts=len(kills) + 2, backoff_base=0.1,
                backoff_max=0.5, checkpoint_dir=ckpt,
                elastic_config=cfg_path, pool_file=pool_file,
                restart_log=os.path.join(work, "restarts.jsonl")),
            run_fn=run_child)
        rc = sup.run()

        # stitch the supervised loss curve: children overwrite replayed
        # steps, and EVERY printed loss must equal the reference's
        flips, mismatches, seen = [], [], {}
        for idx, (proc, wall) in enumerate(outputs):
            losses, resume, digest = parse_stream(proc.stdout)
            for i, loss in losses.items():
                seen[i] = loss
                if ref_losses.get(i) != loss:
                    mismatches.append(
                        {"step": i, "child": idx, "got": loss,
                         "want": ref_losses.get(i)})
            if resume is not None and idx > 0:
                flips.append({
                    "world_from": sup.world_history[idx - 1],
                    "world_to": resume["world"],
                    "resumed_from_step": resume["from_step"],
                    "resume_s": resume["resume_s"],
                    "child_wall_s": round(wall, 2),
                })
            final_digest = digest

        covered = sorted(seen) == list(range(steps))
        max_delta = 0.0
        for i, loss in seen.items():
            if i in ref_losses:
                max_delta = max(max_delta, abs(
                    float(loss) - float(ref_losses[i])))

        result = {
            "pass": bool(rc == 0 and sup.restarts == len(kills)
                         and covered and not mismatches
                         and final_digest == ref_digest
                         and [f["world_to"] for f in flips]
                         == [p for _, p in kills]),
            "supervisor_rc": rc,
            "restarts": sup.restarts,
            "world_history": sup.world_history,
            "flips": flips,
            "steps": steps,
            "loss_steps_covered": covered,
            "loss_mismatches": mismatches[:10],
            "max_abs_loss_delta": max_delta,
            "token_stream_digest_match": final_digest == ref_digest,
        }
        if not result["pass"]:
            for i, (proc, _) in enumerate(outputs):
                sys.stderr.write(f"--- child {i} rc={proc.returncode}\n"
                                 f"{proc.stdout}\n{proc.stderr[-3000:]}\n")
        return result
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "BENCH_elastic.json"))
    args = ap.parse_args()

    result = run_drill(args.steps)
    print(f"elastic drill: pass={result['pass']} "
          f"(worlds {result['world_history']}, "
          f"max loss delta {result['max_abs_loss_delta']:.3e}, "
          f"digest match {result['token_stream_digest_match']})")
    for f in result["flips"]:
        print(f"  flip {f['world_from']} -> {f['world_to']} devices: "
              f"resumed from step {f['resumed_from_step']} in "
              f"{f['resume_s']:.2f} s")
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}")
    if not result["pass"]:
        print("FAIL: elastic drill did not pass", file=sys.stderr)
        return 1
    print("elastic drill PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
