"""Resilience drill: save-stall benchmark + kill-and-resume exercise.

Two measurements, written where ``--out`` says (default: the git-ignored
BENCH_resilience.json at the repo root):

  1. Save stall: how long ``engine.save_checkpoint`` blocks the step
     loop for a ~tens-of-MB model under (a) the legacy inline writer,
     (b) the resilience SYNC two-phase-commit writer, and (c) the
     resilience ASYNC writer (device->host snapshot only; serialize +
     fsync + commit happen on the background thread). The acceptance
     bar: async blocked time < 25% of the sync save time.

  2. End-to-end drill: a real trainer subprocess is SIGKILLed mid-save
     by the fault injector (one-shot flag-file latch), the auto-resume
     supervisor restarts it, and the restarted run resumes from the
     newest committed tag — with per-step losses bit-identical to an
     uninterrupted reference run. Also records resume latency.

The drill runs anywhere (CI included) in under a minute; export
JAX_PLATFORMS=tpu before invoking to measure real device snapshots.

Usage:
  python scripts/resilience_drill.py [--dim 1536 4096] [--reps 3] \
      [--steps 6] [--out BENCH_resilience.json]
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the drill targets the host CPU mesh by design (the acceptance surface
# for resilience work without a chip)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _build_engine(dim):
    import deeperspeed_tpu as deepspeed

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] - y) ** 2)

    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    }
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), dim) * 0.02}
    engine, _, _, _ = deepspeed.initialize(
        model=loss_fn, model_parameters=params, config_params=cfg)
    rs = np.random.RandomState(0)
    batch = (jnp.asarray(rs.randn(8, dim[0]).astype(np.float32)),
             jnp.asarray(rs.randn(8, dim[1]).astype(np.float32)))
    engine.train_batch(batch=batch)  # materialize optimizer state
    return engine


def bench_save_stall(dim, reps):
    """Best-of-N wall time save_checkpoint blocks the caller, per mode."""
    from deeperspeed_tpu.resilience import ResilienceConfig
    from deeperspeed_tpu.resilience.manager import ResilienceManager

    engine = _build_engine(dim)
    payload_mb = sum(
        np.asarray(x).nbytes for x in jax.tree.leaves(
            engine._host_checkpoint_payload())
        if hasattr(x, "nbytes")) / 1e6

    def timed(save_dir, after=None):
        best = float("inf")
        for rep in range(reps):
            t0 = time.perf_counter()
            engine.save_checkpoint(save_dir, tag=f"rep{rep}",
                                   save_latest=False)
            best = min(best, time.perf_counter() - t0)
            if after is not None:
                after()
        return best * 1e3

    out = {}
    work = tempfile.mkdtemp(prefix="resilience_drill_")
    try:
        engine._resilience = None
        out["legacy_save_ms"] = timed(os.path.join(work, "legacy"))

        sync_mgr = ResilienceManager(ResilienceConfig.from_dict(
            {"async_save": False, "preemption_guard": False}))
        engine._resilience = sync_mgr
        out["sync_save_ms"] = timed(os.path.join(work, "sync"))
        sync_mgr.close()

        async_mgr = ResilienceManager(ResilienceConfig.from_dict(
            {"async_save": True, "preemption_guard": False}))
        engine._resilience = async_mgr
        # drain between reps so each measurement sees an idle writer
        out["async_blocked_ms"] = timed(
            os.path.join(work, "async"),
            after=async_mgr.wait_for_pending_saves)
        async_mgr.close()
        engine._resilience = None

        # resume latency: a fresh engine restoring the sync checkpoint
        fresh = _build_engine(dim)
        t0 = time.perf_counter()
        path, _ = fresh.load_checkpoint(os.path.join(work, "sync"),
                                        tag="rep0")
        out["resume_latency_s"] = round(time.perf_counter() - t0, 4)
        assert path is not None, "resume load found no checkpoint"
    finally:
        shutil.rmtree(work, ignore_errors=True)
    out["payload_mb"] = round(payload_mb, 2)
    out["blocked_ratio"] = out["async_blocked_ms"] / out["sync_save_ms"]
    out["blocked_vs_legacy_ratio"] = (
        out["async_blocked_ms"] / out["legacy_save_ms"])
    return out


_TRAINER = """\
import sys
import numpy as np
import jax.numpy as jnp
import deeperspeed_tpu as deepspeed
from deeperspeed_tpu.resilience import shutdown_resilience

ckpt_dir, steps = sys.argv[1], int(sys.argv[2])

def loss_fn(p, b):
    x, y = b
    return jnp.mean((x @ p["w"] - y) ** 2)

cfg = {
    "train_batch_size": 8,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    "resilience": {"save_dir": ckpt_dir, "save_interval_steps": 2,
                   "async_save": True, "preemption_guard": False},
}
params = {"w": jnp.zeros((4, 2), jnp.float32)}  # deterministic init
engine, _, _, _ = deepspeed.initialize(
    model=loss_fn, model_parameters=params, config_params=cfg)
path, _ = engine.load_checkpoint(ckpt_dir)
start = engine.global_steps if path is not None else 0
for i in range(start, steps):
    rs = np.random.RandomState(i)  # batch keyed by global step
    b = (jnp.asarray(rs.randn(8, 4).astype(np.float32)),
         jnp.asarray(rs.randn(8, 2).astype(np.float32)))
    loss = engine.train_batch(batch=b)
    print(f"STEP {i} LOSS {float(loss):.17e}", flush=True)
shutdown_resilience()
"""


def run_drill(steps):
    """SIGKILL-mid-save under the supervisor, then verify the resumed
    losses match an uninterrupted reference run exactly."""
    from deeperspeed_tpu.checkpoint.serialization import read_latest
    from deeperspeed_tpu.resilience import (
        FAULTS_ENV_VAR, Supervisor, SupervisorPolicy, is_committed,
        verify_manifest,
    )

    work = tempfile.mkdtemp(prefix="resilience_drill_e2e_")
    script = os.path.join(work, "trainer.py")
    with open(script, "w") as f:
        f.write(_TRAINER)
    ckpt = os.path.join(work, "ckpt")
    base_env = dict(os.environ, JAX_PLATFORMS="cpu",
                    PYTHONPATH=REPO + os.pathsep
                    + os.environ.get("PYTHONPATH", ""))
    base_env.pop("XLA_FLAGS", None)

    outputs = []

    def parse_losses(text):
        got = {}
        for line in text.splitlines():
            if line.startswith("STEP "):
                _, i, _, loss = line.split()
                got[int(i)] = loss
        return got

    try:
        # reference: uninterrupted run in its own directory
        ref = subprocess.run(
            [sys.executable, script, os.path.join(work, "ref"), str(steps)],
            env=base_env, capture_output=True, text=True, timeout=300)
        assert ref.returncode == 0, ref.stderr[-2000:]
        ref_losses = parse_losses(ref.stdout)

        # supervised run: the 3rd checkpoint file written SIGKILLs the
        # child (mid-save of the 2nd autosave tag); the flag file makes
        # the fault one-shot so the restart proceeds clean
        child_env = dict(base_env)
        child_env[FAULTS_ENV_VAR] = json.dumps({
            "sigkill_mid_save": 3,
            "flag_file": os.path.join(work, "fault.fired"),
        })

        def run_child(cmd, env):
            merged = dict(child_env, **{k: env[k] for k in env
                                        if k.startswith("DS_TPU_RESUME")
                                        or k == "DS_TPU_RESTART_COUNT"})
            proc = subprocess.run(cmd, env=merged, capture_output=True,
                                  text=True, timeout=300)
            outputs.append(proc)
            return (proc.returncode if proc.returncode >= 0
                    else 128 - proc.returncode)

        sup = Supervisor(
            [sys.executable, script, ckpt, str(steps)],
            SupervisorPolicy(max_restarts=3, backoff_base=0.1,
                             backoff_max=0.5, checkpoint_dir=ckpt),
            run_fn=run_child)
        rc = sup.run()

        killed, resumed = outputs[0], outputs[-1]
        committed_tag = read_latest(ckpt)
        tag_dir = os.path.join(ckpt, committed_tag or "")
        res_losses = parse_losses(resumed.stdout)
        resumed_steps = sorted(res_losses)
        match = all(res_losses[i] == ref_losses[i] for i in res_losses)

        result = {
            "pass": bool(
                rc == 0
                and killed.returncode == -signal.SIGKILL
                and sup.restarts >= 1
                and committed_tag is not None
                and is_committed(tag_dir)
                and verify_manifest(tag_dir)[0]
                and resumed_steps
                and resumed_steps[0] > 0  # actually resumed, not from 0
                and match),
            "supervisor_rc": rc,
            "killed_rc": killed.returncode,
            "restarts": sup.restarts,
            "committed_tag": committed_tag,
            "resumed_from_step": resumed_steps[0] if resumed_steps else None,
            "losses_match_reference": match,
        }
        if not result["pass"]:
            for i, proc in enumerate(outputs):
                sys.stderr.write(f"--- child {i} rc={proc.returncode}\n"
                                 f"{proc.stdout}\n{proc.stderr[-2000:]}\n")
        return result
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, nargs=2, default=(1536, 4096),
                    help="weight matrix shape for the stall benchmark "
                         "(default ~75 MB of checkpoint payload)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--steps", type=int, default=6,
                    help="trainer steps in the kill-and-resume drill")
    ap.add_argument("--max-blocked-ratio", type=float, default=0.25)
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "BENCH_resilience.json"))
    args = ap.parse_args()

    stall = bench_save_stall(tuple(args.dim), args.reps)
    print(f"save stall ({stall['payload_mb']:.1f} MB payload): "
          f"legacy {stall['legacy_save_ms']:.1f} ms, "
          f"sync {stall['sync_save_ms']:.1f} ms, "
          f"async blocked {stall['async_blocked_ms']:.1f} ms "
          f"(ratio {stall['blocked_ratio']:.3f}), "
          f"resume {stall['resume_latency_s']:.2f} s")

    drill = run_drill(args.steps)
    print(f"kill-and-resume drill: pass={drill['pass']} "
          f"(killed rc {drill['killed_rc']}, restarts {drill['restarts']}, "
          f"resumed from step {drill['resumed_from_step']}, "
          f"losses match: {drill['losses_match_reference']})")

    report = dict(stall, drill=drill,
                  max_blocked_ratio=args.max_blocked_ratio)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(f"wrote {args.out}")

    if not drill["pass"]:
        print("FAIL: kill-and-resume drill did not pass", file=sys.stderr)
        return 1
    worst = max(stall["blocked_ratio"], stall["blocked_vs_legacy_ratio"])
    if worst >= args.max_blocked_ratio:
        print(f"FAIL: async blocked ratio {worst:.3f} >= "
              f"{args.max_blocked_ratio}", file=sys.stderr)
        return 1
    print("resilience drill PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
