"""Sharding substrate benchmark: named-mesh layouts on the CPU mesh.

Evidence for the "mesh" config block (sharding/ — the dp×fsdp×tp×sp
substrate). On the virtual 8-device CPU mesh this measures, per layout:

  * **loss parity** — the acceptance bar for the substrate is that it
    changes WHERE arrays live, never WHAT the math computes.  Every
    ZeRO stage trains the same small GPT twice: once on the legacy
    ``{data: 8}`` mesh (the pre-substrate layout) and once on a
    canonical mesh chosen through the ``"mesh"`` block.  The loss
    curves must match at the bit level (``parity.max_loss_delta`` <=
    1e-6; observed 0.0 when only axis names change and <= 2 f32 ulps at
    loss scale when the mesh geometry changes the all-reduce tree
    order, e.g. 1-D ``[8]`` vs 2-D ``[2,4]``).
  * **step time** — median ``train_batch`` wall time per layout.  On a
    single-core host with 8 virtual XLA devices this is a compile-and-
    dispatch sanity number, not an interconnect measurement; it exists
    so a layout that accidentally materialises replicated copies shows
    up as a step-time cliff.
  * **placement audit** — ``sharding.audit.audit_tree`` over the
    engine's parameter tree: leaf count, sharded fraction by elements,
    and a digest built from ``jax.debug.visualize_array_sharding``
    renders, so two runs that place differently hash differently.
    ``fsdp8_zero3`` must actually shard its parameters
    (``param_sharded_frac`` > 0) — ZeRO-3 on the fsdp axis is the
    layout where "replicated by accident" would be silent otherwise.
  * **comm regression** — ZeRO-2 + a "comm" block used to warn-and-
    ignore; the substrate made the pair legal.  One layout runs it and
    its loss curve must match the no-comm ZeRO-2 run.
  * **sp microbench** — ring attention through the rule table on a
    ``dp4 × sp2`` mesh vs the dense single-device reference
    (max |delta| must stay at numerical-noise level).
  * **monitor wiring** — one canonical run under a "monitor" block must
    emit the ``mesh/build`` instant (with axes + device count args) and
    a ``mesh/audit`` instant into a Chrome trace that passes
    ``python -m deeperspeed_tpu.monitor.validate --strict``.

Results go where ``--out`` says (default: the git-ignored
BENCH_mesh.json at the repo root); the slow test of
``tests/test_sharding.py`` asserts on them.

Usage:
  python scripts/mesh_bench.py [--steps 12] [--out BENCH_mesh.json]
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

REEXEC_FLAG = "DS_MESH_BENCH_REEXEC"

WORLD = 8
MICRO = 2
SEQ = 32
VOCAB = 256


def _reexec_if_needed():
    import jax

    if len(jax.devices()) >= WORLD or os.environ.get(REEXEC_FLAG):
        return
    env = dict(os.environ)
    env[REEXEC_FLAG] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={WORLD}"
                        ).strip()
    env.pop("PYTHONPATH", None)
    sys.exit(subprocess.call(
        [sys.executable, os.path.abspath(__file__)] + sys.argv[1:], env=env))


def _model():
    import jax.numpy as jnp

    from deeperspeed_tpu.models.gpt import GPTConfig, make_gpt

    cfg = GPTConfig(vocab_size=VOCAB, n_layer=2, n_head=4, d_model=64,
                    max_seq=SEQ, remat=False, dtype=jnp.float32,
                    attn_impl="xla", rotary=True)
    return make_gpt(cfg)


def _data(rows, steps, seed=0):
    import numpy as np

    rs = np.random.RandomState(seed)
    base = rs.randint(0, VOCAB, size=(rows * steps, SEQ + 1)).astype(np.int32)
    base[:, 1::2] = base[:, :-1:2]  # learnable periodic structure
    return base


def _build_engine(mesh_block, zero_stage, comm=None, monitor_trace=None):
    import jax

    import deeperspeed_tpu as deepspeed

    init_fn, _, loss_fn, _ = _model()
    params = init_fn(jax.random.PRNGKey(0))
    cfg = {
        "train_micro_batch_size_per_gpu": MICRO,
        "gradient_accumulation_steps": 1,
        "train_batch_size": MICRO * WORLD,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 10 ** 9,
    }
    if mesh_block is not None:
        cfg["mesh"] = mesh_block
    if comm is not None:
        cfg["comm"] = comm
    if monitor_trace is not None:
        cfg["monitor"] = {"trace_path": monitor_trace}
    engine, _, _, _ = deepspeed.initialize(
        model=loss_fn, model_parameters=params, config_params=cfg)
    return engine


def run_layout(mesh_block, zero_stage, steps, comm=None, warmup=2):
    """Train one layout on the shared token stream; losses + timing +
    parameter placement audit."""
    import numpy as np

    from deeperspeed_tpu.sharding import audit_tree, describe

    engine = _build_engine(mesh_block, zero_stage, comm=comm)
    rows = MICRO * engine.data_parallel_size
    data = _data(rows, steps + warmup)
    losses, times = [], []
    for i in range(steps + warmup):
        batch = data[i * rows:(i + 1) * rows]
        t0 = time.perf_counter()
        loss = float(engine.train_batch(batch=batch))
        dt = time.perf_counter() - t0
        if i >= warmup:
            times.append(dt)
        losses.append(loss)
    aud = audit_tree(engine.state.params, mesh=engine.mesh)
    return {
        "mesh": describe(engine.mesh),
        "zero": zero_stage,
        "data_parallel_size": engine.data_parallel_size,
        "losses": [round(x, 8) for x in losses],
        "final_loss": losses[-1],
        # median: single steps on a shared CPU host see scheduler noise
        "step_ms": round(float(np.median(times)) * 1e3, 3),
        "param_leaves": aud["leaves"],
        "param_sharded_leaves": aud["sharded_leaves"],
        "param_sharded_frac": aud["sharded_frac"],
        "placement_digest": aud["digest"],
    }


def ring_sp_microbench():
    """Ring attention through the rule table on dp4 x sp2 vs the dense
    reference: correctness delta + wall time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeperspeed_tpu.ops.ring_attention import (
        _local_causal_attention, make_context_parallel_attention)
    from deeperspeed_tpu.sharding import from_config

    mesh = from_config({"dp": 4, "sp": 2})
    B, S, H, Dh = 8, 64, 4, 16
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, Dh)).astype(np.float32))
               for _ in range(3))
    attend = make_context_parallel_attention(mesh, strategy="ring")
    out = attend(q, k, v)
    ref = _local_causal_attention(q, k, v, causal=True)
    delta = float(jnp.max(jnp.abs(out - ref)))
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(attend(q, k, v))
    ms = (time.perf_counter() - t0) / 5 * 1e3
    return {"mesh": "dp4_sp2", "shape": [B, S, H, Dh],
            "max_abs_delta_vs_dense": delta, "call_ms": round(ms, 3),
            "ok": bool(delta < 2e-5)}


def monitored_run(workdir, steps=3):
    """One canonical run under a monitor block: the mesh/build instant
    must land in a strict-valid trace, plus a mesh/audit instant emitted
    from the bench (the post-hoc layout-debugging join point)."""
    from deeperspeed_tpu.monitor import shutdown_monitor, trace_instant
    from deeperspeed_tpu.sharding import audit_tree

    trace_path = os.path.join(workdir, "trace_mesh.json")
    engine = _build_engine({"dp": 2, "fsdp": 4}, 2, monitor_trace=trace_path)
    rows = MICRO * engine.data_parallel_size
    data = _data(rows, steps)
    try:
        for i in range(steps):
            engine.train_batch(batch=data[i * rows:(i + 1) * rows])
        aud = audit_tree(engine.state.params, mesh=engine.mesh)
        trace_instant("mesh/audit", lane="mesh", tree="params",
                      sharded_frac=aud["sharded_frac"],
                      digest=aud["digest"])
    finally:
        shutdown_monitor()
    proc = subprocess.run(
        [sys.executable, "-m", "deeperspeed_tpu.monitor.validate",
         "--strict", trace_path], capture_output=True, text=True)
    with open(trace_path) as f:
        raw = json.load(f)
    events = raw["traceEvents"] if isinstance(raw, dict) else raw
    builds = [e for e in events if e.get("name") == "mesh/build"]
    audits = [e for e in events if e.get("name") == "mesh/audit"]
    return {
        "validate_rc": proc.returncode,
        "validate_errors": (proc.stderr.strip().splitlines()[:5]
                            if proc.returncode else []),
        "mesh_build_events": len(builds),
        "mesh_build_args": builds[0].get("args") if builds else None,
        "mesh_audit_events": len(audits),
    }


def main():
    _reexec_if_needed()
    import tempfile

    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_mesh.json"))
    args = ap.parse_args()

    result = {"world": WORLD, "steps": args.steps,
              "layouts": {}, "parity": {}}

    # legacy {data: 8} baselines, one per ZeRO stage — "today's loss
    # curves" that every canonical layout must reproduce
    legacy = {}
    for stage in (1, 2, 3):
        legacy[stage] = run_layout(None, stage, args.steps)
        result["layouts"][f"legacy_data8_zero{stage}"] = legacy[stage]
        print(f"legacy_data8_zero{stage}",
              json.dumps({k: legacy[stage][k]
                          for k in ("final_loss", "step_ms",
                                    "param_sharded_frac")}), flush=True)

    # canonical layouts come from the autotuner's admissibility
    # enumerator — the bench measures a slice of the same space
    # `python -m deeperspeed_tpu.autotune` searches, so the two can
    # never drift apart. The legacy twin is the layout's ZeRO stage.
    from deeperspeed_tpu.autotune.space import (ModelSpec,
                                                enumerate_mesh_layouts)
    space = {c.name: c for c in enumerate_mesh_layouts(
        WORLD, ModelSpec(vocab=VOCAB, n_layer=2, n_head=4, d_model=64,
                         seq=SEQ))}
    CANONICAL_NAMES = ("dp8", "dp2_fsdp4", "dp2_fsdp4_zero2", "fsdp8_zero3")
    missing = [n for n in CANONICAL_NAMES if n not in space]
    if missing:
        raise SystemExit(
            f"mesh_bench: canonical layouts {missing} are no longer "
            f"admitted by autotune.space at world={WORLD} — the bench and "
            f"the tuner disagree about the space")
    CANONICAL = [(n, space[n].block(), space[n].zero_stage,
                  space[n].zero_stage) for n in CANONICAL_NAMES]
    deltas = {}
    for name, block, stage, twin in CANONICAL:
        entry = run_layout(block, stage, args.steps)
        delta = max(abs(a - b) for a, b in
                    zip(entry["losses"], legacy[twin]["losses"]))
        entry["loss_delta_vs_legacy"] = delta
        deltas[name] = delta
        result["layouts"][name] = entry
        print(name, json.dumps({"final_loss": entry["final_loss"],
                                "step_ms": entry["step_ms"],
                                "param_sharded_frac":
                                    entry["param_sharded_frac"],
                                "loss_delta_vs_legacy": delta}), flush=True)
        with open(args.out, "w") as f:  # persist after every layout
            json.dump(result, f, indent=1)

    result["parity"] = {
        "basis": "per-step |loss - legacy twin loss|, max over steps",
        "deltas": deltas,
        "max_loss_delta": max(deltas.values()),
    }

    # ZeRO-2 + comm: the pair the old engine warned-and-ignored; the
    # reducer now runs over the (dp, fsdp) tuple and must not move loss
    comm_entry = run_layout({"dp": 2, "fsdp": 4}, 2, args.steps,
                            comm={"mode": "fp32", "bucket_mb": 0.05})
    comm_delta = max(abs(a - b) for a, b in
                     zip(comm_entry["losses"], legacy[2]["losses"]))
    comm_entry["loss_delta_vs_legacy"] = comm_delta
    result["layouts"]["dp2_fsdp4_zero2_comm"] = comm_entry
    result["parity"]["zero2_comm_delta"] = comm_delta
    print("dp2_fsdp4_zero2_comm",
          json.dumps({"loss_delta_vs_legacy": comm_delta}), flush=True)

    result["ring_sp"] = ring_sp_microbench()
    print("ring_sp", json.dumps(result["ring_sp"]), flush=True)

    with tempfile.TemporaryDirectory() as workdir:
        result["monitor"] = monitored_run(workdir)
    print("monitor", json.dumps(result["monitor"]), flush=True)

    result["timing"] = {
        "basis": "wall_clock_median",
        "caveat": (
            "single-core host, 8 virtual XLA devices: step_ms prices "
            "compile+dispatch, not interconnect; it exists to catch a "
            "layout that silently replicates (step-time cliff), the "
            "parity and audit sections are the transferable evidence"),
    }
    mon = result["monitor"]
    result["pass"] = bool(
        result["parity"]["max_loss_delta"] <= 1e-6
        and comm_delta <= 1e-6
        and result["layouts"]["fsdp8_zero3"]["param_sharded_frac"] > 0.5
        and result["ring_sp"]["ok"]
        and mon["validate_rc"] == 0
        and mon["mesh_build_events"] >= 1
        and mon["mesh_audit_events"] >= 1)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({
        "pass": result["pass"],
        "max_loss_delta": result["parity"]["max_loss_delta"],
        "zero2_comm_delta": comm_delta,
        "zero3_param_sharded_frac":
            result["layouts"]["fsdp8_zero3"]["param_sharded_frac"],
        "ring_sp_delta": result["ring_sp"]["max_abs_delta_vs_dense"],
    }), flush=True)
    if not result["pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
