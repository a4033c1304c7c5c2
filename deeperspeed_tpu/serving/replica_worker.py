"""Subprocess replica runner: one ServingEngine behind a line-JSON pipe.

``python -m deeperspeed_tpu.serving.replica_worker --spec spec.json``
builds a GPT from the spec (config kwargs + init seed — every replica of
a fleet derives IDENTICAL weights from the same spec, which is what
makes cross-replica retries token-identical) and serves requests over a
newline-delimited JSON protocol:

parent -> child (stdin)::

    {"op": "submit", "rid": ..., "prompt": [...],
     "max_new_tokens": N, "temperature": T, "seed": S}
    {"op": "cancel", "rid": ..., "reason": "timeout"}
    {"op": "drain"}          # reject new work, finish what's in flight
    {"op": "stop"}           # graceful exit

child -> parent (stdout; logs go to stderr, stdout is protocol-only)::

    {"ev": "ready"}                                  # engine warm
    {"ev": "hb", "progress": N, "inflight": [...],
     "draining": bool}                               # every loop turn
    {"ev": "first", "rid": ...}                      # first token out
    {"ev": "fin", "rid": ..., "tokens": [...], "reason": ...}
    {"ev": "err", "rid": ..., "error": ...}          # submit rejected

The worker is where the fleet drill's faults land: it calls
``FaultInjector.on_decode_step`` once per engine step, so
``DS_TPU_FAULTS='{"replica_sigkill_at_decode": 12}'`` kills THIS replica
mid-decode and ``replica_stall_at_decode`` wedges it (alive and
heartbeating, emitting no tokens) — the two failure modes the router's
watchdogs must distinguish.
"""

import argparse
import json
import queue
import sys
import threading
import time
from typing import Optional, Sequence

WARM_RID = "_warm"   # internal warmup request, never reported


def _emit(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def _stdin_reader(q: "queue.Queue[Optional[dict]]") -> None:
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        try:
            q.put(json.loads(line))
        except json.JSONDecodeError:
            print(f"replica_worker: bad op line {line!r}", file=sys.stderr)
    q.put(None)   # EOF: parent is gone -> orderly exit


def _load_weights(params, weights: dict):
    """Replace init params with a published checkpoint's module tree.

    ``weights`` is the pointer the lifecycle controller pushes through
    ``SubprocessReplica.set_weights``: ``{"load_dir", "tag"}`` naming a
    trainer checkpoint (legacy single-file layout). Every replica pinned
    to the same WeightVersion loads the same bytes, which is what keeps
    version-pinned failover retries token-identical."""
    import os as _os

    from flax import serialization as _ser

    from ..checkpoint.serialization import load_tree, model_state_filename

    path = _os.path.join(str(weights["load_dir"]), str(weights["tag"]),
                         model_state_filename())
    model_states = load_tree(path)
    return _ser.from_state_dict(params, model_states["module"])


def build_engine(spec: dict):
    """GPT + ServingEngine from a replica spec: deterministic init from
    ``init_seed`` so every replica holds the same weights. A ``weights``
    block (``{"load_dir", "tag"}``) swaps in a published checkpoint —
    same determinism, now anchored to the checkpoint bytes instead of
    the init PRNG."""
    import jax
    import jax.numpy as jnp

    from ..models.gpt import GPTConfig, make_gpt
    from .config import ServingConfig
    from .engine import ServingEngine

    gpt_kwargs = dict(spec.get("gpt") or {})
    gpt_kwargs.setdefault("dtype", jnp.float32)
    cfg = GPTConfig(**gpt_kwargs)
    init_fn, _, _, _ = make_gpt(cfg)
    params = init_fn(jax.random.PRNGKey(int(spec.get("init_seed", 0))))
    if spec.get("weights"):
        params = _load_weights(params, spec["weights"])
    scfg = ServingConfig.from_dict(
        {k: v for k, v in (spec.get("serving") or {}).items()
         if k != "fleet"})
    return ServingEngine(cfg, params, scfg)


def serve(spec: dict, injector=None) -> int:
    from ..monitor import init_monitor, shutdown_monitor
    from ..monitor.runctx import current as current_run
    from .engine import EngineDrainingError

    run_ctx = current_run()
    if spec.get("monitor"):
        # before build_engine so warmup compiles and admits are traced;
        # with an obs_dir the paths derive from DS_TPU_ROLE/INCARNATION
        # set by the parent fleet, and the flight recorder makes this
        # worker's tail survive the drill's SIGKILL
        init_monitor(spec["monitor"])

    eng = build_engine(spec)
    if injector is None:
        from ..resilience.faults import FaultInjector, \
            plan_from_config_and_env

        injector = FaultInjector(plan_from_config_and_env(
            spec.get("faults")))

    if spec.get("warm", True):
        # compile the decode program + smallest prefill bucket up front
        # so fault step counts and health timings hit a warm engine; the
        # sampled (temperature > 0) host path compiles separately, so
        # warm both
        rid = eng.submit([1, 2, 3], max_new_tokens=2, request_id=WARM_RID)
        eng.submit([4, 5, 6], max_new_tokens=2, temperature=0.5,
                   request_id=WARM_RID + "2")
        eng.run()
        assert eng.get(rid).state == "finished"

    ops: "queue.Queue[Optional[dict]]" = queue.Queue()
    threading.Thread(target=_stdin_reader, args=(ops,), daemon=True).start()
    _emit({"ev": "ready", "run_id": run_ctx.run_id, "role": run_ctx.role,
           "incarnation": run_ctx.incarnation, "wall_t": time.time()})

    poll_s = float(spec.get("poll_interval_s", 0.002))
    decode_i = 0
    stalled = False
    draining = False
    stopping = False
    first_sent = set()
    reported = set()
    tracked = []   # rids in submission order, for first/fin scans

    while True:
        while True:
            try:
                op = ops.get_nowait()
            except queue.Empty:
                break
            if op is None or op.get("op") == "stop":
                stopping = True
                break
            kind = op.get("op")
            if kind == "submit":
                try:
                    if draining:
                        raise EngineDrainingError("replica draining")
                    eng.submit(op["prompt"],
                               max_new_tokens=op.get("max_new_tokens"),
                               temperature=op.get("temperature", 0.0),
                               request_id=op["rid"],
                               seed=op.get("seed"))
                    tracked.append(op["rid"])
                except Exception as e:  # noqa: BLE001 - reported upstream
                    _emit({"ev": "err", "rid": op.get("rid"),
                           "error": f"{type(e).__name__}: {e}"})
            elif kind == "cancel":
                eng.cancel(op["rid"], op.get("reason", "timeout"))
            elif kind == "drain":
                draining = True
            elif kind == "clock":
                # NTP-style handshake leg: echo the parent's t0 with our
                # wall time so it can estimate this host's clock offset
                _emit({"ev": "clock", "t0": op.get("t0"),
                       "t_child": time.time()})
            else:
                print(f"replica_worker: unknown op {op!r}", file=sys.stderr)
        if stopping:
            break

        if eng.has_work() and not stalled:
            decode_i += 1
            verdict = injector.on_decode_step(decode_i)
            if verdict == "stall":
                stalled = True
            else:
                eng.step()
        else:
            time.sleep(poll_s)

        # report first tokens and finishes in submission order
        for rid in tracked:
            req = eng.get(rid)
            if rid not in first_sent and req.first_token_t is not None:
                first_sent.add(rid)
                _emit({"ev": "first", "rid": rid})
            if rid not in reported and req.state == "finished":
                reported.add(rid)
                _emit({"ev": "fin", "rid": rid, "tokens": req.output,
                       "reason": req.finish_reason})
        inflight = [r for r in tracked if r not in reported]
        _emit({"ev": "hb", "progress": int(eng.metrics.total_generated),
               "inflight": inflight, "draining": draining})
        if draining and not inflight and not eng.has_work():
            break

    shutdown_monitor(save=True)   # graceful exits write the full trace
    _emit({"ev": "bye"})
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m deeperspeed_tpu.serving.replica_worker")
    ap.add_argument("--spec", required=True,
                    help="JSON replica spec: {gpt: {...GPTConfig kwargs}, "
                         "init_seed, serving: {...ServingConfig}, warm, "
                         "poll_interval_s, faults}")
    args = ap.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    from ..utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    return serve(spec)


if __name__ == "__main__":
    sys.exit(main())
