#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the system's main path once through the entry points a user calls, at
the full width (and depth) of GPT-NeoX-1.3B — 24 layers, d_model 2048,
16 heads x 128, vocab 50304, seq 1024 — with random weights made from a seed:

  trainer  ``deepspeed.initialize(model=loss_fn, model_parameters=params,
           config=...)`` with the masterless-bf16 settings of
           ``configs/neox_1.3b_single_chip.json``, then a few
           ``engine.train_batch(batch)`` steps on one fixed synthetic batch.
           Pass: finite loss that falls, ``global_steps`` advances, and the
           compiled step holds a Mosaic attention kernel (the
           ``kernels/attention_dispatch`` trace instant — ``attn_impl="auto"``
           giving way to the XLA reference is a failure here).
  server   ``ServingEngine(cfg, params, scfg)`` with bf16 params and a paged
           KV pool of 4k tokens; staggered greedy requests of different
           prompt lengths through ``submit`` / ``step``. Pass: every request
           finishes with the token count it asked for,
           ``decode_compile_count == 1``, strict recompile watchdog silent.
           Token parity with ``models/generation.make_generator`` is
           reported, not enforced (bf16 argmax near-ties may differ between
           the paged and the dense cache).

One process holds a chip at a time and the trainer fills the HBM, so each
phase runs in its own child process, one after another, started by a parent
that never initialises a JAX backend. The phases share one persistent compile
cache (``deeperspeed_tpu/utils/compile_cache.py``).

The exit code is the truth: any phase that raises, or finds no TPU, makes the
run exit non-zero and print no result. Every line names where it ran. Wall
times printed here are information only — this is not a benchmark.

    python chip_smoke.py                 # on the chip (fails without a TPU)
    python chip_smoke.py --tiny-cpu      # toy width on the host CPU; says so

The last line of standard output is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

PHASES = ("trainer", "server")
RESULT_PREFIX = "PHASE_RESULT "
# the whole run, compilation included, must end within 1200 s
BUDGET_S = 1140.0
SEED = 0
TRAIN_STEPS = 4
REPO = os.path.dirname(os.path.abspath(__file__))
TRAIN_CONFIG = os.path.join(REPO, "configs", "neox_1.3b_single_chip.json")

# (prompt length, new tokens) per request — four prefill buckets
# (16, 32, 64, 128) of the default doubling table
REQUESTS = ((12, 8), (30, 12), (7, 6), (100, 10), (45, 8))
# requests whose continuation is also generated by make_generator (each
# distinct (prompt, new) shape is a fresh compile there, so not all five)
PARITY_REQUESTS = (0, 2)


# ------------------------------------------------------------------ #
# phases (children: the only processes that touch JAX)
# ------------------------------------------------------------------ #


def _claim_device(tiny_cpu):
    """Initialise the backend; returns where this process runs. Without the
    explicit tiny flag, anything but a TPU is a failure."""
    import jax

    dev = jax.devices()[0]
    where = {"platform": dev.platform, "kind": dev.device_kind,
             "count": len(jax.devices())}
    if not tiny_cpu and dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform={dev.platform!r}, "
            f"device_kind={dev.device_kind!r}). --tiny-cpu runs the toy "
            "size on the host CPU and labels itself cpu.")
    return where


def _memory():
    """Per-device allocator numbers (GiB); {} where the backend has none."""
    import jax

    out = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        out.append({k: round(st[k] / 2**30, 3)
                    for k in ("bytes_in_use", "peak_bytes_in_use",
                              "bytes_limit") if k in st})
    return out


def _model_cfg(tiny_cpu, **overrides):
    from deeperspeed_tpu.models.gpt import GPTConfig, get_preset

    if tiny_cpu:
        return GPTConfig(vocab_size=256, n_layer=2, n_head=2, d_model=64,
                         max_seq=32, **overrides)
    return get_preset("neox-1.3b", max_seq=1024, **overrides)


def phase_trainer(args, say):
    import jax
    import numpy as np

    import deeperspeed_tpu as deepspeed
    from deeperspeed_tpu.models.gpt import make_gpt
    from deeperspeed_tpu.monitor import shutdown_monitor
    from deeperspeed_tpu.sharding import audit_tree

    t0 = time.perf_counter()
    cfg = _model_cfg(args.tiny_cpu, remat_policy="matmuls", ce_chunk=0)
    seq = cfg.max_seq
    with open(TRAIN_CONFIG) as f:
        ds_cfg = json.load(f)
    # the file's 1000-step log warm-up starts at lr 0 and stays under 4e-5
    # for the steps taken here — less than half a bf16 ulp of a typical
    # weight, so the masterless weights would not move. A falling loss
    # needs a plain lr; 1e-4 is the benchmark cell's.
    ds_cfg.pop("scheduler", None)
    ds_cfg["optimizer"]["params"]["lr"] = 1e-4
    ds_cfg["steps_per_print"] = 10**9
    # tracer on: the attention-dispatch instant is the Mosaic evidence;
    # strict watchdog: a recompile after warm-up raises
    ds_cfg["monitor"] = {"watchdog": "strict"}
    if args.zero_stage is not None:
        ds_cfg["zero_optimization"] = {"stage": args.zero_stage}

    init_fn, _, loss_fn, _ = make_gpt(cfg)
    params = init_fn(jax.random.PRNGKey(SEED))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    engine, _, _, _ = deepspeed.initialize(
        model=loss_fn, model_parameters=params, config=ds_cfg)
    del params
    micro = engine.train_micro_batch_size_per_gpu()
    gas = engine.gradient_accumulation_steps()
    dp = engine.data_parallel_size
    batch = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, size=(micro * gas * dp, seq + 1), dtype=np.int32)
    init_s = time.perf_counter() - t0
    say(f"model n_layer={cfg.n_layer} d_model={cfg.d_model} "
        f"n_head={cfg.n_head} vocab={cfg.vocab_size} seq={seq} "
        f"params={n_params:,}; micro={micro} gas={gas} dp={dp} "
        f"zero_stage={engine.zero_optimization_stage()} "
        f"mesh={dict(engine.mesh.shape)}; init {init_s:.1f}s")

    # a step is set-up when the train step's jit cache grew during it (the
    # first call, and the one retrace once the state commits to the mesh)
    losses, setup_s, step_s, cached = [], init_s, [], 0
    for i in range(TRAIN_STEPS):
        t = time.perf_counter()
        losses.append(float(jax.device_get(engine.train_batch(batch))))
        wall = time.perf_counter() - t
        n = engine.monitor.watchdog.counts()["engine/train_step"]
        compiled, cached = n > cached, n
        if compiled:
            setup_s += wall
        else:
            step_s.append(wall)
        say(f"step {i}: loss={losses[-1]:.4f} wall={wall:.2f}s"
            + (" (traced + compiled or loaded from the cache)"
               if compiled else ""))

    events = engine.monitor.tracer.events()
    impls = sorted({e["args"]["impl"] for e in events
                    if e["name"] == "kernels/attention_dispatch"})
    mem = _memory()
    shutdown_monitor()

    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall on a fixed batch: {losses}")
    if engine.global_steps != TRAIN_STEPS:
        raise AssertionError(
            f"global_steps={engine.global_steps}, expected {TRAIN_STEPS}")
    if not args.tiny_cpu and not impls:
        raise AssertionError(
            "no Mosaic attention kernel in the compiled train step: "
            "attn_impl='auto' gave way to the XLA reference")
    say(f"attention kernels in the compiled step: {impls or 'none (cpu)'}")

    result = {"losses": [round(x, 4) for x in losses],
              "micro": micro, "gas": gas, "dp": dp,
              "setup_s": round(setup_s, 1),
              "step_s": [round(x, 3) for x in step_s],
              "attention_impls": impls, "memory_gib": mem}
    if dp > 1 and engine.zero_optimization_stage() >= 1:
        # several chips: the optimizer state must really be spread out
        sharded = (engine.state.opt_state if engine.state.master is None
                   else (engine.state.master, engine.state.opt_state))
        aud = audit_tree(sharded, mesh=engine.mesh)
        in_use = [m.get("bytes_in_use", 0.0) for m in mem]
        say(f"zero placement: sharded_frac={aud['sharded_frac']:.3f} "
            f"bytes_in_use GiB per device={in_use}")
        if not aud["sharded_frac"] > 0.5:
            raise AssertionError(f"optimizer state not sharded: {aud}")
        if min(in_use) * 4 < max(in_use):
            raise AssertionError(
                f"device memory not of the same order: {in_use}")
        result["sharded_frac"] = round(aud["sharded_frac"], 4)
    return result


def phase_server(args, say):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeperspeed_tpu.models.generation import make_generator
    from deeperspeed_tpu.models.gpt import make_gpt
    from deeperspeed_tpu.monitor import shutdown_monitor
    from deeperspeed_tpu.serving import (ServingConfig, ServingEngine,
                                         idle_slots)

    t0 = time.perf_counter()
    cfg = _model_cfg(args.tiny_cpu)
    init_fn, _, _, _ = make_gpt(cfg)
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        init_fn(jax.random.PRNGKey(SEED)))
    scfg = ServingConfig(num_slots=4, block_size=16, num_blocks=257,
                         max_seq_len=256, max_new_tokens=16)
    eng = ServingEngine(cfg, params, scfg,
                        monitor_config={"watchdog": "strict"})
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n, _ in REQUESTS]
    news = [m for _, m in REQUESTS]

    # set-up: one warm-up request per prefill bucket the traffic will hit,
    # so every program (decode + the buckets) is compiled before serving
    for b in sorted({scfg.bucket_for(n) for n, _ in REQUESTS}):
        eng.submit(rng.integers(0, cfg.vocab_size, b - 1).tolist(),
                   max_new_tokens=2, request_id=f"warm-{b}")
    eng.run()
    setup_s = time.perf_counter() - t0
    say(f"model n_layer={cfg.n_layer} d_model={cfg.d_model} "
        f"n_head={cfg.n_head} vocab={cfg.vocab_size} params=bf16; "
        f"kv pool {(scfg.num_blocks - 1) * scfg.block_size} tokens, "
        f"{scfg.num_slots} slots; set-up (init + compiles) {setup_s:.1f}s")

    # staggered arrivals through the normal submit/step loop
    t1 = time.perf_counter()
    rids = [eng.submit(prompts[i], max_new_tokens=news[i]) for i in (0, 1)]
    eng.step()
    eng.step()
    rids += [eng.submit(prompts[i], max_new_tokens=news[i]) for i in (2, 3)]
    eng.step()
    rids.append(eng.submit(prompts[4], max_new_tokens=news[4]))
    outs = eng.run()
    serve_s = time.perf_counter() - t1

    for rid, want in zip(rids, news):
        req = eng.get(rid)
        got = len(outs.get(rid, ()))
        say(f"{rid}: prompt={len(req.prompt)} new={got}/{want} "
            f"finish={req.finish_reason}")
        if req.state != "finished" or got != want:
            raise AssertionError(
                f"{rid} ended {req.state!r} with {got} of {want} tokens")
    if eng.decode_compile_count != 1:
        raise AssertionError(
            f"decode compiled {eng.decode_compile_count} times, expected 1")
    live_frac = eng.metrics.summary()["kv_live_page_frac"]
    say(f"served {sum(news)} tokens over {len(rids)} requests in "
        f"{serve_s:.2f}s; decode_compile_count={eng.decode_compile_count} "
        f"prefill_compile_count={eng.prefill_compile_count} "
        f"kv_live_page_frac={live_frac:.4f}")

    # the decode program holds the donated pool ONCE: what the compiler
    # asks for it (an AOT lowering of the same shapes; the jit's one
    # compile is untouched) stays under 1.5 pools plus the weights. At
    # the toy width the slots' gathered views outweigh the pool itself,
    # so there the number is printed and not judged.
    from deeperspeed_tpu.monitor import extract_memory_analysis

    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (eng.params, eng.kv.k, eng.kv.v,
         idle_slots(scfg.num_slots, scfg.blocks_per_slot), eng._prev))
    ask = extract_memory_analysis(
        eng._decode_step.lower(*shapes).compile())["peak_bytes"]
    pool = eng.kv.k.nbytes + eng.kv.v.nbytes
    weights = sum(x.nbytes for x in jax.tree.leaves(params))
    say(f"decode program asks {ask / 2**20:.1f} MiB: pool "
        f"{pool / 2**20:.1f} MiB, weights {weights / 2**20:.1f} MiB")
    if not args.tiny_cpu and not ask < 1.5 * pool + weights:
        raise AssertionError(
            f"the decode program holds the pool more than once: asks "
            f"{ask:.0f} bytes, pool {pool}, weights {weights}")

    # parity with the dense-cache generator: reported, never a failure
    gen = make_generator(cfg)
    match = []
    for i in PARITY_REQUESTS:
        ref = np.asarray(gen(params, jnp.asarray([prompts[i]], jnp.int32),
                             max_new_tokens=news[i]))[0, len(prompts[i]):]
        same = int(sum(int(a) == int(b)
                       for a, b in zip(outs[rids[i]], ref.tolist())))
        match.append(f"{same}/{news[i]}")
        say(f"parity {rids[i]} vs make_generator: {same}/{news[i]} tokens")
    mem = _memory()
    shutdown_monitor()
    return {"requests": len(rids), "tokens": sum(news),
            "setup_s": round(setup_s, 1), "serve_s": round(serve_s, 2),
            "decode_compile_count": eng.decode_compile_count,
            "prefill_compile_count": eng.prefill_compile_count,
            "kv_live_page_frac": round(live_frac, 4),
            "decode_ask_mib": round(ask / 2**20, 1),
            "parity": match, "memory_gib": mem}


def run_phase(args):
    """Child entry: one phase, in this process. Raises on any failure."""
    if args.tiny_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    where = _claim_device(args.tiny_cpu)
    from deeperspeed_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    size = "tiny" if args.tiny_cpu else "neox-1.3b"
    tag = (f"[{args.phase} platform={where['platform']} "
           f"kind={where['kind']!r} n={where['count']} size={size}]")

    def say(msg):
        print(f"{tag} {msg}", flush=True)

    import jax
    import jaxlib

    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__}; "
        f"compile cache: {cache_dir or 'none (host cpu)'}")
    result = {"phase": args.phase, "device": where, "size": size,
              "compile_cache_dir": cache_dir}
    result.update({"trainer": phase_trainer,
                   "server": phase_server}[args.phase](args, say))
    peak = max((m.get("peak_bytes_in_use", 0.0)
                for m in result["memory_gib"]), default=0.0)
    say(f"PASS set-up {result['setup_s']}s; peak HBM "
        + (f"{peak:.2f} GiB" if peak else "not reported by this backend"))
    print(RESULT_PREFIX + json.dumps(result), flush=True)
    return 0


# ------------------------------------------------------------------ #
# parent: never touches JAX
# ------------------------------------------------------------------ #


def _child_cmd(phase, args):
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
    if args.tiny_cpu:
        cmd.append("--tiny-cpu")
    if args.zero_stage is not None:
        cmd += ["--zero-stage", str(args.zero_stage)]
    return cmd


def _spawn_phase(phase, args, deadline):
    """Run one phase in a child; echo its lines; return its result dict.
    The child is stopped if it outlives the budget or the parent is
    interrupted."""
    child = subprocess.Popen(_child_cmd(phase, args), stdout=subprocess.PIPE,
                             text=True, start_new_session=True)

    def stop(signum=None, frame=None):
        if child.poll() is None:
            os.killpg(child.pid, signal.SIGKILL)
        if signum is not None:
            sys.exit(128 + signum)

    old = {s: signal.signal(s, stop) for s in (signal.SIGTERM, signal.SIGINT)}
    timer = signal.signal(signal.SIGALRM, stop)
    signal.alarm(max(1, int(deadline - time.monotonic())))
    result = None
    try:
        for line in child.stdout:
            if line.startswith(RESULT_PREFIX):
                result = json.loads(line[len(RESULT_PREFIX):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = child.wait()
    finally:
        signal.alarm(0)
        stop()
        signal.signal(signal.SIGALRM, timer)
        for s, h in old.items():
            signal.signal(s, h)
    if rc != 0 or result is None:
        raise SystemExit(f"chip_smoke: phase {phase} failed (exit code {rc})")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny-cpu", action="store_true",
                    help="toy width on the host CPU (labels itself cpu); "
                         "the only mode that runs without a TPU")
    ap.add_argument("--phase", choices=PHASES, default=None,
                    help="run ONE phase in this process (what the parent "
                         "starts for each phase)")
    ap.add_argument("--zero-stage", type=int, default=None,
                    help="override the config file's ZeRO stage (the "
                         "four-chip trainer run uses 1)")
    args = ap.parse_args(argv)
    if args.phase is not None:
        return run_phase(args)

    deadline = time.monotonic() + BUDGET_S
    results = [_spawn_phase(p, args, deadline) for p in PHASES]
    devices = [r["device"] for r in results]
    if any(d != devices[0] for d in devices):
        raise SystemExit(f"chip_smoke: phases ran on different devices: "
                         f"{devices}")
    print(json.dumps({"phases": results}))
    print(json.dumps({"ok": True, "device": devices[0]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
