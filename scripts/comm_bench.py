"""Comm subsystem benchmark: bucketed quantized gradient collectives.

Evidence for the "comm" config block (runtime/comm/reducer.py). On the
virtual dp8 CPU mesh — the compiled program, not hardware, is the
evidence — this measures, per reduction mode:

  * **wire bytes** — the baseline engine's fused forward+grad program
    embeds one full-precision GSPMD all-reduce of every gradient, and
    the imperative ``forward()/backward()`` loop dispatches it once per
    microbatch.  The comm engine's forward program carries NO gradient
    collective (grads come back as per-device local stacks) and the
    GradReducer issues one bucketed reduction per accumulation cycle.
    Both sides are audited from compiled HLO with
    ``profiling/hlo_bytes.compiled_wire_bytes``; the analytic per-bucket
    model (``GradReducer.bucket_wire_bytes``) is reported alongside.
    Two ratios, both stated: ``reduce_only_x`` compares a single
    reduction (int8 two-phase moves ~2 bytes/elem vs fp32's ~7, so
    ~3.9x), and ``per_step_x`` compares a full gas-microbatch step
    (baseline all-reduces every microbatch, the reducer once — the
    DDP-bucketing framing; ~7.8x at gas=2).
  * **convergence smoke** — every mode trains the same MLP regression
    over the same batches; the quantized modes (with error feedback)
    must land within 1% of the fp32 final loss.
  * **step time** — fused ``train_batch`` mean wall time per mode.
  * **monitor wiring** — an imperative run with a "monitor" block must
    emit one ``comm/reduce`` span per bucket per cycle into a Chrome
    trace that passes ``python -m deeperspeed_tpu.monitor.validate
    --strict``, and the ``comm_buckets`` / ``comm_wire_bytes`` counters
    must land in the metrics registry.
  * **overlap fraction** — the monitor run happens twice, with the
    ``comm.overlap`` knob off and on.  The serial trace prices each
    reduction at its blocking dispatch cost; the overlapped trace only
    pays the ``comm/overlap_window`` drain at the accumulation
    boundary.  ``overlap_fraction = 1 - exposed/serial`` (see
    runtime/comm/overlap.py) must be > 0: the schedule provably hides
    comm behind backward even on this host.

Honesty notes baked into the output:

  * every mode carries ``wire_basis: "measured"`` (compiled-HLO bytes);
    when the analytic model disagrees (bf16: CPU lowering upcasts the
    collective operand to f32, doubling measured bytes) the entry says
    so in ``wire_caveat`` instead of silently preferring either number.
  * step times are medians, and the ``timing`` block states that on a
    single-core CPU mesh collectives are memcpys — quantization
    arithmetic here COSTS the time it SAVES on a real interconnect, so
    ``int8_vs_fp32_step`` is reported, not gated on.

Acceptance bar: int8 ``per_step_x`` >= 4 at gas=2 with loss delta < 1%,
strict-valid traces, and ``overlap_fraction`` > 0.
Results go where ``--out`` says (default: the git-ignored BENCH_comm.json
at the repo root).

``--onebit`` additionally regenerates ONEBIT_WIRE.json by delegating to
scripts/onebit_wire_bytes.py (the 1-bit momentum-exchange audit is a
sibling wire-format evidence with its own optimizer-state machinery).

Usage:
  python scripts/comm_bench.py [--steps 30] [--gas 2] [--out BENCH_comm.json]
  python scripts/comm_bench.py --onebit   # also refresh ONEBIT_WIRE.json
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

REEXEC_FLAG = "DS_COMM_BENCH_REEXEC"

WORLD = 8
MICRO = 4
DIMS = [64, 128, 128, 64]


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


def _init_mlp(seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    params = []
    for i in range(len(DIMS) - 1):
        d_in, d_out = DIMS[i], DIMS[i + 1]
        params.append({
            "w": (rng.normal(size=(d_in, d_out)) / np.sqrt(d_in)
                  ).astype(np.float32),
            "b": np.zeros((d_out,), np.float32),
        })
    return params


def _mlp_loss(params, batch):
    import jax.numpy as jnp

    x, y = batch
    h = x
    for i, layer in enumerate(params):
        h = h @ layer["w"] + layer["b"]
        if i < len(params) - 1:
            h = jnp.tanh(h)
    return jnp.mean((h - y) ** 2)


def _make_batches(n, rows, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    w = rng.normal(size=(DIMS[0], DIMS[-1])).astype(np.float32) / 8.0
    out = []
    for _ in range(n):
        x = rng.normal(size=(rows, DIMS[0])).astype(np.float32)
        out.append((x, (np.tanh(x) @ w).astype(np.float32)))
    return out


def _build_engine(comm, gas, monitor_trace=None):
    import deeperspeed_tpu as deepspeed

    cfg = {
        "train_micro_batch_size_per_gpu": MICRO,
        "gradient_accumulation_steps": gas,
        "train_batch_size": MICRO * gas * WORLD,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "steps_per_print": 10 ** 9,
        # auto routes the comm wire formats through the fused quantize/
        # dequant formulation (ops/pallas/fused_quant: XLA route on this
        # host, Pallas on TPU); bit-identical to the reference chain, so
        # losses stay comparable across the kernels knob
        "kernels": {"mode": "auto"},
    }
    if comm is not None:
        cfg["comm"] = comm
    if monitor_trace is not None:
        cfg["monitor"] = {"trace_path": monitor_trace}
    engine, _, _, _ = deepspeed.initialize(
        model=_mlp_loss, model_parameters=_init_mlp(), config_params=cfg)
    return engine


def measure_wire(comm, gas):
    """Compiled-HLO wire bytes for one engine: the per-microbatch
    forward+grad program and (comm engines) each bucket's reduction."""
    import jax

    from deeperspeed_tpu.profiling.hlo_bytes import compiled_wire_bytes

    engine = _build_engine(comm, gas)
    batch = _make_batches(1, MICRO * WORLD)[0]
    placed = engine._pack_pld(engine._place_batch(batch))
    rng = engine._rng_args()
    fwd = engine._forward_grad_fn()
    fwd_wire = int(compiled_wire_bytes(
        fwd, engine.state, placed, rng, world=WORLD)["wire_total"])
    entry = {"fwd_wire": fwd_wire}
    if engine.comm is not None:
        _, grads = fwd(engine.state, placed, rng)
        leaves = jax.tree.leaves(grads)
        reduce_wire = 0
        for j, b in enumerate(engine.comm.plan.buckets):
            reduce_wire += int(compiled_wire_bytes(
                engine.comm._bucket_reduce_fn(j),
                [leaves[i] for i in b.leaf_ids], engine._comm_state[j],
                world=WORLD)["wire_total"])
        modeled = engine.comm.total_wire_bytes()
        entry.update({
            "reduce_wire": reduce_wire,
            "modeled_reduce_wire": modeled,
            "n_buckets": engine.comm.n_buckets,
        })
        entry["per_step_wire"] = gas * fwd_wire + reduce_wire
        entry["wire_basis"] = "measured"
        if reduce_wire != modeled:
            entry["wire_caveat"] = (
                "compiled HLO disagrees with the analytic model: CPU "
                "lowering upcasts the collective operand to f32 (bf16 "
                "wire doubles); modeled_reduce_wire is what the "
                "TPU-native collective moves")
    else:
        # the baseline all-reduces every microbatch's grads
        entry["per_step_wire"] = gas * fwd_wire
        entry["wire_basis"] = "measured"
    return entry


def convergence_and_steptime(comm, gas, steps, warmup=3):
    import numpy as np

    engine = _build_engine(comm, gas)
    data = _make_batches(steps + warmup, MICRO * gas * WORLD, seed=1)
    losses, times = [], []
    for i, b in enumerate(data):
        t0 = time.perf_counter()
        loss = float(engine.train_batch(b))
        dt = time.perf_counter() - t0
        if i >= warmup:
            losses.append(loss)
            times.append(dt)
    # median, not mean: single measured steps on a shared CPU host see
    # +-50% scheduler noise that a mean folds straight into the ratio
    return {
        "final_loss": losses[-1],
        "step_ms": round(float(np.median(times)) * 1e3, 3),
    }


def spans_and_metrics(comm, gas, cycles, workdir, overlap="off"):
    """Imperative run under a monitor block: comm/reduce spans must land
    in a strict-schema-valid trace, counters in the registry.  Returns
    ``(summary, trace_events)`` so the caller can pair an overlap-off
    trace with an overlap-on one for the overlap_fraction computation."""
    from deeperspeed_tpu.monitor import get_monitor, shutdown_monitor

    trace_path = os.path.join(workdir, f"trace_comm_{overlap}.json")
    engine = _build_engine(dict(comm, overlap=overlap), gas,
                           monitor_trace=trace_path)
    data = _make_batches(cycles * gas, MICRO * WORLD, seed=2)
    try:
        for c in range(cycles):
            for m in range(gas):
                engine(data[c * gas + m])
                engine.backward(allreduce_gradients=False)
                engine.step()
        n_buckets = engine.comm.n_buckets
        reg = get_monitor().registry
        counters = {
            "comm_buckets": reg.counter("comm_buckets").value,
            "comm_wire_bytes": reg.counter("comm_wire_bytes").value,
        }
    finally:
        shutdown_monitor()
    proc = subprocess.run(
        [sys.executable, "-m", "deeperspeed_tpu.monitor.validate",
         "--strict", trace_path], capture_output=True, text=True)
    with open(trace_path) as f:
        raw = json.load(f)
    events = raw["traceEvents"] if isinstance(raw, dict) else raw
    spans = [e for e in events
             if e.get("name") == "comm/reduce" and e.get("ph") == "X"]
    windows = [e for e in events
               if e.get("name") == "comm/overlap_window"]
    summary = {
        "overlap": overlap,
        "validate_rc": proc.returncode,
        "validate_errors": (proc.stderr.strip().splitlines()[:5]
                            if proc.returncode else []),
        "comm_reduce_spans": len(spans),
        "expected_spans": n_buckets * cycles,
        "overlapped_spans": sum(
            1 for e in spans if e.get("args", {}).get("overlapped")),
        "overlap_windows": len(windows),
        "counters": counters,
    }
    return summary, events


def main():
    _reexec_if_needed()
    import tempfile

    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--gas", type=int, default=2)
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_comm.json"))
    ap.add_argument("--onebit", action="store_true",
                    help="also regenerate ONEBIT_WIRE.json (delegates to "
                         "scripts/onebit_wire_bytes.py)")
    ap.add_argument("--onebit-args", default="--models tiny",
                    help="extra args for the onebit delegation")
    args = ap.parse_args()

    import numpy as np

    gas = args.gas
    MODES = {
        "fp32": {"mode": "fp32", "bucket_mb": 0.05},
        "bf16": {"mode": "bf16", "bucket_mb": 0.05},
        "int8": {"mode": "int8", "bucket_mb": 0.05},
        "int8_hier": {"mode": "int8", "bucket_mb": 0.05,
                      "hierarchical": "on", "intra_size": 4},
        "compressed": {"mode": "compressed", "bucket_mb": 0.05},
    }

    n_params = sum(int(np.prod(np.asarray(p).shape))
                   for layer in _init_mlp() for p in layer.values())
    result = {"mesh": f"dp{WORLD}", "world": WORLD, "gas": gas,
              "n_params": n_params, "modes": {}}

    base = measure_wire(None, gas)
    base.update(convergence_and_steptime(None, gas, args.steps))
    result["modes"]["baseline"] = base
    print("baseline", json.dumps(base), flush=True)

    for name, comm in MODES.items():
        entry = measure_wire(comm, gas)
        entry.update(convergence_and_steptime(comm, gas, args.steps))
        entry["reduce_only_x"] = round(
            base["fwd_wire"] / max(entry["reduce_wire"], 1), 2)
        entry["per_step_x"] = round(
            base["per_step_wire"] / max(entry["per_step_wire"], 1), 2)
        entry["loss_delta_pct"] = round(
            abs(entry["final_loss"] - base["final_loss"])
            / abs(base["final_loss"]) * 100, 4)
        result["modes"][name] = entry
        print(name, json.dumps(entry), flush=True)
        with open(args.out, "w") as f:  # persist after every entry
            json.dump(result, f, indent=1)

    from deeperspeed_tpu.ops.pallas import fused_quant
    from deeperspeed_tpu.runtime.comm import overlap as comm_overlap

    result["kernels"] = {"mode": "auto",
                         "fused_quant_route": fused_quant.routing()[0]}

    with tempfile.TemporaryDirectory() as workdir:
        mon, serial_events = spans_and_metrics(
            MODES["int8"], gas, cycles=3, workdir=workdir, overlap="off")
        mon_on, overlap_events = spans_and_metrics(
            MODES["int8"], gas, cycles=3, workdir=workdir, overlap="on")
    result["monitor"] = mon
    stats_off = comm_overlap.reduce_span_stats(serial_events)
    stats_on = comm_overlap.reduce_span_stats(overlap_events)
    result["overlap"] = {
        "off": mon,
        "on": mon_on,
        "serial_reduce_ms": round(stats_off["reduce_ms"], 3),
        "exposed_window_ms": round(stats_on["window_ms"], 3),
        "overlap_fraction": round(
            comm_overlap.overlap_fraction(serial_events, overlap_events),
            4),
    }
    print("monitor", json.dumps(result["monitor"]), flush=True)
    print("overlap", json.dumps(result["overlap"]), flush=True)

    i8 = result["modes"]["int8"]
    fp32_ms = result["modes"]["fp32"]["step_ms"]
    result["timing"] = {
        "basis": "wall_clock_median",
        "int8_vs_fp32_step": round(i8["step_ms"] / fp32_ms, 3),
        "caveat": (
            "single-core host, 8 virtual XLA devices: collectives are "
            "memcpys here, so the quantize/dequant arithmetic COSTS the "
            "wall time it SAVES on a real interconnect; the wire ratios "
            "above are the transferable evidence, this ratio is the "
            "honest local reading"),
    }
    ovl = result["overlap"]
    result["pass"] = bool(
        i8["per_step_x"] >= 4.0
        and i8["loss_delta_pct"] < 1.0
        and mon["validate_rc"] == 0
        and ovl["on"]["validate_rc"] == 0
        and mon["comm_reduce_spans"] == mon["expected_spans"]
        and ovl["on"]["comm_reduce_spans"] == ovl["on"]["expected_spans"]
        and ovl["on"]["overlapped_spans"] == ovl["on"]["comm_reduce_spans"]
        and mon["counters"]["comm_buckets"] > 0
        and ovl["overlap_fraction"] > 0.0)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"pass": result["pass"],
                      "int8_per_step_x": i8["per_step_x"],
                      "int8_reduce_only_x": i8["reduce_only_x"],
                      "int8_loss_delta_pct": i8["loss_delta_pct"],
                      "overlap_fraction": ovl["overlap_fraction"],
                      "int8_vs_fp32_step":
                          result["timing"]["int8_vs_fp32_step"]}),
          flush=True)

    if args.onebit:
        rc = subprocess.call(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "onebit_wire_bytes.py")]
            + args.onebit_args.split())
        print(f"onebit delegation rc={rc}", flush=True)
        if rc:
            sys.exit(rc)
    if not result["pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
