"""Datapipe benchmark: host-blocked input time, prefetch off vs on.

Measures what the datapipe exists to remove: the host time each
training step spends blocked waiting for its input batch (index gather
+ collation + curriculum masking + device staging). Two identical
training runs over the same synthetic token corpus:

  * ``prefetch off`` — the step loop produces every batch inline; the
    per-step stall is the full production cost.
  * ``prefetch on``  — the async producer thread builds and stages the
    next global batch while the current step runs; the stall collapses
    to a queue pop.

Acceptance bar: total host-blocked time with prefetch on is < 50% of
the inline run (in practice it is a few percent once the producer keeps
ahead). The prefetch-on run also exercises the monitor wiring end to
end: ``datapipe/wait`` spans land in a Chrome trace which is validated
with the ``monitor.validate`` CLI, and the ``datapipe_*`` gauges must
show up in the metrics registry.

Results go where ``--out`` says (default: the git-ignored
BENCH_datapipe.json at the repo root). Runs anywhere (CI
included) in well under a minute on CPU; export JAX_PLATFORMS=tpu to
measure real device staging.

Usage:
  python scripts/datapipe_bench.py [--steps 24] [--rows 256] \
      [--seq-len 512] [--out BENCH_datapipe.json]
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the bench targets the host CPU mesh by design (the acceptance surface
# for input-pipeline work without a chip)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def _make_corpus(path, n_windows, seq_len):
    rng = np.random.Generator(np.random.Philox(key=7))
    tokens = rng.integers(0, 50000, size=n_windows * (seq_len + 1),
                          dtype=np.uint16)
    np.save(path, tokens)
    return path


def run_mode(prefetch, corpus, workdir, steps, rows, seq_len, warmup=3):
    """One full engine run; returns per-step host-stall stats."""
    import jax.numpy as jnp
    import deeperspeed_tpu as deepspeed
    from deeperspeed_tpu.monitor import get_monitor, shutdown_monitor

    mode = "on" if prefetch else "off"
    trace_path = os.path.join(workdir, f"trace_prefetch_{mode}.json")
    cfg = {
        "train_batch_size": rows,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "datapipe": {"source": corpus, "seq_len": seq_len, "seed": 1,
                     "prefetch": prefetch, "prefetch_depth": 2},
        "monitor": {"trace_path": trace_path},
    }

    def loss_fn(p, b):
        return jnp.mean((b.astype(jnp.float32) @ p["w"]) ** 2)

    params = {"w": jnp.zeros((seq_len + 1, 1024), jnp.float32)}
    engine, _, _, _ = deepspeed.initialize(
        model=loss_fn, model_parameters=params, config_params=cfg)
    try:
        for _ in range(warmup):  # compile + fill the prefetch queue
            engine.train_batch()
        stalls = []
        t0 = time.perf_counter()
        for _ in range(steps):
            engine.train_batch()
            stalls.append(engine.datapipe.last_stall_seconds)
        wall = time.perf_counter() - t0
        mon = get_monitor()
        metric_names = sorted(n for n in mon.registry.collect()
                              if n.startswith("datapipe_"))
    finally:
        engine.datapipe.close()
        shutdown_monitor()
    stalls = np.asarray(stalls)
    return {
        "prefetch": prefetch,
        "steps": steps,
        "host_blocked_total_s": round(float(stalls.sum()), 6),
        "host_blocked_mean_ms": round(float(stalls.mean()) * 1e3, 4),
        "host_blocked_max_ms": round(float(stalls.max()) * 1e3, 4),
        "wall_s": round(wall, 4),
        "trace_path": trace_path,
        "datapipe_metrics": metric_names,
    }


def validate_trace(trace_path):
    """Schema-check the trace with the monitor.validate CLI and confirm
    the datapipe/wait spans actually landed in it."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "deeperspeed_tpu.monitor.validate",
         trace_path],
        env=env, capture_output=True, text=True, timeout=120)
    with open(trace_path) as f:
        data = json.load(f)
    events = data["traceEvents"] if isinstance(data, dict) else data
    has_wait_spans = any(ev.get("name") == "datapipe/wait"
                         for ev in events)
    return {
        "validate_rc": proc.returncode,
        "validate_errors": proc.stderr.strip().splitlines()[:5],
        "has_datapipe_wait_spans": has_wait_spans,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=24,
                    help="measured steps per mode (after warmup)")
    ap.add_argument("--rows", type=int, default=256,
                    help="global batch rows (train_batch_size)")
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--windows", type=int, default=2048,
                    help="corpus size in seq_len+1 windows")
    ap.add_argument("--max-stall-ratio", type=float, default=0.5)
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "BENCH_datapipe.json"))
    args = ap.parse_args()

    work = tempfile.mkdtemp(prefix="datapipe_bench_")
    try:
        corpus = _make_corpus(os.path.join(work, "corpus.npy"),
                              args.windows, args.seq_len)
        off = run_mode(False, corpus, work, args.steps, args.rows,
                       args.seq_len)
        on = run_mode(True, corpus, work, args.steps, args.rows,
                      args.seq_len)
        trace = validate_trace(on["trace_path"])

        ratio = (on["host_blocked_total_s"]
                 / max(off["host_blocked_total_s"], 1e-12))
        expected_metrics = {"datapipe_host_stall_seconds",
                            "datapipe_queue_depth",
                            "datapipe_batches_total"}
        metrics_ok = expected_metrics.issubset(set(on["datapipe_metrics"]))
        ok = (ratio < args.max_stall_ratio
              and trace["validate_rc"] == 0
              and trace["has_datapipe_wait_spans"]
              and metrics_ok)

        report = {
            "pass": bool(ok),
            "stall_ratio": round(ratio, 4),
            "max_stall_ratio": args.max_stall_ratio,
            "prefetch_off": off,
            "prefetch_on": on,
            "trace": trace,
            "metrics_registered": metrics_ok,
            "config": {"steps": args.steps, "rows": args.rows,
                       "seq_len": args.seq_len, "windows": args.windows},
        }
        for mode in (off, on):
            mode.pop("trace_path", None)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)

        print(f"host-blocked per step: inline "
              f"{off['host_blocked_mean_ms']:.2f} ms -> prefetch "
              f"{on['host_blocked_mean_ms']:.2f} ms "
              f"(ratio {ratio:.3f}, bar {args.max_stall_ratio})")
        print(f"trace valid: rc={trace['validate_rc']}, datapipe/wait "
              f"spans: {trace['has_datapipe_wait_spans']}; metrics "
              f"registered: {metrics_ok}")
        print(f"wrote {args.out}")
        if not ok:
            print("FAIL: datapipe bench did not meet the acceptance bar",
                  file=sys.stderr)
            return 1
        print("datapipe bench PASSED")
        return 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
