#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it claims the chip (and ends with a code other than 0 and no
result where JAX finds no TPU or fewer chips than the cell asks for),
makes weights and inputs from ``--seed``, warms exactly the cell's shapes
(set-up), measures for ``--seconds``, checks what the timed path produced
against the plain reference, and prints ONE JSON object as the last line
of its standard output. With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.
"""

import time

T_START = time.perf_counter()   # process start, as near as Python gets

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402
import types         # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import manifest as mf   # noqa: E402


def build_context(man, cell_name, seed, seconds, trace, devices, device_desc,
                  say, t_start=None) -> types.SimpleNamespace:
    """Everything a runner needs, found by the names in BENCHMARK.json.
    ``t_start`` is where ``setup_s`` counts from."""
    from benchmark import profiling

    cell = man.cell(cell_name)
    cell_file = man.workload_file(cell_name)
    config = man.config(cell["config"])
    return types.SimpleNamespace(
        manifest=man, cell=cell, cell_file=cell_file, config=config,
        traffic=man.traffic(cell["traffic"]),
        adapter=importlib.import_module(f"benchmark.adapters.{config['family']}"),
        seed=int(seed), seconds=float(seconds), trace=bool(trace),
        devices=devices, device=device_desc, say=say,
        t_start=time.perf_counter() if t_start is None else t_start,
        spans=profiling.Spans(), notes=[], dump=None,
        profiler=profiling.Profiler(cell_name) if trace else None)


def run_cell(ctx) -> dict:
    """Drive the runner and reduce what it gathered to the result line."""
    from benchmark import profiling

    runner = importlib.import_module(f"benchmark.runners.{ctx.cell_file['runner']}")
    out = runner.run(ctx)
    man, name = ctx.manifest, ctx.cell["name"]
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {},
              "device": dict(ctx.device, memory_peak_bytes=out["memory_peak_bytes"])}
    if not ctx.trace:
        for m in man.metrics_for(name, "end_to_end"):
            result["metrics"][m["name"]] = {"value": out["end_to_end"][m["name"]],
                                            "unit": m["unit"]}
        return result
    run = {"spans": ctx.spans, "device": ctx.device, "notes": ctx.notes,
           "attention": out.get("attention"), "cell": ctx.cell_file,
           "trace": profiling.traced_run(ctx.profiler.events(), len(ctx.devices))}
    if ctx.dump:
        profiling.dump(run["trace"], ctx.dump)
    for m in man.metrics_for(name, "per_layer"):
        spec = man.metric_file(m["name"])
        reader = importlib.import_module(f"benchmark.reducers.{spec['reducer']}")
        value = reader.read(run, spec.get("params", {}))
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    for note in ctx.notes:
        ctx.say(note)
    result["device"].update(busy_s=run["trace"]["busy_s"],
                            window_s=run["trace"]["window_s"])
    result["breakdown"] = profiling.breakdown(run["trace"])
    return result


def open_context(workload, seed, seconds, trace, say) -> types.SimpleNamespace:
    """Claim the cell's chips (the process ends without a TPU), place the
    compile cache and build the cell's context from ``BENCHMARK.json``."""
    from benchmark import device

    man = mf.Manifest()
    cell = man.cell(workload)
    desc = device.claim(cell["chips"], say)
    # setup_s counts from here. The interpreter's start, the import of jax
    # and the TPU runtime's start come before: 8 to 25 s on one machine from
    # run to run (PR 23), nothing this repository's code can move.
    t_claimed = time.perf_counter()
    import jax

    cache = device.place_compile_cache()
    say(f"device {desc}, claimed {t_claimed - T_START:.1f} s after the process "
        f"started (not in setup_s); jax {jax.__version__}; compile cache {cache}")
    return build_context(man, workload, seed, seconds, trace,
                         jax.devices()[:cell["chips"]], desc, say, t_claimed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default=None, metavar="FILE",
                    help="with --trace 1: also write the traced events as "
                         "JSON (gzip), as fixtures are made")
    args = ap.parse_args(argv)

    mf.Manifest().cell(args.workload)
    # the program's code must be there: the benchmark alone measures nothing
    if not os.path.isdir(os.path.join(mf.ROOT, "deeperspeed_tpu")):
        sys.exit("benchmark: the program (deeperspeed_tpu/) is not in this "
                 "checkout; there is nothing to measure")

    def say(msg):
        print(f"[{args.workload} seed={args.seed} +{time.perf_counter() - T_START:.1f}s] "
              f"{msg}", flush=True)

    ctx = open_context(args.workload, args.seed, args.seconds, args.trace, say)
    ctx.dump = args.dump
    result = run_cell(ctx)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
