"""In-situ step-time ablation for the 1.3B flagship (VERDICT r3 item 1).

MFU_DECOMP.json gives the composite-unit floor; this script attributes the
remaining in-engine residual by timing the ACTUAL model functions (not
isolated units) under controlled variants:

  fwd        — jit(loss_fn) per micro
  fwdbwd     — jit(value_and_grad(loss_fn)) per micro
  variants   — attention impl (flash vs xla), remat policy, ce_chunk

The fwd/bwd split shows whether the gap is forward elementwise (paid once)
or backward replay (paid under remat). Usage:
  python scripts/step_ablation.py [--micro 2] [--seq 1024] [--steps 20]

--floor MFU_DECOMP.json additionally prints the composite-unit floor for
the preset and each variant's residual (measured fwdbwd − floor): the ms
the framework pays above raw matmul+attention+head compute. This is the
number the fused kernel layer (ops/pallas/fused_blocks.py etc.) exists to
shrink — rerun with and without the "kernels" block and diff residuals.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _sync(out):
    """A scalar device_get is the barrier (same pattern as bench.py).
    Executions are in-order per device, so fetching one leaf of the LAST
    output waits for the whole queue."""
    jax.device_get(jax.tree.leaves(out)[0])


def time_fn(fn, args, steps, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--micro", type=int, default=2)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--preset", default="neox-1.3b")
    ap.add_argument(
        "--variants",
        default="base,xla_attn,ce128,dots_all",
        help="comma list: base, xla_attn, ce128, ce0, dots_all, flash_policy",
    )
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON with one span per "
                         "timed variant (open in Perfetto)")
    ap.add_argument("--floor", default=None, metavar="MFU_DECOMP.json",
                    help="print the composite-unit floor for this preset "
                         "and each variant's residual (fwdbwd_ms - "
                         "micro_step_floor_ms)")
    args = ap.parse_args()

    from deeperspeed_tpu.models.gpt import get_preset, make_gpt
    from deeperspeed_tpu.monitor import init_monitor, shutdown_monitor
    from deeperspeed_tpu.monitor.tracer import trace_span

    if args.trace is not None:
        init_monitor({"trace_path": args.trace})

    KNOWN = ("base", "xla_attn", "ce128", "ce0", "dots_all", "flash_policy",
             "no_rotary", "no_remat")

    def cfg_for(variant):
        if variant not in KNOWN:
            raise SystemExit(f"unknown variant {variant!r}; choose from {KNOWN}")
        kw = dict(remat=True, remat_policy="matmuls", ce_chunk=0,
                  max_seq=args.seq)
        if variant == "xla_attn":
            kw["attn_impl"] = "xla"
        elif variant == "ce128":
            kw["ce_chunk"] = 128
        elif variant == "dots_all":
            kw["remat_policy"] = "dots_all"
        elif variant == "flash_policy":
            kw["remat_policy"] = "flash"
        elif variant == "no_rotary":
            # attribution only (different model: learned positions instead
            # of rotary trig on q/k) — the delta bounds rotary's step cost
            kw["rotary"] = False
        elif variant == "no_remat":
            kw["remat"] = False
        return get_preset(args.preset, **kw)

    rng = np.random.default_rng(0)
    batch = jnp.asarray(
        rng.integers(0, 50304, size=(args.micro, args.seq + 1), dtype=np.int32)
    )
    out = {"preset": args.preset, "micro": args.micro, "seq": args.seq,
           "platform": jax.devices()[0].platform,
           "device": str(jax.devices()[0].device_kind), "variants": {}}

    base_params = None
    for variant in args.variants.split(","):
        variant = variant.strip()
        cfg = cfg_for(variant)
        init_fn, _, loss_fn, _ = make_gpt(cfg)
        if base_params is None:
            base_params = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16), init_fn(jax.random.PRNGKey(0))
            )
        params = base_params

        fwd = jax.jit(loss_fn)
        with trace_span(f"ablation/{variant}/fwd", lane="engine",
                        steps=args.steps):
            t_fwd = time_fn(fwd, (params, batch), args.steps)

        grad = jax.jit(jax.value_and_grad(loss_fn))
        with trace_span(f"ablation/{variant}/fwdbwd", lane="engine",
                        steps=args.steps):
            t_fb = time_fn(grad, (params, batch), args.steps)

        out["variants"][variant] = {
            "fwd_ms": round(t_fwd * 1e3, 2),
            "fwdbwd_ms": round(t_fb * 1e3, 2),
            "bwd_over_fwd": round((t_fb - t_fwd) / t_fwd, 2),
        }
        print(variant, json.dumps(out["variants"][variant]), flush=True)

    if args.floor is not None:
        _print_floor_residuals(args, out)

    if args.trace is not None:
        out["trace"] = args.trace
        shutdown_monitor(save=True)
    print(json.dumps(out))


# preset name -> MFU_DECOMP.json top-level key; unlisted presets are
# looked up by their own name so new decomp entries need no code change
_FLOOR_PRESET_KEYS = {"neox-1.3b": "1.3b"}


def _print_floor_residuals(args, out):
    with open(args.floor) as f:
        decomp = json.load(f)
    key = _FLOOR_PRESET_KEYS.get(args.preset, args.preset)
    if key not in decomp or "micro_step_floor_ms" not in decomp[key]:
        known = sorted(k for k, v in decomp.items()
                       if isinstance(v, dict) and "micro_step_floor_ms" in v)
        raise SystemExit(
            f"--floor: no floor entry {key!r} in {args.floor}; "
            f"available: {known}")
    entry = decomp[key]
    floor_ms = entry["micro_step_floor_ms"]
    units = entry.get("units_fwdbwd", {})
    # floor = L * (matmul chain + attention) + vocab head; recover L so
    # the per-unit composition prints in step-ms, not per-layer-ms
    per_layer = (units.get("layer_matmul_chain", {}).get("ms", 0.0)
                 + units.get("attention_core", {}).get("ms", 0.0))
    head_ms = units.get("vocab_head", {}).get("ms", 0.0)
    layers = round((floor_ms - head_ms) / per_layer) if per_layer else 0
    print(f"floor[{key}]: micro_step_floor_ms={floor_ms} "
          f"({entry.get('micro_step_floor_tflops')} TF on "
          f"{entry.get('device')})")
    for name, u in units.items():
        detail = ""
        if "impl" in u:
            detail = f" impl={u['impl']} geometry={tuple(u['geometry'])}"
        mult = f" x {layers} layers" if name != "vocab_head" else ""
        print(f"  unit {name}:{detail} {u.get('ms')} ms{mult} "
              f"({u.get('tflops')} TF)")
    if out["platform"] != entry.get("platform", "tpu"):
        print(f"  NOTE: floor measured on {entry.get('platform')!r} but "
              f"this run is on {out['platform']!r} — residuals are not "
              "meaningful off-device")
    out["floor"] = {"key": key, "micro_step_floor_ms": floor_ms,
                    "layers": layers}
    for variant, r in out["variants"].items():
        resid = r["fwdbwd_ms"] - floor_ms
        r["residual_ms"] = round(resid, 2)
        r["residual_frac"] = round(resid / floor_ms, 4)
        print(f"residual {variant}: {r['fwdbwd_ms']} ms fwdbwd - "
              f"{floor_ms} ms floor = {r['residual_ms']:+.2f} ms "
              f"({100 * r['residual_frac']:+.1f}% of floor)")


if __name__ == "__main__":
    main()
