"""MoE dispatch microbench: dense one-hot vs sorted scatter, sweeping E.

Demonstrates the dispatch-cost scaling that motivates
MoEConfig.dispatch_impl="sorted" (see models/moe.py): at GShard capacity
(C ~ kT/E) the dense one-hot dispatch/combine einsums cost O(T^2 k D)
regardless of E, while the sorted path costs O(T k (log Tk + D)).

Run on the real chip (default env) or CPU. Timing discipline against
per-call dispatch overhead: each measurement scans STEPS applications
inside one jit and times the whole program.

Usage: python scripts/moe_dispatch_bench.py [--experts 8,16,32,64]
"""

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeperspeed_tpu.models.moe import MoEConfig, init_moe_params, moe_ffn  # noqa: E402

STEPS = 8


def bench_one(E: int, impl: str, T: int = 4096, D: int = 512, F: int = 2048,
              k: int = 2) -> float:
    cfg = MoEConfig(num_experts=E, top_k=k, dispatch_impl=impl)
    params = init_moe_params(jax.random.PRNGKey(0), D, F, cfg)
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, T // 8, D), jnp.bfloat16)

    @jax.jit
    def run(params, x):
        def body(h, _):
            y, _aux = moe_ffn(params, h, cfg)
            return y, None

        out, _ = jax.lax.scan(body, x, None, length=STEPS)
        return jnp.sum(out.astype(jnp.float32))

    run(params, x).block_until_ready()  # compile + warm
    best = float("inf")
    for i in range(3):
        # fresh input each round: device_get forces the value
        xi = x + jnp.bfloat16(i)
        t0 = time.perf_counter()
        float(jax.device_get(run(params, xi)))
        best = min(best, time.perf_counter() - t0)
    return best / STEPS


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", default="8,16,32,64")
    args = ap.parse_args()
    Es = [int(e) for e in args.experts.split(",")]
    print(f"platform={jax.devices()[0].platform} T=4096 D=512 F=2048 k=2")
    print(f"{'E':>4} {'dense ms':>10} {'sorted ms':>10} {'speedup':>8}")
    for E in Es:
        d = bench_one(E, "dense") * 1e3
        s = bench_one(E, "sorted") * 1e3
        print(f"{E:>4} {d:>10.2f} {s:>10.2f} {d / s:>8.2f}x")


if __name__ == "__main__":
    main()
