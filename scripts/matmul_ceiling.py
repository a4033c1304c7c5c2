"""Per-shape MXU throughput microbench: the ceilings behind the MFU notes.

GPT-125M sits at ~35% MFU while GPT-NeoX 1.3B reaches ~59% on the same
chip and framework. This script demonstrates why with three chained-matmul
shape classes at each model width (timed inside one jit; best-of-3 windows;
values forced via device_get):

  square  — (M, D) @ (D, D): the attention-projection shape class
  ffn     — (M, D) @ (D, 4D) @ (4D, D): the MLP block
  logits  — (M, D) @ (D, 50304) and back: the vocabulary projection

An earlier installation's run (2026-07, MATMUL_CEILING.json; not
re-measured on the current one): D=768
square/ffn cap at ~11/43 TFLOPS (narrow reduction/output dims underfeed
the MXU) while the wide-N logits shape reaches ~94 TF — so the 125M layer
stack is shape-limited, not framework-limited, and its ~68 TF overall is
ABOVE its layer-shape ceiling thanks to the logits matmul. At D=2048 the
same classes reach ~50/137/124 TF, which is why the 1.3B run sustains
117 TF. (Compare shapes within one run only.)

Usage: python scripts/matmul_ceiling.py [--dims 768,2048]
Writes MATMUL_CEILING.json at the repo root.
"""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 50304


def _time_chain(x, weights, flops_per_step, steps):
    @jax.jit
    def chain(x, *ws):
        def body(h, _):
            for w in ws:
                h = jax.lax.dot_general(
                    h, w, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).astype(jnp.bfloat16)
            return h, None

        out, _ = jax.lax.scan(body, x, None, length=steps)
        return jnp.sum(out.astype(jnp.float32))

    float(jax.device_get(chain(x, *weights)))  # compile + warm
    best = float("inf")
    for i in range(3):
        t0 = time.perf_counter()
        float(jax.device_get(chain(x + jnp.bfloat16(i), *weights)))
        best = min(best, time.perf_counter() - t0)
    return flops_per_step * steps / best / 1e12


def _w(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.bfloat16) * 0.02


def measure(D: int, M: int = 32768):
    x = jax.random.normal(jax.random.PRNGKey(0), (M, D), jnp.bfloat16)
    square = _time_chain(x, [_w(1, (D, D))], 2 * M * D * D, steps=32)
    ffn = _time_chain(
        x, [_w(1, (D, 4 * D)), _w(2, (4 * D, D))],
        2 * (2 * M * D * 4 * D), steps=16)
    ml = min(M, 12288)  # logits activations are fp32-heavy; cap M
    xl = x[:ml]
    logits = _time_chain(
        xl, [_w(1, (D, VOCAB)), _w(2, (VOCAB, D))],
        2 * (2 * ml * D * VOCAB), steps=8)
    return {"square": round(square, 1), "ffn": round(ffn, 1),
            "logits": round(logits, 1),
            "M": {"square": M, "ffn": M, "logits": ml}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dims", default="768,2048")
    args = ap.parse_args()
    out = {"platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind,
           "tflops_by_shape": {}}
    for D in (int(d) for d in args.dims.split(",")):
        r = measure(D)
        out["tflops_by_shape"][str(D)] = r
        print(f"D={D}: {r}", flush=True)
    path = os.path.join(REPO, "MATMUL_CEILING.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
