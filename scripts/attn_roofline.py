"""VPU roofline proof for Dh=64 attention (VERDICT r4 weak #2).

The claim to prove or refute: at BERT geometries (Dh=64), the ~50 TF
attention-core ceiling is VPU-bound (softmax elementwise work), not
kernel-iteration-bound — so no fused kernel can beat it by much and the
honest MFU floor for BERT moves.

Method (chained-scan differenced timing, the MFU_DECOMP methodology):
  matmul_only — the attention GEMM pair (q@k^T -> p@v) with NO softmax
                (a jnp.tanh stand-in scaled to ~2 VPU ops, preventing
                XLA from collapsing the chain) — the MXU-side floor.
  softmax_only — exp/max/sum/div over the (B,H,S,S) score tensor — the
                VPU-side floor at this score-tensor size.
  full_xla    — the real XLA attention (what attn_impl='auto' runs at
                S<=256).
  full_flash_v1 / full_static — the two Pallas kernels, each forced
                explicitly (the auto dispatch would hide which ran).

If t(full) ~= max-ish combination of t(matmul_only) and t(softmax_only),
the ceiling is arithmetic-bound (VPU dominating at Dh=64 where the
score tensor is as large as the compute is small), and no kernel
restructuring recovers it; the gap to peak is then a property of the
geometry, not the framework. Writes ATTN_ROOFLINE.json.

Usage: python scripts/attn_roofline.py [--geom bert128 bert512]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

GEOMS = {
    # (B, H, S, Dh, causal)
    "bert128": (64, 16, 128, 64, False),
    "bert512": (16, 16, 512, 64, False),
    "gpt1k_dh128": (2, 16, 1024, 128, True),
}


def _time_chained(make_step, x0, steps_a=8, steps_b=32):
    """Differenced chained-scan timing: run scan of N dependent steps for
    two lengths; (t_b - t_a) / (b - a) cancels dispatch + fixed costs.
    Pallas legs must keep steps_b <= 24 (longer chains explode Mosaic
    compile time)."""

    def runner(n):
        @jax.jit
        def run(x):
            def body(c, _):
                return make_step(c), None

            out, _ = jax.lax.scan(body, x, None, length=n)
            return jax.tree.leaves(out)[0].astype(jnp.float32).sum()

        # warmup (compile + allocator)
        float(jax.device_get(run(x0)))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(jax.device_get(run(x0)))
            best = min(best, time.perf_counter() - t0)
        return best

    ta, tb = runner(steps_a), runner(steps_b)
    return max(tb - ta, 1e-9) / (steps_b - steps_a)


def bench_geom(name, B, H, S, Dh, causal):
    r = jax.random.PRNGKey(0)
    q = jax.random.normal(r, (B, H, S, Dh), jnp.bfloat16)
    scale = 1.0 / np.sqrt(Dh)
    # attention flops (fwd): 2 GEMMs of B*H*S*S*Dh MACs each
    area = B * H * S * S * (0.5 if causal else 1.0)
    flops = 2 * 2 * area * Dh

    def matmul_only(x):
        s = jax.lax.dot_general(x, x, (((3,), (3,)), ((0, 1), (0, 1))),
                                preferred_element_type=jnp.float32)
        p = jnp.tanh(s * scale).astype(jnp.bfloat16)  # cheap stand-in
        o = jax.lax.dot_general(p, x, (((3,), (2,)), ((0, 1), (0, 1))),
                                preferred_element_type=jnp.float32)
        return o.astype(jnp.bfloat16)

    coef = 1.0 + 0.01 * jnp.arange(S, dtype=jnp.float32)

    def softmax_only(x):
        # score-tensor-shaped VPU work: the real softmax's max/sub/exp/
        # sum/div over a (B,H,S,S) fp32 tensor that VARIES along the
        # reduced axis (outer product with an iota ramp — a broadcast of
        # one column would let XLA fold the reductions away and the leg
        # would measure nothing), fed back through a reduction so the
        # chain stays dependent
        s = x[..., 0].astype(jnp.float32)[..., :, None] * coef[None, :]
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        return (x + jnp.mean(p, axis=-1, keepdims=True)
                .astype(jnp.bfloat16))

    def full_xla(x):
        s = jax.lax.dot_general(x, x, (((3,), (3,)), ((0, 1), (0, 1))),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
            s = jnp.where(rows >= cols, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(jnp.bfloat16)
        o = jax.lax.dot_general(p, x, (((3,), (2,)), ((0, 1), (0, 1))),
                                preferred_element_type=jnp.float32)
        return o.astype(jnp.bfloat16)

    out = {"geometry": [B, H, S, Dh], "causal": causal,
           "flops_fwd": flops}
    for key, fn in (("matmul_only", matmul_only),
                    ("softmax_only", softmax_only),
                    ("full_xla", full_xla)):
        dt = _time_chained(fn, q)
        out[key] = {"ms": round(dt * 1e3, 4),
                    "tflops_equiv": round(flops / dt / 1e12, 1)}
    # both Pallas kernels, forced explicitly; chain capped at 24 (Mosaic
    # compile time explodes past that)
    from deeperspeed_tpu.ops.pallas import flash_static
    from deeperspeed_tpu.ops.pallas.flash_attention import (
        flash_attention_bhsd)

    for key, fn in (
        ("full_flash_v1",
         # explicit block sizes force the v1 streaming kernel (no auto
         # dispatch to the static kernel)
         lambda x: flash_attention_bhsd(
             x, x, x, causal=causal, block_q=min(128, S),
             block_k=min(128, S)).astype(jnp.bfloat16)),
        ("full_static",
         lambda x: flash_static.flash_attention_static_bhsd(
             x, x, x, causal=causal).astype(jnp.bfloat16)),
    ):
        try:
            dt = _time_chained(fn, q, steps_a=8, steps_b=24)
            out[key] = {"ms": round(dt * 1e3, 4),
                        "tflops_equiv": round(flops / dt / 1e12, 1)}
        except Exception as e:  # noqa: BLE001
            out[key] = {"error": str(e)[:120]}
    # the verdict's question: is full ~= mxu + vpu floors?
    mxu = out["matmul_only"]["ms"]
    vpu = out["softmax_only"]["ms"]
    full = out["full_xla"]["ms"]
    out["model"] = {
        "mxu_plus_vpu_ms": round(mxu + vpu, 4),
        "full_over_model": round(full / max(mxu + vpu, 1e-9), 3),
        "vpu_share_of_model": round(vpu / max(mxu + vpu, 1e-9), 3),
    }
    print(name, json.dumps(out["model"]),
          {k: out[k]["ms"] for k in
           ("matmul_only", "softmax_only", "full_xla")}, flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--geom", nargs="*", default=["bert128", "bert512"])
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "ATTN_ROOFLINE.json"))
    args = ap.parse_args()
    res = {"platform": jax.devices()[0].platform,
           "device": str(jax.devices()[0].device_kind),
           "methodology": "chained-scan differenced (8 vs 32)",
           "geoms": {}}
    for g in args.geom:
        res["geoms"][g] = bench_geom(g, *GEOMS[g])
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)
    print("wrote", args.out)


if __name__ == "__main__":
    main()
