"""A/B the dense attention kernels (v1 streaming vs v2 static vs XLA).

Correctness: fwd max-err and grad max-err vs the fp32 XLA reference.
Performance: fwd+bwd per-execution time via the repo's differenced
chained-scan methodology (scripts/mfu_decomposition._time_unit) —
per-call dispatch makes naive per-call timing useless for sub-ms kernels,
so executions are chained inside one jit and two window lengths are
differenced.

Usage: python scripts/attn_kernel_bench.py [--geoms 1.3b,bert512,...]
"""

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from mfu_decomposition import _time_unit  # noqa: E402

GEOMS = {
    # (B, H, S, Dh, causal)
    "1.3b": (2, 16, 1024, 128, True),
    "bert512": (16, 16, 512, 64, False),
    "bert128": (64, 16, 128, 64, False),
    "bert256": (32, 16, 256, 64, False),
    "s2048": (1, 16, 2048, 128, True),
}


def xla_ref(q, k, v, causal):
    dh = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / (dh ** 0.5)
    if causal:
        S = q.shape[2]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--geoms", default="1.3b,bert512,bert256,bert128,s2048")
    # default chain for these unit flops would be 128 unrolled fwd+bwd
    # executions per scan body — with Pallas kernels that's hours of
    # Mosaic compile; 24 keeps the hi-lo work difference ~0.3-0.5s
    # (well above timing jitter) at tractable compile time
    ap.add_argument("--chain", type=int, default=24)
    args = ap.parse_args()

    from deeperspeed_tpu.ops.pallas.flash_attention import (
        flash_attention_bhsd, is_available)
    from deeperspeed_tpu.ops.pallas.flash_static import (
        flash_attention_static_bhsd, is_static_available)

    out = {"platform": jax.devices()[0].platform,
           "device": str(jax.devices()[0].device_kind), "geoms": {}}
    for name in args.geoms.split(","):
        B, H, S, Dh, causal = GEOMS[name.strip()]
        key = jax.random.PRNGKey(0)
        kq, kg = jax.random.split(key, 2)
        qh = jax.random.normal(kq, (B, H, S, Dh), jnp.bfloat16)
        do = jax.random.normal(kg, (B, H, S, Dh), jnp.bfloat16)

        flops_fwd = 4.0 * B * H * S * S * Dh * (0.5 if causal else 1.0)
        row = {"geometry": [B, H, S, Dh], "causal": causal}

        impls = {"xla": functools.partial(xla_ref, causal=causal)}
        if is_available(qh.transpose(0, 2, 1, 3)):
            # explicit blocks pin the v1 streaming kernel: parameterless
            # flash_attention_bhsd now dispatches to the static kernel
            from deeperspeed_tpu.ops.pallas.flash_attention import _auto_block
            bq, bk = _auto_block(S, 512), _auto_block(S, 512)
            impls["v1"] = functools.partial(flash_attention_bhsd,
                                            causal=causal,
                                            block_q=bq, block_k=bk)
        if is_static_available(qh):
            impls["v2"] = functools.partial(flash_attention_static_bhsd,
                                            causal=causal)

        ref_o = jax.jit(functools.partial(xla_ref, causal=causal))(
            qh.astype(jnp.float32), qh.astype(jnp.float32),
            qh.astype(jnp.float32))

        def loss_of(impl):
            def f(q):
                o = impl(q, q, q)
                o = o.astype(jnp.float32)
                return jnp.sum(o * o) * 1e-6  # sq-loss: no algebraic collapse
            return f

        ref_grad = jax.jit(jax.grad(
            lambda q: jnp.sum(xla_ref(q, q, q, causal).astype(jnp.float32)
                              * do.astype(jnp.float32))))(
            qh.astype(jnp.float32))

        for label, impl in impls.items():
            o = jax.jit(impl)(qh, qh, qh)
            err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - ref_o)))
            g = jax.jit(jax.grad(
                lambda q: jnp.sum(impl(q, q, q).astype(jnp.float32)
                                  * do.astype(jnp.float32))))(qh)
            gerr = float(jnp.max(jnp.abs(g.astype(jnp.float32) - ref_grad)))
            t, tf, suspect = _time_unit(loss_of(impl), (qh,), flops_fwd,
                                        chain=args.chain)
            row[label] = {
                "fwdbwd_ms": round(t * 1e3, 3),
                "fwdbwd_tflops": round(tf, 1),
                **({"suspect": True} if suspect else {}),
                "max_err": round(err, 4),
                "max_grad_err": round(gerr, 4),
            }
            print(name, label, json.dumps(row[label]), flush=True)
        out["geoms"][name] = row
    print(json.dumps(out))


if __name__ == "__main__":
    main()
