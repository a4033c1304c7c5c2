"""Attention micro-benchmark (reference tests/benchmarks analog): Pallas
flash attention vs dense XLA attention, forward+backward.

Run directly:  python tests/benchmarks/attention_bench.py [seq]
Run it per-config in a FRESH process on the TPU (HBM is not reliably
reclaimed between runs in one process).
"""

import sys
import time


def bench(impl: str, seq: int, batch: int = 8, heads: int = 12,
          head_dim: int = 64, iters: int = 20):
    import jax
    import jax.numpy as jnp

    from deeperspeed_tpu.models.gpt import causal_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (batch, seq, heads, head_dim)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in ks)

    def loss(q, k, v):
        out = causal_attention(q, k, v, impl=impl)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    f = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    out = f(q, k, v)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(q, k, v)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    # fwd 2x + bwd ~2.5x of QK^T + PV matmul flops, causal halves them
    flops = 3.5 * 2 * 2 * batch * heads * seq * seq * head_dim / 2
    return dt, flops / dt / 1e12


def main():
    import jax

    seq = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
    on_tpu = jax.devices()[0].platform == "tpu"
    impls = ["pallas", "xla"] if on_tpu else ["pallas_interpret", "xla"]
    for impl in impls:
        try:
            dt, tflops = bench(impl, seq)
            print(f"{impl:<18} seq={seq}: {dt * 1e3:7.2f} ms  {tflops:6.2f} TFLOP/s")
        except Exception as e:
            print(f"{impl:<18} seq={seq}: failed ({type(e).__name__})")


if __name__ == "__main__":
    main()
