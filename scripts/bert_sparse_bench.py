"""BERT-large ZeRO-2 + block-sparse attention benchmark (north-star #3).

Two measurements, written to BENCH_EXTRA.json at the repo root (bench.py
embeds that file in its one-line JSON so the driver's BENCH_r{N}.json
carries them):

1. BERT-large (24L, d1024, h16, 336M params) MLM pretraining through the
   full engine with ZeRO-2 + bf16, at seq 128 and seq 512 — the two
   configurations of the reference's "fastest BERT" post
   (/root/reference/docs/_posts/2020-05-28-fastest-bert-training.md:38-39:
   272 samples/s = 64 TFLOPS at seq 128; 52 samples/s = 53 TFLOPS at
   seq 512, on one V100).
2. Block-sparse vs dense attention forward+backward at S >= 4096 (BERT
   head geometry, fixed sparsity), against the reference's "up to 6.3x
   faster" sparse-attention claim
   (/root/reference/docs/_posts/2020-09-08-sparse-attention-news.md:10).

Timing discipline: warmup steps excluded, best-of-2 windows, everything
timed inside one process.

Usage: python scripts/bert_sparse_bench.py [--quick]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def peak_tflops():
    """bf16 peak of this device from the one table (keyed by device_kind;
    an unknown accelerator raises)."""
    from deeperspeed_tpu.monitor.perf import platform_peaks

    return platform_peaks()["peak_tflops"]


def bench_bert(seq: int, micro: int, steps: int, warmup: int,
               remat=True, remat_policy="full", gather=0.0,
               ce_chunk=64, masterless=False, zero_stage=2):
    """BERT-large MLM training step through the engine, ZeRO-2 + bf16.

    Perf config (round 3, within-process A/B on the chip): attn_impl
    'auto' now resolves to the XLA batched-GEMM attention at S <= 256
    (flash's dynamic-loop overhead dominated at seq 128: +27% end-to-end
    from the switch, seq 512 keeps flash); full remat beat the 'matmuls'
    selective policy (the save barriers inhibit fusion at these small
    per-layer shapes) and the scored-position head gather was neutral, so
    both stay at their model defaults here."""
    import deeperspeed_tpu as ds
    from deeperspeed_tpu.models.bert import BertConfig, make_bert

    cfg = BertConfig(
        vocab_size=30528,  # padded to a lane multiple
        n_layer=24, n_head=16, d_model=1024, max_seq=seq,
        dtype=jnp.bfloat16, remat=remat, remat_policy=remat_policy,
        ce_chunk=ce_chunk, mlm_gather_frac=gather,
    )
    init_fn, _, mlm_loss_fn, _ = make_bert(cfg)
    params = init_fn(jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    embed = sum(p.size for p in jax.tree.leaves(params["embed"]))
    n_matmul = n_params - embed

    engine, _, _, _ = ds.initialize(
        model=mlm_loss_fn, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam",
                          "params": {"lr": 1e-4, "betas": [0.9, 0.95]}},
            "bf16": {"enabled": True,
                     "master_weights": not masterless},
            "zero_optimization": {"stage": zero_stage},
            "gradient_clipping": 1.0,
            "steps_per_print": 10**9,
        },
    )
    del params
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 30000, size=(micro, seq), dtype=np.int32)
    # MLM labels: 15% positions predicted, rest -100 (ignored)
    labels = np.where(rng.random((micro, seq)) < 0.15, ids, -100).astype(
        np.int32)
    batch = (ids, labels)
    for _ in range(warmup):
        float(jax.device_get(engine.train_batch(batch)))
    dts = []
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(batch)
        float(jax.device_get(loss))
        dts.append((time.perf_counter() - t0) / steps)
    dt = min(dts)

    samples_per_sec = micro / dt
    # 6N per token over matmul params + attention matmul flops
    # (bidirectional: 12*L*D*S per token fwd+bwd)
    flops_per_token = 6.0 * n_matmul + 12.0 * cfg.n_layer * cfg.d_model * seq
    tflops = samples_per_sec * seq * flops_per_token / 1e12
    return {
        "seq": seq, "micro_batch": micro, "n_params": n_params,
        "samples_per_sec": round(samples_per_sec, 1),
        "step_time_s": round(dt, 4),
        "tflops_per_chip": round(tflops, 1),
        "mfu": round(tflops / peak_tflops(), 4),
        "reference_v100": {"seq128": "272 samples/s, 64 TFLOPS",
                           "seq512": "52 samples/s, 53 TFLOPS"}[f"seq{seq}"],
    }


def bench_sparse_vs_dense(S: int, steps: int, sparsity_cfg=None,
                          skip_naive=False):
    """fwd+bwd attention core: block-sparse Pallas vs dense flash, BERT-
    large head geometry (16 heads x 64 dh)."""
    from deeperspeed_tpu.ops.pallas.flash_attention import (
        flash_attention_bhsd)
    from deeperspeed_tpu.ops.sparse_attention import (
        FixedSparsityConfig, SparseSelfAttention)

    B, H, Dh = 1, 16, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, S, Dh), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, Dh), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, Dh), jnp.bfloat16)

    if sparsity_cfg is None:
        sparsity_cfg = FixedSparsityConfig(num_heads=H, block=128,
                                           attention="unidirectional")
    sparse = SparseSelfAttention(sparsity_cfg, max_seq_length=S, causal=True)
    layout = sparse.get_layout(S)
    density = float(layout.sum()) / layout.size

    def time_fn(fn):
        def loss(q, k, v):
            def body(c, _):
                o = fn(q, k, v)
                return c + jnp.sum(o.astype(jnp.float32)), None
            out, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=steps)
            return out

        @jax.jit
        def probe(q, k, v):
            l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
            return l + sum(jnp.sum(g.astype(jnp.float32)) for g in grads)

        # device_get of the scalar forces the value
        float(jax.device_get(probe(q, k, v)))
        best = float("inf")
        for i in range(3):
            qi = q + jnp.bfloat16(i)
            t0 = time.perf_counter()
            float(jax.device_get(probe(qi, k, v)))
            best = min(best, time.perf_counter() - t0)
        return best / steps

    from deeperspeed_tpu.ops.pallas.flash_attention import is_available

    t_sparse = time_fn(lambda q, k, v: sparse(q, k, v))
    # flash itself VMEM-caps out at ~4MB of resident K+V (is_available);
    # beyond that the sparse kernel is the only fused option at this
    # geometry — report sparse absolute time with the cap noted
    flash_ok = is_available(q.transpose(0, 2, 1, 3))
    t_flash = (time_fn(lambda q, k, v: flash_attention_bhsd(
        q, k, v, causal=True)) if flash_ok else None)

    def naive(qh, kh, vh):
        # materialized S x S softmax — the kind of dense attention the
        # reference's 2020 sparse-speedup claim was measured against
        # (flash attention did not exist yet)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                       preferred_element_type=jnp.float32) / (Dh ** 0.5)
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(qh.dtype), vh)

    t_naive = None if skip_naive else time_fn(naive)
    from deeperspeed_tpu.ops.sparse_attention.kernels import auto_route

    routed, waste, _, flash_hint = auto_route(layout, True, S, Dh)
    row = {
        "seq": S, "heads": H, "head_dim": Dh,
        "layout": type(sparsity_cfg).__name__,
        "layout_density": round(density, 4),
        # which SPARSE path auto executes (masking semantics preserved),
        # plus the honest prediction: above the ~12% density break-even
        # dense flash outruns both sparse kernels on this chip — a model
        # whose mask is semantic still gets the sparse path; one using
        # sparsity purely for speed should use dense flash instead
        "auto_impl": routed,
        "supertile_waste": round(waste, 2),
        "dense_flash_predicted_faster": flash_hint,
        "block_sparse_ms": round(t_sparse * 1e3, 3),
        "reference_claim": ("up to 6.3x vs dense (V100, long sequences; "
                            "dense == materialized-softmax in 2020)"),
    }
    if t_flash is not None:
        row["dense_flash_ms"] = round(t_flash * 1e3, 3)
        row["speedup_vs_flash"] = round(t_flash / t_sparse, 2)
    else:
        row["dense_flash"] = "VMEM-capped at this S*Dh (is_available)"
    if t_naive is not None:
        row["dense_naive_ms"] = round(t_naive * 1e3, 3)
        row["speedup_vs_naive"] = round(t_naive / t_sparse, 2)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only-sparse", action="store_true",
                    help="skip the BERT engine benches; sparse sweep only")
    ap.add_argument("--seqs", type=int, nargs="*", default=None,
                    help="restrict the sparse sweep to these seq lens")
    args = ap.parse_args()
    steps = 5 if args.quick else 10

    out = {
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "bert_large_zero2": [],
        "sparse_vs_dense": [],
    }
    for seq, micro in (() if args.only_sparse else ((128, 64), (512, 16))):
        # masterless bf16: r4 hardware grid measured +3.5 TF at both seqs
        # (optimizer state traffic halves); convergence equivalence is
        # gated by tests/test_model_convergence.py (incl. the
        # masterless+zero2 case this bench runs) and the real-corpus
        # gate's masterless config when CONVERGENCE_CORPUS.json is
        # (re)generated
        # remat_policy: seq512 measured 67.0 -> 71.8 TF with 'matmuls'
        # under the static attention kernel; seq128 keeps 'full' (matmuls
        # measured neutral-to-worse at its tiny per-layer shapes)
        r = bench_bert(seq, micro, steps=steps, warmup=2, masterless=True,
                       remat_policy="matmuls" if seq == 512 else "full")
        r["precision"] = "masterless-bf16"
        out["bert_large_zero2"].append(r)
        print(json.dumps(r), flush=True)
    from deeperspeed_tpu.ops.sparse_attention import (
        BigBirdSparsityConfig, LocalSlidingWindowSparsityConfig)

    H = 16
    sweep = [
        (4096, None),   # Fixed default — the r1/r2 comparison point
        (8192, None),
        # sliding-window sweep at S=8192: the VERDICT ~12.5%-density target
        # (w14 = 11.8%) plus denser points to locate the sparse-vs-flash
        # crossover density
        (8192, LocalSlidingWindowSparsityConfig(
            num_heads=H, block=128, num_sliding_window_blocks=14)),
        (8192, LocalSlidingWindowSparsityConfig(
            num_heads=H, block=128, num_sliding_window_blocks=24)),
        (8192, LocalSlidingWindowSparsityConfig(
            num_heads=H, block=128, num_sliding_window_blocks=32)),
        (8192, LocalSlidingWindowSparsityConfig(
            num_heads=H, block=128, num_sliding_window_blocks=40)),
        # long-sequence point: past the resident kernels' VMEM budget the
        # STREAMING kernels serve it — fused sparse attention at a length
        # where flash itself is VMEM-capped out entirely
        (16384, LocalSlidingWindowSparsityConfig(
            num_heads=H, block=128, num_sliding_window_blocks=14)),
        # BigBird (window + random + global) — the r3 verdict's missing
        # measurement; window-dominated so auto should keep it sparse
        (4096, BigBirdSparsityConfig(
            num_heads=H, block=128, num_random_blocks=1,
            num_sliding_window_blocks=3, num_global_blocks=1,
            attention="unidirectional")),
        (8192, BigBirdSparsityConfig(
            num_heads=H, block=128, num_random_blocks=1,
            num_sliding_window_blocks=3, num_global_blocks=1,
            attention="unidirectional")),
    ]
    if args.seqs:
        sweep = [(S, c) for S, c in sweep if S in set(args.seqs)]
    for S, scfg in sweep:
        # steps=16: the harness carries a fixed cost per scan iteration;
        # short scans bias ratios toward 1
        try:
            r = bench_sparse_vs_dense(S, steps=16, sparsity_cfg=scfg,
                                      skip_naive=(S > 8192
                                                  or scfg is not None))
        except Exception as e:  # noqa: BLE001 — keep the sweep's survivors
            r = {"seq": S, "error": f"{type(e).__name__}: {str(e)[:200]}"}
        out["sparse_vs_dense"].append(r)
        print(json.dumps(r), flush=True)

    path = os.path.join(REPO, "BENCH_EXTRA.json")
    if args.only_sparse or args.seqs:
        # partial sweep: merge into the existing artifact instead of
        # clobbering the rows this invocation did not measure
        try:
            with open(path) as f:
                prev = json.load(f)
        except FileNotFoundError:
            prev = {}
        if not args.only_sparse:
            prev["bert_large_zero2"] = out["bert_large_zero2"]
        kept = [r for r in prev.get("sparse_vs_dense", [])
                if r.get("seq") not in {r2.get("seq")
                                        for r2 in out["sparse_vs_dense"]}]
        prev["sparse_vs_dense"] = kept + out["sparse_vs_dense"]
        prev["platform"] = out["platform"]
        prev["tpu_gen"] = out["tpu_gen"]
        out = prev
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
