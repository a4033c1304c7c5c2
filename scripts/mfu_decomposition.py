"""MFU ceiling decomposition (VERDICT r2 weak #1/#3, next-round items 3/9).

Answers "where do the missing MFU points live?" for the flagship GPT-NeoX
1.3B and BERT-large bench shapes, by timing on the real chip:

  matmuls   — every large matmul of one layer (+ the logits/MLM head) at
              the exact bench shapes, fwd and fwd+bwd, standalone;
  attn      — the attention core (flash or xla, whichever the model picks)
              at model geometry, fwd+bwd;
  step      — the full engine train_batch (same path as bench.py).

It then reports a step-time floor = sum of constituent times (matmul chain
+ attention + head) against the measured step, attributing the MFU gap to
(a) per-op inefficiency vs the chip's chained-matmul ceiling
(MATMUL_CEILING.json methodology) and (b) everything-else (layernorms,
rotary, remat recompute, optimizer, dispatch).

Writes MFU_DECOMP.json. Usage:
  python scripts/mfu_decomposition.py [--models 1.3b,bert128,bert512]
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

# Bump when the measurement methodology changes (e.g. the r3 move from
# absolute timing + linear losses to differenced windows + sum-of-squares
# losses). Each model entry is stamped with it, and the artifact merge
# drops kept entries whose stamp differs — retracted-methodology numbers
# must not survive a partial --models rerun under the new header.
METHODOLOGY = "differenced-windows-sq-loss-v2"



def _unit_chain(flops_per_exec, target_ms=60.0, assume_tflops=200.0):
    """Executions per scan iteration sized so per-iteration work is
    ~target_ms even for tiny units (the attention core at seq 128 is a
    4 GFLOP op), so any fixed per-iteration cost stays small against the
    work. assume_tflops is deliberately at the chip's near-peak: the
    matmul/head units really do run at ~180-195 TF, and sizing them for
    50 TF left per-iteration work 4x thinner than intended. Capped at 128
    (the chain is unrolled inside the scan body; compile time grows with
    it)."""
    est_ms = 3.0 * flops_per_exec / (assume_tflops * 1e12) * 1e3
    return int(min(128, max(2, round(target_ms / max(est_ms, 1e-3)))))


def _time_unit(unit_loss, args, flops_per_exec, chain=None,
               iters_lo=16, iters_hi=64):
    """fwd+bwd time per execution of `unit_loss(*args) -> scalar`:
    each scan iteration runs `chain` dependent executions (x perturbed by
    the previous gradient, so nothing hoists). The unit time is the
    DIFFERENCE between an iters_hi-length and an iters_lo-length scan of
    the same compiled body, divided by the extra iterations — this cancels
    the per-call dispatch/transfer overhead and its jitter, which a single
    absolute timing books onto the unit. iters are sized so the hi-lo work
    difference is seconds, far above the jitter (4-vs-12 produced
    above-peak readings). Flops are counted as 3x forward (dgrad +
    wgrad)."""
    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu:
        # CPU smoke path: matmuls run at single-digit TF — tiny windows
        # keep a smoke run in minutes, and the above-peak gate is skipped
        # (the cpu row of the peak table is a nominal 0.5 TF that
        # multithreaded oneDNN matmuls legitimately exceed)
        chain = 2 if chain is None else chain
        iters_lo, iters_hi = 2, 6
    if chain is None:
        chain = _unit_chain(flops_per_exec)
    x0 = args[0]

    def one(x, *rest):
        l, gs = jax.value_and_grad(unit_loss, argnums=tuple(
            range(len(args))))(x, *rest)
        gx = gs[0]
        rest = sum((jnp.sum(g.astype(jnp.float32)) for g in gs[1:]),
                   jnp.float32(0.0))
        return (x + (1e-3 * gx).astype(x.dtype)
                + (1e-9 * rest).astype(x.dtype)), l

    def make_loss(iters):
        @jax.jit
        def loss(x, *rest):
            def body(c, _):
                x = c
                for _ in range(chain):
                    x, _l = one(x, *rest)
                return x, None

            out, _ = jax.lax.scan(body, x, None, length=iters)
            return jnp.sum(out.astype(jnp.float32))

        return loss

    loss_lo, loss_hi = make_loss(iters_lo), make_loss(iters_hi)
    for fn in (loss_lo, loss_hi):  # compile + warm, once per program
        float(jax.device_get(fn(*args)))

    def best_of(fn, n=3):
        best = float("inf")
        for i in range(n):
            t0 = time.perf_counter()
            float(jax.device_get(
                fn(x0 + jnp.asarray(i, x0.dtype), *args[1:])))
            best = min(best, time.perf_counter() - t0)
        return best

    peak = peak_tflops()
    for attempt in range(3):
        t_lo, t_hi = best_of(loss_lo), best_of(loss_hi)
        per_exec = (t_hi - t_lo) / (chain * (iters_hi - iters_lo))
        tf = 3.0 * flops_per_exec / max(per_exec, 1e-12) / 1e12
        # sanity gate: a jitter-inverted pair (t_hi <= t_lo) or an
        # above-peak implied rate means the differencing window lost to
        # drift — remeasure rather than writing garbage into the
        # artifact. After 3 failed attempts an above-peak (but
        # positive-delta) reading is returned marked suspect rather than
        # aborting. A
        # jitter-INVERTED pair (t_hi <= t_lo) is never returnable: its
        # per_exec is negative and would poison the floor silently.
        if t_hi > t_lo and (tf <= 1.1 * peak or not on_tpu):
            return per_exec, tf, False
        print(f"[mfu_decomp] implausible unit timing (t_lo={t_lo:.3f}s "
              f"t_hi={t_hi:.3f}s -> {tf:.0f} TF vs peak {peak:.0f}); "
              f"remeasuring ({attempt + 1}/3)", flush=True)
    if t_hi <= t_lo:
        raise RuntimeError(
            "unit timing inverted (t_hi <= t_lo) 3x — timing too unstable "
            "to decompose; rerun on a quieter host")
    return per_exec, tf, True


def peak_tflops():
    from scripts.bert_sparse_bench import peak_tflops as _pt
    return _pt()


def decompose(name):
    """Composite-unit timing at the given bench geometry: the per-layer
    matmul chain (qkv/attn-out/ffn, with gelu), the attention core, and
    the vocab head, each fwd+bwd."""
    if name == "1.3b":
        # EXACT bench.py geometry: the flagship bench runs seq=1024
        # (max_seq=1024), micro=2 — the floor must be at the same shapes
        # as the step it is compared against
        D, Hh, L, S, micro, V = 2048, 16, 24, 1024, 2, 50304
        causal, head_rows = True, micro * S
        step_ref = "bench.py (BENCH_r0N.json detail.step_time_s / gas=8)"
    elif name == "bert128":
        D, Hh, L, S, micro, V = 1024, 16, 24, 128, 64, 30528
        causal = False
        head_rows = 64 * 128  # bench_bert runs the FULL head (gather off)
        step_ref = "BENCH_EXTRA.json bert_large_zero2 seq128 step_time_s"
    elif name == "bert512":
        D, Hh, L, S, micro, V = 1024, 16, 24, 512, 16, 30528
        causal = False
        head_rows = 16 * 512
        step_ref = "BENCH_EXTRA.json bert_large_zero2 seq512 step_time_s"
    else:
        raise ValueError(name)
    M = micro * S
    Dh = D // Hh
    key = jax.random.PRNGKey(0)
    # mirror _time_unit's platform-dependent windows so the note describes
    # the measurement that actually ran
    lo_it, hi_it = (16, 64) if jax.devices()[0].platform == "tpu" else (2, 6)

    # --- per-layer matmul chain (qkv -> attn_out -> ffn_in/gelu -> out) ---
    x = jax.random.normal(key, (M, D), jnp.bfloat16)
    w_qkv = jax.random.normal(key, (D, 3 * D), jnp.bfloat16) * 0.02
    w_ao = jax.random.normal(key, (D, D), jnp.bfloat16) * 0.02
    w_fi = jax.random.normal(key, (D, 4 * D), jnp.bfloat16) * 0.02
    w_fo = jax.random.normal(key, (4 * D, D), jnp.bfloat16) * 0.02

    def layer_mm(x, w_qkv, w_ao, w_fi, w_fo):
        qkv = x @ w_qkv
        ctx = qkv[:, :D]  # attention core timed separately
        a = ctx @ w_ao
        hgelu = jax.nn.gelu((x + a) @ w_fi, approximate=False)
        y = (hgelu @ w_fo).astype(jnp.float32)
        # sum of SQUARES, not sum: a loss linear in a matmul's output lets
        # XLA's algebraic simplifier replace the matmul (and its dgrad/
        # wgrad) with row/column reductions — sum(x@w) == colsum(x)·rowsum
        # pairs — and the "measurement" reads above hardware peak
        return jnp.sum(y * y) * 1e-6

    mm_flops = 2.0 * M * D * D * (3 + 1 + 4 + 4)
    t_mm, tf_mm, sus_mm = _time_unit(layer_mm,
                                     (x, w_qkv, w_ao, w_fi, w_fo),
                                     mm_flops)

    # --- attention core at model geometry ---
    from deeperspeed_tpu.ops.pallas.flash_attention import (
        flash_attention_bhsd, is_available)

    qh = jax.random.normal(key, (micro, Hh, S, Dh), jnp.bfloat16)
    # mirror the models' attn_impl='auto' policy exactly (incl. the
    # short-sequence XLA preference) so the floor times what the bench runs
    use_flash = S > 256 and is_available(qh.transpose(0, 2, 1, 3))

    def attn_loss(qh):
        if use_flash:
            o = flash_attention_bhsd(qh, qh, qh, causal=causal)
        else:
            s = jnp.einsum("bhqd,bhkd->bhqk", qh, qh,
                           preferred_element_type=jnp.float32) / (Dh ** 0.5)
            if causal:
                mask = jnp.tril(jnp.ones((S, S), bool))
                s = jnp.where(mask[None, None], s, -1e30)
            pr = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", pr.astype(qh.dtype), qh)
        o = o.astype(jnp.float32)
        return jnp.sum(o * o)  # see layer_mm: linear loss collapses

    attn_flops = 2.0 * 2.0 * micro * Hh * S * S * Dh * (
        0.5 if causal else 1.0)
    t_attn, tf_attn, sus_attn = _time_unit(attn_loss, (qh,), attn_flops)

    # --- vocab head ---
    xh = jax.random.normal(key, (head_rows, D), jnp.bfloat16)
    w_v = jax.random.normal(key, (D, V), jnp.bfloat16) * 0.02

    def head_loss(xh, w_v):
        y = (xh @ w_v).astype(jnp.float32)
        return jnp.sum(y * y) * 1e-6  # see layer_mm: linear loss collapses

    head_flops = 2.0 * head_rows * D * V
    t_head, tf_head, sus_head = _time_unit(head_loss, (xh, w_v), head_flops)

    floor = L * (t_mm + t_attn) + t_head
    floor_flops = 3.0 * (L * (mm_flops + attn_flops) + head_flops)
    return {
        "model": name,
        "units_fwdbwd": {
            "layer_matmul_chain": {"ms": round(t_mm * 1e3, 3),
                                   "tflops": round(tf_mm, 1),
                                   **({"suspect": True} if sus_mm else {}),
                                   "flops_fwd": mm_flops},
            "attention_core": {"impl": "flash" if use_flash else "xla",
                               "geometry": [micro, Hh, S, Dh],
                               "ms": round(t_attn * 1e3, 3),
                               "tflops": round(tf_attn, 1),
                               **({"suspect": True} if sus_attn else {}),
                               "flops_fwd": attn_flops},
            "vocab_head": {"rows": head_rows, "ms": round(t_head * 1e3, 3),
                           "tflops": round(tf_head, 1),
                           **({"suspect": True} if sus_head else {}),
                           "flops_fwd": head_flops},
        },
        "micro_step_floor_ms": round(floor * 1e3, 1),
        "micro_step_floor_tflops": round(floor_flops / floor / 1e12, 1),
        "compare_step_time_against": step_ref,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0].device_kind),
        "methodology": METHODOLOGY,
        "note": ("floor = L*(matmul chain + attention) + head, each a "
                 "composite unit timed fwd+bwd as the DIFFERENCE between "
                 f"a {hi_it}- and a {lo_it}-iteration scan of chained "
                 "dependent executions (cancels the per-call "
                 "dispatch overhead and its jitter; unit losses are "
                 "sum-of-squares so XLA cannot algebraically collapse "
                 "the matmuls); a full engine micro-step slower than "
                 "this floor is paying for elementwise/layernorm/remat/"
                 "optimizer/dispatch, a unit whose tflops sit far below "
                 "MATMUL_CEILING.json for its shape class is shape- or "
                 "VPU-bound, not framework-bound"),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="1.3b,bert128,bert512")
    ap.add_argument("--out", default=os.path.join(REPO, "MFU_DECOMP.json"))
    args = ap.parse_args()
    plat = jax.devices()[0].platform
    dev = str(jax.devices()[0].device_kind)
    out = {}
    if os.path.exists(args.out):  # merge: keep models not re-run this time
        try:
            with open(args.out) as f:
                out = json.load(f)
        except (OSError, ValueError):
            out = {}
        # drop kept entries measured on a DIFFERENT platform — a merge
        # must not produce a mixed-provenance artifact (e.g. a CPU smoke
        # run inheriting TPU timings under a "platform": "cpu" header).
        # Legacy entries without their own stamp inherit the loaded
        # file's top-level values, NOT the current ones. Device kind is
        # filtered too: v4-measured timings must not survive under a
        # rewritten v5e header/peak.
        file_plat = out.get("platform", plat)
        file_dev = out.get("device", dev)
        dropped = [k for k, v in out.items()
                   if isinstance(v, dict)
                   and (v.get("platform", file_plat) != plat
                        or v.get("device", file_dev) != dev
                        or v.get("methodology") != METHODOLOGY)]
        if dropped:
            print(f"dropping kept entries (platform/device/methodology "
                  f"mismatch vs current run): {dropped}", flush=True)
        out = {k: v for k, v in out.items() if k not in dropped}
    out.update({"platform": plat, "device": dev,
                "peak_tflops": peak_tflops()})
    for m in args.models.split(","):
        out[m] = decompose(m.strip())
        print(json.dumps(out[m]), flush=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
