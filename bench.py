"""Benchmark: GPT-NeoX 1.3B training throughput on one chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

The run exercises the framework's headline capabilities at once: a
billion-parameter model training on a single 16GB chip (masterless bf16 —
the reference needed ZeRO-Offload for models this size on a 16GB V100),
flash-attention Pallas kernels, selective remat, and the fused jitted
train step.

It measures a device, so it needs one: without a TPU it exits non-zero.
The only exception is the toy size asked for by name
(``DS_BENCH_MODEL=smoke``), which checks that the script still runs and
reports a loss and counts — never a rate or a utilization. Every result
names the device it ran on, and the peak comes from one table keyed by
``device_kind`` (``monitor.perf.PLATFORM_PEAKS``); an unknown device is an
error.

vs_baseline compares achieved MFU against the reference's published peak
efficiency: DeeperSpeed's headline BERT kernel numbers are 52% of V100 peak
(/root/reference/docs/_posts/2020-05-19-bert-record.md:14, BASELINE.md).
vs_baseline = our_MFU / 0.52 — >1.0 means beating the reference's
hardware-efficiency bar on TPU.

No number from this script has been taken on the current code and
installation; see PERF.md.
"""

import json
import os
import sys
import time

import numpy as np

REFERENCE_MFU = 0.52


def main():
    import jax

    import deeperspeed_tpu as ds
    from deeperspeed_tpu.models.gpt import GPTConfig, get_preset, make_gpt
    from deeperspeed_tpu.monitor.perf import platform_peaks
    from deeperspeed_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    model = os.environ.get("DS_BENCH_MODEL", "1.3b")
    if not on_tpu and model != "smoke":
        raise SystemExit(
            f"bench.py measures a TPU; found platform={device.platform!r} "
            f"({device.device_kind}). DS_BENCH_MODEL=smoke runs the toy "
            "size on any platform and reports no rate.")
    # remat A/B knob: DS_BENCH_REMAT=off runs full-save (no remat), which
    # removes the backward's elementwise replay but needs more HBM than
    # mb2 leaves on a 16GB chip; 'matmuls' selective remat is the default
    remat_env = os.environ.get("DS_BENCH_REMAT", "matmuls")
    # ce knob applies only to the 1.3b config below; reject it elsewhere
    # rather than silently ignoring it
    ce_env = int(os.environ.get("DS_BENCH_CE", "-1"))
    if ce_env >= 0 and model != "1.3b":
        raise SystemExit("DS_BENCH_CE only applies to DS_BENCH_MODEL=1.3b")
    if model == "1.3b":
        # ce_chunk=0 (fused logits+lse, no streaming): at mb2 the full
        # (2,1024,50304) fp32 logits are only 412MB, while the chunked path
        # runs the vocab head as 256-row matmuls and its @checkpoint replay
        # adds a 4th head matmul
        cfg = get_preset("neox-1.3b", remat=remat_env != "off",
                         remat_policy="matmuls" if remat_env == "off"
                         else remat_env,
                         ce_chunk=ce_env if ce_env >= 0 else 0,
                         max_seq=1024)
        # 'matmuls' selective remat saves flash o/lse + q/k/v + pre-gelu so
        # the backward replays only elementwise ops; mb2 keeps the saved
        # activations at ~0.8GB while gas=8 restores the batch
        micro, gas, seq, steps, warmup = 2, 8, 1024, 10, 3
        metric = "gpt_neox_1.3b_tokens_per_sec_per_chip"
        # masterless bf16: p+g+m+v at 2 bytes each = 11.3GB for 1.41B params
        precision = {"enabled": True, "master_weights": False}
    elif model == "125m":
        cfg = GPTConfig(
            vocab_size=50304, n_layer=12, n_head=12, d_model=768, max_seq=1024,
            remat=False,
        )
        micro, gas, seq, steps, warmup = 12, 1, 1024, 20, 3
        metric = "gpt_125m_tokens_per_sec_per_chip"
        precision = {"enabled": True, "master_weights": True}
    elif model == "smoke":
        cfg = GPTConfig(
            vocab_size=1024, n_layer=2, n_head=4, d_model=128, max_seq=128,
            attn_impl="xla",
        )
        micro, gas, seq, steps, warmup = 4, 1, 128, 5, 2
        metric = "gpt_smoke_tokens_per_sec_per_chip"
        precision = {"enabled": True, "master_weights": True}
    else:
        raise SystemExit(f"unknown DS_BENCH_MODEL {model!r}; "
                         "choose 1.3b, 125m or smoke")

    # offline tuning knobs (one size per process: the 1.3B run fills HBM)
    micro = int(os.environ.get("DS_BENCH_MICRO", micro))
    gas = int(os.environ.get("DS_BENCH_GAS", gas))
    steps = int(os.environ.get("DS_BENCH_STEPS", steps))

    init_fn, _, loss_fn, _ = make_gpt(cfg)
    params = init_fn(jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    # matmul (flop-doing) params only: the input embedding is a gather, not
    # a matmul — counting it would inflate MFU (~7% at 1.3B)
    embed_params = sum(p.size for p in jax.tree.leaves(params["embed"]))
    n_matmul_params = n_params - embed_params

    # grad accumulation dtype A/B knob (DS_BENCH_ACCUM=bf16|fp32): the
    # gas-scan's accumulator is read+written every micro — at 1.3B that is
    # 2.6GB of grads x 4B fp32 of HBM traffic per micro; bf16 halves it
    accum_env = os.environ.get("DS_BENCH_ACCUM")
    if accum_env:
        precision = {**precision, "grad_accum_dtype": accum_env}
    ds_cfg = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        # beta2=0.95 (standard for LLM pretraining) also lets the masterless
        # mode store the second moment in bf16 — with 0.999 it would stay
        # fp32 (see ops/adam.py state_dtype_sq) and the 1.3B run would OOM
        "optimizer": {"type": "Adam",
                      "params": {"lr": 1e-4, "betas": [0.9, 0.95]}},
        "bf16": precision,
        "zero_optimization": {"stage": 0 if model == "1.3b" else 1},
        "gradient_clipping": 1.0,
        "steps_per_print": 10**9,
    }
    engine, _, _, _ = ds.initialize(
        model=loss_fn, model_parameters=params, config=ds_cfg
    )
    del params
    dp = engine.data_parallel_size
    rng = np.random.default_rng(0)
    batch = rng.integers(
        0, cfg.vocab_size, size=(micro * gas * dp, seq + 1), dtype=np.int32
    )
    for _ in range(warmup):
        loss = engine.train_batch(batch)
        # device_get per warmup step: the first post-compile steps include
        # allocator/layout warmup that must finish before timing
        float(jax.device_get(loss))
    # two timing windows, best taken
    dts = []
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(batch)
        float(jax.device_get(loss))
        dts.append((time.perf_counter() - t0) / steps)
    dt = min(dts)

    detail = {
        "n_params": n_params,
        "micro_batch": micro,
        "grad_accum": gas,
        "steps_timed": 2 * steps,
        "loss": round(float(jax.device_get(loss)), 4),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "compile_cache_dir": cache_dir,
    }
    if not on_tpu:
        # a host-CPU run checks that the script runs; it is not a
        # measurement, so no time, rate or utilization is reported
        print(json.dumps({"metric": metric, "value": None,
                          "unit": "tokens/s/chip", "vs_baseline": None,
                          "note": "not measured: no accelerator",
                          "detail": detail}))
        return 0

    tokens_per_step = micro * gas * dp * seq
    tokens_per_sec_per_chip = tokens_per_step / dt / len(jax.devices())
    # total training flops/token: fwd 2N + bwd 4N over matmul params, plus
    # the attention matmuls — 12*L*D*S fwd+bwd non-causal, halved to 6 for
    # the causal mask
    flops_per_token = (6.0 * n_matmul_params
                       + 6.0 * cfg.n_layer * cfg.d_model * seq)
    model_tflops = tokens_per_sec_per_chip * flops_per_token / 1e12
    peaks = platform_peaks(device)
    mfu = model_tflops / peaks["peak_tflops"]
    detail.update(
        step_time_s=round(dt, 4),
        model_tflops_per_chip=round(model_tflops, 2),
        mfu=round(mfu, 4),
        peak_tflops=peaks["peak_tflops"],
        peak_source=peaks["source"],
    )
    print(json.dumps({
        "metric": metric,
        "value": round(tokens_per_sec_per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / REFERENCE_MFU, 4),
        "detail": detail,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
