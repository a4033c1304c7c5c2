"""Hardware smoke test: run every Pallas kernel forward+backward ON THE REAL
CHIP.

The CPU test suite exercises kernels in interpret mode, which does NOT catch
Mosaic lowering failures (the block-sparse backward shipped broken on
hardware for weeks while interpret-mode tests stayed green — a bool
lane-vector broadcast Mosaic cannot lower). Run this after touching any
kernel:

    python scripts/tpu_smoke.py

Exits non-zero on the first failure; each line prints the op and a checksum
so numerical blow-ups are visible too. Multi-device sections run only on a
multi-chip host. ``tests/test_tpu_lowering.py`` cross-lowers the same entry
points on the CPU (cheap, catches refused block shapes and missing lowering
rules); Mosaic's own compile and the numerics still need this script.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _check(name, fn):
    import jax
    import jax.numpy as jnp

    try:
        out = fn()
        tot = float(jax.device_get(
            sum(jnp.sum(jnp.abs(x.astype(jnp.float32)))
                for x in jax.tree.leaves(out))
        ))
        assert np.isfinite(tot), f"non-finite output {tot}"
        print(f"  {name:44s} OK  (checksum {tot:.4g})", flush=True)
        return out
    except Exception:  # noqa: BLE001 — summary line, then the full evidence
        print(f"  {name:44s} FAIL — full traceback follows", flush=True)
        raise


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", choices=("kernels", "system"), default=None,
                    help="kernels: the single-chip Pallas sections; system: "
                         "the engine/mesh/autotune/audit sections (the ones "
                         "with multi-device legs). Default: both.")
    only = ap.parse_args(argv).only

    import jax

    from deeperspeed_tpu.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU visible (platform={dev.platform}) — this script "
              "checks Mosaic lowering and must run on hardware")
        return 1
    where = (f"platform={dev.platform} device_kind={dev.device_kind!r} "
             f"count={len(jax.devices())}")
    print(f"device: {where}; compile cache: {enable_compile_cache()}")
    if only != "system":
        _kernel_sections()
    if only != "kernels":
        _system_sections()
    print(f"ALL {(only or 'kernels + system').upper()} SECTIONS OK on "
          f"hardware ({where})")
    return 0


def _kernel_sections():
    import jax
    import jax.numpy as jnp

    # ---- dense flash attention ---------------------------------------- #
    from deeperspeed_tpu.ops.pallas.flash_attention import flash_attention

    for Dh, name in ((64, "flash Dh=64"), (128, "flash Dh=128")):
        B, S, H = 2, 1024, 4
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, Dh), jnp.bfloat16)
        _check(f"{name} fwd",
               jax.jit(lambda q=q: flash_attention(q, q, q, causal=True)))
        _check(f"{name} fwd+bwd",
               jax.jit(lambda q=q: jax.grad(
                   lambda q: (flash_attention(q, q, q, causal=True)
                              .astype(jnp.float32) ** 2).sum())(q)))

    # non-causal + odd-ish lengths through the auto-block path
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 640, 4, 64), jnp.bfloat16)
    _check("flash S=640 non-causal fwd+bwd",
           jax.jit(lambda q=q: jax.grad(
               lambda q: (flash_attention(q, q, q, causal=False)
                          .astype(jnp.float32) ** 2).sum())(q)))

    # v1 streaming kernel explicitly (the dispatch above routes short S to
    # the static kernel; v1 still serves S > MAX_STATIC_SEQ and explicit
    # block sizes — keep its Mosaic lowering exercised)
    q = jax.random.normal(jax.random.PRNGKey(2), (2, 1024, 4, 64), jnp.bfloat16)
    _check("flash v1 (explicit blocks) fwd+bwd",
           jax.jit(lambda q=q: jax.grad(
               lambda q: (flash_attention(q, q, q, causal=True, block_q=256,
                                          block_k=256)
                          .astype(jnp.float32) ** 2).sum())(q)))
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 4096, 2, 64), jnp.bfloat16)
    _check("flash v1 long-S (auto past static gate) fwd+bwd",
           jax.jit(lambda q=q: jax.grad(
               lambda q: (flash_attention(q, q, q, causal=True)
                          .astype(jnp.float32) ** 2).sum())(q)))

    # static kernel at its unroll ceiling
    from deeperspeed_tpu.ops.pallas.flash_static import (
        flash_attention_static_bhsd)

    q = jax.random.normal(jax.random.PRNGKey(4), (1, 2, 2048, 128),
                          jnp.bfloat16)
    _check("flash v2 static S=2048 fwd+bwd",
           jax.jit(lambda q=q: jax.grad(
               lambda q: (flash_attention_static_bhsd(q, q, q, causal=True)
                          .astype(jnp.float32) ** 2).sum())(q)))

    # ---- block-sparse attention --------------------------------------- #
    from deeperspeed_tpu.ops.sparse_attention.kernels import (
        make_block_sparse_attention)
    from deeperspeed_tpu.ops.sparse_attention.sparsity_config import (
        BigBirdSparsityConfig, FixedSparsityConfig)

    for S in (1024, 4096, 16384):
        H = 4
        cfg = FixedSparsityConfig(num_heads=H, block=128, num_local_blocks=4,
                                  num_global_blocks=1,
                                  attention="unidirectional")
        layout = np.asarray(cfg.make_layout(S))
        q = jax.random.normal(jax.random.PRNGKey(2), (1, S, H, 64),
                              jnp.bfloat16)
        outs = {}
        # both kernel families on hardware: 'resident' (flash-style,
        # whole-seq K/V in VMEM — only where the VMEM budget admits it)
        # and 'stream' (LUT-driven BlockSpec streaming, the long-S
        # fallback) — and their outputs must agree
        from deeperspeed_tpu.ops.sparse_attention.kernels import resident_ok
        impls = (("resident", "stream") if resident_ok(S, 64)
                 else ("stream",))
        for impl in impls:
            fn = make_block_sparse_attention(layout, 128, causal=True,
                                             impl=impl)
            outs[impl] = _check(f"sparse fixed S={S} {impl} fwd",
                                jax.jit(lambda q=q, fn=fn: fn(q, q, q)))
            _check(f"sparse fixed S={S} {impl} fwd+bwd",
                   jax.jit(lambda q=q, fn=fn: jax.grad(
                       lambda q: (fn(q, q, q).astype(jnp.float32) ** 2)
                       .sum())(q)))
        if "resident" in outs:
            d = np.max(np.abs(np.asarray(outs["resident"], np.float32)
                              - np.asarray(outs["stream"], np.float32)))
            assert d < 2e-2, f"resident/stream divergence {d} at S={S}"
            print(f"  resident/stream parity S={S}: max|d|={d:.2e}")

    cfg = BigBirdSparsityConfig(num_heads=4, block=128, num_random_blocks=1,
                                num_sliding_window_blocks=3,
                                num_global_blocks=1)
    fn = make_block_sparse_attention(np.asarray(cfg.make_layout(2048)), 128,
                                     causal=False)
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 2048, 4, 64), jnp.bfloat16)
    _check("sparse bigbird S=2048 fwd+bwd",
           jax.jit(lambda q=q: jax.grad(
               lambda q: (fn(q, q, q).astype(jnp.float32) ** 2).sum())(q)))

    # flat-LUT edge cases the width-LUT never hit: EMPTY block rows (dummy
    # invalid groups must flush ZERO outputs — asserted, not just finite),
    # an empty key COLUMN (empty row of the transposed dk/dv LUT), and
    # fully-skewed row/column runs
    layout = np.zeros((2, 16, 16), np.int64)
    layout[:, 0, :] = 1        # row 0 attends everything
    layout[:, :, 0] = 1        # everyone attends col 0
    layout[:, 7, :] = 0        # row 7 attends nothing
    layout[0, 7, 0] = 1        # ...except head 0
    layout[1, 0, 5] = 0        # head 1: col 5 has NO attending queries
    fn = make_block_sparse_attention(layout, 128, causal=False)
    q = jax.random.normal(jax.random.PRNGKey(6), (1, 2048, 2, 64),
                          jnp.bfloat16)

    def skewed_check(q=q, fn=fn):
        out = fn(q, q, q)
        # head 1 row-block 7 attends nothing: its output must be EXACT
        # zeros (stale-VMEM garbage would be finite and slip a checksum)
        empty = out[:, 7 * 128:8 * 128, 1, :].astype(jnp.float32)
        zero_ok = jnp.sum(jnp.abs(empty)) == 0.0
        grads = jax.grad(
            lambda q: (fn(q, q, q).astype(jnp.float32) ** 2).sum())(q)
        # poison the checksum iff the empty block was non-zero (a bare
        # multiply would NaN unconditionally: 0 * nan == nan)
        return grads.astype(jnp.float32) + jnp.where(zero_ok, 0.0, jnp.nan)

    _check("sparse skewed+empty rows/cols fwd+bwd",
           jax.jit(skewed_check))

    # ---- fused elementwise blocks ------------------------------------- #
    from deeperspeed_tpu.ops import kernel_config
    from deeperspeed_tpu.ops.pallas import fused_blocks

    with kernel_config.override(mode="fused"):
        for dtype, tag in ((jnp.float32, "fp32"), (jnp.bfloat16, "bf16")):
            x = jax.random.normal(jax.random.PRNGKey(7), (1024, 768), dtype)
            r = jax.random.normal(jax.random.PRNGKey(8), (1024, 768), dtype)
            w = jnp.ones((768,), jnp.float32)
            b = jnp.zeros((768,), jnp.float32)
            _check(f"fused layer_norm {tag} fwd+bwd",
                   jax.jit(lambda x=x, w=w, b=b: jax.grad(
                       lambda x: (fused_blocks.layer_norm(x, w, b, 1e-5)
                                  .astype(jnp.float32) ** 2).sum())(x)))
            _check(f"fused add_layer_norm {tag} fwd+bwd",
                   jax.jit(lambda x=x, r=r, w=w, b=b: jax.grad(
                       lambda x: (fused_blocks.add_layer_norm(x, r, w, b, 1e-5)
                                  .astype(jnp.float32) ** 2).sum())(x)))
            h = jax.random.normal(jax.random.PRNGKey(9), (2048, 1536), dtype)
            hb = jax.random.normal(jax.random.PRNGKey(10), (1536,), dtype)
            for approx in (True, False):
                _check(f"fused bias_gelu {tag} approx={approx} fwd+bwd",
                       jax.jit(lambda h=h, hb=hb, a=approx: jax.grad(
                           lambda h: (fused_blocks.bias_gelu(h, hb, a)
                                      .astype(jnp.float32) ** 2).sum())(h)))

    # ---- fused Adam ---------------------------------------------------- #
    from deeperspeed_tpu.ops.pallas.fused_adam import fused_adam_leaf

    p = jax.random.normal(jax.random.PRNGKey(11), (512, 2048), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(12), (512, 2048), jnp.float32)
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)
    _check("fused adam (adamw + bf16 cast)",
           jax.jit(lambda: fused_adam_leaf(
               p, g, m, v, 1e-3, 0.9, 0.95, b1=0.9, b2=0.95, eps=1e-8,
               wd=0.01, adam_w=True, cast_dtype=jnp.bfloat16)))

    # ---- fused quantize/dequant wire kernels ---------------------------- #
    from deeperspeed_tpu.ops.pallas import fused_quant

    # CPU CI only ever runs these in interpret mode; block=128 is the
    # Mosaic-eligible geometry (the supports() gate), so this is the
    # first time the compiled kernels exist at all
    xq = jax.random.normal(jax.random.PRNGKey(14), (8, 16 * 128),
                           jnp.float32)

    def quant_roundtrip(x=xq):
        q, s, r = fused_quant.quantize_rows(x, 128, want_residual=True,
                                            choice="pallas",
                                            interpret=False)
        w = fused_quant.pack_wire(q, s)
        q2, s2 = fused_quant.unpack_wire(w, x.shape[1], 128)
        tot = fused_quant.dequant_sum_rows(q2, s2, 128, choice="pallas",
                                           interpret=False)
        back = fused_quant.dequant_rows(q2, s2, 128, divisor=8.0,
                                        choice="pallas", interpret=False)
        # poison the checksum iff the packed wire lost bits or the
        # rebuild/residual escape the half-quantum error bound
        bound = jnp.repeat(s, 128, axis=1) * 0.5000001
        ok = (jnp.all(q2 == q) & jnp.all(s2 == s)
              & jnp.all(jnp.abs(back * 8.0 - x) <= bound)
              & jnp.all(jnp.abs(r) <= bound))
        return tot + jnp.where(ok, 0.0, jnp.nan)

    _check("fused quant pack/reduce/rebuild block=128",
           jax.jit(quant_roundtrip))

    def quant_parity(x=xq):
        # Mosaic vs the XLA formulation: scales within an ulp, values
        # within one rounding quantum (same bar as the interpret tests)
        qp, sp, _ = fused_quant.quantize_rows(x, 128, want_residual=False,
                                              choice="pallas",
                                              interpret=False)
        qx, sx, _ = fused_quant.quantize_rows(x, 128, want_residual=False,
                                              choice="xla")
        dq = jnp.max(jnp.abs(qp.astype(jnp.int32) - qx.astype(jnp.int32)))
        ds = jnp.max(jnp.abs(sp - sx) / sx)
        ok = (dq <= 1) & (ds < 1e-6)
        return jnp.where(ok, dq.astype(jnp.float32), jnp.nan)

    _check("fused quant Mosaic-vs-XLA parity", jax.jit(quant_parity))

    # world-size x blocks-per-chunk row counts that are not multiples of 8
    # (a four-chip host, an odd bucket): the kernels pad to 8 sublanes
    for shape in ((4, 200 * 128), (1, 13 * 128)):
        xo = jax.random.normal(jax.random.PRNGKey(16), shape, jnp.float32)
        _check(f"fused quant pack/reduce/rebuild rows={shape[0]} "
               f"blocks={shape[1] // 128}",
               jax.jit(lambda x=xo: quant_roundtrip(x)))

    xb16 = jax.random.normal(jax.random.PRNGKey(15), (1000,), jnp.bfloat16)
    _check("fused quant bf16 non-divisible flat API",
           lambda: fused_quant.quantize_blocks(xb16, 128, choice="pallas",
                                               interpret=False))

    # ---- dense super-tile flash ---------------------------------------- #
    from deeperspeed_tpu.ops.pallas.flash_static import (
        flash_attention_supertile_bhsd)

    for shape, causal in (((4, 2, 64, 64), True),
                          ((64, 16, 128, 64), False)):  # bert128 geometry
        q = jax.random.normal(jax.random.PRNGKey(13), shape, jnp.bfloat16)
        _check(f"supertile {shape} causal={causal} fwd+bwd",
               jax.jit(lambda q=q, c=causal: jax.grad(
                   lambda q: (flash_attention_supertile_bhsd(q, q, q, causal=c)
                              .astype(jnp.float32) ** 2).sum())(q)))

    # ---- paged decode attention ---------------------------------------- #
    # the serving cell's geometry (16 slots x 128 pages of 16, 16 heads of
    # 128) and a grouped-query one, ragged lengths with idle slots first,
    # between and last, against the XLA form the CPU runs
    from deeperspeed_tpu.ops.pallas.paged_decode_attn import (
        is_available, paged_decode_attn)
    from deeperspeed_tpu.serving.kv_cache import paged_attend_rows

    for H, Hkv in ((16, 16), (32, 16)):
        N, L, bs, bps, Dh = 16, 2, 16, 128, 128
        lengths = [0, 1, 16, 17, 0, 128, 129, 2047, 700, 0, 333, 1024, 5, 64,
                   1999, 0]
        nb = 1 + sum(-(-(n + 1) // bs) for n in lengths if n)
        rng = np.random.default_rng(H)
        tables, nxt = np.zeros((N, bps), np.int32), 1
        for i, n in enumerate(lengths):
            used = -(-(n + 1) // bs) if n else 0
            tables[i, :used] = np.arange(nxt, nxt + used)
            nxt += used

        def arr(*shape, rng=rng):
            return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

        kp, vp = arr(L, nb, bs, Hkv, Dh), arr(L, nb, bs, Hkv, Dh)
        q, kr, vr = arr(N, 1, H, Dh), arr(N, Hkv, Dh), arr(N, Hkv, Dh)
        tables, lengths = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
        assert is_available(kp, tables, H)

        def parity(args=(kp, vp, jnp.int32(1), q, kr, vr, tables, lengths)):
            got = paged_decode_attn(*args).astype(jnp.float32)
            want = jax.jit(paged_attend_rows)(*args).astype(jnp.float32)
            gap = float(jnp.max(jnp.abs(got - want)))
            assert gap < 0.05, f"kernel and XLA form differ by {gap}"
            return got

        _check(f"paged decode attn H={H} Hkv={Hkv} vs XLA form", parity)

    # ---- fused transformer layer -------------------------------------- #
    from deeperspeed_tpu.ops.transformer import (
        DeepSpeedTransformerConfig, DeepSpeedTransformerLayer)

    tcfg = DeepSpeedTransformerConfig(
        batch_size=-1, max_seq_length=256, hidden_size=256,
        intermediate_size=1024, heads=4, fp16=True)
    layer = DeepSpeedTransformerLayer(tcfg)
    params = layer.init(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 256, 256), jnp.bfloat16)
    _check("fused transformer layer fwd+bwd",
           jax.jit(lambda: jax.grad(
               lambda x: (layer(params, x).astype(jnp.float32) ** 2).sum())(x)))


def _system_sections():
    import jax
    import jax.numpy as jnp

    # ---- comm overlap schedule on the real dp mesh ---------------------- #
    # standalone end-to-end check: the async reduce dispatch + boundary
    # drain must behave where collectives are real ICI DMAs, with the
    # Mosaic quant kernels on the reduce path (block=128), and the trace
    # must prove it — comm/reduce spans marked overlapped, one
    # comm/overlap_window per accumulation boundary, strict-schema valid
    import json
    import tempfile

    if jax.device_count() > 1:
        import deeperspeed_tpu as deepspeed
        from deeperspeed_tpu.monitor import shutdown_monitor
        from deeperspeed_tpu.monitor.validate import validate_file

        world = jax.device_count()

        def tiny_loss(p, b):
            xx, yy = b
            return jnp.mean((xx @ p["w"] - yy) ** 2)

        with tempfile.TemporaryDirectory() as td:
            trace = os.path.join(td, "trace.json")
            cfg = {
                "train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "train_batch_size": 4 * world,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "comm": {"mode": "int8", "bucket_mb": 0.001, "block": 128,
                         "overlap": "on"},
                "kernels": {"mode": "auto"},
                "monitor": {"trace_path": trace},
            }
            params = {"w": jnp.zeros((64, 32), jnp.float32)}
            try:
                engine, _, _, _ = deepspeed.initialize(
                    model=tiny_loss, model_parameters=params,
                    config_params=cfg)
                rng = np.random.default_rng(0)
                for _ in range(2):
                    for _m in range(2):
                        b = (jnp.asarray(rng.normal(size=(2 * world, 64)),
                                         dtype=jnp.float32),
                             jnp.asarray(rng.normal(size=(2 * world, 32)),
                                         dtype=jnp.float32))
                        engine(b)
                        engine.backward(allreduce_gradients=False)
                        engine.step()
                nb = engine.comm.n_buckets
            finally:
                shutdown_monitor()
            errs = validate_file(trace, strict=True)
            assert not errs, errs[:5]
            with open(trace) as f:
                raw = json.load(f)
            ev = raw["traceEvents"] if isinstance(raw, dict) else raw
            red = [e for e in ev if e.get("name") == "comm/reduce"
                   and e.get("ph") == "X"]
            win = [e for e in ev if e.get("name") == "comm/overlap_window"]
            assert len(red) == 2 * nb and len(win) == 2, (len(red),
                                                          len(win))
            assert all(e["args"]["overlapped"] for e in red)
            print(f"  {'comm overlap schedule (dp mesh)':44s} OK  "
                  f"({len(red)} overlapped reduces, {len(win)} windows)")
    else:
        print("  comm overlap schedule skipped: single-device host")

    # ---- perf doctor: compiled cost + real HBM numbers ------------------ #
    # the CPU suite can only prove the plumbing; the compiled cost model
    # and the allocator ledger only exist here
    from deeperspeed_tpu.monitor.memwatch import (aggregate_memory_stats,
                                                  device_memory_stats)
    from deeperspeed_tpu.monitor.perf import (CompiledCostIndex,
                                              platform_peaks)

    peaks = platform_peaks()
    print(f"  platform peaks: {peaks}")
    mem = aggregate_memory_stats()
    if mem:
        print(f"  hbm: {mem.get('bytes_in_use', 0) / 2**30:.3f} GiB in use, "
              f"{mem.get('peak_bytes_in_use', 0) / 2**30:.3f} GiB peak, "
              f"limit {mem.get('bytes_limit', 0) / 2**30:.3f} GiB "
              f"({len(jax.local_devices())} devices)")
        per0 = device_memory_stats()
        print(f"  hbm[dev0]: {per0}")
    else:
        print("  hbm: no allocator ledger on this backend")

    ci = CompiledCostIndex()
    d = 1024
    mm = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((d, d), jnp.bfloat16)
    rec = ci.observe("smoke/matmul1024", mm, (a, a))
    assert rec is not None and rec.error is None, rec and rec.error

    import time as _time
    mm(a, a).block_until_ready()  # warm
    t0 = _time.perf_counter()
    for _ in range(10):
        out = mm(a, a)
    out.block_until_ready()
    stats = ci.step_stats("smoke/matmul1024", (_time.perf_counter() - t0) / 10)
    assert stats is not None
    print(f"  {'compiled cost (1024^3 bf16 matmul)':44s} OK  "
          f"(flops {rec.flops:.3g}, bytes {rec.bytes_accessed:.3g}, "
          f"peak_hbm {rec.peak_bytes:.3g})")
    print(f"  {'measured matmul roofline':44s} OK  "
          f"(mfu {stats['mfu']:.3f}, {stats['tflops']:.1f} TF, "
          f"{stats['verdict']})")

    # ---- sharding substrate: canonical mesh on real chips --------------- #
    # the CPU suite proves placement semantics on virtual devices; this
    # proves the "mesh" block trains on the real topology (build_mesh's
    # ICI-aware device arrangement only matters here) and that ZeRO
    # shards genuinely land distributed — param_sharded_frac from live
    # device buffers, not specs
    if jax.device_count() > 1 and jax.device_count() % 2 == 0:
        import deeperspeed_tpu as deepspeed
        from deeperspeed_tpu.sharding import audit_tree, describe

        world = jax.device_count()

        def mesh_loss(p, b):
            xx, yy = b
            return jnp.mean((jnp.tanh(xx @ p["w1"]) @ p["w2"] - yy) ** 2)

        mesh_params = {
            "w1": jnp.zeros((64, 128), jnp.float32),
            "w2": jnp.zeros((128, 32), jnp.float32),
        }
        cfg = {
            "train_micro_batch_size_per_gpu": 2,
            "train_batch_size": 2 * world,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 3},
            "mesh": {"dp": 2, "fsdp": -1},
        }
        engine, _, _, _ = deepspeed.initialize(
            model=mesh_loss, model_parameters=mesh_params,
            config_params=cfg)
        rng = np.random.default_rng(1)

        def mesh_step():
            b = (jnp.asarray(rng.normal(size=(2 * world, 64)),
                             dtype=jnp.float32),
                 jnp.asarray(rng.normal(size=(2 * world, 32)),
                             dtype=jnp.float32))
            return engine.train_batch(b)

        _check(f"mesh block zero3 train_batch ({describe(engine.mesh)})",
               mesh_step)
        if world >= 4:  # fsdp extent > 1: params must actually shard
            aud = audit_tree(engine.state.params, mesh=engine.mesh)
            assert aud["sharded_frac"] > 0.5, aud
            print(f"  {'mesh zero3 placement audit':44s} OK  "
                  f"(sharded_frac {aud['sharded_frac']:.3f})")
    else:
        print("  mesh substrate skipped: needs an even multi-device host")

    # autotune on real chips: the CPU CI run prices against nominal
    # peaks — here the budget comes from the device_kind row of the
    # platform table, HBM feasibility is a real constraint, and
    # the fused kernel route flips from infeasible-on-CPU to preferred
    if jax.device_count() > 1 and jax.device_count() % 2 == 0:
        from deeperspeed_tpu.autotune import (
            ModelSpec, enumerate_mesh_layouts, platform_budget,
            price_layout, rank_candidates, sandboxed_cost_index)
        from deeperspeed_tpu.autotune.__main__ import _price_kernel_routes
        from deeperspeed_tpu.autotune.space import enumerate_kernel_routes

        world = jax.device_count()
        tune_model = ModelSpec()
        tune_budget = platform_budget()

        def autotune_price():
            idx = sandboxed_cost_index()
            cands = enumerate_mesh_layouts(world, tune_model,
                                           zero_stages=(1, 3))[:4]
            prices = [price_layout(c, tune_model, world, tune_budget,
                                   index=idx)[0] for c in cands]
            ranked, pruned = rank_candidates(prices)
            assert ranked, [p.reason for p in pruned]
            for p in pruned:  # HBM prunes must carry their reason
                assert p.reason, p.name
            print(f"    best: {ranked[0].name} "
                  f"({ranked[0].predicted_step_s * 1e3:.3f} ms modeled on "
                  f"{tune_budget['source']})")
            return jnp.zeros(())

        _check(f"autotune AOT pricing ({jax.device_count()} devices)",
               autotune_price)

        def autotune_kernel_routes():
            kp = _price_kernel_routes(enumerate_kernel_routes(), 1e-3,
                                      tune_budget)
            by_mode = {p.detail["kernels"]["mode"]: p for p in kp}
            if tune_budget["source"] != "cpu":
                # on the chip the fused route must be admissible AND
                # discounted vs 'off'
                assert by_mode["fused"].feasible
                assert (by_mode["fused"].predicted_step_s
                        < by_mode["off"].predicted_step_s)
            return jnp.zeros(())

        _check("autotune kernel-route pricing", autotune_kernel_routes)
    else:
        print("  autotune pricing skipped: needs an even multi-device host")

    # static analysis on REAL lowerings: the CPU CI audit proves the
    # programs are clean on a virtual mesh; the alias table, collective
    # layout, and callback set can all differ once Mosaic/XLA-TPU
    # compile the same entry points, so re-audit on the chip
    from deeperspeed_tpu.analysis import audit_default_programs

    def analysis_audit():
        notes = []
        findings = audit_default_programs(notes)
        for n in notes:
            print(f"    note: {n}")
        # no suppression file applies here: AST waivers don't cover
        # program audits, so every error-level finding is real
        errors = [f for f in findings if f.severity == "error"]
        for f in findings:
            print(f"    {f.severity}: {f.rule} @ {f.path}: {f.message}")
        assert not errors, f"{len(errors)} error-level audit finding(s)"
        return jnp.zeros(())

    _check("static program audit (donation/collective/callback)",
           analysis_audit)

    # ---- multi-host runtime: process-spanning mesh on the real pod ------ #
    # the CPU suite drills this over 2 localhost gloo processes; on a pod
    # slice the same facts must hold over ICI/DCN: the mesh spans
    # processes, topology derives the true per-host device partition, one
    # cross-host psum agrees with arithmetic, and the hierarchical wire
    # split prices intra+inter hops against the REAL local device count
    if jax.process_count() > 1:
        from jax.sharding import PartitionSpec as P

        from deeperspeed_tpu.distributed import topology as dtopo
        from deeperspeed_tpu.sharding import build_mesh

        world = jax.device_count()
        pod_mesh = build_mesh({"data": world})
        assert dtopo.is_process_spanning(pod_mesh), dtopo.describe(pod_mesh)
        groups = dtopo.process_groups()
        assert len(groups) == jax.process_count(), groups
        assert all(len(g) == jax.local_device_count()
                   for g in groups.values()), groups
        intra = dtopo.derive_intra_size(pod_mesh, ("data",))
        assert intra == jax.local_device_count(), (intra, groups)

        def pod_psum():
            from jax import shard_map
            ones = jnp.ones((world,), jnp.float32)

            @jax.jit
            def tot(x):
                f = shard_map(
                    lambda v: jax.lax.psum(v, "data"),
                    mesh=pod_mesh, in_specs=P("data"), out_specs=P())
                return f(x)

            out = float(jax.device_get(tot(ones))[0])
            assert out == float(world), (out, world)
            return jnp.asarray(out)

        _check(f"pod psum across {jax.process_count()} hosts "
               f"({world} devices)", pod_psum)

        from deeperspeed_tpu.runtime.comm.bucketing import build_plan
        from deeperspeed_tpu.runtime.comm.config import CommConfig
        from deeperspeed_tpu.runtime.comm.wiremodel import hier_wire_split

        if intra > 1:
            ccfg = CommConfig.from_dict({"mode": "int8", "bucket_mb": 1.0,
                                         "hierarchical": "auto"})
            plan = build_plan({"w": jnp.zeros((1024, 1024), jnp.float32)},
                              ccfg.bucket_bytes, ccfg.block * world)
            split = hier_wire_split(plan, ccfg, world, intra)
            assert split["inter_bytes"] > 0 and split["intra_bytes"] > 0, split
            print(f"  {'hierarchical wire split (real topology)':44s} OK  "
                  f"(intra {split['intra_bytes']} B, "
                  f"inter {split['inter_bytes']} B)")
        else:
            print("  hierarchical wire split skipped: one device per host")
    else:
        print("  multi-host runtime skipped: single-process slice (launch "
              "via the fleet supervisor or per-host launcher to exercise)")



if __name__ == "__main__":
    sys.exit(main())
