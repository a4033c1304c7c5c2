"""HBM audit for the streamed ZeRO-Infinity engine, WITHOUT the 40-minute
host-state build: construct a skeletal StreamedOffloadEngine (templates
only — ShapeDtypeStructs, no 74GB Adam state, no uploads), AOT-compile each
device program, and print its compiled memory_analysis().

Motivation: the 6.7B scale demo died with TPU RESOURCE_EXHAUSTED inside the
per-group backward at seq 1024 even with the chip exclusive. The resident
set (bf16 params ~12.9GB + globals ~0.41GB + boundaries) is fixed by
design, so whether the demo fits is decided by the largest single program's
temp allocation. This prints exactly that, per program, in minutes.

Usage:
  python scripts/infinity_mem_audit.py [--model 6.7b] [--seq 1024]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def skeletal_engine(cfg, scfg):
    """A StreamedOffloadEngine with metadata and programs but NO host
    state and NO device uploads (templates are abstract)."""
    from deeperspeed_tpu.runtime.offload.streaming import (
        StreamedOffloadEngine, _ChunkMeta)

    eng = object.__new__(StreamedOffloadEngine)
    eng.cfg, eng.scfg = cfg, scfg
    eng.device = jax.devices()[0]
    eng.n_groups = cfg.n_layer // scfg.group_layers
    eng.step_count = 0
    eng.timings = {}
    eng.capture_grads = False
    eng.last_grads = {}
    eng._rng = np.random.default_rng(scfg.seed)
    eng._leaf_templates, eng._meta = {}, {}
    eng.chunk_names, eng.n_params = [], 0

    D, F, G, V = cfg.d_model, cfg.ffn_dim, scfg.group_layers, cfg.vocab_size
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    lay = {
        "ln1_scale": sds(G, D), "ln1_bias": sds(G, D),
        "ln2_scale": sds(G, D), "ln2_bias": sds(G, D),
        "attn": {"wqkv": sds(G, D, cfg.qkv_dim), "bqkv": sds(G, cfg.qkv_dim),
                 "wo": sds(G, D, D), "bo": sds(G, D)},
        "mlp": {"wi": sds(G, D, F), "bi": sds(G, F),
                "wo": sds(G, F, D), "bo": sds(G, D)},
    }
    gl = {"embed": {"wte": sds(V, D)},
          "final_ln": {"scale": sds(D), "bias": sds(D)}}
    if not cfg.rotary:
        gl["embed"]["wpe"] = sds(cfg.max_seq, D)
    if not cfg.tie_embeddings:
        gl["lm_head"] = sds(D, V)
    for g in range(eng.n_groups):
        eng._leaf_templates[f"g{g}"] = lay
        eng._meta[f"g{g}"] = _ChunkMeta(lay, scfg.wire_bits,
                                        scfg.resident_bits)
        eng.chunk_names.append(f"g{g}")
    eng._leaf_templates["globals"] = gl
    eng._meta["globals"] = _ChunkMeta(gl, scfg.wire_bits,
                                      scfg.resident_bits)
    eng.chunk_names.append("globals")
    # every group owns distinct layers: the real count is all groups +
    # globals (ADVICE r3: a g0+globals shortcut undercounted ~n_groups x)
    eng.n_params = sum(m.total for m in eng._meta.values())
    eng._fns = {}
    eng._build_fns()
    return eng, lay, gl


def report(name, lowered):
    c = lowered.compile()
    m = c.memory_analysis()
    gb = 1 / 2**30
    print(f"{name:>12}: temp {m.temp_size_in_bytes * gb:6.2f} GB  "
          f"args {m.argument_size_in_bytes * gb:6.2f} GB  "
          f"out {m.output_size_in_bytes * gb:6.2f} GB  "
          f"(alias {m.alias_size_in_bytes * gb:5.2f} GB)", flush=True)
    return m


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="6.7b")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--micro-batch", type=int, default=1)
    ap.add_argument("--group-layers", type=int, default=1)
    ap.add_argument("--wire-bits", type=int, default=4)
    ap.add_argument("--resident-bits", type=int, default=16,
                    help="4|8 = quantized device residency (the 20B "
                         "profile); 16 = bf16 resident")
    ap.add_argument("--state", default="cpu", choices=["cpu", "nvme"])
    ap.add_argument("--host-state", default="fp32",
                    choices=["fp32", "bf16"])
    ap.add_argument("--swap-states", default="all",
                    choices=["all", "exp_avg_sq"])
    args = ap.parse_args()

    from deeperspeed_tpu.models.gpt import get_preset
    from deeperspeed_tpu.runtime.offload.streaming import StreamConfig

    preset = {"125m": "neox-125m", "1.3b": "neox-1.3b",
              "6.7b": "neox-6.7b", "20b": "neox-20b"}[args.model]
    cfg = get_preset(preset, tie_embeddings=True, remat=True,
                     dtype=jnp.bfloat16, attn_impl="auto", ce_chunk=128,
                     max_seq=max(args.seq, 2048))
    scfg = StreamConfig(micro_batch=args.micro_batch, seq=args.seq,
                        group_layers=args.group_layers,
                        wire_bits=args.wire_bits,
                        resident_bits=args.resident_bits,
                        state_device=args.state,
                        host_state=args.host_state,
                        swap_states=args.swap_states)
    eng, lay, gl = skeletal_engine(cfg, scfg)
    fns = eng._fns

    B, S, D = scfg.micro_batch, scfg.seq, cfg.d_model
    f32 = jnp.float32
    x_s = jax.ShapeDtypeStruct((B, S, D), cfg.dtype)
    tok_s = jax.ShapeDtypeStruct((B, S), jnp.int32)
    key_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
    blk = scfg.wire_block
    g_meta, gl_meta = eng._meta["g0"], eng._meta["globals"]
    pb, _, sc, _ = g_meta.wire_geometry(blk)
    wire_g = jax.ShapeDtypeStruct((sum(pb),), jnp.uint8)
    scal_g = jax.ShapeDtypeStruct((sum(sc),), f32)
    pbl, _, scl, _ = gl_meta.wire_geometry(blk)
    wire_gl = jax.ShapeDtypeStruct((sum(pbl),), jnp.uint8)
    scal_gl = jax.ShapeDtypeStruct((sum(scl),), f32)

    # head grads (bf16 like gl) except final_ln in fp32 (see f_head_bwd)
    d_gl_s = jax.tree.map(lambda s: s, gl)
    d_gl_s = dict(d_gl_s)
    d_gl_s["final_ln"] = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, f32), gl["final_ln"])

    def storage_aval(cname, template):
        meta = eng._meta[cname]
        if not meta.quant_resident:
            return template
        rpb, _, rsc, _, wl, _ = meta.res_geometry(blk)
        return {"c": jax.ShapeDtypeStruct((int(sum(rpb)),), jnp.uint8),
                "s": jax.ShapeDtypeStruct((int(sum(rsc)),), f32),
                "w": jax.ShapeDtypeStruct((int(sum(wl)),), jnp.bfloat16)}

    def resident_bytes(cname):
        meta = eng._meta[cname]
        if not meta.quant_resident:
            return meta.total * 2
        rpb, _, rsc, _, wl, _ = meta.res_geometry(blk)
        return sum(rpb) + 4 * sum(rsc) + 2 * sum(wl)

    resident = (resident_bytes("g0") * eng.n_groups
                + resident_bytes("globals"))
    bounds = (eng.n_groups + 1) * B * S * D * 2
    print(f"resident params {resident / 2**30:.2f} GB, "
          f"boundaries {bounds / 2**30:.2f} GB, n_groups {eng.n_groups}",
          flush=True)

    peak_extra = 0
    lay_st = storage_aval("g0", lay)
    gl_st = storage_aval("globals", gl)
    # quant-resident uplink buffers use the res geometry
    if not g_meta.quant_resident:
        up_g, upscal_g = wire_g, scal_g
    if not gl_meta.quant_resident:
        up_gl, upscal_gl = wire_gl, scal_gl
    for name, lowered in (
        ("embed", fns["embed"].lower(gl_st, tok_s)),
        ("group", fns["group"].lower(lay_st, x_s)),
        ("head_bwd", fns["head_bwd"].lower(gl_st, x_s, tok_s)),
        ("group_bwd", fns["group_bwd"].lower(lay_st, x_s, x_s, key_s)),
        ("embed_bwd", fns["embed_bwd"].lower(gl_st, x_s, d_gl_s, tok_s,
                                             key_s)),
    ) + (() if g_meta.quant_resident else (
        ("apply_g", fns["apply_g"].lower(lay_st, up_g, upscal_g)),
    )) + (() if gl_meta.quant_resident else (
        ("apply_glob", fns["apply_globals"].lower(gl_st, up_gl,
                                                  upscal_gl)),
    )):
        m = report(name, lowered)
        peak_extra = max(peak_extra, m.temp_size_in_bytes
                         + m.output_size_in_bytes)
    print(f"worst program temp+out: {peak_extra / 2**30:.2f} GB; "
          f"projected peak ~= resident + boundaries + worst = "
          f"{(resident + bounds + peak_extra) / 2**30:.2f} GB", flush=True)

    # step-time projection: the host link and the host optimizer
    # dominate, not the chip
    wire = 0
    for cname in ("g0", "globals"):
        meta = eng._meta[cname]
        mult = eng.n_groups if cname == "g0" else 1
        down = sum(meta.wire_geometry(blk)[0]) + 4 * sum(
            meta.wire_geometry(blk)[2])
        if meta.quant_resident:
            rg = meta.res_geometry(blk)
            up = sum(rg[0]) + 4 * sum(rg[2]) + 2 * sum(rg[4])
        else:
            wg = meta.wire_geometry(blk)
            up = sum(wg[0]) + 4 * sum(wg[2])
        wire += mult * (down + up)
    link = float(os.environ.get("DS_AUDIT_LINK_MBPS", "11"))
    host_ns_per_param = 10.0  # measured at 6.7B: ~65s host_opt / 6.65B
    nvme = 0.0
    if scfg.state_device == "nvme":
        per_state = 4 if scfg.host_state == "fp32" else 2
        n_states = 3 if scfg.swap_states == "all" else 1
        nvme = (2 * n_states * per_state * eng.n_params) / (1.17 * 2**30)
    t_wire = wire / (link * 1e6)
    t_host = host_ns_per_param * eng.n_params / 1e9
    print(f"step-time projection: wire {wire / 2**30:.1f} GB @ {link} MB/s "
          f"= {t_wire / 60:.1f} min; host opt ~{t_host:.0f}s; NVMe "
          f"{nvme:.0f}s; total ~{(t_wire + t_host + nvme) / 60:.1f} min "
          f"per step", flush=True)


if __name__ == "__main__":
    main()
