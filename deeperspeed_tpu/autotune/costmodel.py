"""Static candidate pricing: rank configs without running them.

Per candidate the model combines three sources, every term attributable
in the emitted ``components`` dict:

  * **AOT compiled cost** — the candidate's real fused train step is
    built through ``deepspeed.initialize`` + ``engine._train_batch_fn()``
    and AOT lowered/compiled against abstract avals by the sandboxed
    capture (:mod:`.capture`). XLA's cost model supplies per-device
    ``flops`` / ``bytes_accessed`` / ``peak_bytes`` (verified per-device
    on sharded programs: argument bytes come back divided by the mesh
    size). The roofline max of compute and memory floors is the base
    step time — same methodology as ``CompiledCostIndex.step_stats``.
    One correction rides on top: XLA prices ZeRO-sharded programs
    per-SHARD (8x fewer flops for identical math), so ZeRO >= 2
    candidates are clamped to their same-mesh stage-1 sibling's
    captured compute/memory — ZeRO shards storage, never the math —
    and pay an explicit param re-gather wire term instead.
  * **Modeled wire traffic** — :mod:`~..runtime.comm.wiremodel` prices
    the reducer's actual :class:`BucketPlan` (mode bits × padded
    elements × ring factor) plus two collective launches per bucket;
    the launch-overhead term is what sinks tiny-bucket configs. Model-
    parallel layouts additionally pay for their per-layer activation
    collectives (tp all-reduces, sp ring-attention permutes) — without
    that term the AOT flops alone would call ``sp8`` the cheapest
    layout on a host where it measures slowest.
  * **HBM fit** — per-device ``peak_bytes`` (and the serving KV pool)
    against the platform's capacity. Infeasible candidates keep their
    price and gain ``feasible=False`` + a human-readable ``reason`` —
    they are REPORTED, never silently dropped.

CPU caveat (also in docs/tutorials/autotune.md): on the 8-virtual-device
host the roofline peaks are nominal, so absolute predictions are
meaningless — only the *ordering* is claimed, and the CLI's confirm
phase checks exactly that (Spearman of predicted against measured order).
"""

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from ..monitor.perf import platform_peaks
from ..runtime.comm import wiremodel
from ..runtime.comm.config import CommConfig
from .capture import aot_capture, sandboxed_cost_index
from .space import CommCandidate, LayoutCandidate, ModelSpec, ServingCandidate

__all__ = [
    "CandidatePrice",
    "platform_budget",
    "price_comm_variants",
    "price_layout",
    "price_serving",
    "rank_candidates",
]

# fixed per-collective dispatch overhead (seconds): the term a
# bucket_mb=0.05 config multiplies 40x. TPU launches cost microseconds;
# the single-core host pays python dispatch + thread fan-out per
# collective, which is why tiny buckets crater measured step time there.
LAUNCH_OVERHEAD_S = {"cpu": 1.5e-3, "tpu": 5e-6}


@dataclasses.dataclass
class CandidatePrice:
    """One priced candidate — kept whether or not it is feasible."""

    name: str
    kind: str  # "layout" | "comm" | "serving"
    feasible: bool = True
    reason: str = ""  # stated pruning reason when infeasible
    predicted_step_s: float = 0.0
    flops: float = 0.0            # per device, from the compiled cost model
    bytes_accessed: float = 0.0   # per device
    peak_hbm_bytes: float = 0.0   # per device
    wire_bytes: float = 0.0       # per device, modeled
    launches: float = 0.0
    components: Dict[str, float] = dataclasses.field(default_factory=dict)
    detail: Dict[str, object] = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["predicted_step_s"] = round(self.predicted_step_s, 9)
        return d


def platform_budget(
    hbm_gb: Optional[float] = None,
    peaks: Optional[dict] = None,
) -> Dict[str, float]:
    """Roofline + capacity numbers for the current platform, from the
    ``monitor.perf`` peak table (a device that is not in it raises);
    ``hbm_gb`` overrides capacity — the tests use that to force the
    HBM frontier onto tiny models."""
    p = dict(peaks or platform_peaks())
    src = str(p["source"])
    return {
        "source": src,
        "peak_flops": p["peak_tflops"] * 1e12,
        "peak_bw": p["peak_gbps"] * 1e9,
        "ici_bw": p["ici_gbps"] * 1e9,
        "hbm_bytes": (hbm_gb if hbm_gb is not None
                      else p["hbm_gib"]) * (1 << 30),
        "launch_overhead_s": LAUNCH_OVERHEAD_S["cpu" if src == "cpu"
                                               else "tpu"],
    }


def effective_micro(layout: LayoutCandidate, world: int, micro: int) -> int:
    """Per-device microbatch holding the GLOBAL token count constant
    across layouts: a tp8 mesh has dp_size 1, so its microbatch is 8x
    the dp8 microbatch — otherwise candidates would be priced on
    different workloads and the ranking would be meaningless."""
    return micro * (world // layout.dp_size)


def _train_config(model: ModelSpec, layout: LayoutCandidate, world: int,
                  micro: int, gas: int, comm_block: Optional[dict]) -> dict:
    micro = effective_micro(layout, world, micro)
    cfg = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "train_batch_size": micro * gas * layout.dp_size,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": layout.zero_stage},
        "mesh": layout.block(),
        "steps_per_print": 10 ** 9,
    }
    if comm_block is not None:
        cfg["comm"] = dict(comm_block)
    return cfg


def build_candidate_engine(model: ModelSpec, layout: LayoutCandidate,
                           world: int, *, micro: int = 2, gas: int = 1,
                           comm_block: Optional[dict] = None):
    """A real engine for ``layout`` — the same construction path
    mesh_bench uses, minus any ``monitor``/``resilience`` block so a
    speculative candidate can never install process-global state."""
    import jax
    import jax.numpy as jnp

    import deeperspeed_tpu as deepspeed
    from ..models.gpt import GPTConfig, make_gpt

    gcfg = GPTConfig(vocab_size=model.vocab, n_layer=model.n_layer,
                     n_head=model.n_head, n_kv_head=model.n_kv_head,
                     d_model=model.d_model, max_seq=model.seq,
                     remat=False, dtype=jnp.float32, attn_impl="xla",
                     rotary=True)
    init_fn, _, loss_fn, _ = make_gpt(gcfg)
    params = init_fn(jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed.initialize(
        model=loss_fn, model_parameters=params,
        config_params=_train_config(model, layout, world, micro, gas,
                                    comm_block))
    return engine


def _abstract_step_args(engine, model: ModelSpec):
    import jax
    import jax.numpy as jnp

    rows = (engine.train_micro_batch_size_per_gpu()
            * engine.gradient_accumulation_steps()
            * engine.data_parallel_size)
    batch = jax.ShapeDtypeStruct((rows, model.seq + 1), jnp.int32)
    import numpy as np
    lr = np.float32(1e-3)
    rng = (engine.rng, 0)
    if engine.comm is not None:
        return (engine.state, engine._comm_state, batch, lr, rng)
    return (engine.state, batch, lr, rng)


def price_layout(
    layout: LayoutCandidate,
    model: ModelSpec,
    world: int,
    budget: Dict[str, float],
    *,
    micro: int = 2,
    gas: int = 1,
    comm: Optional[CommCandidate] = None,
    index=None,
    keep_engine: bool = False,
):
    """Price one (layout[, comm]) candidate via AOT capture.

    Returns ``(CandidatePrice, engine_or_None)``. The engine comes back
    only with ``keep_engine=True`` (the confirm stage reuses it);
    otherwise it is dropped before returning so candidate sweeps hold
    one model's memory at a time.
    """
    comm_block = comm.block if comm is not None else None
    name = layout.name if comm is None else f"{layout.name}+{comm.name}"
    price = CandidatePrice(
        name=name, kind="layout" if comm is None else "comm",
        detail={"mesh": layout.block(), "zero_stage": layout.zero_stage,
                **({"comm": comm_block} if comm is not None else {})})
    engine = None
    try:
        engine = build_candidate_engine(model, layout, world, micro=micro,
                                        gas=gas, comm_block=comm_block)
    except Exception as e:  # noqa: BLE001 — report, never crash the sweep
        price.feasible = False
        price.reason = f"engine construction failed: {type(e).__name__}: {e}"
        return price, None

    idx = index if index is not None else sandboxed_cost_index()
    rec = aot_capture(name, engine._train_batch_fn(),
                      _abstract_step_args(engine, model), index=idx)
    if rec is None or rec.error is not None:
        price.feasible = False
        price.reason = (f"AOT capture failed: "
                        f"{rec.error if rec else 'no record'}")
        if not keep_engine:
            engine = None
        return price, engine

    price.flops = rec.flops
    price.bytes_accessed = rec.bytes_accessed
    price.peak_hbm_bytes = rec.peak_bytes

    # ZeRO >= 2 clamp: XLA's cost analysis prices ZeRO-sharded programs
    # per-SHARD — captured flops/bytes come back divided by the fsdp
    # extent (measured: fsdp8_zero3 reports 8x fewer flops than fsdp8
    # for identical math), which would rank ZeRO candidates as cheaper
    # COMPUTE, not just cheaper memory. ZeRO shards storage, never the
    # math: each device still runs the full forward/backward on its
    # rows. So clamp compute/memory to the same-mesh stage-1 sibling's
    # captured cost (cached in the index by mesh name — free when the
    # sibling is in the sweep, one extra AOT compile when not). The HBM
    # footprint is NOT clamped — sharded residency is the whole point.
    if layout.zero_stage >= 2:
        dense = dataclasses.replace(
            layout, name=layout.name.rsplit("_zero", 1)[0], zero_stage=1)
        ref = idx.get(dense.name)
        if ref is None or ref.error is not None:
            try:
                ref_engine = build_candidate_engine(
                    model, dense, world, micro=micro, gas=gas,
                    comm_block=comm_block)
                ref = aot_capture(dense.name, ref_engine._train_batch_fn(),
                                  _abstract_step_args(ref_engine, model),
                                  index=idx)
                del ref_engine
            except Exception:  # noqa: BLE001 — no ref, keep raw capture
                ref = None
        if ref is not None and ref.error is None:
            price.flops = max(price.flops, ref.flops)
            price.bytes_accessed = max(price.bytes_accessed,
                                       ref.bytes_accessed)
            price.detail["zero_dense_ref"] = dense.name

    # wire model: the reducer's real plan when a comm block rides along,
    # else one dense fp32 all-reduce of the whole gradient tree
    grad_elements = model.param_count()
    if engine.comm is not None:
        ccfg = CommConfig.from_dict(comm_block)
        wire = wiremodel.wire_summary(engine.comm.plan, ccfg,
                                      engine.comm.world, grad_elements)
    else:
        wire = wiremodel.wire_summary(None, None, layout.dp_size,
                                      grad_elements)
    price.wire_bytes = wire["wire_bytes_per_device"]
    price.launches = wire["collective_launches"]
    price.detail["wire"] = wire

    ext = layout.extents()

    # a 2D data mesh (dp x fsdp both > 1) reduces gradients in one
    # phase per sharded axis — same bytes on the wire, one extra
    # dispatch per collective (dp2_fsdp4 measures ~65% slower than dp8
    # on the launch-bound host while its captured cost is identical)
    n_data_axes = (1 if ext["dp"] > 1 else 0) + (1 if ext["fsdp"] > 1 else 0)
    if n_data_axes > 1:
        price.launches *= n_data_axes
        price.detail["data_axes"] = n_data_axes

    # ZeRO re-materialization traffic: stage 3 all-gathers the sharded
    # params for forward and again for backward; stage 2 broadcasts the
    # updated shard once per step. This is the comm ZeRO trades for its
    # memory savings — unpriced, ZeRO-3 looks like a free lunch.
    if layout.zero_stage >= 2 and ext["fsdp"] > 1:
        gathers = 2.0 if layout.zero_stage >= 3 else 1.0
        zb = (gathers * model.param_count() * 4
              * wiremodel.ring_factor(ext["fsdp"]))
        price.wire_bytes += zb
        price.launches += gathers
        price.detail["zero_gather"] = {"launches": gathers, "bytes": zb}

    # activation collectives on the model-parallel axes. The gradient
    # wire model above prices only the dp/fsdp reduction; tp inserts
    # per-layer activation all-reduces (2 fwd + 2 bwd, megatron) and sp
    # ring attention circulates KV blocks ((sp-1) permute steps fwd,
    # ~2x for backward), every layer, every step. On a launch-bound
    # host the DISPATCH COUNT of these is what buries sp8 — the AOT
    # flops alone would call it the cheapest layout, while each of
    # its launches is a dispatch the host pays for.
    rows = effective_micro(layout, world, micro)
    act_bytes = 0.0
    act_launches = 0.0
    if ext["tp"] > 1:
        n = 4.0 * model.n_layer
        act_launches += n
        act_bytes += (n * rows * model.seq * model.d_model * 4
                      * 2 * wiremodel.ring_factor(ext["tp"]))
    if ext["sp"] > 1:
        n = 3.0 * (ext["sp"] - 1) * model.n_layer
        act_launches += n
        act_bytes += (n * rows * (model.seq / ext["sp"])
                      * 2 * model.kv_heads * model.head_dim * 4)
    price.launches += act_launches
    price.detail["act"] = {"launches": act_launches, "bytes": act_bytes}

    compute_s = price.flops / budget["peak_flops"]
    memory_s = price.bytes_accessed / budget["peak_bw"]
    wire_s = (price.wire_bytes + act_bytes) / budget["ici_bw"]
    launch_s = price.launches * budget["launch_overhead_s"]
    price.components = {
        "compute_s": compute_s, "memory_s": memory_s,
        "wire_s": wire_s, "launch_s": launch_s,
    }
    price.predicted_step_s = max(compute_s, memory_s) + wire_s + launch_s

    if rec.peak_bytes > budget["hbm_bytes"]:
        price.feasible = False
        price.reason = (
            f"HBM: per-device footprint {rec.peak_bytes / (1 << 30):.3f} "
            f"GiB exceeds {budget['hbm_bytes'] / (1 << 30):.3f} GiB "
            f"({budget['source']})")
    if not keep_engine:
        engine = None
    return price, engine


def price_comm_variants(
    layout: LayoutCandidate,
    comms: Sequence[CommCandidate],
    model: ModelSpec,
    world: int,
    budget: Dict[str, float],
    *,
    micro: int = 2,
    gas: int = 1,
    index=None,
) -> List[CandidatePrice]:
    """Price every comm variant on a fixed layout (engine per variant —
    the quantize/pack arithmetic lands in the AOT flops, the wire in
    the model)."""
    out = []
    for c in comms:
        p, _ = price_layout(layout, model, world, budget, micro=micro,
                            gas=gas, comm=c, index=index)
        out.append(p)
    return out


def price_serving(
    cand: ServingCandidate,
    model: ModelSpec,
    budget: Dict[str, float],
    *,
    dtype_bytes: int = 4,
    accept_rate: float = 0.7,
) -> CandidatePrice:
    """Price a serving shape analytically: the KV pool + resident params
    must fit; among the fits, prefer the largest pool (fewest preempted
    sequences) then the tighter bucket grid (less prefill padding).

    Speculative variants (``"speculative"`` in the block) add the
    drafter's resident weights to the HBM gate (the drafter pool is
    already inside ``cand.kv_pool_bytes``) and scale the decode-cost
    component by the modeled round speedup at ``accept_rate`` per-token
    draft/target agreement: a round of K+1 drafter steps (each
    ``n_drafter/n_layer`` of a target step) plus one verify emits
    ``1 + sum(p^i, i=1..K)`` tokens, so a weak drafter or an
    over-greedy K prices WORSE than plain decode instead of silently
    winning on pool size."""
    params = model.param_bytes(dtype_bytes)
    spec = (cand.block.get("speculative")
            if isinstance(cand.block, dict) else None) or None
    spec_speedup, drafter_params = 1.0, 0
    if spec:
        K = int(spec.get("draft_k", 4))
        n_d = int((spec.get("drafter") or {}).get(
            "n_layer", max(1, model.n_layer // 4)))
        ratio = n_d / float(model.n_layer)
        p = min(max(float(accept_rate), 0.0), 1.0)
        emitted = 1.0 + sum(p ** i for i in range(1, K + 1))
        round_cost = (K + 1) * ratio + 1.0   # in target-step units
        spec_speedup = emitted / round_cost
        # truncated drafter: its layers are resident copies of the
        # target's first n_d — layer params dominate, embeddings shared
        drafter_params = int(params * ratio)
    need = cand.kv_pool_bytes + params + drafter_params
    price = CandidatePrice(
        name=cand.name, kind="serving",
        peak_hbm_bytes=float(need),
        detail={"serving": dict(cand.block),
                "prefill_buckets": list(cand.prefill_buckets),
                "kv_pool_bytes": cand.kv_pool_bytes,
                "param_bytes": params,
                "drafter_param_bytes": drafter_params})
    # waste proxy: mean padded fraction if prompts land uniformly in
    # [1, max bucket] — a finer grid scores lower
    buckets = sorted(cand.prefill_buckets)
    prev, waste = 0, 0.0
    for b in buckets:
        waste += (b - (prev + b + 1) / 2.0) * (b - prev)
        prev = b
    span = buckets[-1] if buckets else 1
    waste_frac = waste / (span * span) if span else 0.0
    pool_tokens = (int(cand.block["num_blocks"])
                   * int(cand.block["block_size"]))
    price.components = {"waste_frac": round(waste_frac, 6),
                        "pool_tokens": float(pool_tokens),
                        "decode_cost": round(1.0 / spec_speedup, 6)}
    if spec:
        price.components["spec_speedup"] = round(spec_speedup, 6)
        price.components["spec_accept_rate_assumed"] = float(accept_rate)
    # smaller is better for the ranking key; feasible pools are ranked
    # by decode cost then padding waste, with a tiny tie-break rewarding
    # pool headroom. decode_cost is 1.0 for plain decode on every
    # candidate, so the pre-speculative ordering is preserved exactly.
    price.predicted_step_s = (1.0 / spec_speedup + waste_frac
                              + 1.0 / (1.0 + pool_tokens))
    if need > budget["hbm_bytes"]:
        price.feasible = False
        price.reason = (
            f"HBM: KV pool {cand.kv_pool_bytes / (1 << 30):.3f} GiB + "
            f"params {(params + drafter_params) / (1 << 30):.3f} GiB "
            f"exceeds {budget['hbm_bytes'] / (1 << 30):.3f} GiB "
            f"({budget['source']})")
    return price


def rank_candidates(
    prices: Sequence[CandidatePrice],
) -> Tuple[List[CandidatePrice], List[CandidatePrice]]:
    """Split into (ranked feasible, pruned) — pruned candidates all carry
    a non-empty ``reason`` and stay in every report."""
    feasible = sorted((p for p in prices if p.feasible),
                      key=lambda p: (p.predicted_step_s, p.name))
    pruned = [p for p in prices if not p.feasible]
    for p in pruned:
        assert p.reason, f"pruned candidate {p.name} has no stated reason"
    return feasible, pruned
