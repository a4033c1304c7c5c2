"""Mixture-of-Experts layer with expert parallelism, TPU-native.

The reference framework (DeepSpeed v0.3.15) predates DeepSpeed-MoE; expert
parallelism is listed as ABSENT in SURVEY.md §2.3. This module supplies the
capability the modern stack expects, designed for XLA/SPMD rather than the
later torch implementation:

  * GShard/Switch-style FIXED-CAPACITY routing: top-k gating produces dense
    dispatch/combine tensors (one-hot matmuls — static shapes, MXU-friendly,
    no data-dependent gather/scatter that would defeat jit).
  * expert weights carry a leading E axis sharded over the 'expert' mesh
    axis (PartitionSpec('expert', ...)); constraining the dispatched
    activations to the same axis makes XLA emit the all-to-all pair
    (tokens->experts, experts->tokens) over ICI — the pjit analog of
    DeepSpeed-MoE's torch.distributed.all_to_all.
  * the auxiliary load-balancing loss (Switch Transformer eq. 4) and router
    z-loss are returned for the caller to add to the task loss.

Public surface:
  init_moe_params / moe_param_specs — expert FFN + router pytrees
  moe_ffn(params, x, ...) -> (y, aux) — drop-in replacement for a dense FFN
  load_balancing_loss / router_z_loss

All of that is TRAINING's (and the ``attention`` kind's in the serving
programs, which take its dropless route: a token over capacity would be
a wrong token served): GeLU experts with biases, four ``dispatch_impl``s,
auxiliary losses. What a served stack of mixed layers takes as its
feed-forward (``mixers.feed_forward``, where ``GPTConfig.moe_num_experts``
is set) is the last section of this file:

  init_gated_experts — router + ``w_gate``/``w_up``/``w_down`` with a
      leading expert axis, no bias
  gated_experts(p, m, top_k, ...) -> (y, counts) — gated SiLU experts,
      routed as a config states it (a float32 softmax over all experts,
      the ``top_k`` largest, renormalised to sum 1 where it says so), with
      NO capacity: assignments sorted by expert feed three grouped
      products (``ops/pallas/grouped_matmul``: the kernel on one TPU,
      ``jax.lax.ragged_dot`` elsewhere), every one computed whatever the
      imbalance; a token that is not
      ``live`` (an idle lane of a decode step, a prompt chunk's padding)
      is routed nowhere and touches no expert
"""

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.topology import DATA_AXIS, EXPERT_AXIS, SEQ_AXIS


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    # capacity per expert = ceil(top_k * tokens / num_experts * capacity_factor)
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 1e-3
    # router computations always run in fp32 (small, numerically sensitive)

    # "dense": one-hot (T, E, C) dispatch/combine einsums — O(T*E*C*D) but
    #   pure matmuls, fastest at small E. "sorted": sort assignments by
    #   expert and build the (E, C, D) buffers with gather/scatter-add —
    #   O(T*k*(log(T*k) + D)), independent of E, the scalable path for
    #   E >= ~16. "auto" picks by num_experts. Both produce identical
    #   buffers (same drop order), so they are loss-equivalent.
    #   "dropless": MegaBlocks-style — sorted assignments feed
    #   jax.lax.ragged_dot grouped matmuls with NO capacity and NO token
    #   drops (dropped_frac is identically 0). With a live 'expert' mesh
    #   axis the dispatch becomes an explicit shard_map: lax.all_to_all
    #   with fixed per-destination slots routes each shard's assignments
    #   to the shard owning the expert (see _moe_ffn_dropless_ep for the
    #   slot/truncation contract), a local ragged_dot runs the shard's
    #   experts, and the reverse all_to_all brings outputs home.
    dispatch_impl: str = "auto"  # "auto" | "dense" | "sorted" | "dropless"

    # EP-dropless receive-buffer headroom: each expert shard statically
    # reserves ep_buffer_factor * (k * T / world) rows (1.0 = perfectly
    # balanced load). Under skew beyond the factor, overflow assignments
    # are dropped DETERMINISTICALLY (every shard computes the same greedy
    # truncation from the all-gathered counts) and reported in
    # dropped_frac. Set >= the 'expert' axis size for a mathematical
    # zero-drop guarantee (worst case: every token routes to one shard) at
    # the cost of proportional buffer memory and ragged_dot padding FLOPs.
    ep_buffer_factor: float = 2.0

    # Combine weights default to RAW softmax probabilities (Switch-style:
    # the mass of unselected experts damps the MoE branch, the residual
    # stream carries the rest). Set True for GShard/Mixtral convention:
    # renormalize the chosen top-k gates to sum to 1.
    normalize_gates: bool = False

    def resolved_dispatch_impl(self) -> str:
        if self.dispatch_impl != "auto":
            return self.dispatch_impl
        return "sorted" if self.num_experts >= 16 else "dense"


def init_moe_params(rng, d_model: int, d_ff: int, cfg: MoEConfig,
                    out_std: Optional[float] = None):
    """Expert FFN params stacked on a leading E axis + router weights."""
    E, D, F = cfg.num_experts, d_model, d_ff
    k1, k2, k3 = jax.random.split(rng, 3)
    std = 0.02
    out_std = out_std if out_std is not None else std
    return {
        "router": {"wg": (jax.random.normal(k1, (D, E), jnp.float32) * std)},
        "experts": {
            "wi": jax.random.normal(k2, (E, D, F), jnp.float32) * std,
            "bi": jnp.zeros((E, F), jnp.float32),
            "wo": jax.random.normal(k3, (E, F, D), jnp.float32) * out_std,
            "bo": jnp.zeros((E, D), jnp.float32),
        },
    }


def moe_param_specs():
    """Experts sharded over the 'expert' mesh axis; router replicated."""
    return {
        "router": {"wg": P(None, None)},
        "experts": {
            "wi": P(EXPERT_AXIS, None, None),
            "bi": P(EXPERT_AXIS, None),
            "wo": P(EXPERT_AXIS, None, None),
            "bo": P(EXPERT_AXIS, None),
        },
    }


def _constrain(x, mesh, spec):
    from .gpt import _shard_act

    return _shard_act(x, mesh, spec)


def router_topk(logits, top_k: int, normalize_gates: bool = False):
    """Shared routing decision: (probs (T,E), expert_idx (T,k), gate (T,k)).

    gate values are the chosen experts' softmax probabilities (raw Switch
    convention), optionally renormalized over the kept top-k
    (GShard/Mixtral). Both dispatch impls consume exactly this."""
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, top_k)  # values ARE the gates
    if normalize_gates:
        gate = gate / (jnp.sum(gate, axis=1, keepdims=True) + 1e-9)
    return probs, expert_idx, gate


def top_k_gating(logits, top_k: int, capacity: int,
                 normalize_gates: bool = False):
    """GShard-style dense routing tensors from router logits.

    logits: (T, E) fp32. Returns (dispatch (T, E, C) bool-ish fp32,
    combine (T, E, C) fp32, aux_metrics dict).

    Position of a token inside its expert's buffer = its rank among the
    tokens that chose that expert (cumsum over the token dim); tokens past
    capacity are dropped (their combine weight is 0 — the residual stream
    carries them, the standard Switch behavior).

    Combine weights are RAW softmax probabilities by default (Switch
    convention — see MoEConfig.normalize_gates); ``normalize_gates=True``
    renormalizes each token's chosen top-k gates to sum to 1
    (GShard/Mixtral convention)."""
    T, E = logits.shape
    probs, expert_idx, gate = router_topk(logits, top_k, normalize_gates)
    mask = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (T, k, E)

    # buffer positions: rank each (token, choice) among all assignments to
    # that expert — cumulate over the flattened (k, T) order so the k=0
    # choice of every token ranks before k=1 overflow
    mask_kt = mask.transpose(1, 0, 2).reshape(top_k * T, E)
    pos_kt = jnp.cumsum(mask_kt, axis=0) - mask_kt  # (k*T, E)
    pos = pos_kt.reshape(top_k, T, E).transpose(1, 0, 2)  # (T, k, E)

    keep = (pos < capacity).astype(jnp.float32) * mask  # (T, k, E)

    # scatter the k choices into (T, E, C)
    pos_c = jax.nn.one_hot(
        jnp.sum(pos * mask, axis=-1).astype(jnp.int32), capacity,
        dtype=jnp.float32,
    )  # (T, k, C)
    dispatch = jnp.einsum("tke,tkc->tec", keep, pos_c)
    combine = jnp.einsum("tke,tkc,tk->tec", keep, pos_c, gate)

    # Switch aux loss ingredients (computed on the FULL router distribution)
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    ce = jnp.mean(mask[:, 0, :], axis=0)  # fraction routed (top-1) per expert
    aux = {
        "mean_prob": me,
        "top1_frac": ce,
        # fraction of (token, choice) assignments that overflowed capacity
        "dropped_frac": 1.0 - jnp.sum(keep) / (T * top_k),
    }
    return dispatch, combine, aux


def sorted_assignments(expert_idx, capacity: int, num_experts: int):
    """Sort (token, choice) assignments by expert; rank within each expert.

    expert_idx: (T, k) int. Returns (order, tid, expert, pos, keep) — all
    (k*T,) arrays in sorted-by-expert order: the originating token id, the
    expert id, the rank of the assignment inside that expert's buffer, and
    whether it fits under ``capacity``.

    Assignments are flattened CHOICE-major (all tokens' choice 0, then
    choice 1, ...) before the stable sort, so ranks — and therefore which
    assignments overflow — match the dense path's cumsum order exactly:
    every token's primary choice outranks any token's secondary choice.
    """
    T, k = expert_idx.shape
    e_flat = expert_idx.T.reshape(-1)  # (k*T,) choice-major
    tid_flat = jnp.tile(jnp.arange(T, dtype=jnp.int32), k)
    order = jnp.argsort(e_flat, stable=True)
    e_s = e_flat[order]
    tid_s = tid_flat[order]
    starts = jnp.searchsorted(e_s, jnp.arange(num_experts))  # (E,)
    pos_s = jnp.arange(k * T, dtype=jnp.int32) - starts[e_s].astype(jnp.int32)
    keep_s = pos_s < capacity
    return order, tid_s, e_s, pos_s, keep_s


def load_balancing_loss(mean_prob, top1_frac, num_experts: int):
    """Switch Transformer eq. 4: E * sum_e me_e * ce_e (==1 when uniform)."""
    return num_experts * jnp.sum(mean_prob * top1_frac)


def router_z_loss(logits):
    """Stabilizes router logits (ST-MoE): mean logsumexp^2."""
    return jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)


def _moe_ffn_dropless(params, x, cfg: MoEConfig, act, logits, mesh):
    """MegaBlocks-style dropless dispatch: assignments sorted by expert
    feed ``jax.lax.ragged_dot`` grouped matmuls — every token is processed
    (no capacity, no drops), and compute scales with T*k regardless of the
    load distribution across experts."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, D)
    probs, expert_idx, gate = router_topk(logits, k, cfg.normalize_gates)
    # capacity = k*T keeps every assignment; reuse the shared sorter
    order, tid_s, e_s, _pos_s, _keep_s = sorted_assignments(
        expert_idx, k * T, E)
    gate_s = gate.T.reshape(-1)[order]
    group_sizes = jnp.zeros((E,), jnp.int32).at[e_s].add(1)

    xs = xt[tid_s]  # (k*T, D) sorted by expert
    wi = params["experts"]["wi"].astype(x.dtype)
    wo = params["experts"]["wo"].astype(x.dtype)
    h = jax.lax.ragged_dot(xs, wi, group_sizes).astype(x.dtype)
    h = h + params["experts"]["bi"].astype(x.dtype)[e_s]
    h = act(h)
    eo = jax.lax.ragged_dot(h, wo, group_sizes).astype(x.dtype)
    eo = eo + params["experts"]["bo"].astype(x.dtype)[e_s]

    # combine accumulates k expert outputs per token in fp32 (the dense
    # path's combine einsum accumulates fp32 on the MXU; a bf16 scatter
    # here would make the impls numerically different, not just faster)
    yt = jnp.zeros((T, D), jnp.float32).at[tid_s].add(
        (eo * gate_s.astype(x.dtype)[:, None]).astype(jnp.float32))
    y = yt.astype(x.dtype).reshape(B, S, D)
    y = _constrain(y, mesh, P(DATA_AXIS, SEQ_AXIS, None))

    aux = {
        "aux_loss": load_balancing_loss(
            jnp.mean(probs, axis=0),
            jnp.zeros(E, jnp.float32).at[expert_idx[:, 0]].add(1.0) / T, E),
        "z_loss": router_z_loss(logits),
        "dropped_frac": jnp.float32(0.0),  # dropless by construction
    }
    return y, aux


def _moe_ffn_dropless_ep(params, x, cfg: MoEConfig, act, mesh):
    """Dropless dispatch composed with EXPERT PARALLELISM.

    shard_map over the token axes ('data' x 'expert'): every device owns
    T/world tokens and E/ep experts. Each shard sorts its (token, choice)
    assignments by global expert id, packs them into fixed per-destination
    slots, exchanges with ``lax.all_to_all`` (the explicit-SPMD analog of
    DeepSpeed-MoE's torch all_to_all; portable to XLA:CPU where
    ragged-all-to-all is not implemented), runs its local experts with ONE
    ragged_dot (a zero-weight padding group absorbs empty slots), and
    reverses the exchange to combine at home.

    Static-shape contract: each (sender, destination) pair carries
    ``cap_pp = ceil(ep_buffer_factor * k * T_local / ep)`` slots.
    Assignments beyond a pair's slots drop DETERMINISTICALLY (reported in
    dropped_frac); since one sender holds at most k*T_local assignments
    for any destination, ``ep_buffer_factor >= ep`` is mathematically
    dropless under arbitrary routing skew."""
    from jax import shard_map
    from ..parallel.topology import filter_spec

    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    T = B * S
    if SEQ_AXIS in mesh.axis_names and mesh.shape[SEQ_AXIS] > 1:
        raise ValueError(
            "dropless EP does not compose with sequence parallelism; "
            "use dispatch_impl='sorted' when the 'seq' axis is live"
        )
    token_axes = tuple(
        a for a in (DATA_AXIS, EXPERT_AXIS)
        if a in mesh.axis_names and mesh.shape[a] > 1
    )
    ep = mesh.shape[EXPERT_AXIS]
    world = math.prod(mesh.shape[a] for a in token_axes)
    if E % ep:
        raise ValueError(f"num_experts {E} not divisible by expert axis {ep}")
    e_loc = E // ep
    if T % world:
        raise ValueError(f"tokens {T} not divisible by mesh world {world}")
    t_loc = T // world
    cap_pp = max(1, int(math.ceil(cfg.ep_buffer_factor * k * t_loc / ep)))
    cap = ep * cap_pp

    def body(xt, wg, wi, bi, wo, bo):
        # xt (t_loc, D); wi/bi/wo/bo carry this shard's e_loc experts
        xt = xt.reshape(t_loc, D)
        my = jax.lax.axis_index(EXPERT_AXIS)
        logits = xt.astype(jnp.float32) @ wg.astype(jnp.float32)
        probs, expert_idx, gate = router_topk(logits, k, cfg.normalize_gates)
        # choice-major flatten + stable sort by global expert id: rows for
        # each destination shard are contiguous runs
        e_flat = expert_idx.T.reshape(-1)
        tid = jnp.tile(jnp.arange(t_loc, dtype=jnp.int32), k)
        order = jnp.argsort(e_flat, stable=True)
        e_s = e_flat[order]
        tid_s = tid[order]
        gate_s = gate.T.reshape(-1)[order]
        dest = e_s // e_loc  # (k*t_loc,) destination shard per assignment
        shard_starts = jnp.searchsorted(
            e_s, jnp.arange(ep, dtype=jnp.int32) * e_loc).astype(jnp.int32)
        pos = (jnp.arange(k * t_loc, dtype=jnp.int32)
               - shard_starts[dest])  # rank within my run for that dest
        ok = pos < cap_pp  # pair-level slots; beyond = deterministic drop
        dropped = jnp.sum(1.0 - ok.astype(jnp.float32))
        slot = jnp.where(ok, dest * cap_pp + pos, cap)  # cap = dump row

        xs = xt[tid_s]  # (k*t_loc, D)
        sendx = jnp.zeros((cap + 1, D), xs.dtype).at[slot].set(xs)[:cap]
        sende = jnp.full((cap + 1,), E, jnp.int32).at[slot].set(e_s)[:cap]
        # (ep, cap_pp, ...) blocks; device d receives every sender's d-th
        # block — DeepSpeed-MoE's all_to_all with explicit slot packing
        x_recv = jax.lax.all_to_all(
            sendx.reshape(ep, cap_pp, D), EXPERT_AXIS, 0, 0).reshape(cap, D)
        e_recv = jax.lax.all_to_all(
            sende.reshape(ep, cap_pp), EXPERT_AXIS, 0, 0).reshape(cap)

        # group received rows by local expert; sentinel padding sorts last
        e_local = jnp.where(e_recv >= E, e_loc, e_recv - my * e_loc)
        order2 = jnp.argsort(e_local, stable=True)
        xs2 = x_recv[order2]
        e2 = e_local[order2]
        group_sizes = jnp.zeros((e_loc + 1,), jnp.int32).at[e2].add(1)

        zpadW = lambda w: jnp.concatenate(
            [w, jnp.zeros((1,) + w.shape[1:], w.dtype)])
        h = jax.lax.ragged_dot(
            xs2, zpadW(wi.astype(xs2.dtype)), group_sizes).astype(xs2.dtype)
        h = h + zpadW(bi.astype(xs2.dtype))[e2]
        h = act(h)
        eo = jax.lax.ragged_dot(
            h, zpadW(wo.astype(xs2.dtype)), group_sizes).astype(xs2.dtype)
        eo = eo + zpadW(bo.astype(xs2.dtype))[e2]
        eo = jnp.zeros_like(eo).at[order2].set(eo)  # back to recv order

        # reverse exchange brings each slot home to its sender
        eo_home = jax.lax.all_to_all(
            eo.reshape(ep, cap_pp, D), EXPERT_AXIS, 0, 0).reshape(cap, D)

        # fp32 combine at home; dropped assignments contribute zero
        okf = ok.astype(jnp.float32)
        eo_s = eo_home[jnp.clip(slot, 0, cap - 1)]
        contrib = (eo_s.astype(jnp.float32)
                   * (gate_s.astype(jnp.float32) * okf)[:, None])
        yt = jnp.zeros((t_loc, D), jnp.float32).at[tid_s].add(contrib)

        pmean = lambda v: jax.lax.pmean(
            v, token_axes if len(token_axes) > 1 else token_axes[0])
        aux_local = {
            "mean_prob": jnp.mean(probs, axis=0),
            "top1_frac": jnp.zeros(E, jnp.float32)
                           .at[expert_idx[:, 0]].add(1.0) / t_loc,
            "dropped_frac": dropped / (k * t_loc),
            "z": router_z_loss(logits),
        }
        return yt.astype(x.dtype), jax.tree.map(pmean, aux_local)

    tok_spec = P(token_axes if len(token_axes) > 1 else
                 (token_axes[0] if token_axes else None), None)
    exp = lambda *rest: filter_spec(P(EXPERT_AXIS, *rest), mesh)
    yt, aux_s = shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, P(None, None), exp(None, None), exp(None),
                  exp(None, None), exp(None)),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )(x.reshape(T, D),
      params["router"]["wg"],
      params["experts"]["wi"], params["experts"]["bi"],
      params["experts"]["wo"], params["experts"]["bo"])

    y = yt.reshape(B, S, D)
    y = _constrain(y, mesh, P(DATA_AXIS, SEQ_AXIS, None))
    aux = {
        "aux_loss": load_balancing_loss(
            aux_s["mean_prob"], aux_s["top1_frac"], E),
        "z_loss": aux_s["z"],
        "dropped_frac": aux_s["dropped_frac"],
    }
    return y, aux


def moe_ffn(params, x, cfg: MoEConfig, mesh=None, activation=None):
    """Drop-in MoE replacement for a dense FFN block.

    params: init_moe_params pytree (experts possibly 'expert'-sharded).
    x: (B, S, D) activations (any float dtype; router runs fp32).
    Returns (y (B, S, D), aux dict with 'aux_loss' and 'z_loss' scalars —
    scale by cfg.*_coef and add to the task loss)."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    T = B * S
    act = activation or (lambda h: jax.nn.gelu(h, approximate=True))

    impl = cfg.resolved_dispatch_impl()
    if impl == "dropless" and (
            mesh is not None and EXPERT_AXIS in mesh.axis_names
            and mesh.shape[EXPERT_AXIS] > 1):
        # EP path computes its router on per-shard tokens inside shard_map
        return _moe_ffn_dropless_ep(params, x, cfg, act, mesh)

    xt = x.reshape(T, D)
    logits = (xt.astype(jnp.float32)
              @ params["router"]["wg"].astype(jnp.float32))  # (T, E)
    # k*T assignments spread over E buffers (GShard convention: capacity
    # scales with top_k, else top-2 structurally drops second choices)
    capacity = max(1, math.ceil(k * T / E * cfg.capacity_factor))

    if impl == "dropless":
        return _moe_ffn_dropless(params, x, cfg, act, logits, mesh)

    if impl == "sorted":
        probs, expert_idx, gate = router_topk(logits, k, cfg.normalize_gates)
        order, tid_s, e_s, pos_s, keep_s = sorted_assignments(
            expert_idx, capacity, E)
        gate_s = gate.T.reshape(-1)[order]  # choice-major, sorted
        slot_s = e_s * capacity + jnp.minimum(pos_s, capacity - 1)
        contrib = xt[tid_s] * keep_s.astype(x.dtype)[:, None]  # (k*T, D)
        expert_in = jnp.zeros((E * capacity, D), x.dtype).at[slot_s].add(
            contrib).reshape(E, capacity, D)
        gaux = {
            "mean_prob": jnp.mean(probs, axis=0),
            "top1_frac": jnp.zeros(E, jnp.float32)
                           .at[expert_idx[:, 0]].add(1.0) / T,
            "dropped_frac": 1.0 - jnp.sum(keep_s) / (T * k),
        }
        combine = None
    else:
        dispatch, combine, gaux = top_k_gating(
            logits, k, capacity, normalize_gates=cfg.normalize_gates)
        # tokens -> expert buffers (XLA lowers the einsum + sharding
        # constraint to an all-to-all over the 'expert' axis when experts
        # are sharded)
        expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), xt)
    expert_in = _constrain(expert_in, mesh, P(EXPERT_AXIS, None, None))

    wi = params["experts"]["wi"].astype(x.dtype)
    wo = params["experts"]["wo"].astype(x.dtype)
    h = jnp.einsum("ecd,edf->ecf", expert_in, wi)
    h = h + params["experts"]["bi"].astype(x.dtype)[:, None, :]
    h = act(h)
    h = _constrain(h, mesh, P(EXPERT_AXIS, None, None))
    eo = jnp.einsum("ecf,efd->ecd", h, wo)
    eo = eo + params["experts"]["bo"].astype(x.dtype)[:, None, :]
    eo = _constrain(eo, mesh, P(EXPERT_AXIS, None, None))

    # expert buffers -> tokens
    if impl == "sorted":
        eo_flat = eo.reshape(E * capacity, D)
        w_s = (gate_s * keep_s).astype(x.dtype)[:, None]
        # fp32 combine accumulator, matching the dense path's fp32 MXU
        # accumulation (see the dropless combine above)
        yt = jnp.zeros((T, D), jnp.float32).at[tid_s].add(
            (eo_flat[slot_s] * w_s).astype(jnp.float32)).astype(x.dtype)
    else:
        yt = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), eo,
                        preferred_element_type=jnp.float32).astype(x.dtype)
    y = yt.reshape(B, S, D)
    y = _constrain(y, mesh, P(DATA_AXIS, SEQ_AXIS, None))

    aux = {
        "aux_loss": load_balancing_loss(gaux["mean_prob"], gaux["top1_frac"], E),
        "z_loss": router_z_loss(logits),
        "dropped_frac": gaux["dropped_frac"],
    }
    return y, aux


def moe_loss(aux, cfg: MoEConfig):
    """Total auxiliary loss term for one (or summed) moe_ffn aux dicts."""
    return cfg.aux_loss_coef * aux["aux_loss"] + cfg.z_loss_coef * aux["z_loss"]


# ------------------------------------------------------------------ #
# served: gated experts, routed as the config states, nothing dropped
# ------------------------------------------------------------------ #

# what ``gated_experts`` counts of one call, in this order; ``away`` only
# where the program holds a share of the experts (``held``)
EXPERT_COUNTS = ("experts_touched", "assignments", "max_load", "away")


def init_gated_experts(rng, d_model: int, d_ff: int, num_experts: int,
                       std: float = 0.02, out_std: Optional[float] = None):
    """Router and gated expert weights, float32, the expert axis first."""
    E, D, F = num_experts, d_model, d_ff
    k = jax.random.split(rng, 4)
    out_std = std if out_std is None else out_std
    w = lambda key, shape, s: jax.random.normal(key, shape, jnp.float32) * s
    return {"router": w(k[0], (D, E), std),
            "w_gate": w(k[1], (E, D, F), std), "w_up": w(k[2], (E, D, F), std),
            "w_down": w(k[3], (E, F, D), out_std)}


def route_top_k(m, router, top_k: int, normalize: bool,
                rule: str = "softmax", bias=None):
    """Which experts each token goes to, and with what weight. m: (T, D)
    in the compute dtype; router: (D, E). The logits' products are exact
    (both operands in m's dtype, sums in float32). Two rules:
    ``"softmax"``: a float32 softmax over ALL experts, the ``top_k``
    largest probabilities win and are the gates. ``"sigmoid_bias"``: the
    scores are ``sigmoid(logits)``, each expert on its own; the ``top_k``
    largest of ``score + bias`` win (``bias`` (E,), a learned selection
    bias), and the gates are the winners' UNBIASED scores. Under both,
    equal ones go to the lower index and ``normalize`` divides the
    winners' gates by their sum. -> (experts (T, top_k) int32, gates (T,
    top_k) float32)."""
    logits = jnp.dot(m, router.astype(m.dtype),
                     preferred_element_type=jnp.float32)
    if rule == "sigmoid_bias":
        score = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(score + bias.astype(jnp.float32), top_k)
        gate = jnp.take_along_axis(score, experts, axis=-1)
    else:
        gate, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if normalize:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), gate


def gated_experts(p, m, top_k: int, normalize: bool = True, live=None,
                  gate_mult: float = 1.0, layer=None, held=None,
                  shared=None, rule: str = "softmax"):
    """``sum_{e in top-k} g_e W_down^e(SiLU(W_gate^e m) * (W_up^e m))``
    for every token of m (T, D), with NO capacity: the T * top_k
    assignments are sorted by expert and meet the experts' weights in
    three grouped products (``ops/pallas/grouped_matmul``: the kernel on a
    TPU, ``jax.lax.ragged_dot`` elsewhere), so the work follows the
    assignments however they fall (all on one expert included) and none
    is dropped. p: ``init_gated_experts``' tree, any float dtype (cast to
    m's); with ``layer`` (a traced index) p is the STACK of many layers'
    trees, a layer axis first, and layer ``layer`` is meant: the products
    then take the whole stack as ``layers x experts`` groups with the
    other layers' sizes zero, and read the layer's weights where they lie
    (a slice by a traced index would be copied for them, every call).
    ``live`` (T,) bool: a token that is not live is assigned to no expert
    (it sorts past the last group, whose rows the product leaves alone)
    and gets zeros. ``rule``: ``route_top_k``'s (``"sigmoid_bias"`` reads
    ``p["router_bias"]``). ``held`` (first, count): the tree holds the
    experts ``first .. first + count - 1`` alone of the E the router
    scores: the routing is over all E, an assignment to an expert that is
    not held sorts past the last group as a dead lane's does and adds
    nothing HERE (its expert's chip adds it), and the groups are the
    ``count`` held experts. ``shared``: a ``mixers.gated_ffn`` tree every
    live token passes, added once. -> (y (T, D) in m's dtype, counts
    int32: ``EXPERT_COUNTS``, the held experts with any assignment, the
    assignments computed here, the largest expert's and, with ``held``
    alone, the live assignments that left)."""
    from ..ops.pallas.grouped_matmul import grouped_matmul_for

    T, D = m.shape
    E = p["router"].shape[-1] if held is None else held[1]
    cdt = m.dtype
    pick = (lambda a: a) if layer is None else (lambda a: a[layer])
    with jax.named_scope("ds.moe.route"):
        experts, gate = route_top_k(
            m, pick(p["router"]), top_k, normalize, rule,
            pick(p["router_bias"]) if rule == "sigmoid_bias" else None)
        flat = experts.reshape(-1)                  # token-major (T k,)
        if held is not None:
            flat = flat - held[0]
            here = (flat >= 0) & (flat < E)
            flat = jnp.where(here, flat, E)
        if live is not None:
            flat = jnp.where(jnp.repeat(live, top_k), flat, E)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.zeros((E + 1,), jnp.int32).at[flat].add(1)[:E]
        # where each assignment's row went, to bring its result home
        home = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * top_k, dtype=order.dtype))
    with jax.named_scope("ds.moe.experts"):
        xs = m[order // top_k]                      # (T k, D) by expert
        groups = sizes
        if layer is not None:
            n = p["w_gate"].shape[0]
            groups = jax.lax.dynamic_update_slice(
                jnp.zeros((n * E,), jnp.int32), sizes, (layer * E,))

        def product(x, w):
            w = w.astype(cdt)
            if layer is not None:
                w = w.reshape(n * E, *w.shape[2:])
            return grouped_matmul_for(x, w)(x, w, groups)

        h = product(xs, p["w_gate"])
        if gate_mult != 1.0:
            h = h * jnp.asarray(gate_mult, h.dtype)
        h = jax.nn.silu(h) * product(xs, p["w_up"])
        out = product(h.astype(cdt), p["w_down"])
        if held is not None:
            # a row past the groups holds whatever the buffer did
            gate = jnp.where(here.reshape(T, top_k), gate, 0.0)
            out = jnp.where(here[order][:, None], out, 0)
        # a token's top_k results weighted and summed in float32
        y = jnp.sum(out[home].reshape(T, top_k, D).astype(jnp.float32)
                    * gate[..., None], axis=1)
        if shared is not None:
            from .mixers import gated_ffn

            with jax.named_scope("ds.moe.shared"):
                y = y + gated_ffn(m, shared, cdt, gate_mult).astype(
                    jnp.float32)
        if live is not None:
            y = jnp.where(live[:, None], y, 0.0)
    counts = [jnp.sum(sizes > 0, dtype=jnp.int32),
              jnp.sum(sizes, dtype=jnp.int32), jnp.max(sizes)]
    if held is not None:
        real = T if live is None else jnp.sum(live, dtype=jnp.int32)
        counts.append(real * top_k - counts[1])
    return y.astype(cdt), jnp.stack(counts)
