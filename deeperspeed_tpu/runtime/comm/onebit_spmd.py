"""Multi-worker 1-bit Adam: the WIRE path, as an SPMD train step.

`runtime/comm/onebit.py`'s OnebitAdam expresses the error-compensated
momentum quantization in-state (single-program view); this module supplies
the actual multi-worker communication pattern of the reference
(/root/reference/deepspeed/runtime/comm/nccl.py:47-186): post-warmup, each
data-parallel worker updates momentum with its LOCAL gradients, 1-bit
compresses it with worker error feedback, all_to_alls sign chunks to the
worker acting as "server" for that chunk, which averages, re-compresses
with SERVER error feedback and all_gathers the result — ~2 x n/8 bytes on
the wire per worker instead of the ~2 x 4n of a ring fp32 allreduce
(~32x). Warmup steps run exact data-parallel Adam (fp32 pmean of grads),
as the reference does before `freeze_step`.

The phase is STATIC per compiled program (the host flips functions at
freeze_step, like the reference flips comm paths): each phase's HLO then
contains exactly its own collectives, which is what lets
scripts/onebit_wire_bytes.py audit bytes-on-wire from the compiled module.
"""

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from ...parallel.topology import DATA_AXIS
from .compressed import _pack_signs, _unpack_signs


class OnebitCommState(NamedTuple):
    """Per-worker communication state: momentum/variance (replicated) plus
    the worker- and server-side error-feedback buffers (one row per data
    shard)."""
    m: jnp.ndarray        # (n,) replicated (post-sync momentum)
    v: jnp.ndarray        # (n,) replicated (frozen after warmup)
    werr: jnp.ndarray     # (W, n) sharded over data: worker error feedback
    serr: jnp.ndarray     # (W, c) sharded over data: server error feedback


def _chunk_len(n: int, W: int) -> int:
    """Per-server chunk length: ceil(n/W) rounded up to a byte of signs."""
    c = -(-n // W)
    return -(-c // 8) * 8


@jax.named_scope("ds.comm/onebit_2phase")
def onebit_all_reduce_2phase(x, axis_name: str, werr, serr, W: int):
    """Two-phase error-compensated 1-bit mean over ``axis_name``.

    x (n,) fp32 local value; werr (n,) worker error; serr (c,) server error
    for this device's chunk. Returns (mean (n,), new_werr, new_serr).
    Wire per device: n/8 bytes of signs each way + 2W scales."""
    n = x.shape[0]
    c = _chunk_len(n, W)
    corrected = x + werr
    xb = jnp.pad(corrected, (0, W * c - n)).reshape(W, c)
    scales = jnp.mean(jnp.abs(xb), axis=1)  # per-chunk L1 scale
    quant = jnp.where(xb >= 0, scales[:, None], -scales[:, None])
    new_werr = (xb - quant).reshape(-1)[:n]
    packed = jax.vmap(lambda r: _pack_signs(r)[0])(xb)  # (W, c/8) u8

    # phase 1: chunk j of every worker -> worker j ("server" for chunk j)
    recv = jax.lax.all_to_all(packed, axis_name, 0, 0)        # (W, c/8)
    rscale = jax.lax.all_to_all(
        scales.reshape(W, 1), axis_name, 0, 0)[:, 0]          # (W,)
    vals = jax.vmap(lambda p, s: _unpack_signs(p, c) * s)(recv, rscale)
    server_avg = jnp.mean(vals, axis=0)  # (c,)

    # phase 2: server compresses its averaged chunk (server error feedback,
    # reference's compensated server momentum) and broadcasts
    s_corr = server_avg + serr
    s_scale = jnp.mean(jnp.abs(s_corr))
    s_quant = jnp.where(s_corr >= 0, s_scale, -s_scale)
    new_serr = s_corr - s_quant
    s_packed, _ = _pack_signs(s_corr)
    all_packed = jax.lax.all_gather(s_packed, axis_name)      # (W, c/8)
    all_scales = jax.lax.all_gather(s_scale, axis_name)       # (W,)
    full = jax.vmap(lambda p, s: _unpack_signs(p, c) * s)(
        all_packed, all_scales).reshape(-1)[:n]
    return full, new_werr, new_serr


def make_onebit_spmd_train_step(loss_fn, optimizer, mesh,
                                phase: str, data_axis: str = DATA_AXIS):
    """Build (init_comm_state, jitted step) for 1-bit data-parallel Adam.

    ``optimizer`` supplies betas/eps/weight_decay (an OnebitAdam). ``phase``
    is 'warmup' (exact fp32 grad pmean + full Adam) or 'compressed'
    (local-momentum 1-bit exchange, frozen variance). step(params, comm,
    batch, lr) -> (params, comm, loss); batch leading dim shards over
    ``data_axis``."""
    if phase not in ("warmup", "compressed"):
        raise ValueError(f"phase must be 'warmup'|'compressed', got {phase}")
    b1, b2 = optimizer.betas
    eps, wd = optimizer.eps, optimizer.weight_decay
    W = mesh.shape[data_axis]

    def init_comm_state(params) -> OnebitCommState:
        import numpy as np

        flat, _ = ravel_pytree(jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))
        n = flat.shape[0]
        c = _chunk_len(n, W)
        # host numpy -> sharded device_put: the (W, n) error buffer never
        # materializes whole on one device (it is W model-sized rows)
        dev = lambda a: jax.device_put(
            a, NamedSharding(mesh, P(data_axis, None)))
        return OnebitCommState(
            m=flat, v=flat.copy(),
            werr=dev(np.zeros((W, n), np.float32)),
            serr=dev(np.zeros((W, c), np.float32)),
        )

    freeze_t = float(max(getattr(optimizer, "freeze_step", 1), 1))

    def body(params, m, v, werr, serr, batch, lr, stepc):
        werr, serr = werr[0], serr[0]  # this device's rows
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, data_axis)
        g, unravel = ravel_pytree(grads)
        p_flat, _ = ravel_pytree(params)
        p_flat = p_flat.astype(jnp.float32)
        t = stepc.astype(jnp.float32)
        bc1 = 1.0 - b1 ** t
        if phase == "warmup":
            g = jax.lax.pmean(g.astype(jnp.float32), data_axis)
            m_new = b1 * m + (1.0 - b1) * g
            v_new = b2 * v + (1.0 - b2) * g * g
            v_hat = v_new / (1.0 - b2 ** t)
        else:
            m_local = b1 * m + (1.0 - b1) * g.astype(jnp.float32)
            m_new, werr, serr = onebit_all_reduce_2phase(
                m_local, data_axis, werr, serr, W)
            v_new = v  # frozen; its bias correction freezes with it
            v_hat = v_new / (1.0 - b2 ** freeze_t)
        upd = (m_new / bc1) / (jnp.sqrt(v_hat) + eps)
        if wd:
            upd = upd + wd * p_flat
        new_params = jax.tree.map(
            lambda p, u: (p.astype(jnp.float32) - lr * u).astype(p.dtype),
            params, unravel(upd))
        return (new_params, m_new, v_new, werr[None], serr[None], loss)

    rep = P()
    sh = P(data_axis, None)
    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, rep, sh, sh, P(data_axis), rep, rep),
        out_specs=(rep, rep, rep, sh, sh, rep),
        check_vma=False,
    )

    @jax.jit
    def step(params, comm: OnebitCommState, batch, lr, step_idx):
        """step_idx: 1-based global Adam step (drives bias correction)."""
        new_p, m, v, werr, serr, loss = mapped(
            params, comm.m, comm.v, comm.werr, comm.serr, batch,
            jnp.float32(lr), jnp.asarray(step_idx, jnp.int32))
        return new_p, OnebitCommState(m=m, v=v, werr=werr, serr=serr), loss

    return init_comm_state, step


class OnebitLambCommState(NamedTuple):
    """1-bit LAMB wire state: OnebitCommState plus the per-leaf lamb
    scaling coefficients (live during warmup, FROZEN in the compressed
    phase — reference lamb.py:137 'frozen lamb coefficients')."""
    m: jnp.ndarray        # (n,) replicated
    v: jnp.ndarray        # (n,) replicated (frozen after warmup)
    werr: jnp.ndarray     # (W, n) sharded over data
    serr: jnp.ndarray     # (W, c) sharded over data
    ratios: jnp.ndarray   # (n_leaves,) replicated lamb coefficients


def make_onebit_lamb_spmd_train_step(loss_fn, optimizer, mesh,
                                     phase: str, data_axis: str = DATA_AXIS):
    """1-bit LAMB wire path (the 20B north-star names 1-bit LAMB,
    BASELINE.md row 5; reference runtime/fp16/onebit/lamb.py:11).

    Same two-phase momentum wire as make_onebit_spmd_train_step; the LAMB
    difference is the per-leaf trust ratio ||w|| / ||update||, which is
    LIVE during warmup and read from comm.ratios in the compressed phase
    (the reference's frozen scaling coefficients — recomputing the ratio
    from 1-bit momentum would feed quantization noise into the layer-wise
    learning rates). The host captures comm.ratios when flipping phases.

    step(params, comm, batch, lr, step_idx) -> (params, comm, loss).
    No bias correction, matching the in-state OnebitLamb (onebit.py:174).
    """
    if phase not in ("warmup", "compressed"):
        raise ValueError(f"phase must be 'warmup'|'compressed', got {phase}")
    b1, b2 = optimizer.betas
    eps, wd = optimizer.eps, optimizer.weight_decay
    min_c = getattr(optimizer, "min_coeff", 0.01)
    max_c = getattr(optimizer, "max_coeff", 10.0)
    W = mesh.shape[data_axis]

    adam_init, _ = make_onebit_spmd_train_step(loss_fn, optimizer, mesh,
                                               phase=phase,
                                               data_axis=data_axis)

    def init_comm_state(params) -> OnebitLambCommState:
        base = adam_init(params)  # same m/v/werr/serr layout and sharding
        return OnebitLambCommState(
            m=base.m, v=base.v, werr=base.werr, serr=base.serr,
            ratios=jnp.ones((len(jax.tree.leaves(params)),), jnp.float32),
        )

    def body(params, m, v, ratios, werr, serr, batch, lr):
        werr, serr = werr[0], serr[0]
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, data_axis)
        g, unravel = ravel_pytree(grads)
        if phase == "warmup":
            g = jax.lax.pmean(g.astype(jnp.float32), data_axis)
            m_new = b1 * m + (1.0 - b1) * g
            v_new = b2 * v + (1.0 - b2) * g * g
        else:
            m_local = b1 * m + (1.0 - b1) * g.astype(jnp.float32)
            m_new, werr, serr = onebit_all_reduce_2phase(
                m_local, data_axis, werr, serr, W)
            v_new = v  # frozen
        upd_flat = m_new / (jnp.sqrt(v_new) + eps)
        upd_tree = unravel(upd_flat)

        flat_p, treedef = jax.tree.flatten(params)
        flat_u = treedef.flatten_up_to(upd_tree)
        new_flat_p, live_ratios = [], []
        for i, (p, u) in enumerate(zip(flat_p, flat_u)):
            p32 = p.astype(jnp.float32)
            if wd:
                u = u + wd * p32
            w_norm = jnp.sqrt(jnp.sum(p32 * p32))
            u_norm = jnp.sqrt(jnp.sum(u * u))
            live = jnp.where(
                (w_norm > 0) & (u_norm > 0),
                jnp.clip(w_norm / u_norm, min_c, max_c),
                1.0,
            )
            ratio = live if phase == "warmup" else ratios[i]
            live_ratios.append(live)
            new_flat_p.append((p32 - lr * ratio * u).astype(p.dtype))
        new_params = treedef.unflatten(new_flat_p)
        # warmup tracks live ratios (the values frozen at the phase flip);
        # compressed keeps the frozen ones unchanged
        new_ratios = (jnp.stack(live_ratios) if phase == "warmup"
                      else ratios)
        return (new_params, m_new, v_new, new_ratios, werr[None], serr[None],
                loss)

    rep = P()
    sh = P(data_axis, None)
    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, rep, rep, sh, sh, P(data_axis), rep),
        out_specs=(rep, rep, rep, rep, sh, sh, rep),
        check_vma=False,
    )

    @jax.jit
    def step(params, comm: OnebitLambCommState, batch, lr, step_idx=None):
        """step_idx accepted for API symmetry with the Adam wire (LAMB has
        no bias correction, so it is unused)."""
        new_p, m, v, ratios, werr, serr, loss = mapped(
            params, comm.m, comm.v, comm.ratios, comm.werr, comm.serr,
            batch, jnp.float32(lr))
        return new_p, OnebitLambCommState(
            m=m, v=v, werr=werr, serr=serr, ratios=ratios), loss

    return init_comm_state, step
