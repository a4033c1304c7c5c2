"""Flash attention (forward + backward) as Pallas TPU kernels.

This is the TPU-native replacement for the reference's fused attention inside
csrc/transformer/ds_transformer_cuda.cpp (softmax_kernels.cu, transform
kernels): instead of materializing the (B, H, S, S) score tensor in HBM (the
XLA fallback does, and OOMs long sequences), the kernel streams K/V blocks
through VMEM with an online softmax, O(S) memory.

Layouts: the kernels run natively on (B, H, S, Dh) — the last two block dims
(S-block, Dh) satisfy the TPU (8, 128)-tiling rule for any Dh that is a
multiple of 8. `flash_attention` keeps the framework-wide (B, S, H, Dh)
convention and transposes at the boundary (XLA usually fuses these copies
into neighboring elementwise ops); `flash_attention_bhsd` skips them for
callers that already hold head-major tensors.

Performance notes (MXU):
  * all dot_generals take the *input* dtype (bf16) and accumulate fp32 via
    preferred_element_type — upcasting operands to fp32 first would run the
    matmuls as multi-pass fp32 MXU ops, ~6x slower;
  * the causal k-loop is split into a full (unmasked) phase and a diagonal
    (masked) phase so the in-block iota/where mask is only paid on diagonal
    blocks;
  * grid dimensions are declared "parallel" so Mosaic can software-pipeline
    the (batch, head, block) steps;
  * softmax statistics (m, l), exp, and accumulators stay fp32.

Backward is the standard flash-2 recomputation split into a dK/dV kernel
(grid over K blocks) and a dQ kernel (grid over Q blocks), using the saved
logsumexp.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernel_config

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _vmem_spec(block_shape=None, index_map=None):
    if block_shape is None:
        return pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _compiler_params(interpret, n_parallel, semantics=None):
    """Grid dimension semantics for Mosaic pipelining: "parallel" dims may
    reorder, "arbitrary" ones run in order (accumulation dims). Default:
    all-parallel with n_parallel dims; pass an explicit tuple otherwise."""
    if interpret:
        return {}
    return {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=semantics or ("parallel",) * n_parallel
        )
    }


def _auto_block(S, default):
    """Largest multiple-of-128 block <= default that divides S. When no
    divisor exists: whole-S for short sequences (a block equal to the full
    dim always tiles), else the largest 128-multiple <= default and the
    kernels run a masked tail (the final partial block is index-clamped and
    the out-of-range columns/rows masked — see the ragged paths below).

    Multiple of 128, not 8: block_q is also the LANE dim of the lse/delta
    BlockSpecs, and lane-dim blocks must be 128-divisible or span the full
    array (caught by scripts/tpu_smoke.py at S=640)."""
    b = min(default, S)
    for d in range(b - b % 128, 127, -128):
        if S % d == 0:
            return d
    if S <= default:
        return S
    return default - default % 128 if default >= 128 else S



def is_available(q) -> bool:
    """Cheap static gate used by models' attn_impl='auto'."""
    if not kernel_config.on_tpu():
        return False
    B, S, H, Dh = q.shape
    if S < 128 or S % 8 or Dh % 8:
        return False
    # the auto-picked blocks must also FIT: the (block_q, block_k) fp32
    # scores tile lives in VMEM, so a whole-S fallback at large awkward S
    # (no multiple-of-128 divisor in [128, default]) must fall back to XLA
    bq = _auto_block(S, DEFAULT_BLOCK_Q)
    bk = _auto_block(S, DEFAULT_BLOCK_K)
    if bq * bk * 4 > 8 * 1024 * 1024:
        return False
    # full-sequence residency: the fwd/dQ kernels pin whole-S K and V in
    # VMEM and the dK/dV kernel pins whole-S Q and dO, so at large S the
    # dominant tile is 2 * S * Dh in the input dtype. Hardware-measured
    # cap (v5e, 16MB VMEM/core): 4MB of resident pair (S=16384, Dh=64,
    # bf16) overflows scoped vmem by ~0.5MB once Mosaic double-buffers it
    # across the head grid dim and adds the score tiles; 3.5MB compiles.
    # Past this, ring/sparse/XLA attention take over.
    itemsize = q.dtype.itemsize if hasattr(q, "dtype") else 2
    if 2 * S * Dh * itemsize > int(3.5 * 1024 * 1024):
        return False
    return True


# ------------------------------------------------------------------ #
# forward
# ------------------------------------------------------------------ #


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, block_k,
                seq_len, causal):
    q = q_ref[0, 0]  # (BQ, D) input dtype — bf16 dots, fp32 accumulation
    bq = q.shape[0]
    qi = pl.program_id(2)
    q_start = qi * bq
    # ragged tail (block_k does not divide S): the last k block's read is
    # clamped to start at S - block_k (an in-bounds window that OVERLAPS the
    # previous block) and the already-processed overlap columns are masked
    # out, so every column is counted exactly once
    ragged = seq_len % block_k != 0
    nk = pl.cdiv(seq_len, block_k)

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, q.shape[1]), jnp.float32)

    def make_body(masked):
        def body(kb, carry):
            m, l, acc = carry
            start = kb * block_k
            if ragged:
                start = jnp.minimum(start, seq_len - block_k)
            k = k_ref[0, 0, pl.ds(start, block_k), :]
            v = v_ref[0, 0, pl.ds(start, block_k), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # (BQ, BK) fp32
            if masked:
                rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                valid = jnp.full(s.shape, True)
                if causal:
                    valid = rows >= cols
                if ragged:
                    valid &= cols >= kb * block_k
                s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc_new

        return body

    if causal and not ragged:
        # blocks strictly below the diagonal need no mask; the (at most
        # ceil(bq/bk)+1) blocks straddling it do. Bounds are clamped to nk
        # for the padded tail q block (q_start may exceed S there).
        num_full = jnp.minimum(q_start // block_k, nk)
        num_all = jnp.minimum(pl.cdiv(q_start + bq, block_k), nk)
        carry = jax.lax.fori_loop(0, num_full, make_body(False),
                                  (m0, l0, acc0))
        m, l, acc = jax.lax.fori_loop(num_full, num_all, make_body(True),
                                      carry)
    elif causal:
        num_all = jnp.minimum(pl.cdiv(q_start + bq, block_k), nk)
        m, l, acc = jax.lax.fori_loop(0, num_all, make_body(True),
                                      (m0, l0, acc0))
    else:
        carry = jax.lax.fori_loop(0, seq_len // block_k,
                                  make_body(False), (m0, l0, acc0))
        if ragged:
            carry = make_body(True)(nk - 1, carry)
        m, l, acc = carry
    o_ref[0, 0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0, 0] = m + jnp.log(l)


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    B, H, S, Dh = q.shape
    grid = (B, H, pl.cdiv(S, block_q))

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, block_k=block_k, seq_len=S, causal=causal
    )
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            _vmem_spec((1, 1, block_q, Dh), lambda b, h, i: (b, h, i, 0)),
            _vmem_spec((1, 1, S, Dh), lambda b, h, i: (b, h, 0, 0)),
            _vmem_spec((1, 1, S, Dh), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=[
            _vmem_spec((1, 1, block_q, Dh), lambda b, h, i: (b, h, i, 0)),
            _vmem_spec((1, 1, 1, block_q), lambda b, h, i: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret, 3),
    )(q, k, v)
    return o, lse


# ------------------------------------------------------------------ #
# backward
# ------------------------------------------------------------------ #


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, o_lse_ref, delta_ref,
                     dk_ref, dv_ref, *, sm_scale, block_q, seq_len, causal):
    k = k_ref[0, 0]  # (BK, D) input dtype
    v = v_ref[0, 0]
    bk = k.shape[0]
    ki = pl.program_id(2)
    k_start = ki * bk
    # ragged q tail: clamp the window like the fwd kernel's k reads and
    # mask the overlap ROWS (the clamped lse/delta reads stay in bounds, so
    # the masked p is exactly 0 — no NaN enters the dk/dv dots)
    ragged = seq_len % block_q != 0
    nq_all = pl.cdiv(seq_len, block_q)

    dk0 = jnp.zeros((bk, k.shape[1]), jnp.float32)
    dv0 = jnp.zeros((bk, v.shape[1]), jnp.float32)
    num_qb = seq_len // block_q

    def make_body(masked):
        def body(qb, carry):
            dk, dv = carry
            start = qb * block_q
            if ragged:
                start = jnp.minimum(start, seq_len - block_q)
            q = q_ref[0, 0, pl.ds(start, block_q), :]
            do = do_ref[0, 0, pl.ds(start, block_q), :]
            lse = o_lse_ref[0, 0, 0, pl.ds(start, block_q)]
            delta = delta_ref[0, 0, 0, pl.ds(start, block_q)]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # (BQ, BK)
            if masked:
                rows = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                valid = jnp.full(s.shape, True)
                if causal:
                    valid = rows >= cols
                if ragged:
                    valid &= rows >= qb * block_q
                s = jnp.where(valid, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])  # (BQ, BK) fp32
            pc = p.astype(do.dtype)
            dv_new = dv + jax.lax.dot_general(
                pc, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta[:, None]) * sm_scale
            dk_new = dk + jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return dk_new, dv_new

        return body

    if causal and not ragged:
        # q blocks strictly past this k block are unmasked; the straddling
        # blocks need the in-block mask
        start_qb = k_start // block_q
        full_from = pl.cdiv(k_start + bk, block_q)
        carry = jax.lax.fori_loop(start_qb, jnp.minimum(full_from, num_qb),
                                  make_body(True), (dk0, dv0))
        dk, dv = jax.lax.fori_loop(full_from, num_qb, make_body(False), carry)
    elif causal:
        start_qb = jnp.minimum(k_start // block_q, nq_all)
        dk, dv = jax.lax.fori_loop(start_qb, nq_all, make_body(True),
                                   (dk0, dv0))
    else:
        carry = jax.lax.fori_loop(0, num_qb, make_body(False), (dk0, dv0))
        if ragged:
            carry = make_body(True)(nq_all - 1, carry)
        dk, dv = carry
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_lse_ref, delta_ref, dq_ref,
                   *, sm_scale, block_k, seq_len, causal):
    q = q_ref[0, 0]  # input dtype
    do = do_ref[0, 0]
    lse = o_lse_ref[0, 0, 0]
    delta = delta_ref[0, 0, 0]
    bq = q.shape[0]
    qi = pl.program_id(2)
    q_start = qi * bq
    ragged = seq_len % block_k != 0  # same clamp+overlap-mask as the fwd
    nk = pl.cdiv(seq_len, block_k)

    dq0 = jnp.zeros((bq, q.shape[1]), jnp.float32)

    def make_body(masked):
        def body(kb, dq):
            start = kb * block_k
            if ragged:
                start = jnp.minimum(start, seq_len - block_k)
            k = k_ref[0, 0, pl.ds(start, block_k), :]
            v = v_ref[0, 0, pl.ds(start, block_k), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale
            if masked:
                rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                valid = jnp.full(s.shape, True)
                if causal:
                    valid = rows >= cols
                if ragged:
                    valid &= cols >= kb * block_k
                s = jnp.where(valid, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta[:, None]) * sm_scale
            return dq + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        return body

    if causal and not ragged:
        num_full = jnp.minimum(q_start // block_k, nk)
        num_all = jnp.minimum(pl.cdiv(q_start + bq, block_k), nk)
        dq = jax.lax.fori_loop(0, num_full, make_body(False), dq0)
        dq = jax.lax.fori_loop(num_full, num_all, make_body(True), dq)
    elif causal:
        num_all = jnp.minimum(pl.cdiv(q_start + bq, block_k), nk)
        dq = jax.lax.fori_loop(0, num_all, make_body(True), dq0)
    else:
        dq = jax.lax.fori_loop(0, seq_len // block_k, make_body(False), dq0)
        if ragged:
            dq = make_body(True)(nk - 1, dq)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _flash_bwd(res, g, sm_scale, causal, block_q, block_k, interpret):
    q, k, v, o, lse = res
    B, H, S, Dh = q.shape
    do = g
    # delta_i = sum_d dO_i * O_i, laid out (B, H, S) like lse
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[:, :, None, :]  # (B, H, 1, S) like lse

    dkdv = functools.partial(
        _bwd_dkdv_kernel, sm_scale=sm_scale, block_q=block_q, seq_len=S,
        causal=causal,
    )
    dk, dv = pl.pallas_call(
        dkdv,
        name="flash_bwd_dkv",
        grid=(B, H, pl.cdiv(S, block_k)),
        in_specs=[
            _vmem_spec((1, 1, S, Dh), lambda b, h, i: (b, h, 0, 0)),  # q
            _vmem_spec((1, 1, block_k, Dh), lambda b, h, i: (b, h, i, 0)),  # k
            _vmem_spec((1, 1, block_k, Dh), lambda b, h, i: (b, h, i, 0)),  # v
            _vmem_spec((1, 1, S, Dh), lambda b, h, i: (b, h, 0, 0)),  # do
            _vmem_spec((1, 1, 1, S), lambda b, h, i: (b, h, 0, 0)),  # lse
            _vmem_spec((1, 1, 1, S), lambda b, h, i: (b, h, 0, 0)),  # delta
        ],
        out_specs=[
            _vmem_spec((1, 1, block_k, Dh), lambda b, h, i: (b, h, i, 0)),
            _vmem_spec((1, 1, block_k, Dh), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, Dh), q.dtype),
        ],
        interpret=interpret,
        **_compiler_params(interpret, 3),
    )(q, k, v, do, lse, delta)

    dqk = functools.partial(
        _bwd_dq_kernel, sm_scale=sm_scale, block_k=block_k, seq_len=S,
        causal=causal,
    )
    dq = pl.pallas_call(
        dqk,
        name="flash_bwd_dq",
        grid=(B, H, pl.cdiv(S, block_q)),
        in_specs=[
            _vmem_spec((1, 1, block_q, Dh), lambda b, h, i: (b, h, i, 0)),  # q
            _vmem_spec((1, 1, S, Dh), lambda b, h, i: (b, h, 0, 0)),  # k
            _vmem_spec((1, 1, S, Dh), lambda b, h, i: (b, h, 0, 0)),  # v
            _vmem_spec((1, 1, block_q, Dh), lambda b, h, i: (b, h, i, 0)),  # do
            _vmem_spec((1, 1, 1, block_q), lambda b, h, i: (b, h, 0, i)),  # lse
            _vmem_spec((1, 1, 1, block_q), lambda b, h, i: (b, h, 0, i)),  # delta
        ],
        out_specs=_vmem_spec((1, 1, block_q, Dh), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dh), q.dtype),
        interpret=interpret,
        **_compiler_params(interpret, 3),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------------ #
# public API with custom VJP
# ------------------------------------------------------------------ #


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    o, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    return o


def _flash_vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret)
    # named so remat policies can pin JUST these residuals (see
    # jax.checkpoint_policies.save_only_these_names): saving o+lse (~2.1
    # bytes/activation-element) lets the backward skip re-running the
    # forward kernel while q/k/v are still rematerialized from the (cheap)
    # qkv projection — the sweet spot for billion-param single-chip runs
    from jax.ad_checkpoint import checkpoint_name

    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(sm_scale, causal, block_q, block_k, interpret, res, g):
    return _flash_bwd(res, g, sm_scale, causal, block_q, block_k, interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _resolve_blocks(S, block_q, block_k):
    """Block sizes need not divide S: the kernels run a masked tail for the
    final partial block (clamped window + overlap mask). Sequences shorter
    than a requested block clamp the block to S."""
    if block_q is None:
        block_q = _auto_block(S, DEFAULT_BLOCK_Q)
    if block_k is None:
        block_k = _auto_block(S, DEFAULT_BLOCK_K)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    return block_q, block_k


def attention_dispatch(shape, itemsize=2, causal=True, interpret=False,
                       mode=None, platform=None):
    """Decide which attention implementation a (B, H, S, Dh) geometry gets:
    'supertile' | 'static' | 'stream' | 'xla'.

    ``mode`` defaults to the global "kernels" config block; ``platform``
    defaults to the detected backend. Both are injectable so the dispatch
    decision itself is testable on CPU (the acceptance test pins
    platform='tpu' and asserts the BERT short-seq geometry routes to the
    super-tile kernel under mode 'auto').

    'xla' is advisory for model-level callers (flash_attention_bhsd itself
    never falls back — callers gate on is_available and friends)."""
    from .flash_static import (MAX_STATIC_SEQ, supertile_geometry_ok)

    B, H, S, Dh = shape
    kc = kernel_config.get()
    if mode is None:
        mode = kc.mode if kc.supertile else "off"
    tpu = kernel_config.on_tpu() if platform is None else platform == "tpu"
    if mode == "fused" or (mode == "auto" and tpu):
        if supertile_geometry_ok(B, H, S, Dh, itemsize):
            return "supertile"
    if interpret:
        return "stream"  # CPU tests target the v1 streaming blocks
    if not tpu:
        return "xla"
    if S <= MAX_STATIC_SEQ and S >= 8 and S % 8 == 0 and Dh % 8 == 0:
        return "static"
    return "stream"


def _mesh_spec_bhsd(mesh, B, H):
    """(B, H, S, Dh) PartitionSpec over ``mesh``: batch over the data
    axes, heads over the tensor-parallel axis, each only where it divides
    (an axis that does not divide is left out: the operand is gathered
    along it and every shard computes the whole of that dim)."""
    from jax.sharding import PartitionSpec as P

    from ...sharding.rules import batch_axes, tp_axis

    def fit(axes, n):
        kept = []
        for a in axes:
            if mesh.shape[a] > 1 and n % mesh.shape[a] == 0:
                kept.append(a)
                n //= mesh.shape[a]
        return tuple(kept) or None

    tp = tp_axis(mesh)
    return P(fit(batch_axes(mesh), B), fit((tp,) if tp else (), H),
             None, None)


def flash_attention_bhsd(
    q,
    k,
    v,
    causal: bool = True,
    sm_scale: float = None,
    block_q: int = None,
    block_k: int = None,
    interpret: bool = False,
):
    """Head-major entry point: q, k, v (B, H, S, Dh) -> (B, H, S, Dh).

    Traced under a multi-device mesh (kernel_config.mesh_scope) the call
    wraps itself in a ``shard_map`` over batch and heads: XLA cannot
    partition a Mosaic kernel, and attention is independent per
    (batch, head), so each shard runs the kernel on its own rows.

    This is the layout the kernels run in; callers that already hold
    head-major tensors avoid the boundary transposes.

    Dispatch (attention_dispatch): short sequences pack into the dense
    super-tile kernel when the "kernels" config block enables it;
    short/mid sequences route to the static-unrolled resident kernel
    (flash_static.py — hardware-measured 78 vs 45 TF at the 1.3B
    geometry); explicit block sizes or long S keep the v1 streaming
    kernel. interpret=True keeps v1 (CPU tests target its blocks) unless
    the kernels config forces the super-tile path."""
    B, H, S, Dh = q.shape
    mesh = kernel_config.active_mesh()
    if mesh is not None:
        spec = _mesh_spec_bhsd(mesh, B, H)

        def per_shard(q, k, v):
            with kernel_config.mesh_scope(None):
                return flash_attention_bhsd(
                    q, k, v, causal=causal, sm_scale=sm_scale,
                    block_q=block_q, block_k=block_k, interpret=interpret)

        return jax.shard_map(per_shard, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check_vma=False)(q, k, v)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(Dh)
    if block_q is None and block_k is None:
        from .flash_static import (flash_attention_static_bhsd,
                                   flash_attention_supertile_bhsd,
                                   is_static_available)
        from ...monitor.tracer import trace_instant

        decision = attention_dispatch(q.shape, q.dtype.itemsize,
                                      causal=causal, interpret=interpret)
        if decision == "supertile":
            trace_instant("kernels/attention_dispatch", lane="kernels",
                          impl="supertile", shape=list(q.shape),
                          causal=causal)
            st_interpret = interpret or kernel_config.resolve("supertile")[1]
            return flash_attention_supertile_bhsd(
                q, k, v, causal=causal, sm_scale=sm_scale,
                interpret=st_interpret)
        if decision == "static" and not interpret and is_static_available(q):
            trace_instant("kernels/attention_dispatch", lane="kernels",
                          impl="static", shape=list(q.shape), causal=causal)
            return flash_attention_static_bhsd(q, k, v, causal=causal,
                                               sm_scale=sm_scale)
    block_q, block_k = _resolve_blocks(S, block_q, block_k)
    return _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret)


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    sm_scale: float = None,
    block_q: int = None,
    block_k: int = None,
    interpret: bool = False,
):
    """q, k, v: (B, S, H, Dh) -> (B, S, H, Dh)."""
    B, S, H, Dh = q.shape
    t = lambda x: x.transpose(0, 2, 1, 3)
    o = flash_attention_bhsd(t(q), t(k), t(v), causal=causal,
                             sm_scale=sm_scale, block_q=block_q,
                             block_k=block_k, interpret=interpret)
    return o.transpose(0, 2, 1, 3)
