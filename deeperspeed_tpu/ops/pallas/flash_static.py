"""Static-unrolled resident flash attention for short/mid sequences.

The r3 super-tile work measured the chip iteration-bound in Pallas: dynamic
loop steps cost ~6us of scalar-core time while one (512, Dh)x(Dh, 512)
block matmul pair is ~1us of MXU time at Dh=64-128. The v1 streaming kernel
(flash_attention.py) pays that overhead on a (B, H, n_q) grid of ~64-128
steps with 1-3 dynamic iterations each — measured 40-45 TF at the bench
geometries, BELOW XLA's batched-GEMM attention at S<=256 (MFU_DECOMP.json
attention_core; VERDICT r3 weak #3).

This kernel removes every dynamic iteration for S up to a static-unroll
budget (default 2048):

  * grid is (B, H) only — 32 steps at the 1.3B geometry vs 192 across the
    v1 fwd + dkdv + dq kernels;
  * K and V (and Q/dO in the backward) are whole-S VMEM-resident per grid
    step, like the super-tile sparse kernels' resident operands;
  * q/k block loops are PYTHON loops, unrolled at trace time, with causal
    bounds computed statically per q block — zero scalar-core loop cost,
    no masked-out block is ever computed (no waste, unlike a rectangular
    grid with pl.when skips);
  * the backward is ONE kernel producing dq, dk, dv together from
    fp32 VMEM scratch accumulators (v1 runs two kernels and re-reads
    q/k/v/do twice).

The reference capability equivalent is the fused attention inside
csrc/transformer/ds_transformer_cuda.cpp (softmax_kernels.cu:591) — same
job, opposite design: the CUDA path fuses mask+softmax+dropout around
cuBLAS batched GEMMs; here the whole attention is one Mosaic kernel per
(batch, head) with the MXU fed from VMEM-resident tiles.

Dispatch: `flash_attention(_bhsd)` in flash_attention.py routes here for
S <= MAX_STATIC_SEQ when shapes allow; the v1 streaming kernel remains for
long sequences (where per-iteration compute amortizes the loop overhead and
whole-S residency stops fitting VMEM).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import kernel_config
from .flash_attention import _compiler_params
from .flash_attention import _vmem_spec as _spec

NEG_INF = -1e30
# unroll budget: S=2048 at block 512 is 10 causal (16 full) block pairs in
# the fwd and 5 matmuls per pair in the bwd — ~80 dots, fine for Mosaic;
# S=4096 would be 36/180 and compile time starts to hurt
MAX_STATIC_SEQ = 2048
_BLOCK = 512


def _block_of(S):
    """Block size: 512 when it divides S, else the largest 128-multiple
    divisor, else whole-S (S < 128 or odd sizes — a single block is always
    legal for a resident kernel since the whole row fits anyway)."""
    if S % _BLOCK == 0:
        return _BLOCK
    for d in range(min(_BLOCK, S) - min(_BLOCK, S) % 128, 127, -128):
        if S % d == 0:
            return d
    return S


def is_static_available(q_bhsd) -> bool:
    """Gate for the auto dispatch: (B, H, S, Dh) head-major shape. The
    budget below is sized for the worst case (non-causal backward), so
    causality does not change the decision."""
    if not kernel_config.on_tpu():
        return False
    B, H, S, Dh = q_bhsd.shape
    if S > MAX_STATIC_SEQ or S < 8 or S % 8 or Dh % 8:
        return False
    itemsize = q_bhsd.dtype.itemsize if hasattr(q_bhsd.dtype, "itemsize") else 2
    # Budget sized from the BACKWARD's worst-case working set (the most
    # expensive kernel the gate admits — the auto dispatch would otherwise
    # pass a geometry whose forward fits but whose backward Mosaic-fails at
    # runtime): q,k,v,do inputs + dq,dk,dv outputs (input dtype), fp32
    # dk/dv accumulators held as unrolled values, lse+delta rows, and the
    # per-(qi,kj) fp32 tiles (s, p, dp, ds + pc + the dq accumulator).
    # 12MB of the 16MB VMEM leaves double-buffering headroom.
    bq = _block_of(S)
    resident = (7 * S * Dh * itemsize      # q,k,v,do in + dq,dk,dv out
                + 2 * S * Dh * 4           # dk_acc + dv_acc fp32 values
                + 2 * S * 4)               # lse + delta rows
    tiles = (4 * bq * bq * 4               # s, p, dp, ds fp32
             + bq * bq * itemsize          # pc cast tile
             + bq * Dh * 4)                # dq accumulator
    return resident + tiles <= 12 * 1024 * 1024


# ------------------------------------------------------------------ #
# forward
# ------------------------------------------------------------------ #


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                block, seq_len):
    S = seq_len
    bq = bk = block
    nq = S // bq
    q_all = q_ref[0, 0]  # (S, Dh) input dtype, VMEM-resident
    k_all = k_ref[0, 0]
    v_all = v_ref[0, 0]

    for qi in range(nq):
        q = q_all[qi * bq:(qi + 1) * bq]
        m = jnp.full((bq,), NEG_INF, jnp.float32)
        l = jnp.zeros((bq,), jnp.float32)
        acc = jnp.zeros((bq, q.shape[1]), jnp.float32)
        # causal: k blocks 0..floor((qi+1)*bq-1 / bk); the last may straddle
        hi = (qi * bq + bq + bk - 1) // bk if causal else S // bk
        for kj in range(hi):
            k = k_all[kj * bk:(kj + 1) * bk]
            v = v_all[kj * bk:(kj + 1) * bk]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale
            if causal and kj * bk + bk > qi * bq:  # straddles the diagonal
                rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(rows >= cols, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m = m_new
        o_ref[0, 0, qi * bq:(qi + 1) * bq, :] = (
            acc / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, 0, qi * bq:(qi + 1) * bq] = m + jnp.log(l)


def _fwd(q, k, v, sm_scale, causal, interpret):
    B, H, S, Dh = q.shape
    block = _block_of(S)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block=block, seq_len=S
    )
    o, lse = pl.pallas_call(
        kernel,
        name="flash_static_fwd",
        grid=(B, H),
        in_specs=[
            _spec((1, 1, S, Dh), lambda b, h: (b, h, 0, 0)),
            _spec((1, 1, S, Dh), lambda b, h: (b, h, 0, 0)),
            _spec((1, 1, S, Dh), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=[
            _spec((1, 1, S, Dh), lambda b, h: (b, h, 0, 0)),
            _spec((1, 1, 1, S), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret, 2),
    )(q, k, v)
    return o, lse


# ------------------------------------------------------------------ #
# backward: one kernel, dq/dk/dv from VMEM scratch
# ------------------------------------------------------------------ #


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, *, sm_scale, causal, block, seq_len):
    S = seq_len
    bq = bk = block
    nq = S // bq
    q_all = q_ref[0, 0]
    k_all = k_ref[0, 0]
    v_all = v_ref[0, 0]
    do_all = do_ref[0, 0]

    # fp32 accumulators live as values per block (unrolled), written once
    dk_acc = [jnp.zeros((bk, k_all.shape[1]), jnp.float32)
              for _ in range(S // bk)]
    dv_acc = [jnp.zeros((bk, v_all.shape[1]), jnp.float32)
              for _ in range(S // bk)]

    for qi in range(nq):
        q = q_all[qi * bq:(qi + 1) * bq]
        do = do_all[qi * bq:(qi + 1) * bq]
        lse = lse_ref[0, 0, 0, qi * bq:(qi + 1) * bq]
        delta = delta_ref[0, 0, 0, qi * bq:(qi + 1) * bq]
        dq = jnp.zeros((bq, q.shape[1]), jnp.float32)
        hi = (qi * bq + bq + bk - 1) // bk if causal else S // bk
        for kj in range(hi):
            k = k_all[kj * bk:(kj + 1) * bk]
            v = v_all[kj * bk:(kj + 1) * bk]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale
            if causal and kj * bk + bk > qi * bq:
                rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(rows >= cols, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])  # (bq, bk) fp32
            pc = p.astype(do.dtype)
            dv_acc[kj] = dv_acc[kj] + jax.lax.dot_general(
                pc, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = (p * (dp - delta[:, None]) * sm_scale).astype(q.dtype)
            dk_acc[kj] = dk_acc[kj] + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dq = dq + jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        dq_ref[0, 0, qi * bq:(qi + 1) * bq, :] = dq.astype(dq_ref.dtype)

    for kj in range(S // bk):
        dk_ref[0, 0, kj * bk:(kj + 1) * bk, :] = dk_acc[kj].astype(dk_ref.dtype)
        dv_ref[0, 0, kj * bk:(kj + 1) * bk, :] = dv_acc[kj].astype(dv_ref.dtype)


def _bwd(res, g, sm_scale, causal, interpret):
    q, k, v, o, lse = res
    B, H, S, Dh = q.shape
    do = g
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[:, :, None, :]  # (B, H, 1, S)
    block = _block_of(S)
    kernel = functools.partial(
        _bwd_kernel, sm_scale=sm_scale, causal=causal, block=block, seq_len=S
    )
    full = lambda: _spec((1, 1, S, Dh), lambda b, h: (b, h, 0, 0))
    row = lambda: _spec((1, 1, 1, S), lambda b, h: (b, h, 0, 0))
    dq, dk, dv = pl.pallas_call(
        kernel,
        name="flash_static_bwd",
        grid=(B, H),
        in_specs=[full(), full(), full(), full(), row(), row()],
        out_specs=[full(), full(), full()],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, Dh), q.dtype),
        ],
        interpret=interpret,
        **_compiler_params(interpret, 2),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------------ #
# public API with custom VJP (same contract as v1's _flash)
# ------------------------------------------------------------------ #


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_static(q, k, v, sm_scale, causal, interpret):
    o, _ = _fwd(q, k, v, sm_scale, causal, interpret)
    return o


def _vjp_fwd(q, k, v, sm_scale, causal, interpret):
    o, lse = _fwd(q, k, v, sm_scale, causal, interpret)
    from jax.ad_checkpoint import checkpoint_name

    # same residual names as the v1 kernel so remat_policy='flash'/'matmuls'
    # pin these across both implementations
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _vjp_bwd(sm_scale, causal, interpret, res, g):
    return _bwd(res, g, sm_scale, causal, interpret)


_flash_static.defvjp(_vjp_fwd, _vjp_bwd)


def flash_attention_static_bhsd(q, k, v, causal=True, sm_scale=None,
                                interpret=False):
    """Head-major (B, H, S, Dh) static-unrolled flash attention."""
    B, H, S, Dh = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(Dh)
    return _flash_static(q, k, v, sm_scale, causal, interpret)


# ------------------------------------------------------------------ #
# dense super-tile mode for SHORT sequences
# ------------------------------------------------------------------ #
#
# At S <= 128 every flash variant above starves the MXU: the score tile is
# at most (S, S) and a 128-row matmul pair cannot amortize even the static
# kernel's per-(batch, head) grid step — MFU_DECOMP.json measures the BERT
# (64, 16, 128, 64) attention core at 52 TF on the XLA fallback. The dense
# super-tile packs G = ~(512/S) whole sequences from the flattened
# (B*H, S, Dh) axis into ONE MXU-aligned query tile (contiguous reshape,
# zero data movement) and computes the full (G*S, G*S) score tile with a
# block-diagonal mask from the sequence index — cross-sequence pairs are
# masked exactly like the causal diagonal is. One grid step now feeds the
# MXU 512-row tiles and the per-step overhead is split across G sequences.
# Softmax is single-pass (no online rescale: the whole row is resident)
# with the same saved-lse backward contract as the kernels above.

SUPERTILE_MAX_SEQ = 256  # at/above this the static kernel already wins
_SUPERTILE_TARGET = 512  # preferred packed-tile rows
_SUPERTILE_MAX_TILE = 1024


def _supertile_group(B, H, S):
    """Sequences per packed tile: must divide B*H, keep the tile (G*S)
    128-aligned and within [256, 1024] rows; prefers the tile closest to
    the 512-row target. Returns 0 when no legal packing exists."""
    N = B * H
    best = 0
    for G in range(2, N + 1):
        T = G * S
        if T > _SUPERTILE_MAX_TILE:
            break
        if N % G or T % 128 or T < 256:
            continue
        if best == 0 or abs(T - _SUPERTILE_TARGET) < abs(
                best * S - _SUPERTILE_TARGET):
            best = G
    return best


def supertile_geometry_ok(B, H, S, Dh, itemsize=2) -> bool:
    """Platform-independent shape gate (the dispatch test and non-TPU
    interpret runs share it with the TPU path)."""
    if S >= SUPERTILE_MAX_SEQ or S < 8 or S % 8 or Dh % 8:
        return False
    G = _supertile_group(B, H, S)
    if G == 0:
        return False
    T = G * S
    # q,k,v,do in + dq,dk,dv out (+o) tiles, fp32 s/p/dp/ds + cast tile —
    # same 12MB bar as the static gate, sized for the one-kernel backward
    resident = 8 * T * Dh * itemsize + 2 * T * 4
    tiles = 4 * T * T * 4 + T * T * itemsize
    return resident + tiles <= 12 * 1024 * 1024


def is_supertile_available(q_bhsd) -> bool:
    if not kernel_config.on_tpu():
        return False
    B, H, S, Dh = q_bhsd.shape
    itemsize = q_bhsd.dtype.itemsize if hasattr(q_bhsd.dtype, "itemsize") else 2
    return supertile_geometry_ok(B, H, S, Dh, itemsize)


def _st_mask(T, seq, causal):
    rows = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    same = (rows // seq) == (cols // seq)
    if causal:
        # within one block rows/cols share the same seq offset, so global
        # row >= col is exactly the per-sequence causal constraint
        return same & (rows >= cols)
    return same


def _st_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                   seq):
    q = q_ref[0]  # (T, Dh) input dtype
    k = k_ref[0]
    v = v_ref[0]
    T = q.shape[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sm_scale  # (T, T) fp32, resident
    s = jnp.where(_st_mask(T, seq, causal), s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[:, None])
    l = jnp.sum(p, axis=-1)
    acc = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


def _st_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dk_ref, dv_ref, *, sm_scale, causal, seq):
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    T = q.shape[0]
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sm_scale
    s = jnp.where(_st_mask(T, seq, causal), s, NEG_INF)
    p = jnp.exp(s - lse[:, None])  # zero on every masked pair
    pc = p.astype(do.dtype)
    dv = jax.lax.dot_general(
        pc, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = (p * (dp - delta[:, None]) * sm_scale).astype(q.dtype)
    dk = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dq = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _st_fwd(qg, kg, vg, sm_scale, causal, seq, interpret):
    NG, T, Dh = qg.shape
    tile = lambda: _spec((1, T, Dh), lambda i: (i, 0, 0))
    row = lambda: _spec((1, 1, T), lambda i: (i, 0, 0))
    kernel = functools.partial(
        _st_fwd_kernel, sm_scale=sm_scale, causal=causal, seq=seq
    )
    o, lse = pl.pallas_call(
        kernel,
        name="flash_supertile_fwd",
        grid=(NG,),
        in_specs=[tile(), tile(), tile()],
        out_specs=[tile(), row()],
        out_shape=[
            jax.ShapeDtypeStruct((NG, T, Dh), qg.dtype),
            jax.ShapeDtypeStruct((NG, 1, T), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret, 1),
    )(qg, kg, vg)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_supertile(qg, kg, vg, sm_scale, causal, seq, interpret):
    o, _ = _st_fwd(qg, kg, vg, sm_scale, causal, seq, interpret)
    return o


def _st_vjp_fwd(qg, kg, vg, sm_scale, causal, seq, interpret):
    o, lse = _st_fwd(qg, kg, vg, sm_scale, causal, seq, interpret)
    from jax.ad_checkpoint import checkpoint_name

    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (qg, kg, vg, o, lse)


def _st_vjp_bwd(sm_scale, causal, seq, interpret, res, g):
    qg, kg, vg, o, lse = res
    NG, T, Dh = qg.shape
    do = g
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[:, None, :]  # (NG, 1, T)
    tile = lambda: _spec((1, T, Dh), lambda i: (i, 0, 0))
    row = lambda: _spec((1, 1, T), lambda i: (i, 0, 0))
    kernel = functools.partial(
        _st_bwd_kernel, sm_scale=sm_scale, causal=causal, seq=seq
    )
    dq, dk, dv = pl.pallas_call(
        kernel,
        name="flash_supertile_bwd",
        grid=(NG,),
        in_specs=[tile(), tile(), tile(), tile(), row(), row()],
        out_specs=[tile(), tile(), tile()],
        out_shape=[
            jax.ShapeDtypeStruct((NG, T, Dh), qg.dtype),
            jax.ShapeDtypeStruct((NG, T, Dh), qg.dtype),
            jax.ShapeDtypeStruct((NG, T, Dh), qg.dtype),
        ],
        interpret=interpret,
        **_compiler_params(interpret, 1),
    )(qg, kg, vg, do, lse, delta)
    return dq, dk, dv


_flash_supertile.defvjp(_st_vjp_fwd, _st_vjp_bwd)


def flash_attention_supertile_bhsd(q, k, v, causal=True, sm_scale=None,
                                   interpret=False):
    """Head-major (B, H, S, Dh) dense super-tile flash attention for short
    sequences. Packs G sequences per query tile (contiguous reshape) with a
    block-diagonal mask; the caller is responsible for gating on
    supertile_geometry_ok/is_supertile_available."""
    B, H, S, Dh = q.shape
    G = _supertile_group(B, H, S)
    if G == 0:
        raise ValueError(
            f"no legal super-tile packing for geometry {(B, H, S, Dh)}"
        )
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(Dh)
    NG = (B * H) // G
    pack = lambda x: x.reshape(NG, G * S, Dh)
    o = _flash_supertile(pack(q), pack(k), pack(v), sm_scale, causal, S,
                         interpret)
    return o.reshape(B, H, S, Dh)
