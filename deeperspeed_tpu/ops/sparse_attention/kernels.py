"""Block-sparse flash attention kernels (Pallas TPU).

TPU-native replacement for the reference's triton block-sparse stack
(ops/sparse_attention/matmul.py sdd/dsd/dds :615, softmax.py :230, and the
csrc/sparse_attention/utils.cpp sdd_segment LUT builder): instead of three
separate sparse GEMM/softmax launches over a compressed block tensor, one
flash-style kernel streams only the ACTIVE K/V blocks of each Q block row —
selected through a host-precomputed LUT — with online softmax, so both
compute and HBM traffic scale with nnz blocks, not S^2.

LUTs are plain numpy (host, once per layout): per (head, q-block) the list of
active k-block indices, padded to the row max; plus the transpose for the
dK/dV pass. The backward follows the flash-2 split (dq kernel over q-blocks,
dkdv kernel over k-blocks) restricted to active blocks.
"""

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..pallas.flash_attention import _compiler_params, _vmem_spec

NEG_INF = -1e30


def _lut_pallas_call(name, kernel, grid, in_specs, out_specs, out_shape,
                     scratch_shapes, interpret):
    """pallas_call wrapper feeding the two integer LUT arrays (cols/counts)
    as scalar-prefetch args: whole-array SMEM residents, readable from BOTH
    the kernel body and the BlockSpec index maps. LUT-driven index maps are
    what lets K/V blocks STREAM from HBM per grid step (double-buffered by
    Mosaic) instead of pinning full-sequence tensors in VMEM — the TPU idiom
    replacing the triton kernels' LUT pointer arguments, with no VMEM cap on
    sequence length."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    # the batch*head dim reorders freely; the flat-LUT entry dim accumulates
    # into scratch and must run in order
    kwargs = _compiler_params(interpret, 2, ("parallel", "arbitrary"))
    return pl.pallas_call(
        kernel, name=name, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret, **kwargs,
    )


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


# ------------------------------------------------------------------ #
# LUT construction (host-side, replaces csrc sdd_segment + triton LUTs)
# ------------------------------------------------------------------ #


def build_lut(layout: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """layout (H, nb, nb) 0/1 -> (cols (H, nb, width), counts (H, nb)).

    cols[h, qb, :counts[h, qb]] are the active k-block indices of q-block row
    qb (ascending); padding entries repeat the last valid index so kernel
    loads stay in bounds."""
    H, nb, _ = layout.shape
    counts = layout.sum(axis=2).astype(np.int32)
    width = max(1, int(counts.max()))
    cols = np.zeros((H, nb, width), np.int32)
    for h in range(H):
        for qb in range(nb):
            (idx,) = np.nonzero(layout[h, qb])
            if len(idx):
                cols[h, qb, : len(idx)] = idx
                cols[h, qb, len(idx):] = idx[-1]
    return cols, counts


def layout_density(layout: np.ndarray) -> float:
    return float(layout.mean())


def build_flat_lut(layout: np.ndarray,
                   lane: int = 1) -> Tuple[np.ndarray, np.ndarray]:
    """layout (H, nb, nb) 0/1 -> flat nonzero-entry LUT (rows, cols), each
    (H, N) int32 in row-major order, N = max per-head (padded) nnz.

    The width-LUT (build_lut) makes every q-row pay the MAX row width in
    grid iterations — O(nb * width) steps with most masked out at realistic
    densities. The flat LUT spends ~one grid step per nonzero block pair,
    so kernel work scales with nnz. Padding entries carry rows = nb-1 /
    cols = -1: an invalid column contributes nothing, and a padded row id
    of nb-1 either continues the genuine last row (harmless) or finalizes
    an EMPTY last row with the correct zero output.

    ``lane > 1``: each row's entry run is padded (with that row's id,
    col = -1) to a multiple of ``lane``, so the kernels can consume `lane`
    entries per grid step — one wide concatenated MXU dot and one online-
    softmax update per step instead of `lane` narrow ones. Every group's
    entries share a row id by construction.

    Every row id appears at least once (empty rows get a full invalid
    group) so the kernel still initializes and flushes every output block
    (zeros / lse = -inf) instead of leaving uninitialized garbage."""
    H, nb, _ = layout.shape
    per = []
    for h in range(H):
        rs, cs = [], []
        for qb in range(nb):
            (idx,) = np.nonzero(layout[h, qb])
            n = max(len(idx), 1)
            padded = -np.ones(((n + lane - 1) // lane) * lane, np.int64)
            padded[: len(idx)] = idx
            rs.append(np.full(len(padded), qb, np.int64))
            cs.append(padded)
        per.append((np.concatenate(rs).astype(np.int32),
                    np.concatenate(cs).astype(np.int32)))
    N = max(lane, max(len(r) for r, _ in per))
    N = ((N + lane - 1) // lane) * lane
    rows = np.full((H, N), nb - 1, np.int32)
    cols = np.full((H, N), -1, np.int32)
    for h, (r, c) in enumerate(per):
        rows[h, : len(r)] = r
        cols[h, : len(c)] = c
    return rows, cols


# entries consumed per grid step: one wide concatenated MXU dot + one
# online-softmax update per LANE LUT entries (per-step overhead amortizes,
# dots widen from block to LANE*block — the per-flop gap vs flash)
LANE = 4


def _group_flags(rows_ref, cols_ref, h, i, n_entries):
    """(row, first-of-row, last-of-row) for flat-LUT group i (LANE entries
    starting at i*LANE; all share a row id by build_flat_lut construction).

    first/last derive from adjacent SMEM entries; `last` also fires when
    the next group is global padding (col < 0 with the same row id), and is
    additionally gated on this group being genuine (own first col >= 0) or
    first-of-row (an empty row's single invalid group must still flush its
    zero output) — so trailing global-padding groups do not redundantly
    re-write the final row's output block every step."""
    base = i * LANE
    row = rows_ref[h, base]
    prev_row = rows_ref[h, jnp.maximum(base - 1, 0)]
    first = jnp.logical_or(base == 0, prev_row != row)
    nxt = jnp.minimum(base + LANE, n_entries - 1)
    last = jnp.logical_or(
        base + LANE >= n_entries,
        jnp.logical_or(rows_ref[h, nxt] != row, cols_ref[h, nxt] < 0),
    )
    last = jnp.logical_and(
        last, jnp.logical_or(first, cols_ref[h, base] >= 0)
    )
    return row, first, last


def _concat_cols_mask(col_ids, block):
    """(col-position matrix (block, LANE*block), additive validity mask):
    per-chunk column positions for causal masking plus 0/-inf padding mask
    (scalar select per chunk — fp32 additive, never a bool lane-vector
    broadcast, which Mosaic cannot lower)."""
    pos = []
    add = []
    for kb in col_ids:
        iota = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        pos.append(kb * block + iota)
        addj = jnp.where(kb >= 0, 0.0, NEG_INF)
        add.append(jnp.zeros((block, block), jnp.float32) + addj)
    return jnp.concatenate(pos, axis=1), jnp.concatenate(add, axis=1)


# ------------------------------------------------------------------ #
# forward
# ------------------------------------------------------------------ #


def _bs_fwd_kernel(rows_ref, cols_ref, q_ref, *rest, sm_scale, block, causal,
                   num_heads, n_entries):
    """One grid step = LANE nonzero (q-block, k-block) pairs of one row from
    the flat LUT; the k/v blocks stream via LUT-driven BlockSpecs
    (double-buffered), concatenate into one wide (LANE*block) MXU dot, and
    the online-softmax state lives in VMEM scratch across a row's groups —
    the output flushes when the row id changes."""
    k_refs = rest[:LANE]
    v_refs = rest[LANE:2 * LANE]
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest[2 * LANE:]
    h = pl.program_id(0) % num_heads
    i = pl.program_id(1)
    row, first, last = _group_flags(rows_ref, cols_ref, h, i, n_entries)
    col_ids = [cols_ref[h, i * LANE + j] for j in range(LANE)]
    q_start = row * block

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]  # (BLK, D) input dtype — bf16 MXU dots, fp32 accumulation
    k = jnp.concatenate([r[0] for r in k_refs], axis=0)  # (LANE*BLK, D)
    v = jnp.concatenate([r[0] for r in v_refs], axis=0)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # (BLK, LANE*BLK)
    pos, addmask = _concat_cols_mask(col_ids, block)
    if causal:
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(rows >= pos, s, NEG_INF)
    s = s + addmask

    m, l, acc = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # keep m finite for fully-masked rows so exp() stays NaN-free
    m_safe = jnp.maximum(m_new, NEG_INF / 2)
    p = jnp.exp(s - m_safe[:, None])
    dead = (m_new <= NEG_INF).astype(jnp.float32)
    p = p * (1.0 - dead)[:, None]
    alpha = jnp.exp(jnp.maximum(m, NEG_INF / 2) - m_safe)
    alpha = alpha * (1.0 - (m <= NEG_INF).astype(jnp.float32))
    alpha = jnp.where(m_new <= NEG_INF, 1.0, alpha)
    m_scr[...] = m_new
    l_scr[...] = l * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(last)
    def _finish():
        l = l_scr[...]
        m = m_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            l == 0.0, NEG_INF, jnp.maximum(m, NEG_INF / 2) + jnp.log(l_safe)
        )


def _row_spec(block, Dh, H):
    return _vmem_spec(
        (1, block, Dh), lambda b, i, r, c: (b, r[b % H, i * LANE], 0))


def _lane_specs(block, Dh, H):
    """LANE BlockSpecs fetching the j-th column block of group i."""
    def at(j):
        return _vmem_spec(
            (1, block, Dh),
            lambda b, i, r, c: (b, jnp.maximum(c[b % H, i * LANE + j], 0), 0))

    return [at(j) for j in range(LANE)]


def _bs_fwd(q, k, v, rows, cols, sm_scale, block, causal, interpret):
    B, S, H, Dh = q.shape
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    n_entries = cols.shape[-1]
    grid = (B * H, n_entries // LANE)

    kernel = functools.partial(
        _bs_fwd_kernel, sm_scale=sm_scale, block=block, causal=causal,
        num_heads=H, n_entries=n_entries,
    )
    o, lse = _lut_pallas_call(
        "block_sparse_fwd",
        kernel,
        grid=grid,
        in_specs=(
            [_row_spec(block, Dh, H)]
            + _lane_specs(block, Dh, H)   # k blocks
            + _lane_specs(block, Dh, H)   # v blocks
        ),
        out_specs=[
            _vmem_spec((1, block, Dh),
                       lambda b, i, r, c: (b, r[b % H, i * LANE], 0)),
            _vmem_spec((1, 1, block),
                       lambda b, i, r, c: (b, 0, r[b % H, i * LANE])),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, Dh), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32),
        ],
        # 1-D (block,) m/l scratch lowers fine on current Mosaic
        # (hardware-verified at S=1024..16384); jax's reference kernel pads
        # to 2-D for older toolchains — revisit if a Mosaic bump rejects it
        scratch_shapes=[_scratch((block,)), _scratch((block,)),
                        _scratch((block, Dh))],
        interpret=interpret,
    )(rows, cols, qf, *([kf] * LANE), *([vf] * LANE))
    return o, lse, (qf, kf, vf)


# ------------------------------------------------------------------ #
# backward
# ------------------------------------------------------------------ #


def _bs_bwd_dq_kernel(rows_ref, cols_ref, q_ref, *rest, sm_scale, block,
                      causal, num_heads, n_entries):
    k_refs = rest[:LANE]
    v_refs = rest[LANE:2 * LANE]
    do_ref, lse_ref, delta_ref, dq_ref, dq_scr = rest[2 * LANE:]
    h = pl.program_id(0) % num_heads
    i = pl.program_id(1)
    row, first, last = _group_flags(rows_ref, cols_ref, h, i, n_entries)
    col_ids = [cols_ref[h, i * LANE + j] for j in range(LANE)]
    q_start = row * block

    @pl.when(first)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0]  # input dtype
    do = do_ref[0]
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    k = jnp.concatenate([r[0] for r in k_refs], axis=0)  # (LANE*BLK, D)
    v = jnp.concatenate([r[0] for r in v_refs], axis=0)
    s = sm_scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (BLK, LANE*BLK)
    pos, addmask = _concat_cols_mask(col_ids, block)
    if causal:
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(rows >= pos, s, NEG_INF)
    s = s + addmask
    p = jnp.exp(s - lse[:, None])
    # rows with no visible key stored lse=NEG_INF; exp(-1e30 - -1e30)=1
    # would poison them. Multiplicative fp32 mask, NOT a bool-vector where:
    # Mosaic cannot lower a lane-vector bool broadcast along a new sublane
    # dim, while fp32 broadcasts lower fine
    alive = (lse > NEG_INF / 2).astype(jnp.float32)
    p = p * alive[:, None]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta[:, None]) * sm_scale
    dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(last)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bs_bwd_dkdv_kernel(keys_ref, qrows_ref, k_ref, v_ref, *rest, sm_scale,
                        block, causal, num_heads, n_entries):
    """Flat TRANSPOSED LUT (entries sorted by key-block): each grid step
    consumes LANE attending q-blocks of one key block; scratch accumulates
    dk/dv for that key block across its groups."""
    q_refs = rest[:LANE]
    do_refs = rest[LANE:2 * LANE]
    lse_refs = rest[2 * LANE:3 * LANE]
    delta_refs = rest[3 * LANE:4 * LANE]
    dk_ref, dv_ref, dk_scr, dv_scr = rest[4 * LANE:]
    h = pl.program_id(0) % num_heads
    i = pl.program_id(1)
    kb, first, last = _group_flags(keys_ref, qrows_ref, h, i, n_entries)
    row_ids = [qrows_ref[h, i * LANE + j] for j in range(LANE)]
    k_start = kb * block

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    k = k_ref[0]  # input dtype
    v = v_ref[0]
    q = jnp.concatenate([r[0] for r in q_refs], axis=0)  # (LANE*BLK, D)
    do = jnp.concatenate([r[0] for r in do_refs], axis=0)
    # 2-D per-chunk broadcasts BEFORE the concat: Mosaic cannot concatenate
    # 1-D vectors, while sublane-axis concat of (BLK, BLK) tiles lowers fine
    lse = jnp.concatenate(
        [jnp.zeros((block, block), jnp.float32) + r[0, 0][:, None]
         for r in lse_refs], axis=0)  # (LANE*BLK, BLK)
    delta = jnp.concatenate(
        [jnp.zeros((block, block), jnp.float32) + r[0, 0][:, None]
         for r in delta_refs], axis=0)
    s = sm_scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (LANE*BLK, BLK)
    # per-chunk q-row positions (concat along the ROW axis here) + additive
    # validity mask for padded entries
    rpos = []
    radd = []
    for qb in row_ids:
        iota = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        rpos.append(qb * block + iota)
        addj = jnp.where(qb >= 0, 0.0, NEG_INF)
        radd.append(jnp.zeros((block, block), jnp.float32) + addj)
    rows = jnp.concatenate(rpos, axis=0)  # (LANE*BLK, BLK)
    s = s + jnp.concatenate(radd, axis=0)
    if causal:
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    p = jnp.exp(s - lse)
    # fp32 multiplicative mask, not a bool-vector where (see dq kernel)
    alive = (lse > NEG_INF / 2).astype(jnp.float32)
    p = p * alive
    dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta) * sm_scale
    dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(last)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _lane_lse_specs(block, H):
    """LANE (1, 1, block) specs following the j-th q-row of group i."""
    def at(j):
        return _vmem_spec(
            (1, 1, block),
            lambda b, i, kk, r: (b, 0,
                                 jnp.maximum(r[b % H, i * LANE + j], 0)))

    return [at(j) for j in range(LANE)]


def _lane_qrow_specs(block, Dh, H):
    """LANE (1, block, Dh) specs following the j-th q-row of group i
    (transposed-LUT second prefetch array)."""
    def at(j):
        return _vmem_spec(
            (1, block, Dh),
            lambda b, i, kk, r: (b, jnp.maximum(r[b % H, i * LANE + j], 0),
                                 0))

    return [at(j) for j in range(LANE)]


def _bs_bwd(res, g, rows, cols, keys_t, qrows_t, sm_scale, block, causal,
            interpret, num_heads):
    qf, kf, vf, o, lse = res
    BH, S, Dh = qf.shape
    H = num_heads
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.reshape(BH, 1, S)
    n_entries = cols.shape[-1]
    n_entries_t = qrows_t.shape[-1]

    dq = _lut_pallas_call(
        "block_sparse_bwd_dq",
        functools.partial(
            _bs_bwd_dq_kernel, sm_scale=sm_scale, block=block, causal=causal,
            num_heads=H, n_entries=n_entries,
        ),
        grid=(BH, n_entries // LANE),
        in_specs=(
            [_row_spec(block, Dh, H)]       # q
            + _lane_specs(block, Dh, H)     # k blocks
            + _lane_specs(block, Dh, H)     # v blocks
            + [
                _row_spec(block, Dh, H),    # do
                _vmem_spec((1, 1, block),
                           lambda b, i, r, c: (b, 0, r[b % H, i * LANE])),
                _vmem_spec((1, 1, block),
                           lambda b, i, r, c: (b, 0, r[b % H, i * LANE])),
            ]
        ),
        out_specs=_vmem_spec((1, block, Dh),
                             lambda b, i, r, c: (b, r[b % H, i * LANE], 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, Dh), qf.dtype),
        scratch_shapes=[_scratch((block, Dh))],
        interpret=interpret,
    )(rows, cols, qf, *([kf] * LANE), *([vf] * LANE), do, lse, delta)

    kb_spec = _vmem_spec((1, block, Dh),
                         lambda b, i, kk, r: (b, kk[b % H, i * LANE], 0))
    dk, dv = _lut_pallas_call(
        "block_sparse_bwd_dkv",
        functools.partial(
            _bs_bwd_dkdv_kernel, sm_scale=sm_scale, block=block,
            causal=causal, num_heads=H, n_entries=n_entries_t,
        ),
        grid=(BH, n_entries_t // LANE),
        in_specs=(
            [kb_spec, kb_spec]                    # k, v
            + _lane_qrow_specs(block, Dh, H)      # q blocks
            + _lane_qrow_specs(block, Dh, H)      # do blocks
            + _lane_lse_specs(block, H)           # lse blocks
            + _lane_lse_specs(block, H)           # delta blocks
        ),
        out_specs=[kb_spec, kb_spec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, Dh), qf.dtype),
            jax.ShapeDtypeStruct((BH, S, Dh), qf.dtype),
        ],
        scratch_shapes=[_scratch((block, Dh)), _scratch((block, Dh))],
        interpret=interpret,
    )(keys_t, qrows_t, kf, vf, *([qf] * LANE), *([do] * LANE),
      *([lse] * LANE), *([delta] * LANE))
    return dq, dk, dv


# ================================================================== #
# resident-K/V kernels (the fast path while 2*S*Dh fits VMEM, same
# residency idea as ops/pallas/flash_attention.py)
# ================================================================== #
#
# Design (v4, hardware-profiled). Three earlier shapes of this kernel
# were bound by fixed costs, not flops. The decisive v5e measurement:
# a dynamic-trip-count loop iteration carries ~6us of UNOVERLAPPED
# scalar-core work (SMEM entry reads, dynamic-slice address math, loop
# bookkeeping — Mosaic cannot software-pipeline dynamic while loops), so
# kernel wall time ~= 6us x total iterations, for flash itself as much
# as for any sparse variant (flash at Dh=64/S=8192 runs ~4k iterations
# of (512 q x 512 k) tiles ~= 25ms regardless of anything else). A
# sparse kernel beats flash iff it runs FEWER iterations, i.e. its
# per-iteration tile must cover the same area while the LUT drops the
# inactive area.
#
# v4 therefore processes one (SROW*block q-rows x CHUNK*block k-cols)
# SUPER-TILE per iteration — the same 512x512 area as a flash iteration
# at block=128 — selected by a flat per-super-row entry list built from
# runs of the UNION of the tile rows' active blocks. Per-block activity
# inside the super-tile travels as a 16-bit bitmap in the entry (bit
# r*CHUNK+c), reconstructed in-kernel as a vector mask; union waste (a
# row masked out of a neighbouring row's window) is ~20% for sliding-
# window layouts and bounded by CHUNK x (SROW-1) blocks per run. The
# online-softmax state lives in registers for the whole super-row and
# flushes ONCE after the loop (static store — no per-entry flush, no
# dummy entries, no rloc/last bookkeeping).

CHUNK = 4   # k blocks per entry window: 512 cols at block=128
SROW = 4    # q rows (key rows for dkdv) per super-tile: 512 at block=128


def _pick_tile(nb: int, tile: int) -> int:
    tile = min(tile, nb)
    while nb % tile:
        tile -= 1
    return tile


def build_super_lut(layout: np.ndarray, chunk: int, srow: int,
                    causal: bool = False, transposed: bool = False):
    """layout (H, nb, nb) 0/1 (pre-filtered to the lower block triangle by
    the caller when causal) -> per-super-row entry lists.

    Active columns are UNIONed over each super-row's `srow` rows, grouped
    into runs of consecutive block ids, and split into windows of
    <= `chunk` blocks (win clamped to nb - chunk so the kernel's
    static-size dynamic slice never clips). Each entry carries win plus a
    bitmap of which (row, col) blocks of the super-tile are genuinely
    active (bit r*chunk + c); every active layout block lands in exactly
    one entry because the windows partition the union runs.

    Entries that need NO in-kernel mask — bitmap all-ones and, when
    causal, the whole tile x window strictly below the diagonal (the
    criterion flips for the dkdv kernel's transposed LUT) — sort FIRST;
    nfull counts them, so the kernels run a lean flash-like loop over
    [0, nfull) and pay the bitmap/causal mask only on [nfull, counts).

    Returns wins, bitmaps (H, nsr, W) int32 and counts, nfull (H, nsr)
    int32, nsr = nb/srow; entries past counts are never executed."""
    lay = np.asarray(layout) != 0
    H, nb, _ = lay.shape
    chunk = min(chunk, nb)
    nsr = nb // srow
    per = []
    W = 1
    for h in range(H):
        rows_h = []
        for sr in range(nsr):
            tile_rows = lay[h, sr * srow:(sr + 1) * srow]  # (srow, nb)
            union = tile_rows.any(axis=0)
            (idx,) = np.nonzero(union)
            entries = []
            i = 0
            while i < len(idx):
                j = i
                while j + 1 < len(idx) and idx[j + 1] == idx[j] + 1:
                    j += 1
                a, b = int(idx[i]), int(idx[j])
                while a <= b:
                    seg = min(chunk, b - a + 1)
                    win = min(a, nb - chunk)
                    bm = 0
                    for r in range(srow):
                        for c in range(chunk):
                            col = win + c
                            # only the segment's own columns: windows
                            # partition the union, clamp overlap included
                            # once (by the first window that covers it)
                            if a <= col <= a + seg - 1 and tile_rows[r, col]:
                                bm |= 1 << (r * chunk + c)
                    full_bm = (1 << (srow * chunk)) - 1
                    if causal:
                        below = (win >= (sr + 1) * srow if transposed
                                 else sr * srow >= win + chunk)
                    else:
                        below = True
                    entries.append((win, bm, bm == full_bm and below))
                    a += seg
                i = j + 1
            # mask-free entries first (online softmax is order-invariant)
            entries.sort(key=lambda e: not e[2])
            rows_h.append(entries)
            W = max(W, len(entries))
        per.append(rows_h)
    wins = np.zeros((H, nsr, W), np.int32)
    bitmaps = np.zeros((H, nsr, W), np.int64)
    counts = np.zeros((H, nsr), np.int32)
    nfull = np.zeros((H, nsr), np.int32)
    for h in range(H):
        for sr in range(nsr):
            es = per[h][sr]
            counts[h, sr] = len(es)
            nfull[h, sr] = sum(1 for e in es if e[2])
            for j, (w, bm, _) in enumerate(es):
                wins[h, sr, j] = w
                bitmaps[h, sr, j] = bm
    if srow * chunk <= 31:
        bitmaps = bitmaps.astype(np.int32)
    else:
        # TPU SMEM scalars are int32: split into (lo, hi) row-half words
        # (lo = rows [0, srow/2), hi = the rest), matching
        # _super_mask_consts' hi_sel row split
        half_bits = (srow // 2) * chunk
        lo = (bitmaps & ((1 << half_bits) - 1)).astype(np.int32)
        hi = (bitmaps >> half_bits).astype(np.int32)
        bitmaps = np.stack([lo, hi], axis=-1)
    return wins, bitmaps, counts, nfull


def supertile_covered(layout: np.ndarray, chunk: int = None,
                      srow: int = None) -> int:
    """Absolute block area the super-tile kernels traverse for this
    layout (windows x srow x chunk) — proportional to kernel iteration
    count, the quantity the v5e 6us/iteration cost model prices."""
    lay = np.asarray(layout) != 0
    H, nb, _ = lay.shape
    chunk = min(chunk or CHUNK, nb)
    srow = _pick_tile(nb, srow or SROW)
    nsr = nb // srow
    union = lay.reshape(H, nsr, srow, nb).any(axis=2)
    windows = 0
    for h in range(H):
        for sr in range(nsr):
            (idx,) = np.nonzero(union[h, sr])
            i = 0
            while i < len(idx):
                j = i
                while j + 1 < len(idx) and idx[j + 1] == idx[j] + 1:
                    j += 1
                run = int(idx[j]) - int(idx[i]) + 1
                windows += -(-run // chunk)
                i = j + 1
    return windows * srow * chunk


def supertile_waste(layout: np.ndarray, chunk: int = None,
                    srow: int = None) -> float:
    """Ratio of super-tile-covered block area to genuinely active blocks —
    the cost model behind impl='auto'. Window-family layouts (sliding,
    longformer, bigbird) land near 1.2-1.5; STRIDED patterns (the Fixed
    config's every-Nth-column globals) explode the union windows and land
    3+, where the streaming kernels' narrow per-block gathers win on
    hardware despite their per-step overhead."""
    lay = np.asarray(layout) != 0
    active = int(lay.sum())
    return supertile_covered(lay, chunk, srow) / max(active, 1)


def resident_ok(S: int, Dh: int, itemsize: int = 2) -> bool:
    """Whole-sequence VMEM residency budget: the fwd/dq kernels pin K+V,
    dkdv pins Q+dO, and Mosaic double-buffers the resident pair across the
    batch*head grid dim — hardware-measured on v5e (16MB VMEM/core), 4MB
    of resident tensors (S=16384, Dh=64, bf16) overflows by 65KB once the
    score tiles and output buffers are added, while 3MB fits. Beyond this
    the streaming kernels take over (no VMEM cap on S)."""
    return 2 * S * Dh * itemsize <= 3 * 1024 * 1024


def _super_mask_consts(s_shape, sr, block, chunk, srow, transposed):
    """Loop-INVARIANT pieces of the super-tile mask, hoisted out of the
    dynamic entry loop (the VPU passes building iotas and the bit-index
    matrix are identical for every entry of a super-row)."""
    r_off = jax.lax.broadcasted_iota(jnp.int32, s_shape, 0)
    c_off = jax.lax.broadcasted_iota(jnp.int32, s_shape, 1)
    if transposed:
        row_blk = c_off // block                  # key-row block index
        col_blk = r_off // block                  # window block index
        fixed_pos = sr * (srow * block) + c_off   # key positions
        win_off = r_off                           # q offset inside window
    else:
        row_blk = r_off // block
        col_blk = c_off // block
        fixed_pos = sr * (srow * block) + r_off   # q positions
        win_off = c_off                           # key offset inside window
    if srow * chunk <= 31:
        bit = row_blk * chunk + col_blk
        hi_sel = None
    else:
        # >31-bit bitmaps travel as (lo, hi) words split at srow/2 rows
        half = srow // 2
        bit = (row_blk % half) * chunk + col_blk
        hi_sel = row_blk >= half
    return fixed_pos, win_off, bit, hi_sel


def _super_mask(consts, win, bitmap, block, causal, transposed):
    """Per-entry mask from the hoisted constants: one variable shift + one
    compare for the bitmap, one add + compare for the causal triangle.
    transposed=False: rows are the super-row's q rows, cols the window
    (q-side kernels); True: rows are the window's q rows, cols the
    super-row's KEY rows (dkdv kernel)."""
    fixed_pos, win_off, bit, hi_sel = consts
    if hi_sel is None:
        bm = jnp.broadcast_to(bitmap, bit.shape)
    else:
        bm = jnp.where(hi_sel, jnp.broadcast_to(bitmap[1], bit.shape),
                       jnp.broadcast_to(bitmap[0], bit.shape))
    ok = (jax.lax.shift_right_logical(bm, bit) & 1) == 1
    if causal:
        win_pos = win * block + win_off
        if transposed:
            ok = ok & (win_pos >= fixed_pos)   # qpos >= kpos
        else:
            ok = ok & (fixed_pos >= win_pos)
    return ok


def _bm_read(bitmaps_ref, h, sr, j):
    """Bitmap scalar(s) for entry j: a bare int32, or the (lo, hi) pair
    when build_super_lut packed a >31-bit bitmap into a trailing dim."""
    if len(bitmaps_ref.shape) == 4:
        return (bitmaps_ref[h, sr, j, 0], bitmaps_ref[h, sr, j, 1])
    return bitmaps_ref[h, sr, j]


def _bs_fwd_kernel_res(wins_ref, bitmaps_ref, counts_ref, nfull_ref,
                       q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale,
                       block, chunk, srow, causal, num_heads):
    h = pl.program_id(0) % num_heads
    sr = pl.program_id(1)
    width = block * chunk
    span = block * srow
    Dh = q_ref.shape[-1]
    q = q_ref[0]  # (span, Dh) — static block, loop-invariant
    m0 = jnp.full((span,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((span,), jnp.float32)
    a0 = jnp.zeros((span, Dh), jnp.float32)
    consts = _super_mask_consts((span, width), sr, block, chunk, srow,
                                False)

    def make_body(masked):
        def body(j, carry):
            m, l, acc = carry
            win = wins_ref[h, sr, j]
            k = k_ref[0, pl.ds(win * block, width), :]
            v = v_ref[0, pl.ds(win * block, width), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale  # (span, width) fp32
            if masked:
                ok = _super_mask(consts, win,
                                 _bm_read(bitmaps_ref, h, sr, j), block,
                                 causal, False)
                s = jnp.where(ok, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            if masked:
                # rows inactive in this entry keep m = -inf; clamp the
                # subtrahend and kill p so exp(-1e30 - -1e30) = 1 cannot
                # poison l (a mask-free entry has every score finite)
                m_safe = jnp.maximum(m_new, NEG_INF * 0.5)
                alive = (m_new > NEG_INF * 0.5).astype(jnp.float32)
                p = jnp.exp(s - m_safe[:, None]) * alive[:, None]
            else:
                p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc_new

        return body

    nf = nfull_ref[h, sr]
    carry = jax.lax.fori_loop(0, nf, make_body(False), (m0, l0, a0))
    m, l, acc = jax.lax.fori_loop(nf, counts_ref[h, sr], make_body(True),
                                  carry)
    l_safe = jnp.where(l == 0.0, 1.0, l)  # empty rows -> zero output
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.where(
        l == 0.0, NEG_INF, jnp.maximum(m, NEG_INF * 0.5) + jnp.log(l_safe))


def _bs_bwd_dq_kernel_res(wins_ref, bitmaps_ref, counts_ref, nfull_ref,
                          q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dq_ref, *, sm_scale, block, chunk, srow, causal,
                          num_heads):
    h = pl.program_id(0) % num_heads
    sr = pl.program_id(1)
    width = block * chunk
    Dh = q_ref.shape[-1]
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0]      # (span,); -inf on empty rows — clamp below
    delta = delta_ref[0, 0]
    lse_safe = jnp.maximum(lse, NEG_INF * 0.5)
    consts = _super_mask_consts((q.shape[0], width), sr, block, chunk,
                                srow, False)

    def make_body(masked):
        def body(j, dq):
            win = wins_ref[h, sr, j]
            k = k_ref[0, pl.ds(win * block, width), :]
            v = v_ref[0, pl.ds(win * block, width), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale
            if masked:
                ok = _super_mask(consts, win,
                                 _bm_read(bitmaps_ref, h, sr, j), block,
                                 causal, False)
                s = jnp.where(ok, s, NEG_INF)
            p = jnp.exp(s - lse_safe[:, None])
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

    nf = nfull_ref[h, sr]
    dq = jax.lax.fori_loop(0, nf, make_body(False),
                           jnp.zeros(q.shape, jnp.float32))
    dq = jax.lax.fori_loop(nf, counts_ref[h, sr], make_body(True), dq)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bs_bwd_dkdv_kernel_res(wins_ref, bitmaps_ref, counts_ref, nfull_ref,
                            k_ref, v_ref, q_ref, do_ref, lse_ref,
                            delta_ref, dk_ref, dv_ref, *, sm_scale, block,
                            chunk, srow, causal, num_heads):
    """Transposed super-tiles: per KEY super-row, windows of attending q
    blocks, dynamic-sliced from whole-sequence-resident Q/dO/lse/delta."""
    h = pl.program_id(0) % num_heads
    sr = pl.program_id(1)
    width = block * chunk
    Dh = k_ref.shape[-1]
    k = k_ref[0]   # (span, Dh) key super-tile
    v = v_ref[0]
    span = k.shape[0]
    consts = _super_mask_consts((width, span), sr, block, chunk, srow,
                                True)

    def make_body(masked):
      def body(j, carry):
        dk, dv = carry
        win = wins_ref[h, sr, j]
        qc = q_ref[0, pl.ds(win * block, width), :]   # (width, Dh)
        doc = do_ref[0, pl.ds(win * block, width), :]
        lsec = lse_ref[0, 0, pl.ds(win * block, width)]
        deltac = delta_ref[0, 0, pl.ds(win * block, width)]
        s = jax.lax.dot_general(
            qc, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # (width, span)
        if masked:
            ok = _super_mask(consts, win, _bm_read(bitmaps_ref, h, sr, j),
                             block, causal, True)
            s = jnp.where(ok, s, NEG_INF)
        # window rows can be EMPTY q rows (lse = -inf): clamp so
        # exp(-1e30 - -1e30) = 1 cannot leak into dk/dv
        p = jnp.exp(s - jnp.maximum(lsec, NEG_INF * 0.5)[:, None])
        dv_new = dv + jax.lax.dot_general(
            p.astype(doc.dtype), doc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            doc, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - deltac[:, None]) * sm_scale
        dk_new = dk + jax.lax.dot_general(
            ds.astype(qc.dtype), qc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk_new, dv_new

      return body

    z = jnp.zeros(k.shape[:1] + (Dh,), jnp.float32)
    nf = nfull_ref[h, sr]
    carry = jax.lax.fori_loop(0, nf, make_body(False), (z, z))
    dk, dv = jax.lax.fori_loop(nf, counts_ref[h, sr], make_body(True),
                               carry)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _res_pallas_call(name, kernel, grid, in_specs, out_specs, out_shape,
                     interpret, n_prefetch=4):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
    )
    # no cross-step state: both grid dims reorder/pipeline freely
    kwargs = _compiler_params(interpret, 2, ("parallel", "parallel"))
    return pl.pallas_call(
        kernel, name=name, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret, **kwargs,
    )


def _bs_fwd_res(q, k, v, lut, sm_scale, block, chunk, causal, srow,
                interpret):
    B, S, H, Dh = q.shape
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
    nsr = (S // block) // srow
    span = srow * block
    kernel = functools.partial(
        _bs_fwd_kernel_res, sm_scale=sm_scale, block=block, chunk=chunk,
        srow=srow, causal=causal, num_heads=H,
    )
    blk = lambda b, i, *_: (b, i, 0)
    o, lse = _res_pallas_call(
        "block_sparse_res_fwd",
        kernel,
        grid=(B * H, nsr),
        in_specs=[
            _vmem_spec((1, span, Dh), blk),
            _vmem_spec((1, S, Dh), lambda b, i, *_: (b, 0, 0)),
            _vmem_spec((1, S, Dh), lambda b, i, *_: (b, 0, 0)),
        ],
        out_specs=[
            _vmem_spec((1, span, Dh), blk),
            _vmem_spec((1, 1, span), lambda b, i, *_: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, Dh), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32),
        ],
        interpret=interpret,
    )(*lut, qf, kf, vf)
    return o, lse, (qf, kf, vf)


def _bs_bwd_res(res, g, lut, lut_t, sm_scale, block, chunk, causal, srow,
                interpret, num_heads):
    qf, kf, vf, o, lse = res
    BH, S, Dh = qf.shape
    H = num_heads
    nsr = (S // block) // srow
    span = srow * block
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.reshape(BH, 1, S)
    blk = lambda b, i, *_: (b, i, 0)
    row1 = lambda b, i, *_: (b, 0, i)
    full = lambda b, i, *_: (b, 0, 0)

    dq = _res_pallas_call(
        "block_sparse_res_bwd_dq",
        functools.partial(
            _bs_bwd_dq_kernel_res, sm_scale=sm_scale, block=block,
            chunk=chunk, srow=srow, causal=causal, num_heads=H,
        ),
        grid=(BH, nsr),
        in_specs=[
            _vmem_spec((1, span, Dh), blk),    # q
            _vmem_spec((1, S, Dh), full),      # k resident
            _vmem_spec((1, S, Dh), full),      # v resident
            _vmem_spec((1, span, Dh), blk),    # do
            _vmem_spec((1, 1, span), row1),    # lse
            _vmem_spec((1, 1, span), row1),    # delta
        ],
        out_specs=_vmem_spec((1, span, Dh), blk),
        out_shape=jax.ShapeDtypeStruct((BH, S, Dh), qf.dtype),
        interpret=interpret,
    )(*lut, qf, kf, vf, do, lse, delta)

    dk, dv = _res_pallas_call(
        "block_sparse_res_bwd_dkv",
        functools.partial(
            _bs_bwd_dkdv_kernel_res, sm_scale=sm_scale, block=block,
            chunk=chunk, srow=srow, causal=causal, num_heads=H,
        ),
        grid=(BH, nsr),
        in_specs=[
            _vmem_spec((1, span, Dh), blk),    # k super-tile
            _vmem_spec((1, span, Dh), blk),    # v super-tile
            _vmem_spec((1, S, Dh), full),      # q resident
            _vmem_spec((1, S, Dh), full),      # do resident
            _vmem_spec((1, 1, S), full),       # lse
            _vmem_spec((1, 1, S), full),       # delta
        ],
        out_specs=[
            _vmem_spec((1, span, Dh), blk),
            _vmem_spec((1, span, Dh), blk),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, Dh), qf.dtype),
            jax.ShapeDtypeStruct((BH, S, Dh), qf.dtype),
        ],
        interpret=interpret,
    )(*lut_t, kf, vf, qf, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------------ #
# public factory
# ------------------------------------------------------------------ #


# Measured on v5e before the current installation: the streaming sparse kernels beat
# DENSE flash only below ~12% effective density; above it, computing the
# full S^2 on flash is faster than gathering the sparse blocks. auto CANNOT
# route to flash — dense attention attends positions the layout masks out,
# and the mask is model semantics, not an optimization — so above the
# break-even the honest answer is: this layout's sparsity does not pay on
# this chip (auto_route reports the prediction; the bench labels it).
FLASH_DENSITY_BREAK_EVEN = 0.12


def split_global_columns(lay_c: np.ndarray, causal: bool = True,
                         min_frac: float = 0.5, min_rows: int = 2):
    """Separate STRIDED-GLOBAL block columns from a (causal-filtered)
    layout (VERDICT r3/r4 stretch: the Fixed config's every-Nth-column
    globals explode the super-tile union windows — waste 3-5x — because
    a contiguous CHUNK window covering an isolated column is mostly
    dead area; those columns are exactly the ones EVERY row attends, so
    they run better as one dense pass over gathered K/V columns).

    A column c is global for head h when it is active in >= min_frac of
    its possible rows (``causal`` True: the nb-c rows at or below the
    diagonal of a causal-filtered layout; False: all nb rows — using the
    causal denominator on a non-causal layout misclassifies ordinary
    right-edge window columns as globals) and >= min_rows rows. Columns
    whose removal would empty any formerly-nonempty row are kept (the
    merge math needs a finite lse from the windowed pass).

    Returns (lay_rest, cols (H, G) int32 padded -1, colmask (H, nb, G)
    bool — which row blocks genuinely attend each gathered column)."""
    lay = np.asarray(lay_c) != 0
    H, nb, _ = lay.shape
    possible = (np.arange(nb, 0, -1) if causal
                else np.full(nb, nb))  # causal: rows >= c -> nb - c rows
    per_head_cols = []
    lay_rest = lay.copy()
    for h in range(H):
        counts = lay[h].sum(axis=0)
        glob = np.nonzero(
            (counts >= np.maximum(min_frac * possible, min_rows)))[0]
        # greedy strip, never removing a row's only content (the merge
        # math needs a finite windowed-pass lse everywhere)
        rest = lay[h].copy()
        stripped = []
        for c in glob:
            saved = rest[:, c].copy()
            rest[:, c] = False
            if (((~rest.any(axis=1)) & lay[h].any(axis=1)).any()):
                rest[:, c] = saved  # would empty a row: keep windowed
            else:
                stripped.append(int(c))
        lay_rest[h] = rest
        per_head_cols.append(np.asarray(stripped, np.int64))
    G = max((len(c) for c in per_head_cols), default=0)
    cols = np.full((H, max(G, 1)), -1, np.int32)
    colmask = np.zeros((H, nb, max(G, 1)), bool)
    for h, cs in enumerate(per_head_cols):
        cols[h, : len(cs)] = cs
        for j, c in enumerate(cs):
            colmask[h, :, j] = lay[h, :, c]
    return lay_rest, cols, colmask


def _gather_cols(kh, cols, block):
    """kh (B, H, S, Dh), cols (H, G) block ids (-1 pad) -> (B, H,
    G*block, Dh) gathered block columns (pad blocks gather block 0 and
    are masked downstream)."""
    B, H, S, Dh = kh.shape
    nb = S // block
    kb = kh.reshape(B, H, nb, block, Dh)
    safe = jnp.maximum(jnp.asarray(cols), 0)
    hidx = jnp.arange(H)[:, None]
    g = kb[:, hidx, safe]  # (B, H, G, block, Dh)
    return g.reshape(B, H, cols.shape[1] * block, Dh)


def _global_mask_parts(cols, colmask, block):
    """SMALL numpy constants for the gathered-pass mask — the token-level
    (H, S, G*block) expansion happens in-trace (_expand_global_mask), so
    traces bake KBs of block-level constants instead of a 100MB+ dense
    token mask. Returns (block mask (H, nb, G) with pad columns off,
    col_tok (H, G*block) gathered token ids)."""
    cols = np.asarray(cols)
    G = cols.shape[1]
    cm = colmask & (cols >= 0)[:, None, :]
    col_tok = (np.repeat(np.maximum(cols, 0) * block, block, axis=1)
               + np.tile(np.arange(block), G))  # (H, G*block)
    return cm, col_tok


def _expand_global_mask(cm, col_tok, S, block, causal):
    """In-trace (H, S, G*block) bool from the block-level constants:
    layout activity for the stripped columns + token causality inside
    active blocks."""
    m = jnp.repeat(jnp.repeat(jnp.asarray(cm), block, axis=1),
                   block, axis=2)
    if causal:
        m = m & (jnp.asarray(col_tok)[:, None, :]
                 <= jnp.arange(S)[None, :, None])
    return m


def _global_pass_fwd(qh, kh, vh, cols, mask_parts, causal, scale, block):
    """Dense attention over the gathered global columns. Returns
    (o2 (B,H,S,Dh) fp32, lse2 (B,H,S) fp32). Rows with no active
    gathered tokens return o2=0, lse2=-inf (zero weight in the merge)."""
    kg = _gather_cols(kh, cols, block)
    vg = _gather_cols(vh, cols, block)
    s = jnp.einsum("bhsd,bhtd->bhst", qh, kg,
                   preferred_element_type=jnp.float32) * scale
    mask = _expand_global_mask(*mask_parts, qh.shape[2], block,
                               causal)[None]
    s = jnp.where(mask, s, -jnp.inf)
    m2 = jnp.max(s, axis=-1)
    m2s = jnp.where(jnp.isfinite(m2), m2, 0.0)
    p = jnp.where(mask, jnp.exp(s - m2s[..., None]), 0.0)
    l2 = jnp.sum(p, axis=-1)
    lse2 = jnp.where(l2 > 0, jnp.log(jnp.maximum(l2, 1e-30)) + m2s,
                     -jnp.inf)
    o2 = jnp.einsum("bhst,bhtd->bhsd", p.astype(qh.dtype), vg,
                    preferred_element_type=jnp.float32)
    o2 = o2 / jnp.maximum(l2, 1e-30)[..., None]
    return o2, lse2


def _global_pass_bwd(qh, kh, vh, cols, mask_parts, causal, scale, block,
                     lse, delta, gh):
    """Backward of the gathered dense pass under the GLOBAL softmax
    (merged lse + delta): the attention backward decomposes additively
    over key subsets given global statistics. Returns (dq2, dk2, dv2)
    full-shaped (B,H,S,Dh) fp32 with the gathered grads scattered back."""
    B, H, S, Dh = qh.shape
    nb = S // block
    G = cols.shape[1]
    kg = _gather_cols(kh, cols, block)
    vg = _gather_cols(vh, cols, block)
    s = jnp.einsum("bhsd,bhtd->bhst", qh, kg,
                   preferred_element_type=jnp.float32) * scale
    mask = _expand_global_mask(*mask_parts, S, block, causal)[None]
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
    p = jnp.where(mask, jnp.exp(s - lse_safe[..., None]), 0.0)
    dv_g = jnp.einsum("bhst,bhsd->bhtd", p.astype(gh.dtype), gh,
                      preferred_element_type=jnp.float32)
    dp = jnp.einsum("bhsd,bhtd->bhst", gh, vg,
                    preferred_element_type=jnp.float32)
    ds = (p * (dp - delta[..., None]) * scale).astype(qh.dtype)
    dq2 = jnp.einsum("bhst,bhtd->bhsd", ds, kg,
                     preferred_element_type=jnp.float32)
    dk_g = jnp.einsum("bhst,bhsd->bhtd", ds, qh,
                      preferred_element_type=jnp.float32)
    # scatter the gathered dk/dv back onto their true block columns
    valid = (jnp.asarray(cols) >= 0)[None, :, :, None, None]
    safe = jnp.maximum(jnp.asarray(cols), 0)
    hidx = jnp.arange(H)[:, None]
    dkb = jnp.zeros((B, H, nb, block, Dh), jnp.float32)
    dvb = jnp.zeros((B, H, nb, block, Dh), jnp.float32)
    dk_g = dk_g.reshape(B, H, G, block, Dh) * valid
    dv_g = dv_g.reshape(B, H, G, block, Dh) * valid
    dkb = dkb.at[:, hidx, safe].add(dk_g)
    dvb = dvb.at[:, hidx, safe].add(dv_g)
    return dq2, dkb.reshape(B, H, S, Dh), dvb.reshape(B, H, S, Dh)


def _resident_split_decision(lay_c: np.ndarray, chunk: int, srow: int,
                             causal: bool):
    """THE shared resident/split/stream policy (factory dispatch AND
    auto_route introspection — one implementation so the bench labels can
    never desynchronize from what executes). Assumes resident_ok already
    held. Returns (impl, waste, parts) where parts =
    (lay_rest, cols, colmask) for 'split', else None. Split criterion is
    ABSOLUTE covered area (iteration count): stripping strided globals
    can RAISE the remainder's waste ratio (active shrinks faster than
    coverage) while cutting covered area, and iterations — not ratios —
    are what the 6us/iteration cost model prices; the stripped columns
    re-run as one gathered dense GEMM at MXU efficiency."""
    waste = supertile_waste(lay_c, chunk, srow)
    if waste <= 2.0:
        return "resident", waste, None
    lay_rest, cols, colmask = split_global_columns(lay_c, causal)
    cov_full = supertile_covered(lay_c, chunk, srow)
    cov_rest = supertile_covered(lay_rest, chunk, srow)
    if (cols >= 0).sum() > 0 and cov_rest <= 0.67 * cov_full:
        return ("split", supertile_waste(lay_rest, chunk, srow),
                (lay_rest, cols, colmask))
    return "stream", waste, None


def auto_route(layout: np.ndarray, causal: bool, S: int,
               Dh: int, dtype=jnp.bfloat16):
    """What impl='auto' executes for this layout/geometry, with the
    numbers behind it: (impl, waste, density, dense_flash_predicted_faster)
    where impl is 'resident'|'split'|'stream' (for 'split', waste is the
    windowed remainder's). Mirrors make_block_sparse_attention's dispatch
    via the shared _resident_split_decision — benchmark/report
    introspection."""
    lay = np.asarray(layout)
    H, nb, _ = lay.shape
    chunk = min(CHUNK, nb)
    srow = _pick_tile(nb, SROW)
    lay_c = lay
    denom = H * nb * nb
    if causal:
        tri = np.tril(np.ones((nb, nb), bool))
        lay_c = lay * tri
        denom = H * int(tri.sum())
    waste = supertile_waste(lay_c, chunk, srow)
    density = float((lay_c != 0).sum()) / denom
    itemsize = jnp.dtype(dtype).itemsize
    if resident_ok(S, Dh, itemsize):
        impl, waste, _ = _resident_split_decision(lay_c, chunk, srow,
                                                  causal)
    else:
        impl = "stream"
    from ..pallas.flash_attention import is_available

    probe = jax.ShapeDtypeStruct((1, S, H, Dh), jnp.dtype(dtype))
    flash_faster = bool(
        impl == "stream" and density >= FLASH_DENSITY_BREAK_EVEN
        and is_available(probe))
    return impl, waste, density, flash_faster


def make_block_sparse_attention(layout: np.ndarray, block: int,
                                causal: bool = False, sm_scale: float = None,
                                interpret: bool = False, impl: str = "auto"):
    """Compile-ready block-sparse attention for a FIXED layout.

    layout: (H, nb, nb) 0/1 numpy array; returns fn(q, k, v) on (B, S, H, Dh)
    with S == nb * block. The layout and its LUTs are baked into the
    computation as constants (they are static configuration, like the
    reference's cached triton ops per seq-len).

    impl: "auto" picks the flash-style resident-K/V kernels while the
    whole-sequence tensors fit the VMEM budget (resident_ok) and falls back
    to the LUT-streaming kernels beyond; "resident"/"stream" force a path
    (benchmarks, tests)."""
    layout = np.asarray(layout)
    H, nb, _ = layout.shape
    if impl not in ("auto", "resident", "stream", "split"):
        raise ValueError(
            f"impl must be auto|resident|stream|split, got {impl!r}")
    # LUTs stay NUMPY: converting to jnp here would capture a tracer when
    # the factory is first invoked inside someone else's jit trace (ops are
    # cached per seq-len — a cached tracer poisons every later call with
    # UnexpectedTracerError). numpy constants bind safely into any trace.
    # Built LAZILY per path: the host-side per-row python loops are ~O(nnz)
    # and only the path actually traced should pay them.
    chunk = min(CHUNK, nb)
    srow = _pick_tile(nb, SROW)
    _luts = {}

    def _stream_luts():
        if "stream" not in _luts:
            _luts["stream"] = (
                build_flat_lut(layout, lane=LANE),
                build_flat_lut(layout.transpose(0, 2, 1), lane=LANE),
            )
        return _luts["stream"]

    def _causal_layout():
        # THE single causal-filter site: the resident LUTs (both
        # orientations) and the auto cost model all derive from this one
        # filtered layout, so masking and kernel selection can never
        # desynchronize
        lay_c = layout != 0
        if causal:
            lay_c = lay_c & np.tril(np.ones((nb, nb), bool))[None]
        return lay_c

    def _resident_luts():
        if "resident" not in _luts:
            lay_c = _causal_layout()
            _luts["resident"] = (
                build_super_lut(lay_c, chunk, srow, causal),
                build_super_lut(lay_c.transpose(0, 2, 1), chunk, srow,
                                causal, transposed=True),
            )
        return _luts["resident"]

    _waste = [None]

    def _split_parts():
        """Strided-global decomposition: windowed remainder (resident
        super-tile kernels) + gathered dense pass over the stripped
        columns, merged under one global softmax. Built from the shared
        routing decision (or directly when impl='split' is forced)."""
        if "split" not in _luts:
            lay_c = _causal_layout()
            decided = _luts.get("route")
            parts = decided[2] if decided and decided[2] else None
            if parts is None:
                parts = split_global_columns(lay_c, causal)
            lay_rest, cols, colmask = parts
            _luts["split"] = (
                cols,
                _global_mask_parts(cols, colmask, block),
                build_super_lut(lay_rest, chunk, srow, causal),
                build_super_lut(lay_rest.transpose(0, 2, 1), chunk, srow,
                                causal, transposed=True),
            )
        return _luts["split"]

    def _route(S, Dh, dtype):
        """'resident' | 'split' | 'stream' (cached; policy lives in the
        shared _resident_split_decision so auto_route introspection and
        this dispatch cannot desynchronize)."""
        if impl != "auto":
            return impl
        if not resident_ok(S, Dh, jnp.dtype(dtype).itemsize):
            return "stream"
        if "route" not in _luts:
            _luts["route"] = _resident_split_decision(
                _causal_layout(), chunk, srow, causal)
            _waste[0] = _luts["route"][1]
        return _luts["route"][0]

    def _use_resident(S, Dh, dtype):
        return _route(S, Dh, dtype) == "resident"

    def _merge_passes(o1, lse1, o2, lse2):
        """(o1 flat (BH,S,Dh), lse1 (BH,1,S)) + dense-pass (o2 fp32,
        lse2) -> merged flat o (o1.dtype) + lse, one global softmax."""
        lse = jnp.logaddexp(lse1, lse2)
        fin = jnp.isfinite(lse)
        w1 = jnp.where(fin, jnp.exp(lse1 - jnp.where(fin, lse, 0.0)), 0.0)
        w2 = jnp.where(fin, jnp.exp(lse2 - jnp.where(fin, lse, 0.0)), 0.0)
        o = (o1.astype(jnp.float32) * w1[:, 0, :, None]
             + o2 * w2[:, 0, :, None])
        return o.astype(o1.dtype), lse

    def _split_fwd(q, k, v, scale):
        B, S, Hq, Dh = q.shape
        cols, mask_parts, lut, lut_t = _split_parts()
        o1, lse1, (qf, kf, vf) = _bs_fwd_res(
            q, k, v, lut, scale, block, chunk, causal, srow, interpret)
        qh = qf.reshape(B, Hq, S, Dh)
        o2, lse2 = _global_pass_fwd(
            qh, kf.reshape(B, Hq, S, Dh), vf.reshape(B, Hq, S, Dh),
            cols, mask_parts, causal, scale, block)
        o, lse = _merge_passes(o1, lse1, o2.reshape(B * Hq, S, Dh),
                               lse2.reshape(B * Hq, 1, S))
        return o, lse, (qf, kf, vf)

    def _split_bwd(res, gf, scale, B, Hq):
        qf, kf, vf, o, lse = res
        BH, S, Dh = qf.shape
        cols, mask_parts, lut, lut_t = _split_parts()
        dq1, dk1, dv1 = _bs_bwd_res(
            (qf, kf, vf, o, lse), gf, lut, lut_t, scale, block, chunk,
            causal, srow, interpret, Hq)
        qh = qf.reshape(B, Hq, S, Dh)
        gh = gf.reshape(B, Hq, S, Dh)
        delta = jnp.sum(gh.astype(jnp.float32)
                        * o.reshape(B, Hq, S, Dh).astype(jnp.float32),
                        axis=-1)
        dq2, dk2, dv2 = _global_pass_bwd(
            qh, kf.reshape(B, Hq, S, Dh), vf.reshape(B, Hq, S, Dh),
            cols, mask_parts, causal, scale, block,
            lse.reshape(B, Hq, S), delta, gh)
        add = lambda a, b: (a.astype(jnp.float32)
                            + b.reshape(BH, S, Dh)).astype(a.dtype)
        return add(dq1, dq2), add(dk1, dk2), add(dv1, dv2)

    @jax.custom_vjp
    def attend(q, k, v):
        scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
        B, S, _, Dh = q.shape
        route = _route(S, Dh, q.dtype)
        if route == "split":
            o, _, _ = _split_fwd(q, k, v, scale)
        elif route == "resident":
            o, _, _ = _bs_fwd_res(q, k, v, _resident_luts()[0], scale,
                                  block, chunk, causal, srow, interpret)
        else:
            rows, cols = _stream_luts()[0]
            o, _, _ = _bs_fwd(q, k, v, rows, cols, scale, block, causal,
                              interpret)
        return o.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)

    def fwd(q, k, v):
        scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
        B, S, _, Dh = q.shape
        route = _route(S, Dh, q.dtype)
        if route == "split":
            o, lse, (qf, kf, vf) = _split_fwd(q, k, v, scale)
        elif route == "resident":
            o, lse, (qf, kf, vf) = _bs_fwd_res(
                q, k, v, _resident_luts()[0], scale, block, chunk, causal,
                srow, interpret
            )
        else:
            rows, cols = _stream_luts()[0]
            o, lse, (qf, kf, vf) = _bs_fwd(
                q, k, v, rows, cols, scale, block, causal, interpret
            )
        out = o.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)
        return out, (qf, kf, vf, o, lse, scale, (B, S, H, Dh))

    def bwd(res, g):
        qf, kf, vf, o, lse, scale, (B, S, H_, Dh) = res
        gf = g.transpose(0, 2, 1, 3).reshape(B * H_, S, Dh)
        route = _route(S, Dh, qf.dtype)
        if route == "split":
            dq, dk, dv = _split_bwd(
                (qf, kf, vf, o, lse), gf, scale, B, H_)
        elif route == "resident":
            lut_res, lut_res_t = _resident_luts()
            dq, dk, dv = _bs_bwd_res(
                (qf, kf, vf, o, lse), gf, lut_res, lut_res_t, scale, block,
                chunk, causal, srow, interpret, H_,
            )
        else:
            (rows, cols), (keys_t, qrows_t) = _stream_luts()
            dq, dk, dv = _bs_bwd(
                (qf, kf, vf, o, lse), gf, rows, cols, keys_t, qrows_t,
                scale, block, causal, interpret, H_,
            )
        unflat = lambda x: x.reshape(B, H_, S, Dh).transpose(0, 2, 1, 3)
        return unflat(dq), unflat(dk), unflat(dv)

    attend.defvjp(fwd, bwd)

    _hinted = [False]

    def checked(q, k, v):
        B, S, Hq, Dh = q.shape
        if Hq != H:
            raise ValueError(f"layout built for {H} heads, got {Hq}")
        if S != nb * block:
            raise ValueError(
                f"layout built for seq len {nb * block} (block {block}), got {S}"
            )
        if impl == "auto" and not _hinted[0]:
            _hinted[0] = True
            route, waste, density, flash_faster = auto_route(
                layout, causal, S, Dh, q.dtype)
            if flash_faster:
                from ...utils.logging import logger

                logger.info(
                    "block-sparse auto: layout density %.3f is above the "
                    "measured ~%.2f break-even where DENSE flash outruns "
                    "the sparse kernels on this chip (waste %.2f rules "
                    "out the resident path). Sparsity is not buying "
                    "speed here — if the mask is only an approximation "
                    "for you, dense flash_attention is faster; the mask "
                    "SEMANTICS are preserved on the %s sparse path.",
                    density, FLASH_DENSITY_BREAK_EVEN, waste, route)
        return attend(q, k, v)

    return checked


def block_sparse_attention_xla(q, k, v, layout: np.ndarray, block: int,
                               causal: bool = False, sm_scale: float = None,
                               key_padding_mask=None):
    """Dense-mask XLA reference implementation (for testing and as a
    numerically identical fallback on platforms without Pallas).

    key_padding_mask: optional (B, S) additive float mask (0 keep /
    large-negative drop) — the reference softmax's 'add' mode."""
    B, S, H, Dh = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(Dh)
    mask = np.kron(np.asarray(layout) != 0, np.ones((block, block), bool))
    mask = mask[:, :S, :S]
    if causal:
        mask = mask & np.tril(np.ones((S, S), bool))[None]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    s = jnp.where(jnp.asarray(mask)[None], s, NEG_INF)
    visible = jnp.asarray(mask)[None]  # (1, H, Sq, Sk)
    if key_padding_mask is not None:
        s = s + key_padding_mask[:, None, None, :].astype(jnp.float32)
        visible = visible & (key_padding_mask > NEG_INF / 2)[:, None, None, :]
    # rows with no visible key: output 0 (matches the kernel's l==0 path)
    any_visible = visible.any(axis=-1)  # (B|1, H, Sq)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(any_visible[..., None], p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
