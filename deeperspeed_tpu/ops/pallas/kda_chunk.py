"""The gated delta rule with a decay a CHANNEL (Kimi Delta Attention) over
one prompt chunk, chunkwise.

Per head, with a state ``S`` of ``d_k x d_v`` float32, log-decays ``g_t <=
0`` a channel of the key, ``beta_t`` and unit keys:

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Over a block of B positions (``BLOCK``) with ``G_i = sum_{j<=i} g_j``:
``A_ij = beta_i (k_i * exp(G_i - G_j)) . k_j`` for ``j < i``, ``V' = (I +
A)^-1 (beta V - (beta K * exp G) S_0)``, ``o_i = (q_i * exp G_i) S_0 +
sum_{j<=i} ((q_i * exp(G_i - G_j)) . k_j) V'_j`` and ``S_B = Diag(exp G_B)
S_0 + (K * exp(G_B - G))^T V'`` (``block_rule``). The decay differs by
channel, so ``exp(G_i - G_j)`` does not factor into a row's and a column's
part that are both finite: ``exp(-G_j)`` alone overflows under a strong
decay. ONLY exponents ``<= 0`` are ever taken, in two levels: a block is
``B / SUB`` sub-blocks; between two sub-blocks ``I > J`` the exponent
splits at ``R_I``, the sum before sub-block I's first position (``G_i -
R_I <= 0`` and ``R_I - G_j <= 0``), and the scores are a matrix product;
inside a sub-block every pair ``(i, i - d)`` is met directly, one shift
``d`` at a time, the channels summed on the lanes. ``(I + A)`` is solved
sub-block by sub-block, a sub-block's own unit-triangular part inverted by
its finite Neumann product (``A_II^SUB = 0``).

The kernel: grid (head, block), a head's blocks in order with its state
(kept transposed, ``d_v x d_k``, so that the decay scales lanes) in VMEM
between them; q, k, beta k, beta v and g cross HBM once (float32, a lane-
aligned ``(B, 128)`` window of the projections' ``(C, H * 128)`` layout),
the state twice a chunk. Positions at or beyond ``n_valid`` come with ``g
= 0`` and ``beta = 0`` (the caller's masking): they neither decay the
state nor add to it. The oracle is the token recurrence
(models/mixers.kda_recurrence); models/mixers.kda_chunk_xla runs the same
``block_rule`` in plain XLA.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernel_config

BLOCK = 64      # positions a grid step takes
SUB = 16        # ... in sub-blocks of this many, met pair by pair


def block_rule(q, k, kb, vb, g, St, roll, dot, sub: int = SUB):
    """One block of one head, all float32 2-D arrays: q (scaled), k,
    ``kb = beta k`` (B, dk); ``vb = beta v`` (B, dv); g (B, dk) the
    log-decays; ``St`` (dv, dk) the state before the block, TRANSPOSED.
    ``roll(x, d)`` shifts rows down by a static ``d`` (row i takes row i
    - d; what wraps is masked); ``dot(a, b, dims)`` is a float32
    ``dot_general`` contracting ``dims`` with no batch. B a multiple of
    ``sub`` (a power of two). Returns (o (B, dv), the state after)."""
    B = q.shape[0]
    f32 = jnp.float32
    ab, abt, atb = (((1,), (0,)), ((1,), (1,)), ((0,), (0,)))
    ri = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
    within = ri & (sub - 1)
    n_sub = B // sub
    # G inside each sub-block (a prefix sum by doubling shifts), then R,
    # the sum before a row's own sub-block, and G = Gs + R
    Gs, d = g, 1
    while d < sub:
        Gs = Gs + jnp.where(within >= d, roll(Gs, d), 0.0)
        d *= 2
    totals, R, run = [], jnp.zeros_like(g), None
    for I in range(1, n_sub):
        last = Gs[I * sub - 1:I * sub]                      # (1, dk)
        run = last if run is None else run + last
        totals.append(run)                                  # R_I
        R = jnp.where(ri // sub == I, run, R)
    G = Gs + R
    el = jnp.exp(jnp.minimum(Gs, 0.0))                      # exp(G - R)
    kbl, ql = kb * el, q * el
    # between sub-blocks: rows of I against every earlier row, split at R_I
    a_rows = [jnp.zeros((sub, B), f32)]
    p_rows = [jnp.zeros((sub, B), f32)]
    for I in range(1, n_sub):
        kr = k * jnp.exp(jnp.minimum(totals[I - 1] - G, 0.0))
        lhs = jnp.concatenate([kbl[I * sub:(I + 1) * sub],
                               ql[I * sub:(I + 1) * sub]], 0)
        both = dot(lhs, kr, abt)                            # (2 sub, B)
        a_rows.append(both[:sub])
        p_rows.append(both[sub:])
    before = col < (row // sub) * sub
    A_off = jnp.where(before, jnp.concatenate(a_rows, 0), 0.0)
    P = jnp.where(before, jnp.concatenate(p_rows, 0), 0.0)
    # inside a sub-block: the pairs (i, i - d), one shift at a time
    A_in = jnp.zeros((B, B), f32)
    for d in range(sub):
        kd, Gd = (k, G) if d == 0 else (roll(k, d), roll(G, d))
        e = jnp.exp(jnp.minimum(G - Gd, 0.0)) * kd
        at = (col == row - d) & (within >= d)
        if d:
            A_in = jnp.where(at, jnp.sum(kb * e, 1, keepdims=True), A_in)
        P = jnp.where(at, jnp.sum(q * e, 1, keepdims=True), P)
    # (I + A_in)^-1, block-diagonal: the Neumann product, A_in^sub = 0
    eye = (row == col).astype(f32)
    X = -A_in
    T = eye + X
    n = 2
    while n < sub:
        X = dot(X, X, ab)
        T = dot(T, eye + X, ab)
        n *= 2
    eG = jnp.exp(G)
    rhs = vb - dot(kb * eG, St, abt)                        # (B, dv)
    Vp = jnp.zeros_like(rhs)
    for I in range(n_sub):
        r = rhs - dot(A_off, Vp, ab) if I else rhs
        Vp = Vp + dot(T, jnp.where(ri // sub == I, r, 0.0), ab)
    o = dot(q * eG, St, abt) + dot(P, Vp, ab)
    Gend = G[B - 1:B]                                       # (1, dk)
    St = St * jnp.exp(Gend) + dot(Vp, k * jnp.exp(Gend - G), atb)
    return o, St


def is_available(C: int, head_k: int, head_v: int) -> bool:
    """A chunk of ``C`` positions, keys of ``head_k`` and values of
    ``head_v`` entries: whole lanes a head, whole blocks a chunk."""
    if not kernel_config.on_tpu():
        return False
    return head_k == head_v == 128 and C % BLOCK == 0


def _kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, s_in_ref, o_ref, s_out_ref,
            St):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        St[...] = s_in_ref[0]

    roll = lambda x, d: pltpu.roll(x, d, 0)
    # float32 products in full: the solve multiplies what it sums
    dot = lambda a, b, dims: jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    o, new = block_rule(q_ref[...], k_ref[...], kb_ref[...], vb_ref[...],
                        g_ref[...], St[...], roll, dot)
    o_ref[...] = o
    St[...] = new

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        s_out_ref[0] = new


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_chunk(q, k, v, g, beta, s_in, interpret=False):
    """models/mixers.kda_chunk_xla as a kernel: q (scaled), k (C, H, dk)
    and v (C, H, dv) float32, g (C, H, dk) log-decays, beta (C, H); s_in
    (H, dk, dv) float32, the state before position 0. dk = dv = 128. ->
    (o (C, H, dv) float32, the state after position C - 1)."""
    C, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    b = beta.astype(f32)[..., None]
    flat = lambda t: t.astype(f32).reshape(C, -1)
    tok = lambda w: pl.BlockSpec((BLOCK, w), lambda h, i: (i, h))
    state = pl.BlockSpec((1, dv, dk), lambda h, i: (h, 0, 0))
    o, s_out = pl.pallas_call(
        _kernel,
        name="kda_chunk",
        grid=(H, C // BLOCK),
        in_specs=[tok(dk), tok(dk), tok(dk), tok(dv), tok(dk), state],
        out_specs=[tok(dv), state],
        scratch_shapes=[pltpu.VMEM((dv, dk), f32)],
        out_shape=[jax.ShapeDtypeStruct((C, H * dv), f32),
                   jax.ShapeDtypeStruct((H, dv, dk), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(flat(q), flat(k), flat(k.astype(f32) * b), flat(v.astype(f32) * b),
      flat(g), jnp.swapaxes(s_in.astype(f32), 1, 2))
    return o.reshape(C, H, dv), jnp.swapaxes(s_out, 1, 2)
