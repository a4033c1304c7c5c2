"""Fused elementwise Pallas blocks: LayerNorm, residual+LayerNorm, bias+GeLU.

These are the TPU-native replacements for the reference's fused CUDA
elementwise kernels (csrc/transformer/normalize_kernels.cu and
gelu_kernels.cu): one VMEM round-trip per block instead of the ~5 HBM
passes the unfused XLA graph pays (upcast, mean, var, normalize, affine as
separate fusions bounded by layout changes around the matmuls).

Every public entry point is a *dispatcher*: it consults
ops/kernel_config.py and either launches the Pallas kernel (TPU, or
interpret mode when forced/off-TPU) or falls back to the plain XLA
reference — the exact math the models used before this layer existed, so
`kernels: off` is byte-identical to the pre-fusion graphs.

Layout: inputs are flattened to (R, D) with D the normalized/bias axis.
The grid tiles rows; the feature axis always spans the full block (lane
dim covers the whole array, so no 128-divisibility constraint on D). Row
blocks must be 128-divisible for the LN kernels because the saved
mean/rstd rows are laid out (1, R) with R on lanes (same trick as the
flash kernels' lse). Geometries with no suitable row block fall back to
XLA under `auto` — correctness never depends on the kernel firing.

Backwards are `jax.custom_vjp`s: dx is computed in a row-tiled kernel;
the dw/db reductions over rows are emitted as per-block partials and
summed outside the kernel — a cross-block accumulation inside the kernel
would force an "arbitrary" grid dimension and serialize the pipeline.
Each partial is written as an (8, D) tile (the row replicated over the 8
sublanes): the TPU lowering refuses a (1, D) block over an (nb, D) array,
whose second-to-last block dim must be a multiple of 8 or the full extent.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..kernel_config import resolve as _resolve_kernels
from .flash_attention import _compiler_params, _vmem_spec

_SQRT_2_OVER_PI = 0.7978845608028654
_GELU_C = 0.044715
_INV_SQRT2 = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327
_SUBLANES = 8


def _part_spec(D):
    """Per-grid-step partial-sum tile of an (nb, 8, D) output."""
    return _vmem_spec((1, _SUBLANES, D), lambda i: (i, 0, 0))


def _part_shape(nb, D):
    return jax.ShapeDtypeStruct((nb, _SUBLANES, D), jnp.float32)


def _write_part(ref, row):
    ref[0] = jnp.broadcast_to(row[None, :], ref.shape[1:])


def _sum_parts(parts, dtype):
    """(nb, 8, D) replicated partial tiles -> the (1, D) reduction."""
    return jnp.sum(parts[:, 0, :], axis=0, keepdims=True).astype(dtype)


def _row_block(R, D, lane128):
    """Row-block size: divides R, working set ~16 B/element under ~8 MB
    VMEM. LN kernels additionally need 128 | block (stats lanes); a single
    whole-R block (grid of 1) is always legal when it fits."""
    budget_elems = 512 * 1024
    cands = (1024, 512, 256, 128)
    if not lane128:
        cands = cands + (64, 32, 16, 8)
    for br in cands:
        if br <= R and R % br == 0 and br * D <= budget_elems:
            return br
    if R * D <= budget_elems:
        return R
    return None


# ------------------------------------------------------------------ #
# layer norm
# ------------------------------------------------------------------ #


def _ln_stats(x32, eps):
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return mu, jax.lax.rsqrt(var + eps)


def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mu_ref, rs_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)  # (BR, D)
    mu, rs = _ln_stats(x, eps)
    w = w_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    y_ref[...] = ((x - mu) * rs * w + b).astype(y_ref.dtype)
    mu_ref[0] = mu[:, 0]
    rs_ref[0] = rs[:, 0]


def _ln_dx(x32, g32, w32, mu, rs):
    """dx for y = (x - mu) * rs * w + b, plus the per-block dw/db partials.
    Standard LN backward: dx = rs * (dy - mean(dy) - xhat * mean(dy*xhat))
    with dy = g * w."""
    xhat = (x32 - mu) * rs
    dy = g32 * w32
    c1 = jnp.mean(dy, axis=-1, keepdims=True)
    c2 = jnp.mean(dy * xhat, axis=-1, keepdims=True)
    dx = rs * (dy - c1 - xhat * c2)
    return dx, jnp.sum(g32 * xhat, axis=0), jnp.sum(g32, axis=0)


def _ln_bwd_kernel(x_ref, w_ref, mu_ref, rs_ref, g_ref,
                   dx_ref, dwp_ref, dbp_ref):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    mu = mu_ref[0][:, None]
    rs = rs_ref[0][:, None]
    dx, dwp, dbp = _ln_dx(x, g, w, mu, rs)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    _write_part(dwp_ref, dwp)
    _write_part(dbp_ref, dbp)


def _ln_fwd_call(x2, w2, b2, eps, block, interpret):
    R, D = x2.shape
    grid = (R // block,)
    feat = _vmem_spec((1, D), lambda i: (0, 0))
    rows = _vmem_spec((block, D), lambda i: (i, 0))
    stat = _vmem_spec((1, block), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        name="layer_norm_fwd",
        grid=grid,
        in_specs=[rows, feat, feat],
        out_specs=[rows, stat, stat],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x2.dtype),
            jax.ShapeDtypeStruct((1, R), jnp.float32),
            jax.ShapeDtypeStruct((1, R), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret, 1),
    )(x2, w2, b2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln(x2, w2, b2, eps, block, interpret):
    y, _, _ = _ln_fwd_call(x2, w2, b2, eps, block, interpret)
    return y


def _ln_vjp_fwd(x2, w2, b2, eps, block, interpret):
    y, mu, rs = _ln_fwd_call(x2, w2, b2, eps, block, interpret)
    return y, (x2, w2, mu, rs)


def _ln_vjp_bwd(eps, block, interpret, res, g):
    x2, w2, mu, rs = res
    R, D = x2.shape
    nb = R // block
    feat = _vmem_spec((1, D), lambda i: (0, 0))
    rows = _vmem_spec((block, D), lambda i: (i, 0))
    stat = _vmem_spec((1, block), lambda i: (0, i))
    part = _part_spec(D)
    dx, dwp, dbp = pl.pallas_call(
        _ln_bwd_kernel,
        name="layer_norm_bwd",
        grid=(nb,),
        in_specs=[rows, feat, stat, stat, rows],
        out_specs=[rows, part, part],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x2.dtype),
            _part_shape(nb, D),
            _part_shape(nb, D),
        ],
        interpret=interpret,
        **_compiler_params(interpret, 1),
    )(x2, w2, mu, rs, g)
    dw = _sum_parts(dwp, w2.dtype)
    db = _sum_parts(dbp, w2.dtype)
    return dx, dw, db


_ln.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


# ------------------------------------------------------------------ #
# residual add + layer norm (BERT post-LN: LN(x + sublayer(x)))
# ------------------------------------------------------------------ #


def _aln_fwd_kernel(x_ref, r_ref, w_ref, b_ref, y_ref, mu_ref, rs_ref, *,
                    eps):
    s = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    mu, rs = _ln_stats(s, eps)
    w = w_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    y_ref[...] = ((s - mu) * rs * w + b).astype(y_ref.dtype)
    mu_ref[0] = mu[:, 0]
    rs_ref[0] = rs[:, 0]


def _aln_bwd_kernel(x_ref, r_ref, w_ref, mu_ref, rs_ref, g_ref,
                    ds_ref, dwp_ref, dbp_ref):
    s = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    mu = mu_ref[0][:, None]
    rs = rs_ref[0][:, None]
    ds, dwp, dbp = _ln_dx(s, g, w, mu, rs)
    ds_ref[...] = ds.astype(ds_ref.dtype)
    _write_part(dwp_ref, dwp)
    _write_part(dbp_ref, dbp)


def _aln_fwd_call(x2, r2, w2, b2, eps, block, interpret):
    R, D = x2.shape
    feat = _vmem_spec((1, D), lambda i: (0, 0))
    rows = _vmem_spec((block, D), lambda i: (i, 0))
    stat = _vmem_spec((1, block), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_aln_fwd_kernel, eps=eps),
        name="add_layer_norm_fwd",
        grid=(R // block,),
        in_specs=[rows, rows, feat, feat],
        out_specs=[rows, stat, stat],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x2.dtype),
            jax.ShapeDtypeStruct((1, R), jnp.float32),
            jax.ShapeDtypeStruct((1, R), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret, 1),
    )(x2, r2, w2, b2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _aln(x2, r2, w2, b2, eps, block, interpret):
    y, _, _ = _aln_fwd_call(x2, r2, w2, b2, eps, block, interpret)
    return y


def _aln_vjp_fwd(x2, r2, w2, b2, eps, block, interpret):
    y, mu, rs = _aln_fwd_call(x2, r2, w2, b2, eps, block, interpret)
    return y, (x2, r2, w2, mu, rs)


def _aln_vjp_bwd(eps, block, interpret, res, g):
    x2, r2, w2, mu, rs = res
    R, D = x2.shape
    nb = R // block
    feat = _vmem_spec((1, D), lambda i: (0, 0))
    rows = _vmem_spec((block, D), lambda i: (i, 0))
    stat = _vmem_spec((1, block), lambda i: (0, i))
    part = _part_spec(D)
    ds, dwp, dbp = pl.pallas_call(
        _aln_bwd_kernel,
        name="add_layer_norm_bwd",
        grid=(nb,),
        in_specs=[rows, rows, feat, stat, stat, rows],
        out_specs=[rows, part, part],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x2.dtype),
            _part_shape(nb, D),
            _part_shape(nb, D),
        ],
        interpret=interpret,
        **_compiler_params(interpret, 1),
    )(x2, r2, w2, mu, rs, g)
    dw = _sum_parts(dwp, w2.dtype)
    db = _sum_parts(dbp, w2.dtype)
    # d/dx and d/dresidual of LN(x + r) are the same cotangent
    return ds, ds, dw, db


_aln.defvjp(_aln_vjp_fwd, _aln_vjp_bwd)


# ------------------------------------------------------------------ #
# bias + GeLU
# ------------------------------------------------------------------ #


# erf(x) ~= x * P(x^2) / Q(x^2) on [-4, 4] (the single-precision rational
# fit XLA and Eigen use; within 4.2e-7 of float64 erf, i.e. fp32 rounding).
# Written out because the Pallas TPU lowering has no rule for lax.erf.
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08, -2.10102402082508e-06,
          -5.69250639462346e-05, -7.34990630326855e-04,
          -2.95459980854025e-03, -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)


def _horner(x, coeffs):
    acc = jnp.full_like(x, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def _erf_f32(x):
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    return x * _horner(x2, _ERF_P) / _horner(x2, _ERF_Q)


def _gelu_fwd_f32(u, approximate):
    if approximate:
        inner = _SQRT_2_OVER_PI * (u + _GELU_C * u * u * u)
        return 0.5 * u * (1.0 + jnp.tanh(inner))
    return 0.5 * u * (1.0 + _erf_f32(u * _INV_SQRT2))


def _gelu_grad_f32(u, approximate):
    if approximate:
        inner = _SQRT_2_OVER_PI * (u + _GELU_C * u * u * u)
        t = jnp.tanh(inner)
        dinner = _SQRT_2_OVER_PI * (1.0 + 3.0 * _GELU_C * u * u)
        return 0.5 * (1.0 + t) + 0.5 * u * (1.0 - t * t) * dinner
    phi = 0.5 * (1.0 + _erf_f32(u * _INV_SQRT2))
    return phi + u * jnp.exp(-0.5 * u * u) * _INV_SQRT_2PI


def _bg_fwd_kernel(x_ref, b_ref, y_ref, *, approximate):
    u = x_ref[...].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
    y_ref[...] = _gelu_fwd_f32(u, approximate).astype(y_ref.dtype)


def _bg_bwd_kernel(x_ref, b_ref, g_ref, dx_ref, dbp_ref, *, approximate):
    u = x_ref[...].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
    dx = g_ref[...].astype(jnp.float32) * _gelu_grad_f32(u, approximate)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    _write_part(dbp_ref, jnp.sum(dx, axis=0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _bg(x2, b2, approximate, block, interpret):
    R, D = x2.shape
    feat = _vmem_spec((1, D), lambda i: (0, 0))
    rows = _vmem_spec((block, D), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_bg_fwd_kernel, approximate=approximate),
        name="bias_gelu_fwd",
        grid=(R // block,),
        in_specs=[rows, feat],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((R, D), x2.dtype),
        interpret=interpret,
        **_compiler_params(interpret, 1),
    )(x2, b2)


def _bg_vjp_fwd(x2, b2, approximate, block, interpret):
    return _bg(x2, b2, approximate, block, interpret), (x2, b2)


def _bg_vjp_bwd(approximate, block, interpret, res, g):
    x2, b2 = res
    R, D = x2.shape
    nb = R // block
    feat = _vmem_spec((1, D), lambda i: (0, 0))
    rows = _vmem_spec((block, D), lambda i: (i, 0))
    part = _part_spec(D)
    dx, dbp = pl.pallas_call(
        functools.partial(_bg_bwd_kernel, approximate=approximate),
        name="bias_gelu_bwd",
        grid=(nb,),
        in_specs=[rows, feat, rows],
        out_specs=[rows, part],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), x2.dtype),
            _part_shape(nb, D),
        ],
        interpret=interpret,
        **_compiler_params(interpret, 1),
    )(x2, b2, g)
    db = _sum_parts(dbp, b2.dtype)
    return dx, db


_bg.defvjp(_bg_vjp_fwd, _bg_vjp_bwd)


# ------------------------------------------------------------------ #
# XLA references (the exact pre-fusion math; `kernels: off` path)
# ------------------------------------------------------------------ #


def _ln_ref(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(x.dtype)


def _bg_ref(x, b, approximate):
    return jax.nn.gelu(x + b, approximate=approximate)


# ------------------------------------------------------------------ #
# dispatchers (the public API models call)
# ------------------------------------------------------------------ #


def _as_2d(x):
    D = x.shape[-1]
    return x.reshape(-1, D), x.shape


def _trace_kernel(name, shape, interpret):
    from ...monitor.tracer import trace_span

    return trace_span(f"kernels/{name}", lane="kernels",
                      shape=list(shape), interpret=interpret)


def layer_norm(x, w, b, eps):
    """LN(x) * w + b over the last axis, fp32 statistics."""
    use, interpret = _resolve_kernels("fused_blocks")
    if use:
        x2, shape = _as_2d(x)
        block = _row_block(x2.shape[0], x2.shape[1], lane128=True)
        if block is not None:
            with _trace_kernel("fused_layer_norm", shape, interpret):
                y = _ln(x2, w.reshape(1, -1), b.reshape(1, -1),
                        float(eps), block, interpret)
            return y.reshape(shape)
    return _ln_ref(x, w, b, eps)


def add_layer_norm(x, residual, w, b, eps):
    """LN(x + residual) * w + b — the BERT post-LN add&norm in one pass."""
    use, interpret = _resolve_kernels("fused_blocks")
    if use and x.shape == residual.shape:
        x2, shape = _as_2d(x)
        r2 = residual.reshape(x2.shape)
        block = _row_block(x2.shape[0], x2.shape[1], lane128=True)
        if block is not None:
            with _trace_kernel("fused_add_layer_norm", shape, interpret):
                y = _aln(x2, r2, w.reshape(1, -1), b.reshape(1, -1),
                         float(eps), block, interpret)
            return y.reshape(shape)
    return _ln_ref(x + residual, w, b, eps)


def bias_gelu(x, b, approximate):
    """gelu(x + b) in one pass; `approximate` picks tanh vs erf GeLU."""
    use, interpret = _resolve_kernels("fused_blocks")
    if use:
        x2, shape = _as_2d(x)
        block = _row_block(x2.shape[0], x2.shape[1], lane128=False)
        if block is not None:
            with _trace_kernel("fused_bias_gelu", shape, interpret):
                y = _bg(x2, b.reshape(1, -1), bool(approximate), block,
                        interpret)
            return y.reshape(shape)
    return _bg_ref(x, b, approximate)
