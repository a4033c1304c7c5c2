"""Interpret-mode parity sweep for the fused Pallas kernel layer.

Runs every fused surface (fused LayerNorm / add+LayerNorm / bias+GeLU,
fused Adam, dense super-tile flash, ragged-block streaming flash) over a
grid of supported geometries — including the BERT seq-128
attention geometry (64, 16, 128, 64) that motivated the super-tile
kernel — comparing against the plain XLA math, and prints a max-rel-err
table. Errors are max |fused - ref| normalized by max |ref| (stable where
the reference crosses zero).

Everything runs in Pallas interpret mode so the sweep works under
JAX_PLATFORMS=cpu; the same kernels compile unchanged on TPU. Exit code
is non-zero iff any geometry exceeds its tolerance.

Usage:
  python scripts/kernel_parity.py [--quick]

--quick skips the full bert128 super-tile geometry (the 256-group
interpret run dominates wall time). tests/test_fused_kernels.py has a
slow-marked wrapper running the full sweep.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _err(a, b):
    """max |a - b| / max |b| — scale-free, stable near zeros of b."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    denom = float(np.max(np.abs(b)))
    return float(np.max(np.abs(a - b))) / (denom if denom else 1.0)


def _grad_err(f_fused, f_ref, args):
    n = len(args)
    loss = lambda f: (lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2))
    g_f = jax.grad(loss(f_fused), argnums=tuple(range(n)))(*args)
    g_r = jax.grad(loss(f_ref), argnums=tuple(range(n)))(*args)
    return max(_err(a, b) for a, b in zip(g_f, g_r))


def _rand(shape, dtype, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def run_sweep(quick=False):
    """Returns (rows, failures); rows are printed by main()."""
    from deeperspeed_tpu.ops import kernel_config
    from deeperspeed_tpu.ops.pallas import fused_blocks
    from deeperspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deeperspeed_tpu.ops.pallas.flash_static import (
        flash_attention_supertile_bhsd)

    rows = []

    def record(surface, geometry, dtype, fwd_err, grad_err, ftol, gtol):
        ok = fwd_err <= ftol and (grad_err is None or grad_err <= gtol)
        rows.append({
            "surface": surface, "geometry": geometry,
            "dtype": np.dtype(dtype).name,
            "fwd_err": fwd_err, "grad_err": grad_err,
            "ftol": ftol, "gtol": gtol, "ok": ok,
        })

    # ---- fused elementwise blocks (dispatcher fused vs off) ---------- #
    for R, D in ((1024, 768), (8192, 1024), (26, 96)):
        for dtype, ftol in ((jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)):
            if dtype == jnp.bfloat16 and (R, D) != (1024, 768):
                continue
            x = _rand((R, D), dtype, 0)
            w = _rand((D,), jnp.float32, 1) * 0.1 + 1.0
            b = _rand((D,), jnp.float32, 2) * 0.1
            ln = lambda x, w, b: fused_blocks.layer_norm(x, w, b, 1e-5)
            loss = lambda *a: jnp.sum(ln(*a).astype(jnp.float32) ** 2)
            ref = ln(x, w, b)
            g_r = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
            with kernel_config.override(mode="fused"):
                out = ln(x, w, b)
                g_f = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
            gerr = max(_err(a, b_) for a, b_ in zip(g_f, g_r))
            record("fused_layer_norm", (R, D), dtype, _err(out, ref), gerr,
                   ftol, ftol * 20)

    for R, D in ((2048, 1024),):
        x = _rand((R, D), jnp.float32, 0)
        r = _rand((R, D), jnp.float32, 3)
        w = _rand((D,), jnp.float32, 1) * 0.1 + 1.0
        b = _rand((D,), jnp.float32, 2) * 0.1
        aln = lambda x, r, w, b: fused_blocks.add_layer_norm(x, r, w, b,
                                                             1e-12)
        ref = aln(x, r, w, b)
        g_r = jax.grad(lambda *a: jnp.sum(aln(*a) ** 2),
                       argnums=(0, 1, 2, 3))(x, r, w, b)
        with kernel_config.override(mode="fused"):
            out = aln(x, r, w, b)
            g_f = jax.grad(lambda *a: jnp.sum(aln(*a) ** 2),
                           argnums=(0, 1, 2, 3))(x, r, w, b)
        gerr = max(_err(a, b_) for a, b_ in zip(g_f, g_r))
        record("fused_add_layer_norm", (R, D), jnp.float32, _err(out, ref),
               gerr, 1e-5, 2e-4)

    for approximate in (True, False):
        R, D = (4096, 1536)
        x = _rand((R, D), jnp.float32, 0) * 2.0
        b = _rand((D,), jnp.float32, 1)
        bg = lambda x, b: fused_blocks.bias_gelu(x, b, approximate)
        ref = bg(x, b)
        g_r = jax.grad(lambda *a: jnp.sum(bg(*a) ** 2), argnums=(0, 1))(x, b)
        with kernel_config.override(mode="fused"):
            out = bg(x, b)
            g_f = jax.grad(lambda *a: jnp.sum(bg(*a) ** 2),
                           argnums=(0, 1))(x, b)
        gerr = max(_err(a, b_) for a, b_ in zip(g_f, g_r))
        record(f"fused_bias_gelu[approx={approximate}]", (R, D),
               jnp.float32, _err(out, ref), gerr, 1e-5, 2e-4)

    # ---- fused Adam -------------------------------------------------- #
    from deeperspeed_tpu.ops.adam import FusedAdam

    for shape in ((512, 2048), (50304, 8), (768,)):
        kw = dict(lr=1e-2, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.01)
        opt_x = FusedAdam(use_pallas=False, **kw)
        opt_p = FusedAdam(use_pallas=True, **kw)
        pa = {"p": _rand(shape, jnp.float32, 0)}
        pb = {"p": pa["p"]}
        sa, sb = opt_x.init(pa), opt_p.init(pb)
        err = 0.0
        for step in range(3):
            g = {"p": _rand(shape, jnp.float32, 10 + step)}
            pa, sa = opt_x.update(g, sa, pa)
            pb, sb = opt_p.update(g, sb, pb)
            err = max(err, _err(pb["p"], pa["p"]))
        record("fused_adam", shape, jnp.float32, err, None, 1e-6, None)

    # ---- dense super-tile flash -------------------------------------- #
    def ref_bhsd(q, k, v, causal):
        dh = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / np.sqrt(dh)
        if causal:
            mask = np.tril(np.ones((q.shape[2], k.shape[2]), bool))
            s = jnp.where(mask[None, None], s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                          v.astype(jnp.float32))

    st_geoms = [((2, 2, 64, 16), True, True), ((8, 2, 128, 64), True, True),
                ((4, 4, 96, 32), False, True)]
    if not quick:
        # the BERT seq-128 geometry, forward only (256 groups
        # of (512, 512) scores in interpret mode; grads would double it)
        st_geoms.append(((64, 16, 128, 64), False, False))
    for shape, causal, with_grad in st_geoms:
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)
        st = lambda q, k, v: flash_attention_supertile_bhsd(
            q, k, v, causal=causal, interpret=True)
        rf = lambda q, k, v: ref_bhsd(q, k, v, causal)
        ferr = _err(st(q, k, v), rf(q, k, v))
        gerr = _grad_err(st, rf, (q, k, v)) if with_grad else None
        record(f"supertile[causal={causal}]", shape, jnp.float32, ferr,
               gerr, 2e-3, 5e-3)

    # ---- ragged-block streaming flash -------------------------------- #
    for shape, blocks in (((1, 200, 2, 32), (128, 128)),
                          ((1, 328, 2, 32), (128, 128))):
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)
        fa = lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True,
            block_q=blocks[0], block_k=blocks[1])
        t = lambda x: x.transpose(0, 2, 1, 3)
        rf = lambda q, k, v: t(ref_bhsd(t(q), t(k), t(v), True))
        ferr = _err(fa(q, k, v), rf(q, k, v))
        gerr = _grad_err(fa, rf, (q, k, v))
        record(f"ragged_flash[bq={blocks[0]},bk={blocks[1]}]", shape,
               jnp.float32, ferr, gerr, 2e-3, 5e-3)

    failures = [r for r in rows if not r["ok"]]
    return rows, failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the full bert128 super-tile geometry")
    args = ap.parse_args()
    rows, failures = run_sweep(quick=args.quick)

    hdr = (f"{'surface':<34} {'geometry':<20} {'dtype':<9} "
           f"{'fwd max-rel-err':<16} {'grad max-rel-err':<17} ok")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        gerr = "-" if r["grad_err"] is None else f"{r['grad_err']:.2e}"
        print(f"{r['surface']:<34} {str(r['geometry']):<20} "
              f"{r['dtype']:<9} {r['fwd_err']:<16.2e} {gerr:<17} "
              f"{'PASS' if r['ok'] else 'FAIL'}")
    if failures:
        print(f"\n{len(failures)} geometry(ies) out of tolerance")
        return 1
    print(f"\nall {len(rows)} geometries within tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
