"""Two optimizer steps of a reference model, one layer and one block of
rows at a time, so that a 1.4-billion-parameter model in float32 fits
beside its gradient on one 16 GB chip.

Nothing is approximated: the forward keeps each layer's input, the
backward walks the layers in reverse and differentiates one layer at a
time (``jax.vjp``), gradients of all blocks are summed and divided by the
number of scored positions, then clipped by their global norm and handed
to the optimizer as published (Adam: Kingma & Ba 2015; LAMB: You et al.
2020, the trust ratio taken over each stored tensor).

With a mesh the rows of a block are spread over its devices and the
parameters replicated; the compiler sums the gradients across chips.
"""

import functools
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .init import round_to


def _at(tree, l):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False), tree)


@functools.partial(jax.jit, donate_argnums=0)
def _scale_tree(tree, factor):
    return jax.tree.map(lambda a: a * factor, tree)


def _outer(params):
    return {k: v for k, v in params.items() if k != "layers"}


class Layerwise:
    """Loss and gradient of ``model = (embed, layer, head_loss, _)`` over
    a global batch, in blocks of ``rows_per_block`` rows."""

    def __init__(self, model, n_layer: int, rows_per_block: int, mesh=None):
        self.embed, self.layer, self.head_loss = model[:3]
        self.L, self.R, self.mesh = n_layer, rows_per_block, mesh
        rep = self._rep

        @jax.jit
        def fwd_embed(outer, batch):
            return self.embed(outer, batch)

        @jax.jit
        def fwd_layer(layers, l, x):
            return self.layer(_at(layers, l), x)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def bwd_layer(layers, g_layers, l, x, dx):
            _, vjp = jax.vjp(self.layer, _at(layers, l), x)
            dp, dx_prev = vjp(dx)
            g_layers = jax.tree.map(
                lambda g, d: jax.lax.dynamic_update_index_in_dim(
                    g, jax.lax.dynamic_index_in_dim(g, l, 0, keepdims=False) + d,
                    l, 0), g_layers, dp)
            return rep(g_layers), dx_prev

        @functools.partial(jax.jit, donate_argnums=(1,))
        def head_step(outer, g_outer, x_last, batch):
            (total, count), vjp = jax.vjp(
                lambda o, x: self.head_loss(o, x, batch), outer, x_last)
            d_outer, dx = vjp((jnp.float32(1.0), jnp.float32(0.0)))
            return total, count, rep(jax.tree.map(jnp.add, g_outer, d_outer)), dx

        @functools.partial(jax.jit, donate_argnums=(1,))
        def embed_step(outer, g_outer, batch, dx0):
            _, vjp = jax.vjp(lambda o: self.embed(o, batch), outer)
            (d_outer,) = vjp(dx0)
            return rep(jax.tree.map(jnp.add, g_outer, d_outer))

        self._fwd_embed, self._fwd_layer = fwd_embed, fwd_layer
        self._bwd_layer, self._head_step, self._embed_step = \
            bwd_layer, head_step, embed_step

    def _rep(self, tree):
        if self.mesh is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.lax.with_sharding_constraint(tree, NamedSharding(self.mesh, P()))

    def place_rows(self, block):
        if self.mesh is None:
            return tuple(jnp.asarray(b) for b in block)
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(self.mesh, P(self.mesh.axis_names[0]))
        return tuple(jax.device_put(b, sh) for b in block)

    def forward(self, params, block) -> jnp.ndarray:
        """Final hidden states of one block of rows (no gradient)."""
        x = self._fwd_embed(_outer(params), block)
        for l in range(self.L):
            x = self._fwd_layer(params["layers"], jnp.int32(l), x)
        return x

    def loss_and_grad(self, params, batch: Sequence[np.ndarray]):
        """(mean loss, gradient of it as a pytree like params)."""
        outer, layers = _outer(params), params["layers"]
        g_outer = jax.tree.map(jnp.zeros_like, outer)
        g_layers = jax.tree.map(jnp.zeros_like, layers)
        total = count = 0.0
        rows = batch[0].shape[0]
        for r in range(0, rows, self.R):
            block = self.place_rows(tuple(b[r:r + self.R] for b in batch))
            xs = [self._fwd_embed(outer, block)]
            for l in range(self.L):
                xs.append(self._fwd_layer(layers, jnp.int32(l), xs[-1]))
            t, c, g_outer, dx = self._head_step(outer, g_outer, xs.pop(), block)
            total, count = total + float(t), count + float(c)
            for l in reversed(range(self.L)):
                g_layers, dx = self._bwd_layer(layers, g_layers, jnp.int32(l),
                                               xs.pop(), dx)
            g_outer = self._embed_step(outer, g_outer, block, dx)
        grads = dict(g_outer, layers=g_layers)
        return total / count, _scale_tree(grads, jnp.float32(1.0 / count))


# ------------------------------------------------------------------ #
# optimizers, as published, one stored tensor at a time
# ------------------------------------------------------------------ #


def leaf_norms(tree) -> Dict[str, float]:
    """Euclidean norm of every leaf, by its path."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.jit(lambda ls: [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                                for a in ls])([a for _, a in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, norms)}


SAMPLE = 65536


def sample_index(seed: int, tree) -> Dict[str, np.ndarray]:
    """For every leaf the same seeded sample of flat positions, so that
    two holders of a tensor can compare it entry by entry without either
    handing the other the whole of it."""
    from ..generator import rng_for

    rng = rng_for(seed, 6)
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for p, a in flat:
        n = int(np.prod(a.shape))
        out[jax.tree_util.keystr(p)] = (
            np.arange(n, dtype=np.int32) if n <= SAMPLE
            else np.sort(rng.choice(n, SAMPLE, replace=False)).astype(np.int32))
    return out


@jax.jit
def _take(a, i):
    return a.reshape(-1)[i].astype(jnp.float32)


def sample_leaves(tree, index: Dict[str, np.ndarray], scale: float = 1.0):
    """The sampled entries of every leaf, as float32 on the host."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p):
            np.asarray(_take(a, index[jax.tree_util.keystr(p)])) * np.float32(scale)
            for p, a in flat}


def worst_leaf_difference(prog: Dict[str, np.ndarray],
                          ref: Dict[str, np.ndarray], sizes: Dict[str, int]) -> dict:
    """The widest norm of (program - reference) over a leaf's sampled
    entries, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (sample norms scaled to whole leaves)."""
    up = {k: np.sqrt(sizes[k] / len(ref[k])) for k in ref}
    norms = {k: float(np.linalg.norm(ref[k]) * up[k]) for k in ref}
    med = float(np.median(list(norms.values())))
    gaps = {k: float(np.linalg.norm(prog[k] - ref[k]) * up[k]) / max(norms[k], med)
            for k in ref}
    k = max(gaps, key=gaps.get)
    return {"gap": gaps[k], "leaf": k, "reference": norms[k]}


def clip_by_global_norm(grads, max_norm: float):
    """The gradient as the optimizer gets it, and its norm before."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    if not max_norm:
        return grads, float(gnorm)
    return _scale_tree(grads, jnp.minimum(1.0, max_norm / (gnorm + 1e-6))), float(gnorm)


def make_update(opt: dict, store_dtype=jnp.float32) -> Callable:
    """``update(p, g, g_first, step) -> p'`` for one stored tensor. The
    arithmetic is float32; the result is rounded to ``store_dtype``, the
    type the configuration keeps its weights in (and held as float32).

    Two steps need no state carried beside the first step's gradient:
    after step one the moments are (1-b1)*g1 and (1-b2)*g1**2. ``step``
    is 1 (``g_first`` ignored) or 2."""
    b1, b2 = opt["betas"]
    eps, lr, wd = opt["eps"], opt["lr"], opt.get("weight_decay", 0.0)
    kind = opt["type"].lower()
    if kind not in ("adam", "lamb"):
        raise ValueError(f"no reference optimizer {opt['type']!r}")

    @functools.partial(jax.jit, static_argnums=3, donate_argnums=0)
    def update(p, g, g_first, step):
        if step == 1:
            m, v = (1 - b1) * g, (1 - b2) * g * g
        else:
            m = b1 * (1 - b1) * g_first + (1 - b1) * g
            v = b2 * (1 - b2) * g_first * g_first + (1 - b2) * g * g
        upd = (m / (1 - b1 ** step)) / (jnp.sqrt(v / (1 - b2 ** step)) + eps)
        if wd:
            upd = upd + wd * p
        if kind == "lamb":
            wn, un = jnp.sqrt(jnp.sum(p * p)), jnp.sqrt(jnp.sum(upd * upd))
            ratio = jnp.where((wn > 0) & (un > 0),
                              jnp.clip(wn / un, opt["min_coeff"], opt["max_coeff"]),
                              1.0)
            upd = ratio * upd
        return round_to(p - lr * upd, store_dtype)

    return update


def two_steps(lw: Layerwise, params, batches: List[tuple], opt: dict,
              clip: float, stash_on_host: bool, say=lambda s: None,
              store_dtype=jnp.float32, index=None) -> dict:
    """Follow the first two optimizer steps. ``params`` (float32) is
    consumed. Returns the losses, the per-leaf norms of the first gradient
    as the optimizer got it, and the parameters after step two."""
    update = make_update(opt, store_dtype)
    loss1, g1 = lw.loss_and_grad(params, batches[0])
    g1, gnorm1 = clip_by_global_norm(g1, clip)
    g1_norms = leaf_norms(g1)
    g1_samples = sample_leaves(g1, index) if index is not None else None
    say(f"reference step 1: loss {loss1:.6f} grad norm {gnorm1:.6f}")
    flat_p, treedef = jax.tree.flatten(params)
    flat_g1 = jax.tree.leaves(g1)
    del params, g1
    flat_p = [update(p, g, g, 1) for p, g in zip(flat_p, flat_g1)]
    if stash_on_host:
        flat_g1 = [np.asarray(g) for g in flat_g1]
    params = treedef.unflatten(flat_p)
    loss2, g2 = lw.loss_and_grad(params, batches[1])
    g2, gnorm2 = clip_by_global_norm(g2, clip)
    say(f"reference step 2: loss {loss2:.6f} grad norm {gnorm2:.6f}")
    flat_g2 = jax.tree.leaves(g2)
    del params, g2
    out = []
    for i in range(len(flat_p)):
        g_first = jnp.asarray(flat_g1[i])
        out.append(update(flat_p[i], flat_g2[i], g_first, 2))
        flat_p[i] = flat_g2[i] = flat_g1[i] = None
    return {"losses": [loss1, loss2], "grad_norm": gnorm1,
            "first_grad_norms": g1_norms, "first_grad_samples": g1_samples,
            "params": treedef.unflatten(out)}


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float]) -> dict:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    med = float(np.median(list(ref.values())))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in ref}
    k = max(gaps, key=gaps.get)
    return {"gap": gaps[k], "leaf": k, "program": prog[k], "reference": ref[k]}
