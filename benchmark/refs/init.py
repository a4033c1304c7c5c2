"""Weights from a seed, one stored tensor at a time, so that any single
tensor can be made again later (to measure how far training moved it)
without a second copy of the model."""

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    """How one tensor starts: normal(0, std), or a constant (std None)."""
    shape: Tuple[int, ...]
    std: float = None
    const: float = 0.0


def round_to(x, dtype):
    """float32 values rounded to those ``dtype`` can hold. A bare
    ``astype`` there and back may be dropped by the compiler (it is
    allowed to keep excess precision); ``reduce_precision`` may not."""
    fi = jnp.finfo(dtype)
    if fi.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp, mantissa_bits=fi.nmant)


def seed_key(seed: int):
    """A key from any whole-number seed (beyond 32 bits too)."""
    k = jax.random.PRNGKey(np.uint32(int(seed) % 2**32))
    return jax.random.fold_in(k, int(seed) // 2**32)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, spec: Spec, index: int, dtype):
    if spec.std is None:
        return jnp.full(spec.shape, spec.const, dtype)
    k = jax.random.fold_in(key, index)
    return round_to(jax.random.normal(k, spec.shape, jnp.float32) * spec.std,
                    dtype).astype(dtype)


def init_leaves(seed: int, specs, dtype):
    """[(path, tensor)] in the tree's own order."""
    key = seed_key(seed)
    flat, _ = jax.tree_util.tree_flatten_with_path(specs)
    return [(jax.tree_util.keystr(p), _make(key, s, i, dtype))
            for i, (p, s) in enumerate(flat)]


def init_tree(seed: int, specs, dtype=jnp.float32):
    """The whole pytree, every tensor rounded once to ``dtype``."""
    _, treedef = jax.tree.flatten(specs)
    return treedef.unflatten([a for _, a in init_leaves(seed, specs, dtype)])


def moved_norms(seed: int, specs, dtype, now) -> dict:
    """Norm of (now - start) for every tensor, by path; each start value
    is made again from the seed and dropped at once."""
    key = seed_key(seed)
    flat, _ = jax.tree_util.tree_flatten_with_path(specs)
    leaves = jax.tree.leaves(now)

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def one(key, a, spec, index):
        d = a.astype(jnp.float32) - _make(key, spec, index, dtype).astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(d)))

    return {jax.tree_util.keystr(p): float(one(key, a, s, i))
            for i, ((p, s), a) in enumerate(zip(flat, leaves))}
