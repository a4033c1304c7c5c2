"""Placement audit: stable digests of how arrays actually landed.

``jax.debug.visualize_array_sharding`` is great interactively but its
box-drawing output is useless in CI. This module turns committed
shardings into small JSON-able digests so benches and tests can assert
"this layout actually sharded the MLP over tp" instead of eyeballing:

* :func:`spec_digest` — one placed array → ``{"spec", "shape",
  "n_shards", "shard_shape", "viz_sha1"}`` where ``viz_sha1`` hashes the
  visualize_array_sharding rendering (layout changes flip the hash even
  when the spec string happens to match).
* :func:`tree_digest` — a placed pytree → per-leaf digests keyed by
  flattened path.
* :func:`audit_tree` — summary: total/sharded/replicated leaf counts,
  bytes by axis usage — the number ``scripts/mesh_bench.py`` reports
  per layout.
"""

import hashlib
import io
from typing import Any, Dict

import jax
import numpy as np

__all__ = ["spec_digest", "tree_digest", "audit_tree"]


def _viz_sha1(x) -> str:
    """SHA-1 of the visualize_array_sharding rendering (empty on
    failure — some backends can't render >2-D layouts)."""
    try:
        buf = io.StringIO()
        import rich.console

        console = rich.console.Console(file=buf, force_terminal=False,
                                       width=120)
        jax.debug.visualize_array_sharding(
            x.reshape(x.shape[0], -1) if x.ndim > 2 else x,
            use_color=False, console=console)
        return hashlib.sha1(buf.getvalue().encode()).hexdigest()[:12]
    except Exception:
        return ""


def spec_digest(x) -> Dict[str, Any]:
    """Digest of one committed array's placement."""
    sharding = getattr(x, "sharding", None)
    spec = getattr(sharding, "spec", None)
    try:
        n_shards = len(x.addressable_shards)
        shard_shape = list(x.addressable_shards[0].data.shape)
    except Exception:
        n_shards, shard_shape = 1, list(getattr(x, "shape", ()))
    return {
        "spec": str(spec) if spec is not None else "unsharded",
        "shape": list(getattr(x, "shape", ())),
        "n_shards": int(n_shards),
        "shard_shape": shard_shape,
        "viz_sha1": _viz_sha1(x),
    }


def tree_digest(tree) -> Dict[str, Dict[str, Any]]:
    """Per-leaf placement digests keyed by flattened tree path."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): spec_digest(leaf)
            for path, leaf in flat}


def audit_tree(tree, mesh=None) -> Dict[str, Any]:
    """Placement summary for a whole tree (params, optimizer state...).

    ``sharded_bytes`` counts leaves whose committed spec names at least
    one mesh axis; a ZeRO-3 run on an fsdp mesh should show nearly all
    parameter bytes there, a pure-dp run nearly none."""
    leaves = tree_digest(tree)
    total_b = sharded_b = 0
    sharded = replicated = 0
    for d in leaves.values():
        nbytes = int(np.prod(d["shape"], dtype=np.int64)) if d["shape"] else 1
        total_b += nbytes
        if d["n_shards"] > 1 and d["shard_shape"] != d["shape"]:
            sharded += 1
            sharded_b += nbytes
        else:
            replicated += 1
    out = {
        "leaves": len(leaves),
        "sharded_leaves": sharded,
        "replicated_leaves": replicated,
        "total_elems": int(total_b),
        "sharded_elems": int(sharded_b),
        "sharded_frac": round(sharded_b / total_b, 4) if total_b else 0.0,
        "digest": hashlib.sha1(
            "".join(sorted(f"{k}:{v['spec']}:{v['shard_shape']}"
                           for k, v in leaves.items())).encode()
        ).hexdigest()[:12],
    }
    if mesh is not None:
        from .mesh import describe

        out["mesh"] = describe(mesh)
    return out
