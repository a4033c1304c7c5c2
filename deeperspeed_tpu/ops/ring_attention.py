"""Context / sequence parallelism: ring attention + Ulysses all-to-all.

The reference (v0.3.15) has NO distributed long-sequence strategy — its
long-context story is block-sparse attention (SURVEY §2.3 'SP' row, §5).
A TPU-native rebuild treats sequence parallelism as first-class: sequences
are sharded over the ``'seq'`` mesh axis and attention runs distributed.

Two strategies (both standard in modern practice):

  * **Ring attention** (`ring_attention`): K/V chunks rotate around the seq
    axis via ``lax.ppermute`` while each device keeps its Q chunk, combining
    per-chunk results with the flash-attention online-softmax recurrence.
    Comm rides the ICI ring; memory is O(S/P) per device. Causal masking is
    chunk-granular: a K chunk strictly older than the local Q chunk needs no
    mask, the diagonal chunk gets the triangular mask, strictly newer chunks
    are skipped (their contribution multiplies to zero).
  * **Ulysses** (`ulysses_attention`): ``all_to_all`` re-shards from
    sequence-sharded to head-sharded, runs ordinary (flash) attention on
    full-length sequences locally, and all_to_all's back. Cheaper at modest
    sequence lengths when heads >= seq axis size.

Both are written against a bare ``axis_name`` so they compose with any mesh;
``make_context_parallel_attention`` wraps them in ``shard_map`` for use on
global (B, S, H, Dh) arrays inside pjit-ted training steps.
"""

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.topology import DATA_AXIS, MODEL_AXIS, SEQ_AXIS

_NEG = -1e30  # finite -inf: keeps the online-softmax free of NaNs on
              # fully-masked (future) chunks


def _chunk_attend(q, k, v, o, l, m, mask):
    """One online-softmax accumulation step.

    q (B,Sq,H,D); k,v (B,Sk,H,D); o (B,Sq,H,D) f32; l,m (B,H,Sq) f32;
    mask None | (Sq,Sk) bool."""
    dh = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    s = s / math.sqrt(dh)
    if mask is not None:
        s = jnp.where(mask[None, None, :, :], s, _NEG)
    m_chunk = jnp.max(s, axis=-1)  # (B,H,Sq)
    m_new = jnp.maximum(m, m_chunk)
    p = jnp.exp(s - m_new[..., None])
    # rows where everything so far (incl. this chunk) is masked: m_new == _NEG
    p = jnp.where((m_new == _NEG)[..., None], 0.0, p)
    corr = jnp.exp(m - m_new)
    corr = jnp.where(m == _NEG, 0.0, corr)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v).astype(jnp.float32)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return o_new, l_new, m_new


def ring_attention(q, k, v, axis_name: str = SEQ_AXIS, causal: bool = True):
    """Distributed attention over sequence chunks; call inside shard_map.

    q,k,v: LOCAL chunks (B, S_local, H, Dh), sequence sharded in order over
    `axis_name`. Returns the local output chunk.
    """
    p_size = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, Sq, H, Dh = q.shape
    o = jnp.zeros(q.shape, jnp.float32)
    l = jnp.zeros((B, H, Sq), jnp.float32)
    m = jnp.full((B, H, Sq), _NEG, jnp.float32)
    # each step: attend local q to the k/v chunk currently resident, then
    # rotate k/v one hop along the ring (device d -> d+1), so after t steps we
    # hold the chunk originally owned by (my - t) mod p
    perm = [(i, (i + 1) % p_size) for i in range(p_size)]
    tri = jnp.tril(jnp.ones((Sq, Sq), bool)) if causal else None

    def body(t, carry):
        o, l, m, k, v = carry
        src = (my - t) % p_size
        if causal:
            # src < my: fully visible; src == my: diagonal (causal mask);
            # src > my: fully masked (handled by _NEG scores)
            full = jnp.ones((Sq, Sq), bool)
            none = jnp.zeros((Sq, Sq), bool)
            mask = jnp.where(src == my, tri, jnp.where(src < my, full, none))
        else:
            mask = None
        o, l, m = _chunk_attend(q, k, v, o, l, m, mask)
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        return o, l, m, k, v

    o, l, m, k, v = jax.lax.fori_loop(0, p_size, body, (o, l, m, k, v))
    l = jnp.maximum(l, 1e-30)  # fully-masked rows (none for causal q>=1 chunk)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = SEQ_AXIS, causal: bool = True,
                      attn_fn=None):
    """DeepSpeed-Ulysses-style all-to-all sequence parallelism.

    Local chunks (B, S/P, H, Dh) -> all_to_all -> (B, S, H/P, Dh) -> local
    attention over the FULL sequence -> all_to_all back. Head count must be
    divisible by the axis size.
    """
    p_size = jax.lax.psum(1, axis_name)
    B, Sl, H, Dh = q.shape

    def to_heads(x):
        # split heads (axis 2) across devices, gather sequence (axis 1)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)  # (B, S, H/P, Dh)
    if attn_fn is None:
        o = _local_causal_attention(qh, kh, vh, causal)
    else:
        o = attn_fn(qh, kh, vh)
    return to_seq(o)


def _local_causal_attention(q, k, v, causal: bool):
    """Per-device attention for the Ulysses path: flash (Pallas) when the
    shapes/platform allow, else the dense XLA fallback."""
    from .pallas.flash_attention import flash_attention, is_available

    if causal and is_available(q):
        return flash_attention(q, k, v, causal=True)
    dh = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(dh)
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def make_context_parallel_attention(
    mesh: Mesh,
    strategy: str = "ring",
    causal: bool = True,
    batch_axis: Optional[str] = DATA_AXIS,
    head_axis: Optional[str] = MODEL_AXIS,
    seq_axis: str = SEQ_AXIS,
):
    """Wrap ring/ulysses attention in shard_map over `mesh` for GLOBAL
    (B, S, H, Dh) arrays: batch sharded over `batch_axis`, sequence over
    `seq_axis`, heads over `head_axis` (TP). Returns fn(q, k, v) -> out.

    Axis names resolve through the sharding rule table, so the legacy
    defaults (``data``/``model``/``seq``) bind to a canonical
    dp×fsdp×tp×sp mesh's ``sp`` axis (and vice versa)."""
    assert strategy in ("ring", "ulysses"), strategy
    from ..sharding.rules import translate_spec

    spec = translate_spec(P(batch_axis, seq_axis, head_axis, None), mesh)
    resolved_seq = tuple(spec)[1]
    if resolved_seq is None:
        # Refuse rather than silently running dense full-sequence attention:
        # a user who asked for context parallelism must get it (or an error).
        raise ValueError(
            f"{strategy} attention needs a mesh with a '{seq_axis}' (or "
            f"'sp') axis of size > 1; got mesh axes {dict(mesh.shape)}"
        )
    inner = ring_attention if strategy == "ring" else ulysses_attention

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    def attend(q, k, v):
        return inner(q, k, v, axis_name=resolved_seq, causal=causal)

    return attend
