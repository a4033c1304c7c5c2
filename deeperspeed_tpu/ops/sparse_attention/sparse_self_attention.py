"""SparseSelfAttention module.

API parity with /root/reference/deepspeed/ops/sparse_attention/
sparse_self_attention.py:14 — (B, H, S, Dh) q/k/v in, dense context out,
per-seq-len cached ops — redesigned over the Pallas block-sparse flash kernel
(kernels.py) instead of triton sdd/softmax/dsd triple launches. The master
layout is built once at max_seq_length and sliced per actual sequence length,
exactly like the reference's master_layout buffer.
"""

import math
from typing import Optional

import jax.numpy as jnp
import numpy as np

from .. import kernel_config
from .kernels import (
    block_sparse_attention_xla,
    make_block_sparse_attention,
)
from .sparsity_config import SparsityConfig


def _pallas_ok(block: int, Dh: int) -> bool:
    if not kernel_config.on_tpu():
        return False
    # the block-sparse kernels have no shard_map wrapper, and XLA cannot
    # partition a Mosaic kernel: under a multi-device mesh 'auto' is XLA
    if kernel_config.active_mesh() is not None:
        return False
    # Mosaic lane rule: the lse/delta outputs carry (1, 1, block) tiles, so
    # the sparsity block must be a lane multiple (128) on hardware — %8
    # alone compiles in interpret mode but fails Mosaic lowering
    return block % 128 == 0 and Dh % 8 == 0


class SparseSelfAttention:
    """Block-sparse self attention with a pluggable SparsityConfig.

    Call with query/key/value of shape (B, num_heads, S, head_dim) (the
    reference's convention). ``causal`` defaults to True when the sparsity
    config's attention mode is 'unidirectional'.
    """

    def __init__(self, sparsity_config: Optional[SparsityConfig] = None,
                 max_seq_length: int = 2048, causal: Optional[bool] = None,
                 impl: str = "auto"):
        self.sparsity_config = sparsity_config or SparsityConfig(num_heads=4)
        if not hasattr(self.sparsity_config, "make_layout"):
            raise TypeError("sparsity_config must provide make_layout()")
        self.max_seq_length = max_seq_length
        self.master_layout = np.asarray(self.sparsity_config.make_layout(max_seq_length))
        if causal is None:
            causal = getattr(self.sparsity_config, "attention", None) == "unidirectional"
        self.causal = causal
        assert impl in ("auto", "pallas", "pallas_interpret", "xla"), impl
        self.impl = impl
        self._ops = {}  # per-seq-len compiled attention (reference ops cache)

    def get_layout(self, L: int) -> np.ndarray:
        if L % self.sparsity_config.block != 0:
            raise ValueError(
                f"Sequence Length, {L}, needs to be divisible by Block size "
                f"{self.sparsity_config.block}!"
            )
        nb = L // self.sparsity_config.block
        return self.master_layout[..., :nb, :nb]

    def _get_op(self, L: int, Dh: int):
        key = (L, Dh)
        if key not in self._ops:
            layout = self.get_layout(L)
            block = self.sparsity_config.block
            impl = self.impl
            if impl == "auto":
                impl = "pallas" if _pallas_ok(block, Dh) else "xla"
            if impl in ("pallas", "pallas_interpret"):
                self._ops[key] = make_block_sparse_attention(
                    layout, block, causal=self.causal,
                    interpret=(impl == "pallas_interpret"),
                )
            else:
                def xla_op(q, k, v, _layout=layout, _block=block):
                    return block_sparse_attention_xla(
                        q, k, v, _layout, _block, causal=self.causal
                    )

                self._ops[key] = xla_op
        return self._ops[key]

    def __call__(self, query, key, value, key_padding_mask=None):
        """query/key/value: (B, H, S, Dh). key_padding_mask: (B, S) additive
        float mask (0 keep / -inf drop) applied pre-softmax, the reference's
        'add' mode."""
        B, H, S, Dh = query.shape
        if query.shape != key.shape or key.shape != value.shape:
            raise NotImplementedError("only self-attention is supported for now")
        if key_padding_mask is not None:
            # fold the padding mask into K by pushing masked keys to -inf via
            # a large negative bias on their scores: implemented by zeroing V
            # and biasing K is fragile — use the XLA path for masked batches
            layout = self.get_layout(S)
            out = block_sparse_attention_xla(
                query.transpose(0, 2, 1, 3), key.transpose(0, 2, 1, 3),
                value.transpose(0, 2, 1, 3), layout,
                self.sparsity_config.block, causal=self.causal,
                key_padding_mask=key_padding_mask,
            )
            return out.transpose(0, 2, 1, 3)
        op = self._get_op(S, Dh)
        # kernels take (B, S, H, Dh)
        out = op(
            query.transpose(0, 2, 1, 3),
            key.transpose(0, 2, 1, 3),
            value.transpose(0, 2, 1, 3),
        )
        return out.transpose(0, 2, 1, 3)

    # reference-compat alias
    forward = __call__


class BertSparseSelfAttention:
    """BERT-style QKV projection + SparseSelfAttention (reference
    bert_sparse_self_attention.py). Functional: init(rng) -> params,
    apply(params, hidden, key_padding_mask)."""

    def __init__(self, hidden_size: int, num_heads: int,
                 sparsity_config: Optional[SparsityConfig] = None,
                 max_seq_length: int = 2048):
        if hidden_size % num_heads:
            raise ValueError(
                f"The hidden size ({hidden_size}) is not a multiple of the "
                f"number of attention heads ({num_heads})"
            )
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.head_dim = hidden_size // num_heads
        self.attn = SparseSelfAttention(
            sparsity_config or SparsityConfig(num_heads=num_heads),
            max_seq_length=max_seq_length,
        )

    def init(self, rng):
        import jax

        ks = jax.random.split(rng, 3)
        D = self.hidden_size
        s = 1.0 / math.sqrt(D)
        return {
            name: {
                "w": jax.random.normal(k, (D, D), jnp.float32) * s,
                "b": jnp.zeros((D,), jnp.float32),
            }
            for name, k in zip(("query", "key", "value"), ks)
        }

    def _split_heads(self, x):
        B, S, _ = x.shape
        return x.reshape(B, S, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)

    def apply(self, params, hidden, key_padding_mask=None):
        q = hidden @ params["query"]["w"] + params["query"]["b"]
        k = hidden @ params["key"]["w"] + params["key"]["b"]
        v = hidden @ params["value"]["w"] + params["value"]["b"]
        ctx = self.attn(
            self._split_heads(q), self._split_heads(k), self._split_heads(v),
            key_padding_mask=key_padding_mask,
        )  # (B, H, S, Dh)
        B, H, S, Dh = ctx.shape
        return ctx.transpose(0, 2, 1, 3).reshape(B, S, H * Dh)
