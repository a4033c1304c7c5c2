"""GPT / GPT-NeoX decoder-only transformer, TPU-native.

This is the flagship model family the reference framework was built to train
(GPT-NeeoX used DeeperSpeed's PipelineModule + Megatron mpu; see SURVEY §1).
Design is jax-first rather than a port:

  * params are a plain pytree with per-layer tensors STACKED on a leading
    layer axis, so the forward is a `lax.scan` over layers — this is what
    makes ZeRO-3 parameter gathering per-layer (XLA all-gathers each layer's
    slice inside the scan, the analog of stage3's fetch/release hooks) and
    keeps compile time O(1) in depth.
  * `jax.checkpoint` (remat) per scan step == activation checkpointing with
    checkpoint_interval=1 (reference activation_checkpointing/checkpointing.py).
  * tensor parallelism is a PartitionSpec pytree over the 'model' axis
    (attention heads / ffn columns), the native replacement for the external
    Megatron mpu the reference consumed (engine.py:630-641).
  * sequence-axis sharding constraints give context-parallel long-sequence
    training over the 'seq' mesh axis.

Supports GPT-2 (learned positions, serial residual) and GPT-NeoX (rotary,
parallel attention+MLP residual) variants.
"""

import dataclasses
import math
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..parallel.topology import DATA_AXIS, MODEL_AXIS, SEQ_AXIS
from ..utils import hooks


@dataclasses.dataclass(frozen=True)
class SparseAttnConfig:
    """The constants of an InfLLM-v2 block-sparse attention layer (the
    ``minicpm4`` mixer): keys are mean-pooled over windows of
    ``kernel_size`` every ``kernel_stride`` tokens, a query scores the
    pooled keys, and attends over ``topk`` blocks of ``block_size`` tokens
    (the first ``init_blocks`` and the ``window_size`` tokens' blocks that
    end at its own among them). A query that sees ``dense_len`` tokens or
    fewer attends to all of them."""
    block_size: int = 64
    topk: int = 64
    kernel_size: int = 32
    kernel_stride: int = 16
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        if self.block_size % self.kernel_stride \
                or self.kernel_size != 2 * self.kernel_stride \
                or self.kernel_size > self.block_size:
            raise ValueError(
                "sparse attention: block_size must be a multiple of "
                "kernel_stride and kernel_size twice kernel_stride "
                f"(got {self})")
        if self.window_size % self.block_size \
                or self.dense_len % self.block_size:
            raise ValueError(
                "sparse attention: window_size and dense_len must be "
                f"multiples of block_size (got {self})")
        if self.init_blocks + self.local_blocks > self.topk:
            raise ValueError(
                f"sparse attention: topk ({self.topk}) is less than the "
                f"forced blocks ({self.init_blocks} + {self.local_blocks})")

    @property
    def local_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def windows_per_block(self) -> int:
        """Pooled keys whose window STARTS in one block."""
        return self.block_size // self.kernel_stride

    @property
    def list_blocks(self) -> int:
        """Width of a query's page list: the selection, or every block of
        a context the dense rule still covers."""
        return max(self.topk, self.dense_len // self.block_size)


@dataclasses.dataclass(frozen=True)
class MambaAttnConfig:
    """The constants of a ``mamba_attn`` layer (Falcon-H1's): the sizes of
    its Mamba-2 state-space branch and the muP multiplier of every branch.
    The branch has ``n_heads`` heads of ``head_dim`` channels, each with a
    state of ``head_dim x d_state`` floats; B and C come in ``n_groups``
    groups of ``d_state``; a depthwise causal convolution ``d_conv`` wide
    runs over x, B and C before the recurrence; a prompt chunk computes
    the recurrence chunkwise (SSD) in blocks of ``chunk`` positions.
    ``ssm_mult`` scales the five segments of the input projection, z, x,
    B, C and dt in that order."""
    n_heads: int = 32
    head_dim: int = 128
    d_state: int = 256
    n_groups: int = 2
    d_conv: int = 4
    chunk: int = 128
    ssm_in: float = 1.0
    ssm_mult: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out: float = 1.0
    attn_in: float = 1.0
    attn_out: float = 1.0
    key: float = 1.0
    mlp_gate: float = 1.0
    mlp_out: float = 1.0

    def __post_init__(self):
        if self.n_heads % self.n_groups or len(self.ssm_mult) != 5:
            raise ValueError(
                "mamba_attn: n_heads must be a multiple of n_groups and "
                f"ssm_mult name five segments (got {self})")

    @property
    def d_ssm(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, then B, then C."""
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def proj_dim(self) -> int:
        """Width of the input projection: z, the convolved channels, dt."""
        return self.d_ssm + self.conv_dim + self.n_heads


@dataclasses.dataclass(frozen=True)
class EvaAttnConfig:
    """The constants of an ``eva`` layer (EVA attention as EvaByte applies
    it): a query attends to the exact keys of its own aligned window of
    ``window`` positions, and to ONE pooled key and value for every
    ``chunk`` positions of the windows before it, in one softmax. The
    pooling weights are learned, a pair of vectors a head."""
    window: int = 2048
    chunk: int = 16

    def __post_init__(self):
        if self.chunk < 1 or self.window % self.chunk:
            raise ValueError(
                f"eva: window must be a multiple of chunk (got {self})")

    @property
    def summaries(self) -> int:
        """Pooled keys a window leaves behind."""
        return self.window // self.chunk


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """The rotary constants of one kind of layer: ``theta`` and, where
    ``factor`` is not 1, YaRN's (Peng et al. 2023) as a config states
    them: pair ``j`` of ``head_dim / 2`` turns at ``theta^(-2j/head_dim)``
    below the pair ``low`` (``beta_fast`` turns over the
    ``original_positions``), at that over ``factor`` from the pair ``high``
    on (``beta_slow`` turns), a straight line between; cos and sin are
    BOTH times ``attention_factor``, so a score carries its square. The
    frequencies are static: the same at every length."""
    theta: float = 10000.0
    factor: float = 1.0
    original_positions: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self, head_dim: int):
        """The ``head_dim / 2`` inverse frequencies, float32 (numpy: a
        constant of the program)."""
        import numpy as np

        half = head_dim // 2
        base = self.theta ** (-2.0 * np.arange(half, dtype=np.float64)
                              / head_dim)
        if self.factor == 1.0:
            return base.astype(np.float32)

        def pair(turns):     # the pair that makes ``turns`` turns
            return (head_dim * math.log(self.original_positions
                                        / (turns * 2 * math.pi))
                    / (2 * math.log(self.theta)))

        low = max(math.floor(pair(self.beta_fast)), 0)
        high = min(math.ceil(pair(self.beta_slow)), head_dim - 1)
        ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
        return ((1 - ramp) * base + ramp * base / self.factor).astype(
            np.float32)


@dataclasses.dataclass(frozen=True)
class GroupedAttnConfig:
    """The constants of the ``full_attn`` and ``window_attn`` layers:
    grouped-query softmax attention, RMSNorm, no bias. A ``full_attn``
    layer keeps every key; a ``window_attn`` layer's query at position
    ``i`` sees the keys ``i - window < j <= i`` and the layer keeps the
    last ``window`` keys alone (a ring a slot). Each kind turns q and k by
    its own rotary constants; ``qk_norm``: q and k pass an RMSNorm over
    each head's entries (one learned vector each a layer) before that.
    ``rotary`` False: q and k are not turned at all (a stack whose other
    layers carry the position); ``out_gate``: the heads' output times
    ``sigmoid(W_g m)`` entry by entry before the projection out;
    ``sandwich``: each sublayer's OUTPUT passes an RMSNorm of its own
    before it joins the stream (``x + N1'(Attn(N1(x)))``, ``x +
    N2'(FFN(N2(x)))``: four norms a layer, weights ``ln1_post``,
    ``ln2_post``)."""
    window: int = 1024
    qk_norm: bool = True
    full_rope: RopeScaling = RopeScaling()
    window_rope: RopeScaling = RopeScaling()
    rotary: bool = True
    out_gate: bool = False
    sandwich: bool = False

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"window_attn: window >= 1 (got {self})")

    def rope(self, kind: str) -> RopeScaling:
        return self.window_rope if kind == "window_attn" else self.full_rope


@dataclasses.dataclass(frozen=True)
class KdaConfig:
    """The constants of a ``kda`` layer (Kimi Delta Attention, a gated
    delta rule with a decay for every CHANNEL): ``n_heads`` heads, keys of
    ``head_k`` and values of ``head_v`` entries, a state of ``head_k x
    head_v`` float32 a head; q, k and v each pass a depthwise causal
    convolution ``d_conv`` wide and a SiLU; the decay and the output gate
    come through low-rank pairs ``d_model -> low_rank -> n_heads x head``;
    ``beta = beta_scale sigmoid(.)`` (2 where the model allows a negative
    eigenvalue)."""
    n_heads: int = 64
    head_k: int = 128
    head_v: int = 128
    d_conv: int = 4
    low_rank: int = 128
    beta_scale: float = 2.0

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: q, then k, then v."""
        return self.n_heads * (2 * self.head_k + self.head_v)


# The kinds of layer a stack may hold, by the name of the mixer. The kind
# settles the rest of the layer, so nothing else is configured: what its
# mixer keeps between tokens (its cache), its norm and its feed-forward.
#   attention  gpt.decoder_block: LayerNorm, softmax attention over every
#              key (pages of keys and values), a GeLU feed-forward, biases
#   minicpm4   mixers.mixed_block: RMSNorm, InfLLM-v2 block-sparse attention
#              (pages, and a pooled key per stride for the selector), a
#              gated SiLU feed-forward, no bias
#   lightning  mixers.mixed_block: RMSNorm, decayed linear attention (one
#              float32 state row a slot, no pages), gated SiLU, no bias
#   mamba_attn mixers.mamba_attn_block: RMSNorm, causal attention over every
#              key (pages) AND a Mamba-2 state-space mixer (a float32 state
#              row and a convolution tail a slot) on the same normed input,
#              summed; gated SiLU; a muP multiplier a branch; no bias
#   eva        mixers.eva_block: RMSNorm, softmax attention over the exact
#              keys of the query's own window (pages a slot reuses window
#              after window) and one pooled key and value for every chunk
#              of the windows behind it (pages of summaries), one query a
#              key head; gated SiLU; no bias
#   full_attn  mixers.grouped_attn_block: RMSNorm, grouped-query softmax
#              attention over every key (pages that follow the length),
#              q and k normed a head where the model says so, rotary by
#              the kind's own constants (YaRN among them); no bias
#   window_attn  the same block over the last ``window`` keys alone: a
#              ring of ``window / block_size`` pages a slot, in a pool of
#              its own beside the full layers' (two page rules, one stack)
#   kda        mixers.kda_block: RMSNorm, a gated delta rule with a decay a
#              channel (a float32 state row and three convolution tails a
#              slot, no pages); shares a stack with ``full_attn`` layers
# The feed-forward of every kind but ``attention`` is a VALUE of the
# configuration (``mixers.feed_forward``): dense gated SiLU, or, where
# ``moe_num_experts`` is set, gated SiLU experts routed ``moe_top_k`` a
# token with no token dropped (``moe.gated_experts``).
LAYER_KINDS = ("attention", "minicpm4", "lightning", "mamba_attn", "eva",
               "full_attn", "window_attn", "kda")
# the kinds that share ``mixers.grouped_attn_block`` (and may share a stack)
GROUPED_KINDS = frozenset({"full_attn", "window_attn"})


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    n_layer: int = 12
    n_head: int = 12
    # grouped-query attention: number of K/V heads (0 = n_head = classic
    # MHA; 1 = MQA). Shrinks the qkv projection and the decode KV cache by
    # n_head/n_kv_head; attention repeats K/V heads to match Q
    n_kv_head: int = 0
    d_model: int = 768
    # entries of one attention head; 0 => d_model // n_head
    head_size: int = 0
    d_ff: int = 0  # 0 => 4 * d_model
    max_seq: int = 1024
    rotary: bool = True  # NeoX-style rotary; False => learned positions
    rotary_pct: float = 1.0
    rope_theta: float = 10000.0   # read by the mamba_attn and eva layers
    parallel_residual: bool = True  # NeoX parallel attn+mlp
    layernorm_eps: float = 1e-5
    tie_embeddings: bool = False
    remat: bool = True
    # remat policy: 'full' recomputes everything (min memory); 'flash'
    # additionally saves the flash-attention output+logsumexp so the
    # backward skips re-running the attention forward kernel; 'matmuls'
    # saves flash o/lse + post-rotary q/k/v + pre-gelu ffn — the backward
    # recomputes only layernorms/gelu/residuals (near-zero recompute FLOPs
    # at ~1/2 the no-remat activation memory); 'dots_all' saves every dot
    # output; 'dots' saves only batch-free dots (weight-stationary)
    remat_policy: str = "full"
    dtype: Any = jnp.bfloat16  # compute dtype for activations
    # 'auto' | 'pallas' | 'xla' | 'ring' | 'ulysses' (the last two are the
    # context-parallel paths over the 'seq' mesh axis)
    attn_impl: str = "auto"
    # cross-entropy sequence chunk: the (B, S, V) logits tensor is never
    # materialized; the loss scans over S-chunks of this many tokens,
    # rematerializing each chunk's logits in the backward (softmax - onehot).
    # 0 disables chunking (single fused logits+lse).
    ce_chunk: int = 128
    # Mixture-of-Experts: 0 = dense MLP; >0 replaces every layer's FFN with
    # experts (models/moe.py). In a stack of ``attention`` layers (the model
    # that is trained): GeLU experts with biases, expert-parallel over the
    # 'expert' mesh axis, dispatched as ``moe_dispatch_impl`` says, with
    # the auxiliary losses; a capability BEYOND the reference, which
    # predates DeepSpeed-MoE (SURVEY.md §2.3 lists EP as absent). In a
    # stack of mixed layers (served only): gated SiLU experts of width
    # ``d_ff``, no bias, a float32 softmax over all of them, the
    # ``moe_top_k`` largest a token (renormalised to sum 1 where
    # ``moe_normalize_gates``), every assignment computed whatever the
    # imbalance (``moe.gated_experts``): of the keys below it reads
    # ``moe_num_experts``, ``moe_top_k`` and ``moe_normalize_gates`` alone.
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    moe_z_coef: float = 1e-3
    moe_dispatch_impl: str = "auto"  # auto | dense | sorted | dropless
    moe_normalize_gates: bool = False
    # a mixed stack's routed experts beyond the plain softmax rule, all
    # read by ``moe.gated_experts`` alone. ``moe_rule``: how a token's
    # scores choose its experts (``moe.route_top_k``: "softmax", or
    # "sigmoid_bias": sigmoid scores, a learned selection bias, the gates
    # the winners' unbiased scores). ``moe_held`` (first, count): this
    # program holds the experts ``first .. first + count - 1`` of the
    # ``moe_num_experts`` it routes over (one chip's share of a layer whose
    # experts are spread over several): an assignment to another expert
    # leaves and is counted, its result is some other chip's to add.
    # ``moe_shared``: experts of the same shape every token passes, added
    # once beside the routed ones
    moe_rule: str = "softmax"
    moe_held: Optional[Tuple[int, int]] = None
    moe_shared: int = 0
    # EP-dropless receive-buffer headroom (see MoEConfig.ep_buffer_factor);
    # >= the 'expert' axis size guarantees zero drops under any skew
    moe_ep_buffer_factor: float = 2.0
    # what each layer is, one of LAYER_KINDS a layer. () = ``n_layer``
    # attention layers (the stacked ``layers`` tree, one scan). A stack
    # that names its kinds keeps its weights stacked by kind
    # (models/mixers.py) and its layer loop runs kind by kind in order.
    mixer_types: Tuple[str, ...] = ()
    # muP scalings (MiniCPM): the embedding times scale_emb, every residual
    # branch of a mixed_block times residual_scale, the logits times
    # logit_scale
    scale_emb: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    sparse: Optional[SparseAttnConfig] = None   # the minicpm4 layers'
    ssm: Optional[MambaAttnConfig] = None       # the mamba_attn layers'
    eva: Optional[EvaAttnConfig] = None         # the eva layers'
    # the full_attn and window_attn layers'
    gqa: Optional[GroupedAttnConfig] = None
    kda: Optional[KdaConfig] = None             # the kda layers'
    # what a model states of its norms, its residual stream and its head
    # (read by the eva layers, the final norm and the head): an RMSNorm
    # scales by ``norm_offset + w``; the stream between layers and the
    # logits stay float32 whatever ``dtype`` the matmuls run in; the head
    # has ``n_pred * vocab_size`` columns, block p scoring the token p + 1
    # positions on (block 0 is the served one)
    norm_offset: float = 0.0
    fp32_stream: bool = False
    n_pred: int = 1
    # the head's product accumulated and kept in float32 over a stream in
    # ``dtype`` (``fp32_stream`` implies it)
    fp32_logits: bool = False
    # a looped stack (served only): the whole stack of layers is applied
    # ``loop_steps`` times to the stream with the SAME weights, the final
    # norm after EVERY pass (its output is the next pass's input, the last
    # pass's the head's), and every (pass, layer) pair keeps a cache of its
    # own: cache layer ``pass * count(kind) + layer`` (``cache_layers``). A
    # linear exit gate (``exit_gate``: d_model -> 1) reads each pass's
    # normed output; it is read out, never acted on (every token runs
    # every pass)
    loop_steps: int = 1

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The stack, layer by layer: what prefill, chunk and decode
        programs dispatch on."""
        return self.mixer_types or ("attention",) * self.n_layer

    @property
    def classic(self) -> bool:
        """Every layer is ``decoder_block``'s (the stacked ``layers`` tree,
        the model ``make_gpt`` trains)."""
        return not self.mixer_types

    def count(self, kind: str) -> int:
        return self.layer_kinds.count(kind)

    def cache_layers(self, kind: str) -> int:
        """How deep a cache of the kind's layers is: one layer a (pass,
        layer) pair."""
        return self.loop_steps * self.count(kind)

    @property
    def moe(self):
        if not self.moe_num_experts:
            return None
        from .moe import MoEConfig

        return MoEConfig(
            num_experts=self.moe_num_experts,
            top_k=self.moe_top_k,
            capacity_factor=self.moe_capacity_factor,
            aux_loss_coef=self.moe_aux_coef,
            z_loss_coef=self.moe_z_coef,
            dispatch_impl=self.moe_dispatch_impl,
            normalize_gates=self.moe_normalize_gates,
            ep_buffer_factor=self.moe_ep_buffer_factor,
        )

    def __post_init__(self):
        kv = self.n_kv_head or self.n_head
        if self.n_head % kv:
            raise ValueError(
                f"n_head ({self.n_head}) must be a multiple of n_kv_head "
                f"({kv})"
            )
        if self.mixer_types:
            # attention layers keep their own weight tree and a pool laid
            # out position by position: they do not mix with the others,
            # each of which keeps one of six shapes of cache (pages; a
            # state row a slot, with convolution tails in a kda layer;
            # both; pages of two roles behind a window; a ring of pages a
            # slot, in a pool of its own)
            mixable = set(LAYER_KINDS) - {"attention"}
            if set(self.mixer_types) - mixable \
                    or len(self.mixer_types) != self.n_layer:
                raise ValueError(
                    f"mixer_types must name one of the {len(mixable)} kinds "
                    f"{sorted(mixable)} (of LAYER_KINDS {LAYER_KINDS}) for "
                    f"each of the {self.n_layer} layers (or be empty: a "
                    f"stack of attention layers), got {self.mixer_types}")
            if "minicpm4" in self.mixer_types and self.sparse is None:
                raise ValueError("minicpm4 layers need cfg.sparse")
            if "mamba_attn" in self.mixer_types and self.ssm is None:
                raise ValueError("mamba_attn layers need cfg.ssm")
            if "eva" in self.mixer_types and self.eva is None:
                raise ValueError("eva layers need cfg.eva")
            if set(self.mixer_types) & GROUPED_KINDS and self.gqa is None:
                raise ValueError(
                    "full_attn and window_attn layers need cfg.gqa")
            if "kda" in self.mixer_types and self.kda is None:
                raise ValueError("kda layers need cfg.kda")
        if self.loop_steps < 1 or (self.loop_steps > 1 and set(
                self.layer_kinds) != {"full_attn"}):
            raise ValueError(
                f"loop_steps ({self.loop_steps}) must be >= 1, and a looped "
                f"stack (loop_steps > 1) one of full_attn layers alone (got "
                f"{sorted(set(self.layer_kinds))}): only a cache of pages "
                f"that follow the length is laid out a pass deep, and the "
                f"looped stack is served, not trained")
        if self.moe_rule not in ("softmax", "sigmoid_bias"):
            raise ValueError(
                f"moe_rule must be 'softmax' or 'sigmoid_bias', got "
                f"{self.moe_rule!r}")
        if self.moe_held is not None:
            first, count = self.moe_held
            if first < 0 or count < 1 \
                    or first + count > self.moe_num_experts:
                raise ValueError(
                    f"moe_held {self.moe_held} must name experts among the "
                    f"{self.moe_num_experts} routed over")
        if self.remat_policy not in ("full", "flash", "matmuls", "dots",
                                     "dots_all"):
            raise ValueError(
                f"remat_policy must be 'full', 'flash', 'matmuls', 'dots', "
                f"or 'dots_all', got {self.remat_policy!r}"
            )

    @property
    def ffn_dim(self):
        return self.d_ff if self.d_ff else 4 * self.d_model

    @property
    def head_dim(self):
        if self.head_size:
            return self.head_size
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @property
    def kv_heads(self):
        return self.n_kv_head or self.n_head  # validated in __post_init__

    @property
    def qkv_dim(self):
        """Width of the fused qkv projection: H*Dh + 2*Hkv*Dh."""
        return (self.n_head + 2 * self.kv_heads) * self.head_dim


# ------------------------------------------------------------------ #
# init
# ------------------------------------------------------------------ #


def init_params(rng, cfg: GPTConfig):
    """Initial fp32 params. Per-layer tensors stacked on axis 0."""
    D, F, L, V = cfg.d_model, cfg.ffn_dim, cfg.n_layer, cfg.vocab_size
    k = iter(jax.random.split(rng, 16))
    std = 0.02
    # output projections scaled by 1/sqrt(2L) (GPT-2/NeoX convention)
    out_std = std / math.sqrt(2.0 * L)

    def norm(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(jnp.float32)

    params = {
        "embed": {"wte": norm(next(k), (V, D), std)},
        "layers": {
            "ln1_scale": jnp.ones((L, D), jnp.float32),
            "ln1_bias": jnp.zeros((L, D), jnp.float32),
            "ln2_scale": jnp.ones((L, D), jnp.float32),
            "ln2_bias": jnp.zeros((L, D), jnp.float32),
            "attn": {
                "wqkv": norm(next(k), (L, D, cfg.qkv_dim), std),
                "bqkv": jnp.zeros((L, cfg.qkv_dim), jnp.float32),
                "wo": norm(next(k), (L, D, D), out_std),
                "bo": jnp.zeros((L, D), jnp.float32),
            },
            "mlp": {
                "wi": norm(next(k), (L, D, F), std),
                "bi": jnp.zeros((L, F), jnp.float32),
                "wo": norm(next(k), (L, F, D), out_std),
                "bo": jnp.zeros((L, D), jnp.float32),
            },
        },
        "final_ln": {
            "scale": jnp.ones((D,), jnp.float32),
            "bias": jnp.zeros((D,), jnp.float32),
        },
    }
    if cfg.moe is not None:
        from .moe import init_moe_params

        moe_keys = jax.random.split(next(k), L)
        per_layer = [
            init_moe_params(moe_keys[i], D, F, cfg.moe, out_std=out_std)
            for i in range(L)
        ]
        params["layers"]["moe"] = jax.tree.map(
            lambda *xs: jnp.stack(xs), *per_layer
        )
        del params["layers"]["mlp"]
    if not cfg.rotary:
        params["embed"]["wpe"] = norm(next(k), (cfg.max_seq, D), std)
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(next(k), (D, V), std)
    return params


def param_specs(cfg: GPTConfig):
    """Tensor-parallel PartitionSpecs over the 'model' axis (megatron-style
    column/row split: qkv+ffn-in column-parallel, attn-out+ffn-out
    row-parallel, embeddings vocab-sharded)."""
    M = MODEL_AXIS
    specs = {
        # wte sharded over d_model, not vocab: XLA's sharded-gather from a
        # vocab-sharded table falls back to full replication (SPMD warning),
        # while column-sharded embedding rows gather cleanly
        "embed": {"wte": P(None, M)},
        "layers": {
            "ln1_scale": P(None, None),
            "ln1_bias": P(None, None),
            "ln2_scale": P(None, None),
            "ln2_bias": P(None, None),
            "attn": {
                "wqkv": P(None, None, M),
                "bqkv": P(None, M),
                "wo": P(None, M, None),
                "bo": P(None, None),
            },
            "mlp": {
                "wi": P(None, None, M),
                "bi": P(None, M),
                "wo": P(None, M, None),
                "bo": P(None, None),
            },
        },
        "final_ln": {"scale": P(None), "bias": P(None)},
    }
    if cfg.moe is not None:
        from .moe import moe_param_specs

        # prepend the stacked layer axis to every expert/router spec
        specs["layers"]["moe"] = jax.tree.map(
            lambda s: P(None, *s), moe_param_specs(),
            is_leaf=lambda x: isinstance(x, P),
        )
        del specs["layers"]["mlp"]
    if not cfg.rotary:
        specs["embed"]["wpe"] = P(None, None)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, M)
    return specs


# ------------------------------------------------------------------ #
# building blocks
# ------------------------------------------------------------------ #


def pick_ce_chunk(S: int, chunk: int) -> int:
    """Streaming-CE chunk for sequence length S: the configured chunk when
    it divides S, else the largest divisor of S not above it. Below 32 the
    scan would degenerate into tiny matmuls (prime S) — return 0 (fused
    path) instead. Shared by the GPT and BERT loss functions."""
    if not chunk or S <= chunk:
        return 0
    if S % chunk:
        chunk = next(c for c in range(min(chunk, S), 0, -1) if S % c == 0)
        if chunk < 32:
            return 0
    return chunk


def layer_norm(x, scale, bias, eps):
    # dispatches through the "kernels" config block: fused Pallas LN on
    # TPU when enabled, else the exact fp32-stats XLA math this function
    # used to inline (fused_blocks._ln_ref)
    from ..ops.pallas import fused_blocks

    return fused_blocks.layer_norm(x, scale, bias, eps)


def layer_norm2(x, scale1, bias1, scale2, bias2, eps):
    """Two layernorms of the SAME input (the NeoX parallel-residual block
    applies ln1 and ln2 both to x): mean/var are computed once and only
    the affine differs — halves the fp32 reduction passes over x in both
    the forward and the backward."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return ((y * scale1 + bias1).astype(x.dtype),
            (y * scale2 + bias2).astype(x.dtype))


def rotary_embedding(x, positions, rotary_dims, theta: float = 10000.0,
                     rope: Optional[RopeScaling] = None):
    """Apply rotary position embedding to the first rotary_dims of head_dim.

    x: (B, S, H, Dh); positions: (S,) shared across the batch, or (B, S)
    per-row absolute positions (batched cache decode, where rows sit at
    different offsets). ``rope``, where given, brings the frequencies (its
    own ``theta``, YaRN's) and the factor on cos and sin."""
    dh = x.shape[-1]
    rot, rest = x[..., :rotary_dims], x[..., rotary_dims:]
    half = rotary_dims // 2
    if rope is not None:
        freq = jnp.asarray(rope.inv_freq(rotary_dims))
    else:
        freq = jnp.exp(
            -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
        )
    angles = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    if positions.ndim == 1:
        angles = angles[None]

    def turn(f):
        t = f(angles)
        if rope is not None and rope.attention_factor != 1.0:
            t = t * jnp.float32(rope.attention_factor)
        return t[:, :, None, :].astype(x.dtype)

    cos, sin = turn(jnp.cos), turn(jnp.sin)
    x1, x2 = rot[..., :half], rot[..., half:]
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if rest.shape[-1]:
        return jnp.concatenate([rotated, rest], axis=-1)
    return rotated


def _xla_causal_attention(q, k, v):
    """Reference attention; XLA fuses this well on the MXU. (B,S,H,Dh)."""
    dh = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(dh)
    s_q, s_k = q.shape[1], k.shape[1]
    mask = jnp.tril(jnp.ones((s_q, s_k), bool))
    scores = jnp.where(mask[None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


_ATTN_IMPLS = ("auto", "pallas", "pallas_interpret", "xla", "ring", "ulysses")


def expand_kv_heads(q, k, v):
    """GQA: repeat K/V heads to match Q's head count (q head i attends to
    kv head i // rep, the HF repeat_kv convention). The projection and the
    decode cache keep the small Hkv; full-H tensors only exist transiently
    for the attention kernels. The decode path avoids even that via a
    grouped einsum (models/generation.py)."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def causal_attention(q, k, v, impl="auto"):
    if impl not in _ATTN_IMPLS:
        raise ValueError(f"unknown attn_impl {impl!r}; choose from {_ATTN_IMPLS}")
    if impl in ("ring", "ulysses"):
        raise ValueError(
            f"attn_impl {impl!r} is context-parallel and needs a mesh; use "
            "ops.ring_attention.make_context_parallel_attention (make_gpt "
            "wires it automatically when given a mesh)"
        )
    if impl in ("auto", "pallas", "pallas_interpret"):
        from ..ops.pallas.flash_attention import (attention_dispatch,
                                                  flash_attention,
                                                  is_available)

        if impl == "pallas_interpret":  # CPU testing path
            return flash_attention(q, k, v, causal=True, interpret=True)
        # auto avoids flash at short S: its per-(batch, head, q-block)
        # dynamic k-loop overhead beats the compute there and XLA's
        # batched-GEMM scores path is faster (hardware-measured at S<=256)
        # — unless the "kernels" config routes the geometry to the dense
        # super-tile kernel, which packs short sequences into MXU-sized
        # tiles and beats the batched-GEMM path
        B, S, H, Dh = q.shape
        supertile = attention_dispatch(
            (B, H, S, Dh), q.dtype.itemsize, causal=True
        ) == "supertile"
        if impl == "pallas" or supertile or (is_available(q) and S > 256):
            return flash_attention(q, k, v, causal=True)
    return _xla_causal_attention(q, k, v)


# ------------------------------------------------------------------ #
# forward
# ------------------------------------------------------------------ #


def _shard_act(x, mesh, spec):
    if mesh is None:
        return x
    from jax.sharding import NamedSharding

    from ..sharding.rules import translate_spec

    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, translate_spec(spec, mesh))
    )


def decoder_block(cfg: GPTConfig, mesh, x, layer_params, positions, attend,
                  mlp_fn=None):
    """One decoder layer shared by training (make_gpt) and KV-cache decoding
    (models/generation.py): qkv projection, rotary, residual/MLP wiring.

    ``attend(q, k, v) -> (ctx, aux)`` supplies the attention core — dense /
    flash / context-parallel for training, cache-updating for decode.
    ``mlp_fn(mlp_in) -> (mlp_out, moe_aux_or_None)`` overrides the dense FFN
    (the MoE hook). Returns (x_out, aux) — with an mlp_fn override, aux is
    (attend_aux, moe_aux)."""
    cdt = cfg.dtype
    B, S, D = x.shape
    H, Dh = cfg.n_head, cfg.head_dim
    with jax.named_scope("ds.attn"):
        mlp_in_shared = None
        if cfg.parallel_residual:
            # ln1(x) and ln2(x) normalize the SAME x — share the mean/var pass
            attn_in, mlp_in_shared = layer_norm2(
                x, layer_params["ln1_scale"], layer_params["ln1_bias"],
                layer_params["ln2_scale"], layer_params["ln2_bias"],
                cfg.layernorm_eps,
            )
        else:
            attn_in = layer_norm(
                x, layer_params["ln1_scale"], layer_params["ln1_bias"],
                cfg.layernorm_eps,
            )
        qkv = attn_in @ layer_params["attn"]["wqkv"].astype(cdt) + layer_params[
            "attn"
        ]["bqkv"].astype(cdt)
        Hkv = cfg.kv_heads
        q = qkv[..., : H * Dh].reshape(B, S, H, Dh)
        k = qkv[..., H * Dh: (H + Hkv) * Dh].reshape(B, S, Hkv, Dh)
        v = qkv[..., (H + Hkv) * Dh:].reshape(B, S, Hkv, Dh)
        if cfg.rotary:
            rd = int(cfg.rotary_pct * Dh) // 2 * 2
            q = rotary_embedding(q, positions, rd)
            k = rotary_embedding(k, positions, rd)
        # named for selective remat (remat_policy='matmuls'): saving the
        # post-rotary q/k/v lets the backward skip the qkv projection+rotary
        q = checkpoint_name(q, "attn_q")
        k = checkpoint_name(k, "attn_k")
        v = checkpoint_name(v, "attn_v")
        ctx, aux = attend(q, k, v)
        attn = ctx.reshape(B, S, D)
        attn_out = attn @ layer_params["attn"]["wo"].astype(cdt) + layer_params[
            "attn"
        ]["bo"].astype(cdt)

    with jax.named_scope("ds.mlp"):
        if cfg.parallel_residual:
            # NeoX: x + attn(ln1(x)) + mlp(ln2(x)); mlp_in computed above in
            # the shared-normalization pass
            mlp_in = mlp_in_shared
        else:
            x = x + attn_out
            mlp_in = layer_norm(
                x, layer_params["ln2_scale"], layer_params["ln2_bias"], cfg.layernorm_eps
            )
        if mlp_fn is not None:
            mlp_out, moe_aux = mlp_fn(mlp_in)
            aux = (aux, moe_aux)
        else:
            from ..ops.pallas.fused_blocks import bias_gelu

            h = mlp_in @ layer_params["mlp"]["wi"].astype(cdt)
            # pre-gelu: saving it skips the ffn-in matmul recompute while the
            # bias+gelu stays cheap to replay (saved pre-bias so the fused
            # kernel owns the add)
            h = checkpoint_name(h, "mlp_pre")
            h = bias_gelu(h, layer_params["mlp"]["bi"].astype(cdt),
                          approximate=True)
            h = _shard_act(h, mesh, P(DATA_AXIS, SEQ_AXIS, MODEL_AXIS))
            mlp_out = h @ layer_params["mlp"]["wo"].astype(cdt) + layer_params[
                "mlp"
            ]["bo"].astype(cdt)

    if cfg.parallel_residual:
        x = x + attn_out + mlp_out
    else:
        x = x + mlp_out
    x = _shard_act(x, mesh, P(DATA_AXIS, SEQ_AXIS, None))
    return x, aux


def make_gpt(cfg: GPTConfig, mesh=None):
    """Returns (init_fn, apply_fn, loss_fn, specs).

    apply_fn(params, tokens) -> logits (B, S, V)
    loss_fn(params, batch) with batch = tokens (B, S+1) or (inputs, targets)
    """

    cp_attend = None
    if cfg.attn_impl in ("ring", "ulysses"):
        if mesh is None:
            raise ValueError(
                f"attn_impl={cfg.attn_impl!r} is a context-parallel strategy "
                "and needs a mesh with a 'seq' axis; pass mesh= to make_gpt"
            )
        from ..ops.ring_attention import make_context_parallel_attention

        # raises if the mesh has no usable 'seq' axis — never silently dense
        cp_attend = make_context_parallel_attention(
            mesh, strategy=cfg.attn_impl, causal=True
        )

    def attend(q, k, v):
        k, v = expand_kv_heads(q, k, v)
        q = _shard_act(q, mesh, P(DATA_AXIS, SEQ_AXIS, MODEL_AXIS, None))
        k = _shard_act(k, mesh, P(DATA_AXIS, SEQ_AXIS, MODEL_AXIS, None))
        v = _shard_act(v, mesh, P(DATA_AXIS, SEQ_AXIS, MODEL_AXIS, None))
        if cp_attend is not None:
            return cp_attend(q, k, v), None
        if mesh is None:  # an engine tracing this model names its mesh
            return causal_attention(q, k, v, impl=cfg.attn_impl), None
        from ..ops import kernel_config

        with kernel_config.mesh_scope(mesh):
            return causal_attention(q, k, v, impl=cfg.attn_impl), None

    moe_cfg = cfg.moe

    def block(carry, layer_params, positions):
        """-> (x, this layer's scalar moe auxiliary loss; 0 when dense)."""
        if moe_cfg is None:
            x, _ = decoder_block(cfg, mesh, carry, layer_params, positions,
                                 attend)
            return x, jnp.float32(0.0)
        from .moe import moe_ffn, moe_loss

        def mlp_fn(mlp_in):
            return moe_ffn(layer_params["moe"], mlp_in, moe_cfg, mesh=mesh)

        x, (_, moe_aux) = decoder_block(cfg, mesh, carry, layer_params,
                                        positions, attend, mlp_fn=mlp_fn)
        return x, moe_loss(moe_aux, moe_cfg)

    def hidden_fn(params, tokens):
        """tokens (B, S) int32 -> (final-layernormed hidden states (B, S, D),
        summed moe auxiliary loss — 0.0 for dense models)."""
        cdt = cfg.dtype
        B, S = tokens.shape
        with jax.named_scope("ds.embed"):
            wte = params["embed"]["wte"].astype(cdt)
            x = jnp.take(wte, tokens, axis=0)  # (B, S, D)
            positions = jnp.arange(S, dtype=jnp.int32)
            if not cfg.rotary:
                x = x + params["embed"]["wpe"][:S].astype(cdt)
            x = _shard_act(x, mesh, P(DATA_AXIS, SEQ_AXIS, None))

        step = partial(block, positions=positions)
        if cfg.remat:
            policy = {
                "full": None,
                "flash": jax.checkpoint_policies.save_only_these_names(
                    "flash_o", "flash_lse"
                ),
                "matmuls": jax.checkpoint_policies.save_only_these_names(
                    "flash_o", "flash_lse", "attn_q", "attn_k", "attn_v",
                    "mlp_pre"
                ),
                "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                "dots_all": jax.checkpoint_policies.dots_saveable,
            }[cfg.remat_policy]
            step = jax.checkpoint(step, prevent_cse=False, policy=policy)

        def scan_body(carry, xs):
            x, aux_sum = carry
            layer_params, layer_idx = xs
            out, layer_aux = step(x, layer_params)
            # cooperative layer-output tap (engine.register_forward_hook);
            # identity unless a collector is active at trace time
            out = hooks.record_layer_output("transformerlayer", out, layer_idx)
            return (out, aux_sum + layer_aux), None

        layer_ids = jnp.arange(cfg.n_layer, dtype=jnp.int32)
        (x, moe_aux), _ = jax.lax.scan(
            scan_body, (x, jnp.float32(0.0)), (params["layers"], layer_ids)
        )
        x = layer_norm(
            x, params["final_ln"]["scale"], params["final_ln"]["bias"], cfg.layernorm_eps
        )
        return x, moe_aux

    def head_weight(params):
        cdt = cfg.dtype
        if cfg.tie_embeddings:
            return params["embed"]["wte"].astype(cdt).T
        return params["lm_head"].astype(cdt)

    def apply_fn(params, tokens):
        """tokens (B, S) int32 -> logits (B, S, V)."""
        return hidden_fn(params, tokens)[0] @ head_weight(params)

    def loss_fn(params, batch):
        """batch: (inputs, targets) int (B, S) each, or tokens (B, S+1)."""
        if isinstance(batch, (tuple, list)):
            inputs, targets = batch
        else:
            inputs, targets = batch[:, :-1], batch[:, 1:]
        x, moe_aux = hidden_fn(params, inputs)
        with jax.named_scope("ds.loss"):
            w = head_weight(params)
            B, S, D = x.shape
            chunk = pick_ce_chunk(S, cfg.ce_chunk)
            if chunk and S > chunk:
                # stream the cross-entropy over sequence chunks: the (B, S, V)
                # logits are never materialized. Each chunk's logits are
                # recomputed in the backward (one extra head matmul) in exchange
                # for GBs of saved HBM — this is what unlocks large micro-batches
                # (the reference's fp16 fused softmax-xent serves the same role,
                # csrc/transformer/softmax_kernels.cu)
                n = S // chunk
                xs = jnp.moveaxis(x.reshape(B, n, chunk, D), 1, 0)
                ts = jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0)

                @jax.checkpoint
                def chunk_nll(xc, tc):
                    logits = (xc @ w).astype(jnp.float32)  # (B, chunk, V)
                    lse = jax.scipy.special.logsumexp(logits, axis=-1)
                    tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
                    return jnp.sum(lse - tgt)

                def body(acc, xt):
                    return acc + chunk_nll(*xt), None

                total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts))
                return total / (B * S) + moe_aux
            logits = (x @ w).astype(jnp.float32)
            # nll = logsumexp - target_logit, WITHOUT materializing the fp32
            # log-softmax over the full (B, S, V) tensor (pure HBM traffic)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
            return jnp.mean(lse - tgt) + moe_aux

    def init_fn(rng):
        return init_params(rng, cfg)

    if not cfg.classic:
        # served only (serving/engine.py); the training block is
        # decoder_block's. A loss that ran a wrong model would be worse
        # than none.
        def refuse(*_a, **_k):
            raise NotImplementedError(
                f"training a stack of {sorted(set(cfg.mixer_types))} layers "
                "is not implemented: this model is served only "
                "(ServingEngine); models/mixers.py has its forward")

        from .mixers import init_params as init_mixed

        return (lambda rng: init_mixed(rng, cfg)), refuse, refuse, None

    return init_fn, apply_fn, loss_fn, param_specs(cfg)


def params_from_hf(model, cfg: Optional[GPTConfig] = None):
    """Import a huggingface GPT2LMHeadModel/GPT2Model checkpoint into the
    stacked param pytree (the GPT-family counterpart of
    bert.params_from_hf), giving bit-compatible fine-tuning starts.

    HF GPT-2's Conv1D weights are already (in, out), matching this module's
    layout; c_attn's fused q|k|v column order matches the wqkv split.
    Returns (cfg, params) with tie_embeddings=True (HF GPT-2 ties lm_head
    to wte)."""
    from ..ops.transformer.transformer import to_numpy_f32

    def f32(t):
        return jnp.asarray(to_numpy_f32(t))

    gpt2 = getattr(model, "transformer", model)
    hf_cfg = model.config
    if cfg is None:
        cfg = GPTConfig(
            vocab_size=hf_cfg.vocab_size,
            n_layer=hf_cfg.n_layer,
            n_head=hf_cfg.n_head,
            d_model=hf_cfg.n_embd,
            max_seq=hf_cfg.n_positions,
            rotary=False,
            parallel_residual=False,
            tie_embeddings=True,
            layernorm_eps=hf_cfg.layer_norm_epsilon,
            dtype=jnp.float32,
        )
    if cfg.rotary or cfg.parallel_residual:
        raise ValueError(
            "HF GPT-2 is learned-position + serial-residual; pass a "
            "matching cfg"
        )
    if (cfg.kv_heads != cfg.n_head or cfg.n_head != hf_cfg.n_head
            or cfg.d_model != hf_cfg.n_embd or cfg.n_layer != hf_cfg.n_layer):
        raise ValueError(
            f"cfg (layers={cfg.n_layer}, d={cfg.d_model}, heads="
            f"{cfg.n_head}, kv_heads={cfg.kv_heads}) does not match the HF "
            f"checkpoint (layers={hf_cfg.n_layer}, d={hf_cfg.n_embd}, "
            f"heads={hf_cfg.n_head}, MHA) — GQA cannot import MHA weights"
        )

    blocks = list(gpt2.h)
    stack = lambda ts: jnp.stack([f32(t) for t in ts])
    params = {
        "embed": {
            "wte": f32(gpt2.wte.weight),
            "wpe": f32(gpt2.wpe.weight),
        },
        "layers": {
            "ln1_scale": stack([b.ln_1.weight for b in blocks]),
            "ln1_bias": stack([b.ln_1.bias for b in blocks]),
            "ln2_scale": stack([b.ln_2.weight for b in blocks]),
            "ln2_bias": stack([b.ln_2.bias for b in blocks]),
            "attn": {
                "wqkv": stack([b.attn.c_attn.weight for b in blocks]),
                "bqkv": stack([b.attn.c_attn.bias for b in blocks]),
                "wo": stack([b.attn.c_proj.weight for b in blocks]),
                "bo": stack([b.attn.c_proj.bias for b in blocks]),
            },
            "mlp": {
                "wi": stack([b.mlp.c_fc.weight for b in blocks]),
                "bi": stack([b.mlp.c_fc.bias for b in blocks]),
                "wo": stack([b.mlp.c_proj.weight for b in blocks]),
                "bo": stack([b.mlp.c_proj.bias for b in blocks]),
            },
        },
        "final_ln": {
            "scale": f32(gpt2.ln_f.weight),
            "bias": f32(gpt2.ln_f.bias),
        },
    }
    return cfg, params


# convenience presets ------------------------------------------------- #

PRESETS = {
    "gpt2-125m": GPTConfig(n_layer=12, n_head=12, d_model=768, rotary=False,
                           parallel_residual=False),
    "gpt2-350m": GPTConfig(n_layer=24, n_head=16, d_model=1024, rotary=False,
                           parallel_residual=False),
    "neox-125m": GPTConfig(n_layer=12, n_head=12, d_model=768),
    "neox-1.3b": GPTConfig(n_layer=24, n_head=16, d_model=2048),
    "neox-6.7b": GPTConfig(n_layer=32, n_head=32, d_model=4096),
    "neox-20b": GPTConfig(
        n_layer=44, n_head=64, d_model=6144, d_ff=24576, vocab_size=50432,
        rotary_pct=0.25,
    ),
}


def get_preset(name: str, **overrides) -> GPTConfig:
    cfg = PRESETS[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg
