"""Causal transformer LM — the flagship model family.

Covers the reference's trainable transformer stack
(``deepspeed/ops/transformer/transformer.py`` ``DeepSpeedTransformerLayer`` +
the model zoo its tests/benchmarks train: BERT/GPT-2/Megatron-GPT/Llama-style
decoders).  TPU-first design:

* pure functional: params are an explicit pytree; layers are **stacked**
  (leading dim = n_layers) and the forward is ``lax.scan`` over layers — the
  shape XLA needs so ZeRO-3's per-layer all-gather overlaps layer compute
  (this replaces the reference's prefetch coordinator,
  ``partitioned_param_coordinator.py:44``);
* ``jax.checkpoint`` (remat) per layer replaces
  ``runtime/activation_checkpointing`` (policy configurable);
* RoPE + RMSNorm + SwiGLU (Llama family) or learned-pos + LayerNorm + GELU
  (GPT-2 family), GQA supported;
* tensor-parallel sharding shipped as ``tp_rules`` (regex → PartitionSpec):
  column-parallel wq/wk/wv/w_up, row-parallel wo/w_down — the Megatron split
  the reference gets from its injected mpu;
* logits/loss in fp32 (matching the reference's fused softmax numerics).
"""

import contextlib
import functools
import math
from dataclasses import dataclass, field, replace
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.attention import attention, reference_attention
from deepspeed_tpu.ops.block_sparse_attention import SparseSizes
from deepspeed_tpu.ops.decode_attention import (KVCache, decode_attention,
                                                init_cache, update_cache)
from deepspeed_tpu.parallel.topology import (BATCH_AXES, DP_AXIS, FSDP_AXIS,
                                             SP_AXIS, TP_AXIS)
from deepspeed_tpu.runtime.zero.stage_plan import layer_scan, maybe_constrain


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: Optional[int] = None        # None → MHA
    ffn_hidden_size: Optional[int] = None   # None → 4x (gelu) or 8/3x (swiglu)
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    activation: str = "silu"    # "silu" (SwiGLU) | "gelu" (tanh approx)
                                # | "gelu_exact" (erf, MPT) | "relu"
    gated_mlp: Optional[bool] = None   # None → gated iff silu; True forces
                                       # a GLU (Gemma GeGLU)
    head_dim_override: Optional[int] = None  # H*dh != d (Gemma-7b)
    embed_scale: Optional[float] = None      # input embeds × scale (Gemma
                                             # sqrt(d); tied head unscaled)
    use_rmsnorm: bool = True
    use_rope: bool = True                   # False → learned positions (GPT-2)
    rope_dim: Optional[int] = None          # partial rotary (GPT-NeoX); None → full
    rope_inv_freq: Optional[Tuple[float, ...]] = None  # scaled inverse
    #   frequencies (Llama-3 / linear rope scaling), length rotary_dim//2
    #   (= the ROTATED slice's half-dim when rope_dim is set)
    use_bias: bool = False                  # linear biases (GPT-2/OPT families)
    norm_bias: bool = False                 # LayerNorm beta (GPT-2/OPT)
    use_alibi: bool = False                 # ALiBi slopes, no positions (Bloom)
    embed_norm: bool = False                # LayerNorm after embedding (Bloom)
    parallel_block: bool = False            # x + attn(ln(x)) + mlp(ln'(x))
    #                                         (GPT-J / parallel-residual NeoX)
    lm_head_bias: bool = False              # bias on the LM head (GPT-J)
    attn_scale: Optional[float] = None      # softmax scale override (GPT-Neo
    #                                         uses 1.0 instead of 1/sqrt(dh))
    local_attn_pattern: Optional[Tuple[int, ...]] = None  # per-layer sliding
    #                window (0 = global); GPT-Neo alternates (0, 256, 0, ...)
    # The rest of the layer pattern, as data.  ``rope_pattern``: per layer,
    # whether q and k turn (None: every layer, under ``use_rope``; a
    # full-attention layer of a window model may carry no positions).
    # ``layer_period`` > 0: the patterns above repeat with that period
    # after the leading layers (the whole periods that hold the
    # ``first_dense_layers``); params keep those leading layers as a list
    # and the others STACKED by position in the period
    # (``params["periods"][j]``: leaves [n_periods, ...]), and the serving
    # forward scans over the periods instead of unrolling every layer
    rope_pattern: Optional[Tuple[bool, ...]] = None
    layer_period: int = 0
    # ``ssm_pattern``: per layer, whether its mixer is a state-space
    # (Mamba-2) layer in place of attention (ops/ssm.py): ``ssm_heads``
    # heads of ``ssm_head_dim`` (their product the mixer's inner width),
    # a state of ``ssm_state`` a head element, ``ssm_groups`` groups
    # sharing B and C, a causal depthwise convolution of ``ssm_conv`` rows
    # ahead of the recurrence, which a sequence of rows computes in
    # chunks of ``ssm_chunk``
    ssm_pattern: Optional[Tuple[bool, ...]] = None
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # ``lin_pattern``: per layer, whether its mixer is a linear-attention
    # layer with a constant decay a head (Lightning Attention,
    # ops/linear_attention.py) in place of attention: ``lin_heads`` heads
    # of ``lin_head_dim`` for q, k and v alike (no grouping), a matrix
    # state [head_dim, head_dim] a head, an RMSNorm over the heads'
    # concatenated output and a sigmoid gate ahead of the output
    # projection; per-head q/k norms under ``qk_norm``, rotary where
    # ``rope_pattern`` says so
    lin_pattern: Optional[Tuple[bool, ...]] = None
    lin_heads: int = 0
    lin_head_dim: int = 0
    # Block-sparse attention in the attention layers (its ``SparseSizes``;
    # ops/block_sparse_attention.py, InfLLM v2): a query of a context of
    # ``dense_len`` or more attends ``topk`` blocks of ``block`` keys a
    # key/value head, picked by its heads' scores over compressed keys
    # (the mean of ``kernel`` keys every ``stride`` tokens); the first
    # ``init_blocks`` blocks and those of the last ``window`` keys are
    # always among them.  Under ``dense_len`` the layer attends densely
    sparse: Optional[SparseSizes] = None
    attn_gate: bool = False                 # attention output x sigmoid(h
    #   wg_attn) ahead of ``wo`` (afmoe's gated attention)
    sandwich_norm: bool = False             # pre-norms AND post-norms on
    #   both sub-blocks (Gemma-2 / afmoe); ``init`` makes all four
    residual_scale: Optional[float] = None  # x + scale*delta on every
    #   sub-block residual add (Granite residual_multiplier)
    post_norm_only: bool = False            # OLMo2: no pre-norms; blocks
    #   are x + post_norm(sublayer(x)) (sandwich keys only)
    qk_norm: Optional[str] = None           # "rms" | "layernorm": per-head
    #   q/k normalization over head_dim before rope (Qwen3 / qk-norm
    #   lineages); "rms_flat": RMS over the whole flat projection
    #   (OLMo2).  Weights ride presence-based layer keys q_norm/k_norm
    clip_qkv: Optional[float] = None        # clamp q/k/v projections to
    #   [-clip, clip] pre-rope (OLMo / MPT-30b / DBRX lineage)
    attn_logit_softcap: Optional[float] = None   # tanh-cap raw attention
    #                scores (Gemma-2); runs the XLA attention path
    final_logit_softcap: Optional[float] = None  # tanh-cap LM-head logits
    final_logit_scale: Optional[float] = None    # multiply LM-head logits
    #   (Cohere logit_scale); applied before any softcap
    tie_embeddings: bool = False
    remat: bool = True
    remat_policy: str = "nothing_saveable"
    attn_impl: str = "auto"
    # ring attention token layout: "zigzag" balances the causal triangle
    # across sp devices (~2x step time at large sp); needs S % (2*sp) == 0
    ring_layout: str = "contiguous"
    # Pallas flash-attention tile sizes: None = picked from each call's
    # shapes (``ops/pallas/flash_attention.py pick_flash_tiles``); a value
    # overrides the picker (the autotuner's ``attn_blocks``)
    attn_block_q: Optional[int] = None
    attn_block_k: Optional[int] = None
    # training loss: stream logits in chunks of this many tokens under a
    # remat'd scan so the full fp32 [B,S,V] tensor never hits HBM (the
    # logits buffer, not the model states, caps the trainable micro-batch
    # at large vocab).  0 = materialize full logits.  Per-token softmax is
    # independent of the chunking, so numerics match the dense path up to
    # fp reassociation of the final mean.
    loss_chunk_size: int = 4096
    # MoE (0 experts = dense; reference deepspeed/moe):
    moe_num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.0
    moe_min_capacity: int = 4
    moe_layer_freq: int = 1        # every Nth layer is MoE
    moe_aux_loss_coef: float = 0.01
    moe_noisy_gate_policy: Optional[str] = None
    moe_norm_topk_prob: bool = True  # renormalize the k gate values
    #   (Mixtral / Qwen2-MoE norm_topk_prob); False keeps softmax mass
    moe_eval_capacity_factor: Optional[float] = None  # None → capacity_factor
    # The serving-time expert layer (``moe_dropless``): every token gets
    # its ``moe_top_k`` experts, no capacity and no dropped token (the
    # capacity path above stays the trainer's), and the chip computes the
    # terms of the ``moe_experts_held`` experts (None: all) from
    # ``moe_experts_first`` on; the other experts' terms are other chips'
    # (docs/serving.md).  ``moe_scoring`` is its router's: "softmax" over
    # all ``moe_num_experts``, or "sigmoid" with a selection bias
    # (DeepSeek-V3 / GLM-5 ``noaux_tc``)
    moe_dropless: bool = False
    moe_scoring: str = "softmax"
    moe_experts_held: Optional[int] = None
    moe_experts_first: int = 0
    moe_routed_scale: float = 1.0       # routed_scaling_factor
    moe_route_norm_eps: float = 0.0     # added to the chosen scores' sum
    moe_ffn_hidden_size: Optional[int] = None   # expert width; None → ffn
    moe_shared_experts: int = 0         # ungated always-on experts
    first_dense_layers: int = 0         # leading layers with a dense FFN
    # Latent attention (MLA; ``kv_lora_rank`` > 0): the cache holds
    # [c_kv | k_rope] a token, heads are nope | rope wide on the query
    # and key side and ``v_head_dim`` on the value side
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the learned key selection inside it (``index_topk`` > 0): each query
    # attends to its index_topk best causal keys by the indexer's score
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_norm_eps: float = 1e-6        # the indexer key's LayerNorm
    # ``rope_scaling`` of type ``yarn`` for the latent mixer's rotary
    # slice, as ``(factor, original positions, beta_fast, beta_slow,
    # mscale, mscale_all_dim)`` (ops/latent_attention.py RopeYarn):
    # frequencies blended by dimension, and the softmax scale times
    # ``mscale_all_dim``'s magnitude squared.  On a model WITHOUT latent
    # attention it is the rotary specification of the FULL-attention
    # layers (window 0 in ``local_attn_pattern``; every layer without a
    # pattern): rotate-half heads turned by the blended frequencies, cos
    # and sin times the magnitude; window layers keep ``rope_theta`` /
    # ``rope_inv_freq`` unscaled (``layer_rotary`` says which a layer is)
    rope_yarn: Optional[Tuple[float, ...]] = None
    # ``init``'s seeded token embeddings: None keeps
    # 1 / sqrt(hidden), a row of norm 1, which one layer's attention
    # output (norm 2-3 under the variance-keeping projections) outweighs,
    # so a token's state after the first layer is mostly an average of
    # its context's values.  A model that SELECTS its keys is then
    # ill-conditioned: two forwards that differ by a rounding swap keys at
    # the top-k boundary, and the swap moves everything downstream by a
    # tenth (docs/serving.md).  1.0 is unit elements (torch.nn.Embedding's
    # default): the token keeps its identity and attention is a few
    # percent of the stream, as in a trained network
    init_embed_std: Optional[float] = None
    # the seeded spread of an untied head's elements.  None is
    # 1 / sqrt(hidden): logits of unit spread on a normed stream.  A model
    # whose logits carry a multiplier (``final_logit_scale``, muP's
    # base / hidden) presumes a trained head; seeded at 1 / sqrt(hidden)
    # its largest logit is a fraction of 1, and a check that holds errors
    # to the larger of 1 and the largest logit then sees nothing.
    # ``1 / (final_logit_scale * sqrt(hidden))`` seeds logits of unit
    # spread again
    init_head_std: Optional[float] = None

    @property
    def is_moe(self):
        return self.moe_num_experts > 1

    @property
    def is_latent(self):
        return self.kv_lora_rank > 0

    @property
    def experts_held(self):
        return self.moe_experts_held or self.moe_num_experts

    @property
    def has_ssm(self):
        """Some layers' mixer is a state-space layer (``ssm_pattern``):
        the serving path keeps a recurrent state a slot beside the pages."""
        return bool(self.ssm_pattern) and any(self.ssm_pattern)

    def layer_ssm(self, i):
        return bool(self.ssm_pattern) and bool(self.ssm_pattern[i])

    @property
    def ssm_inner(self):
        """The state-space mixer's inner width, heads x head width."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self):
        """Channels of its convolution: x | B | C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def has_lin(self):
        """Some layers' mixer is a linear-attention layer
        (``lin_pattern``): a matrix state a slot beside the pages."""
        return bool(self.lin_pattern) and any(self.lin_pattern)

    def layer_lin(self, i):
        return bool(self.lin_pattern) and bool(self.lin_pattern[i])

    @property
    def has_state(self):
        """Some layers keep a recurrent state a slot in place of keys and
        values (state-space or linear-attention layers)."""
        return self.has_ssm or self.has_lin

    def layer_state(self, i):
        return self.layer_ssm(i) or self.layer_lin(i)

    @property
    def state_layers(self):
        """How many layers keep such a state."""
        return sum(self.layer_state(i) for i in range(self.n_layers))

    @property
    def has_sparse(self):
        """The attention layers select blocks of keys (``sparse``)."""
        return self.sparse is not None

    @property
    def counts_serving(self):
        """A serving dispatch of this model returns ``SERVE_COUNTERS``
        (and is told which of its rows are tokens)."""
        return self.is_latent or (self.is_moe and self.moe_dropless) \
            or self.attn_window > 0 or self.has_sparse

    @property
    def attn_window(self):
        """The sliding window of the model's window layers (the widest, if
        they differ); 0 without one."""
        return max(self.local_attn_pattern or (0,))

    @property
    def leading_layers(self):
        """Layers ahead of the scanned periods: the whole periods that
        hold the leading dense layers; all of them without a period."""
        if not self.layer_period:
            return self.n_layers
        return -(-self.first_dense_layers // self.layer_period) \
            * self.layer_period

    def layer_window(self, i):
        return self.local_attn_pattern[i] if self.local_attn_pattern else 0

    def layer_rotary(self, i):
        """Layer ``i``'s rotary kind: False (no positions), True (the
        model's ``rope_theta`` / ``rope_inv_freq``), or ``"yarn"`` (a
        full-attention layer under ``rope_yarn``, without latent
        attention)."""
        if not (self.use_rope and (self.rope_pattern is None
                                   or bool(self.rope_pattern[i]))):
            return False
        if self.rope_yarn is not None and not self.is_latent \
                and not self.layer_window(i):
            return "yarn"
        return True

    @property
    def layers_listed(self):
        """Layers differ in structure, so params["layers"] is a list (and
        ``layer_period`` may stack the periods after it), for one of three
        reasons: some layers hold experts (MoE), the attention is latent,
        or the kinds of mixer differ (state-space or linear-attention
        layers among attention layers; block-sparse attention keeps its
        layers listed too)."""
        return self.is_moe or self.is_latent or self.has_state \
            or self.has_sparse

    @property
    def keeps_flash_residuals(self):
        """The layers run under ``jax.checkpoint`` with a policy that keeps
        their matrix products, the flash call's among them."""
        return self.remat and self.remat_policy in PRODUCT_SAVING_POLICIES

    def checkpoint_policy(self):
        """What ``jax.checkpoint`` of a layer takes as ``policy``:
        ``remat_policy`` by its name in ``jax.checkpoint_policies`` (None,
        which keeps nothing, for a name it does not have), and under a
        policy that keeps matrix products the flash call's result and
        ``lse`` too (``FLASH_RESIDUALS``: ``B S H D`` elements of the
        compute dtype and ``B H S`` float32 a layer and micro-batch).
        ``nothing_saveable``, whose user asked for the least memory, and
        the offload policies stay as they are.  The names are identities
        wherever the kernel does not run, so nothing here asks which
        attention a layer takes."""
        from deepspeed_tpu.ops.pallas.flash_attention import FLASH_RESIDUALS
        policies = jax.checkpoint_policies
        policy = getattr(policies, self.remat_policy, None)
        if self.remat_policy in PRODUCT_SAVING_POLICIES:
            policy = policies.save_from_both_policies(
                policy, policies.save_only_these_names(*FLASH_RESIDUALS))
        return policy

    @property
    def kv_heads(self):
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self):
        return self.head_dim_override or self.hidden_size // self.n_heads

    @property
    def gated(self):
        """Gated (GLU) MLP: explicit flag, else implied by SwiGLU."""
        if self.gated_mlp is not None:
            return self.gated_mlp
        return self.activation == "silu"

    @property
    def ffn_dim(self):
        if self.ffn_hidden_size is not None:
            return self.ffn_hidden_size
        if self.activation == "silu":
            d = int(8 * self.hidden_size / 3)
            return 256 * ((d + 255) // 256)
        return 4 * self.hidden_size

    @property
    def rotary_dim(self):
        return self.rope_dim or self.head_dim

    # ---- presets -----------------------------------------------------
    @staticmethod
    def tiny(**kw):
        base = TransformerConfig(
            vocab_size=256, hidden_size=64, n_layers=2, n_heads=4,
            max_seq_len=128, remat=False)
        return replace(base, **kw)

    @staticmethod
    def gpt2_125m(**kw):
        base = TransformerConfig(
            vocab_size=50304, hidden_size=768, n_layers=12, n_heads=12,
            max_seq_len=1024, activation="gelu", use_rmsnorm=False,
            use_rope=False, tie_embeddings=True)
        return replace(base, **kw)

    @staticmethod
    def gpt2_1_5b(**kw):
        base = TransformerConfig(
            vocab_size=50304, hidden_size=1600, n_layers=48, n_heads=25,
            max_seq_len=1024, activation="gelu", use_rmsnorm=False,
            use_rope=False, tie_embeddings=True)
        return replace(base, **kw)

    @staticmethod
    def moe_tiny(**kw):
        base = TransformerConfig.tiny(moe_num_experts=4, moe_top_k=1)
        return replace(base, **kw)

    @staticmethod
    def llama2_7b(**kw):
        base = TransformerConfig(
            vocab_size=32000, hidden_size=4096, n_layers=32, n_heads=32,
            max_seq_len=4096, ffn_hidden_size=11008)
        return replace(base, **kw)

    @staticmethod
    def llama2_70b(**kw):
        base = TransformerConfig(
            vocab_size=32000, hidden_size=8192, n_layers=80, n_heads=64,
            n_kv_heads=8, max_seq_len=4096, ffn_hidden_size=28672)
        return replace(base, **kw)

    def num_params(self) -> int:
        d, f, v = self.hidden_size, self.ffn_dim, self.vocab_size
        dh = self.head_dim
        per_layer = (d * self.n_heads * dh + 2 * d * self.kv_heads * dh +
                     self.n_heads * dh * d)
        per_layer += 2 * d  # norms
        if self.attn_gate:
            per_layer += d * self.n_heads * dh
        if self.qk_norm and not self.is_latent:
            per_layer += (self.n_heads * dh + self.kv_heads * dh
                          if self.qk_norm == "rms_flat" else 2 * dh)
        dense = (3 if self.gated else 2) * d * f
        total = self.n_layers * per_layer + v * d + d
        if self.has_ssm:
            # a state-space layer holds its mixer in place of q, k, v, o:
            # in and out projections, the convolution and its bias, A_log,
            # D and dt_bias a head, the gated norm
            inner, conv = self.ssm_inner, self.ssm_conv_dim
            mixer = d * (inner + conv + self.ssm_heads) + inner * d \
                + (self.ssm_conv + 1) * conv + 3 * self.ssm_heads + inner
            total += sum(self.ssm_pattern) * (mixer + 2 * d - per_layer)
        if self.has_lin:
            # a linear-attention layer: q, k, v, the gate and the output
            # projection of the inner width, the output norm, the q/k norms
            inner = self.lin_heads * self.lin_head_dim
            mixer = 5 * d * inner + inner \
                + (2 * self.lin_head_dim if self.qk_norm else 0)
            total += sum(self.lin_pattern) * (mixer + 2 * d - per_layer)
        if self.is_moe:
            # what this process HOLDS: the router's published width, the
            # held experts (all of them on the capacity path), the shared
            # one; the leading and the in-between layers keep a dense FFN
            fe = (self.moe_ffn_hidden_size or f) if self.moe_dropless else f
            held = self.experts_held if self.moe_dropless \
                else self.moe_num_experts
            expert = (3 if self.gated or self.moe_dropless else 2) * d * fe
            moe = d * self.moe_num_experts + held * expert
            if self.moe_dropless:
                moe += self.moe_shared_experts * expert
                if self.moe_scoring == "sigmoid":
                    moe += self.moe_num_experts      # the selection bias
            n_moe = sum(
                1 for i in range(self.first_dense_layers, self.n_layers)
                if i % self.moe_layer_freq == self.moe_layer_freq - 1)
            total += n_moe * moe + (self.n_layers - n_moe) * dense
        else:
            total += self.n_layers * dense
        if not self.tie_embeddings:
            total += v * d
            if self.lm_head_bias:
                total += v
        if not self.use_rope and not self.use_alibi:
            total += self.max_seq_len * d
        if self.embed_norm:
            total += d
        return total


class LatentQuery(NamedTuple):
    """A latent-attention layer's query side, per head."""
    nope: Any       # [B, T, H, qk_nope_head_dim]
    rope: Any       # [B, T, H, qk_rope_head_dim], turned


class LatentKey(NamedTuple):
    """Its key side, shared by all heads: what the cache holds."""
    c_kv: Any       # [B, T, kv_lora_rank], normed
    rope: Any       # [B, T, qk_rope_head_dim], turned


class Indexer(NamedTuple):
    """The selection's own query heads, head weights and key."""
    q: Any          # [B, T, Hi, Di]
    w: Any          # [B, T, Hi]
    k: Any          # [B, T, Di]


# what a serving dispatch of a latent / dropless model counts on the device
# (``apply_with_paged_cache`` returns them, in this order, as one int32
# vector beside the logits): keys attended and causal keys in context,
# summed over the REAL queries and the layers; (real token, expert) pairs
# computed here, summed over expert layers; the fullest held expert of any
# layer; the rows the grouped expert product computed for those pairs, tile
# padding included, summed over expert layers
SERVE_COUNTERS = ("selected", "context_keys", "expert_pairs",
                  "expert_load_max", "expert_rows")


class ServeCounts:
    """One serving dispatch's account of itself, filled while it is
    traced: ``real`` [B, T] says which rows are tokens (not bucket padding,
    not an idle slot of a decode batch), the layers add their traced
    counts (``expert_load_max`` keeps the largest).  ``expert_impl`` /
    ``interpret``: what the dispatch's expert layers run their grouped
    product on (``dropless_held_experts``).  The trainer's forward keeps
    the same account of a step (``real`` None: every row is a token;
    ``apply(counts=)``), of which the engine hands out ``TRAIN_COUNTERS``."""

    def __init__(self, real, expert_impl=None, interpret=False):
        self.real = real
        self.expert_impl = expert_impl
        self.interpret = interpret
        self.counts = {}

    def fresh(self):
        """The same dispatch, nothing counted yet (a scan's iteration)."""
        return ServeCounts(self.real, self.expert_impl, self.interpret)

    def add(self, **counts):
        for name, value in counts.items():
            if name == "expert_load_max":
                self.counts[name] = jnp.maximum(self.counts.get(name, 0),
                                                value)
            else:
                self.counts[name] = self.counts.get(name, 0) + value

    def vector(self):
        return jnp.stack([jnp.asarray(self.counts.get(name, 0), jnp.int32)
                          for name in SERVE_COUNTERS])

    def absorb(self, vectors):
        """Add what the iterations of a scan counted: ``vectors``
        [n, len(SERVE_COUNTERS)], each one iteration's :meth:`vector`."""
        for name, column in zip(SERVE_COUNTERS, vectors.T):
            self.add(**{name: jnp.max(column) if name == "expert_load_max"
                        else jnp.sum(column)})


# what a training step of a dropless expert model counts on the device
# (``loss(counted=True)`` returns them as one int32 vector beside the loss,
# summed over the expert layers; the engine sums them over a step's
# micro-batches, the load's maximum kept: ``train/moe/<name>`` gauges)
TRAIN_COUNTERS = ("expert_pairs", "expert_load_max", "expert_rows")


# what the flash kernels of one training step do, reckoned from the shapes
# when the step is built (``CausalTransformerLM.attention_plan``; static,
# so the engine sets them once: ``train/attn/<name>`` gauges).  Masked over
# visited is the share of tiles that pay for a mask, needed over visited
# the most the kernels' share of their roofline can read
ATTN_PLAN = ("tiles_visited", "tiles_masked", "pairs_visited",
             "pairs_needed")
# beside them, ``train/attn/saved_residual_bytes``: what ONE layer keeps of
# ONE micro-batch's flash call for the backward pass (its result and
# ``lse``), 0 where the forward kernel runs again instead
# (``CausalTransformerLM.saved_attention_bytes``)
ATTN_SAVED = "saved_residual_bytes"

# the ``remat_policy`` names (of ``jax.checkpoint_policies``) that keep a
# layer's matrix products for the backward pass.  The flash call is a
# product like them, but a ``pallas_call`` and no ``dot_general``: under
# these its result and ``lse`` are kept by name (``TransformerConfig
# .checkpoint_policy``), or the whole forward kernel runs a second time in
# every layer's backward pass
PRODUCT_SAVING_POLICIES = (
    "dots_saveable", "checkpoint_dots", "dots_with_no_batch_dims_saveable",
    "checkpoint_dots_with_no_batch_dims", "everything_saveable")


def merge_train_counters(a, b):
    """Two micro-batches' ``TRAIN_COUNTERS`` vectors as one."""
    largest = jnp.asarray([n.endswith("_max") for n in TRAIN_COUNTERS])
    return jnp.where(largest, jnp.maximum(a, b), a + b)


def _hold_expert_stack(stacked_layer):
    """A stacked layer [n, ...] as (what a scan over it may cut a layer
    out of, its dropless experts' leaves, kept whole): the held experts'
    weights are most of a layer, and the grouped product reads a stack in
    place (``dropless_held_experts(layer=)``)."""
    moe = stacked_layer.get("moe")
    if not moe:
        return stacked_layer, {}
    held = {k: moe[k] for k in ("w_gate", "w_up", "w_down")}
    rest = {k: v for k, v in moe.items() if k not in held}
    return dict(stacked_layer, moe=rest), held


# "gelu" is the tanh approximation (GPT-2 gelu_new / Gemma
# gelu_pytorch_tanh); "gelu_exact" the erf form (MPT).  One table shared
# by the dense MLP and the MoE expert_fn so the two can never disagree.
_ACTIVATIONS = {
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),
}


# ``jax.named_scope``s below (``embed``, ``norm``, ``attn``, ``mlp``,
# ``loss_head``) put the model's parts into every instruction's ``op_name``:
# an xprof capture groups by them, and ``telemetry.op_scopes`` reads the
# phase of a compiled step's instructions from the same path.
@jax.named_scope("norm")
def _norm(x, weight, eps, use_rms, bias=None):
    xf = x.astype(jnp.float32)
    if use_rms:
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


@jax.named_scope("loss_head")
def next_token_xent(logits, batch):
    """Next-token cross-entropy shared by the dense model and the pipeline
    default loss.  ``batch``: dict with ``input_ids`` [B,S] (+ optional
    ``labels``, ``loss_mask``) or a raw [B,S] array.  When ``labels`` is
    absent, labels are the inputs shifted left and the last logit is dropped."""
    if isinstance(batch, dict):
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
        loss_mask = batch.get("loss_mask")
    else:
        input_ids, labels, loss_mask = batch, None, None
    if labels is None:
        labels = input_ids[:, 1:]
        logits = logits[:, :-1]
        if loss_mask is not None:
            loss_mask = loss_mask[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if loss_mask is not None:
        return jnp.sum(nll * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1)
    return jnp.mean(nll)


def _pre_norm(x, layer, key, c):
    """Pre-sub-block norm.  Identity ONLY under ``post_norm_only``
    (OLMo2's blocks omit the pre-norms entirely); for every other
    architecture a missing weight stays a loud KeyError so a conversion
    bug cannot silently run un-normalized activations."""
    if c.post_norm_only:
        w = layer.get(key)
        if w is None:
            return x
        return _norm(x, w, c.norm_eps, c.use_rmsnorm,
                     layer.get(key + "_b"))
    return _norm(x, layer[key], c.norm_eps, c.use_rmsnorm,
                 layer.get(key + "_b"))


def _softcap(logits, cap):
    """Gemma-2 tanh capping: bounded logits, one definition for every
    head/loss path so decode can never drift from the full forward."""
    if cap:
        return cap * jnp.tanh(logits / cap)
    return logits


@jax.named_scope("loss_head")
def chunked_next_token_xent(x, head, head_b, batch, chunk_size: int,
                            logit_softcap=None, logit_scale=None):
    """Next-token cross-entropy WITHOUT materializing the full fp32
    ``[B, S, V]`` logits tensor: the flattened token stream is processed in
    ``chunk_size``-token chunks under a remat'd ``lax.scan`` — each chunk's
    ``[chunk, V]`` logits live only inside its scan step (and are recomputed
    in the backward), so peak HBM for the loss drops from ``O(B*S*V)`` to
    ``O(chunk*V)``.  At GPT vocab (50k) the logits buffer, not the model
    states, caps the trainable micro-batch, so this buys batch (and MFU)
    directly.  Per-token softmax is independent of the chunking: numerics
    equal :func:`next_token_xent` up to fp reassociation of the mean.

    ``x``: final-normed hidden ``[B, S, d]``; ``head``: ``[d, V]``;
    ``head_b``: ``[V]`` or None; ``batch`` as in :func:`next_token_xent`.
    """
    if isinstance(batch, dict):
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
        loss_mask = batch.get("loss_mask")
    else:
        input_ids, labels, loss_mask = batch, None, None
    if labels is None:
        labels = input_ids[:, 1:]
        x = x[:, :-1]
        if loss_mask is not None:
            loss_mask = loss_mask[:, 1:]

    B, S, d = x.shape
    n = B * S
    xt = x.reshape(n, d)
    yt = labels.reshape(n)
    mt = (jnp.ones((n,), jnp.float32) if loss_mask is None
          else loss_mask.reshape(n).astype(jnp.float32))

    chunk = max(1, min(int(chunk_size), n))
    pad = (-n) % chunk
    if pad:
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
        yt = jnp.pad(yt, (0, pad))
        mt = jnp.pad(mt, (0, pad))
    steps = (n + pad) // chunk
    xt = xt.reshape(steps, chunk, d)
    yt = yt.reshape(steps, chunk)
    mt = mt.reshape(steps, chunk)

    head_c = head.astype(x.dtype)
    bias32 = None if head_b is None else head_b.astype(jnp.float32)

    @jax.checkpoint
    def body(carry, xs):
        xc, yc, mc = xs
        logits = (xc @ head_c).astype(jnp.float32)
        if bias32 is not None:
            logits = logits + bias32
        if logit_scale is not None:
            logits = logits * logit_scale
        logits = _softcap(logits, logit_softcap)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        nll_sum, m_sum = carry
        return (nll_sum + jnp.sum((lse - ll) * mc),
                m_sum + jnp.sum(mc)), None

    (nll_sum, m_sum), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (xt, yt, mt))
    return nll_sum / jnp.maximum(m_sum, 1.0)


def _rope(x, positions, theta, rope_dim=None, inv_freq=None, magnitude=1.0):
    """Rotary embedding; x: [B, S, H, D].  ``rope_dim`` < D rotates only the
    leading dims (GPT-NeoX partial rotary).  ``inv_freq``: per-dim inverse
    frequencies overriding the theta power law — how Llama-3 / linear
    rope scaling ships (the policy precomputes the scaled table).
    ``magnitude``: what cos and sin are multiplied by (YaRN's
    ``attention_factor``)."""
    if rope_dim is not None and rope_dim < x.shape[-1]:
        rot, rest = x[..., :rope_dim], x[..., rope_dim:]
        return jnp.concatenate(
            [_rope(rot, positions, theta, inv_freq=inv_freq,
                   magnitude=magnitude), rest], axis=-1)
    B, S, H, D = x.shape
    half = D // 2
    if inv_freq is not None:
        freqs = jnp.asarray(inv_freq, jnp.float32)
        assert freqs.shape == (half,), \
            (f"rope_inv_freq must cover the rotated slice: expected "
             f"length {half}, got {freqs.shape}")
    else:
        freqs = jnp.exp(-math.log(theta) *
                        jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def alibi_slopes(n_heads: int) -> jnp.ndarray:
    """Per-head ALiBi slopes (Bloom; reference serves Bloom through
    ``module_inject/containers/bloom.py`` whose kernels consume the same
    slope schedule).  Matches HF ``build_alibi_tensor``: geometric slopes
    for the largest power-of-two head count, interleaved extras beyond."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start ** (i + 1) for i in range(n)]

    n = 2 ** math.floor(math.log2(n_heads))
    slopes = pow2_slopes(n)
    if n < n_heads:
        slopes += pow2_slopes(2 * n)[0::2][: n_heads - n]
    return jnp.asarray(slopes, jnp.float32)


class CausalTransformerLM:
    """Functional model: ``init`` → params pytree; ``apply`` → logits;
    ``loss`` → scalar (the engine's model contract)."""

    def __init__(self, config: TransformerConfig):
        self.config = config
        self.gate = None
        c = config
        for name in ("local_attn_pattern", "rope_pattern", "ssm_pattern",
                     "lin_pattern"):
            pattern = getattr(c, name)
            assert pattern is None or len(pattern) == c.n_layers, \
                f"{name} has {len(pattern)} entries for {c.n_layers} layers"
        if c.rope_pattern is not None or c.layer_period:
            assert c.layers_listed, (
                "rope_pattern / layer_period need a listed layer stack: a "
                "model with expert layers, latent attention, state-space "
                "layers, linear-attention layers or block-sparse attention "
                "(TransformerConfig.layers_listed)")
        if c.has_ssm:
            assert c.ssm_heads % c.ssm_groups == 0 and c.ssm_state > 0 \
                and c.ssm_head_dim > 0 and c.ssm_conv > 1, \
                "a state-space layer needs its sizes (ssm_heads, " \
                "ssm_head_dim, ssm_state, ssm_groups, ssm_conv)"
            assert not (c.is_latent or c.attn_window or c.parallel_block), \
                "state-space layers stand among plain attention layers"
        if c.has_lin:
            assert c.lin_heads > 0 and c.lin_head_dim > 0, \
                "a linear-attention layer needs lin_heads and lin_head_dim"
            assert not (c.is_latent or c.attn_window or c.parallel_block
                        or c.has_ssm), (
                "linear-attention layers stand among plain (or "
                "block-sparse) attention layers")
        if c.has_sparse:
            c.sparse.check()
            assert not (c.is_latent or c.attn_window or c.use_alibi
                        or c.attn_logit_softcap), (
                "block-sparse attention selects over plain causal "
                "grouped-query attention")
        if c.layer_period:
            lead, period = c.leading_layers, c.layer_period
            assert (c.n_layers - lead) % period == 0 and \
                c.moe_layer_freq == 1, (
                    f"{c.n_layers} layers do not make whole periods of "
                    f"{period} after the {lead} leading ones")
            for i in range(lead, c.n_layers):
                at = lead + (i - lead) % period
                assert (c.layer_window(i), c.layer_rotary(i),
                        c.layer_ssm(i), c.layer_lin(i)) == \
                    (c.layer_window(at), c.layer_rotary(at),
                     c.layer_ssm(at), c.layer_lin(at)), \
                    f"layer {i} does not repeat layer {at}'s pattern"
        if config.is_moe and not config.moe_dropless:
            from deepspeed_tpu.moe.sharded_moe import TopKGate
            self.gate = TopKGate(
                config.hidden_size, config.moe_num_experts,
                k=config.moe_top_k,
                capacity_factor=config.moe_capacity_factor,
                eval_capacity_factor=(config.moe_eval_capacity_factor
                                      if config.moe_eval_capacity_factor
                                      is not None
                                      else config.moe_capacity_factor),
                min_capacity=config.moe_min_capacity,
                noisy_gate_policy=config.moe_noisy_gate_policy,
                norm_topk_prob=config.moe_norm_topk_prob)

    def _is_moe_layer(self, i: int) -> bool:
        # reference convention: every Nth layer hosts experts (freq=2 →
        # alternating dense/MoE, MoE on odd layers)
        c = self.config
        if i < c.first_dense_layers:
            return False
        return c.is_moe and (i % c.moe_layer_freq == c.moe_layer_freq - 1)

    # ------------------------------------------------------------------
    def init(self, rng, dtype=jnp.float32) -> Dict[str, Any]:
        c = self.config
        d, f, v = c.hidden_size, c.ffn_dim, c.vocab_size
        dh, H, Hkv, L = c.head_dim, c.n_heads, c.kv_heads, c.n_layers
        keys = jax.random.split(rng, 16)

        def dense(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32) /
                    math.sqrt(fan_in)).astype(dtype)

        # dense() divides by sqrt(fan_in): the fan-in that gives the
        # embeddings' seeded spread
        embed_fan = d if c.init_embed_std is None else c.init_embed_std ** -2
        head_fan = d if c.init_head_std is None else c.init_head_std ** -2
        if c.layers_listed:
            return self._init_moe(rng, dtype, dense, embed_fan, head_fan)

        layers = {
            "attn_norm": jnp.ones((L, d), dtype),
            "wq": dense(keys[0], (L, d, H * dh), d),
            "wk": dense(keys[1], (L, d, Hkv * dh), d),
            "wv": dense(keys[2], (L, d, Hkv * dh), d),
            "wo": dense(keys[3], (L, H * dh, d), H * dh),
            "mlp_norm": jnp.ones((L, d), dtype),
            "w_up": dense(keys[4], (L, d, f), d),
            "w_down": dense(keys[5], (L, f, d), f),
        }
        if c.gated:
            layers["w_gate"] = dense(keys[6], (L, d, f), d)
        if c.qk_norm:
            qd, kd = ((H * dh, Hkv * dh) if c.qk_norm == "rms_flat"
                      else (dh, dh))
            layers["q_norm"] = jnp.ones((L, qd), dtype)
            layers["k_norm"] = jnp.ones((L, kd), dtype)
            if c.qk_norm == "layernorm" and c.norm_bias:
                layers["q_norm_b"] = jnp.zeros((L, qd), dtype)
                layers["k_norm_b"] = jnp.zeros((L, kd), dtype)
        if c.use_bias:
            for name, width in (("wq_b", H * dh), ("wk_b", Hkv * dh),
                                ("wv_b", Hkv * dh), ("wo_b", d),
                                ("w_up_b", f), ("w_down_b", d)):
                layers[name] = jnp.zeros((L, width), dtype)
        if c.post_norm_only:
            # OLMo2 blocks: x + post_norm(sublayer(x)) — no pre-norms at
            # all.  Fresh init must create the post-norm weights, not the
            # pre-norm ones, or the configured architecture silently
            # degrades to un-normalized blocks (the converted-checkpoint
            # path supplies these keys; init now matches it).
            del layers["attn_norm"], layers["mlp_norm"]
            layers["attn_post_norm"] = jnp.ones((L, d), dtype)
            layers["mlp_post_norm"] = jnp.ones((L, d), dtype)
        if c.norm_bias and not c.post_norm_only:
            layers["attn_norm_b"] = jnp.zeros((L, d), dtype)
            layers["mlp_norm_b"] = jnp.zeros((L, d), dtype)
        params = {
            "tok_embed": dense(keys[7], (v, d), embed_fan),
            "final_norm": jnp.ones((d,), dtype),
            "layers": layers,
        }
        if c.norm_bias:
            params["final_norm_b"] = jnp.zeros((d,), dtype)
        if c.embed_norm:
            params["embed_norm"] = jnp.ones((d,), dtype)
            if c.norm_bias:
                params["embed_norm_b"] = jnp.zeros((d,), dtype)
        if not c.use_rope and not c.use_alibi:
            params["pos_embed"] = dense(keys[8], (c.max_seq_len, d), d)
        if not c.tie_embeddings:
            params["lm_head"] = dense(keys[9], (d, v), head_fan)
            if c.lm_head_bias:
                params["lm_head_b"] = jnp.zeros((v,), dtype)
        return params

    def _init_moe(self, rng, dtype, dense, embed_fan, head_fan):
        """MoE variant: ``layers`` is a LIST of per-layer dicts (layers
        differ in structure, so the forward unrolls instead of scanning —
        reference MoE models interleave dense/expert layers the same way)."""
        c = self.config
        d, f, v = c.hidden_size, c.ffn_dim, c.vocab_size
        dh, H, Hkv, E = c.head_dim, c.n_heads, c.kv_heads, c.moe_num_experts
        keys = jax.random.split(rng, c.n_layers + 4)

        def one_layer(key, moe: bool, ssm: bool = False,
                      lin: bool = False):
            ks = jax.random.split(key, 8)
            norm_keys = (("attn_post_norm", "mlp_post_norm")
                         if c.post_norm_only else ("attn_norm", "mlp_norm"))
            if c.sandwich_norm:
                norm_keys += ("attn_post_norm", "mlp_post_norm")
            if ssm:
                layer = {"ssm": self._init_ssm(ks[0], dtype, dense)}
                layer.update({k: jnp.ones((d,), dtype) for k in norm_keys})
            elif lin:
                layer = {"lin": self._init_lin(ks[0], dtype, dense)}
                layer.update({k: jnp.ones((d,), dtype) for k in norm_keys})
            elif c.is_latent:
                layer = self._init_latent_attn(ks[0], dtype, dense)
                layer.update({k: jnp.ones((d,), dtype) for k in norm_keys})
            else:
                layer = {
                    "wq": dense(ks[0], (d, H * dh), d),
                    "wk": dense(ks[1], (d, Hkv * dh), d),
                    "wv": dense(ks[2], (d, Hkv * dh), d),
                    "wo": dense(ks[3], (H * dh, d), H * dh),
                }
                layer.update({k: jnp.ones((d,), dtype) for k in norm_keys})
                if c.attn_gate:
                    layer["wg_attn"] = dense(jax.random.fold_in(ks[0], 1),
                                             (d, H * dh), d)
            if c.qk_norm and not (ssm or lin):
                qd, kd = ((H * dh, Hkv * dh) if c.qk_norm == "rms_flat"
                          else (dh, dh))
                layer["q_norm"] = jnp.ones((qd,), dtype)
                layer["k_norm"] = jnp.ones((kd,), dtype)
                if c.qk_norm == "layernorm" and c.norm_bias:
                    layer["q_norm_b"] = jnp.zeros((qd,), dtype)
                    layer["k_norm_b"] = jnp.zeros((kd,), dtype)
            if moe and c.moe_dropless:
                # the router keeps its published width; the expert leaves
                # hold this chip's share (experts 0..held-1)
                fe, held = c.moe_ffn_hidden_size or f, c.experts_held
                kg, kb, ksh = jax.random.split(ks[4], 3)
                layer["moe"] = {
                    "wg": dense(kg, (d, E), d).astype(jnp.float32),
                    "w_up": dense(ks[5], (held, d, fe), d),
                    "w_down": dense(ks[6], (held, fe, d), fe),
                    "w_gate": dense(ks[7], (held, d, fe), d),
                }
                if c.moe_scoring == "sigmoid":      # the selection bias
                    layer["moe"]["router_bias"] = 0.02 * jax.random.normal(
                        kb, (E,), jnp.float32)
                if c.moe_shared_experts:
                    fs = fe * c.moe_shared_experts
                    k0, k1, k2 = jax.random.split(ksh, 3)
                    layer["moe"]["shared"] = {
                        "w_gate": dense(k0, (d, fs), d),
                        "w_up": dense(k1, (d, fs), d),
                        "w_down": dense(k2, (fs, d), fs)}
            elif moe:
                layer["moe"] = {
                    "wg": dense(ks[4], (d, E), d).astype(jnp.float32),
                    "w_up": dense(ks[5], (E, d, f), d),
                    "w_down": dense(ks[6], (E, f, d), f),
                }
                if c.gated:          # SwiGLU/GLU experts (Mixtral)
                    layer["moe"]["w_gate"] = dense(ks[7], (E, d, f), d)
            else:
                layer["w_up"] = dense(ks[5], (d, f), d)
                layer["w_down"] = dense(ks[6], (f, d), f)
                if c.gated:
                    layer["w_gate"] = dense(ks[7], (d, f), d)
            return layer

        lead, period = c.leading_layers, c.layer_period
        params = {
            "tok_embed": dense(keys[-1], (v, d), embed_fan),
            "final_norm": jnp.ones((d,), dtype),
            "layers": [one_layer(keys[i], self._is_moe_layer(i),
                                 c.layer_ssm(i), c.layer_lin(i))
                       for i in range(lead)],
        }
        if period:
            # the layers of one position in the period, made stacked (each
            # from the key the list layout would give it): a stack of
            # finished layers would hold the weights twice
            params["periods"] = [
                jax.vmap(functools.partial(
                    one_layer, moe=self._is_moe_layer(lead + j),
                    ssm=c.layer_ssm(lead + j), lin=c.layer_lin(lead + j)))(
                        keys[lead + j:c.n_layers:period])
                for j in range(period)]
        if not c.use_rope:
            params["pos_embed"] = dense(keys[-2], (c.max_seq_len, d), d)
        if not c.tie_embeddings:
            params["lm_head"] = dense(keys[-3], (d, v), head_fan)
        return params

    def _init_ssm(self, key, dtype, dense):
        """One state-space layer's mixer: the joint projection to
        [z | x B C | dt], the depthwise convolution over x B C (``conv_w``
        [K, C], row K - 1 on the current input) with its bias, a decay
        rate ``A_log``, a skip ``D`` and a ``dt_bias`` a head, the gated
        norm and the output projection.

        The three per-head vectors are seeded as Mamba-2's own
        initialiser seeds them: ``dt`` log-uniform in [0.001, 0.1] with
        ``dt_bias`` its inverse softplus, ``A`` uniform in [1, 16], ``D``
        ones: time constants from one token to a thousand.  (``log(1..H)``
        and ones, the values a converted checkpoint would overwrite, decay
        by ``exp(-1.3 h)`` a step: a seeded model that forgets within two
        tokens, on which a lost state would read as nothing.)"""
        c = self.config
        d, H, K = c.hidden_size, c.ssm_heads, c.ssm_conv
        inner, conv = c.ssm_inner, c.ssm_conv_dim
        ks = jax.random.split(key, 6)

        def uniform(key, shape, lo, hi):
            return jax.random.uniform(key, shape, jnp.float32, lo, hi)

        dt = jnp.exp(uniform(ks[2], (H,), math.log(1e-3), math.log(1e-1)))
        bound = 1.0 / math.sqrt(K)
        return {
            "w_in": dense(ks[0], (d, inner + conv + H), d),
            "conv_w": uniform(ks[1], (K, conv), -bound, bound).astype(dtype),
            "conv_b": uniform(ks[5], (conv,), -bound, bound).astype(dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(uniform(ks[3], (H,), 1.0, 16.0)).astype(dtype),
            "D": jnp.ones((H,), dtype),
            "norm": jnp.ones((inner,), dtype),
            "w_out": dense(ks[4], (inner, d), inner),
        }

    def _init_lin(self, key, dtype, dense):
        """One linear-attention layer's mixer: q, k and v of ``lin_heads``
        heads of ``lin_head_dim`` each, the output gate ``wg``, the norm
        over the heads' concatenated output, the output projection, and
        under ``qk_norm`` a per-head norm weight for q and k.  The decay a
        head is no weight (``ops/linear_attention.py decay_slopes``)."""
        c = self.config
        d, D = c.hidden_size, c.lin_head_dim
        inner = c.lin_heads * D
        ks = jax.random.split(key, 5)
        w = {"wq": dense(ks[0], (d, inner), d),
             "wk": dense(ks[1], (d, inner), d),
             "wv": dense(ks[2], (d, inner), d),
             "wg": dense(ks[3], (d, inner), d),
             "norm": jnp.ones((inner,), dtype),
             "wo": dense(ks[4], (inner, d), inner)}
        if c.qk_norm:
            w["q_norm"] = jnp.ones((D,), dtype)
            w["k_norm"] = jnp.ones((D,), dtype)
        return w

    def _init_latent_attn(self, key, dtype, dense):
        """One layer's latent-attention weights: the query's low-rank pair
        with its norm, the joint [c_kv | k_rope] projection with the
        latent's norm, the per-head decompression ``wkv_b`` ([k_nope | v]
        a head: ``W_uk`` and ``W_uv`` of the absorbed form), ``wo``, and
        the indexer (query heads off the query latent, one LayerNormed key
        a token, a weight a head)."""
        c = self.config
        d, H, R, Rq = c.hidden_size, c.n_heads, c.kv_lora_rank, c.q_lora_rank
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        ks = jax.random.split(key, 8)
        layer = {
            "wq_a": dense(ks[0], (d, Rq), d),
            "q_a_norm": jnp.ones((Rq,), dtype),
            "wq_b": dense(ks[1], (Rq, H * (dn + dr)), Rq),
            "wkv_a": dense(ks[2], (d, R + dr), d),
            "kv_a_norm": jnp.ones((R,), dtype),
            "wkv_b": dense(ks[3], (R, H * (dn + dv)), R),
            "wo": dense(ks[4], (H * dv, d), H * dv),
        }
        if c.index_topk:
            Hi, Di = c.index_n_heads, c.index_head_dim
            layer.update({
                "idx_wq": dense(ks[5], (Rq, Hi * Di), Rq),
                "idx_wk": dense(ks[6], (d, Di), d),
                "idx_k_norm": jnp.ones((Di,), dtype),
                "idx_k_norm_b": jnp.zeros((Di,), dtype),
                "idx_w": dense(ks[7], (d, Hi), d)})
        return layer

    # ------------------------------------------------------------------
    def tp_rules(self):
        """Megatron-style split over the ``tp`` axis: column-parallel in,
        row-parallel out (reference auto-TP ``module_inject/auto_tp.py``)."""
        if self.config.is_moe:
            from deepspeed_tpu.parallel.topology import EP_AXIS
            return [
                # shared (always-on) expert first: 2-D leaves that the
                # 3-D expert patterns below must not capture
                (r"moe.*shared.*wg", P()),
                (r"moe.*shared.*(w_gate|w_up)", P(None, TP_AXIS)),
                (r"moe.*shared.*w_down", P(TP_AXIS, None)),
                # expert biases first (the weight patterns would match them)
                (r"moe.*w_up_b", P(EP_AXIS, TP_AXIS)),
                (r"moe.*w_down_b", P(EP_AXIS, None)),
                # expert weights: expert dim over ep, ffn dim over tp
                (r"moe.*w_gate", P(EP_AXIS, None, TP_AXIS)),
                (r"moe.*w_up", P(EP_AXIS, None, TP_AXIS)),
                (r"moe.*w_down", P(EP_AXIS, TP_AXIS, None)),
                (r"moe.*wg", P()),
                # per-layer dense biases / norms
                (r"wq_b|wk_b|wv_b|w_up_b|w_gate_b", P(TP_AXIS)),
                (r"wo_b|w_down_b|_norm", P()),
                # per-layer dense weights are 2-D in the MoE layout
                (r"wq|wk|wv|w_up|w_gate", P(None, TP_AXIS)),
                (r"\bwo|w_down", P(TP_AXIS, None)),
                (r"lm_head", P(None, TP_AXIS)),
            ]
        return [
            # biases first: the generic weight patterns would also match them
            (r"wq_b|wk_b|wv_b|w_up_b|w_gate_b", P(None, TP_AXIS)),
            (r"wo_b|w_down_b|_norm", P()),
            (r"wq|wk|wv|w_up|w_gate", P(None, None, TP_AXIS)),
            (r"wo|w_down", P(None, TP_AXIS, None)),
            (r"lm_head", P(None, TP_AXIS)),
        ]

    # ------------------------------------------------------------------
    @staticmethod
    def _proj(h, layer, name):
        out = h @ layer[name]
        if f"{name}_b" in layer:
            out = out + layer[f"{name}_b"].astype(out.dtype)
        return out

    def _latent_qkv(self, h, layer, positions):
        """A latent-attention layer's query side, key side and indexer
        from the pre-normed input (what ``_qkv`` hands the latent mixers
        as its q, k, v): ``LatentQuery(q_nope [B,T,H,dn], q_rope
        [B,T,H,dr])``, ``LatentKey(c_kv [B,T,R], k_rope [B,T,dr])`` and
        ``Indexer(q [B,T,Hi,Di], w [B,T,Hi], k [B,T,Di])`` (None without a
        selection).  Rotary pairs are (2i, 2i+1); the indexer turns the
        FIRST ``qk_rope_head_dim`` values of its heads."""
        from deepspeed_tpu.ops.latent_attention import (RopeYarn,
                                                        rope_interleaved)
        c = self.config
        B, T, _ = h.shape
        H, R = c.n_heads, c.kv_lora_rank
        dn, dr = c.qk_nope_head_dim, c.qk_rope_head_dim
        rope = functools.partial(rope_interleaved, positions=positions,
                                 theta=c.rope_theta)
        if c.rope_yarn is not None:
            yarn = RopeYarn(*c.rope_yarn)
            rope = functools.partial(
                rope, inv_freq=yarn.inv_freq(dr, c.rope_theta),
                magnitude=yarn.rotary_magnitude)
        with jax.named_scope("latent_attn"):
            c_q = _norm(h @ layer["wq_a"], layer["q_a_norm"], c.norm_eps,
                        True)
            q = (c_q @ layer["wq_b"]).reshape(B, T, H, dn + dr)
            kv = h @ layer["wkv_a"]
            query = LatentQuery(q[..., :dn], rope(q[..., dn:]))
            key = LatentKey(
                _norm(kv[..., :R], layer["kv_a_norm"], c.norm_eps, True),
                rope(kv[..., R:]))
        if not c.index_topk:
            return query, key, None
        with jax.named_scope("select"):
            q_i = (c_q @ layer["idx_wq"]).reshape(
                B, T, c.index_n_heads, c.index_head_dim)
            k_i = _norm(h @ layer["idx_wk"], layer["idx_k_norm"],
                        c.index_norm_eps, False, layer["idx_k_norm_b"])
            turn = lambda x: jnp.concatenate(   # noqa: E731
                [rope(x[..., :dr]), x[..., dr:]], axis=-1)
            return query, key, Indexer(turn(q_i), h @ layer["idx_w"],
                                       turn(k_i))

    def _qkv(self, h, layer, B, S, positions, rotary=True):
        c = self.config
        if c.is_latent:
            return self._latent_qkv(h, layer, positions)
        H, Hkv, dh = c.n_heads, c.kv_heads, c.head_dim
        qf = self._proj(h, layer, "wq")
        kf = self._proj(h, layer, "wk")
        if c.qk_norm == "rms_flat":
            # OLMo2: RMS over the WHOLE flat projection (variance pooled
            # across heads), weights [H*dh] / [Hkv*dh], pre-reshape
            qf = _norm(qf, layer["q_norm"], c.norm_eps, True)
            kf = _norm(kf, layer["k_norm"], c.norm_eps, True)
        q = qf.reshape(B, S, H, dh)
        k = kf.reshape(B, S, Hkv, dh)
        v = self._proj(h, layer, "wv").reshape(B, S, Hkv, dh)
        if c.clip_qkv:
            # OLMo / MPT-30b / DBRX: clamp the projections pre-rope
            lim = jnp.asarray(c.clip_qkv, q.dtype)
            q = jnp.clip(q, -lim, lim)
            k = jnp.clip(k, -lim, lim)
            v = jnp.clip(v, -lim, lim)
        if c.qk_norm and c.qk_norm != "rms_flat":
            # Qwen3-style per-head q/k norm over head_dim, pre-rope
            # (weight [dh] broadcasts over [B, S, H, dh])
            rms = c.qk_norm == "rms"
            q = _norm(q, layer["q_norm"], c.norm_eps, rms,
                      layer.get("q_norm_b"))
            k = _norm(k, layer["k_norm"], c.norm_eps, rms,
                      layer.get("k_norm_b"))
        if c.use_rope and rotary:
            turn = functools.partial(_rope, positions=positions,
                                     theta=c.rope_theta, rope_dim=c.rope_dim,
                                     inv_freq=c.rope_inv_freq)
            if rotary == "yarn":    # a full-attention layer's own kind
                from deepspeed_tpu.ops.latent_attention import RopeYarn
                yarn = RopeYarn(*c.rope_yarn)
                turn = functools.partial(
                    turn, inv_freq=yarn.inv_freq(c.rotary_dim, c.rope_theta),
                    magnitude=yarn.rotary_magnitude)
            q, k = turn(q), turn(k)
        return q, k, v

    def _attn_bias(self, layer, Sq, Sk):
        """Additive attention bias beyond the causal mask: ALiBi slopes
        (Bloom) and/or a per-layer sliding window (GPT-Neo ``local``
        layers; ``layer['attn_window']`` is a traced scalar, 0 = global).
        Returns None when neither applies so the flash path stays usable."""
        c = self.config
        from deepspeed_tpu.ops.attention import alibi_window_bias
        return alibi_window_bias(
            Sq, Sk,
            slopes=alibi_slopes(c.n_heads) if c.use_alibi else None,
            window=layer.get("attn_window"))

    # ------------------------------------------------------------------
    # The mixers: what turns one layer's q, k, v into its attention output,
    # and what that does to the layer's cache on the way.  The one thing a
    # forward hands to ``block``: ``mix(q, k, v, layer, cache) -> (attn
    # [B, T, H, dh], cache)``.  A new kind of cache is one more of these.
    # ------------------------------------------------------------------
    def mix_full(self, q, k, v, layer, cache):
        """Causal attention over the whole sequence, no cache (the trainer,
        ``apply``)."""
        c = self.config
        if c.has_sparse and q.shape[1] >= c.sparse.dense_len:
            # a whole sequence is its own context: at ``sparse.dense_len``
            # or more every query attends its selected blocks, the
            # selection a mask over the sequence's keys
            from deepspeed_tpu.ops.block_sparse_attention import \
                sparse_prefill_attention
            return sparse_prefill_attention(q, k, v, c.sparse,
                                            c.attn_scale), cache
        window = layer.get("attn_window")
        if c.attn_window and isinstance(window, int):
            # the layer's kind is static (a scanned period's place): its
            # work carries the kind's name, and a full layer takes the
            # plain causal path
            layer = dict(layer)
            if window:
                layer["attn_window"] = jnp.int32(window)
            else:
                del layer["attn_window"]
            with jax.named_scope("attn_window" if window else "attn_full"):
                return self.mix_full(q, k, v, layer, cache)
        H, Hkv = c.n_heads, c.kv_heads
        has_alibi = c.use_alibi
        has_window = "attn_window" in layer
        on_cpu = jax.default_backend() in ("cpu",)
        if has_alibi or has_window:
            # ALiBi / sliding-window ride the flash kernel's in-kernel bias
            # (slope·kpos + window mask; far-past K blocks skipped), so
            # Bloom / GPT-Neo / Mistral stay on the fast path.  attention()
            # owns the pallas-vs-reference policy and its loud fallback;
            # ring/ulysses don't take a bias, so those impls serve the
            # biased layers via the reference path as before
            impl = (c.attn_impl if c.attn_impl in ("auto", "pallas",
                                                   "reference")
                    else "reference")
            attn = attention(
                q, k, v, causal=True, softmax_scale=c.attn_scale,
                impl=impl, block_q=c.attn_block_q, block_k=c.attn_block_k,
                alibi_slopes=alibi_slopes(H) if has_alibi else None,
                window=layer["attn_window"] if has_window else None,
                interpret=on_cpu and impl == "pallas",
                logit_softcap=c.attn_logit_softcap)
        elif c.attn_impl == "ring":
            if c.attn_logit_softcap:
                raise ValueError(
                    "attn_logit_softcap is not implemented for the ring "
                    "attention path; use attn_impl='reference'/'auto'")
            from deepspeed_tpu.ops.ring_attention import ring_attention
            attn = ring_attention(q, k, v, causal=True,
                                  softmax_scale=c.attn_scale,
                                  layout=c.ring_layout)
        elif c.attn_impl == "ulysses":
            if c.attn_logit_softcap:
                raise ValueError(
                    "attn_logit_softcap is not implemented for the ulysses "
                    "attention path; use attn_impl='reference'/'auto'")
            from deepspeed_tpu.ops.ulysses import ulysses_attention, sp_degree
            sp = sp_degree()
            # K/V only need a head count divisible by sp for the all-to-all;
            # the inner attention handles the remaining GQA grouping, so
            # repeat by the smallest factor that reaches divisibility
            if sp > 1 and Hkv % sp != 0:
                group = H // Hkv
                r = next((r for r in range(1, group + 1)
                          if group % r == 0 and (Hkv * r) % sp == 0), group)
                k = jnp.repeat(k, r, axis=2)
                v = jnp.repeat(v, r, axis=2)
            attn = ulysses_attention(
                q, k, v, lambda q, k, v: attention(q, k, v, causal=True))
        elif c.attn_impl in ("auto", "pallas", "reference"):
            attn = attention(q, k, v, causal=True,
                             softmax_scale=c.attn_scale, impl=c.attn_impl,
                             block_q=c.attn_block_q, block_k=c.attn_block_k,
                             interpret=on_cpu and c.attn_impl == "pallas",
                             logit_softcap=c.attn_logit_softcap)
        else:
            raise ValueError(
                f"unknown attn_impl '{c.attn_impl}'; expected one of "
                "auto/pallas/reference/ring/ulysses")
        return attn, cache

    def mix_cached(self, q, k, v, layer, cache):
        """Append to one layer's dense ``KVCache`` and attend over it
        (``apply_with_cache``, ``InferenceEngine``'s streamed forward)."""
        c = self.config
        cache = update_cache(cache, k, v)
        bias = self._cached_attn_bias(layer, q.shape[1], cache.k.shape[2],
                                      cache.length)
        attn = decode_attention(q, cache, softmax_scale=c.attn_scale,
                                bias=bias,
                                logit_softcap=c.attn_logit_softcap)
        return attn, cache

    def mix_paged(self, q, k, v, layer, pools, *, index, block_tables,
                  lengths, impl, interpret, items):
        """Write this layer's rows into the STACKED page pools at
        ``lengths`` and attend over each sequence's ragged prefix, both in
        place by (traced) layer ``index`` (``apply_with_paged_cache``
        binds the keywords; ``items`` is what every layer's read shares).
        A full-attention layer of a window model reads and writes the
        same way, on that model's ``full`` stack."""
        from deepspeed_tpu.ops.paged_attention import (paged_decode_attention,
                                                       write_paged)
        c = self.config
        with (jax.named_scope("attn_full") if c.attn_window or c.has_state
              else contextlib.nullcontext()):
            pools = write_paged(pools, index, block_tables, lengths, k, v,
                                impl=impl, interpret=interpret)
            attn = paged_decode_attention(
                q, pools, block_tables, lengths + q.shape[1],
                softmax_scale=c.attn_scale, impl=impl, interpret=interpret,
                logit_softcap=c.attn_logit_softcap, layer=index, items=items)
        return attn, pools

    def mix_sparse_paged(self, q, k, v, layer, pools, *, index,
                         block_tables, lengths, context, read_lengths, impl,
                         interpret, items, counts=None):
        """A block-sparse attention layer on the serving path
        (``ops/block_sparse_attention.py``): layer ``index`` of the
        STACKED ``SparseKVCache``, its keys and values written and read as
        :meth:`mix_paged` does, its compressed keys beside them under the
        same tables.

        ``context`` [B] is each sequence's context at this dispatch (what
        it held and the tokens it brings).  A sequence under
        ``sparse.dense_len`` attends densely through the pages' own read
        (``read_lengths``: its length there, 0 for the others, so that
        read touches nothing of theirs; ``items`` is built from it); the
        others attend their selected blocks.  A decode step (T = 1)
        writes the compressed key its token completes, then reads the
        compressed keys of its table and ``sparse.topk`` blocks of K/V a
        (slot, key/value head) and nothing else of the pool; T > 1 is a
        prefill FROM AN EMPTY CONTEXT (``lengths`` 0: ``ServingEngine``
        refuses what would break that), which writes every compressed key
        its rows start and applies the selection as a mask over the keys
        it brings.  A decode dispatch counts ``selected`` /
        ``context_keys`` over its real rows (a prefill counts none: its
        early queries attend everything, and the ratio is the decode
        step's)."""
        from deepspeed_tpu.ops import block_sparse_attention as bsa
        from deepspeed_tpu.ops.paged_attention import (PagedKVCache,
                                                       paged_decode_attention,
                                                       write_paged)
        c = self.config
        sizes = c.sparse
        B, T = q.shape[:2]
        read = functools.partial(
            paged_decode_attention, softmax_scale=c.attn_scale, impl=impl,
            interpret=interpret, layer=index, items=items)
        with jax.named_scope("sparse_attn"):
            pools = pools._replace(**write_paged(
                PagedKVCache(pools.k_pages, pools.v_pages), index,
                block_tables, lengths, k, v, impl=impl,
                interpret=interpret)._asdict())
        kv = PagedKVCache(pools.k_pages, pools.v_pages)
        dense = (context < sizes.dense_len)[:, None, None, None]
        if T > 1:
            k, v = (k.astype(pools.k_pages.dtype),
                    v.astype(pools.v_pages.dtype))     # as the pages hold them
            with jax.named_scope("ckey_write"):
                c_keys = bsa.compress_keys(k, sizes)
                pools = pools._replace(c_pages=bsa.write_compressed_prefill(
                    pools.c_pages, index, block_tables, c_keys))
            with jax.named_scope("sparse_attn"):
                attn = read(q, kv, block_tables, read_lengths)
            if T < sizes.dense_len:     # no context of T rows selects
                return attn, pools
            chosen = jax.lax.cond(
                jnp.all(dense), lambda: jnp.zeros_like(q),
                lambda: bsa.sparse_prefill_attention(
                    q, k, v, sizes, c.attn_scale, c=c_keys))
            return jnp.where(dense, attn, chosen), pools
        with jax.named_scope("ckey_write"):
            pools = bsa.write_compressed_decode(pools, index, block_tables,
                                                lengths, sizes)
        with jax.named_scope("sparse_attn"):
            attn = read(q, kv, block_tables, read_lengths)
        chosen, attended = bsa.sparse_decode_attention(
            q[:, 0], pools, index, block_tables, context, sizes,
            c.attn_scale)
        if counts is not None:
            real = counts.real[:, 0]
            counts.add(
                selected=jnp.sum(jnp.where(
                    real, jnp.where(dense[:, 0, 0, 0], context, attended),
                    0)).astype(jnp.int32),
                context_keys=jnp.sum(jnp.where(real, context, 0)
                                     ).astype(jnp.int32))
        return jnp.where(dense, attn, chosen[:, None]), pools

    @jax.named_scope("attn_window")
    def mix_ring(self, q, k, v, layer, pool, *, index, window, ring_tables,
                 lengths, real_lengths, impl, interpret, items):
        """A sliding-window layer on the serving path: its keys and values
        live in a RING of pages a sequence (``ring_tables`` [B, ring]),
        layer ``index`` of the window layers' stack ``pool``.

        A decode step (T = 1) writes its row into the ring, over the
        oldest page's row of a ring ago, and attends through the ring, the
        mask by logical position.  T > 1 is a prefill FROM AN EMPTY CONTEXT
        (``lengths`` 0: ``ServingEngine`` refuses what would break that),
        which may be many times the ring: it attends over the rows it
        brings, laid out as pages of their own and read by the same kernel
        under the window, and leaves in the ring only what a later query
        can still see, the last ``ring x page`` of its ``real_lengths``
        rows, each in the row its position wraps to."""
        from deepspeed_tpu.ops.paged_attention import (PagedKVCache,
                                                       paged_decode_attention,
                                                       write_paged)
        c = self.config
        B, T, Hkv, dh = k.shape
        page, ring = pool.k_pages.shape[3], ring_tables.shape[1]
        kwargs = dict(softmax_scale=c.attn_scale, impl=impl,
                      interpret=interpret,
                      logit_softcap=c.attn_logit_softcap, window=window)
        write = functools.partial(write_paged, pool, index, ring_tables,
                                  impl=impl, interpret=interpret, ring=ring)
        if T == 1:
            pool = write(lengths, k, v)
            return paged_decode_attention(
                q, pool, ring_tables, lengths + 1, layer=index, ring=ring,
                items=items, **kwargs), pool
        n_pages = -(-T // page)

        # as the pool holds them: heads narrower than the lanes share a
        # row (ops/paged_attention.py kv_lane_pack)
        Hkv, dh = pool.k_pages.shape[2], pool.k_pages.shape[4]

        def as_pages(rows):     # [B, T, Hkv, dh] -> [1, B x n, Hkv, page, dh]
            rows = jnp.pad(rows.reshape(B, T, Hkv, dh),
                           ((0, 0), (0, n_pages * page - T),
                            (0, 0), (0, 0)))
            return jnp.swapaxes(rows.reshape(B * n_pages, page, Hkv, dh),
                                1, 2)[None].astype(pool.k_pages.dtype)

        fresh = PagedKVCache(as_pages(k), as_pages(v))
        attn = paged_decode_attention(
            q, fresh, jnp.arange(B * n_pages, dtype=jnp.int32
                                 ).reshape(B, n_pages),
            jnp.full((B,), T, jnp.int32), layer=0, items=items, **kwargs)
        held = ring * page
        if T > held:
            # ring row s takes the newest real position congruent to it
            s_ = jnp.arange(held)[None, :]
            at = s_ + held * ((real_lengths[:, None] - 1 - s_) // held)
            at = jnp.clip(at, 0, T - 1)
            # whole [Hkv, dh] rows by position (an index an element, as
            # ``take_along_axis`` would broadcast it, is a scalar gather)
            seq = jnp.arange(B)[:, None]
            k, v = k[seq, at], v[seq, at]
        return attn, write(jnp.zeros((B,), jnp.int32), k, v)

    # ------------------------------------------------------------------
    # The state-space mixer: between the pre-norm and the residual add in
    # place of q, k, v ... ``wo`` altogether.  ``block`` hands a layer
    # with ``ssm`` weights to ``mix(h, weights, cache) -> (delta, cache)``.
    # ------------------------------------------------------------------
    def _ssm_mixer(self, h, w, state, tail, real=None):
        """A Mamba-2 mixer over T rows a sequence.  h: [B, T, d], normed;
        ``state`` [B, H, P, N] float32 and ``tail`` [B, K - 1, C]: what
        the rows before row 0 left (zeros ahead of position 0); ``real``
        [B]: how many of the T rows are tokens (None: all), the others
        advance nothing.  Returns (delta [B, T, d], state, tail).  A
        decode dispatch (T = 1) hands as ``state`` the recurrence on the
        state where it lies, ``state(x, dt, A, B, C, D) -> (y, pool)``,
        and gets the pool back in the state's place."""
        from deepspeed_tpu.ops.ssm import causal_conv, ssd_scan, ssm_step
        c = self.config
        B, T, _ = h.shape
        H, P, N, G = c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups
        inner, conv = c.ssm_inner, c.ssm_conv_dim
        with jax.named_scope("ssm_proj"):
            # the product's float32 sums: z and x B C leave in the
            # activations' dtype, dt (a head's 64 columns, from which the
            # decays of hundreds of steps are made) as summed
            z, xbc, dt = jnp.split(
                jnp.dot(h, w["w_in"], preferred_element_type=jnp.float32),
                (inner, inner + conv), axis=-1)
            z, xbc = z.astype(h.dtype), xbc.astype(h.dtype)
        with jax.named_scope("ssm_conv"):
            xbc, tail = causal_conv(xbc, tail, w["conv_w"], w["conv_b"],
                                    real)
        with jax.named_scope("ssm_scan"):
            x, Bm, Cm = jnp.split(xbc, (inner, inner + G * N), axis=-1)
            x = x.reshape(B, T, H, P)
            Bm, Cm = Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N)
            dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))
            if real is not None:    # padding advances nothing
                dt = jnp.where(jnp.arange(T)[None, :, None]
                               < real[:, None, None], dt, 0.0)
            A = -jnp.exp(w["A_log"].astype(jnp.float32))
            if T == 1:
                row = (x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], w["D"])
                y, state = state(*row) if callable(state) \
                    else ssm_step(*row, state)
                y = y[:, None]
            else:
                y, state = ssd_scan(x, dt, A, Bm, Cm, w["D"], state,
                                    c.ssm_chunk)
        with jax.named_scope("ssm_proj"):
            # the gate first, then the norm over the whole inner width
            y = y.reshape(B, T, inner).astype(jnp.float32) \
                * jax.nn.silu(z.astype(jnp.float32))
            y = _norm(y, w["norm"], c.norm_eps, True).astype(h.dtype)
            return y @ w["w_out"], state, tail

    def mix_ssm_whole(self, h, w, cache):
        """The state-space mixer over whole sequences from an empty state
        (the trainer, ``apply``)."""
        c = self.config
        B = h.shape[0]
        delta, _, _ = self._ssm_mixer(
            h, w, jnp.zeros((B, c.ssm_heads, c.ssm_head_dim, c.ssm_state),
                            jnp.float32),
            jnp.zeros((B, c.ssm_conv - 1, c.ssm_conv_dim), h.dtype))
        return delta, cache

    def mix_ssm_paged(self, h, w, pool, *, index, lengths, real_lengths,
                      slots, impl, interpret):
        """A state-space layer on the serving path: its state lives in
        ``pool`` (``ops/ssm.py StateCache``) at layer ``index`` of the
        state-space layers' stack, a row a SLOT, read and written in place.

        ``slots`` [B] (a prefill: the slot of each sequence) starts from
        zeros at ``lengths`` 0 whatever the slot held, from the slot's
        state otherwise (a later piece of a long prompt), advances it by
        the first ``real_lengths`` rows and leaves there the last K - 1
        REAL inputs of the convolution.  ``slots`` None is a decode
        dispatch: row b is slot b, and a row the dispatch does not serve
        (``lengths`` 0: idle, or part-way through its prompt) keeps its
        state bit for bit.  Its recurrence runs on the stacked pool
        (``ops/ssm.py state_decode_update``): with ``impl`` "pallas" one
        kernel a layer, ``ssm_decode_update``, that reads a slot's state
        once and writes it once (its interpreter with ``interpret``), the
        dispatch's one backend as ``mix_paged``'s; with "jnp" the slice,
        ``ssm_step`` and the masked write.  The prefill and the conv
        tails' masked write are XLA's whatever the backend."""
        from deepspeed_tpu.ops.ssm import (read_slot_state,
                                           state_decode_update,
                                           write_slot_state)
        state_pool, conv_pool = pool
        B, c = h.shape[0], self.config
        rows = (B, c.ssm_conv - 1, c.ssm_conv_dim)
        scan, conv = (functools.partial(jax.named_scope, name)
                      for name in ("ssm_scan", "ssm_conv"))
        if slots is None:
            with conv():
                tail = jax.lax.dynamic_index_in_dim(conv_pool, index, 0,
                                                    False)
            live = lengths > 0
            delta, state_pool, new_tail = self._ssm_mixer(
                h, w, functools.partial(
                    state_decode_update, state_pool, index, live=live,
                    impl=impl, interpret=interpret), tail.reshape(rows))
            with conv():
                conv_pool = jax.lax.dynamic_update_index_in_dim(
                    conv_pool, jnp.where(
                        live[:, None], new_tail.reshape(B, -1),
                        tail).astype(conv_pool.dtype), index, 0)
            return delta, type(pool)(state_pool, conv_pool)
        fresh = lengths == 0
        with scan():    # one sequence a prefill dispatch
            state = jnp.where(fresh[:, None, None, None], 0.0, jnp.stack([
                read_slot_state(state_pool, index, slots[b])
                for b in range(B)]))
        with conv():
            tail = jnp.where(fresh[:, None], 0, jnp.stack([
                jax.lax.dynamic_slice(
                    conv_pool, (index, slots[b], 0),
                    (1, 1, conv_pool.shape[2]))[0, 0] for b in range(B)]))
        delta, state, tail = self._ssm_mixer(h, w, state, tail.reshape(rows),
                                             real_lengths)
        tail = tail.reshape(B, -1).astype(conv_pool.dtype)
        for b in range(B):
            with scan():
                state_pool = write_slot_state(state_pool, index, slots[b],
                                              state[b])
            with conv():
                conv_pool = jax.lax.dynamic_update_slice(
                    conv_pool, tail[b][None, None], (index, slots[b], 0))
        return delta, type(pool)(state_pool, conv_pool)

    # ------------------------------------------------------------------
    # The linear-attention mixer, in attention's place as the state-space
    # one is: ``mix(h, weights, cache, positions) -> (delta, cache)``,
    # ``positions`` None for a layer that carries none.
    # ------------------------------------------------------------------
    def _lin_mixer(self, h, w, state, positions, real=None):
        """A linear-attention mixer with a constant decay a head over T
        rows a sequence.  h: [B, T, d], normed; ``state`` [B, H, D, D]
        float32: what the rows before row 0 left (zeros ahead of position
        0); ``positions`` [B, T] turn q and k (None: no rotary); ``real``
        [B]: how many of the T rows are tokens (None: all), the others
        advance nothing.  Returns (delta [B, T, d], state).  A decode
        dispatch (T = 1) hands as ``state`` the recurrence on the state
        where it lies, ``state(q, k, v, slopes, scale=) -> (o, pool)``,
        and gets the pool back in the state's place."""
        from deepspeed_tpu.ops.linear_attention import (decay_slopes,
                                                        linear_scan,
                                                        linear_step)
        c = self.config
        B, T, _ = h.shape
        H, D = c.lin_heads, c.lin_head_dim
        with jax.named_scope("attn"):
            q, k, v = ((h @ w[name]).reshape(B, T, H, D)
                       for name in ("wq", "wk", "wv"))
            if "q_norm" in w:
                q = _norm(q, w["q_norm"], c.norm_eps, True)
                k = _norm(k, w["k_norm"], c.norm_eps, True)
            if positions is not None:
                q, k = (_rope(x, positions, c.rope_theta) for x in (q, k))
        with jax.named_scope("lin_attn"):
            slopes, scale = decay_slopes(H), 1.0 / math.sqrt(D)
            if T == 1:
                row = (q[:, 0], k[:, 0], v[:, 0], slopes)
                o, state = state(*row, scale=scale) if callable(state) \
                    else linear_step(*row, state, scale)
                o = o[:, None]
            else:
                o, state = linear_scan(q, k, v, slopes, state, real, scale)
            # the norm over the heads' whole output first, then the gate
            o = _norm(o.reshape(B, T, H * D).astype(jnp.float32), w["norm"],
                      c.norm_eps, True) * jax.nn.sigmoid(
                          (h @ w["wg"]).astype(jnp.float32))
        with jax.named_scope("attn"):
            return o.astype(h.dtype) @ w["wo"], state

    def mix_lin_whole(self, h, w, cache, positions):
        """The linear-attention mixer over whole sequences from an empty
        state (the trainer, ``apply``)."""
        c = self.config
        delta, _ = self._lin_mixer(
            h, w, jnp.zeros((h.shape[0], c.lin_heads, c.lin_head_dim,
                             c.lin_head_dim), jnp.float32), positions)
        return delta, cache

    def mix_lin_paged(self, h, w, pool, positions, *, index, lengths,
                      real_lengths, slots):
        """A linear-attention layer on the serving path: its state lives
        in ``pool`` (``ops/linear_attention.py LinearStateCache``) at
        layer ``index`` of the linear layers' stack, a row a SLOT, read
        and written in place, by :meth:`mix_ssm_paged`'s rules: ``slots``
        [B] (a prefill) starts from zeros at ``lengths`` 0 and from the
        slot's state otherwise, and advances it by the first
        ``real_lengths`` rows; ``slots`` None is a decode dispatch, row b
        is slot b, and a row at ``lengths`` 0 keeps its state bit for
        bit."""
        from deepspeed_tpu.ops.linear_attention import state_decode_update
        from deepspeed_tpu.ops.ssm import read_slot_state, write_slot_state
        (state_pool,) = pool
        B = h.shape[0]
        if slots is None:
            delta, state_pool = self._lin_mixer(
                h, w, functools.partial(state_decode_update, state_pool,
                                        index, live=lengths > 0), positions)
            return delta, type(pool)(state_pool)
        with jax.named_scope("lin_attn"):   # one sequence a prefill dispatch
            state = jnp.where(
                (lengths == 0)[:, None, None, None], 0.0, jnp.stack([
                    read_slot_state(state_pool, index, slots[b])
                    for b in range(B)]))
        delta, state = self._lin_mixer(h, w, state, positions, real_lengths)
        with jax.named_scope("lin_attn"):
            for b in range(B):
                state_pool = write_slot_state(state_pool, index, slots[b],
                                              state[b])
        return delta, type(pool)(state_pool)

    def _latent_fresh(self, q, k, idx, layer, positions=None, counts=None,
                      context=None, impl=None, interpret=False):
        """Latent attention of T tokens over themselves (a whole sequence,
        or a prefill from an empty context): keys and values DECOMPRESSED
        for the tokens at hand, each query over its selected causal keys
        (all of them without an indexer).  ``context``: ``(pools, layer
        index, block_tables, lengths)`` of a model without a selection
        whose tokens follow ``lengths`` entries already in the pool (a
        chunk of a longer prompt) and are themselves written there: with
        ``impl`` "pallas" ONE kernel walks the pool over both
        (``ops/pallas/latent_attention.py``; its interpreter with
        ``interpret``); otherwise the queries attend over the cached
        entries first (``la.context_attention``, scope ``latent_ctx``)
        and over their own decompressed keys after, in XLA.
        -> [B, T, H, dv]."""
        from deepspeed_tpu.ops import latent_attention as la
        c = self.config
        B, T, H, dn = q.nope.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        real = None if counts is None else counts.real
        if idx is None and context is not None and impl == "pallas":
            from deepspeed_tpu.ops.pallas.latent_attention import \
                latent_prefill_attention
            pools, index, block_tables, lengths = context
            rows = None if real is None \
                else jnp.sum(real, axis=1, dtype=jnp.int32)
            with jax.named_scope("latent_attn"):
                out = latent_prefill_attention(
                    q.nope, q.rope, pools.latent_pages, index, block_tables,
                    lengths, layer["wkv_b"], self._latent_scale(),
                    real_lengths=rows, interpret=interpret)
            if counts is not None:
                # real query t of a sequence met its ``lengths`` cached
                # entries and the t + 1 causal keys of the chunk
                met = jnp.sum(rows * lengths + rows * (rows + 1) // 2
                              ).astype(jnp.int32)
                counts.add(selected=met, context_keys=met)
            return out
        with jax.named_scope("latent_attn"):
            kv = (k.c_kv @ layer["wkv_b"]).reshape(B, T, H, -1)
            keys = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(
                    k.rope[:, :, None], (B, T, H, k.rope.shape[-1]))], -1)
            queries = jnp.concatenate([q.nope, q.rope], axis=-1)
            state = None
            if context is not None:
                pools, index, block_tables, lengths = context
                with jax.named_scope("latent_ctx"):
                    state = la.context_attention(
                        queries, pools, index, block_tables, lengths,
                        layer["wkv_b"].reshape(-1, H, dn + c.v_head_dim),
                        k.rope.shape[-1], self._latent_scale(), real=real)
        if idx is None:     # no selection: every causal key
            idx = Indexer(None, None, None)
        out, attended, causal = la.prefill_attention(
            queries, keys, kv[..., dn:], idx.q, idx.w, idx.k, positions,
            c.index_topk, self._latent_scale(), real=real, state=state)
        if counts is not None and context is not None:
            # every real query also met the ``lengths`` cached entries
            cached = jnp.sum(jnp.sum(real, axis=1) * lengths).astype(
                jnp.int32)
            attended, causal = attended + cached, causal + cached
        if counts is not None:
            counts.add(selected=attended, context_keys=causal)
        return out

    def mix_latent_whole(self, q, k, idx, layer, cache):
        """``mix_full``'s place for a latent model: the whole sequence,
        no cache."""
        return self._latent_fresh(q, k, idx, layer), cache

    def _latent_scale(self):
        c = self.config
        if c.attn_scale is not None:
            return c.attn_scale
        scale = 1.0 / math.sqrt(c.qk_nope_head_dim + c.qk_rope_head_dim)
        if c.rope_yarn is not None:
            from deepspeed_tpu.ops.latent_attention import RopeYarn
            scale *= RopeYarn(*c.rope_yarn).softmax_factor
        return scale

    def mix_latent(self, q, k, idx, layer, pools, *, index, block_tables,
                   lengths, counts=None, impl=None, interpret=False):
        """Write this layer's entries ``[c_kv | k_rope]`` and indexer keys
        into the two STACKED latent pools at ``lengths``, then attend: a
        decode step (T = 1) gathers its selected entries out of the pool
        and uses the absorbed weights; T > 1 is a prefill FROM AN EMPTY
        CONTEXT (``lengths`` 0: ``ServingEngine`` refuses what would break
        that) and decompresses the tokens it brings.  A model without a
        selection takes :meth:`mix_latent_dense`, the only reader of
        ``impl`` / ``interpret``."""
        from deepspeed_tpu.ops import latent_attention as la
        c = self.config
        if idx is None:
            return self.mix_latent_dense(
                q, k, layer, pools, index=index, block_tables=block_tables,
                lengths=lengths, counts=counts, impl=impl,
                interpret=interpret)
        B, T, H, dn = q.nope.shape
        entry = jnp.concatenate([k.c_kv, k.rope], axis=-1)
        with jax.named_scope("latent_attn"):
            pools = la.write_latent(pools, index, block_tables, lengths,
                                    entry, idx.k)
        if T > 1:
            positions = lengths[:, None] + jnp.arange(T)[None, :]
            return self._latent_fresh(q, k, idx, layer, positions,
                                      counts), pools
        R = c.kv_lora_rank
        w_kvb = layer["wkv_b"].reshape(R, H, -1)
        with jax.named_scope("latent_attn"):
            q_abs = jnp.einsum("bhd,rhd->bhr", q.nope[:, 0], w_kvb[..., :dn])
        o_lat, attended, context = la.decode_attention(
            q_abs, q.rope[:, 0], idx.q[:, 0], idx.w[:, 0], pools, index,
            block_tables, lengths + 1, c.index_topk, self._latent_scale(),
            real=None if counts is None else counts.real[:, 0])
        with jax.named_scope("latent_attn"):
            out = jnp.einsum("bhr,rhd->bhd", o_lat, w_kvb[..., dn:])
        if counts is not None:
            counts.add(selected=attended, context_keys=context)
        return out[:, None], pools

    def mix_latent_dense(self, q, k, layer, pools, *, index, block_tables,
                         lengths, counts=None, impl=None, interpret=False):
        """:meth:`mix_latent` of a model WITHOUT a selection: no index
        pool, no scores, no top-k.  The entries are written at
        ``lengths``; T > 1 is a prefill from whatever the pool holds of
        the sequence (``lengths`` >= 0: a chunk of a longer prompt attends
        over the cached entries, then causally over itself; at 0 it is
        the fresh prefill; ``impl`` "pallas": both in one kernel over the
        pool, else in XLA), a decode step reads the context's entries in
        page order with the absorbed weights, in XLA."""
        from deepspeed_tpu.ops import latent_attention as la
        c = self.config
        B, T, H, dn = q.nope.shape
        with jax.named_scope("latent_attn"):
            pools = la.write_latent(
                pools, index, block_tables, lengths,
                jnp.concatenate([k.c_kv, k.rope], axis=-1), None)
        if T > 1:
            positions = lengths[:, None] + jnp.arange(T)[None, :]
            return self._latent_fresh(
                q, k, None, layer, positions, counts,
                context=(pools, index, block_tables, lengths), impl=impl,
                interpret=interpret), pools
        w_kvb = layer["wkv_b"].reshape(c.kv_lora_rank, H, -1)
        with jax.named_scope("latent_attn"):
            q_abs = jnp.einsum("bhd,rhd->bhr", q.nope[:, 0], w_kvb[..., :dn])
            o_lat, context = la.dense_decode_attention(
                q_abs, q.rope[:, 0], pools, index, block_tables, lengths + 1,
                self._latent_scale(),
                real=None if counts is None else counts.real[:, 0])
            out = jnp.einsum("bhr,rhd->bhd", o_lat, w_kvb[..., dn:])
        if counts is not None:
            counts.add(selected=context, context_keys=context)
        return out[:, None], pools

    def _cached_attn_bias(self, layer, T, S, length):
        """Decode-path analogue of ``_attn_bias`` over the full cache
        buffer [S]; query positions are ``length - T + arange(T)``."""
        c = self.config
        bias = None
        if c.use_alibi:
            bias = (alibi_slopes(c.n_heads)[None, :, None, None] *
                    jnp.arange(S, dtype=jnp.float32)[None, None, None, :])
        if "attn_window" in layer:
            w = layer["attn_window"]
            qpos = length - T + jnp.arange(T, dtype=jnp.int32)[:, None]
            delta = qpos - jnp.arange(S, dtype=jnp.int32)[None, :]
            allowed = (delta < w) | (w <= 0)
            wbias = jnp.where(allowed, 0.0, -1e30).astype(jnp.float32)
            bias = wbias if bias is None else bias + wbias
        return bias

    # ------------------------------------------------------------------
    # The block, stated once.  Every forward calls it and supplies a mixer.
    # ------------------------------------------------------------------
    @jax.named_scope("attn")
    def _attn_delta(self, h, layer, positions, mix, cache, rotary=True):
        """Attention sub-block on pre-normed input → (residual delta with
        the wo projection applied and no residual add, cache).  A layer
        with ``wg_attn`` gates what its mixer returns, whichever mixer:
        ``attn * sigmoid(h wg_attn)`` ahead of ``wo``."""
        B, T, _ = h.shape
        q, k, v = self._qkv(h, layer, B, T, positions, rotary)
        attn, cache = mix(q, k, v, layer, cache)
        attn = attn.reshape(B, T, -1)
        if "wg_attn" in layer:
            with jax.named_scope("attn_gate"):
                attn = attn * jax.nn.sigmoid(
                    (h @ layer["wg_attn"]).astype(jnp.float32)
                ).astype(attn.dtype)
        return self._proj(attn, layer, "wo"), cache

    def _dropless_delta(self, h, layer, counts=None, train=False):
        """The dropless expert layer, this chip's share of it: routing
        over all experts, the held experts' terms by a grouped product
        with no capacity, plus the ungated shared expert."""
        from deepspeed_tpu.moe.sharded_moe import (dropless_held_experts,
                                                   dropless_route)
        c = self.config
        moe = layer["moe"]
        B, T, d = h.shape
        flat = h.reshape(B * T, d)
        # the balance statistic is the trainer's (``loss`` weighs it);
        # a serving dispatch (``train`` False) routes and nothing more
        balance = train and c.moe_aux_loss_coef > 0
        aux = jnp.float32(0.0)
        with jax.named_scope("router"):
            chosen, weights, *scores = dropless_route(
                flat, moe["wg"], moe.get("router_bias"), c.moe_top_k,
                scoring=c.moe_scoring, scale=c.moe_routed_scale,
                norm=c.moe_norm_topk_prob, norm_eps=c.moe_route_norm_eps,
                with_scores=balance)
            if balance:
                from deepspeed_tpu.moe.sharded_moe import balance_statistic
                aux = balance_statistic(chosen, scores[0])
        if counts is not None and counts.real is not None:
            # bucket padding and idle slots are nobody's tokens: their
            # pairs are neither computed nor counted
            chosen = jnp.where(counts.real.reshape(B * T, 1), chosen, -1)
        with jax.named_scope("experts"):
            out, load, rows = dropless_held_experts(
                flat, chosen, weights, moe, _ACTIVATIONS[c.activation],
                first=c.moe_experts_first, layer=moe.get("stack_layer"),
                impl=None if counts is None else counts.expert_impl,
                interpret=counts is not None and counts.interpret)
        out = out.astype(h.dtype)
        if "shared" in moe:
            with jax.named_scope("shared_expert"):
                sh = moe["shared"]
                act = _ACTIVATIONS[c.activation]
                out = out + (act(flat @ sh["w_gate"]) * (flat @ sh["w_up"])
                             ) @ sh["w_down"]
        if counts is not None:
            counts.add(expert_pairs=jnp.sum(load),
                       expert_load_max=jnp.max(load), expert_rows=rows)
        return out.reshape(B, T, d), aux

    @jax.named_scope("mlp")
    def _mlp_delta(self, h, layer, rng=None, train=True, counts=None):
        """FFN sub-block on pre-normed input; returns (delta, aux_loss)."""
        c = self.config
        if "moe" in layer and c.moe_dropless:
            return self._dropless_delta(h, layer, counts, train)
        if "moe" in layer:
            from deepspeed_tpu.moe.sharded_moe import moe_layer_forward
            act = _ACTIVATIONS[c.activation]

            def expert_fn(ep, dispatched):
                # 2-layer expert FFN (reference Experts module) or GLU
                # experts when w_gate is present (Mixtral SwiGLU);
                # activation follows the model config; optional per-expert
                # biases for Megatron-MoE checkpoints
                inner = jnp.einsum("ecd,edf->ecf", dispatched, ep["w_up"])
                if "w_up_b" in ep:
                    inner = inner + ep["w_up_b"][:, None, :]
                if "w_gate" in ep:
                    gate = jnp.einsum("ecd,edf->ecf", dispatched,
                                      ep["w_gate"])
                    inner = act(gate) * inner
                else:
                    inner = act(inner)
                out = jnp.einsum("ecf,efd->ecd", inner, ep["w_down"])
                if "w_down_b" in ep:
                    out = out + ep["w_down_b"][:, None, :]
                return out

            moe_out, l_aux, _ = moe_layer_forward(
                self.gate, {"wg": layer["moe"]["wg"]}, layer["moe"],
                expert_fn, h, train=train, rng=rng)
            if "shared" in layer["moe"]:
                # Qwen2-MoE: an always-on SwiGLU expert scaled by a
                # sigmoid gate rides beside the routed experts
                sh = layer["moe"]["shared"]
                inner = jax.nn.silu(h @ sh["w_gate"]) * (h @ sh["w_up"])
                shared_out = inner @ sh["w_down"]
                sg = jax.nn.sigmoid(
                    (h @ sh["wg"]).astype(jnp.float32)).astype(h.dtype)
                moe_out = moe_out + sg * shared_out
            return moe_out, l_aux
        act = _ACTIVATIONS[c.activation]
        if c.gated:
            inner = act(h @ layer["w_gate"]) * self._proj(h, layer, "w_up")
        else:
            inner = act(self._proj(h, layer, "w_up"))
        return self._proj(inner, layer, "w_down"), jnp.float32(0.0)

    def _sandwich(self, delta, layer, key):
        """A sub-block's output on its way to the residual add."""
        c = self.config
        if key in layer:   # Gemma-2 sandwich / OLMo2: norm the
            delta = _norm(delta, layer[key], c.norm_eps,
                          c.use_rmsnorm)   # sub-block OUTPUT pre-residual
        if c.residual_scale is not None:   # Granite residual_multiplier
            delta = delta * c.residual_scale
        return delta

    def block(self, x, layer, positions, mix, cache=None, rng=None,
              train=True, counts=None, rotary=True):
        """One transformer block → (x, cache, aux_loss): the residual
        structure, pre-norms, q/k/v, ``wo``, sandwich norms, residual scale
        and the MLP (dense or MoE).  ``mix`` (one of the mixers above) is
        all a forward chooses; ``cache`` goes into it and comes out.
        ``counts``: the serving dispatch's :class:`ServeCounts`, which the
        expert layer adds to.  ``rotary`` (static): whether this layer's q
        and k turn (``config.layer_rotary``).  A layer with ``ssm``
        weights has a state-space mixer in attention's place, and its
        ``mix`` is ``mix(h, weights, cache) -> (delta, cache)``; one with
        ``lin`` weights a linear-attention mixer, ``mix(h, weights, cache,
        positions) -> (delta, cache)``, ``positions`` None where
        ``rotary`` is false."""
        c = self.config
        if c.parallel_block:
            # GPT-J / parallel-residual NeoX: both sub-blocks read the
            # residual stream, one fused add (GPT-J shares one LN — the
            # policy duplicates it into attn_norm/mlp_norm; NeoX parallel
            # keeps two distinct LNs).  No sandwich norms here: no policy
            # builds that pair
            ha = _pre_norm(x, layer, "attn_norm", c)
            hm = _pre_norm(x, layer, "mlp_norm", c)
            mlp, aux = self._mlp_delta(hm, layer, rng=rng, train=train)
            attn, cache = self._attn_delta(ha, layer, positions, mix, cache,
                                           rotary)
            if c.residual_scale is not None:   # Granite-style multiplier
                attn = attn * c.residual_scale
                mlp = mlp * c.residual_scale
            return x + attn + mlp, cache, aux
        h = _pre_norm(x, layer, "attn_norm", c)
        if "ssm" in layer:      # a state-space mixer in attention's place
            delta, cache = mix(h, layer["ssm"], cache)
        elif "lin" in layer:    # a linear-attention one
            delta, cache = mix(h, layer["lin"], cache,
                               positions if rotary else None)
        else:
            delta, cache = self._attn_delta(h, layer, positions, mix, cache,
                                            rotary)
        x = x + self._sandwich(delta, layer, "attn_post_norm")
        h = _pre_norm(x, layer, "mlp_norm", c)
        delta, aux = self._mlp_delta(h, layer, rng=rng, train=train,
                                     counts=counts)
        return x + self._sandwich(delta, layer, "mlp_post_norm"), cache, aux

    def _layer(self, x, layer, positions, rng=None, train=True, rotary=True,
               window=None, counts=None):
        """The block over a whole sequence → (x, aux): what ``apply``
        scans and ``stream_layer`` / ``runtime/pipe`` call.  ``window``
        (static; None: whatever ``layer["attn_window"]`` holds): the
        layer's sliding window, 0 for a full-attention layer."""
        mix = self.mix_ssm_whole if "ssm" in layer \
            else self.mix_lin_whole if "lin" in layer \
            else self.mix_latent_whole if self.config.is_latent \
            else self.mix_full
        if window is not None:
            layer = dict(layer, attn_window=int(window))
        x, _, aux = self.block(x, layer, positions, mix, rng=rng,
                               train=train, rotary=rotary, counts=counts)
        return x, aux

    def layer_list(self, params):
        """Every layer's weights, in order: the listed layers, then each
        scanned period's cut out of ``params["periods"]``."""
        layers = list(params["layers"])
        for p in range((self.config.n_layers - len(layers))
                       // max(self.config.layer_period, 1)):
            layers += [jax.tree_util.tree_map(lambda w: w[p], at)
                       for at in params["periods"]]
        return layers

    # ------------------------------------------------------------------
    # The embedding and the head, stated once.
    # ------------------------------------------------------------------
    @jax.named_scope("embed")
    def embed(self, params, input_ids, positions):
        """Token ids [B, T] at ``positions`` [B, T] → the first hidden
        state: table, scale, learned positions, embedding norm."""
        c = self.config
        x = params["tok_embed"][input_ids]
        if c.embed_scale is not None:   # Gemma: sqrt(d) on the
            x = x * jnp.asarray(c.embed_scale, x.dtype)  # input side only
        if not c.use_rope and not c.use_alibi:
            x = x + params["pos_embed"][positions].astype(x.dtype)
        if c.embed_norm:
            x = _norm(x, params["embed_norm"], c.norm_eps, c.use_rmsnorm,
                      params.get("embed_norm_b"))
        return x

    def final_norm(self, params, x):
        """The norm between the last block and the head."""
        c = self.config
        return _norm(x, params["final_norm"], c.norm_eps, c.use_rmsnorm,
                     params.get("final_norm_b"))

    def head_table(self, params):
        """The LM head's [d, V] table, tied or not."""
        return (params["tok_embed"].T if self.config.tie_embeddings
                else params["lm_head"])

    def logits(self, params, x):
        """The last hidden state → float32 logits: final norm, table,
        bias, scale, softcap."""
        c = self.config
        x = self.final_norm(params, x)
        with jax.named_scope("loss_head"):
            logits = (x @ self.head_table(params).astype(x.dtype)
                      ).astype(jnp.float32)
            if "lm_head_b" in params:
                logits = logits + params["lm_head_b"].astype(jnp.float32)
            if c.final_logit_scale is not None:   # Cohere logit_scale
                logits = logits * c.final_logit_scale
            return _softcap(logits, c.final_logit_softcap)

    def _windows(self):
        """Per-layer local-attention windows ride the layer loops as a side
        input (NOT a param leaf: integer leaves would break jax.grad)."""
        c = self.config
        return (jnp.asarray(c.local_attn_pattern, jnp.int32)
                if c.local_attn_pattern else None)

    def apply(self, params, input_ids, positions=None, rng=None, train=True,
              return_aux=False, return_hidden=False, counts=None):
        """Logits [B, S, V] of a whole sequence; ``return_hidden`` gives the
        last hidden state BEFORE the final norm (what ``stream_head_loss``
        takes) and the MoE aux loss.  ``counts`` (a :class:`ServeCounts`
        with ``real`` None): the expert layers add their pairs, load and
        rows to it.  A model with ``layer_period`` runs its leading layers
        one by one and SCANS the periods after them, a period's places
        unrolled in the body, each with its own static window, rotary
        kind and scope."""
        c = self.config
        B, S = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

        # activation layout: batch over all data axes, sequence over sp
        x = maybe_constrain(self.embed(params, input_ids, positions),
                            P(tuple(BATCH_AXES), SP_AXIS, None))

        aux = jnp.float32(0.0)
        windows = self._windows()
        if isinstance(params["layers"], (list, tuple)):
            # MoE / heterogeneous stack: unrolled layer loop, then a scan
            # over the periods that follow it
            scanned = c.layer_period and "periods" in params
            policy = c.checkpoint_policy()

            def run(indices, static_window, x, layers, positions, rngs):
                """The blocks of layers ``indices`` (their static pattern)
                one after another → (x, aux, what they counted)."""
                inner = None if counts is None else counts.fresh()
                total = jnp.float32(0.0)
                for i, layer, lrng in zip(indices, layers, rngs):
                    x, l_aux = self._layer(
                        x, layer, positions, lrng, train, c.layer_rotary(i),
                        c.layer_window(i) if static_window else None, inner)
                    total = total + l_aux
                return x, total, None if inner is None else inner.vector()

            def checkpointed(fn):
                return jax.checkpoint(fn, policy=policy) if c.remat else fn

            listed = params["layers"] if scanned else self.layer_list(params)
            for i, layer in enumerate(listed):
                if windows is not None and not scanned:
                    layer = dict(layer, attn_window=windows[i])
                lrng = jax.random.fold_in(rng, i) if rng is not None else None
                x, l_aux, counted = checkpointed(functools.partial(
                    run, (i,), scanned))(x, (layer,), positions, (lrng,))
                aux = aux + l_aux
                if counts is not None:
                    counts.absorb(counted[None])
            if scanned:
                lead, period = c.leading_layers, c.layer_period
                at = tuple(range(lead, lead + period))

                def one_period(x, inp):
                    layers, p = inp
                    rngs = tuple(
                        None if rng is None else jax.random.fold_in(
                            rng, lead + p * period + j)
                        for j in range(period))
                    x, l_aux, counted = run(at, True, x, layers, positions,
                                            rngs)
                    return x, (l_aux, counted)

                x, (l_auxs, counted) = layer_scan(
                    checkpointed(one_period), x,
                    (tuple(params["periods"]),
                     jnp.arange((c.n_layers - lead) // period)))
                aux = aux + jnp.sum(l_auxs)
                if counts is not None:
                    counts.absorb(counted)
        else:
            def body(x, inp):
                if windows is not None:
                    layer, w = inp
                    layer = dict(layer, attn_window=w)
                else:
                    layer = inp
                x, l_aux = self._layer(x, layer, positions, train=train)
                return x, l_aux

            if c.remat:
                body = jax.checkpoint(body, policy=c.checkpoint_policy())
            xs = (params["layers"] if windows is None
                  else (params["layers"], windows))
            x, l_auxs = layer_scan(body, x, xs)
            aux = jnp.sum(l_auxs)

        if return_hidden:
            return x, aux
        logits = self.logits(params, x)
        if return_aux:
            return logits, aux
        return logits

    __call__ = apply

    # ------------------------------------------------------------------
    # KV-cache decode path (inference engine hot loop)
    # ------------------------------------------------------------------
    def init_caches(self, batch, max_seq, dtype=jnp.bfloat16):
        """Stacked per-layer KV caches: leaves have leading n_layers dim so
        the decode forward stays a single scan.  (MoE models use a list of
        caches matching their per-layer params list.)"""
        c = self.config
        if c.is_latent or c.has_state or c.has_sparse:
            raise NotImplementedError(
                "latent attention, block-sparse attention, state-space "
                "and linear-attention layers have no dense KVCache path: "
                "serve them through the paged pools (init_paged_caches)")
        if c.is_moe:
            return [init_cache(batch, max_seq, c.kv_heads, c.head_dim, dtype)
                    for _ in range(c.n_layers)]
        one = init_cache(batch, max_seq, c.kv_heads, c.head_dim, dtype)
        return KVCache(
            k=jnp.broadcast_to(one.k[None], (c.n_layers,) + one.k.shape).copy(),
            v=jnp.broadcast_to(one.v[None], (c.n_layers,) + one.v.shape).copy(),
            length=one.length)

    def apply_with_cache(self, params, input_ids, caches):
        """Forward for prefill (T=prompt) or decode (T=1), appending to
        ``caches``.  Returns (logits [B,T,V], new caches)."""
        B, T = input_ids.shape
        if isinstance(caches, list):
            start = caches[0].length
        else:
            start = caches.length
        positions = start + jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        x = self.embed(params, input_ids, positions)

        windows = self._windows()
        if isinstance(caches, list):  # MoE / heterogeneous stack
            new_caches = []
            for i, (layer, cache) in enumerate(zip(self.layer_list(params),
                                                   caches)):
                if windows is not None:
                    layer = dict(layer, attn_window=windows[i])
                x, cache, _ = self.block(
                    x, layer, positions, self.mix_cached, cache, train=False,
                    rotary=self.config.layer_rotary(i))
                new_caches.append(cache)
            out_caches = new_caches
        else:
            def body(x, inp):
                layer, ck, cv = inp
                if windows is not None:
                    layer, w = layer
                    layer = dict(layer, attn_window=w)
                x, cache, _ = self.block(
                    x, layer, positions, self.mix_cached,
                    KVCache(k=ck, v=cv, length=start), train=False)
                return x, (cache.k, cache.v)

            lxs = (params["layers"] if windows is None
                   else (params["layers"], windows))
            x, (new_k, new_v) = jax.lax.scan(
                body, x, (lxs, caches.k, caches.v))
            out_caches = KVCache(k=new_k, v=new_v, length=start + T)

        return self.logits(params, x), out_caches

    # ------------------------------------------------------------------
    # paged KV-cache path (continuous-batching serving engine)
    # ------------------------------------------------------------------
    def init_paged_caches(self, num_pages, page_size, dtype=jnp.bfloat16,
                          ring_slots=0, state_slots=0):
        """Stacked per-layer page pools: leaves [L, P, Hkv, page, D] — one
        scan for homogeneous stacks; MoE / heterogeneous models index the
        same pools per layer in a static loop.  A model with
        sliding-window layers gets a ``WindowedKVCache``: that stack for
        its full-attention layers alone, and for its window layers one of
        ``ring_slots`` rings of ``ring_pages(window, page_size)`` pages
        (and a scratch page), whatever ``num_pages``.  A model with
        state-space layers gets a ``HybridKVCache``: the stack for its
        attention layers alone, and for the others ``state_slots`` rows of
        recurrent state (float32) and of the convolution's last inputs
        (``dtype``), a row a slot; one with linear-attention layers the
        same with a ``LinearStateCache`` (a matrix state a slot, no tail)
        in ``ssm``'s place.  Block-sparse attention layers' stack is a
        ``SparseKVCache``: the pages and the compressed keys."""
        from deepspeed_tpu.ops.paged_attention import (PagedKVCache,
                                                       WindowedKVCache,
                                                       paged_pool_shape,
                                                       ring_pages)
        c = self.config
        assert not c.use_alibi, \
            "paged serving has no ALiBi: the paged kernels take no bias"

        def attention_pools(layers):
            """The stacked pools of ``layers`` attention layers."""
            shape = paged_pool_shape(layers, num_pages, c.kv_heads,
                                     page_size, c.head_dim)
            if c.has_sparse:
                from deepspeed_tpu.ops.block_sparse_attention import \
                    init_sparse_pools
                c.sparse.check(page_size)
                assert shape[2] == c.kv_heads and \
                    page_size % c.sparse.stride == 0, (
                        "block-sparse attention reads its blocks out of "
                        "pages of whole heads and whole compressed keys")
                return init_sparse_pools(layers, num_pages, c.kv_heads,
                                         page_size, c.head_dim,
                                         c.sparse.stride, dtype)
            return PagedKVCache(jnp.zeros(shape, dtype),
                                jnp.zeros(shape, dtype))

        if c.attn_window:
            assert c.layers_listed, (
                "window layers are served paged out of a listed layer "
                "stack: a scan over stacked layers has one kind of layer")
            assert ring_slots > 0, "a window model's pools need ring_slots"
            n_window = sum(1 for w in c.local_attn_pattern if w)
            ring = ring_slots * ring_pages(c.attn_window, page_size) + 1

            def stack(layers, pages):
                shape = paged_pool_shape(layers, pages, c.kv_heads,
                                         page_size, c.head_dim)
                return PagedKVCache(jnp.zeros(shape, dtype),
                                    jnp.zeros(shape, dtype))

            return WindowedKVCache(
                full=stack(c.n_layers - n_window, num_pages),
                ring=stack(n_window, ring))
        if c.has_state:
            from deepspeed_tpu.ops.linear_attention import \
                init_linear_state_cache
            from deepspeed_tpu.ops.ssm import HybridKVCache, init_state_cache
            assert state_slots > 0, \
                "a model with a recurrent state needs state_slots"
            n_state = c.state_layers
            return HybridKVCache(
                full=attention_pools(c.n_layers - n_state),
                ssm=init_state_cache(
                    n_state, state_slots, c.ssm_heads, c.ssm_head_dim,
                    c.ssm_state, c.ssm_conv - 1, c.ssm_conv_dim, dtype)
                if c.has_ssm else init_linear_state_cache(
                    n_state, state_slots, c.lin_heads, c.lin_head_dim))
        if c.is_latent:
            # one entry a token, [c_kv | k_rope], and the indexer's key:
            # two pools of unlike widths over the same pages
            from deepspeed_tpu.ops.latent_attention import init_latent_pools
            return init_latent_pools(
                c.n_layers, num_pages, page_size,
                c.kv_lora_rank + c.qk_rope_head_dim,
                c.index_head_dim if c.index_topk else 0, dtype)
        # each stack made in place: a broadcast of one layer's pool and a
        # copy of it held four stacks at once, the process's HBM peak
        return attention_pools(c.n_layers)

    def apply_with_paged_cache(self, params, input_ids, caches, block_tables,
                               lengths, *, attn_backend=None,
                               attn_interpret=False, real_lengths=None,
                               head_rows=None, expert_backend=None,
                               latent_backend=None, state_slots=None):
        """Forward over paged KV caches: appends the T new tokens of every
        sequence at ``lengths`` (tables must already map the pages) and
        attends over each sequence's ragged prefix.  Returns
        (logits [B, T, V], new caches, lengths + T); with ``head_rows``
        (int32 [B, R]: of each sequence the rows its caller will read, a
        prefill's one last token, none of a chunk that samples nothing)
        the final norm and the head run on those rows alone and the logits
        are [B, R, V].

        ``caches``: pytree from ``init_paged_caches``; ``block_tables``:
        [B, max_pages] int32; ``lengths``: [B] int32.  ``attn_backend`` /
        ``attn_interpret`` select the paged-attention implementation
        (``ops/paged_attention.py``: None = auto, "jnp" oracle, "pallas"
        fused ragged kernel; interpret runs the kernel on CPU) — static
        kwargs, so the serving engine binds them before jit.  A latent
        model's pools are written, and read by a decode step and by a
        prefill under a selection, in XLA whatever the backend;
        ``latent_backend`` ("pallas" | "jnp" / None) is what the prefill
        of a latent model WITHOUT a selection reads them with
        (``mix_latent_dense``; the kernel's interpreter with
        ``attn_interpret``).  ``expert_backend`` ("pallas" | "jnp" | None =
        auto) is what a dropless expert layer's grouped product runs on
        (``moe/sharded_moe.py:dropless_held_experts``; the kernel's
        interpreter with ``attn_interpret``).  A model that counts its
        serving dispatches (``config.counts_serving``) returns a fourth result, the
        dispatch's ``SERVE_COUNTERS`` as one int32 vector, over the real
        rows: the first ``real_lengths`` [B] of each sequence's T (all
        without it; a bucket's padding and a decode batch's idle slots
        are the engine's to name).  A model with state-space layers
        (``config.has_ssm``) is told a PREFILL's ``state_slots`` [B], the
        slot whose state each sequence starts from (zeros at ``lengths``
        0) and leaves advanced by its ``real_lengths`` rows; without
        ``state_slots`` the dispatch is a decode step, row b is slot b,
        and a row at ``lengths`` 0 keeps its state (``mix_ssm_paged``);
        a model with linear-attention layers (``config.has_lin``) the
        same (``mix_lin_paged``).  Block-sparse attention layers
        (``config.has_sparse``) take each sequence's context at the
        dispatch from ``lengths`` and ``real_lengths``
        (``mix_sparse_paged``).
        """
        from deepspeed_tpu.ops.paged_attention import (paged_read_items,
                                                       resolve_paged_impl,
                                                       ring_pages)
        c = self.config
        B, T = input_ids.shape
        positions = lengths[:, None] + jnp.broadcast_to(
            jnp.arange(T)[None, :], (B, T))
        x = self.embed(params, input_ids, positions)
        counts = None
        if c.counts_serving:
            counts = ServeCounts(
                jnp.ones((B, T), bool) if real_lengths is None
                else jnp.arange(T)[None, :] < real_lengths[:, None],
                expert_backend, attn_interpret)
        ring_mix = {}       # window -> what its layers' mixer is bound to
        if c.is_latent:
            paged = dict(block_tables=block_tables, lengths=lengths,
                         counts=counts, impl=latent_backend,
                         interpret=attn_interpret)
            mixer = self.mix_latent
        else:
            # one backend for the write and the read of the pools
            impl = resolve_paged_impl(attn_backend, c.attn_logit_softcap)
            full = caches.full if c.attn_window or c.has_state else caches
            if c.attn_window:
                # the table's last columns are each sequence's ring
                page = full.k_pages.shape[3]
                ring = ring_pages(c.attn_window, page)
                block_tables, ring_tables = (block_tables[:, :-ring],
                                             block_tables[:, -ring:])
                q_shape = (B, T, c.n_heads, c.head_dim)
                for window in sorted({w for w in c.local_attn_pattern if w}):
                    if T == 1:
                        items = paged_read_items(
                            q_shape, caches.ring, ring_tables, lengths + 1,
                            impl, window, ring)
                    else:   # over the rows the prefill brings (mix_ring)
                        n = -(-T // page)
                        items = paged_read_items(
                            q_shape, jax.tree_util.tree_map(
                                lambda pool: jax.ShapeDtypeStruct(
                                    (1, B * n) + pool.shape[2:], pool.dtype),
                                caches.ring),
                            jax.ShapeDtypeStruct((B, n), jnp.int32),
                            jnp.full((B,), T, jnp.int32), impl, window)
                    ring_mix[window] = dict(
                        window=window, ring_tables=ring_tables,
                        lengths=lengths, impl=impl, interpret=attn_interpret,
                        items=items, real_lengths=(
                            real_lengths if real_lengths is not None
                            else jnp.full((B,), T, jnp.int32)))
            read_lengths, sparse = lengths + T, {}
            if c.has_sparse:
                # each sequence's context at this dispatch; the pages' own
                # read serves those under ``sparse.dense_len`` alone
                context = lengths + (T if real_lengths is None
                                     else real_lengths)
                read_lengths = jnp.where(context < c.sparse.dense_len,
                                         read_lengths, 0)
                sparse = dict(context=context, read_lengths=read_lengths)
            # the steps of the read that hold keys: once a dispatch, not a
            # layer
            items = paged_read_items((B, T, c.n_heads, c.head_dim), full,
                                     block_tables, read_lengths, impl)
            paged = dict(block_tables=block_tables, lengths=lengths,
                         impl=impl, interpret=attn_interpret, items=items,
                         **sparse)
            mixer = self.mix_sparse_paged if c.has_sparse else self.mix_paged

        def body(carry, inp, at=None, counts=counts):
            # the stacked pools stay ONE buffer through the layers: carried,
            # written in place, read in place by layer index.  ``at``: the
            # (static) layer whose pattern this one repeats, where ``i`` is
            # its traced place in its kind's stack
            x, pools = carry
            layer, i = inp
            attend = functools.partial(mixer, index=i, **paged)
            if c.has_sparse:    # its decode step counts what it attended
                attend = functools.partial(attend, counts=counts)
            if not (c.attn_window or c.has_state):
                x, pools, _ = self.block(
                    x, layer, positions, attend, pools, train=False,
                    counts=counts,
                    rotary=True if at is None else c.layer_rotary(at))
                return (x, pools), None
            kind, window = kind_of(at), c.layer_window(at)
            if c.layer_lin(at):
                mix = functools.partial(
                    self.mix_lin_paged, index=i, lengths=lengths,
                    real_lengths=real_lengths, slots=state_slots)
            elif kind == "ssm":
                mix = functools.partial(
                    self.mix_ssm_paged, index=i, lengths=lengths,
                    real_lengths=real_lengths, slots=state_slots,
                    impl=impl, interpret=attn_interpret)
            elif kind == "ring":
                mix = functools.partial(self.mix_ring, index=i,
                                        **ring_mix[window])
            else:
                mix = attend
            x, pool, _ = self.block(x, layer, positions, mix,
                                    getattr(pools, kind), train=False,
                                    counts=counts,
                                    rotary=c.layer_rotary(at))
            return (x, pools._replace(**{kind: pool})), None

        def kind_of(i):
            """Which of the dispatch's pools layer ``i`` keeps its cache
            in: its kind of mixer (a linear-attention layer's state lies
            where a state-space layer's would)."""
            return "ssm" if c.layer_state(i) else \
                "ring" if c.layer_window(i) else "full"

        def place(i):
            """Layer ``i``'s place in the stack of its kind."""
            return sum(1 for j in range(i) if kind_of(j) == kind_of(i))

        if isinstance(params["layers"], (list, tuple)):
            # MoE / heterogeneous stack: static per-layer loop (expert
            # leaves carry an [E, ...] dim sharded over ep at serve time —
            # the MoE dispatch inside the block lowers to the same
            # all-to-alls as training, reference megatron_gpt_moe serving)
            carry = (x, caches)
            for i, layer in enumerate(params["layers"]):
                carry, _ = body(carry, (layer, place(i)), i)
            if c.layer_period:
                # the periods that follow repeat one pattern: scanned, each
                # layer's place in its stack a traced step on from the
                # first period's
                lead, period = c.leading_layers, c.layer_period
                kinds = [kind_of(lead + j) for j in range(period)]
                stride = [kinds.count(kind) for kind in kinds]

                # the experts' weights stay stacked, out of the scanned
                # operands: the grouped product reads period ``p``'s in
                # place (``stack_layer``); cut out by the scan, all of a layer's
                # experts would be copied every iteration
                scanned, stacks = zip(*map(_hold_expert_stack,
                                           params["periods"]))

                def one_period(carry, inp):
                    layers, p = inp
                    inner = None if counts is None else counts.fresh()
                    for j, layer in enumerate(layers):
                        if stacks[j]:
                            layer = dict(layer, moe=dict(
                                layer["moe"], **stacks[j], stack_layer=p))
                        carry, _ = body(
                            carry, (layer, place(lead + j) + p * stride[j]),
                            lead + j, inner)
                    return carry, None if inner is None else inner.vector()

                carry, counted = jax.lax.scan(
                    one_period, carry,
                    (scanned, jnp.arange((c.n_layers - lead) // period)))
                if counts is not None:
                    counts.absorb(counted)
            x, caches = carry
        else:
            (x, caches), _ = jax.lax.scan(
                body, (x, caches),
                (params["layers"], jnp.arange(c.n_layers)))

        if head_rows is not None:
            x = jnp.take_along_axis(x, head_rows[:, :, None], axis=1)
        if counts is not None:
            return (self.logits(params, x), caches, lengths + T,
                    counts.vector())
        return self.logits(params, x), caches, lengths + T

    # ------------------------------------------------------------------
    # layer-stream contract (training-time parameter offload —
    # runtime/zero/param_stream.py; reference partition_parameters.py:539
    # zero.Init(remote_device) + partitioned_param_coordinator.py:458).
    # These decompose apply()/loss() into per-layer programs with
    # IDENTICAL math, so the streamed step's trajectory matches the
    # scan-over-layers step.
    # ------------------------------------------------------------------
    def stream_split(self, params):
        """(resident, layers): resident = everything device-pinned
        (embeddings / head / final norm), layers = the streamed stack."""
        resident = {k: v for k, v in params.items() if k != "layers"}
        return resident, params["layers"]

    def stream_join(self, resident, layers):
        out = dict(resident)
        out["layers"] = layers
        return out

    def stream_embed(self, resident, batch, rng=None):
        """Embedding front of ``apply`` → (x, positions)."""
        del rng
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        B, S = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x = maybe_constrain(self.embed(resident, input_ids, positions),
                            P(tuple(BATCH_AXES), SP_AXIS, None))
        return x, positions

    def stream_layer(self, layer, x, positions, window=None, rng=None,
                     train=True):
        """One transformer block → (x, aux).  ``window``: traced scalar
        per-layer sliding window (0 = global), matching the scan's
        side-input convention."""
        if window is not None:
            layer = dict(layer, attn_window=window)
        return self._layer(x, layer, positions, rng, train)

    def stream_head_loss(self, resident, x, batch):
        """Final norm + LM head + next-token cross-entropy on the last
        hidden state — the tail of ``loss`` (chunked logits included)."""
        c = self.config
        if c.loss_chunk_size and c.loss_chunk_size > 0:
            return chunked_next_token_xent(
                self.final_norm(resident, x), self.head_table(resident),
                resident.get("lm_head_b"), batch, c.loss_chunk_size,
                logit_softcap=c.final_logit_softcap,
                logit_scale=c.final_logit_scale)
        return next_token_xent(self.logits(resident, x), batch)

    # ------------------------------------------------------------------
    merge_train_counters = staticmethod(merge_train_counters)

    def _trains_on_flash(self, seq):
        """``mix_full`` takes the flash kernel for sequences of ``seq``
        tokens (not under another ``attn_impl``, on the CPU, with a score
        cap, for a shape it cannot tile or for latent attention)."""
        from deepspeed_tpu.ops.pallas.flash_attention import flash_tiles
        c = self.config
        flash = c.attn_impl == "pallas" or (
            c.attn_impl == "auto" and jax.default_backend() != "cpu")
        return bool(flash and not c.is_latent and not c.attn_logit_softcap
                    and flash_tiles(seq, c.n_heads, c.kv_heads,
                                    c.attn_block_q, c.attn_block_k))

    def saved_attention_bytes(self, batch, seq, itemsize=2):
        """``ATTN_SAVED``: the bytes ONE attention layer keeps of one
        micro-batch of ``batch`` sequences of ``seq`` tokens (all shards')
        from its flash forward to its backward pass, the result's
        ``B S H D`` elements of the compute dtype (``itemsize``) and the
        ``B H S`` float32 of ``lse``: so much more is alive a layer, and
        the forward kernel runs once.  0 where the layers' policy keeps
        neither (``TransformerConfig.checkpoint_policy``) or they are not
        checkpointed; None where the kernel does not run."""
        c = self.config
        if not self._trains_on_flash(seq):
            return None
        if not c.keeps_flash_residuals:
            return 0
        return batch * seq * c.n_heads * (c.head_dim * itemsize + 4)

    def attention_plan(self, batch, seq):
        """``ATTN_PLAN`` of one micro-batch of ``batch`` sequences of
        ``seq`` tokens: what its flash kernels (forward, dq, dk/dv: a tile
        of one is a tile of each) visit and mask and how many pairs they
        compute for how many attended to, summed over heads and layers,
        from the bounds the kernels' loops run on
        (``ops/pallas/flash_attention.py flash_plan``).  None where
        ``mix_full`` does not take the kernel (another ``attn_impl``, the
        CPU, a score cap, a shape it cannot tile, latent attention)."""
        from deepspeed_tpu.ops.pallas.flash_attention import (
            flash_plan, resolve_tiles)
        c = self.config
        if not self._trains_on_flash(seq):
            return None
        tiles = resolve_tiles(seq, c.head_dim, c.n_heads // c.kv_heads, 2,
                              c.attn_block_q, c.attn_block_k)
        total = dict.fromkeys(ATTN_PLAN, 0)
        for i in range(c.n_layers):
            if c.layer_ssm(i):
                continue
            plan = flash_plan(seq, *tiles, causal=True,
                              window=c.layer_window(i))
            for name in ATTN_PLAN:
                total[name] += 3 * batch * c.n_heads * plan[name]
        return total

    @property
    def train_counters(self):
        """Names of what ``loss(counted=True)`` counts beside the loss
        (``TRAIN_COUNTERS`` for a dropless expert model, else none)."""
        c = self.config
        return TRAIN_COUNTERS if c.is_moe and c.moe_dropless else ()

    def loss(self, params, batch, rng=None, counted=False):
        """Next-token cross-entropy.  batch: dict with ``input_ids`` [B,S]
        (+ optional ``labels``, ``loss_mask``) or a raw [B,S] array.
        ``counted``: (loss, int32 vector of ``train_counters``)."""
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        counts = ServeCounts(None) if counted else None
        x, aux = self.apply(params, input_ids, rng=rng, return_hidden=True,
                            counts=counts)
        ce = self.stream_head_loss(params, x, batch)
        # MoE load-balancing loss (reference engine adds l_aux scaled by coef)
        loss = ce + self.config.moe_aux_loss_coef * aux
        if not counted:
            return loss
        return loss, jnp.stack([
            jnp.asarray(counts.counts.get(name, 0), jnp.int32)
            for name in self.train_counters])
