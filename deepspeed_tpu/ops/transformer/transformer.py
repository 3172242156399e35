"""Fused training transformer layer — parity surface.

Parity: reference ``deepspeed/ops/transformer/transformer.py``
(``DeepSpeedTransformerConfig``/``DeepSpeedTransformerLayer`` backed by the
``transformer`` CUDA op: a fully fused fwd+bwd encoder layer; the
``stochastic_transformer`` variant trades determinism for speed).

TPU design: one jitted layer IS the fused kernel — XLA fuses
norm+qkv+attention+mlp, and autodiff supplies the fused backward; the
Pallas flash-attention path covers the attention core.  This class adapts
the reference's layer-level API onto ``CausalTransformerLM``'s single-layer
machinery so code written against DeepSpeedTransformerLayer ports directly.
"""

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)


@dataclass
class DeepSpeedTransformerConfig:
    """Reference ctor args (transformer.py DeepSpeedTransformerConfig)."""
    batch_size: int = 1
    hidden_size: int = 768
    intermediate_size: Optional[int] = None
    heads: int = 12
    attn_dropout_ratio: float = 0.0
    hidden_dropout_ratio: float = 0.0
    num_hidden_layers: int = 1
    initializer_range: float = 0.02
    seed: int = 0
    fp16: bool = False
    pre_layer_norm: bool = True
    normalize_invertible: bool = False
    gelu_checkpoint: bool = False
    stochastic_mode: bool = False
    huggingface: bool = False
    training: bool = True

    def to_model_config(self) -> TransformerConfig:
        return TransformerConfig(
            vocab_size=1, hidden_size=self.hidden_size, n_layers=1,
            n_heads=self.heads,
            ffn_hidden_size=self.intermediate_size or 4 * self.hidden_size,
            activation="gelu", use_rmsnorm=False, use_rope=True,
            use_bias=True, norm_bias=True, remat=self.gelu_checkpoint)


class DeepSpeedTransformerLayer:
    """One pre-LN encoder/decoder layer with the reference's call shape:
    ``layer(params, hidden_states)``. Causality follows ``causal=``
    (the reference BERT kernel is bidirectional)."""

    def __init__(self, config: DeepSpeedTransformerConfig, causal=False):
        self.config = config
        self.causal = causal
        mc = config.to_model_config()
        if not causal:
            mc = TransformerConfig(**{**mc.__dict__, "attn_impl": "reference"})
        self.model_config = mc
        self._lm = CausalTransformerLM(mc)
        self._compiled = None

    def init(self, rng, dtype=jnp.float32):
        """Single-layer params (the model's stacked layout with L=1)."""
        full = self._lm.init(rng, dtype=dtype)
        return full["layers"]

    def __call__(self, params, hidden_states, attention_mask=None, rng=None):
        B, S, _ = hidden_states.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        layer = jax.tree_util.tree_map(lambda x: x[0], params)  # drop L dim
        mix = self._lm.mix_full
        if not self.causal:
            # bidirectional: reference BERT-style full attention
            from deepspeed_tpu.ops.attention import reference_attention

            def mix(q, k, v, layer, cache):
                return reference_attention(q, k, v, causal=False), cache
        x, _, _ = self._lm.block(hidden_states, layer, positions, mix,
                                 rng=rng, train=self.config.training)
        return x

    forward = __call__


# stochastic variant: same math on TPU (XLA is deterministic); kept for API
DeepSpeedStochasticTransformerLayer = DeepSpeedTransformerLayer
