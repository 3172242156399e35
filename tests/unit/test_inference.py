"""Inference engine tests (parity model: reference ``unit/inference/``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)


@pytest.fixture
def tiny_model():
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def test_init_inference_api(tiny_model):
    cfg, model, params = tiny_model
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32", "max_out_tokens": 64},
        params=params)
    ids = np.arange(8)[None, :] % cfg.vocab_size
    logits, caches = engine.forward(ids)
    assert logits.shape == (1, 8, cfg.vocab_size)


def test_generate_greedy_matches_training_forward(tiny_model):
    """Decode-loop logits must agree with the training (full) forward —
    the KV-cache path is an exact rewrite, not an approximation."""
    cfg, model, params = tiny_model
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=(2, 5))
    out = engine.generate(prompt, max_new_tokens=6)
    assert out.shape == (2, 11)

    # replay: greedy next-token from the full training forward
    seq = jnp.asarray(prompt)
    for _ in range(6):
        logits = model.apply(params, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        seq = jnp.concatenate([seq, nxt.astype(seq.dtype)], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


@pytest.mark.parametrize("variant", [
    # Bloom-shaped: ALiBi + embedding LayerNorm
    dict(use_alibi=True, embed_norm=True, use_rope=False, use_rmsnorm=False,
         activation="gelu", use_bias=True, norm_bias=True,
         tie_embeddings=True),
    # GPT-J-shaped: parallel residual + partial rotary + biased head
    dict(parallel_block=True, rope_dim=8, activation="gelu",
         use_rmsnorm=False, norm_bias=True, lm_head_bias=True),
    # GPT-Neo-shaped: unscaled attention + alternating local windows
    dict(attn_scale=1.0, local_attn_pattern=(0, 4), use_rope=False,
         use_rmsnorm=False, activation="gelu", use_bias=True, norm_bias=True,
         tie_embeddings=True),
])
def test_decode_matches_training_forward_new_archs(variant):
    """The KV-cache decode path must reproduce the full forward for the
    Bloom/GPT-J/GPT-Neo architecture features (alibi, parallel block,
    local windows) through the dense-cache mixer of the shared block."""
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, **variant)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(1))
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, size=(2, 7))
    out = engine.generate(prompt, max_new_tokens=5)

    seq = jnp.asarray(prompt)
    for _ in range(5):
        logits = model.apply(params, seq, train=False)
        nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        seq = jnp.concatenate([seq, nxt.astype(seq.dtype)], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_generate_with_tp(tiny_model):
    cfg, model, params = tiny_model
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32", "tensor_parallel": {"tp_size": 2}},
        params=params)
    assert engine.mesh.shape["tp"] == 2
    out = engine.generate(np.zeros((1, 4), np.int32), max_new_tokens=4)
    assert out.shape == (1, 8)


def test_generate_temperature_sampling(tiny_model):
    cfg, model, params = tiny_model
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32"}, params=params)
    prompt = np.zeros((1, 4), np.int32)
    a = engine.generate(prompt, max_new_tokens=8, temperature=1.5, seed=1)
    b = engine.generate(prompt, max_new_tokens=8, temperature=1.5, seed=2)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_mp_size_legacy_alias(tiny_model):
    cfg, model, params = tiny_model
    engine = deepspeed_tpu.init_inference(
        model=model, config={"dtype": "float32", "mp_size": 2}, params=params)
    assert engine._config.tp_size == 2
