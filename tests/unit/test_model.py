"""Transformer model tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.ops.attention import reference_attention


def test_forward_shapes():
    cfg = TransformerConfig.tiny()
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    ids = jnp.zeros((2, 16), jnp.int32)
    logits = model.apply(params, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_gqa_forward():
    cfg = TransformerConfig.tiny(n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    logits = model.apply(params, jnp.zeros((2, 8), jnp.int32))
    assert logits.shape == (2, 8, cfg.vocab_size)


def test_causality():
    """Changing a future token must not affect past logits."""
    cfg = TransformerConfig.tiny()
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    ids1 = jnp.zeros((1, 8), jnp.int32)
    ids2 = ids1.at[0, 7].set(5)
    l1 = model.apply(params, ids1)
    l2 = model.apply(params, ids2)
    np.testing.assert_allclose(l1[0, :7], l2[0, :7], atol=1e-5)
    assert not np.allclose(l1[0, 7], l2[0, 7])


def test_gpt2_preset_size():
    cfg = TransformerConfig.gpt2_125m()
    n = cfg.num_params()
    assert 100e6 < n < 170e6


def test_llama7b_preset_size():
    cfg = TransformerConfig.llama2_7b()
    assert 6.5e9 < cfg.num_params() < 7.5e9


def test_llama70b_preset_size():
    cfg = TransformerConfig.llama2_70b()
    assert 65e9 < cfg.num_params() < 72e9


def test_reference_attention_gqa_equals_repeat():
    rng = jax.random.key(0)
    q = jax.random.normal(rng, (2, 8, 4, 16))
    k = jax.random.normal(jax.random.key(1), (2, 8, 2, 16))
    v = jax.random.normal(jax.random.key(2), (2, 8, 2, 16))
    out = reference_attention(q, k, v)
    k_rep = jnp.repeat(k, 2, axis=2)
    v_rep = jnp.repeat(v, 2, axis=2)
    out_rep = reference_attention(q, k_rep, v_rep)
    np.testing.assert_allclose(out, out_rep, atol=1e-6)


def test_loss_mask():
    cfg = TransformerConfig.tiny()
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(3), (2, 16), 0, cfg.vocab_size)
    full = model.loss(params, {"input_ids": ids})
    masked = model.loss(params, {"input_ids": ids,
                                 "loss_mask": jnp.ones_like(ids)})
    np.testing.assert_allclose(full, masked, rtol=1e-6)


def test_train_with_tp_mesh():
    """2-way TP × 4-way fsdp end-to-end."""
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    ds_config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3},
        "mesh": {"tp": 2, "fsdp": 4},
    }
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds_config,
        tp_rules=model.tp_rules())
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, size=(8, 32))}
    losses = [float(engine.train_batch(batch=batch)) for _ in range(5)]
    assert losses[-1] < losses[0]
    wq = engine.state.params["layers"]["wq"]
    assert "tp" in str(wq.sharding.spec)


def test_tied_embeddings():
    cfg = TransformerConfig.tiny(tie_embeddings=True)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    assert "lm_head" not in params
    logits = model.apply(params, jnp.zeros((1, 4), jnp.int32))
    assert logits.shape[-1] == cfg.vocab_size


# ----------------------------------------------------------------------
# chunked cross-entropy (streamed logits)
# ----------------------------------------------------------------------
def test_chunked_xent_matches_dense_loss():
    """chunked_next_token_xent streams [chunk,V] logits under a remat'd
    scan; per-token softmax is chunking-independent, so loss and grads
    must match the dense path to fp32 noise (including ragged padding)."""
    import dataclasses
    from deepspeed_tpu.models.transformer import chunked_next_token_xent

    cfg_d = dataclasses.replace(TransformerConfig.tiny(), loss_chunk_size=0)
    cfg_c = dataclasses.replace(cfg_d, loss_chunk_size=7)  # ragged chunks
    m_d, m_c = CausalTransformerLM(cfg_d), CausalTransformerLM(cfg_c)
    params = m_d.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {
        "input_ids": jnp.asarray(
            rng.integers(0, cfg_d.vocab_size, (3, 33)), jnp.int32),
        "loss_mask": jnp.asarray(rng.random((3, 33)) > 0.3, jnp.float32),
    }
    l_d, l_c = float(m_d.loss(params, batch)), float(m_c.loss(params, batch))
    assert abs(l_d - l_c) < 1e-5
    g_d = jax.grad(lambda p: m_d.loss(p, batch))(params)
    g_c = jax.grad(lambda p: m_c.loss(p, batch))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                atol=1e-5), g_d, g_c)


def test_chunked_xent_explicit_labels():
    from deepspeed_tpu.models.transformer import (chunked_next_token_xent,
                                                  next_token_xent)
    rng = np.random.default_rng(1)
    B, S, d, V = 2, 9, 8, 32
    x = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(d, V)), jnp.float32)
    head_b = jnp.asarray(rng.normal(size=(V,)), jnp.float32)
    batch = {"input_ids": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)}
    logits = (x @ head) + head_b
    want = float(next_token_xent(logits, batch))
    got = float(chunked_next_token_xent(x, head, head_b, batch, 4))
    assert abs(want - got) < 1e-5


def test_qk_norm_scratch_init_trains():
    """qk_norm must work from scratch init (not just HF conversion):
    init materializes q_norm/k_norm at the right shapes (per-head [dh]
    vs rms_flat [H*dh]/[Hkv*dh] with GQA) and the forward consumes
    them."""
    import numpy as np
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    for mode, qshape, kshape in (("rms", (2, 16), (2, 16)),
                                 ("rms_flat", (2, 64), (2, 32))):
        cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4,
                                     n_kv_heads=2, qk_norm=mode)
        model = CausalTransformerLM(cfg)
        params = model.init(jax.random.key(0))
        assert params["layers"]["q_norm"].shape == qshape, mode
        assert params["layers"]["k_norm"].shape == kshape, mode
        ids = jnp.asarray(np.arange(32, dtype=np.int32)[None, :])
        logits = model.apply(params, ids, train=False)
        assert np.isfinite(np.asarray(logits)).all(), mode


def test_residual_scale_consistent_across_paths():
    """residual_scale must mean the same thing in apply() and the cached
    decode path, including under parallel_block."""
    import numpy as np
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    for parallel in (False, True):
        cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4,
                                     residual_scale=0.5,
                                     parallel_block=parallel)
        model = CausalTransformerLM(cfg)
        params = model.init(jax.random.key(1))
        ids = np.arange(24, dtype=np.int32)[None, :]
        full = np.asarray(model.apply(params, jnp.asarray(ids),
                                      train=False))
        caches = model.init_caches(1, 32, dtype=jnp.float32)
        cached_logits, _ = model.apply_with_cache(params,
                                                  jnp.asarray(ids), caches)
        np.testing.assert_allclose(full, np.asarray(cached_logits),
                                   rtol=2e-4, atol=2e-5)


# One case for each architecture feature that each forward used to spell
# out for itself.  ``extra`` names layer leaves ``init`` does not make for
# the flags alone (Gemma-2 keeps its pre-norms beside the sandwich norms).
FORWARD_FEATURES = {
    "parallel_block": dict(parallel_block=True, use_rmsnorm=False,
                           norm_bias=True, activation="gelu"),
    "post_norm_only": dict(post_norm_only=True),
    "sandwich_norms": dict(extra=("attn_post_norm", "mlp_post_norm")),
    "residual_scale": dict(residual_scale=0.5),
    "embed_scale": dict(embed_scale=8.0),
    "embed_norm": dict(embed_norm=True, use_rmsnorm=False, norm_bias=True),
    "lm_head_bias": dict(lm_head_bias=True),
    "final_logit_scale": dict(final_logit_scale=0.25),
    "final_logit_softcap": dict(final_logit_softcap=5.0,
                                attn_logit_softcap=10.0),
    "qk_norm_rms": dict(qk_norm="rms"),
    "qk_norm_rms_flat": dict(qk_norm="rms_flat"),
    "tied_head": dict(tie_embeddings=True),
    "partial_rotary": dict(rope_dim=8),
    "learned_positions": dict(use_rope=False, use_bias=True),
}


@pytest.mark.parametrize("feature", sorted(FORWARD_FEATURES))
def test_every_forward_is_the_same_model(feature):
    """``apply``'s logits against the dense-cache forward, the paged one
    (jnp pair) and ``InferenceEngine``'s layer-streamed one, each as a
    prefill and four decode steps: the embedding, the block and the head
    are stated once, so a feature cannot reach one forward and miss
    another."""
    from deepspeed_tpu.parallel import groups
    settings = dict(FORWARD_FEATURES[feature])
    extra = settings.pop("extra", ())
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2,
                                 **settings)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    for name in extra:
        params["layers"][name] = jnp.ones((cfg.n_layers, cfg.hidden_size))
    # no leaf left at the ones and zeros a dropped norm or bias would hide
    # behind
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    params = jax.tree_util.tree_unflatten(treedef, [
        x + 0.1 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])

    B, T0, steps, page = 2, 6, 4, 4
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, T0 + steps)), jnp.int32)
    want = np.asarray(model.apply(params, ids, train=False))
    pieces = [ids[:, :T0]] + [ids[:, T0 + i:T0 + i + 1]
                              for i in range(steps)]

    def check(got, name):
        np.testing.assert_allclose(
            np.concatenate([np.asarray(g) for g in got], axis=1), want,
            rtol=2e-4, atol=2e-4, err_msg=f"{feature}: {name}")

    caches, got = model.init_caches(B, 16, dtype=jnp.float32), []
    for piece in pieces:
        logits, caches = model.apply_with_cache(params, piece, caches)
        got.append(logits)
    check(got, "apply_with_cache")

    pools = model.init_paged_caches(1 + B * 3, page, dtype=jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
    lengths, got = jnp.zeros((B,), jnp.int32), []
    for piece in pieces:
        logits, pools, lengths = model.apply_with_paged_cache(
            params, piece, pools, tables, lengths, attn_backend="jnp")
        got.append(logits)
    check(got, "apply_with_paged_cache")

    engine = deepspeed_tpu.init_inference(
        model=model, params=params, dtype="fp32",
        zero={"offload_param": {"device": "cpu"}})
    try:
        assert engine._streaming
        caches, got = None, []
        for piece in pieces:
            logits, caches = engine.forward(piece, caches)
            got.append(logits)
        check(got, "InferenceEngine, layer-streamed")
    finally:
        groups.reset_mesh()
