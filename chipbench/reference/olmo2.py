"""Plain float32 reference of OLMo-2, after the published model: no
pre-norms (x + norm(sublayer(x))), RMSNorm of the whole flat query and key
projections before the rotary embedding, SwiGLU, untied head."""

import jax
import jax.numpy as jnp

from chipbench.reference import common


def _block(x, w, positions, heads, kv_heads, theta, eps):
    B, S, d = x.shape
    dh = d // heads
    q = common.rms_norm(x @ w["wq"], w["q_norm"], eps).reshape(B, S, heads, dh)
    k = common.rms_norm(x @ w["wk"], w["k_norm"], eps).reshape(
        B, S, kv_heads, dh)
    v = (x @ w["wv"]).reshape(B, S, kv_heads, dh)
    q = common.rotate_half_rope(q, positions, theta, dh)
    k = common.rotate_half_rope(k, positions, theta, dh)
    if kv_heads != heads:
        k = jnp.repeat(k, heads // kv_heads, axis=2)
        v = jnp.repeat(v, heads // kv_heads, axis=2)
    attn = common.causal_attention(q, k, v) @ w["wo"]
    x = x + common.rms_norm(attn, w["attn_post_norm"], eps)
    mlp = (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]
    return x + common.rms_norm(mlp, w["mlp_post_norm"], eps)


@common.highest
def logits(params, ids, cfg, last=None):
    """ids: [B, S] -> float32 logits [B, S, vocab], or of the ``last``
    positions only."""
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    x = params["tok_embed"][ids].astype(jnp.float32)
    x = common.run_stack(
        lambda x, w, pos: _block(
            x, w, pos, cfg["num_attention_heads"],
            cfg["num_key_value_heads"], float(cfg["rope_theta"]),
            cfg["rms_norm_eps"]),
        x, params["layers"], positions)
    x = common.rms_norm(x, params["final_norm"].astype(jnp.float32),
                        cfg["rms_norm_eps"])
    if last is not None:
        x = x[:, -last:]
    head = (params["tok_embed"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])
    return x @ head.astype(jnp.float32)
