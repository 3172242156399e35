"""Plain float32 reference of GPT-NeoX (Pythia), after the published
model: LayerNorm with bias, biased projections, rotary embedding on the
first ``rotary_pct`` of each head, GELU (erf form) and, with
``use_parallel_residual``, x + attn(ln1 x) + mlp(ln2 x)."""

import jax
import jax.numpy as jnp

from chipbench.reference import common


def _block(x, w, positions, heads, rotary_dim, theta, eps, parallel):
    B, S, d = x.shape

    def attn(h):
        q, k, v = (
            (h @ w[n] + w[n + "_b"]).reshape(B, S, heads, d // heads)
            for n in ("wq", "wk", "wv"))
        q = common.rotate_half_rope(q, positions, theta, rotary_dim)
        k = common.rotate_half_rope(k, positions, theta, rotary_dim)
        return common.causal_attention(q, k, v) @ w["wo"] + w["wo_b"]

    def mlp(h):
        inner = jax.nn.gelu(h @ w["w_up"] + w["w_up_b"], approximate=False)
        return inner @ w["w_down"] + w["w_down_b"]

    ln1 = lambda h: common.layer_norm(   # noqa: E731
        h, w["attn_norm"], w["attn_norm_b"], eps)
    ln2 = lambda h: common.layer_norm(   # noqa: E731
        h, w["mlp_norm"], w["mlp_norm_b"], eps)
    if parallel:
        return x + attn(ln1(x)) + mlp(ln2(x))
    x = x + attn(ln1(x))
    return x + mlp(ln2(x))


@common.highest
def logits(params, ids, cfg, last=None):
    """ids: [B, S] -> float32 logits [B, S, vocab], or of the ``last``
    positions only."""
    heads = cfg["num_attention_heads"]
    rotary_dim = int(cfg["hidden_size"] // heads * cfg["rotary_pct"])
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    x = params["tok_embed"][ids].astype(jnp.float32)
    x = common.run_stack(
        lambda x, w, pos: _block(
            x, w, pos, heads, rotary_dim, float(cfg["rotary_emb_base"]),
            cfg["layer_norm_eps"], cfg["use_parallel_residual"]),
        x, params["layers"], positions)
    x = common.layer_norm(x, *common.f32((params["final_norm"],
                                          params["final_norm_b"])),
                          cfg["layer_norm_eps"])
    if last is not None:
        x = x[:, -last:]
    head = (params["tok_embed"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])
    return x @ head.astype(jnp.float32)
