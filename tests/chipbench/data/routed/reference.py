"""Plain float32 reference of the made-up routed family, with its routing
and the rows that routing leaves decided.

Block: x + attention(rms_norm(x)); then h = rms_norm(x), selection scores
s = h @ wg in float32, the one expert e = argmax(s), and
x + softmax(s)[e] * expert_e(h), each expert a SwiGLU.  No capacity, no
dropped token: the family's evaluation capacity holds every token.
"""

import jax
import jax.numpy as jnp

from chipbench.reference import common

# A row is undecided where, at some layer, its own token's best and
# second-best selection scores lie closer than this.  The cell is served
# in float32: program and reference differ by the order of their sums,
# about 1e-6 of a hidden state's entries after two layers (the logits
# agree to 1e-5, tests/chipbench/test_routed.py), and a score is a sum of
# 64 entries of unit size against weights of variance 1/64, so the two
# sides' scores differ by about 1e-6 and a gap of two scores by as much.
# 1e-3 is a thousand of those, and masks about one row in 500.  Every
# expert is held here, so every pair of candidates counts.
MARGIN = 1e-3

# A choice that flips at an EARLIER token reaches a row through attention:
# where this is above 0, a row is also undecided once some earlier token's
# margin, at a layer before the last (what the last layer feeds reaches no
# other token), is under it.  Served in float32 nothing in the context
# flips unrigged, so it is off.  Served in bfloat16 (the tests' bf16 case
# sets both margins) program and reference chose differently at margins up
# to 0.0154 (CPU, 16 seeds, 1,152 rows), and with one expert of four a
# token a context flip moves the rows after it by 0.03-0.2: this family's
# rule needs the context, where a many-expert model's may not
# (chipbench/serve_cell.py has that reckoning; it is unverified).
CONTEXT_MARGIN = 0.0


def _attention(x, w, positions, heads, theta):
    B, S, d = x.shape
    dh = d // heads
    q, k, v = ((x @ w[name]).reshape(B, S, heads, dh)
               for name in ("wq", "wk", "wv"))
    q = common.rotate_half_rope(q, positions, theta, dh)
    k = common.rotate_half_rope(k, positions, theta, dh)
    return common.causal_attention(q, k, v) @ w["wo"]


def _experts(h, moe):
    """(routed output, margin of the routing) of tokens h: [B, S, d]."""
    scores = h @ moe["wg"]                                   # [B, S, E]
    best = jnp.sort(scores, axis=-1)
    margin = best[..., -1] - best[..., -2]
    chosen = jnp.argmax(scores, axis=-1)
    weight = jnp.take_along_axis(jax.nn.softmax(scores, axis=-1),
                                 chosen[..., None], axis=-1)
    out = jnp.zeros_like(h)
    for e in range(moe["wg"].shape[-1]):        # one expert at a time
        inner = jax.nn.silu(h @ moe["w_gate"][e]) * (h @ moe["w_up"][e])
        out = out + jnp.where((chosen == e)[..., None],
                              inner @ moe["w_down"][e], 0.0)
    return weight * out, margin


@common.highest
def logits(params, ids, cfg, last=None):
    """ids: [B, S] -> (float32 logits [B, S or last, vocab], decided
    [B, S or last]): a row is decided where its own token's routing margin
    is at least MARGIN in every layer and no earlier token's is under
    CONTEXT_MARGIN in a layer before the last."""
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    x = params["tok_embed"][ids].astype(jnp.float32)
    decided = jnp.ones(ids.shape, bool)
    layers = len(params["layers"])
    for depth, layer in enumerate(params["layers"]):
        w = common.f32(layer)
        x = x + _attention(common.rms_norm(x, w["attn_norm"], eps), w,
                           positions, cfg["num_attention_heads"], theta)
        routed, margin = _experts(common.rms_norm(x, w["mlp_norm"], eps),
                                  w["moe"])
        x = x + routed
        decided = decided & (margin >= MARGIN)
        if depth < layers - 1:
            near = (margin < CONTEXT_MARGIN).astype(jnp.int32)
            decided = decided & (jnp.cumsum(near, axis=1) - near == 0)
    x = common.rms_norm(x, params["final_norm"].astype(jnp.float32), eps)
    if last is not None:
        x, decided = x[:, -last:], decided[:, -last:]
    head = (params["tok_embed"].T if cfg["tie_word_embeddings"]
            else params["lm_head"])
    return x @ head.astype(jnp.float32), decided
