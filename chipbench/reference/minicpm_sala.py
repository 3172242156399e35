"""Plain float32 reference of MiniCPM-SALA (``model_type: minicpm_sala``),
written from the equations ISSUE 56 states (no network here: they rest on
the catalog row's ``config``, on MiniCPM4's public ``sparse_config`` and
description (InfLLM v2, arXiv:2506.07900) and on Lightning Attention
(arXiv:2401.04658); what is no key of the row is under ``assumed`` in the
configuration's file).  Nothing here imports the program; the weights
arrive as its parameter tree (names are the interface): ``params["layers"]``
the leading layers as a list (none here), ``params["periods"][j]`` the
layers of position ``j`` in the listed period, stacked on a leading axis.

The stream (MiniCPM's muP convention), RMSNorm (eps ``rms_norm_eps``):
  x0 = E[ids] * scale_emb
  x = x + r * mixer(norm(x; attn_norm)),  r = scale_depth / sqrt(DEPTH)
  x = x + r * mlp(norm(x; mlp_norm)),     DEPTH the PUBLISHED number of
  mlp(h) = (silu(h W_gate) * (h W_up)) W_down      layers, whatever is held
  logits = (norm(x; final_norm) / (hidden_size / dim_model_base)) W_head

``minicpm4`` layers: q = h Wq [T, H, D], k = h Wk, v = h Wv [T, Hkv, D],
no bias; per-head RMSNorm on q and k (a weight of D each); NO rotary
(``attn_use_rope`` false); softmax scale 1 / sqrt(D); the heads' output
times sigmoid(h W_g) ahead of Wo.  Which keys query ``t`` attends, for a
request whose context (at the time the token was dispatched) is
``dense_len`` or more: compressed key ``c_j = mean(k_g[stride j : stride j
+ kernel])`` of key/value head ``g``, visible iff ``stride j + kernel - 1
<= t``; each of the group's H / Hkv query heads ``p_h = softmax_j(q_h .
c_j / sqrt(D))`` over the visible j; ``P_j = sum_h p_h[j]``; block ``b``
(keys ``block b .. block b + block - 1``) scores the largest ``P_j`` of the
visible compressed keys that overlap it; forced in: the first
``init_blocks`` blocks and the ``window / block`` blocks up to the
query's own; the query's blocks are the forced ones and the
highest-scoring others up to ``topk`` in all, ties to the lower index,
one set a group; then the ordinary causal softmax over those blocks'
keys.  Under ``dense_len`` the layer attends every causal key.  Every
query is taken by itself (``QUERIES`` of them a step, so that a 16k
prompt fits beside the engine): its own scores, its own block set by
RANK (no sort, no top-k routine), its own softmax.

``lightning-attn`` layers: q, k, v of ``lightning_nh`` heads of
``lightning_head_dim`` (no grouping); per-head RMSNorm on q and k; rotary
on the whole head, ``rope_theta``, halves rotated; a head's state S [D,
D]: ``S_t = a_h S_{t-1} + k_t^T v_t``, ``o_t = (q_t / sqrt(D)) S_t``,
``a_h = exp(-2^(-8 (h + 1) / H))``; ``y = (norm_over_all_heads(o) *
sigmoid(h W_g)) W_o``.  The recurrence runs as a ``lax.scan`` over single
tokens: no chunks, no cache, no slots.

``contexts`` (optional, [S]): the request's context when each position
was dispatched (a prompt's tokens: the prompt's length; a decoded token
``t``: ``t + 1``); without it every position's is S, one prefill of the
whole sequence.  It decides nothing but the dense switch.

Not ``ROUTED``: a plain array comes back.  A block that flips between
the bf16 program and this file trades one of ``topk`` blocks for its
neighbour in score (ISSUE 56, item 2).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common

QUERIES = 128       # queries a step of a sparse layer


def block_scores(q, c, t, sz):
    """q: [C, Hkv, R, D] queries at positions ``t`` [C]; c: [J, Hkv, D].
    -> (score [C, Hkv, NB]: ``+inf`` on a forced block, ``-inf`` on one
    with no key at or before ``t``; valid [C, NB])."""
    J, nb = c.shape[0], sz["n_blocks"]
    st, ks, bs = sz["stride"], sz["kernel"], sz["block"]
    j, b = np.arange(J), np.arange(nb)
    visible = jnp.asarray(st * j + ks - 1)[None, :] <= t[:, None]    # [C, J]
    dots = jnp.einsum("chrd,jhd->chrj", q, c) / np.sqrt(q.shape[-1])
    dots = jnp.where(visible[:, None, None, :], dots, -jnp.inf)
    top = jnp.max(dots, axis=-1, keepdims=True)
    e = jnp.where(visible[:, None, None, :],
                  jnp.exp(dots - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    total = jnp.sum(e, axis=-1, keepdims=True)
    p = jnp.where(total > 0, e / jnp.where(total > 0, total, 1.0), 0.0)
    P = jnp.sum(p, axis=2)                                     # [C, Hkv, J]
    # compressed key j overlaps block b
    overlap = (st * j[None, :] < bs * (b[:, None] + 1)) & \
        (st * j[None, :] + ks > bs * b[:, None])                 # [NB, J]
    score = jnp.max(jnp.where(
        jnp.asarray(overlap)[None, None] & visible[:, None, None, :],
        P[:, :, None, :], -jnp.inf), axis=-1)                 # [C, Hkv, NB]
    own = t // bs
    valid = jnp.asarray(b)[None, :] <= own[:, None]              # [C, NB]
    forced = (jnp.asarray(b)[None, :] < sz["init_blocks"]) | \
        ((jnp.asarray(b)[None, :] > own[:, None] - sz["window"] // bs)
         & valid)
    score = jnp.where(forced[:, None, :], jnp.inf, score)
    return jnp.where(valid[:, None, :], score, -jnp.inf), valid


def _block_sets(q, c, t, sz):
    """-> chosen [C, Hkv, NB] bool: a block's rank is how many come
    before it (a larger score, or the same score at a lower index), and
    the first ``topk`` by rank are the query's."""
    score, valid = block_scores(q, c, t, sz)
    b = np.arange(sz["n_blocks"])
    ahead = (score[..., None, :] > score[..., :, None]) | \
        ((score[..., None, :] == score[..., :, None])
         & jnp.asarray(b[None, :] < b[:, None]))
    return valid[:, None, :] & (jnp.sum(ahead, axis=-1) < sz["topk"])


def _sparse_attention(h, w, sz, contexts):
    """One sequence: h [S, d] -> [S, H x D] (ahead of the gate and Wo)."""
    S = h.shape[0]
    H, Hkv, D = sz["heads"], sz["kv_heads"], sz["head_dim"]
    R, bs, st, ks = H // Hkv, sz["block"], sz["stride"], sz["kernel"]
    q = common.rms_norm((h @ w["wq"]).reshape(S, Hkv, R, D), w["q_norm"],
                    sz["eps"])
    k = common.rms_norm((h @ w["wk"]).reshape(S, Hkv, D), w["k_norm"],
                    sz["eps"])
    v = (h @ w["wv"]).reshape(S, Hkv, D)
    # compressed keys of whole windows: the mean of ``kernel`` normed keys
    J = max((S - ks) // st + 1, 0)
    c = jnp.mean(k[st * np.arange(J)[:, None] + np.arange(ks)[None, :]],
                 axis=1) if J else jnp.zeros((0, Hkv, D))       # [J, Hkv, D]
    sz = dict(sz, n_blocks=-(-S // bs))
    pad = (-S) % QUERIES
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    ctx = jnp.pad(contexts, (0, pad), mode="edge")
    kpos = jnp.arange(S)

    def step(_, inp):
        q_i, t, context = inp
        chosen = _block_sets(q_i, c, t, sz)                   # [C, Hkv, NB]
        keys = jnp.repeat(chosen, bs, axis=-1)[..., :S]
        keys = jnp.where((context < sz["dense_len"])[:, None, None],
                         True, keys) & (kpos[None, :] <= t[:, None]
                                        )[:, None, :]          # [C, Hkv, S]
        s = jnp.einsum("chrd,shd->chrs", q_i, k) / np.sqrt(D)
        s = jnp.where(keys[:, :, None, :], s, -jnp.inf)
        return None, jnp.einsum("chrs,shd->chrd",
                                jax.nn.softmax(s, axis=-1), v)

    n = (S + pad) // QUERIES
    _, out = jax.lax.scan(step, None, (
        qp.reshape(n, QUERIES, Hkv, R, D),
        jnp.arange(S + pad).reshape(n, QUERIES),
        ctx.reshape(n, QUERIES)))
    return out.reshape(S + pad, H * D)[:S]


def _minicpm4(h, w, sz, contexts):
    out = jax.vmap(lambda row: _sparse_attention(row, w, sz, contexts))(h)
    return (out * jax.nn.sigmoid(h @ w["wg_attn"])) @ w["wo"]


def _lightning(h, w, sz):
    B, S, _ = h.shape
    H, D = sz["lin_heads"], sz["lin_head_dim"]
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    q, k = (common.rotate_half_rope(
        common.rms_norm((h @ w[name]).reshape(B, S, H, D), w[norm], sz["eps"]),
        positions, sz["theta"], D)
        for name, norm in (("wq", "q_norm"), ("wk", "k_norm")))
    v = (h @ w["wv"]).reshape(B, S, H, D)
    a = jnp.exp(-(2.0 ** (-8.0 * (jnp.arange(H) + 1.0) / H)))

    def token(state, row):          # state: [B, H, D, D]
        q_t, k_t, v_t = row
        state = a[None, :, None, None] * state \
            + k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("bhd,bhde->bhe", q_t / np.sqrt(D), state)

    _, o = jax.lax.scan(token, jnp.zeros((B, H, D, D), jnp.float32),
                        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * D)
    return (common.rms_norm(o, w["norm"], sz["eps"])
            * jax.nn.sigmoid(h @ w["wg"])) @ w["wo"]


def _block(x, w, sz, contexts):
    h = common.rms_norm(x, w["attn_norm"], sz["eps"])
    mixed = _lightning(h, w["lin"], sz) if "lin" in w \
        else _minicpm4(h, w, sz, contexts)
    x = x + sz["residual"] * mixed
    h = common.rms_norm(x, w["mlp_norm"], sz["eps"])
    mlp = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return x + sz["residual"] * mlp


def published_depth(cfg):
    """The depth the residual scale is reckoned from: the published
    number of layers, whatever this file holds of them."""
    return int(cfg.get("published", {}).get("num_hidden_layers",
                                            cfg["num_hidden_layers"]))


def _sizes(cfg):
    sparse = cfg["sparse_config"]
    assert cfg["qk_norm"] and not cfg["attn_use_rope"] and \
        cfg["lightning_use_rope"] and cfg["use_output_gate"] and \
        cfg["use_output_norm"] and cfg["attn_use_output_gate"] and \
        cfg["lightning_nkv"] == cfg["lightning_nh"] and \
        cfg["lightning_scale"] == "1/sqrt(d)" and \
        not cfg["attention_bias"] and not cfg["tie_word_embeddings"]
    return dict(
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], eps=cfg["rms_norm_eps"],
        lin_heads=cfg["lightning_nh"], lin_head_dim=cfg["lightning_head_dim"],
        theta=float(cfg["rope_theta"]),
        residual=float(cfg["scale_depth"]) / np.sqrt(published_depth(cfg)),
        block=sparse["block_size"], topk=sparse["topk"],
        kernel=sparse["kernel_size"], stride=sparse["kernel_stride"],
        init_blocks=sparse["init_blocks"], window=sparse["window_size"],
        dense_len=sparse["dense_len"])


@functools.lru_cache(maxsize=None)
def _layer_step(sizes):
    """One layer of a stack, cut out and cast to float32 only while it
    runs (``common.run_stack``'s rule); the layer's place in its stack is
    traced, so a kind of layer and a prompt length compile once."""
    sizes = dict(sizes)

    @jax.jit
    def step(x, stacked, p, contexts):
        return _block(x, common.f32(jax.tree_util.tree_map(
            lambda leaf: leaf[p], stacked)), sizes, contexts)

    return step


@common.highest
def logits(params, ids, cfg, last=None, contexts=None):
    """ids: [B, S] -> float32 logits [B, S, vocab], or of the ``last``
    positions only.  One float32 layer at a time, in the model's order:
    the leading layers, then period by period, each layer cut out of its
    position's stack."""
    S = ids.shape[1]
    contexts = jnp.full((S,), S, jnp.int32) if contexts is None \
        else jnp.asarray(contexts, jnp.int32)
    step = _layer_step(tuple(sorted(_sizes(cfg).items())))
    x = params["tok_embed"][ids].astype(jnp.float32) * float(cfg["scale_emb"])
    for w in params["layers"]:      # layers ahead of the periods, if any
        x = step(x, jax.tree_util.tree_map(lambda leaf: leaf[None], w), 0,
                 contexts)
    periods = params.get("periods") or []
    n = jax.tree_util.tree_leaves(periods)[0].shape[0] if periods else 0
    for p in range(n):
        for stacked in periods:
            x = jax.block_until_ready(step(x, stacked, p, contexts))
    x = common.rms_norm(x, params["final_norm"].astype(jnp.float32),
                        cfg["rms_norm_eps"])
    if last is not None:
        x = x[:, -last:]
    return (x / (cfg["hidden_size"] / cfg["dim_model_base"])) \
        @ params["lm_head"].astype(jnp.float32)
