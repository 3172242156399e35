"""Plain float32 reference of Mellum2-12B-A2.5B (``model_type: mellum``),
written from the catalog row's ``config`` (model-configs guide; no network
here).  What the config does not say is under ``assumed`` in the
configuration's file.  Nothing here imports the program; the weights arrive
as its parameter tree (names are the interface): ``params["layers"]`` the
leading layers as a list (none for this family), ``params["periods"][j]``
the layers of position ``j`` in the period of ``layer_types``, stacked on a
leading axis.

The block, RMSNorm (eps ``rms_norm_eps``) everywhere, no bias anywhere:
  h = norm(x; attn_norm);  q = h Wq [T, H, D], k = h Wk, v = h Wv [T, Hkv, D]
  q = norm_D(q; q_norm), k = norm_D(k; k_norm): one weight of D a layer,
  applied to every head, ahead of the rotary
  rotary on all D dims, ``rotate_half`` halves, by the layer's kind
  (``rope_parameters``): ``sliding_attention`` theta unscaled, query i
  attends keys j with i - sliding_window < j <= i; ``full_attention``
  YaRN (the slow frequencies divided by ``factor``, a linear ramp between
  the dimensions that turn ``beta_fast`` and ``beta_slow`` times over the
  original positions, cos and sin times ``attention_factor``), keys j <= i
  scores q.k / sqrt(D), softmax in float32, H / Hkv query heads a kv head
  x = x + attention Wo
  h = norm(x; mlp_norm);  p = softmax(h Wr) over ALL published experts,
  chosen = the ``num_experts_per_tok`` largest (ties to the lower index),
  w = p[chosen] / sum p[chosen] (``norm_topk_prob``),
  x = x + sum_e w_e Wdown_e (silu(Wgate_e h) * Wup_e h)
Embedding ``E[ids]``, final RMSNorm, untied head.

THE CHIP'S SHARE: the expert leaves hold experts 0 .. num_experts-1 of the
``published`` count; the other experts' terms belong to other chips and are
left out, here as in the program, and the partial sum goes on.

:func:`logits` is what the benchmark compares with (blocked, so that 8,192
positions fit beside the seeded weights: a kv head and a block of queries
at a time, one expert's rows at a time).  :func:`loss` is the same
equations with nothing blocked and nothing picked on the host, so that
``jax.grad`` goes through it: the next-token loss plus ``aux_coef`` times
the balance statistic ``E sum_e f_e P_e`` of every expert layer (f: share
of the (token, choice) pairs on expert e, P: mean router probability of e,
over all published experts).  The CPU tests hold the two to each other.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common

BLOCK_Q = 512           # queries a block of attention
ROWS = 512              # an expert's rows are padded to multiples of this


def _f32(x):
    return x.astype(jnp.float32)


class _Sizes:
    """The configuration's numbers the jitted parts read (hashable)."""

    def __init__(self, cfg):
        self.eps = cfg["rms_norm_eps"]
        self.heads = cfg["num_attention_heads"]
        self.kv_heads = cfg["num_key_value_heads"]
        self.head_dim = cfg["head_dim"]
        self.window = cfg["sliding_window"]
        self.per_token = cfg["num_experts_per_tok"]
        self.held = cfg["num_experts"]
        self.norm_topk = bool(cfg["norm_topk_prob"])
        self.rotary = tuple(sorted(
            (kind, rotary_of(cfg, kind))
            for kind in cfg["rope_parameters"]))
        self._key = tuple(sorted(self.__dict__.items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return self._key == other._key


def yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """The ``dim // 2`` inverse frequencies of a YaRN-stretched rotary
    embedding (float64): ``theta**(-2i/dim)`` where dimension ``i`` turns
    more than ``beta_fast`` times over ``original`` positions, that over
    ``factor`` where it turns fewer than ``beta_slow`` times, a linear
    ramp over the indices between."""
    half = dim // 2
    plain = theta ** (-np.arange(half, dtype=np.float64) * 2 / dim)

    def index_turning(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(index_turning(beta_fast)), 0)
    high = min(math.ceil(index_turning(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    stretched = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return plain * (1.0 - stretched) + plain / factor * stretched


def rotary_of(cfg, kind):
    """(inverse frequencies, what cos and sin are multiplied by) of layer
    kind ``kind`` (``sliding_attention`` | ``full_attention``)."""
    spec, dim = cfg["rope_parameters"][kind], cfg["head_dim"]
    theta = float(spec["rope_theta"])
    if spec["rope_type"] == "default":
        freq = theta ** (-np.arange(dim // 2, dtype=np.float64) * 2 / dim)
        return tuple(freq), 1.0
    assert spec["rope_type"] == "yarn", spec
    freq = yarn_inv_freq(dim, theta, spec["factor"],
                         spec["original_max_position_embeddings"],
                         spec["beta_fast"], spec["beta_slow"])
    magnitude = spec.get("attention_factor")
    if magnitude is None:
        magnitude = 0.1 * math.log(spec["factor"]) + 1.0
    return tuple(freq), float(magnitude)


def _turn(x, positions, inv_freq, magnitude):
    """``rotate_half`` rotary on all of the last axis; x: [B, S, H, D]."""
    half = x.shape[-1] // 2
    angles = positions[..., None].astype(jnp.float32) * \
        jnp.asarray(inv_freq, jnp.float32)
    cos = (jnp.cos(angles) * magnitude)[:, :, None]
    sin = (jnp.sin(angles) * magnitude)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _qkv(x, w, positions, sizes, kind):
    B, S, _ = x.shape
    H, Hkv, D = sizes.heads, sizes.kv_heads, sizes.head_dim
    h = common.rms_norm(x, _f32(w["attn_norm"]), sizes.eps)
    q = common.rms_norm((h @ _f32(w["wq"])).reshape(B, S, H, D),
                        _f32(w["q_norm"]), sizes.eps)
    k = common.rms_norm((h @ _f32(w["wk"])).reshape(B, S, Hkv, D),
                        _f32(w["k_norm"]), sizes.eps)
    v = (h @ _f32(w["wv"])).reshape(B, S, Hkv, D)
    rotary = dict(sizes.rotary)[kind]
    return (_turn(q, positions, *rotary).reshape(B, S, Hkv, H // Hkv, D),
            _turn(k, positions, *rotary), v)


def _seen(q_positions, k_positions, sizes, kind):
    """[B, Q, K]: which keys a query attends."""
    seen = k_positions[:, None, :] <= q_positions[:, :, None]
    if kind == "sliding_attention":
        seen &= k_positions[:, None, :] > q_positions[:, :, None] \
            - sizes.window
    return seen


def _attend(q, k, v, seen):
    """q: [B, Q, G, D] of one kv head's group, k, v: [B, K, D]."""
    s = jnp.einsum("bqgd,bkd->bgqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), -1)
    return jnp.einsum("bgqk,bkd->bqgd", p, v)


def _blocks(x, block):
    """[B, S, ...] -> [S / block, B, block, ...] (S a multiple)."""
    B, S = x.shape[:2]
    return jnp.moveaxis(x.reshape((B, S // block, block) + x.shape[2:]), 1, 0)


def _unblocks(x):
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])


@functools.partial(jax.jit, static_argnames=("sizes", "kind"))
def _attention(x, w, positions, sizes, kind):
    """x + the attention sub-block, a kv head's group and a block of
    queries at a time."""
    B, S, _ = x.shape
    q, k, v = _qkv(x, w, positions, sizes, kind)

    def one(block):
        qb, pb = block      # [B, BLOCK_Q, Hkv, G, D], [B, BLOCK_Q]
        seen = _seen(pb, positions, sizes, kind)
        return jnp.stack([_attend(qb[:, :, head], k[:, :, head],
                                  v[:, :, head], seen)
                          for head in range(sizes.kv_heads)], axis=2)

    attn = _unblocks(jax.lax.map(one, (_blocks(q, BLOCK_Q),
                                       _blocks(positions, BLOCK_Q))))
    return x + attn.reshape(B, S, -1) @ _f32(w["wo"])


def _route(h, wg, sizes):
    """(chosen [N, k], weights [N, k], probabilities [N, E]) over ALL
    experts."""
    p = jax.nn.softmax(h @ _f32(wg), axis=-1)
    _, chosen = jax.lax.top_k(p, sizes.per_token)
    weights = jnp.take_along_axis(p, chosen, -1)
    if sizes.norm_topk:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return chosen, weights, p


def _glu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


@functools.partial(jax.jit, static_argnames=("sizes",))
def _routed(x, w, sizes):
    h = common.rms_norm(x, _f32(w["mlp_norm"]), sizes.eps)
    h = h.reshape(-1, h.shape[-1])
    return (h,) + _route(h, w["moe"]["wg"], sizes)[:2]


@jax.jit
def _expert_rows(out, h, rows, weights, back, moe, e):
    """Add expert ``e``'s term (traced: one program for all) for its
    ``rows`` of ``h``, weighted; ``back[t]`` is token ``t``'s row among
    them, or a padding row (weight 0): brought back by a gather, as
    ``reference/afmoe.py`` does and for its reason."""
    term = _glu(h[rows], moe["w_gate"][e], moe["w_up"][e],
                moe["w_down"][e]) * weights[:, None]
    return out + term[back]


def _expert_layer(x, w, sizes):
    """x + the held experts' terms, one expert's rows at a time, the rows
    picked on the host."""
    h, chosen, weights = _routed(x, w, sizes)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    out = jnp.zeros_like(h)
    experts = {k: w["moe"][k] for k in ("w_gate", "w_up", "w_down")}
    for e in range(sizes.held):     # ONE expert in float32 at a time
        tokens, slot = np.nonzero(chosen == e)
        pad = ROWS - len(tokens) % ROWS     # at least one padding row
        rows = np.concatenate([tokens, np.zeros(pad, tokens.dtype)])
        wt = np.concatenate([weights[tokens, slot],
                             np.zeros(pad, weights.dtype)])
        back = np.full(h.shape[0], len(tokens), np.int32)
        back[tokens] = np.arange(len(tokens), dtype=np.int32)
        out = _expert_rows(out, h, jnp.asarray(rows.astype(np.int32)),
                           jnp.asarray(wt), jnp.asarray(back), experts, e)
    return x + out.reshape(x.shape)


@jax.jit
def _cut(stacked, p):
    """Layer ``p`` (traced: one program a position in the period) of a
    stacked layer."""
    return jax.tree_util.tree_map(lambda leaf: leaf[p], stacked)


def layer_weights(params):
    """Every layer's weights in order (the stacked periods cut one layer
    at a time, never copied whole)."""
    for w in params["layers"]:
        yield w
    periods = params.get("periods") or []
    n = jax.tree_util.tree_leaves(periods)[0].shape[0] if periods else 0
    for p in range(n):
        for at in periods:
            yield _cut(at, p)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, table, eps):
    return common.rms_norm(x, _f32(norm), eps) @ _f32(table)


def _check(cfg):
    assert cfg["hidden_act"] == "silu" and not cfg["attention_bias"] \
        and not cfg["tie_word_embeddings"]
    assert all(kind == "sparse" for kind in cfg["mlp_layer_types"])
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"]


@common.highest
def logits(params, ids, cfg, last=None):
    """ids: [B, S] -> float32 logits [B, S, vocab], or of the ``last``
    positions only."""
    _check(cfg)
    sizes = _Sizes(cfg)
    B, S = ids.shape
    # keys after a query change nothing for it: pad to whole blocks
    ids = jnp.pad(ids, ((0, 0), (0, (-S) % BLOCK_Q)))
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    x = _f32(params["tok_embed"][ids])
    for kind, w in zip(cfg["layer_types"], layer_weights(params)):
        x = _attention(x, {k: v for k, v in w.items() if k != "moe"},
                       positions, sizes, kind)
        x = jax.block_until_ready(_expert_layer(x, w, sizes))
    x = x[:, :S] if last is None else x[:, S - last:S]
    return _head(x, params["final_norm"], params["lm_head"], sizes.eps)


# ----------------------------------------------------------------------
# the same equations, differentiable (small sizes: the CPU tests)
# ----------------------------------------------------------------------
def expert_layer_terms(h, moe, sizes, first=0, held=None):
    """(sum of the terms of experts ``first .. first + held - 1`` [N, d],
    balance statistic of the layer's routing): every held expert on every
    row, its chosen rows kept by their weight.  ``moe``'s expert leaves
    hold those experts along their leading axis."""
    held = sizes.held if held is None else held
    chosen, weights, p = _route(h, moe["wg"], sizes)
    out = jnp.zeros_like(h)
    for e in range(held):
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        out = out + mine[:, None] * _glu(h, moe["w_gate"][e], moe["w_up"][e],
                                         moe["w_down"][e])
    E = p.shape[-1]
    share = jnp.mean((chosen[..., None] == jnp.arange(E)
                      ).astype(jnp.float32), axis=(0, 1))
    return out, E * jnp.sum(share * jnp.mean(p, axis=0))


@common.highest
def loss(params, ids, cfg, aux_coef=0.0):
    """Mean next-token loss of ``ids`` [B, S] plus ``aux_coef`` times the
    sum over the layers of the balance statistic; plain code throughout,
    for ``jax.grad``."""
    _check(cfg)
    sizes = _Sizes(cfg)
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    x = _f32(params["tok_embed"])[ids]
    balance = 0.0
    layers = list(params["layers"])
    periods = params.get("periods") or []
    n = jax.tree_util.tree_leaves(periods)[0].shape[0] if periods else 0
    for p in range(n):
        layers += [jax.tree_util.tree_map(lambda leaf: leaf[p], at)
                   for at in periods]
    for kind, w in zip(cfg["layer_types"], layers):
        q, k, v = _qkv(x, w, positions, sizes, kind)
        seen = _seen(positions, positions, sizes, kind)
        attn = jnp.stack([_attend(q[:, :, head], k[:, :, head],
                                  v[:, :, head], seen)
                          for head in range(sizes.kv_heads)], axis=2)
        x = x + attn.reshape(x.shape[:2] + (-1,)) @ _f32(w["wo"])
        h = common.rms_norm(x, _f32(w["mlp_norm"]), sizes.eps)
        out, stat = expert_layer_terms(h.reshape(-1, h.shape[-1]), w["moe"],
                                       sizes)
        x = x + out.reshape(x.shape)
        balance = balance + stat
    out = common.rms_norm(x, _f32(params["final_norm"]), sizes.eps) \
        @ _f32(params["lm_head"])
    return common.next_token_loss(out, ids) + aux_coef * balance
