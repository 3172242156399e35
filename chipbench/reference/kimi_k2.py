"""Plain float32 reference of Kimi-K2-Instruct (``model_type: kimi_k2``),
written from the published descriptions: the DeepSeek-V3 block with
Kimi-K2's numbers.  Latent attention (MLA) over the WHOLE causal context
(there is no indexer: nothing is selected), YaRN-scaled rotary
frequencies, a leading dense layer, then ``noaux_tc`` sigmoid-routed
expert layers with one ungated shared expert.  Nothing here imports the
program; the weights arrive as its parameter tree (names are the
interface).  The attention below is this file's own copy, not
``glm_moe_dsa``'s: the two are compared with the program independently.

The block (pre-norm, RMSNorm): ``h = x + Attn(norm(x))``, ``y = h +
FFN(norm(h))``; FFN is SwiGLU, dense in the first ``first_k_dense_replace``
layers, else the expert layer; final RMSNorm, untied head.

Attention, per layer (H heads, no bias), token t at position p:
  c_q = rms(x W_qa);  [q_nope | q_rope] = c_q W_qb  per head
  [c_kv | k_rope] = x W_kva;  c_kv = rms(c_kv);  k_rope is ONE head
  [k_nope | v] = c_kv W_kvb  per head;  k = [k_nope | k_rope]
  s[t, u, h] = scale * q[t, h] . k[u, h]  for EVERY u <= t;  softmax over u
Rotary embedding on q_rope and k_rope: pairs (2i, 2i+1) turn by ``p *
inv_freq_i``.  YaRN (``rope_scaling``: factor f over L0 original
positions): ``freq_i = theta**(-2i / dr)``; ``d(b) = dr ln(L0 / (2 pi b))
/ (2 ln theta)``; ``low = floor(d(beta_fast))``, ``high =
ceil(d(beta_slow))`` (if equal, high += 0.001), clipped to [0, dr/2 - 1];
``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i = freq_i
(1 - ramp_i) + freq_i / f ramp_i``; cos and sin times ``m(f, mscale) /
m(f, mscale_all_dim)`` with ``m(f, a) = 0.1 a ln f + 1``; ``scale = (dn +
dr)**-0.5 m(f, mscale_all_dim)**2``.

Expert layer: g = sigmoid(x W_r) over ALL published experts; the
``num_experts_per_tok`` largest of g + b chosen (``n_group`` =
``topk_group`` = 1: no group step; ties to the lower index); weights g_e /
(sum of the chosen g + 1e-20) * routed_scaling_factor; out = sum_e w_e
SwiGLU_e(x) + SwiGLU_shared(x).  THE CHIP'S SHARE: the expert leaves hold
experts 0 .. n_routed_experts-1 of the ``published`` count; the terms of
the other experts belong to other chips and are left out, here as in the
program, and the partial sum goes on to the next layer.

Departures from the published model:
* rotary pairs are (2i, 2i+1) as stored; the HF code de-interleaves q and
  k alike into [evens | odds] first and turns halves, which leaves every
  dot product the same;
* the correction range is clipped to the 32 frequency indices, [0, dr/2 -
  1]; the HF code clips to [0, dr - 1], which differs only where a bound
  passes 31 (19 and 20 here);
* the selection bias ``e_score_correction_bias`` is seeded N(0, 0.02), so
  that it moves choices; the published one is trained;
* depth, experts held and vocabulary are one chip's share (``reduced``).

``decided``: a row is undecided where, at some expert layer, its own
token's margin in g + b between a chosen and an unchosen expert, at least
one of them held here, is under that layer's margin, ``MARGIN *
sqrt(blocks before it)``.  The program rounds to bf16; every block adds
its share of that to the residual stream, and a router logit is a sum over
the stream's 7168 elements, so the score gap of two experts wanders by an
amount that grows as the root of the depth.  ``MARGIN`` 0.003 is the rule
``glm_moe_dsa`` read on thirty seeds of the chip for the same router
(sigmoid scores, bf16 stream, five expert layers); this model selects no
keys, so its stream wanders less, and it holds 12 of 384 experts where
that one holds 16 of 256, so fewer rows lie near a held expert's
boundary: PERF.md section 6 keeps the chip's readings of this cell
(rows left and the largest error of a decided row, by seed).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common

MARGIN = 0.003          # times sqrt(blocks before the expert layer)
BLOCK_Q = 512           # queries a block of attention
HEAD_GROUP = 8          # heads decompressed at a time
FFN_BLOCK = 2048        # columns of a feed-forward product at a time
ROWS = 512              # an expert's rows are padded to multiples of this


def _f32(x):
    return x.astype(jnp.float32)


def magnitude(factor, mscale):
    """``m(f, a) = 0.1 a ln f + 1`` (1 without a stretch)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim, theta, scaling):
    """The ``dim // 2`` rotary frequencies under ``rope_scaling``
    (float64)."""
    half = dim // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dim)
    if not scaling:
        return freq
    factor = float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])

    def index_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = min(max(math.floor(index_of(scaling["beta_fast"])), 0), half - 1)
    high = min(max(math.ceil(index_of(scaling["beta_slow"])), 0), half - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / factor * ramp


def softmax_scale(cfg):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg.get("rope_scaling")
    if scaling and scaling.get("mscale_all_dim"):
        scale *= magnitude(float(scaling["factor"]),
                           float(scaling["mscale_all_dim"])) ** 2
    return scale


def _rope_pairs(x, positions, cfg):
    """x: [B, S, ..., D] turned on pairs (2i, 2i+1); positions [B, S]."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[..., None] * \
        jnp.asarray(cfg.inv_freq, jnp.float32)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang) * cfg.rotary_magnitude, \
        jnp.sin(ang) * cfg.rotary_magnitude
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


def _blocks(x, block):
    """[B, S, ...] -> [S / block, B, block, ...] (S a multiple)."""
    B, S = x.shape[:2]
    return jnp.moveaxis(x.reshape((B, S // block, block) + x.shape[2:]), 1, 0)


def _unblocks(x):
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])


@functools.partial(jax.jit, static_argnames=("cfg",))
def _latents(x, w, positions, cfg):
    """What all heads share: the query latent, the normed key-value
    latent and the one rotary key."""
    eps, R = cfg.eps, cfg.kv_rank
    h = common.rms_norm(x, _f32(w["attn_norm"]), eps)
    c_q = common.rms_norm(h @ _f32(w["wq_a"]), _f32(w["q_a_norm"]), eps)
    kv = h @ _f32(w["wkv_a"])
    c_kv = common.rms_norm(kv[..., :R], _f32(w["kv_a_norm"]), eps)
    return c_q, c_kv, _rope_pairs(kv[..., R:], positions, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _heads(c_q, c_kv, k_rope, w, group, positions, cfg):
    """Group ``group`` (traced: one program for all) of ``HEAD_GROUP``
    heads: decompress, attend over every causal key (a block of queries at
    a time, so that a 16k prompt's scores fit beside the engine), and this
    group's part of the output projection."""
    B, S, _ = c_q.shape
    dn, dr = cfg.nope, cfg.rope
    G = min(HEAD_GROUP, cfg.heads)

    def cut(weight, width, axis):
        return jax.lax.dynamic_slice_in_dim(
            weight, group * G * width, G * width, axis)

    wq_b = cut(w["wq_b"], dn + dr, 1)
    wkv_b = cut(w["wkv_b"], dn + cfg.v_dim, 1)
    wo = cut(w["wo"], cfg.v_dim, 0)
    q = (c_q @ _f32(wq_b)).reshape(B, S, G, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], _rope_pairs(q[..., dn:], positions, cfg)], -1)
    kv = (c_kv @ _f32(wkv_b)).reshape(B, S, G, -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[:, :, None], (B, S, G, dr))],
        -1)
    v = kv[..., dn:]

    def one(block):
        qb, pb = block
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * cfg.scale
        causal = positions[:, None, :] <= pb[:, :, None]
        p = jax.nn.softmax(jnp.where(causal[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = _unblocks(jax.lax.map(one, (_blocks(q, BLOCK_Q),
                                      _blocks(positions, BLOCK_Q))))
    return out.reshape(B, S, -1) @ _f32(wo)


def _glu_block(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


@jax.jit
def _glu(x, w_gate, w_up, w_down):
    """SwiGLU, the inner width a block at a time (it is a sum over it): a
    loop, so that one block of the weights is float32 at a time."""
    width = w_up.shape[-1]
    block = min(FFN_BLOCK, width)
    assert width % block == 0, (width, block)

    def one(i, out):
        lo = i * block
        return out + _glu_block(
            x, jax.lax.dynamic_slice_in_dim(w_gate, lo, block, 1),
            jax.lax.dynamic_slice_in_dim(w_up, lo, block, 1),
            jax.lax.dynamic_slice_in_dim(w_down, lo, block, 0))

    return jax.lax.fori_loop(0, width // block, one, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, weight, eps):
    return common.rms_norm(x, _f32(weight), eps)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _route(h, wg, bias, cfg, margin):
    """(chosen [N, k], weights [N, k], undecided [N]) over ALL experts;
    ``margin`` is this layer's (a traced scalar: one program for all)."""
    g = jax.nn.sigmoid(h @ _f32(wg))
    biased = g + _f32(bias)
    top, chosen = jax.lax.top_k(biased, cfg.per_token)
    picked = jnp.take_along_axis(g, chosen, -1)
    weights = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) \
        if cfg.norm_topk else picked
    # own-token margin: a held chosen expert too close above the best
    # unchosen one, or a held unchosen one too close under the weakest
    # chosen one
    is_chosen = jnp.zeros(g.shape, bool).at[
        jnp.arange(g.shape[0])[:, None], chosen].set(True)
    held = jnp.arange(g.shape[1]) < cfg.held
    kth = top[:, -1:]
    best_out = jnp.max(jnp.where(is_chosen, -jnp.inf, biased), -1,
                       keepdims=True)
    near = (is_chosen & held & (biased - best_out < margin)) | \
        (~is_chosen & held & (kth - biased < margin))
    return chosen, weights * cfg.routed_scale, jnp.any(near, -1)


@functools.partial(jax.jit, donate_argnums=0)
def _expert_rows(out, h, rows, weights, moe, e):
    """Add expert ``e``'s term (traced: one program for all) for its
    ``rows`` of ``h``, weighted, into ``out`` in place."""
    return out.at[rows].add(_glu_block(
        h[rows], moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e])
        * weights[:, None])


def layer_margin(depth):
    """The own-token margin of an expert layer with ``depth`` blocks
    before it (module docstring)."""
    return MARGIN * max(1, depth) ** 0.5


def expert_layer(h, moe, cfg, depth, first=0):
    """The terms of the experts held here (``first .. first + held - 1``
    of the router's ids, the leaves' leading axis) and the shared expert;
    h: [N, d] -> (out [N, d], undecided [N])."""
    chosen, weights, undecided = _route(h, moe["wg"], moe["router_bias"],
                                        cfg, layer_margin(depth))
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    out = jnp.zeros_like(h)
    experts = {k: moe[k] for k in ("w_gate", "w_up", "w_down")}
    for e in range(cfg.held):       # ONE expert in float32 at a time
        tokens, slot = np.nonzero(chosen == first + e)
        pad = (-len(tokens)) % ROWS or (ROWS if not len(tokens) else 0)
        rows = np.concatenate([tokens, np.zeros(pad, tokens.dtype)])
        w = np.concatenate([weights[tokens, slot],
                            np.zeros(pad, weights.dtype)])
        out = _expert_rows(out, h, jnp.asarray(rows), jnp.asarray(w),
                           experts, e)
    if "shared" in moe:
        sh = moe["shared"]
        out = out + _glu(h, sh["w_gate"], sh["w_up"], sh["w_down"])
    return out, undecided


@jax.jit
def _head(x, table):
    return x @ _f32(table)


class Sizes:
    """The configuration's numbers the jitted parts read (hashable)."""

    def __init__(self, cfg):
        scaling = cfg.get("rope_scaling") or None
        self.eps = cfg["rms_norm_eps"]
        self.kv_rank = cfg["kv_lora_rank"]
        self.nope, self.rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        self.heads, self.v_dim = cfg["num_attention_heads"], cfg["v_head_dim"]
        self.inv_freq = tuple(yarn_inv_freq(
            self.rope, float(cfg["rope_theta"]), scaling))
        self.rotary_magnitude = 1.0 if not scaling else \
            magnitude(float(scaling["factor"]),
                      float(scaling.get("mscale", 1))) / \
            magnitude(float(scaling["factor"]),
                      float(scaling.get("mscale_all_dim", 0)))
        self.scale = softmax_scale(cfg)
        self.per_token = cfg["num_experts_per_tok"]
        self.held = cfg["n_routed_experts"]
        self.norm_topk = bool(cfg["norm_topk_prob"])
        self.routed_scale = float(cfg["routed_scaling_factor"])
        self._key = tuple(sorted(self.__dict__.items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return self._key == other._key


@common.highest
def logits(params, ids, cfg, last=None):
    """ids: [B, S] -> (float32 logits [B, last, vocab], decided [B, last]):
    the rows of the ``last`` positions (all without it) and which of them
    this file's own routing leaves decided."""
    sizes = Sizes(cfg)
    B, S = ids.shape
    last = S if last is None else last
    # keys after a query change nothing for it: pad to whole blocks
    ids = jnp.pad(ids, ((0, 0), (0, (-S) % BLOCK_Q)))
    positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    x = _f32(params["tok_embed"][ids])
    undecided = np.zeros((B * ids.shape[1],), bool)
    for depth, w in enumerate(params["layers"]):
        c_q, c_kv, k_rope = _latents(x, {
            k: w[k] for k in ("attn_norm", "wq_a", "q_a_norm", "wkv_a",
                              "kv_a_norm")}, positions, sizes)
        for group in range(-(-sizes.heads // HEAD_GROUP)):
            # one group in flight at a time: a program's temporaries and
            # results are allocated when it is ENQUEUED, and eight queued
            # groups of a 14k prompt hold 6 GB beside the engine (the
            # process's peak read 16.1-16.3 of 16.9 GB without the wait)
            x = jax.block_until_ready(x + _heads(
                c_q, c_kv, k_rope,
                {k: w[k] for k in ("wq_b", "wkv_b", "wo")}, group,
                positions, sizes))
        h = _normed(x, w["mlp_norm"], sizes.eps)
        if "moe" in w:
            out, undecided_here = expert_layer(
                h.reshape(-1, h.shape[-1]), w["moe"], sizes, depth)
            undecided |= np.asarray(undecided_here)
            x = x + out.reshape(x.shape)
        else:
            x = x + _glu(h, w["w_gate"], w["w_up"], w["w_down"])
    x = _normed(x[:, S - last:S], params["final_norm"], sizes.eps)
    decided = ~undecided.reshape(B, -1)[:, S - last:S]
    return _head(x, params["lm_head"]), decided
