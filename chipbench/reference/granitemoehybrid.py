"""Plain float32 reference of Granite 4.0-H (``model_type:
granitemoehybrid``), written from the public description of
``modeling_granitemoehybrid.py`` and of Mamba-2 as the machine's notes hold
them (no network here; what could not be checked against the files
themselves is under ``assumed`` in the configuration's file).  Nothing here
imports the program; the weights arrive as its parameter tree (names are
the interface): ``params["layers"]`` the leading layers as a list (none for
the published pattern), ``params["periods"][j]`` the layers of position
``j`` in the period of ``layer_types``, stacked on a leading axis.

The stream, RMSNorm (eps ``rms_norm_eps``) everywhere:
  x0 = E[ids] * embedding_multiplier
  x = x + residual_multiplier * mixer(norm(x; attn_norm))
  x = x + residual_multiplier * mlp(norm(x; mlp_norm))
  mlp(h) = (silu(h W_gate) * (h W_up)) W_down, width
  ``shared_intermediate_size`` (``num_local_experts`` 0: no routed part)
  logits = norm(x; final_norm) E^T / logits_scaling   (tied table)

``attention`` layers: q = h Wq [T, H, D], k = h Wk, v = h Wv [T, Hkv, D],
no bias, NO positional embedding of any kind (``position_embedding_type:
nope``), causal, scores q.k * attention_multiplier (not 1 / sqrt(D)),
softmax in float32, H / Hkv query heads a key/value head, then Wo.

``mamba`` layers (Mamba-2), u = norm(x) [T, d], d_in = mamba_n_heads *
mamba_d_head, N = mamba_d_state, G = mamba_n_groups, K = mamba_d_conv:
  1. [z | xBC | dt] = u W_in, widths d_in | d_in + 2 G N | heads, no bias
  2. xBC = silu(conv(xBC)): causal depthwise, row t sees rows t-K+1 .. t
     (zeros ahead of position 0), with bias; ``conv_w[k]`` multiplies row
     t - (K - 1) + k.  Split xBC -> x [T, heads, P] | B [T, G, N] | C
  3. dt = softplus(dt + dt_bias) [T, heads]; a_t = exp(dt_t * A), A =
     -exp(A_log), a scalar a head
  4. a head's state S [P, N]: S_t = a_t S_{t-1} + dt_t x_t (outer) B_t,
     y_t = S_t C_t + D x_t  (B and C of the head's group)
  5. y = norm_over_d_in(y * silu(z); norm) (the gate first, then the norm,
     one group), out = y W_out
The recurrence runs here as a ``lax.scan`` over single tokens: no chunks,
no cache, no slots.

Departures from the published description: the SEEDING of ``A_log``,
``dt_bias`` and ``D`` is the program's (Mamba-2's own initialiser, the
configuration's ``seeded_weights``), which changes no equation; and
nothing else.  Not ``ROUTED``: a plain array comes back.
"""

import functools

import jax
import jax.numpy as jnp

from chipbench.reference import common


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _attention(h, w, sizes):
    B, S, _ = h.shape
    H, Hkv = sizes["heads"], sizes["kv_heads"]
    D = h.shape[-1] // H
    q = (h @ w["wq"]).reshape(B, S, Hkv, H // Hkv, D)
    k = (h @ w["wk"]).reshape(B, S, Hkv, D)
    v = (h @ w["wv"]).reshape(B, S, Hkv, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * sizes["attn_scale"]
    seen = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return out.reshape(B, S, H * D) @ w["wo"]


def _mamba(u, w, sizes):
    B, T, _ = u.shape
    H, P = sizes["ssm_heads"], sizes["ssm_head_dim"]
    N, G, K = sizes["ssm_state"], sizes["ssm_groups"], sizes["ssm_conv"]
    d_in = H * P
    z, xbc, dt = jnp.split(u @ w["w_in"], (d_in, 2 * d_in + 2 * G * N),
                           axis=-1)
    # the causal depthwise convolution as K shifted adds
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = w["conv_b"] + sum(padded[:, k:k + T] * w["conv_w"][k]
                             for k in range(K))
    x, Bm, Cm = jnp.split(jax.nn.silu(conv), (d_in, d_in + G * N), axis=-1)
    x = x.reshape(B, T, G, H // G, P)
    Bm, Cm = Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N)
    dt = jax.nn.softplus(dt + w["dt_bias"]).reshape(B, T, G, H // G)
    A = -jnp.exp(w["A_log"]).reshape(G, H // G)
    D = w["D"].reshape(G, H // G, 1)

    def token(S, row):      # S: [B, G, H / G, P, N]
        x_t, B_t, C_t, dt_t = row
        S = jnp.exp(dt_t * A)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, None, :]
        y_t = jnp.sum(S * C_t[:, :, None, None, :], axis=-1) + D * x_t
        return S, y_t

    _, y = jax.lax.scan(
        token, jnp.zeros((B, G, H // G, P, N), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (x, Bm, Cm, dt)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, T, d_in) * jax.nn.silu(z)
    return common.rms_norm(y, w["norm"], sizes["eps"]) @ w["w_out"]


def _block(x, w, sizes):
    h = common.rms_norm(x, w["attn_norm"], sizes["eps"])
    mixed = _mamba(h, w["ssm"], sizes) if "ssm" in w \
        else _attention(h, w, sizes)
    x = x + sizes["residual"] * mixed
    h = common.rms_norm(x, w["mlp_norm"], sizes["eps"])
    mlp = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return x + sizes["residual"] * mlp


def _sizes(cfg):
    return dict(
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        attn_scale=float(cfg["attention_multiplier"]),
        residual=float(cfg["residual_multiplier"]), eps=cfg["rms_norm_eps"],
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], ssm_groups=cfg["mamba_n_groups"],
        ssm_conv=cfg["mamba_d_conv"])


@functools.lru_cache(maxsize=None)
def _layer_step(sizes):
    """One layer of a stack, cut out and cast to float32 only while it
    runs, as ``common.run_stack`` does for the dense families (which makes
    its program anew each call: fine once a prompt, not forty times); the
    layer's place in its stack is traced, so a kind of layer and a prompt
    length compile once."""
    sizes = dict(sizes)

    @jax.jit
    def step(x, stacked, p):
        return _block(x, _f32(jax.tree_util.tree_map(
            lambda leaf: leaf[p], stacked)), sizes)

    return step


@common.highest
def logits(params, ids, cfg, last=None):
    """ids: [B, S] -> float32 logits [B, S, vocab], or of the ``last``
    positions only.  One float32 layer at a time, in the model's order:
    the leading layers, then period by period, each layer cut out of its
    position's stack."""
    assert cfg["num_local_experts"] == 0 and \
        cfg["position_embedding_type"] == "nope"
    step = _layer_step(tuple(sorted(_sizes(cfg).items())))
    x = params["tok_embed"][ids].astype(jnp.float32) \
        * float(cfg["embedding_multiplier"])
    for w in params["layers"]:      # layers ahead of the periods, if any
        x = step(x, jax.tree_util.tree_map(lambda leaf: leaf[None], w), 0)
    periods = params.get("periods") or []
    n = jax.tree_util.tree_leaves(periods)[0].shape[0] if periods else 0
    for p in range(n):
        for stacked in periods:
            x = step(x, stacked, p)
    x = common.rms_norm(x, params["final_norm"].astype(jnp.float32),
                        cfg["rms_norm_eps"])
    if last is not None:
        x = x[:, -last:]
    return x @ params["tok_embed"].T.astype(jnp.float32) \
        / float(cfg["logits_scaling"])
