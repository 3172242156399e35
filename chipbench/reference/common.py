"""Shared plain arithmetic of the float32 references: nothing here (or in
the family files) imports the program.  Everything runs in float32 with
``jax.default_matmul_precision("highest")`` (a TPU multiplies float32 in
lower precision otherwise), no kernels, no cache, no batching tricks.

The weights arrive as the program's own parameter tree (a dict of named
arrays, layers stacked on a leading axis): names are an interface, the
mathematics is written here from the published model descriptions.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * weight


def rotate_half_rope(x, positions, theta, rotary_dim):
    """Rotary embedding in the ``rotate_half`` layout both families
    publish; x: [B, S, H, D], only the first ``rotary_dim`` dims turn."""
    half = rotary_dim // 2
    inv_freq = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    angles = positions[..., None].astype(jnp.float32) * \
        jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def causal_attention(q, k, v):
    """q, k, v: [B, S, H, D] (same head count) -> [B, S, H*D]."""
    B, S, H, D = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    mask = np.tril(np.ones((S, S), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(B, S, H * D)


def next_token_loss(logits, ids):
    """Mean cross-entropy of position t's logits against token t+1."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def run_stack(block, x, layers, *args):
    """Apply ``block`` layer after layer; each layer's weights are cast to
    float32 only while it runs, so the reference holds one float32 layer
    beside whatever type the weights are stored in."""
    n_layers = jax.tree_util.tree_leaves(layers)[0].shape[0]
    step = jax.jit(lambda x, layers, i, *a: block(
        x, f32(jax.tree_util.tree_map(lambda w: w[i], layers)), *a))
    for i in range(n_layers):
        x = step(x, layers, i, *args)
    return x


def highest(fn):
    """Run ``fn`` with float32 matrix products at full precision."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped
