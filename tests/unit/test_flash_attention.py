"""Flash-attention kernel vs jnp oracle — run via the Pallas interpreter on
CPU (exact fp32 math, so tolerances are tight).  On real TPU the compiled
kernel is exercised by chip_smoke.py / the model's auto dispatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import reference_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(B=2, S=512, H=4, D=64, Hkv=None, seed=0):
    rng = jax.random.key(seed)
    q = jax.random.normal(rng, (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(rng, 1),
                          (B, S, Hkv or H, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(rng, 2),
                          (B, S, Hkv or H, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_exact(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_gqa():
    q, k, v = _qkv(Hkv=2)
    out = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_gradients_match():
    q, k, v = _qkv(S=256)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=128, block_k=128,
                                interpret=True).astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v).astype(jnp.float32) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_uneven_seq():
    q, k, v = _qkv(S=100)  # smaller than a block: single full-S block
    out = flash_attention(q, k, v, interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_block_sizes():
    q, k, v = _qkv(S=512)
    ref = reference_attention(q, k, v, causal=True)
    for bq, bk in [(128, 256), (256, 128), (512, 512)]:
        out = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


# ---- Pallas backward kernels (tiled dq / dkv from saved LSE) ----------

@pytest.mark.parametrize("causal", [True, False])
def test_flash_bwd_pallas_matches_einsum_oracle(causal):
    from deepspeed_tpu.ops.pallas.flash_attention import (_flash_bwd,
                                                          _flash_bwd_pallas,
                                                          _flash_fwd)
    q, k, v = _qkv(S=256, D=32)
    g = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out, lse = _flash_fwd(q, k, v, scale, causal, 64, 64, interpret=True)
    res = (q, k, v, out, lse)
    oracle = _flash_bwd(scale, causal, res, g)
    tiled = _flash_bwd_pallas(scale, causal, res, g, 64, 64, interpret=True)
    for a, b in zip(tiled, oracle):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_bwd_pallas_gqa_group_reduce():
    from deepspeed_tpu.ops.pallas.flash_attention import (_flash_bwd,
                                                          _flash_bwd_pallas,
                                                          _flash_fwd)
    q, k, v = _qkv(S=128, H=8, Hkv=2, D=32)
    g = jax.random.normal(jax.random.key(7), q.shape, jnp.float32)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out, lse = _flash_fwd(q, k, v, scale, True, 64, 64, interpret=True)
    res = (q, k, v, out, lse)
    oracle = _flash_bwd(scale, True, res, g)
    tiled = _flash_bwd_pallas(scale, True, res, g, 64, 64, interpret=True)
    for a, b in zip(tiled, oracle):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_bwd_long_sequence_vs_autodiff():
    """S=4096 grad-vs-oracle (round-1 done-criterion): the tiled
    backward never materialises the [S, S] score matrix."""
    B, S, H, D = 1, 4096, 1, 16
    rng = jax.random.key(3)
    q = jax.random.normal(rng, (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, H, D))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, H, D))

    gf = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, causal=True, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(reference_attention(
        *a, causal=True)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        rel = float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-9)
        assert rel < 2e-3


# ----------------------------------------------------------------------
# ALiBi + sliding-window kernel variants
# ----------------------------------------------------------------------
def _bias_for(S, H=None, slopes=None, window=None):
    import jax.numpy as jnp
    bias = None
    if slopes is not None:
        bias = (jnp.asarray(slopes, jnp.float32)[None, :, None, None]
                * jnp.arange(S, dtype=jnp.float32)[None, None, None, :])
    if window is not None:
        qpos = jnp.arange(S)[:, None]
        kpos = jnp.arange(S)[None, :]
        wb = jnp.where((qpos - kpos < window) | (window <= 0), 0.0,
                       -1e30)[None, None]
        bias = wb if bias is None else bias + wb
    return bias


def test_flash_alibi_matches_reference():
    from deepspeed_tpu.models.transformer import alibi_slopes
    rng = np.random.default_rng(10)
    B, S, H, D = 2, 256, 4, 32
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
               for _ in range(3))
    slopes = alibi_slopes(H)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True, alibi_slopes=slopes)
    want = reference_attention(q, k, v, causal=True,
                               bias=_bias_for(S, slopes=slopes))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_window_matches_reference_and_skips_blocks():
    rng = np.random.default_rng(11)
    B, S, H, D = 1, 256, 2, 32
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
               for _ in range(3))
    for w in (32, 100, 0):
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True, window=w)
        want = reference_attention(q, k, v, causal=True,
                                   bias=_bias_for(S, window=w))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"window={w}")


def test_flash_alibi_window_gradients_match():
    rng = np.random.default_rng(12)
    B, S, H, D = 1, 128, 4, 16
    Hkv = 2                                       # GQA too
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    from deepspeed_tpu.models.transformer import alibi_slopes
    slopes = alibi_slopes(H)
    bias = _bias_for(S, slopes=slopes, window=48)
    kr = jnp.repeat(k, H // Hkv, axis=2)
    vr = jnp.repeat(v, H // Hkv, axis=2)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                            interpret=True, alibi_slopes=slopes, window=48)
        return jnp.sum(o ** 2)

    def loss_ref(q, kf, vf):
        o = reference_attention(q, kf, vf, causal=True, bias=bias)
        return jnp.sum(o ** 2)

    gq, gk, gv = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    rq, rkf, rvf = jax.grad(loss_ref, argnums=(0, 1, 2))(q, kr, vr)
    rk = rkf.reshape(B, S, Hkv, H // Hkv, D).sum(axis=3)
    rv = rvf.reshape(B, S, Hkv, H // Hkv, D).sum(axis=3)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(rq),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(rk),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv),
                               rtol=1e-3, atol=1e-3)


def test_flash_window_traced_per_layer():
    """window may be a traced scalar (the model scans over per-layer
    windows) — one compiled program covers all layers."""
    rng = np.random.default_rng(13)
    B, S, H, D = 1, 128, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
               for _ in range(3))

    @jax.jit
    def f(w):
        return flash_attention(q, k, v, causal=True, block_q=32,
                               block_k=32, interpret=True, window=w)

    for w in (16, 0):
        want = reference_attention(q, k, v, causal=True,
                                   bias=_bias_for(S, window=w))
        np.testing.assert_allclose(np.asarray(f(jnp.int32(w))),
                                   np.asarray(want), rtol=2e-4, atol=2e-4)
