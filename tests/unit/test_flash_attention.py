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


# ----------------------------------------------------------------------
# The kinds of tile a call can hold (PR 49): wholly inside the causal
# triangle and the window, across the diagonal, across the window's far
# edge, with rows that have no key in it; and what the kernels no longer do
# (guard such rows, scale the score tile, upcast their operands)
# ----------------------------------------------------------------------
# name, S, H, Hkv, D, block_q, block_k, window, alibi, causal, dtype
EDGE_CASES = [
    ("window_under_a_tile", 256, 2, 2, 32, 64, 64, 16, False, True, "f32"),
    ("window_off_the_tiles", 256, 2, 2, 32, 64, 64, 100, False, True,
     "f32"),
    ("window_of_two_tiles", 256, 2, 2, 32, 64, 64, 128, False, True, "f32"),
    ("window_past_the_sequence", 128, 2, 2, 32, 32, 32, 500, False, True,
     "f32"),
    # a q tile of 128 rows over key steps of 32 under a window of 8: the
    # last rows' keys lie three steps past the first visited one
    ("rows_wholly_masked_in_a_tile", 256, 2, 2, 32, 128, 32, 8, False,
     True, "f32"),
    ("group_8", 128, 8, 1, 32, 32, 64, 48, False, True, "f32"),
    ("q_tile_over_k_step", 256, 2, 2, 32, 128, 32, None, False, True,
     "f32"),
    ("k_step_over_q_tile", 256, 2, 2, 32, 32, 128, None, False, True,
     "f32"),
    ("alibi_with_a_window", 256, 4, 2, 32, 64, 32, 80, True, True, "f32"),
    ("alibi_alone", 128, 4, 4, 32, 32, 64, None, True, True, "f32"),
    ("not_causal", 256, 2, 2, 32, 64, 128, None, False, False, "f32"),
    ("not_causal_with_a_window", 256, 2, 2, 32, 64, 32, 70, False, False,
     "f32"),
    ("picked_tiles", 1024, 1, 1, 32, None, None, 300, False, True, "f32"),
    ("picked_q_tile_alone", 1024, 1, 1, 32, None, 128, None, False, True,
     "f32"),
    ("bf16_inputs", 256, 4, 2, 64, 64, 128, 96, False, True, "bf16"),
]


@pytest.mark.parametrize(
    "S,H,Hkv,D,bq,bk,window,alibi,causal,dtype",
    [c[1:] for c in EDGE_CASES], ids=[c[0] for c in EDGE_CASES])
def test_flash_edges_forward_and_gradients(S, H, Hkv, D, bq, bk, window,
                                           alibi, causal, dtype):
    """Forward and all three gradients against ``reference_attention`` over
    the kinds of tile the split tells apart."""
    from deepspeed_tpu.models.transformer import alibi_slopes
    from deepspeed_tpu.ops.attention import alibi_window_bias
    rng = np.random.default_rng(49)
    dt = jnp.float32 if dtype == "f32" else jnp.bfloat16
    B = 1
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), dt)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dt)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dt)
    w = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    slopes = alibi_slopes(H) if alibi else None
    bias = alibi_window_bias(S, S, slopes=slopes, window=window) \
        if alibi or window is not None else None

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                            interpret=True, alibi_slopes=slopes,
                            window=window)
        return jnp.sum(o.astype(jnp.float32) * w), o

    def loss_ref(q, k, v):
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        o = reference_attention(*f32, causal=causal, bias=bias)
        return jnp.sum(o * w), o

    (_, out), grads = jax.value_and_grad(
        loss_flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), wants = jax.value_and_grad(
        loss_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    tol = dict(rtol=2e-4, atol=2e-4) if dtype == "f32" \
        else dict(rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), **tol)
    gtol = dict(rtol=1e-3, atol=1e-3) if dtype == "f32" \
        else dict(rtol=1e-1, atol=1e-1)
    for name, a, b in zip("qkv", grads, wants):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   err_msg=f"d{name}", **gtol)


@pytest.mark.parametrize("S,bq,bk,causal,window", [
    (256, 64, 64, True, None), (256, 64, 64, True, 16),
    (256, 64, 64, True, 100), (256, 128, 32, True, 8),
    (256, 32, 128, True, 48), (256, 64, 32, False, 70),
    (256, 64, 128, False, None), (2048, 512, 512, True, None),
    (8192, 512, 512, True, 1024), (256, 64, 64, True, 0),
])
def test_the_plan_counts_the_tiles_that_hold_a_pair(S, bq, bk, causal,
                                                    window):
    """``flash_plan``'s bounds against the mask itself: every tile with an
    attended pair is visited and no other, and the dk/dv kernel's bounds
    (``_q_bounds``) name the same tiles."""
    from deepspeed_tpu.ops.pallas.flash_attention import (_k_bounds,
                                                          _q_bounds,
                                                          flash_plan)
    qpos, kpos = np.arange(S)[:, None], np.arange(S)[None, :]
    allowed = np.ones((S, S), bool)
    if causal:
        allowed &= qpos >= kpos
    if window:
        allowed &= qpos - kpos < window
    some = allowed.reshape(S // bq, bq, S // bk, bk).any(axis=(1, 3))
    w = np.int64(window) if window else None
    kb = np.arange(S // bk)[None, :]
    lo, hi = (x[:, None] for x in _k_bounds(
        np.arange(S // bq), bq, bk, S, causal, w, xp=np))
    np.testing.assert_array_equal((kb >= lo) & (kb < hi), some)
    qb = np.arange(S // bq)[:, None]
    lo, hi = (x[None, :] for x in _q_bounds(
        np.arange(S // bk), bq, bk, S, causal, w, xp=np))
    np.testing.assert_array_equal((qb >= lo) & (qb < hi), some)
    assert flash_plan(S, bq, bk, causal, window) == {
        "tiles_visited": int(some.sum()),
        "tiles_masked": int(some.sum()) if causal or window else 0,
        "pairs_visited": int(some.sum()) * bq * bk,
        "pairs_needed": int(allowed.sum())}


# the three training cells' calls: sequence, head_dim, group, window
CELL_CALLS = [
    ("train-pythia-1.4b-s2048", 2048, 128, 1, None),
    ("train-pythia-6.9b-fsdp4", 2048, 128, 1, None),
    ("train-mellum2-12b-ep4-s8192_full", 8192, 128, 8, None),
    ("train-mellum2-12b-ep4-s8192_window", 8192, 128, 8, 1024),
]


@pytest.mark.parametrize("S,D,group,window", [c[1:] for c in CELL_CALLS],
                         ids=[c[0] for c in CELL_CALLS])
def test_picked_tiles_of_the_cells(S, D, group, window):
    """The tiles divide the sequence, the picker's own VMEM count is under
    the limit the call asks for, and the pairs a causal call of 2,048
    positions needs are four fifths of those it computes.  (Under the
    window of 1,024 they are two thirds: a 256-row tile would make it four
    fifths and made each kernel 31-54 % slower on the chip, PERF.md §6,
    PR 49.)"""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        DEFAULT_SCOPED_VMEM, VMEM_CEILING, _vmem_bytes, _vmem_params,
        flash_plan, pick_flash_tiles)
    bq, bk = pick_flash_tiles(S, D, group, 2)
    assert (bq, bk) == (512, 512)
    need = _vmem_bytes(S, D, bq, bk, 2, group)
    asked = _vmem_params(S, D, bq, bk, 2, group)
    limit = asked["compiler_params"].vmem_limit_bytes if asked \
        else DEFAULT_SCOPED_VMEM
    assert need <= limit <= VMEM_CEILING
    plan = flash_plan(S, bq, bk, True, window)
    share = plan["pairs_needed"] / plan["pairs_visited"]
    assert share >= (2 / 3 if window else 0.8 if S == 2048 else 0.94)


@pytest.mark.parametrize("S,ok", [(100, True), (512, True), (640, True),
                                  (1000, False), (2048, True),
                                  (32768, True)])
def test_the_picker_refuses_what_no_tile_divides(S, ok):
    from deepspeed_tpu.ops.pallas.flash_attention import (flash_tiles,
                                                          pick_flash_tiles)
    assert flash_tiles(S, 4, 2) is ok
    assert flash_tiles(S, 4, 3) is False
    if ok:
        bq, bk = pick_flash_tiles(S, 64)
        assert S % bq == 0 and S % bk == 0 and bq == bk <= 512
    else:
        with pytest.raises(ValueError, match="cannot tile"):
            pick_flash_tiles(S, 64)
