"""Pallas flash attention (TPU).

Parity role: the fused attention inside the reference's training transformer
kernel (``csrc/transformer/ds_transformer_cuda.cpp``) and its
softmax/dropout/transform sub-kernels — rebuilt as a tiled online-softmax
kernel that streams K/V blocks through VMEM into the MXU and never
materialises the [S, S] score matrix.

Forward: Pallas kernel, grid (batch·heads, q_blocks); K/V for the head stay
in VMEM (fine to S≈8k at D=128); inner ``fori_loop`` over K blocks carries
(acc, row-max, row-sum) registers.  Causal blocks beyond the diagonal are
skipped via the loop bound, the diagonal block is masked with iota.

Backward: custom VJP using the saved log-sum-exp, as two Pallas kernels —
``_bwd_dq_kernel`` (grid over q blocks; streams K/V) and
``_bwd_dkv_kernel`` (grid over k blocks; streams Q/dO) — O(S) memory,
recomputing the probabilities tile-by-tile instead of materialising the
[B,H,S,S] score matrix.  ``_flash_bwd`` (jnp einsums) is the test oracle
only: non-tiling shapes never reach the custom VJP, because
``flash_attention()`` refuses them.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG = -1e30


def _tile_positions(q_base, k_base, block_q, block_k):
    qpos = q_base + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = k_base + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return qpos, kpos


def _mask_bias(s, qpos, kpos, causal, slope, window):
    """Shared score-tile transform: ALiBi bias (``slope * kpos`` — the
    row-constant part cancels in softmax, matching the model's
    ``_attn_bias``) then causal / sliding-window masking.  ``slope`` and
    ``window`` are traced scalars (0 disables)."""
    if slope is not None:
        s = s + slope * kpos.astype(jnp.float32)
    allowed = None
    if causal:
        allowed = qpos >= kpos
    if window is not None:
        in_win = (qpos - kpos < window) | (window <= 0)
        allowed = in_win if allowed is None else (allowed & in_win)
    if allowed is not None:
        s = jnp.where(allowed, s, _NEG)
    return s


def _k_range(qi, block_q, block_k, seq_len, causal, window):
    """[lo, hi) K-block range visible to q-block ``qi``; with a window the
    far-past blocks are skipped (true sliding-window FLOPs)."""
    num_k_blocks = seq_len // block_k
    if causal:
        hi = jax.lax.div((qi + 1) * block_q + block_k - 1, block_k)
        hi = jnp.minimum(hi, num_k_blocks)
    else:
        hi = num_k_blocks
    lo = 0
    if window is not None:
        lo_w = jax.lax.div(qi * block_q - (window - 1), block_k)
        lo = jnp.where(window > 0, jnp.maximum(0, lo_w), 0)
    return lo, hi


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k, seq_len):
    _fwd_impl(q_ref, k_ref, v_ref, None, None, o_ref, lse_ref, scale=scale,
              causal=causal, block_q=block_q, block_k=block_k,
              seq_len=seq_len)


def _fwd_kernel_biased(q_ref, k_ref, v_ref, slope_ref, window_ref, o_ref,
                       lse_ref, *, scale, causal, block_q, block_k,
                       seq_len, use_slope=True, use_window=True):
    _fwd_impl(q_ref, k_ref, v_ref, slope_ref if use_slope else None,
              window_ref if use_window else None, o_ref, lse_ref,
              scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              seq_len=seq_len)


def _fwd_impl(q_ref, k_ref, v_ref, slope_ref, window_ref, o_ref, lse_ref,
              *, scale, causal, block_q, block_k, seq_len):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # [BLK_Q, D]
    d = q.shape[-1]

    bh = pl.program_id(0)
    slope = slope_ref[bh, 0] if slope_ref is not None else None
    window = window_ref[bh, 0] if window_ref is not None else None
    lo, hi = _k_range(qi, block_q, block_k, seq_len, causal, window)

    def body(kb, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = q @ k.T                                    # [BLK_Q, BLK_K]
        if causal or slope is not None or window is not None:
            qpos, kpos = _tile_positions(qi * block_q, kb * block_k,
                                         block_q, block_k)
            s = _mask_bias(s, qpos, kpos, causal, slope, window)
        bm = jnp.max(s, axis=-1, keepdims=True)        # [BLK_Q, 1]
        new_m = jnp.maximum(m, bm)
        p = jnp.exp(s - new_m)
        p = jnp.where(new_m <= _NEG / 2, 0.0, p)
        corr = jnp.exp(m - new_m)
        corr = jnp.where(m <= _NEG / 2, 0.0, corr)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + p @ v
        return acc, new_m, l

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))

    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)


def _scalar_specs():
    """Block specs for the per-(batch·head) bias scalars.

    The scalars ride as FULL ``[B*H, 1]`` arrays — a ``(1, 1)`` VMEM block of
    a ``[B*H, 1]`` array violates Mosaic's last-two-dims tiling rule (must
    tile (8, 128) or equal the array dims).  They live in SMEM (the scalar
    memory, where dynamic scalar reads are native); kernels index them with
    ``pl.program_id(0)``.
    """
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return [smem, smem]


def _bias_inputs(alibi_slopes, window, B, H):
    """Per-(batch·head) ALiBi slope and window scalars as [B*H, 1] arrays
    (None, None when the no-bias fast path applies)."""
    if alibi_slopes is None and window is None:
        return None, None
    slopes = (jnp.zeros((H,), jnp.float32) if alibi_slopes is None
              else jnp.asarray(alibi_slopes, jnp.float32))
    slopes_bh = jnp.tile(slopes, B).reshape(B * H, 1)
    w = jnp.asarray(0 if window is None else window).astype(jnp.int32)
    w_bh = jnp.broadcast_to(w, (B * H,)).reshape(B * H, 1)
    return slopes_bh, w_bh


# Mosaic's default scoped VMEM, which the kernels' double-buffered blocks
# fit up to about 4k positions; a call whose blocks take more asks for it
DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


def _vmem_params(block_bytes):
    """``pallas_call`` keywords for a call whose blocks (one buffer of
    each) take ``block_bytes``: nothing while two buffers of each fit the
    default with room for the kernel's own temporaries (every program of
    a shorter sequence stays as it was), else a limit of its own."""
    need = 2 * block_bytes + 4 * 2 ** 20
    if need <= DEFAULT_SCOPED_VMEM:
        return {}
    return dict(compiler_params=pltpu.CompilerParams(
        vmem_limit_bytes=int(min(need + need // 4, 100 * 2 ** 20))))


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret=False,
               alibi_slopes=None, window=None):
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qr = jnp.swapaxes(q, 1, 2).reshape(B * H, S, D)
    kr = jnp.swapaxes(k, 1, 2).reshape(B * Hkv, S, D)
    vr = jnp.swapaxes(v, 1, 2).reshape(B * Hkv, S, D)

    block_q = min(block_q, S)
    block_k = min(block_k, S)
    grid = (B * H, S // block_q)

    slopes_bh, w_bh = _bias_inputs(alibi_slopes, window, B, H)
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, S, D), lambda bh, qi, g=group: (bh // g, 0, 0)),
        pl.BlockSpec((1, S, D), lambda bh, qi, g=group: (bh // g, 0, 0)),
    ]
    args = [qr, kr, vr]
    if slopes_bh is None:
        kernel = functools.partial(
            _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, seq_len=S)
    else:
        kernel = functools.partial(
            _fwd_kernel_biased, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, seq_len=S,
            use_slope=alibi_slopes is not None,
            use_window=window is not None)
        in_specs += _scalar_specs()
        args += [slopes_bh, w_bh]

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",     # the instruction's name in a trace
        **_vmem_params(2 * S * D * k.dtype.itemsize
                       + 2 * block_q * D * q.dtype.itemsize),
    )(*args)

    out = jnp.swapaxes(out.reshape(B, H, S, D), 1, 2)
    return out, lse.reshape(B, H, S)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale, causal, block_q, block_k, seq_len):
    _bwd_dq_impl(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, None,
                 None, dq_ref, scale=scale, causal=causal, block_q=block_q,
                 block_k=block_k, seq_len=seq_len)


def _bwd_dq_kernel_biased(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          slope_ref, window_ref, dq_ref, *, scale, causal,
                          block_q, block_k, seq_len, use_slope=True,
                          use_window=True):
    _bwd_dq_impl(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 slope_ref if use_slope else None,
                 window_ref if use_window else None, dq_ref, scale=scale,
                 causal=causal, block_q=block_q, block_k=block_k,
                 seq_len=seq_len)


def _bwd_dq_impl(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, slope_ref,
                 window_ref, dq_ref, *, scale, causal, block_q, block_k,
                 seq_len):
    """dQ for one (batch·head, q-block): stream K/V blocks, recompute P
    from the saved LSE, accumulate dq = Σ_kb dS @ K."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                   # [BQ, D]
    do = do_ref[0].astype(jnp.float32)                 # [BQ, D]
    lse = lse_ref[0].reshape(block_q, 1)               # [BQ, 1, 1]→[BQ, 1]
    delta = delta_ref[0].reshape(block_q, 1)
    d = q.shape[-1]

    bh = pl.program_id(0)
    slope = slope_ref[bh, 0] if slope_ref is not None else None
    window = window_ref[bh, 0] if window_ref is not None else None
    lo, hi = _k_range(qi, block_q, block_k, seq_len, causal, window)

    def body(kb, dq):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or slope is not None or window is not None:
            qpos, kpos = _tile_positions(qi * block_q, kb * block_k,
                                         block_q, block_k)
            s = _mask_bias(s, qpos, kpos, causal, slope, window)
        p = jnp.exp(s - lse)
        p = jnp.where(s <= _NEG / 2, 0.0, p)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(lo, hi, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, block_k,
                    seq_len):
    _bwd_dkv_impl(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, None,
                  None, dk_ref, dv_ref, scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k, seq_len=seq_len)


def _bwd_dkv_kernel_biased(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           slope_ref, window_ref, dk_ref, dv_ref, *, scale,
                           causal, block_q, block_k, seq_len,
                           use_slope=True, use_window=True):
    _bwd_dkv_impl(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  slope_ref if use_slope else None,
                  window_ref if use_window else None, dk_ref, dv_ref,
                  scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, seq_len=seq_len)


def _bwd_dkv_impl(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  slope_ref, window_ref, dk_ref, dv_ref, *, scale, causal,
                  block_q, block_k, seq_len):
    """dK/dV for one (batch·head, k-block): stream Q/dO blocks.
    dv = Σ_qb Pᵀ @ dO;  dk = Σ_qb dSᵀ @ Q."""
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)                   # [BK, D]
    v = v_ref[0].astype(jnp.float32)
    d = k.shape[-1]

    bh = pl.program_id(0)
    slope = slope_ref[bh, 0] if slope_ref is not None else None
    window = window_ref[bh, 0] if window_ref is not None else None
    num_q_blocks = seq_len // block_q
    lo = (ki * block_k) // block_q if causal else 0
    hi = num_q_blocks
    if window is not None:
        # last q block that can see this k block: qpos < kpos + window
        hi_w = jax.lax.div((ki + 1) * block_k + window - 2, block_q) + 1
        hi = jnp.where(window > 0,
                       jnp.minimum(num_q_blocks, hi_w), num_q_blocks)

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), :].reshape(block_q, 1)
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), :].reshape(
            block_q, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or slope is not None or window is not None:
            qpos, kpos = _tile_positions(qb * block_q, ki * block_k,
                                         block_q, block_k)
            s = _mask_bias(s, qpos, kpos, causal, slope, window)
        p = jnp.exp(s - lse)
        p = jnp.where(s <= _NEG / 2, 0.0, p)
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, hi, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_pallas(scale, causal, res, g, block_q, block_k,
                      interpret=False, alibi_slopes=None, window=None):
    """O(S)-memory flash backward: recompute P per tile from the saved LSE.
    Returns (dq, dk, dv) with GQA group reduction."""
    q, k, v, out, lse = res
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv

    qr = jnp.swapaxes(q, 1, 2).reshape(B * H, S, D)
    kr = jnp.swapaxes(k, 1, 2).reshape(B * Hkv, S, D)
    vr = jnp.swapaxes(v, 1, 2).reshape(B * Hkv, S, D)
    gr = jnp.swapaxes(g, 1, 2).reshape(B * H, S, D)
    of = jnp.swapaxes(out, 1, 2).reshape(B * H, S, D)
    # trailing singleton dim: mosaic requires the last two block dims to
    # tile (8, 128) or equal the array dims — (block, 1) blocks of an
    # [..., 1] array are legal where (1, block) blocks of a 2-D one aren't
    lser = lse.reshape(B * H, S, 1)
    # delta_i = Σ_d dO_i · O_i  (the softmax-jacobian row term)
    delta = jnp.sum(gr.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1, keepdims=True)

    slopes_bh, w_bh = _bias_inputs(alibi_slopes, window, B, H)
    scalar_specs = ([] if slopes_bh is None
                    else _scalar_specs())
    scalar_args = [] if slopes_bh is None else [slopes_bh, w_bh]

    kv_spec = pl.BlockSpec((1, S, D), lambda bh, i, g=group: (bh // g, 0, 0))
    dq_kernel = _bwd_dq_kernel if slopes_bh is None else functools.partial(
        _bwd_dq_kernel_biased, use_slope=alibi_slopes is not None,
        use_window=window is not None)
    dq = pl.pallas_call(
        functools.partial(dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=S),
        grid=(B * H, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
            kv_spec,
            kv_spec,
            pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0)),
        ] + scalar_specs,
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        interpret=interpret,
        name="flash_attention_dq",
        **_vmem_params(2 * S * D * k.dtype.itemsize
                       + 3 * block_q * D * q.dtype.itemsize),
    )(qr, kr, vr, gr, lser, delta, *scalar_args)

    full_spec = pl.BlockSpec((1, S, D), lambda bh, ki: (bh, 0, 0))
    dkv_kernel = (_bwd_dkv_kernel if slopes_bh is None
                  else functools.partial(
                      _bwd_dkv_kernel_biased,
                      use_slope=alibi_slopes is not None,
                      use_window=window is not None))
    dk, dv = pl.pallas_call(
        functools.partial(dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=S),
        grid=(B * H, S // block_k),
        in_specs=[
            full_spec,                                     # q
            pl.BlockSpec((1, block_k, D),
                         lambda bh, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, ki, g=group: (bh // g, ki, 0)),
            full_spec,                                     # dO
            pl.BlockSpec((1, S, 1), lambda bh, ki: (bh, 0, 0)),  # lse
            pl.BlockSpec((1, S, 1), lambda bh, ki: (bh, 0, 0)),  # delta
        ] + scalar_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B * H, S, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_dkv",
        # whole-sequence q and dO, and lse and delta as [S, 1] float32
        # columns, which VMEM pads to 128 lanes
        **_vmem_params(2 * S * D * q.dtype.itemsize + 2 * S * 128 * 4
                       + 2 * block_k * D * (k.dtype.itemsize + 4)),
    )(qr, kr, vr, gr, lser, delta, *scalar_args)

    dq = jnp.swapaxes(dq.reshape(B, H, S, D), 1, 2)
    dk = dk.reshape(B, Hkv, group, S, D).sum(axis=2)     # GQA group reduce
    dv = dv.reshape(B, Hkv, group, S, D).sum(axis=2)
    dk = jnp.swapaxes(dk, 1, 2).astype(k.dtype)
    dv = jnp.swapaxes(dv, 1, 2).astype(v.dtype)
    return dq, dk, dv


def _flash_bwd(scale, causal, res, g):
    """Flash backward from saved LSE (jnp einsums; fp32)."""
    q, k, v, out, lse = res
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k_full = jnp.repeat(k, rep, axis=2)
        v_full = jnp.repeat(v, rep, axis=2)
    else:
        k_full, v_full = k, v

    qf = q.astype(jnp.float32)
    kf = k_full.astype(jnp.float32)
    vf = v_full.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    of = out.astype(jnp.float32)

    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    if causal:
        qpos = jnp.arange(S)[:, None]
        kpos = jnp.arange(S)[None, :]
        s = jnp.where((qpos >= kpos)[None, None], s, _NEG)
    p = jnp.exp(s - lse[..., None])                    # [B,H,S,S]

    dv = jnp.einsum("bhqk,bqhd->bkhd", p, gf)
    dp = jnp.einsum("bqhd,bkhd->bhqk", gf, vf)
    delta = jnp.sum(gf * of, axis=-1)                  # [B,S,H]
    ds = p * (dp - jnp.swapaxes(delta, 1, 2)[..., None]) * scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, kf)
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)

    if Hkv != H:
        rep = H // Hkv
        dk = dk.reshape(B, S, Hkv, rep, D).sum(axis=3)
        dv = dv.reshape(B, S, Hkv, rep, D).sum(axis=3)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_attention(q, k, v, alibi_slopes, window, scale, causal, block_q,
                     block_k, interpret=False):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                        alibi_slopes=alibi_slopes, window=window)
    return out


def _flash_attention_fwd(q, k, v, alibi_slopes, window, scale, causal,
                         block_q, block_k, interpret=False):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          interpret, alibi_slopes=alibi_slopes,
                          window=window)
    return out, (q, k, v, alibi_slopes, window, out, lse)


def _flash_attention_bwd(scale, causal, block_q, block_k, interpret,
                         res, g):
    # the forward only runs the kernel on tiling shapes, so the tiled
    # backward applies whenever this VJP is reached
    q, k, v, alibi_slopes, window, out, lse = res
    dq, dk, dv = _flash_bwd_pallas(scale, causal, (q, k, v, out, lse), g,
                                   block_q, block_k, interpret,
                                   alibi_slopes=alibi_slopes, window=window)
    dslopes = (None if alibi_slopes is None
               else jnp.zeros_like(jnp.asarray(alibi_slopes, jnp.float32)))
    dwindow = (None if window is None
               else jnp.zeros_like(jnp.asarray(window, jnp.float32)))
    return dq, dk, dv, dslopes, dwindow


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def flash_tiles(seq_len, n_heads, n_kv_heads, block_q=DEFAULT_BLOCK_Q,
                block_k=DEFAULT_BLOCK_K):
    """Whether the kernel's grid covers this shape exactly."""
    return (seq_len % min(block_q, seq_len) == 0
            and seq_len % min(block_k, seq_len) == 0
            and n_heads % n_kv_heads == 0)


def flash_attention(q, k, v, causal=True, softmax_scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False, alibi_slopes=None, window=None):
    """q: [B, S, H, D]; k/v: [B, S, Hkv, D].  Raises ``ValueError`` when the
    shape doesn't tile (:func:`flash_tiles`) — choosing another
    implementation is ``ops.attention.attention``'s decision, not the
    kernel's.  ``interpret=True`` runs the kernel in the Pallas interpreter
    (CPU CI).

    ``alibi_slopes`` ([H] fp32, treated as CONSTANT — stop_gradient; ALiBi
    slopes are a deterministic function of the head count, never learned)
    adds the Bloom-style per-head bias ``slope * kpos`` in-kernel; ``window`` (traced int scalar, 0/None =
    unlimited) applies a sliding-window mask AND skips K blocks wholly
    outside the window, so GPT-Neo/Mistral local attention gets its
    asymptotics (role of the reference's local-attention inference kernels,
    ``csrc/transformer/inference``)."""
    B, S, H, D = q.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    if not flash_tiles(S, H, k.shape[2], block_q, block_k):
        raise ValueError(
            f"flash attention cannot tile q{q.shape} k{k.shape} with "
            f"block_q={block_q} block_k={block_k}: the sequence must be a "
            "multiple of both blocks and the kv heads must divide the heads")
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    window_f = (None if window is None
                else jnp.asarray(window, jnp.float32))
    if alibi_slopes is not None:
        # slopes are a deterministic function of the head count, not a
        # learned parameter: declare them constant so the custom VJP's
        # zero cotangent is stop_gradient semantics, not a silent grad loss
        alibi_slopes = jax.lax.stop_gradient(
            jnp.asarray(alibi_slopes, jnp.float32))
    return _flash_attention(q, k, v, alibi_slopes, window_f, scale, causal,
                            block_q, block_k, interpret)
