"""Pallas flash attention (TPU).

Parity role: the fused attention inside the reference's training transformer
kernel (``csrc/transformer/ds_transformer_cuda.cpp``) and its
softmax/dropout/transform sub-kernels — rebuilt as a tiled online-softmax
kernel that streams K/V blocks through VMEM into the MXU and never
materialises the [S, S] score matrix.

Three kernels, one a ``pallas_call``: ``flash_attention_fwd`` and
``flash_attention_dq`` (grid (batch·heads, q blocks); K/V of the head stay
in VMEM, an inner loop over K blocks) and ``flash_attention_dkv`` (grid
(batch·heads, k blocks); Q/dO of the head stay in VMEM, an inner loop over
q blocks), the backward two from the saved log-sum-exp: O(S) memory, the
probabilities recomputed a tile at a time.

**What a tile does beside its products** is what the chip (v5e) said
pays (PERF.md §6, PR 49).  A call visits only the tiles that hold an
attended pair (:func:`_k_bounds`, :func:`_q_bounds`; the window is a traced
scalar, so the bounds are computed in the kernel) and masks each with one
compare against the tile's own iota difference (:func:`_mask`).  Operands
reach the MXU in the dtype they arrive in (float32 accumulation; ``p`` and
``ds`` are cast to it for the second products), the softmax scale is folded
into the ``[block, D]`` operand, never the score tile, and the running max
starts ABOVE a masked score, so a row with no key yet needs no guard:
``exp(masked - m)`` is 0.  The forward keeps its running sum a lane
(``[BQ, 128]``) and folds the lanes once a q block, not once a tile.
dk/dv work on transposed scores (``k qᵀ``), so both of their accumulating
products are plain and ``lse`` / ``delta`` ride as lane-dense rows.  Tiles
come from :func:`pick_flash_tiles` unless the caller names them.

``_flash_bwd`` (jnp einsums) is the test oracle only: non-tiling shapes
never reach the custom VJP, because ``flash_attention()`` refuses them.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
# where the running max starts: above a masked score (so a row whose
# tiles so far were all masked gives exp(_NEG - _M0) = 0, no guard) and
# below every real one (so its first real tile rescales by exp(-inf) = 0)
_M0 = _NEG / 2
_NT = (((1,), (1,)), ((), ()))      # a bᵀ: contract the minor dim of both

# Mosaic's default scoped VMEM; a call whose blocks and temporaries take
# more asks for its own limit (the v5e has 128 MiB)
DEFAULT_SCOPED_VMEM = 16 * 2 ** 20
VMEM_CEILING = 100 * 2 ** 20
# tile sides the picker takes, the first that divides the sequence
# (multiples of the 128 lanes; why none is larger: PERF.md §6, PR 49)
TILE_SIDES = (512, 256, 128)
# a sequence this short is one tile whatever its length
WHOLE_TILE = 512
# what the forward kernel made and the backward pass reads, by the names a
# ``jax.checkpoint`` policy can keep them under (``out`` [B·H, S, D] in q's
# dtype, ``lse`` [B·H, 1, S] float32, as the kernel wrote them): a policy
# that lists neither runs the forward again in the backward pass, and
# outside ``jax.checkpoint`` a name is the identity
# (``models/transformer.py TransformerConfig.checkpoint_policy``)
FLASH_RESIDUALS = ("flash_out", "flash_lse")


# ----------------------------------------------------------------------
# Which tiles a call visits.  One arithmetic for the kernels (``xp=jnp``:
# traced scalars) and the plan (``xp=np``: every block at once).
# ----------------------------------------------------------------------
def _div(a, b, xp):
    """a // b for a >= 0 (the kernels' scalar core divides truncating)."""
    return jax.lax.div(a, b) if xp is jnp else a // b


def _k_bounds(qi, block_q, block_k, seq_len, causal, window, xp=jnp):
    """K blocks ``[lo, hi)`` of q block ``qi``: every block with a key that
    one of its rows attends to, and no other — with a window the far-past
    blocks are skipped (true sliding-window FLOPs).  ``window`` <= 0:
    unlimited."""
    q0 = qi * block_q
    hi = seq_len // block_k + 0 * qi
    if causal:
        hi = xp.minimum(hi, _div(q0 + block_q + block_k - 1, block_k, xp))
    lo = 0 * qi
    if window is not None:
        # the first block with a key inside the FIRST row's window
        lo = xp.where(window > 0,
                      _div(xp.maximum(q0 - window + 1, 0), block_k, xp), lo)
    return lo, hi


def _q_bounds(ki, block_q, block_k, seq_len, causal, window, xp=jnp):
    """Q blocks ``[lo, hi)`` of k block ``ki``: :func:`_k_bounds` the other
    way round."""
    k0 = ki * block_k
    hi = seq_len // block_q + 0 * ki
    lo = _div(k0, block_q, xp) if causal else 0 * ki
    if window is not None:
        # the last q block with a row that still sees the block's last key
        hi = xp.where(window > 0, xp.minimum(
            hi, _div(k0 + block_k + window - 2, block_q, xp) + 1), hi)
    return xp.minimum(lo, hi), hi


def attended_pairs(seq_len, causal=True, window=None):
    """(query, key) pairs one head attends to."""
    q = np.arange(seq_len, dtype=np.int64)
    last = q if causal else np.full_like(q, seq_len - 1)
    first = np.maximum(q - window + 1, 0) if window and window > 0 \
        else np.zeros_like(q)
    return int((last - first + 1).sum())


def flash_plan(seq_len, block_q, block_k, causal=True, window=None):
    """What ONE head's kernel does under these tiles, from the bounds its
    loop runs on (all three kernels visit the same tiles): ``tiles_visited``
    and, of them, ``tiles_masked`` (those that build a mask: every tile of
    a causal or windowed call, see the module's text); ``pairs_visited``
    (scores computed) over ``pairs_needed`` (pairs attended to) is the tile
    ceiling of the kernel's roofline share.  ``window``: a Python int or
    None."""
    block_q, block_k = min(block_q, seq_len), min(block_k, seq_len)
    w = np.int64(window) if window else None
    lo, hi = _k_bounds(np.arange(seq_len // block_q, dtype=np.int64),
                       block_q, block_k, seq_len, causal, w, xp=np)
    visited = int((hi - lo).sum())
    return {"tiles_visited": visited,
            "tiles_masked": visited if causal or w is not None else 0,
            "pairs_visited": visited * block_q * block_k,
            "pairs_needed": attended_pairs(seq_len, causal, window)}


# ----------------------------------------------------------------------
# Tiles from what the call can see
# ----------------------------------------------------------------------
def _vmem_bytes(seq_len, head_dim, block_q, block_k, itemsize, group=1):
    """The most any of the three kernels holds in VMEM: two whole-sequence
    operands and its blocks, two buffers each, and the float32 tiles and
    accumulators of its loop body (dk/dv is the largest: four score-sized
    temporaries and float32 results for a grouped call)."""
    whole = 2 * seq_len * head_dim * itemsize
    out = 4 if group > 1 else itemsize
    blocks = max(3 * block_q * head_dim * itemsize,
                 2 * block_k * head_dim * (itemsize + out))
    # lse and delta, a row a q block, in whole tiles of 8 sublanes
    rows = 2 * max(8, seq_len // block_q) * block_q * 4
    body = 4 * block_q * block_k * 4 + 4 * max(block_q, block_k) \
        * head_dim * 4
    return 2 * (whole + blocks + rows) + body


def pick_flash_tiles(seq_len, head_dim, group=1, itemsize=2):
    """``(block_q, block_k)`` for a call, from its shapes: the largest of
    ``TILE_SIDES`` that divides ``seq_len`` and whose VMEM
    (:func:`_vmem_bytes`) the chip has.  On the v5e a 512 x 512 tile beat
    every other at 2,048 and at 8,192 positions, causal and under a window
    of 1,024 alike: a smaller q tile computes fewer pairs beyond the
    diagonal or the window but loses more than that a tile, a longer key
    step computes more of them and gains nothing a pair (PERF.md §6,
    PR 49) — so a window has no say here.  Raises ``ValueError`` for a
    sequence no tile divides."""
    if seq_len <= WHOLE_TILE:
        return seq_len, seq_len
    sides = [b for b in TILE_SIDES if seq_len % b == 0]
    if not sides:
        raise ValueError(
            f"flash attention cannot tile a sequence of {seq_len}: past "
            f"{WHOLE_TILE} positions it must be a multiple of "
            f"{TILE_SIDES[-1]}")
    side = next((b for b in sides if _vmem_bytes(
        seq_len, head_dim, b, b, itemsize, group) <= VMEM_CEILING),
        sides[-1])
    return side, side


def flash_tiles(seq_len, n_heads, n_kv_heads, block_q=None, block_k=None):
    """Whether the kernel's grid covers this shape exactly (``None``: the
    tile :func:`pick_flash_tiles` would take, which divides the sequence
    if any does)."""
    if n_heads % n_kv_heads:
        return False
    if None in (block_q, block_k) and seq_len > WHOLE_TILE \
            and seq_len % TILE_SIDES[-1]:
        return False
    return all(seq_len % min(b, seq_len) == 0
               for b in (block_q, block_k) if b)


def resolve_tiles(seq_len, head_dim, group, itemsize, block_q, block_k):
    """The caller's tiles where it names them, the picker's elsewhere."""
    if block_q is None or block_k is None:
        picked = pick_flash_tiles(seq_len, head_dim, group, itemsize)
        block_q = block_q or picked[0]
        block_k = block_k or picked[1]
    return min(block_q, seq_len), min(block_k, seq_len)


def _vmem_params(seq_len, head_dim, block_q, block_k, itemsize, group):
    """``pallas_call`` keywords: nothing while the call fits Mosaic's
    default scoped VMEM (every program of a shorter sequence stays as it
    was), else a limit of its own."""
    need = _vmem_bytes(seq_len, head_dim, block_q, block_k, itemsize, group)
    if need <= DEFAULT_SCOPED_VMEM:
        return {}
    return dict(compiler_params=pltpu.CompilerParams(
        vmem_limit_bytes=int(min(need + need // 4, VMEM_CEILING))))


# ----------------------------------------------------------------------
# The tile
# ----------------------------------------------------------------------
def _mask(s, rel, off, causal, window):
    """Mask one tile: ``rel + off`` is ``qpos - kpos`` (``rel`` the tile's
    own iota difference, made once a grid step; ``off`` a scalar)."""
    allowed = None
    if causal:
        allowed = rel >= -off
    if window is not None:
        in_win = (rel < window - off) | (window <= 0)
        allowed = in_win if allowed is None else (allowed & in_win)
    return s if allowed is None else jnp.where(allowed, s, _NEG)


def _alibi(slope, start, n, axis):
    """``slope * kpos`` of ``n`` keys from ``start`` on, along ``axis`` of a
    tile (a row for scores, a column for transposed ones); None without a
    slope."""
    if slope is None:
        return None
    shape = (1, n) if axis else (n, 1)
    kpos = start + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return slope * kpos.astype(jnp.float32)


def _scores(a, b, bias, rel, off, causal, window):
    """The masked float32 score tile ``a bᵀ`` (+ ``bias``)."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    if bias is not None:
        s = s + bias
    return _mask(s, rel, off, causal, window)


def _scalars(refs, use_slope, use_window):
    """(the operand refs, slope, window) of a kernel whose bias scalars,
    where the call has any, follow its operands."""
    if not (use_slope or use_window):
        return refs, None, None
    *refs, slope_ref, window_ref = refs
    bh = pl.program_id(0)
    return (refs, slope_ref[bh, 0] if use_slope else None,
            window_ref[bh, 0] if use_window else None)


def _scaled(x, scale):
    """x * scale in x's own dtype (rounded once, as the MXU's feed would)."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _col_to_row(x):
    """[R, 1] -> [1, R].  Through a transpose where R fills whole lane
    tiles: Mosaic's own reshape of a column costs about half a
    microsecond a grid step on the v5e."""
    rows = x.shape[0]
    if rows % 128:
        return x.reshape(1, rows)
    return jnp.broadcast_to(x, (rows, 128)).T[:1]


def _row_to_col(x):
    """[1, R] -> [R, 1]."""
    rows = x.shape[1]
    if rows % 128:
        return x.reshape(rows, 1)
    return jnp.broadcast_to(x, (128, rows)).T[:, :1]


def _lane_sums(p):
    """[R, n * 128] -> [R, 128]: the 128-wide column groups added up (no
    cross-lane work: that is left to once a q block)."""
    lanes = min(128, p.shape[-1])
    return sum(p[:, j:j + lanes] for j in range(0, p.shape[-1], lanes))


def _fwd_kernel(*refs, n_in, scale, causal, block_q, block_k, seq_len,
                use_slope, use_window):
    (q_ref, k_ref, v_ref), slope, window = _scalars(
        refs[:n_in], use_slope, use_window)
    o_ref, lse_ref = refs[n_in:]
    qi = pl.program_id(1)
    q = _scaled(q_ref[0], scale)                       # [BQ, D]
    d = q.shape[-1]
    rel = (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
           - jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))

    def tile(kb, carry):
        acc, m, l = carry
        k0 = pl.multiple_of(kb * block_k, block_k)
        k = k_ref[0, pl.ds(k0, block_k), :]
        v = v_ref[0, pl.ds(k0, block_k), :]
        s = _scores(q, k, _alibi(slope, k0, block_k, 1), rel,
                    qi * block_q - k0, causal, window)
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)                         # [BQ, BK]
        corr = jnp.exp(m - new_m)
        l = l * corr + _lane_sums(p)                   # [BQ, 128]
        acc = acc * corr + jnp.dot(p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)
        return acc, new_m, l

    lo, hi = _k_bounds(qi, block_q, block_k, seq_len, causal, window)
    acc, m, l = jax.lax.fori_loop(lo, hi, tile, (
        jnp.zeros((block_q, d), jnp.float32),
        jnp.full((block_q, 1), _M0, jnp.float32),
        jnp.zeros((block_q, min(128, block_k)), jnp.float32)))

    l_safe = jnp.maximum(jnp.sum(l, axis=-1, keepdims=True), 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = _col_to_row(m + jnp.log(l_safe))


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


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret=False,
               alibi_slopes=None, window=None, names=None):
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
    q_spec = pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0))
    kv_spec = pl.BlockSpec((1, S, D), lambda bh, qi, g=group: (bh // g, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [qr, kr, vr]
    if slopes_bh is not None:
        in_specs += _scalar_specs()
        args += [slopes_bh, w_bh]

    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, n_in=len(args), scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_len=S,
            use_slope=alibi_slopes is not None,
            use_window=window is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            q_spec,
            # a lane-dense row: an [S, 1] column is padded 128 times
            # over, in HBM as in VMEM
            pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",     # the instruction's name in a trace
        **_vmem_params(S, D, block_q, block_k, k.dtype.itemsize, group),
    )(*args)

    if names:
        # named as the kernel wrote them, AHEAD of the transpose: what is
        # kept is then laid out as the call left it and the output
        # projection reads the call's result in place; named behind the
        # transpose, XLA kept a copy with the positions minor and the 1.4b
        # step gave back 3 of the 10 ms a kept forward saves (PERF.md §6,
        # PR 52)
        out, lse = map(checkpoint_name, (out, lse), names)
    out = jnp.swapaxes(out.reshape(B, H, S, D), 1, 2)
    return out, lse.reshape(B, H, S)


def _bwd_dq_kernel(*refs, n_in, scale, causal, block_q, block_k, seq_len,
                   use_slope, use_window):
    """dQ for one (batch·head, q-block): stream K/V blocks, recompute P
    from the saved LSE, accumulate dq = Σ_kb dS @ K."""
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), slope, window = \
        _scalars(refs[:n_in], use_slope, use_window)
    dq_ref, = refs[n_in:]
    qi = pl.program_id(1)
    q = _scaled(q_ref[0], scale)                       # [BQ, D]
    do = do_ref[0]                                     # [BQ, D]
    lse = _row_to_col(lse_ref[0])                      # [BQ, 1]
    delta = _row_to_col(delta_ref[0])
    d = q.shape[-1]
    rel = (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
           - jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))

    def tile(kb, dq):
        k0 = pl.multiple_of(kb * block_k, block_k)
        k = k_ref[0, pl.ds(k0, block_k), :]
        v = v_ref[0, pl.ds(k0, block_k), :]
        s = _scores(q, k, _alibi(slope, k0, block_k, 1), rel,
                    qi * block_q - k0, causal, window)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jnp.dot(ds.astype(k.dtype), k,
                            preferred_element_type=jnp.float32)

    lo, hi = _k_bounds(qi, block_q, block_k, seq_len, causal, window)
    dq = jax.lax.fori_loop(lo, hi, tile,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, n_in, scale, causal, block_q, block_k, seq_len,
                    use_slope, use_window):
    """dK/dV for one (batch·head, k-block): stream Q/dO blocks, the scores
    transposed ([BK, BQ]) so that both sums are plain products:
    dv = Σ_qb Pᵀ @ dO;  dk = Σ_qb dSᵀ @ Q."""
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), slope, window = \
        _scalars(refs[:n_in], use_slope, use_window)
    dk_ref, dv_ref = refs[n_in:]
    ki = pl.program_id(1)
    k = _scaled(k_ref[0], scale)                       # [BK, D]
    v = v_ref[0]
    d = k.shape[-1]
    # qpos - kpos of the transposed tile, less the tile's offset
    rel = (jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
           - jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0))
    bias = _alibi(slope, ki * block_k, block_k, 0)     # [BK, 1]

    def tile(qb, carry):
        dk, dv = carry
        q0 = pl.multiple_of(qb * block_q, block_q)
        q = q_ref[0, pl.ds(q0, block_q), :]
        do = do_ref[0, pl.ds(q0, block_q), :]
        lse = lse_ref[0, pl.ds(qb, 1), :]              # [1, BQ]
        delta = delta_ref[0, pl.ds(qb, 1), :]
        s = _scores(k, q, bias, rel, q0 - ki * block_k, causal, window)
        p = jnp.exp(s - lse)                           # [BK, BQ]
        dv = dv + jnp.dot(p.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = dk + jnp.dot(ds.astype(q.dtype), q,
                          preferred_element_type=jnp.float32)
        return dk, dv

    zeros = jnp.zeros((block_k, d), jnp.float32)
    lo, hi = _q_bounds(ki, block_q, block_k, seq_len, causal, window)
    dk, dv = jax.lax.fori_loop(lo, hi, tile, (zeros, zeros))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_pallas(scale, causal, res, g, block_q, block_k,
                      interpret=False, alibi_slopes=None, window=None):
    """O(S)-memory flash backward: recompute P per tile from the saved LSE.
    Returns (dq, dk, dv) with GQA group reduction."""
    q, k, v, out, lse = res
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)

    qr = jnp.swapaxes(q, 1, 2).reshape(B * H, S, D)
    kr = jnp.swapaxes(k, 1, 2).reshape(B * Hkv, S, D)
    vr = jnp.swapaxes(v, 1, 2).reshape(B * Hkv, S, D)
    gr = jnp.swapaxes(g, 1, 2).reshape(B * H, S, D)
    # delta_i = Σ_d dO_i · O_i  (the softmax-jacobian row term), [B, H, S]
    delta = jnp.einsum("bshd,bshd->bhs", g.astype(jnp.float32),
                       out.astype(jnp.float32))
    # lane-dense rows: dq takes a [1, BQ] block of the [1, S] row (and
    # turns it), dk/dv, whose scores are transposed, a q block's row by its
    # index
    lse_row, delta_row = (x.reshape(B * H, 1, S) for x in (lse, delta))
    lse_blk, delta_blk = (x.reshape(B * H, S // block_q, block_q)
                          for x in (lse, delta))

    slopes_bh, w_bh = _bias_inputs(alibi_slopes, window, B, H)
    scalar_specs = ([] if slopes_bh is None
                    else _scalar_specs())
    scalar_args = [] if slopes_bh is None else [slopes_bh, w_bh]
    static = dict(n_in=6 + len(scalar_args), scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k, seq_len=S,
                  use_slope=alibi_slopes is not None,
                  use_window=window is not None)
    vmem = _vmem_params(S, D, block_q, block_k, k.dtype.itemsize, group)

    q_spec = pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0))
    kv_spec = pl.BlockSpec((1, S, D), lambda bh, i, g=group: (bh // g, 0, 0))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh, 0, qi))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **static),
        grid=(B * H, S // block_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
        + scalar_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        interpret=interpret,
        name="flash_attention_dq",
        **vmem,
    )(qr, kr, vr, gr, lse_row, delta_row, *scalar_args)

    full_spec = pl.BlockSpec((1, S, D), lambda bh, ki: (bh, 0, 0))
    k_spec = pl.BlockSpec((1, block_k, D),
                          lambda bh, ki, g=group: (bh // g, ki, 0))
    rows_spec = pl.BlockSpec((1, S // block_q, block_q),
                             lambda bh, ki: (bh, 0, 0))
    dkv_spec = pl.BlockSpec((1, block_k, D), lambda bh, ki: (bh, ki, 0))
    # a query head's dk/dv: the gradient itself where it has the kv head
    # to itself, else a float32 term of the group's sum
    dkv_shape = jax.ShapeDtypeStruct(
        (B * H, S, D), k.dtype if group == 1 else jnp.float32)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **static),
        grid=(B * H, S // block_k),
        in_specs=[full_spec, k_spec, k_spec, full_spec, rows_spec,
                  rows_spec] + scalar_specs,
        out_specs=[dkv_spec, dkv_spec],
        out_shape=[dkv_shape, dkv_shape],
        interpret=interpret,
        name="flash_attention_dkv",
        **vmem,
    )(qr, kr, vr, gr, lse_blk, delta_blk, *scalar_args)

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
                          window=window, names=FLASH_RESIDUALS)
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


def flash_attention(q, k, v, causal=True, softmax_scale=None,
                    block_q=None, block_k=None, interpret=False,
                    alibi_slopes=None, window=None):
    """q: [B, S, H, D]; k/v: [B, S, Hkv, D].  Raises ``ValueError`` when the
    shape doesn't tile (:func:`flash_tiles`) — choosing another
    implementation is ``ops.attention.attention``'s decision, not the
    kernel's.  ``interpret=True`` runs the kernel in the Pallas interpreter
    (CPU CI).

    ``block_q`` / ``block_k``: None takes :func:`pick_flash_tiles`' tiles
    for the call's shapes; a value overrides (tests, the autotuner).

    ``alibi_slopes`` ([H] fp32, treated as CONSTANT — stop_gradient; ALiBi
    slopes are a deterministic function of the head count, never learned)
    adds the Bloom-style per-head bias ``slope * kpos`` in-kernel; ``window`` (traced int scalar, 0/None =
    unlimited) applies a sliding-window mask AND skips K blocks wholly
    outside the window, so GPT-Neo/Mistral local attention gets its
    asymptotics (role of the reference's local-attention inference kernels,
    ``csrc/transformer/inference``)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    if H % Hkv:
        raise ValueError(
            f"flash attention cannot tile q{q.shape} k{k.shape}: the kv "
            "heads must divide the heads")
    block_q, block_k = resolve_tiles(S, D, H // Hkv, q.dtype.itemsize,
                                     block_q, block_k)
    if S % block_q or S % block_k:
        raise ValueError(
            f"flash attention cannot tile q{q.shape} k{k.shape} with "
            f"block_q={block_q} block_k={block_k}: the sequence must be a "
            "multiple of both blocks")
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
