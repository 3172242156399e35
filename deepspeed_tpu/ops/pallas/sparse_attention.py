"""Pallas block-sparse attention (BigBird / Longformer / Fixed layouts).

Parity: reference ``deepspeed/ops/sparse_attention`` Triton kernels
(``matmul.py:8-14`` block-sparse sddmm/dsd, ``softmax.py``) — compute that
scales with the number of SET blocks of the layout, not O(S²).

TPU design: the layout [H, nb, nb] is static config, so the active-block
structure is precomputed on the host into an index table
``table[H, nQ, max_active]`` + ``counts[H, nQ]`` and shipped as
scalar-prefetch operands.  The grid is (batch·heads, q_blocks,
max_active): the K/V BlockSpec index maps look the k-block id up in the
table (clamping past ``counts`` so the repeated index skips the DMA), and
``pl.when`` skips the compute — both memory traffic and MXU work scale
with set blocks, which is exactly what the Triton sddmm/dsd pair buys the
reference.  Online softmax accumulates in VMEM scratch across the
active-block grid dimension; rows whose blocks are all masked produce
zeros (the reference kernel's empty-row handling).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def layout_tables(layout: np.ndarray, causal: bool):
    """[H, nb, nb] boolean layout → (table [H, nb, max_active] int32,
    counts [H, nb] int32).  With ``causal`` the upper triangle is dropped
    (those blocks would be fully masked anyway)."""
    lay = np.asarray(layout).astype(bool)
    H, nq, nk = lay.shape
    if causal:
        lay = lay & (np.arange(nq)[:, None] >= np.arange(nk)[None, :])
    counts = lay.sum(-1).astype(np.int32)                    # [H, nq]
    max_active = max(int(counts.max()), 1)
    table = np.zeros((H, nq, max_active), np.int32)
    for h in range(H):
        for qi in range(nq):
            idx = np.nonzero(lay[h, qi])[0]
            table[h, qi, :len(idx)] = idx
    return table, counts, max_active


def _sparse_kernel(counts_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, causal, block, n_heads):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    i = pl.program_id(2)
    n_steps = pl.num_programs(2)
    h = bh % n_heads
    count = counts_ref[h, qi]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i < count)
    def _compute():
        kb = table_ref[h, qi, i]
        q = q_ref[0].astype(jnp.float32) * scale            # [BLK, D]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            kpos = kb * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            s = jnp.where(qpos >= kpos, s, _NEG)
        m_prev, l_prev = m_ref[...], l_ref[...]
        bm = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, bm)
        p = jnp.exp(s - m_new)
        p = jnp.where(m_new <= _NEG / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        corr = jnp.where(m_prev <= _NEG / 2, 0.0, corr)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == n_steps - 1)
    def _finalize():
        # empty rows (count==0 or fully causal-masked) have l==0 and
        # acc==0: 0/eps = 0, matching the oracle's empty-row zeroing
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def sparse_attention_pallas(q, k, v, layout, block, causal=False,
                            softmax_scale=None, interpret=False):
    """q/k/v: [B, S, H, D]; layout: [H, nb, nb] (numpy, static).
    Only set blocks are fetched and computed."""
    B, S, H, D = q.shape
    assert S % block == 0, f"S {S} must tile by layout block {block}"
    nb = S // block
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    table, counts, max_active = layout_tables(
        np.asarray(layout)[:, :nb, :nb], causal)

    qr = jnp.swapaxes(q, 1, 2).reshape(B * H, S, D)
    kr = jnp.swapaxes(k, 1, 2).reshape(B * H, S, D)
    vr = jnp.swapaxes(v, 1, 2).reshape(B * H, S, D)

    def kv_map(bh, qi, i, counts_ref, table_ref):
        h = bh % H
        last = jnp.maximum(counts_ref[h, qi] - 1, 0)
        return (bh, table_ref[h, qi, jnp.minimum(i, last)], 0)

    kernel = functools.partial(
        _sparse_kernel, scale=scale, causal=causal, block=block, n_heads=H)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * H, nb, max_active),
            in_specs=[
                pl.BlockSpec((1, block, D),
                             lambda bh, qi, i, c, t: (bh, qi, 0)),
                pl.BlockSpec((1, block, D), kv_map),
                pl.BlockSpec((1, block, D), kv_map),
            ],
            out_specs=pl.BlockSpec((1, block, D),
                                   lambda bh, qi, i, c, t: (bh, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((block, D), jnp.float32),
                pltpu.VMEM((block, 1), jnp.float32),
                pltpu.VMEM((block, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        interpret=interpret,
    )(jnp.asarray(counts), jnp.asarray(table), qr, kr, vr)
    return jnp.swapaxes(out.reshape(B, H, S, D), 1, 2)


def sparse_flops(layout, block, causal, head_dim):
    """Analytic kernel cost: FLOPs proportional to set blocks (the
    scaling contract the Triton kernels have; used by tests/profilers)."""
    table, counts, _ = layout_tables(np.asarray(layout), causal)
    set_blocks = int(counts.sum())
    return 4 * set_blocks * block * block * head_dim
