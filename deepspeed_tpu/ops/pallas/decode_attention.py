"""Pallas decode attention over the contiguous KV cache.

Parity role: the reference's fused inference attention
``softmax_context_fp16`` (``csrc/transformer/inference/csrc/pt_binding.cpp``
~:1720) — attention over a growing KV cache, GQA-aware, without
materialising logits in HBM.

TPU design:

* grid = (batch, kv_heads, key_blocks); the per-sequence valid length is a
  **scalar-prefetch** operand so both the BlockSpec index maps and the
  kernel see it before the body runs;
* key blocks past a sequence's length are never fetched: the index map
  clamps to the last valid block (Pallas skips the DMA when the block index
  repeats) and ``pl.when`` skips their compute;
* online softmax (running max / sum / accumulator in VMEM scratch that
  persists across the key-block grid dimension), fp32 accumulation, one
  [group·T, D] output tile per (batch, kv head);
* GQA comes free: the q tile for one kv head is its whole head group.

The paged pools have their own kernel,
``ops/pallas/ragged_paged_attention.py``.  The jnp path in
``ops/decode_attention.py`` remains the test oracle; ``interpret=True``
runs this kernel on CPU CI.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, block_k, n_q_tokens,
                   group):
    """One (batch, kv-head, key-block) step of online-softmax attention.

    q_ref: [1, T, 1, group, D]; k_ref/v_ref: [1, 1, block_k, D]
    (cache layout [B, Hkv, S, D] — seq on sublanes, D on lanes);
    o_ref: [1, T, 1, group, D]; scratch acc/m/l persist across the
    key-block grid dim (TPU grids are sequential)."""
    b = pl.program_id(0)
    i = pl.program_id(2)
    n_blocks = pl.num_programs(2)
    length = lengths_ref[b]

    T, G = n_q_tokens, group
    rows = T * G
    d = q_ref.shape[-1]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i * block_k < length)
    def _compute():
        q = q_ref[0].reshape(rows, d).astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)                # [BK, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [rows, BK]

        # causal-ragged mask: row r is query token t = r // group at
        # absolute position length - T + t; keys at i*block_k + col
        row_t = jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0) // G
        kpos = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        qpos = length - T + row_t
        s = jnp.where(kpos <= qpos, s, _NEG)

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

    @pl.when(i == n_blocks - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0] = (acc_ref[...] / l_safe).reshape(T, G, d) \
            .astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, lengths, softmax_scale=None,
                            block_k=256, interpret=False):
    """Ragged decode attention over a contiguous cache.

    q: [B, T, H, D] — the last T tokens of each sequence (T=1 decode,
    T>1 chunked prefill; they are already appended to the cache);
    k/v: [B, Hkv, S_max, D]; lengths: [B] int32 valid prefix lengths.
    """
    B, T, H, D = q.shape
    S = k.shape[2]
    Hkv = k.shape[1]
    group = H // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    block_k = min(block_k, S)
    assert S % block_k == 0, f"S_max {S} must tile by block_k {block_k}"
    n_blocks = S // block_k
    lengths = jnp.asarray(lengths, jnp.int32)

    # [B, T, H, D] -> [B, T, Hkv, group, D]: head h of kv-head hk is
    # column hk*group + g, which is exactly how H is laid out for GQA
    qg = q.reshape(B, T, Hkv, group, D)

    def k_map(b, h, i, lens):
        # never fetch blocks past the valid length: clamp to the last
        # block containing valid keys (repeat index -> DMA skipped)
        last = jnp.maximum(pl.cdiv(lens[b], block_k) - 1, 0)
        return (b, h, jnp.minimum(i, last), 0)

    grid = (B, Hkv, n_blocks)
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, n_q_tokens=T,
        group=group)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, T, 1, group, D),
                             lambda b, h, i, lens: (b, 0, h, 0, 0)),
                pl.BlockSpec((1, 1, block_k, D), k_map),
                pl.BlockSpec((1, 1, block_k, D), k_map),
            ],
            out_specs=pl.BlockSpec((1, T, 1, group, D),
                                   lambda b, h, i, lens: (b, 0, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((T * group, D), jnp.float32),
                pltpu.VMEM((T * group, 1), jnp.float32),
                pltpu.VMEM((T * group, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, T, Hkv, group, D), q.dtype),
        interpret=interpret,
    )(lengths, qg, k, v)
    return out.reshape(B, T, H, D)

