"""Ragged paged attention: ONE Pallas kernel for mixed prefill+decode.

Parity role: the serving engine's hottest op.  The jnp gather path in
``ops/paged_attention.py`` materialises every sequence's pages as a dense
``[B, Hkv, max_pages*page, D]`` view each step — three HBM passes over
max-length-padded K/V per decoded token.  This kernel (Ragged Paged
Attention, arXiv:2604.15464, cf. PAPERS.md) reads K/V pages IN PLACE
through the block table and serves a whole mixed batch in one launch:

* **Packed ragged queries.**  ``q`` is a flat ``[total_q, H, D]`` row
  stack — a 37-token prefill, three single-token decodes, and a 9-token
  chunked prefill ride in ONE call.  Per-sequence query lengths are host
  metadata (the engine knows them), so there is no per-slot padding to a
  batch max and no host-side regrouping into separate prefill and decode
  dispatches.  Internally each sequence's rows are padded only up to the
  next ``q_tile`` multiple.
* **grid = (q_tiles, kv_heads, pages)**; scalar-prefetched metadata
  (context lengths, query lengths, padded row starts, tile→sequence /
  tile→q-tile maps, block tables) steers the BlockSpec index maps, so the
  K/V index map fetches exactly the owning sequence's pages — shared
  prefix-cache pages and partial last pages read in place; pages past the
  tile's causal frontier are clamped to a repeat index (DMA skipped) and
  their compute is ``pl.when``-predicated off.
* **Online softmax** (running max / sum / fp32 accumulator in VMEM
  scratch persisting across the sequential page grid dim), one
  ``[q_tile·group, D]`` tile per (q-tile, kv-head); GQA comes free by
  folding each kv head's whole query group into the tile rows.

``ragged_paged_attention`` is the packed front-end (tests/bench/gate);
``ragged_paged_attention_rect`` adapts the rectangular ``[B, T, H, D]``
calls the jitted serving path makes (every sequence q_len = T) onto the
same kernel — it is what ``paged_decode_attention(backend="pallas")``
and the deprecated ``paged_attention_pallas`` route through, so there is
one paged-attention kernel surface.  The jnp gather path remains the
oracle; ``interpret=True`` runs this kernel on CPU CI.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30

DEFAULT_Q_TILE = 8


def _ragged_kernel(ctx_ref, qlens_ref, qstarts_ref, sot_ref, qot_ref,
                   tables_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, page_size, q_tile,
                   group):
    """One (q-tile, kv-head, page) step of online-softmax attention.

    q_ref: [q_tile, 1, group, D] — ``q_tile`` padded query rows of ONE
    sequence for one kv head's whole group; k_ref/v_ref: [1, 1, page, D]
    (the page the index map resolved through the block table);
    o_ref: [q_tile, 1, group, D]; scratch acc/m/l persist across the
    page grid dim (TPU grids are sequential)."""
    t = pl.program_id(0)
    i = pl.program_id(2)
    n_pages = pl.num_programs(2)
    s = sot_ref[t]
    qt = qot_ref[t]
    ctx = ctx_ref[s]          # tokens in the cache INCLUDING the queries
    qlen = qlens_ref[s]       # this sequence's real (unpadded) query rows
    # keys this q tile may attend (causal): positions < kv_hi
    kv_hi = ctx - qlen + jnp.minimum(qlen, (qt + 1) * q_tile)

    rows = q_tile * group
    d = q_ref.shape[-1]

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i * page_size < kv_hi)
    def _compute():
        q = q_ref[:, 0].reshape(rows, d).astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)                # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [rows, page]

        # row r is the sequence's local query token qt*q_tile + r//group
        # at absolute position ctx - qlen + local_t; per-sequence padding
        # rows (local_t >= qlen) mask to nothing and finalize to zeros
        local_t = qt * q_tile + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) // group
        kpos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        qpos = ctx - qlen + local_t
        sc = jnp.where((kpos <= qpos) & (local_t < qlen), sc, _NEG)

        m_prev, l_prev = m_ref[...], l_ref[...]
        bm = jnp.max(sc, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, bm)
        p = jnp.exp(sc - m_new)
        p = jnp.where(m_new <= _NEG / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        corr = jnp.where(m_prev <= _NEG / 2, 0.0, corr)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == n_pages - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[:, 0] = (acc_ref[...] / l_safe).reshape(q_tile, group, d) \
            .astype(o_ref.dtype)


# fixed names on the device's lines (a trace shows the instruction as
# ``ragged_paged_attention_decode.<n>``): decode when every sequence brings
# one query token, prefill otherwise (bucketed or chunked prompts, and the
# speculative verify window)
KERNEL_PREFILL = "ragged_paged_attention_prefill"
KERNEL_DECODE = "ragged_paged_attention_decode"


def _ragged_call(qg, k_pages, v_pages, block_tables, ctx_lens, q_lens,
                 q_starts, seq_of_tile, qtile_of_tile, q_tile, scale,
                 interpret, name):
    """Launch the kernel over a q-tile-padded packed query stack.

    qg: [total_padded, Hkv, group, D] — every sequence's rows start at a
    q_tile multiple (``q_starts``).  ctx_lens/q_lens may be traced;
    q_starts / seq_of_tile / qtile_of_tile are host metadata (they size
    the grid)."""
    total_padded, Hkv, group, D = qg.shape
    page_size = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    n_tiles = len(seq_of_tile)
    ctx_lens = jnp.asarray(ctx_lens, jnp.int32)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    q_starts = jnp.asarray(q_starts, jnp.int32)
    sot = jnp.asarray(seq_of_tile, jnp.int32)
    qot = jnp.asarray(qtile_of_tile, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)

    def q_map(t, h, i, ctx, qls, qst, sot, qot, tbl):
        return (qst[sot[t]] // q_tile + qot[t], h, 0, 0)

    def kv_map(t, h, i, ctx, qls, qst, sot, qot, tbl):
        # fetch only pages under this tile's causal frontier: clamp to the
        # last needed page (repeat index -> DMA skipped)
        s = sot[t]
        kv_hi = ctx[s] - qls[s] + jnp.minimum(qls[s], (qot[t] + 1) * q_tile)
        last = jnp.maximum(pl.cdiv(kv_hi, page_size) - 1, 0)
        return (tbl[s, jnp.minimum(i, last)], h, 0, 0)

    kernel = functools.partial(_ragged_kernel, scale=scale,
                               page_size=page_size, q_tile=q_tile,
                               group=group)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n_tiles, Hkv, max_pages),
            in_specs=[
                pl.BlockSpec((q_tile, 1, group, D), q_map),
                pl.BlockSpec((1, 1, page_size, D), kv_map),
                pl.BlockSpec((1, 1, page_size, D), kv_map),
            ],
            out_specs=pl.BlockSpec((q_tile, 1, group, D), q_map),
            scratch_shapes=[
                pltpu.VMEM((q_tile * group, D), jnp.float32),
                pltpu.VMEM((q_tile * group, 1), jnp.float32),
                pltpu.VMEM((q_tile * group, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        interpret=interpret,
        name=name,
    )(ctx_lens, q_lens, q_starts, sot, qot, tables,
      qg, k_pages, v_pages)
    return out


def _pack_metadata(q_lens, q_tile):
    """Per-sequence padded row starts and tile maps for a packed stack."""
    starts, seq_of_tile, qtile_of_tile = [], [], []
    off = 0
    for s, ql in enumerate(q_lens):
        starts.append(off)
        n_t = -(-ql // q_tile)
        seq_of_tile.extend([s] * n_t)
        qtile_of_tile.extend(range(n_t))
        off += n_t * q_tile
    return (np.asarray(starts, np.int32),
            np.asarray(seq_of_tile, np.int32),
            np.asarray(qtile_of_tile, np.int32), off)


def ragged_paged_attention(q, k_pages, v_pages, block_tables, ctx_lens,
                           q_lens, softmax_scale=None,
                           q_tile=DEFAULT_Q_TILE, interpret=False):
    """Mixed prefill+decode attention over a packed ragged batch.

    q: [total_q, H, D] — sequence b's rows are
    ``q[sum(q_lens[:b]) : sum(q_lens[:b+1])]`` (its LAST q_lens[b] tokens,
    already appended to the cache); k_pages/v_pages: [P, Hkv, page, D];
    block_tables: [B, max_pages] int32; ctx_lens: [B] int32 tokens stored
    per sequence INCLUDING the query tokens (may be traced); q_lens: [B]
    host ints — the packed layout is host metadata, like the block
    tables' shape.  Returns [total_q, H, D].
    """
    total_q, H, D = q.shape
    Hkv = k_pages.shape[1]
    group = H // Hkv
    q_lens = [int(x) for x in np.asarray(q_lens).reshape(-1)]
    assert q_lens and min(q_lens) >= 1, f"bad q_lens {q_lens}"
    assert sum(q_lens) == total_q, \
        f"q has {total_q} rows but q_lens sums to {sum(q_lens)}"
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    q_tile = int(min(q_tile, max(q_lens)))
    starts, sot, qot, total_padded = _pack_metadata(q_lens, q_tile)

    # scatter each sequence's rows to its q_tile-aligned start (static
    # offsets: this is shape plumbing, not data-dependent control flow)
    qp = jnp.zeros((total_padded, H, D), q.dtype)
    off = 0
    for s, ql in enumerate(q_lens):
        qp = qp.at[int(starts[s]):int(starts[s]) + ql].set(q[off:off + ql])
        off += ql

    out = _ragged_call(qp.reshape(total_padded, Hkv, group, D),
                       k_pages, v_pages, block_tables, ctx_lens, q_lens,
                       starts, sot, qot, q_tile, scale, interpret,
                       KERNEL_DECODE if max(q_lens) == 1 else KERNEL_PREFILL)
    out = out.reshape(total_padded, H, D)
    return jnp.concatenate(
        [out[int(starts[s]):int(starts[s]) + ql]
         for s, ql in enumerate(q_lens)], axis=0)


def ragged_paged_attention_rect(q, k_pages, v_pages, block_tables, lengths,
                                softmax_scale=None, q_tile=DEFAULT_Q_TILE,
                                interpret=False):
    """Rectangular front-end for the jitted serving path.

    q: [B, T, H, D] — the last T tokens of each sequence (T=1 decode,
    T>1 bucketed/chunked prefill); lengths: [B] int32 valid tokens
    including the T new ones (may be traced — T itself is the static
    shape, so the packed metadata stays host-side).  Same kernel as
    :func:`ragged_paged_attention`; rows past a multiple-of-q_tile pad
    are masked inside the kernel.
    """
    B, T, H, D = q.shape
    Hkv = k_pages.shape[1]
    group = H // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    q_tile = int(min(q_tile, T))
    n_qt = -(-T // q_tile)
    Tp = n_qt * q_tile
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    starts = np.arange(B, dtype=np.int32) * Tp
    sot = np.repeat(np.arange(B, dtype=np.int32), n_qt)
    qot = np.tile(np.arange(n_qt, dtype=np.int32), B)
    q_lens = jnp.full((B,), T, jnp.int32)
    out = _ragged_call(q.reshape(B * Tp, Hkv, group, D),
                       k_pages, v_pages, block_tables, lengths, q_lens,
                       starts, sot, qot, q_tile, scale, interpret,
                       KERNEL_DECODE if T == 1 else KERNEL_PREFILL)
    return out.reshape(B, Tp, H, D)[:, :T]
