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
* **A grid step is MXU-sized, and its shape is picked from the call's
  shapes** (:func:`pick_tiles`, one pure function of the query lengths,
  ``group``, ``Hkv``, ``page_size``, ``D``, the table width and the
  cache's itemsize, under ``VMEM_BUDGET``).  One step multiplies ``heads``
  kv heads' ``[q_tile·group, D]`` query rows by ``pages`` pages of keys at
  once (a batched dot over the heads).  A long prefill takes up to 1,024
  rows of one head by up to 1,024 keys a step; a T=1 decode takes ALL kv
  heads of its pages (one page's ``[Hkv, page, D]`` block is contiguous
  in the pool), two pages of 128 a step; a few rows (speculative verify,
  small chunks) sit in between; GQA folds each kv head's whole query
  group into the rows.  The
  wrapper lays q out as ``[tiles, Hkv, q_tile·group, D]`` (XLA's
  transpose, outside the kernel) so a tile's rows are the second-minor
  dimension of its block.
* **The grid follows the contexts.**  grid = (kv-head blocks, items): an
  item is one (q tile, kv step) pair under that tile's causal frontier,
  and the NUMBER of items is a traced value (a dynamic grid bound), so a
  call runs as many steps as its contexts hold keys — not the rectangle
  of tiles x block-table columns — and the compiled program stays one
  per shape.  The item map (:func:`build_item_map`) is built inside the
  jit from the context lengths: the running sum of each tile's steps
  (:func:`live_steps`, the arithmetic the serving engine's
  ``kernel_grid`` repeats on the host) and, from it, the tile of every
  item.  A tile with no key keeps one item, so every output block is
  written.  A dispatch builds the map once, ahead of its layer loop
  (:func:`rect_item_map`).  Where tiles x columns is past
  ``ITEM_TABLE_MAX`` (an engine of hundreds of slots by hundreds of
  columns) the running sum alone rides in scalar memory and the index
  maps bisect it; ``TileChoice.item_table`` says which.
* **Scalar-prefetched metadata** (context lengths, query lengths,
  tile→sequence / tile→q-tile maps, block tables, the layer, the item
  map) steers the BlockSpec index maps: each of the step's ``pages`` K/V
  operands resolves its own page through the block table, so exactly the
  owning sequence's pages are fetched — shared prefix-cache pages and
  partial last pages read in place; the last step's operands past the
  frontier clamp to its last page.  Head blocks are the outer grid axis,
  so one tile's steps stay consecutive and its accumulators live in
  scratch between them.
* **Online softmax** (running max / sum / accumulator in float32 VMEM
  scratch persisting across a tile's consecutive items).  ``QK^T`` and
  ``PV`` take operands in the cache's dtype (bf16 cache: bf16 operands,
  float32 accumulation; float32 cache: float32 operands), ``p`` is cast
  to the value dtype for ``PV`` — what the jnp oracle does.

``ragged_paged_attention`` is the packed front-end (tests/bench/gate);
``ragged_paged_attention_rect`` adapts the rectangular ``[B, T, H, D]``
calls the jitted serving path makes (every sequence q_len = T) onto the
same kernel — it is what ``paged_decode_attention(impl="pallas")``
(``ops/paged_attention.py``) routes through, so there is
one paged-attention kernel surface.  The jnp gather path remains the
oracle; ``interpret=True`` runs this kernel on CPU CI.

The serving dispatch hands the kernel the STACKED pools
``[L, P, Hkv, page, D]`` and a layer index (a sixth scalar-prefetch
operand the K/V index maps read), and writes each layer's new rows with
the second kernel of this file, ``paged_kv_write``, whose pool operands
are aliased to its outputs: between its donated argument and its result a
dispatch never copies, slices or re-lays a pool (docs/serving.md).
"""

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30

# what one grid step aims at: rows of the score tile (kv heads x query
# tokens x group) and keys (pages x page_size) — a compute-bound step
# (many rows) wants both large; a step with few rows is bound by its K/V
# traffic and wants no more than ``STEP_KV_BYTES`` of it, because the
# last step of a sequence fetches whole groups of pages (measured on the
# v5e, PERF.md §6 PR 25).  ``VMEM_BUDGET`` bounds the step's blocks,
# scratch and score temporaries by ``TileChoice``'s own count; the
# compiler is given twice that (the v5e has 128 MiB of VMEM).
MAX_ROWS = 1024
TARGET_KEYS = 1024
STEP_KV_BYTES = 2 * 2 ** 20
MAX_PAGES_PER_STEP = 8
VMEM_BUDGET = 16 * 2 ** 20
# (q tile, kv step) pairs whose tile may be looked up in scalar memory,
# one int32 each beside the block tables
ITEM_TABLE_MAX = 16 * 1024


class TileChoice(NamedTuple):
    """Block shapes of one call: ``q_tile`` query tokens of one sequence
    x ``heads`` kv heads x ``pages`` K/V pages a grid step.  ``grid`` is
    the rectangle the block table spans; a call runs the part of it that
    holds keys (:func:`live_steps`)."""
    q_tile: int
    heads: int
    pages: int
    grid: Tuple[int, int, int]     # (q tiles, kv-head blocks, kv steps)
    vmem_bytes: int
    # the item map's tile_of_item rides in scalar memory (else the index
    # maps search the running sum of the tiles' steps)
    item_table: bool

    @property
    def grid_steps(self) -> int:
        return self.grid[0] * self.grid[1] * self.grid[2]


def _sublanes(itemsize):
    """Rows of one sublane tile of a dtype (8 of float32, 16 of bf16)."""
    return 8 * max(1, 4 // itemsize)


def _step_vmem_bytes(q_tile, heads, pages, group, page_size, D, itemsize):
    """VMEM one grid step holds: double-buffered q/out and K/V blocks, the
    concatenated K/V of a multi-page step, the float32 scratch (max and
    sum padded to a lane tile) and three score-sized temporaries."""
    rows = heads * q_tile * group
    lanes = -(-D // 128) * 128
    keys = pages * page_size
    kv = 2 * heads * keys * lanes * itemsize
    return (2 * 2 * rows * lanes * itemsize
            + 2 * kv + (kv if pages > 1 else 0)
            + rows * (lanes + 2 * 128) * 4
            + 3 * rows * max(keys, 128) * 4)


def pick_tiles(q_lens, group, n_kv_heads, page_size, head_dim, table_width,
               itemsize, q_tile=None, window=None, ring=None) -> TileChoice:
    """Block shapes for one call, from what the call can see.

    ``q_lens``: host ints, one per sequence (``[T] * B`` for the
    rectangular path).  Query rows first: up to ``MAX_ROWS // group``
    tokens a tile, the tiles of the longest sequence evened out; then as
    many kv heads a step as keep the score tile within ``MAX_ROWS`` rows
    (all of them at T=1); then pages up to ``TARGET_KEYS`` keys and
    ``STEP_KV_BYTES`` of K and V a step (a page must fill whole sublane
    tiles of its dtype to be concatenated with others); pages, then
    heads, shrink to fit ``VMEM_BUDGET``.  ``q_tile`` overrides the first
    choice (tests).  Under a ``window`` a tile runs no more kv steps than
    hold its rows' windows, whatever the table's width (a ``ring``'s width
    is no bound at all: its columns come round again)."""
    T = max(q_lens)
    if q_tile is None:
        cap = max(1, MAX_ROWS // group)
        q_tile = -(-T // -(-T // cap))
        if q_tile < T:
            q_tile = min(T, -(-q_tile // 8) * 8)
    q_tile = int(min(q_tile, T))
    rows = q_tile * group
    divisors = [h for h in range(n_kv_heads, 0, -1) if n_kv_heads % h == 0]
    heads = next((h for h in divisors if h * rows <= MAX_ROWS), 1)
    sublanes = _sublanes(itemsize)
    pages = 1
    if page_size % sublanes == 0:
        page_kv_bytes = 2 * heads * page_size * head_dim * itemsize
        pages = max(1, min(table_width, MAX_PAGES_PER_STEP,
                           TARGET_KEYS // page_size,
                           STEP_KV_BYTES // page_kv_bytes))

    def vmem(heads, pages):
        return _step_vmem_bytes(q_tile, heads, pages, group, page_size,
                                head_dim, itemsize)

    while pages > 1 and vmem(heads, pages) > VMEM_BUDGET:
        pages -= 1
    while heads > 1 and vmem(heads, pages) > VMEM_BUDGET:
        heads = next(h for h in divisors if h < heads)
    n_tiles = sum(-(-int(ql) // q_tile) for ql in q_lens)
    kv_steps = -(-table_width // pages)
    if window is not None:
        most = (window + q_tile - 2) // (pages * page_size) + 2
        kv_steps = most if ring else min(kv_steps, most)
    grid = (n_tiles, n_kv_heads // heads, kv_steps)
    return TileChoice(q_tile, heads, pages, grid, vmem(heads, pages),
                      item_table=grid[0] * grid[2] <= ITEM_TABLE_MAX)


def first_step(ctx, qlen, qtile, q_tile, keys, window, xp=jnp):
    """The kv step (of ``keys`` keys) that holds the oldest key inside the
    ``window`` of a q tile's first row."""
    return xp.maximum(ctx - qlen + qtile * q_tile - window + 1, 0) // keys


def live_steps(ctx_lens, q_lens, seq_of_tile, qtile_of_tile,
               tiles: TileChoice, page_size, xp=jnp, window=None):
    """kv steps each q tile runs, [..., n_tiles]: the steps of
    ``tiles.pages`` pages under the tile's causal frontier (from the one
    that holds the first key inside the ``window`` of its first row), and
    one for a tile with no key (its output block is still written).
    ``xp`` is ``jnp`` inside the jit (the item map) or ``numpy`` on the
    host (:func:`rect_grid_steps`): the same arithmetic for both."""
    ctx, qlen = ctx_lens[..., seq_of_tile], q_lens[seq_of_tile]
    keys = tiles.pages * page_size
    # keys this q tile may attend (causal): positions < kv_hi
    kv_hi = ctx - qlen + xp.minimum(qlen, (qtile_of_tile + 1) * tiles.q_tile)
    steps = -(-kv_hi // keys)
    if window is not None:
        steps = steps - first_step(ctx, qlen, qtile_of_tile, tiles.q_tile,
                                   keys, window, xp)
    return xp.clip(steps, 1, tiles.grid[2])


def rect_metadata(B, T, q_tile):
    """(seq_of_tile, qtile_of_tile) of B sequences of T query rows each."""
    n_qt = -(-T // q_tile)
    return (np.repeat(np.arange(B, dtype=np.int32), n_qt),
            np.tile(np.arange(n_qt, dtype=np.int32), B))


def rect_grid_steps(tiles: TileChoice, B, T, ctx_lens, page_size,
                    window=None) -> int:
    """Grid steps a [B, T] call runs over host ``ctx_lens`` ([..., B], the
    new tokens included; leading axes are further calls of the shape):
    what the item map of each call will hold, times the kv-head blocks.
    ``tiles.grid_steps`` is the rectangle it is drawn from."""
    steps = live_steps(np.asarray(ctx_lens), np.full(B, T),
                       *rect_metadata(B, T, tiles.q_tile), tiles, page_size,
                       xp=np, window=window)
    return tiles.grid[1] * int(steps.sum())


class ItemMap(NamedTuple):
    """The (q tile, kv step) pairs a call runs, in tile order with steps
    ascending.  ``first``: [n_tiles + 1] int32, the running sum of the
    tiles' steps (tile ``t`` owns items ``first[t] .. first[t + 1] - 1``;
    ``first[-1]`` is their number, the grid's traced bound);
    ``tile_of_item``: [n_tiles x kv steps] int32 where
    ``TileChoice.item_table``, else one unused zero."""
    first: jnp.ndarray
    tile_of_item: jnp.ndarray


def build_item_map(ctx_lens, q_lens, seq_of_tile, qtile_of_tile,
                   tiles: TileChoice, page_size, window=None) -> ItemMap:
    """The item map of one call, inside the jit; the same for every layer
    of a dispatch (of one ``window``), so a layer loop builds it once,
    outside."""
    steps = live_steps(jnp.asarray(ctx_lens, jnp.int32),
                       jnp.asarray(q_lens, jnp.int32), seq_of_tile,
                       qtile_of_tile, tiles, page_size, window=window)
    first = jnp.concatenate([jnp.zeros(1, jnp.int32),
                             jnp.cumsum(steps, dtype=jnp.int32)])
    if not tiles.item_table:
        return ItemMap(first, jnp.zeros(1, jnp.int32))
    n_tiles, _, kv_steps = tiles.grid
    tile_of_item = jnp.searchsorted(
        first[1:], jnp.arange(n_tiles * kv_steps, dtype=jnp.int32),
        side="right", method="compare_all")
    return ItemMap(first, jnp.minimum(tile_of_item, n_tiles - 1)
                   .astype(jnp.int32))


def _locate(item, first_ref, toi_ref, n_tiles, item_table):
    """(q tile, kv step) of grid item ``item``: looked up, or the last
    tile whose first item is not past it (a bisection of ``first``)."""
    if item_table:
        t = toi_ref[item]
    else:
        def halve(_, span):
            lo, hi = span
            mid = (lo + hi + 1) // 2
            under = first_ref[mid] <= item
            return jnp.where(under, mid, lo), jnp.where(under, hi, mid - 1)
        t, _ = jax.lax.fori_loop(0, (n_tiles - 1).bit_length(), halve,
                                 (jnp.int32(0), jnp.int32(n_tiles - 1)))
    return t, item - first_ref[t]


def _ragged_kernel(ctx_ref, qlens_ref, sot_ref, qot_ref, tables_ref,
                   layer_ref, first_ref, toi_ref, q_ref, *refs, scale,
                   page_size, q_tile, group, pages, locate, window=None):
    """One (kv-head block, item) of online-softmax attention; an item is
    one (q tile, kv step) pair of the item map.

    q_ref: [1, heads, q_tile*group, D] — ``q_tile`` padded query rows of
    ONE sequence for ``heads`` kv heads' whole groups; then ``pages`` K
    refs and ``pages`` V refs of [1, heads, page, D] (the pages the index
    maps resolved through the block table); o_ref like q_ref; scratch
    acc/m/l persist across a tile's items, which are consecutive (TPU
    grids are sequential).  Under a ``window`` a tile's items start at
    :func:`first_step` and a row's keys end ``window`` behind it."""
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * pages:]
    item = pl.program_id(1)
    t, i = locate(item, first_ref, toi_ref)
    s = sot_ref[t]
    qt = qot_ref[t]
    ctx = ctx_ref[s]          # tokens in the cache INCLUDING the queries
    qlen = qlens_ref[s]       # this sequence's real (unpadded) query rows
    # keys this q tile may attend (causal): positions < kv_hi
    kv_hi = ctx - qlen + jnp.minimum(qlen, (qt + 1) * q_tile)

    rows = q_tile * group
    keys = pages * page_size
    step = i if window is None else \
        i + first_step(ctx, qlen, qt, q_tile, keys, window)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # the map holds no step past the frontier: only the one item of a tile
    # with no key at all is turned off here
    @pl.when(step * keys < kv_hi)
    def _compute():
        if pages == 1:
            k, v = k_refs[0][0], v_refs[0][0]              # [heads, page, D]
        else:
            k = jnp.concatenate([r[0] for r in k_refs], axis=1)
            v = jnp.concatenate([r[0] for r in v_refs], axis=1)
        q = q_ref[0].astype(k.dtype)                       # [heads, rows, D]
        sc = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # [heads, rows, keys]

        # row r is the sequence's local query token qt*q_tile + r//group
        # at absolute position ctx - qlen + local_t; per-sequence padding
        # rows (local_t >= qlen) see no key and finalize to zeros
        local_t = qt * q_tile + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) // group
        last_key = jnp.where(local_t < qlen, ctx - qlen + local_t, -1)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
        seen = kpos <= last_key - step * keys
        if window is not None:
            seen = seen & (kpos > last_key - window - step * keys)
        sc = jnp.where(seen[None], sc, _NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        # a row with no key yet keeps m = _NEG: exponentiate against 0 so
        # its p is exp(_NEG) = 0, not exp(0)
        p = jnp.exp(sc - jnp.where(m_new <= _NEG / 2, 0.0, m_new))
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(item + 1 == first_ref[t + 1])     # the tile's last item
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


# fixed names on the device's lines (a trace shows the instruction as
# ``ragged_paged_attention_decode.<n>``): decode when every sequence brings
# one query token, prefill otherwise (bucketed or chunked prompts, and the
# speculative verify window)
KERNEL_PREFILL = "ragged_paged_attention_prefill"
KERNEL_DECODE = "ragged_paged_attention_decode"


def _ragged_call(qt, k_pages, v_pages, block_tables, ctx_lens, q_lens,
                 seq_of_tile, qtile_of_tile, tiles: TileChoice, scale,
                 interpret, name, layer=None, items: ItemMap = None,
                 window=None, ring=None):
    """Launch the kernel over a tiled query stack.

    qt: [n_tiles, Hkv, q_tile*group, D] — tile ``t`` holds ``q_tile``
    rows of sequence ``seq_of_tile[t]`` (its ``qtile_of_tile[t]``-th
    tile), each row with its kv head's whole group.  ctx_lens/q_lens may
    be traced; seq_of_tile / qtile_of_tile are host metadata (they size
    the item map).  k_pages/v_pages: one layer's pool [P, Hkv, page, D],
    or the stacked pools [L, P, Hkv, page, D] with ``layer`` (may be
    traced) the one to read — the index maps pick it, so no layer's pool
    is ever sliced out of the stack.  ``items``: the call's
    :func:`build_item_map`, built here when the caller brings none.
    ``window`` / ``ring`` (static): a sliding window over the keys, and a
    block table that is a ring of ``ring`` columns (logical page ``p`` in
    column ``p % ring``)."""
    n_tiles, Hkv, rows, D = qt.shape
    if k_pages.ndim == 4:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    page_size = k_pages.shape[3]
    width = block_tables.shape[1]
    q_tile, heads, pages = tiles.q_tile, tiles.heads, tiles.pages
    assert tiles.grid[0] == n_tiles and rows % q_tile == 0
    ctx_lens = jnp.asarray(ctx_lens, jnp.int32)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    sot = jnp.asarray(seq_of_tile, jnp.int32)
    qot = jnp.asarray(qtile_of_tile, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    if items is None:
        items = build_item_map(ctx_lens, q_lens, seq_of_tile, qtile_of_tile,
                               tiles, page_size, window)
    locate = functools.partial(_locate, n_tiles=n_tiles,
                               item_table=tiles.item_table)

    def q_map(h, item, ctx, qls, sot, qot, tbl, lay, first, toi):
        return (locate(item, first, toi)[0], h, 0, 0)

    def kv_map(j, h, item, ctx, qls, sot, qot, tbl, lay, first, toi):
        # the step's j-th operand is page i*pages + j of the tile's
        # sequence, clamped to the last page under its causal frontier
        t, i = locate(item, first, toi)
        s = sot[t]
        kv_hi = ctx[s] - qls[s] + jnp.minimum(qls[s], (qot[t] + 1) * q_tile)
        last = jnp.maximum(pl.cdiv(kv_hi, page_size) - 1, 0)
        if window is not None:
            i = i + first_step(ctx[s], qls[s], qot[t], q_tile,
                               pages * page_size, window)
        col = jnp.minimum(i * pages + j, last)
        # a ring's columns come round again; a table's last one catches
        # whatever lies past it
        return (lay[0], tbl[s, col % ring if ring
                            else jnp.minimum(col, width - 1)], h, 0, 0)

    kv_specs = [pl.BlockSpec((None, 1, heads, page_size, D),
                             functools.partial(kv_map, j))
                for j in range(pages)]
    kernel = functools.partial(_ragged_kernel, scale=scale,
                               page_size=page_size, q_tile=q_tile,
                               group=rows // q_tile, pages=pages,
                               locate=locate, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=8,
            # head blocks outermost, so a tile's items stay consecutive;
            # the item bound is traced: one program whatever the contexts
            grid=(Hkv // heads, items.first[n_tiles]),
            in_specs=[pl.BlockSpec((1, heads, rows, D), q_map)]
            + kv_specs + kv_specs,
            out_specs=pl.BlockSpec((1, heads, rows, D), q_map),
            scratch_shapes=[
                pltpu.VMEM((heads, rows, D), jnp.float32),
                pltpu.VMEM((heads, rows, 1), jnp.float32),
                pltpu.VMEM((heads, rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * VMEM_BUDGET),
        interpret=interpret,
        name=name,
    )(ctx_lens, q_lens, sot, qot, tables, lay, *items, qt,
      *([k_pages] * pages), *([v_pages] * pages))


def _pick_for(q_lens, q_shape, k_pages, block_tables, q_tile, window=None,
              ring=None):
    H, D = q_shape[-2:]
    Hkv, page_size = k_pages.shape[-3:-1]
    return pick_tiles(q_lens, H // Hkv, Hkv, page_size, D,
                      block_tables.shape[1], k_pages.dtype.itemsize, q_tile,
                      window, ring)


def _to_tiles(q, n_tiles, q_tile, Hkv):
    """[n_tiles*q_tile, H, D] rows -> [n_tiles, Hkv, q_tile*group, D]."""
    _, H, D = q.shape
    group = H // Hkv
    q = q.reshape(n_tiles, q_tile, Hkv, group, D)
    return jnp.swapaxes(q, 1, 2).reshape(n_tiles, Hkv, q_tile * group, D)


def _from_tiles(out, q_tile):
    """Inverse of :func:`_to_tiles`."""
    n_tiles, Hkv, rows, D = out.shape
    group = rows // q_tile
    out = out.reshape(n_tiles, Hkv, q_tile, group, D)
    return jnp.swapaxes(out, 1, 2).reshape(n_tiles * q_tile, Hkv * group, D)


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
                           q_lens, softmax_scale=None, q_tile=None,
                           interpret=False, window=None, ring=None):
    """Mixed prefill+decode attention over a packed ragged batch.

    q: [total_q, H, D] — sequence b's rows are
    ``q[sum(q_lens[:b]) : sum(q_lens[:b+1])]`` (its LAST q_lens[b] tokens,
    already appended to the cache); k_pages/v_pages: [P, Hkv, page, D];
    block_tables: [B, max_pages] int32; ctx_lens: [B] int32 tokens stored
    per sequence INCLUDING the query tokens (may be traced); q_lens: [B]
    host ints — the packed layout is host metadata, like the block
    tables' shape.  ``q_tile`` None lets :func:`pick_tiles` choose (every
    sequence pads to a multiple of the tile the longest one picks).
    ``window`` / ``ring`` as :func:`_ragged_call` reads them.
    Returns [total_q, H, D].
    """
    total_q, H, D = q.shape
    Hkv = k_pages.shape[1]
    q_lens = [int(x) for x in np.asarray(q_lens).reshape(-1)]
    assert q_lens and min(q_lens) >= 1, f"bad q_lens {q_lens}"
    assert sum(q_lens) == total_q, \
        f"q has {total_q} rows but q_lens sums to {sum(q_lens)}"
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    tiles = _pick_for(q_lens, q.shape, k_pages, block_tables, q_tile,
                      window, ring)
    starts, sot, qot, total_padded = _pack_metadata(q_lens, tiles.q_tile)

    # scatter each sequence's rows to its q_tile-aligned start (static
    # offsets: this is shape plumbing, not data-dependent control flow)
    qp = jnp.zeros((total_padded, H, D), q.dtype)
    off = 0
    for s, ql in enumerate(q_lens):
        qp = qp.at[int(starts[s]):int(starts[s]) + ql].set(q[off:off + ql])
        off += ql

    out = _ragged_call(_to_tiles(qp, len(sot), tiles.q_tile, Hkv),
                       k_pages, v_pages, block_tables, ctx_lens, q_lens,
                       sot, qot, tiles, scale, interpret,
                       KERNEL_DECODE if max(q_lens) == 1 else KERNEL_PREFILL,
                       window=window, ring=ring)
    out = _from_tiles(out, tiles.q_tile)
    return jnp.concatenate(
        [out[int(starts[s]):int(starts[s]) + ql]
         for s, ql in enumerate(q_lens)], axis=0)


def _rect_layout(q_shape, k_pages, block_tables, q_tile, window=None,
                 ring=None):
    """(tiles, seq_of_tile, qtile_of_tile) of a [B, T, H, D] call."""
    B, T = q_shape[:2]
    tiles = _pick_for([T] * B, q_shape, k_pages, block_tables, q_tile,
                      window, ring)
    return (tiles,) + rect_metadata(B, T, tiles.q_tile)


def rect_item_map(q_shape, k_pages, block_tables, lengths,
                  q_tile=None, window=None, ring=None) -> ItemMap:
    """The item map :func:`ragged_paged_attention_rect` runs for these
    arguments (``q_shape``: [B, T, H, D]).  It is the same for every layer
    of a dispatch: the layer loop's caller builds it once and hands it to
    each layer's call as ``items``."""
    B, T = q_shape[:2]
    tiles, sot, qot = _rect_layout(q_shape, k_pages, block_tables, q_tile,
                                   window, ring)
    return build_item_map(lengths, jnp.full((B,), T, jnp.int32), sot, qot,
                          tiles, k_pages.shape[-2], window)


def ragged_paged_attention_rect(q, k_pages, v_pages, block_tables, lengths,
                                softmax_scale=None, q_tile=None,
                                interpret=False, layer=None, items=None,
                                window=None, ring=None):
    """Rectangular front-end for the jitted serving path.

    q: [B, T, H, D] — the last T tokens of each sequence (T=1 decode,
    T>1 bucketed/chunked prefill); lengths: [B] int32 valid tokens
    including the T new ones (may be traced — T itself is the static
    shape, so the packed metadata stays host-side).  Same kernel as
    :func:`ragged_paged_attention`; rows past a multiple-of-q_tile pad
    are masked inside the kernel.  With ``layer`` (may be traced) the
    pools are the stacked [L, P, Hkv, page, D] and are read in place.
    ``items``: :func:`rect_item_map` of the same arguments, where the
    caller built it once for all its layers.  ``window`` / ``ring`` as
    :func:`_ragged_call` reads them.
    """
    B, T, H, D = q.shape
    Hkv = k_pages.shape[-3]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    tiles, sot, qot = _rect_layout(q.shape, k_pages, block_tables, q_tile,
                                   window, ring)
    Tp = len(sot) // B * tiles.q_tile
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    q_lens = jnp.full((B,), T, jnp.int32)
    out = _ragged_call(_to_tiles(q.reshape(B * Tp, H, D), len(sot),
                                 tiles.q_tile, Hkv),
                       k_pages, v_pages, block_tables, lengths, q_lens,
                       sot, qot, tiles, scale, interpret,
                       KERNEL_DECODE if T == 1 else KERNEL_PREFILL, layer,
                       items, window, ring)
    return _from_tiles(out, tiles.q_tile).reshape(B, Tp, H, D)[:, :T]


# ---------------------------------------------------------------------------
# the write half: new K and V rows merged into their pages, in place
# ---------------------------------------------------------------------------
KERNEL_KV_WRITE = "paged_kv_write"


class WriteChoice(NamedTuple):
    """Block shapes of one write: ``rows`` rows of a page x ``heads`` kv
    heads a grid step, ``blocks`` row blocks a sequence can touch."""
    rows: int
    heads: int
    blocks: int


def pick_write_blocks(T, n_kv_heads, page_size, head_dim,
                      itemsize) -> WriteChoice:
    """Block shapes for one write, from what the call can see.

    The kernel merges whole tile-aligned row blocks, so a block is a group
    of whole sublane tiles that divides the page: the smallest that holds
    a sequence's ``T`` new rows (a decode step moves 16 rows of bf16 a
    head, not the page), else the page.  ``T`` rows from an arbitrary
    start touch at most ``ceil((T - 1) / rows) + 1`` blocks.  Heads
    shrink until K and V, new, old and merged, double-buffered, fit
    ``VMEM_BUDGET``."""
    sublanes = _sublanes(itemsize)
    rows = next((r for r in range(sublanes, page_size, sublanes)
                 if page_size % r == 0 and r >= T), page_size)
    lanes = -(-head_dim // 128) * 128
    divisors = [h for h in range(n_kv_heads, 0, -1) if n_kv_heads % h == 0]
    heads = next((h for h in divisors
                  if 2 * 6 * h * rows * lanes * itemsize <= VMEM_BUDGET), 1)
    return WriteChoice(rows, heads, -(-(T - 1) // rows) + 1)


def _kv_write_kernel(layer_ref, starts_ref, tables_ref, kn_ref, vn_ref,
                     k_ref, v_ref, ko_ref, vo_ref, *, T, rows):
    """One (sequence, row block, kv-head block): ``out = where(row is new,
    new, old)`` on [heads, rows, D] of K and of V.  Steps past the
    sequence's last touched block repeat it (same indices: no DMA, the
    same merge again)."""
    off = starts_ref[pl.program_id(0)] % rows
    j = jnp.minimum(pl.program_id(1), (off + T - 1) // rows)
    token = j * rows - off + jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0)
    new = ((token >= 0) & (token < T))[None]
    ko_ref[...] = jnp.where(new, kn_ref[...], k_ref[...])
    vo_ref[...] = jnp.where(new, vn_ref[...], v_ref[...])


def paged_kv_write(k_pages, v_pages, layer, block_tables, lengths, k_new,
                   v_new, interpret=False, ring=None):
    """Write each sequence's new rows into layer ``layer`` of the stacked
    pools, touching no other byte of them.

    k_pages/v_pages: [L, P, Hkv, page, D], aliased to the outputs (under a
    jit that donates them, or in a loop's carry, the pools are never
    copied); k_new/v_new: [B, T, Hkv, D], written at positions
    ``lengths[b] + arange(T)`` through ``block_tables`` exactly as
    ``write_paged`` resolves them (columns past the table clamp to its
    last one, the engine's overrun column on the scratch page; with
    ``ring``, static, the table is a ring of that many columns and the
    rows wrap around it).  ``layer`` and ``lengths`` may be traced.

    XLA lines the new rows up with the pool's row blocks (they are small:
    ``[B, blocks, Hkv, rows, D]``), the kernel merges whole blocks chosen
    through the scalar-prefetched layer, starts and tables; it never
    slices at a dynamic sublane offset.  The grid is sequential: idle
    slots and bucket padding all land on the scratch page, whose content
    nobody reads.  Returns (k_pages, v_pages)."""
    _, _, Hkv, page_size, D = k_pages.shape
    B, T = k_new.shape[:2]
    width = block_tables.shape[1]
    rows, heads, blocks = pick_write_blocks(T, Hkv, page_size, D,
                                            k_pages.dtype.itemsize)
    per_page = page_size // rows
    starts = jnp.asarray(lengths, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)

    def aligned(new, pool):
        new = new.astype(pool.dtype)
        if T == 1:      # every row is the one token: no gather
            return jnp.broadcast_to(new[:, :, :, None],
                                    (B, 1, Hkv, rows, D))
        # row r of a sequence's block j holds its token
        # j*rows + r - start%rows (clipped: the kernel masks the rest)
        token = jnp.clip(jnp.arange(blocks * rows)[None, :]
                         - (starts % rows)[:, None], 0, T - 1)
        new = jnp.take_along_axis(new, token[:, :, None, None], axis=1)
        return jnp.swapaxes(new.reshape(B, blocks, rows, Hkv, D), 2, 3)

    def block_of(b, j, st):
        return jnp.minimum(j, (st[b] % rows + T - 1) // rows)

    def new_map(b, j, h, lay, st, tbl):
        return (b, block_of(b, j, st), h, 0, 0)

    def pool_map(b, j, h, lay, st, tbl):
        blk = st[b] // rows + block_of(b, j, st)
        col = blk // per_page
        col = col % ring if ring else jnp.minimum(col, width - 1)
        return (lay[0], tbl[b, col], h, blk % per_page, 0)

    block = (None, None, heads, rows, D)
    pool_spec = pl.BlockSpec(block, pool_map)
    return pl.pallas_call(
        functools.partial(_kv_write_kernel, T=T, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, blocks, Hkv // heads),
            in_specs=[pl.BlockSpec(block, new_map)] * 2 + [pool_spec] * 2,
            out_specs=[pool_spec] * 2,
        ),
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        # operands count the scalar-prefetch ones: 5 and 6 are the pools
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=2 * VMEM_BUDGET),
        interpret=interpret,
        name=KERNEL_KV_WRITE,
    )(lay, starts, tables, aligned(k_new, k_pages), aligned(v_new, v_pages),
      k_pages, v_pages)
