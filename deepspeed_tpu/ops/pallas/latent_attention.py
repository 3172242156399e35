"""Dense latent prefill: ONE Pallas running-softmax kernel over the pool.

A prefill chunk of a latent-attention model WITHOUT a selection
(DeepSeek-V3 / Kimi-K2; ``models/transformer.py`` ``mix_latent_dense``)
attends over what its sequence already holds in the pool and causally
over itself.  In XLA (``ops/latent_attention.py`` ``context_attention`` +
``prefill_attention``, which stay as the oracle and as the path of every
other caller) each [queries, keys] score tile of all heads goes through
HBM three to four times (written; read for the running max; read for
``exp`` and the sum, ``p`` written; ``p`` read for the value product):
that traffic, not the products, was the cost of a chunk over its cached
context (PERF.md section 5, PR 41 and PR 45).  Here the score tile, ``p``,
the running max / sum and the accumulator never leave VMEM:

* **One walk, one normalisation.**  The chunk's entries are in the pool
  when the attention runs (``write_latent`` goes first), so the kernel
  walks the sequence's pages through the block table from entry 0 to the
  chunk's last real row: the cached entries and the chunk itself are the
  SAME loop, masked by position (key ``s`` is seen by the query at
  position ``p`` iff ``s <= p``), and end in one division.  The state
  (largest score, sum of weights, weighted values: what ``_fold`` carries)
  lives in VMEM scratch across the key axis of the grid.
* **A block of keys is decompressed ONCE a head, in VMEM.**  grid =
  (sequences, head blocks, blocks of query rows, key steps).  A step
  brings ``pages`` pages of ``[c_kv | k_rope | zeros]`` rows and the head
  block's slice ``[R, heads * (dn + dv)]`` of ``w_kvb``, forms ``[k_nope |
  v] = c_kv w_kvb`` for its ``pages * page_size`` keys (bf16 operands,
  float32 accumulation, rounded to the activations' dtype, as the
  einsum of the XLA path) and then runs EVERY tile of ``q_tile`` query
  rows of its block over them, so the products of the decompression are
  those of the XLA walk (once a block and head) and neither a
  decompressed context nor a [T, S] tensor exists anywhere.
* **Key width != value width, and no [keys, H, 192].**  A score is two
  products: ``q_nope . k_nope`` over ``dn`` and the rotary part over the
  row's TAIL ``[k_rope | zeros]`` (``row - R`` lanes, one operand shared
  by all heads), the query's rotary part padded with zeros to the same
  width by the caller, so every slice of a row is whole lane tiles.  The
  values are ``dv`` wide.  All of it is read from the call's shapes.
* **The grid follows the contexts.**  The number of key steps is a traced
  value (a dynamic grid bound, as the ragged kernel's): as far as the
  longest sequence of the call reaches.  A tile of queries runs a step
  only while the step's first key lies at or before its last real row
  (``lax.fori_loop`` with traced bounds over the tiles: the upper triangle
  is never computed), a shorter sequence's steps past its frontier do
  nothing and fetch nothing new (their page index clamps to the last page
  under the frontier), a tile with no real row (a bucket's padding) never
  runs and comes out as zeros.
* **The arithmetic is ``_fold``'s**: scores in float32 times ``scale``,
  masked to -1e30, ``p`` cast to the values' dtype for the value product,
  float32 accumulation.

Block shapes come from the call's shapes (:func:`pick_latent_tiles`,
pure).  ``interpret=True`` runs the kernel on the CPU (tier-1).
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.latent_attention import LANES

# the name on the device's lines (docs/telemetry.md)
KERNEL_NAME = "latent_attention_prefill"
_NEG = -1e30
Q_TILE = 512            # query rows of one score tile
MAX_ROWS = 2048         # query rows a grid step holds (a chunk of the cell)
# keys a step decompresses and folds: measured on the v5e at Kimi-K2's
# widths, 2,048 rows over 2,048 cached entries of one layer cost 3.90 /
# 2.33 / 1.63 / 1.63 ms at 256 / 512 / 1,024 / 2,048 keys a step (PERF.md
# section 6, PR 45): a tile's rescaling of its accumulator and the drain
# between two tiles are paid once a (tile, step)
TARGET_KEYS = 1024
MAX_PAGES_PER_STEP = 8
# a step's blocks, scratch and temporaries by ``LatentTiles``'s own count;
# the compiler is given twice that (the v5e has 128 MiB of VMEM)
VMEM_BUDGET = 32 * 2 ** 20


class LatentTiles(NamedTuple):
    """Block shapes of one call: a grid step holds ``rows`` query rows of
    one sequence (tiles of ``q_tile`` at a time) for ``heads`` heads and
    ``pages`` pages of keys."""
    q_tile: int
    rows: int
    heads: int
    pages: int
    vmem_bytes: int


def _lanes(n):
    return -(-n // LANES) * LANES


def _step_vmem_bytes(q_tile, rows, heads, keys, rank, row, dn, dv, itemsize):
    """VMEM one grid step holds: the double-buffered query (nope and
    tail), output, page and weight blocks, the float32 scratch (max and
    sum padded to a lane tile), the decompressed block in float32 and in
    the operands' dtype, and three score-sized temporaries."""
    tail = row - rank
    blocks = rows * heads * (_lanes(dn) + _lanes(tail) + _lanes(dv)) \
        + keys * row + rank * _lanes(heads * (dn + dv))
    return (2 * blocks * itemsize
            + heads * rows * (_lanes(dv) + 2 * LANES) * 4
            + keys * _lanes(heads * (dn + dv)) * (4 + itemsize)
            + 3 * q_tile * max(keys, LANES) * 4)


def pick_latent_tiles(T, n_heads, dn, dv, rank, row, page_size, table_width,
                      itemsize) -> LatentTiles:
    """Block shapes for one call, from what the call can see: tiles of up
    to ``Q_TILE`` query rows (whole sublane tiles of bf16), up to
    ``MAX_ROWS`` rows a grid step, so that a chunk's rows all meet a block
    of keys decompressed once; the fewest heads a step whose blocks are
    whole lane tiles (all of them where no fewer are: a toy's widths);
    pages up to ``TARGET_KEYS`` keys, fewer where ``VMEM_BUDGET`` says."""
    q_tile = min(Q_TILE, -(-T // 16) * 16)
    rows = min(-(-T // q_tile), MAX_ROWS // q_tile) * q_tile
    tail = row - rank
    heads = next((h for h in range(1, n_heads) if n_heads % h == 0 and all(
        (h * w) % LANES == 0 for w in (dn, tail, dv))), n_heads)
    pages = max(1, min(table_width, MAX_PAGES_PER_STEP,
                       TARGET_KEYS // page_size))

    def vmem(pages):
        return _step_vmem_bytes(q_tile, rows, heads, pages * page_size,
                                rank, row, dn, dv, itemsize)

    while pages > 1 and vmem(pages) > VMEM_BUDGET:
        pages -= 1
    return LatentTiles(q_tile, rows, heads, pages, vmem(pages))


def _tile_frontier(cached, real, block, rows):
    """(position of the block of queries' first row, keys it may see:
    positions below it) of block ``block`` of ``rows`` rows of a sequence
    that holds ``cached`` entries before its ``real`` new ones; nothing
    where the block holds no real row."""
    first = cached + block * rows
    live = jnp.clip(real - block * rows, 0, rows)
    return first, jnp.where(live > 0, first + live, 0)


def _latent_prefill_kernel(len_ref, real_ref, steps_ref, tables_ref,
                           layer_ref, qn_ref, qt_ref, w_ref, *refs, scale,
                           page_size, q_tile, pages, heads, dn, dv, rank):
    """One (sequence, head block, block of query rows, key step).

    qn_ref: [1, rows, heads * dn]; qt_ref: [1, rows, heads * tail] (the
    rotary part, zero-padded to the row's tail); w_ref: [R, heads * (dn +
    dv)]; then ``pages`` refs of [page, row] (the pages the index maps
    resolved through the block table); o_ref: [1, rows, heads * dv];
    scratch acc / m / l a head and row, kept across the key steps."""
    page_refs = refs[:pages]
    o_ref, acc_ref, m_ref, l_ref = refs[pages:]
    b, block, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    rows = qn_ref.shape[1]
    tail = qt_ref.shape[2] // heads
    keys = pages * page_size
    first, frontier = _tile_frontier(len_ref[b], real_ref[b], block, rows)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * keys < frontier)
    def _compute():
        entries = page_refs[0][...] if pages == 1 else jnp.concatenate(
            [r[...] for r in page_refs], axis=0)             # [keys, row]
        k_tail = entries[:, rank:]
        kv = jnp.dot(entries[:, :rank], w_ref[...],
                     preferred_element_type=jnp.float32).astype(qn_ref.dtype)
        # the tiles of queries whose last row lies at or past the step's
        # first key, as far as the real rows reach
        lo = jnp.maximum(j * keys - first, 0) // q_tile
        hi = pl.cdiv(frontier - first, q_tile)
        nt = (((1,), (1,)), ((), ()))
        for h in range(heads):
            k_nope = kv[:, h * (dn + dv):h * (dn + dv) + dn]
            v = kv[:, h * (dn + dv) + dn:(h + 1) * (dn + dv)]

            def fold(i, carry, h=h, k_nope=k_nope, v=v):
                at = pl.ds(pl.multiple_of(i * q_tile, q_tile), q_tile)
                s = (jax.lax.dot_general(
                    qn_ref[0, at, h * dn:(h + 1) * dn], k_nope, nt,
                    preferred_element_type=jnp.float32)
                    + jax.lax.dot_general(
                        qt_ref[0, at, h * tail:(h + 1) * tail], k_tail, nt,
                        preferred_element_type=jnp.float32)) * scale
                # a row sees the keys at or before its position, and none
                # past the sequence's last real row
                last = jnp.minimum(
                    first + i * q_tile + jax.lax.broadcasted_iota(
                        jnp.int32, (q_tile, 1), 0), frontier - 1)
                kpos = j * keys + jax.lax.broadcasted_iota(
                    jnp.int32, (q_tile, keys), 1)
                s = jnp.where(kpos <= last, s, _NEG)
                # every row a tile runs has met key 0 in step 0, so a
                # step that masks a whole row scales its exp(0)s to nothing
                m_prev = m_ref[h, at, :]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1,
                                                    keepdims=True))
                p = jnp.exp(s - m_new)
                keep = jnp.exp(m_prev - m_new)
                m_ref[h, at, :] = m_new
                l_ref[h, at, :] = l_ref[h, at, :] * keep + jnp.sum(
                    p, axis=-1, keepdims=True)
                acc_ref[h, at, :] = acc_ref[h, at, :] * keep + jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)
                return carry

            jax.lax.fori_loop(lo, hi, fold, 0)

    @pl.when(j + 1 == steps_ref[0])
    def _finalize():
        for h in range(heads):
            o_ref[0, :, h * dv:(h + 1) * dv] = (
                acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            ).astype(o_ref.dtype)


def latent_prefill_attention(q_nope, q_rope, latent_pages, layer,
                             block_tables, lengths, w_kvb, scale,
                             real_lengths=None, tiles: LatentTiles = None,
                             interpret=False):
    """Attention of T new tokens a sequence over EVERY entry of their
    context: the ``lengths`` [B] entries the pool held before them and,
    causally, themselves — which ``write_latent`` has already put into the
    pool at ``lengths`` on (the kernel reads keys from the pool alone).

    q_nope: [B, T, H, dn]; q_rope: [B, T, H, dr]; latent_pages: the
    STACKED pool [L, P, page, row] of ``[c_kv (R) | k_rope (dr) | zeros]``
    rows, ``layer`` (may be traced) the one to read; block_tables: [B,
    W]; w_kvb: [R, H * (dn + dv)] (a head's ``[k_nope | v]`` columns side
    by side, as ``wkv_b`` is stored); ``real_lengths`` [B] (T, without
    it): the first rows of each sequence that are tokens; the others see
    what a token at their position would and a whole tile of them comes
    out as zeros: the caller reads none.  Returns [B, T, H, dv]."""
    B, T, H, dn = q_nope.shape
    dr = q_rope.shape[-1]
    page_size, row = latent_pages.shape[2:]
    rank = w_kvb.shape[0]
    dv = w_kvb.shape[-1] // H - dn
    tail = row - rank
    assert tail >= dr and w_kvb.shape[-1] == H * (dn + dv), (
        latent_pages.shape, w_kvb.shape, q_rope.shape)
    width = block_tables.shape[1]
    if tiles is None:
        tiles = pick_latent_tiles(T, H, dn, dv, rank, row, page_size, width,
                                  q_nope.dtype.itemsize)
    q_tile, rows, heads, pages = tiles[:4]
    keys = pages * page_size
    padded = -(-T // rows) * rows

    def flat(x, lanes):
        x = jnp.pad(x, ((0, 0), (0, padded - T), (0, 0),
                        (0, lanes - x.shape[-1])))
        return x.reshape(B, padded, H * lanes)

    lengths = jnp.asarray(lengths, jnp.int32)
    real = jnp.full((B,), T, jnp.int32) if real_lengths is None \
        else jnp.asarray(real_lengths, jnp.int32)
    tables = jnp.asarray(block_tables, jnp.int32)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    # as far as the longest sequence's last real row; one step at least,
    # which writes the zeros of a call with no real row at all
    steps = jnp.maximum(pl.cdiv(jnp.max(jnp.where(
        real > 0, lengths + real, 0)), keys), 1).astype(jnp.int32).reshape(1)

    def q_map(b, h, block, j, lens, real, steps, tbl, lay):
        return (b, block, h)

    def w_map(b, h, block, j, lens, real, steps, tbl, lay):
        return (0, h)

    def page_map(p, b, h, block, j, lens, real, steps, tbl, lay):
        # the step's p-th operand is page j * pages + p of the sequence,
        # clamped to the last page under the block of queries' frontier
        _, frontier = _tile_frontier(lens[b], real[b], block, rows)
        last = jnp.maximum(pl.cdiv(frontier, page_size) - 1, 0)
        col = jnp.minimum(jnp.minimum(j * pages + p, last), width - 1)
        return (lay[0], tbl[b, col], 0, 0)

    kernel = functools.partial(
        _latent_prefill_kernel, scale=scale, page_size=page_size,
        q_tile=q_tile, pages=pages, heads=heads, dn=dn, dv=dv, rank=rank)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            # the key steps' bound is traced: one program whatever the
            # contexts hold
            grid=(B, H // heads, padded // rows, steps[0]),
            in_specs=[
                pl.BlockSpec((1, rows, heads * dn), q_map),
                pl.BlockSpec((1, rows, heads * tail), q_map),
                pl.BlockSpec((rank, heads * (dn + dv)), w_map),
            ] + [pl.BlockSpec((None, None, page_size, row),
                              functools.partial(page_map, p))
                 for p in range(pages)],
            out_specs=pl.BlockSpec((1, rows, heads * dv), q_map),
            scratch_shapes=[
                pltpu.VMEM((heads, rows, dv), jnp.float32),
                pltpu.VMEM((heads, rows, 1), jnp.float32),
                pltpu.VMEM((heads, rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, padded, H * dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=2 * VMEM_BUDGET),
        interpret=interpret,
        name=KERNEL_NAME,
    )(lengths, real, steps, tables, lay, flat(q_nope, dn),
      flat(q_rope.astype(q_nope.dtype), tail), w_kvb,
      *([latent_pages] * pages))
    return out[:, :T].reshape(B, T, H, dv)
