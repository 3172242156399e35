"""Grouped expert GLU: ONE Pallas kernel for a chip's held experts.

The dropless expert layer (``moe/sharded_moe.py:dropless_held_experts``)
sorts its (token, expert) pairs by expert and lays them out in row tiles,
each expert's group starting on a tile boundary, so a tile has ONE
expert.  This kernel computes ``(act(x w_gate) * (x w_up)) w_down`` of
every tile that holds pairs:

* **The weights are read in place.**  ``w_gate | w_up | w_down`` are the
  stacked leaves ``[held, ...]`` of a listed layer or ``[periods, held,
  ...]`` of a scanned one; the tile's expert (``tile_expert``) and the
  layer ride in scalar memory and the BlockSpec index maps pick
  ``[(layer,) expert]`` from them.  No expert is ever sliced out of a
  leaf in XLA, so there is nothing for the compiler to lift out of a
  loop and copy, and an expert nobody chose costs no weight bytes.
* **The grid follows the pairs.**  grid = (tiles that hold pairs, blocks
  of the intermediate width); the first is a traced value (a dynamic
  grid bound, as the ragged attention kernel's), so one compiled program
  runs as many tiles as the routing filled.  Rows of the other tiles are
  left unwritten: the caller reads none of them.
* **Block shapes come from the call's shapes** (:func:`pick_expert_tiles`,
  pure): 16 rows for a decode batch, where a step is bound by its
  expert's bytes, up to ``MAX_ROW_TILE`` for a long prefill, where a tile
  that re-reads its expert brings the rows that balance its products
  with its bytes (256 FLOP a byte; the v5e's ridge is 240) and larger
  tiles only pad more; the intermediate width in the largest blocks
  ``VMEM_BUDGET`` holds (a whole expert where it fits: consecutive tiles
  of one expert then read its weights once).
* **The arithmetic**: operands in the activations' dtype (bf16 serving:
  bf16 operands), float32 accumulation in all three products, the
  activation and the gate-up product in float32, the down product's
  terms summed in float32 across the blocks (VMEM scratch) and rounded
  once to the activations' dtype.

:func:`grouped_glu_jnp` is the same product in plain jnp (a gather of each
tile's expert, two einsums), for processes without a TPU.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "grouped_expert_glu"
MIN_ROW_TILE = 16       # a bf16 sublane tile
MAX_ROW_TILE = 256
# double-buffered blocks, the float32 accumulator and the three
# intermediate-width temporaries by ``ExpertTiles``'s own count; the
# compiler is given half as much again (the v5e has 128 MiB of VMEM)
VMEM_BUDGET = 64 * 2 ** 20


class ExpertTiles(NamedTuple):
    """Block shapes of one call: ``rows`` rows of one expert by ``cols``
    of its intermediate width a grid step."""
    rows: int
    cols: int
    vmem_bytes: int


def _step_vmem_bytes(rows, cols, d, itemsize):
    blocks = 3 * d * cols * itemsize + 2 * rows * d * itemsize
    return 2 * blocks + rows * d * 4 + 3 * rows * cols * 4


def pick_expert_tiles(n_rows, held, d, f, itemsize, rows=None):
    """The row tile and the intermediate-width block of a call over
    ``n_rows`` tokens and ``held`` experts of ``[d, f]``: half the rows
    an expert expects where every token brings one pair, so that the half
    tile of padding an expert ends in is a quarter of its rows (a power
    of two between ``MIN_ROW_TILE`` and ``MAX_ROW_TILE``; ``rows``
    overrides), and the widest block of ``f`` (all of it, or a multiple
    of 128 that divides it) that ``VMEM_BUDGET`` holds."""
    if rows is None:
        rows = MIN_ROW_TILE
        while rows < MAX_ROW_TILE and 2 * rows * held < n_rows:
            rows *= 2
    widths = [f] + [c for c in range((f - 1) // 128 * 128, 0, -128)
                    if f % c == 0]
    for cols in widths:
        need = _step_vmem_bytes(rows, cols, d, itemsize)
        if need <= VMEM_BUDGET:
            break
    return ExpertTiles(rows, cols, need)


def _glu_kernel(te_ref, at_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
                acc_ref, *, act):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    inner = (act(gate) * up).astype(x.dtype)
    acc_ref[...] += jnp.dot(inner, wd_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _stacked(w, layer):
    """A leaf as a stack of layers and the layer to read in it."""
    return (w[None], 0) if w.ndim == 3 else (w, layer)


def grouped_expert_glu(x, w_gate, w_up, w_down, tile_expert, live, act,
                       tiles: ExpertTiles, layer=None, base=0,
                       interpret=False):
    """x: [R, d], rows in tiles of ``tiles.rows``; tile ``i`` belongs to
    expert ``tile_expert[base + i]`` and the first ``live`` tiles hold
    pairs (``live``, ``base`` and ``layer`` may be traced; ``live`` >= 1).
    The weights: ``[held, d, f]`` / ``[held, f, d]``, or a stack ``[L,
    held, ...]`` with ``layer`` the one to read.  Returns [R, d] in x's
    dtype; rows of tiles past ``live`` are undefined."""
    R, d = x.shape
    rows, cols = tiles.rows, tiles.cols
    (w_gate, lay), (w_up, _), (w_down, _) = (
        _stacked(w, layer) for w in (w_gate, w_up, w_down))
    f = w_up.shape[-1]
    assert R % rows == 0 and f % cols == 0, (x.shape, w_up.shape, tiles)
    at = jnp.stack([jnp.asarray(lay, jnp.int32),
                    jnp.asarray(base, jnp.int32)])

    def row_map(i, j, te, at):
        return (i, 0)

    def up_map(i, j, te, at):
        return (at[0], te[at[1] + i], 0, j)

    def down_map(i, j, te, at):
        return (at[0], te[at[1] + i], j, 0)

    return pl.pallas_call(
        functools.partial(_glu_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # the tile bound is traced: one program whatever the routing
            grid=(live, f // cols),
            in_specs=[pl.BlockSpec((rows, d), row_map),
                      pl.BlockSpec((None, None, d, cols), up_map),
                      pl.BlockSpec((None, None, d, cols), up_map),
                      pl.BlockSpec((None, None, cols, d), down_map)],
            out_specs=pl.BlockSpec((rows, d), row_map),
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET * 3 // 2),
        interpret=interpret,
        name=KERNEL_NAME,
    )(jnp.asarray(tile_expert, jnp.int32), at, x, w_gate, w_up, w_down)


def grouped_glu_jnp(x, w_gate, w_up, w_down, tile_expert, live, act,
                    tiles: ExpertTiles, layer=None, base=0, interpret=False):
    """:func:`grouped_expert_glu` in plain jnp, every tile computed (a
    tile past ``live`` with the last expert's weights): each tile's
    expert gathered out of the leaves, float32 accumulation."""
    del live, interpret
    R, d = x.shape
    n = R // tiles.rows
    at = jnp.minimum(base + jnp.arange(n), tile_expert.shape[0] - 1)
    expert = tile_expert[at]

    def of(w):
        w, lay = _stacked(w, layer)
        return w[lay, expert]

    xt = x.reshape(n, tiles.rows, d)
    product = functools.partial(jnp.einsum,
                                preferred_element_type=jnp.float32)
    inner = (act(product("trd,tdf->trf", xt, of(w_gate)))
             * product("trd,tdf->trf", xt, of(w_up))).astype(x.dtype)
    return product("trf,tfd->trd", inner, of(w_down)).astype(
        x.dtype).reshape(R, d)
