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

**The backward** (the trainer; ``dropless_held_experts`` is a
``custom_vjp``) is two more kernels over the same tiles.  Each row brings
its token's cotangent ``g`` (unweighted, zero on a tile's padding rows)
and its pair's routing weight ``w``:

* :func:`grouped_expert_glu_dx` (``grouped_expert_glu_dx``), grid as the
  forward's: it runs the gate and up products again (nothing of the
  forward is kept but its operands), takes ``g w_down^T``, and writes the
  rows' gradient ``dx``, the routing weight's gradient ``<inner, g
  w_down^T>`` a row, and what the weights' gradients are products of:
  ``dgate``, ``dup`` and ``w * inner``, in the activations' dtype.
* :func:`grouped_expert_glu_dw` (``grouped_expert_glu_dw``), grid =
  (blocks of the intermediate width, tiles that hold pairs): ``x^T dgate``,
  ``x^T dup`` and ``(w inner)^T g`` of a tile, ADDED into its expert's
  block of three float32 accumulators ``[held, ...]`` that alias the
  call's operands: consecutive tiles of one expert keep the block in VMEM,
  an expert nobody chose is never visited and keeps what it had (zeros),
  and a second call (the next chunk of pairs) adds on.

:func:`grouped_glu_dx_jnp` / :func:`grouped_glu_dw_jnp` are their jnp
cousins.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "grouped_expert_glu"
# the backward's two kernels (docs/telemetry.md): the names a trace shows
KERNEL_NAME_DX = "grouped_expert_glu_dx"
KERNEL_NAME_DW = "grouped_expert_glu_dw"
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


def _tile_experts(tile_expert, n, base):
    """The experts of the ``n`` tiles from ``base`` on."""
    at = jnp.minimum(base + jnp.arange(n), tile_expert.shape[0] - 1)
    return tile_expert[at]


def grouped_glu_jnp(x, w_gate, w_up, w_down, tile_expert, live, act,
                    tiles: ExpertTiles, layer=None, base=0, interpret=False):
    """:func:`grouped_expert_glu` in plain jnp, every tile computed (a
    tile past ``live`` with the last expert's weights): each tile's
    expert gathered out of the leaves, float32 accumulation."""
    del live, interpret
    R, d = x.shape
    n = R // tiles.rows
    expert = _tile_experts(tile_expert, n, base)

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


# ----------------------------------------------------------------------
# the backward
# ----------------------------------------------------------------------
def _t(a, b, contract):
    """``a`` times ``b`` over ``contract`` = (axis of a, axis of b),
    float32 accumulation."""
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def backward_cols(rows, d, f, itemsize):
    """(columns a step of the dx kernel, of the dw kernel): the widest
    blocks of ``f`` whose double-buffered operands fit ``VMEM_BUDGET``."""
    widths = [f] + [c for c in range((f - 1) // 128 * 128, 0, -128)
                    if f % c == 0]

    def dx_bytes(cols):
        blocks = (3 * d * cols + 3 * rows * d + 3 * rows * cols) * itemsize \
            + 2 * rows * 128 * 4
        return 2 * blocks + rows * d * 4 + 8 * rows * cols * 4

    def dw_bytes(cols):
        blocks = (2 * rows * d + 3 * rows * cols) * itemsize
        return 2 * blocks + 4 * 3 * d * cols * 4

    pick = lambda need: next(      # noqa: E731
        (c for c in widths if need(c) <= VMEM_BUDGET), widths[-1])
    return pick(dx_bytes), pick(dw_bytes)


def _glu_dx_kernel(te_ref, at_ref, x_ref, g_ref, w_ref, wg_ref, wu_ref,
                   wd_ref, dx_ref, dgate_ref, dup_ref, inner_ref, dw_ref,
                   acc_ref, dw_acc_ref, *, act):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        dw_acc_ref[...] = jnp.zeros_like(dw_acc_ref)

    x, g = x_ref[...], g_ref[...]
    w = w_ref[...][:, :1]
    gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    a, pull = jax.vjp(act, gate)
    inner = a * up
    d_inner = _t(g, wd_ref[...], (1, 1))         # unweighted: [rows, cols]
    dw_acc_ref[...] += jnp.sum(inner * d_inner, axis=1, keepdims=True)
    d_inner = d_inner * w
    d_gate = pull(d_inner * up)[0].astype(x.dtype)
    d_up = (d_inner * a).astype(x.dtype)
    dgate_ref[...] = d_gate
    dup_ref[...] = d_up
    inner_ref[...] = (inner * w).astype(x.dtype)
    acc_ref[...] += _t(d_gate, wg_ref[...], (1, 1)) \
        + _t(d_up, wu_ref[...], (1, 1))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)
        dw_ref[...] = jnp.broadcast_to(dw_acc_ref[...], dw_ref.shape)


def grouped_expert_glu_dx(x, g, w, w_gate, w_up, w_down, tile_expert, live,
                          act, rows, cols, layer=None, base=0,
                          interpret=False):
    """The rows' side of the backward.  x, g: [R, d] (a tile's padding
    rows have ``g`` zero); w: [R] float32, each row's routing weight; the
    rest as :func:`grouped_expert_glu`.  Returns ``(dx [R, d], dgate, dup,
    w * inner [R, f], dw [R] float32)``, the first four in x's dtype; rows
    of tiles past ``live`` are undefined."""
    R, d = x.shape
    (w_gate, lay), (w_up, _), (w_down, _) = (
        _stacked(v, layer) for v in (w_gate, w_up, w_down))
    f = w_up.shape[-1]
    assert R % rows == 0 and f % cols == 0, (x.shape, w_up.shape, rows, cols)
    at = jnp.stack([jnp.asarray(lay, jnp.int32),
                    jnp.asarray(base, jnp.int32)])
    row_map = lambda i, j, te, at: (i, 0)                        # noqa: E731
    col_map = lambda i, j, te, at: (i, j)                        # noqa: E731
    up_map = lambda i, j, te, at: (at[0], te[at[1] + i], 0, j)   # noqa: E731
    down_map = lambda i, j, te, at: (at[0], te[at[1] + i], j, 0)  # noqa: E731
    wide = jax.ShapeDtypeStruct((R, f), x.dtype)
    dx, dgate, dup, inner, dw = pl.pallas_call(
        functools.partial(_glu_dx_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(live, f // cols),
            in_specs=[pl.BlockSpec((rows, d), row_map),
                      pl.BlockSpec((rows, d), row_map),
                      pl.BlockSpec((rows, 128), row_map),
                      pl.BlockSpec((None, None, d, cols), up_map),
                      pl.BlockSpec((None, None, d, cols), up_map),
                      pl.BlockSpec((None, None, cols, d), down_map)],
            out_specs=[pl.BlockSpec((rows, d), row_map),
                       pl.BlockSpec((rows, cols), col_map),
                       pl.BlockSpec((rows, cols), col_map),
                       pl.BlockSpec((rows, cols), col_map),
                       pl.BlockSpec((rows, 128), row_map)],
            scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), wide, wide, wide,
                   jax.ShapeDtypeStruct((R, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET * 3 // 2),
        interpret=interpret,
        name=KERNEL_NAME_DX,
    )(jnp.asarray(tile_expert, jnp.int32), at, x, g,
      jnp.broadcast_to(w.astype(jnp.float32)[:, None], (R, 128)),
      w_gate, w_up, w_down)
    return dx, dgate, dup, inner, dw[:, 0]


def _glu_dw_kernel(te_ref, at_ref, x_ref, g_ref, dgate_ref, dup_ref,
                   inner_ref, ig_ref, iu_ref, id_ref, og_ref, ou_ref, od_ref):
    i = pl.program_id(1)
    here = at_ref[0] + i
    first = (i == 0) | (te_ref[here] != te_ref[jnp.maximum(here - 1, 0)])

    @pl.when(first)
    def _():        # the expert's block as the call found it
        og_ref[...] = ig_ref[...]
        ou_ref[...] = iu_ref[...]
        od_ref[...] = id_ref[...]

    x = x_ref[...]
    og_ref[...] += _t(x, dgate_ref[...], (0, 0))
    ou_ref[...] += _t(x, dup_ref[...], (0, 0))
    od_ref[...] += _t(inner_ref[...], g_ref[...], (0, 0))


def grouped_expert_glu_dw(x, g, dgate, dup, inner, acc, tile_expert, live,
                          rows, cols, base=0, interpret=False):
    """The weights' side: ``acc`` = three float32 accumulators ``(gate
    [held, d, f], up [held, d, f], down [held, f, d])``, returned with
    every live tile's ``x^T dgate``, ``x^T dup`` and ``inner^T g`` added
    into its expert's; the other experts' are left as they were."""
    R, d = x.shape
    f = dgate.shape[1]
    assert R % rows == 0 and f % cols == 0, (x.shape, dgate.shape, rows, cols)
    at = jnp.asarray(base, jnp.int32).reshape(1)
    row_map = lambda j, i, te, at: (i, 0)                       # noqa: E731
    col_map = lambda j, i, te, at: (i, j)                       # noqa: E731
    up_map = lambda j, i, te, at: (te[at[0] + i], 0, j)         # noqa: E731
    down_map = lambda j, i, te, at: (te[at[0] + i], j, 0)       # noqa: E731
    blocks = [pl.BlockSpec((None, d, cols), up_map),
              pl.BlockSpec((None, d, cols), up_map),
              pl.BlockSpec((None, cols, d), down_map)]
    return tuple(pl.pallas_call(
        _glu_dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(f // cols, live),
            in_specs=[pl.BlockSpec((rows, d), row_map),
                      pl.BlockSpec((rows, d), row_map),
                      pl.BlockSpec((rows, cols), col_map),
                      pl.BlockSpec((rows, cols), col_map),
                      pl.BlockSpec((rows, cols), col_map)] + blocks,
            out_specs=blocks,
        ),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in acc],
        # operand 7.. (after the two scalar tables) are the accumulators
        input_output_aliases={7: 0, 8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET * 3 // 2),
        interpret=interpret,
        name=KERNEL_NAME_DW,
    )(jnp.asarray(tile_expert, jnp.int32), at, x, g, dgate, dup, inner,
      *acc))


def grouped_glu_dx_jnp(x, g, w, w_gate, w_up, w_down, tile_expert, live, act,
                       rows, cols, layer=None, base=0, interpret=False):
    """:func:`grouped_expert_glu_dx` in plain jnp, every tile computed."""
    del live, cols, interpret
    R, d = x.shape
    n = R // rows
    expert = _tile_experts(tile_expert, n, base)

    def of(v):
        v, lay = _stacked(v, layer)
        return v[lay, expert]

    product = functools.partial(jnp.einsum,
                                preferred_element_type=jnp.float32)
    xt, gt = x.reshape(n, rows, d), g.reshape(n, rows, d)
    wt = w.astype(jnp.float32).reshape(n, rows, 1)
    gate = product("trd,tdf->trf", xt, of(w_gate))
    up = product("trd,tdf->trf", xt, of(w_up))
    a, pull = jax.vjp(act, gate)
    inner = a * up
    d_inner = product("trd,tfd->trf", gt, of(w_down))
    dw = jnp.sum(inner * d_inner, axis=-1).reshape(R)
    d_inner = d_inner * wt
    d_gate = pull(d_inner * up)[0].astype(x.dtype)
    d_up = (d_inner * a).astype(x.dtype)
    dx = product("trf,tdf->trd", d_gate, of(w_gate)) \
        + product("trf,tdf->trd", d_up, of(w_up))
    flat = lambda v: v.reshape(R, -1)     # noqa: E731
    return (flat(dx.astype(x.dtype)), flat(d_gate), flat(d_up),
            flat((inner * wt).astype(x.dtype)), dw)


def grouped_glu_dw_jnp(x, g, dgate, dup, inner, acc, tile_expert, live, rows,
                       cols, base=0, interpret=False):
    """:func:`grouped_expert_glu_dw` in plain jnp: each live tile's three
    products, summed into their experts' accumulators."""
    del cols, interpret
    R, d = x.shape
    n = R // rows
    held = acc[0].shape[0]
    expert = _tile_experts(tile_expert, n, base)
    # a tile past the live ones adds to nobody
    onto = ((expert[:, None] == jnp.arange(held)[None, :])
            & (jnp.arange(n) < live)[:, None]).astype(jnp.float32)
    tiled = lambda v: v.reshape(n, rows, -1)     # noqa: E731
    product = functools.partial(jnp.einsum,
                                preferred_element_type=jnp.float32)

    def summed(a, b):
        # the tiles' float32 products go to their experts whole: at the
        # TPU's default precision this sum would round them to bf16
        return jnp.einsum("te,tab->eab",
                          onto, product("tra,trb->tab", tiled(a), tiled(b)),
                          precision=jax.lax.Precision.HIGHEST)

    return (acc[0] + summed(x, dgate), acc[1] + summed(x, dup),
            acc[2] + summed(inner, g))
