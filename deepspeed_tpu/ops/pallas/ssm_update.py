"""The one-row Mamba-2 update of a decode dispatch, in place in the state pool.

One state-space layer of a decode step does, for every slot ``b`` and head
``h`` (``ops/ssm.py``: ``ssm_step`` and the masked write after it):

    S' = exp(dt A_h) S + (dt x) (outer) B
    y  = S' C + D_h x

on a state ``S`` [P, N] float32 that is 32 KB a head and 2 MB a slot at
granite-4.0-h-micro's widths, 134 MB a layer of 64 slots: nothing of the
step is larger, and nothing of it is reused.  ``ssm_decode_update`` reads
each block of the STACKED pool ``[L, slots, H, P, N]`` into VMEM once,
forms ``S'`` and ``y`` from the block it holds and writes ``S'`` back to
where it came from: the pool operand is aliased to the output
(``paged_kv_write`` is the pattern, the layer's traced index a
scalar-prefetch operand that the index maps read), so the state crosses
HBM once in and once out and a dispatch never copies, slices or re-lays
the pool.

* **Grid** (slot, block of heads), a step ``heads`` whole heads of one
  slot (:func:`pick_state_tiles`, from the shapes alone).  ``N`` is the
  lane dimension and ``P`` the sublanes, as the pool lies.
* **A slot the dispatch does not serve** (``live`` 0) has its block
  written back as read, bit for bit, and ``y`` 0.
* **What varies along a head's rows comes in with the rows on the
  sublanes**: ``dt x`` as ``[B, H / heads, P, lanes]`` (XLA's transpose of
  a megabyte, the heads filled up to whole vregs of 128 lanes), a head
  its lane column.  The decay ``exp(dt A)`` is a scalar a head, read from
  scalar memory; ``B`` and ``C`` are a row of ``N`` lanes a group.
* **The heads go eight a turn of a loop**, not unrolled: a turn takes its
  heads' columns from lanes 0-7 of ``dt x``, rolls the rest down eight
  lanes for the next and stores its eight rows of ``y`` as one tile.
  Unrolled over 64 heads the kernel took 0.4-0.5 s to trace and lower at
  every place it is compiled, which ``setup_s`` paid for (PERF.md, PR 48).
* **``y``'s sum over the ``N`` lanes is the MXU's**: ``C [8, N]`` times a
  head's ``S' [P, N]`` transposed, at float32 precision
  (``Precision.HIGHEST``) with float32 accumulation, which leaves ``y``
  with ``P`` on the lanes, as it goes out.  One sum a vreg of state on
  the vector unit set the kernel's time (0.50 ms a layer of 64 slots at
  granite's widths where the blocks' copy alone takes 0.43); on the MXU
  it hides under the copy (0.435; PERF.md, PR 48).
* **Precision** is ``ssm_step``'s: the state float32 in HBM and in VMEM,
  the decay, the outer product and ``y``'s sum float32.  The caller adds
  ``D x`` and casts.

``ops/ssm.py state_decode_update`` is the entry point and the jnp oracle;
``interpret=True`` runs this kernel on the CPU.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_SSM_UPDATE = "ssm_decode_update"

# the state a grid step holds: under about a megabyte the step's fixed
# cost is paid on every megabyte, and a slot of granite's is two
STATE_BLOCK_BYTES = 2 * 2 ** 20


class StateTiles(NamedTuple):
    """``heads`` heads of one slot a grid step; ``vmem_bytes`` what the
    call asks the compiler for: the block in and out, double-buffered, as
    much again for the step's temporaries, and the small operands."""
    heads: int
    vmem_bytes: int


def pick_state_tiles(n_heads, head_dim, state) -> StateTiles:
    """Block shape for one update, from what the call can see: the most
    whole heads of a slot (a divisor of ``n_heads``) whose float32 state
    fits ``STATE_BLOCK_BYTES`` as VMEM holds it (rows in eights, lanes in
    128s), one head where none does."""
    head_bytes = -(-head_dim // 8) * 8 * -(-state // 128) * 128 * 4
    heads = next((h for h in range(n_heads, 0, -1)
                  if n_heads % h == 0
                  and h * head_bytes <= STATE_BLOCK_BYTES), 1)
    return StateTiles(heads, 6 * heads * head_bytes + 4 * 2 ** 20)


def _ssm_update_kernel(layer_ref, live_ref, decay_ref, dtx_ref, b_ref, c_ref,
                       s_ref, so_ref, y_ref, *, heads, per_group, unroll):
    """One (slot, head block): s_ref / so_ref [heads, P, N] of the pool,
    dtx_ref [P, lanes] (a head a lane), y_ref [heads, P], b_ref / c_ref
    [G, 1, N], decay_ref [B, H] in scalar memory.  The heads go ``unroll``
    at a time: a loop over groups, so that the kernel's text (and the time
    to trace and lower it) does not grow with the heads."""
    b, j = pl.program_id(0), pl.program_id(1)
    P, N = s_ref.shape[1:]

    @pl.when(live_ref[b] == 0)
    def _():
        so_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live_ref[b] != 0)
    def _():
        row_of = jax.lax.broadcasted_iota(jnp.int32, (unroll, P), 0)

        def group(i, dtx):
            # ``dtx``'s lanes 0 .. unroll - 1 are this group's heads
            rows = jnp.zeros((unroll, P), jnp.float32)
            for r in range(unroll):
                h = i * unroll + r
                head = j * heads + h
                g = head // per_group
                S = decay_ref[b, head] * s_ref[h] \
                    + dtx[:, r:r + 1] * b_ref[g]
                so_ref[h] = S
                # the MXU's rows come eight at a time: C that many times
                # over, every row of the product the head's y
                y = jax.lax.dot_general(
                    jnp.broadcast_to(c_ref[g], (max(8, unroll), N)), S,
                    (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)[:unroll]
                rows = jnp.where(row_of == r, y, rows)
            y_ref[pl.ds(pl.multiple_of(i * unroll, unroll), unroll), :] = rows
            # the next group's heads to lanes 0 .. unroll - 1
            return pltpu.roll(dtx, dtx.shape[1] - unroll, 1)

        jax.lax.fori_loop(0, heads // unroll, group, dtx_ref[...])


# jitted, so that the layer loop's call sites (nine of a granite period)
# share ONE trace and one lowering of the kernel
@functools.partial(jax.jit, static_argnames="interpret")
def ssm_decode_update(pool, layer, live, decay, dtx, B, C, interpret=False):
    """Advance layer ``layer`` of the stacked state pool by one row a slot.

    pool: [L, slots, H, P, N] float32, aliased to the output (under a jit
    that donates it, or in a loop's carry, it is never copied); ``layer``
    (may be traced) and ``live`` [slots] (nonzero: the slot is served) ride
    in scalar memory; decay [slots, H] float32 (``exp(dt A)``), dtx
    [slots, H, P] float32 (``dt x``), B, C [slots, G, N] float32.  Returns
    (``S' C`` [slots, H, P] float32, the pool): a slot that is not live
    keeps its state bit for bit and reads 0."""
    _, slots, H, P, N = pool.shape
    G = B.shape[1]
    heads, vmem_bytes = pick_state_tiles(H, P, N)
    blocks = H // heads
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    live = jnp.asarray(live, jnp.int32)

    def state_map(b, j, lay, live):
        return (lay[0], b, j, 0, 0)

    def rows_map(b, j, lay, live):
        return (b, j, 0, 0)

    def group_map(b, j, lay, live):
        return (b, 0, 0, 0)

    state_spec = pl.BlockSpec((None, None, heads, P, N), state_map)
    group_spec = pl.BlockSpec((None, G, 1, N), group_map)
    # eight heads a turn of the kernel's loop, or all of a block that does
    # not divide by eight (a toy's)
    unroll = 8 if heads % 8 == 0 else heads
    # [slots, H, P] -> [slots, blocks, P, lanes]: a head's rows down the
    # sublanes, as its state's are, a head a lane, the lanes filled up to
    # whole vregs (the kernel rolls them)
    lanes = -(-heads // 128) * 128
    dtx = jnp.pad(jnp.swapaxes(dtx.reshape(slots, blocks, heads, P), 2, 3),
                  ((0, 0), (0, 0), (0, 0), (0, lanes - heads)))
    pool, y = pl.pallas_call(
        functools.partial(_ssm_update_kernel, heads=heads, per_group=H // G,
                          unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots, blocks),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec((None, None, P, lanes), rows_map),
                      group_spec, group_spec, state_spec],
            out_specs=[state_spec,
                       pl.BlockSpec((None, None, heads, P), rows_map)],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((slots, blocks, heads, P),
                                        jnp.float32)],
        # operands count the scalar-prefetch ones: 6 is the pool
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2,
            vmem_limit_bytes=vmem_bytes),
        interpret=interpret,
        name=KERNEL_SSM_UPDATE,
    )(lay, live, decay, dtx, B[:, :, None, :], C[:, :, None, :], pool)
    return y.reshape(slots, H, P), pool
