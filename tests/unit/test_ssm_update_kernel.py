"""The aliased ``ssm_decode_update`` kernel against ``ssm_step`` and the
masked write, on a stacked state pool.

``ops/ssm.py state_decode_update`` is the one entry point of both: "jnp"
slices the layer out, runs ``ssm_step``, masks the rows that are not live
and updates the stack (the oracle); "pallas" is the kernel, here through
its interpreter.  Tolerance: both sides float32, the sums in another
order: 1e-5 of the largest value compared (a state or ``y`` lost, stale or
another row's reads 1e-1 and more).  Rows the dispatch does not serve and
layers it does not name are compared bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.ssm_update import (STATE_BLOCK_BYTES,
                                                 pick_state_tiles)
from deepspeed_tpu.ops.ssm import ssm_step, state_decode_update

TOL = 1e-5
# L, slots, H, P, N, G: granite-4.0-h-micro's widths at three slots, a toy
# width with two groups, and two groups of heads too wide for one block
GRANITE = (3, 3, 64, 64, 128, 1)
TOY = (3, 3, 4, 16, 128, 2)
TWO_BLOCKS = (2, 2, 128, 64, 128, 2)


def _operands(shape, dtype=jnp.float32, seed=0):
    L, slots, H, P, N, G = shape
    ks = jax.random.split(jax.random.key(seed), 7)
    return dict(
        pool=jax.random.normal(ks[0], (L, slots, H, P, N), jnp.float32),
        x=jax.random.normal(ks[1], (slots, H, P), dtype),
        dt=jax.nn.softplus(jax.random.normal(ks[2], (slots, H))),
        A=-jnp.exp(jax.random.normal(ks[3], (H,))),
        B=jax.random.normal(ks[4], (slots, G, N), dtype),
        C=jax.random.normal(ks[5], (slots, G, N), dtype),
        D=jax.random.normal(ks[6], (H,)))


@jax.jit
def _kernel(pool, layer, x, dt, A, B, C, D, live):
    return state_decode_update(pool, layer, x, dt, A, B, C, D, live,
                               impl="pallas", interpret=True)


def _oracle(pool, layer, x, dt, A, B, C, D, live):
    return state_decode_update(pool, layer, x, dt, A, B, C, D, live)


def _close(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.abs(got - want).max() <= TOL * np.abs(want).max()


@pytest.mark.parametrize("shape", [GRANITE, TOY, TWO_BLOCKS],
                         ids=["granite", "toy_g2", "two_blocks"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_kernel_is_ssm_step_on_the_stack(shape, dtype):
    ops = _operands(shape, dtype)
    pool, live = ops.pop("pool"), jnp.ones((shape[1],), bool)
    y, new = _kernel(pool, 1, live=live, **ops)
    want_y, want = _oracle(pool, 1, live=live, **ops)
    assert y.dtype == dtype and new.dtype == jnp.float32
    assert _close(new[1], want[1])
    if dtype == jnp.bfloat16:   # one rounding of the float32 sum apart
        assert np.abs(np.asarray(y - want_y, np.float32)).max() <= \
            2 ** -7 * np.abs(np.asarray(want_y, np.float32)).max()
    else:
        assert _close(y, want_y)
    # and the oracle is ``ssm_step`` on the layer's rows
    step_y, step_state = ssm_step(state=pool[1], **ops)
    assert bool(jnp.all(want[1] == step_state) & jnp.all(want_y == step_y))


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_every_layer_index_and_no_other_layers_rows(layer):
    ops = _operands(TOY, seed=layer)
    pool, live = ops.pop("pool"), jnp.ones((3,), bool)
    # the index is traced, as the layer loop's is
    y, new = _kernel(pool, jnp.asarray(layer), live=live, **ops)
    want_y, want = _oracle(pool, layer, live=live, **ops)
    assert _close(new[layer], want[layer]) and _close(y, want_y)
    others = [i for i in range(3) if i != layer]
    assert bool(jnp.all(new[jnp.asarray(others)]
                        == pool[jnp.asarray(others)]))


@pytest.mark.parametrize("shape", [GRANITE, TOY], ids=["granite", "toy_g2"])
def test_a_dead_row_keeps_its_bits_and_the_others_are_served(shape):
    ops = _operands(shape, seed=3)
    pool = ops.pop("pool")
    # bits a multiplication by one and an addition of zero would not keep
    pool = pool.at[1, 1, 0, 0, :4].set(
        jnp.asarray([-0.0, jnp.inf, jnp.nan, 1e-45]))
    live = jnp.asarray([True, False, True])
    y, new = _kernel(pool, 1, live=live, **ops)
    want_y, want = _oracle(pool, 1, live=live, **ops)
    bits = lambda a: np.asarray(a).view(np.uint32)      # noqa: E731
    assert (bits(new[1, 1]) == bits(pool[1, 1])).all()
    assert (bits(want[1, 1]) == bits(pool[1, 1])).all()
    served = jnp.asarray([0, 2])
    assert _close(new[1, served], want[1, served])
    assert _close(y[served], want_y[served])
    assert bool(jnp.all(new[0] == pool[0]) & jnp.all(new[2] == pool[2]))


def test_a_row_at_dt_zero_changes_nothing():
    ops = _operands(TOY, seed=4)
    pool = ops.pop("pool")
    ops["dt"] = ops["dt"].at[1].set(0.0)
    _, new = _kernel(pool, 2, live=jnp.ones((3,), bool), **ops)
    assert bool(jnp.all(new[2, 1] == pool[2, 1]))
    assert not bool(jnp.all(new[2, 0] == pool[2, 0]))


def test_fifty_steps_stay_within_rounding_of_fifty_ssm_steps():
    ops = _operands(TOY, seed=5)
    pool, live = ops.pop("pool"), jnp.ones((TOY[1],), bool)
    state = pool[1]
    for t in range(50):
        row = _operands(TOY, seed=100 + t)
        row.pop("pool")
        row.update(A=ops["A"], D=ops["D"])
        y, pool = _kernel(pool, 1, live=live, **row)
        want_y, state = ssm_step(state=state, **row)
        assert _close(y, want_y), t
    assert _close(pool[1], state)


@pytest.mark.parametrize("shape,heads", [
    # the cell's: a whole slot of 64 heads x 32 KB a step, 64 steps a layer
    ((64, 64, 128), 64),
    # the toy engine's (tiny-granite.json): all 16 heads, 16 KB each in VMEM
    ((16, 32, 32), 16),
    # twice granite's heads: the largest divisor under the block's bytes
    ((128, 64, 128), 64),
    ((24, 64, 256), 24), ((48, 64, 256), 24),
    # a head over the block's bytes goes alone
    ((4, 512, 2048), 1),
])
def test_the_tile_function(shape, heads):
    tiles = pick_state_tiles(*shape)
    # as VMEM holds a head: rows in eights, lanes in 128s
    head_bytes = -(-shape[1] // 8) * 8 * -(-shape[2] // 128) * 128 * 4
    assert tiles.heads == heads and shape[0] % tiles.heads == 0
    assert heads == 1 or heads * head_bytes <= STATE_BLOCK_BYTES
    # in and out, double-buffered, and the step's temporaries
    assert tiles.vmem_bytes >= 6 * heads * head_bytes
