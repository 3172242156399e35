"""The dropless expert layer's grouped product
(``moe/sharded_moe.py:dropless_held_experts`` over
``ops/pallas/grouped_expert_glu.py``): the kernel through the interpreter
and the same product in jnp, each against the per-pair float32 sum."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.ops.pallas import grouped_expert_glu as glu

D, F, HELD = 32, 48, 6
IMPLS = ["jnp", "pallas"]


def _experts(seed=0, stack=None, dtype=jnp.float32):
    lead = (HELD,) if stack is None else (stack, HELD)
    keys = jax.random.split(jax.random.key(seed), 3)
    return {"w_gate": jax.random.normal(keys[0], lead + (D, F), dtype) / 6,
            "w_up": jax.random.normal(keys[1], lead + (D, F), dtype) / 6,
            "w_down": jax.random.normal(keys[2], lead + (F, D), dtype) / 7}


def _per_pair(h, chosen, weights, experts, first=0, layer=None):
    """Every held pair's term, one at a time, summed in float32."""
    out = np.zeros(h.shape, np.float32)
    load = np.zeros(HELD, np.int64)
    at = (lambda w: w) if layer is None else (lambda w: w[layer])
    for n, (row, w_row) in enumerate(zip(np.asarray(chosen),
                                         np.asarray(weights))):
        for e, w in zip(row - first, w_row):
            if 0 <= e < HELD:
                x = h[n].astype(jnp.float32)
                y = (jax.nn.silu(x @ at(experts["w_gate"])[e])
                     * (x @ at(experts["w_up"])[e])) \
                    @ at(experts["w_down"])[e]
                out[n] += w * np.asarray(y)
                load[e] += 1
    return out, load


def _inputs(n, k, seed, spread=HELD):
    """``n`` tokens with ``k`` distinct experts each of ``spread``."""
    keys = jax.random.split(jax.random.key(seed), 3)
    h = jax.random.normal(keys[0], (n, D))
    chosen = jnp.argsort(jax.random.uniform(keys[1], (n, spread)),
                         axis=-1)[:, :k].astype(jnp.int32)
    weights = jax.random.uniform(keys[2], (n, k), minval=0.2)
    return h, chosen, weights


def _held(impl, *args, **kwargs):
    return jax.jit(functools.partial(
        sharded_moe.dropless_held_experts, act=jax.nn.silu, impl=impl,
        interpret=True, **kwargs))(*args)


def _check(impl, h, chosen, weights, experts, tile, **kwargs):
    out, load, rows = _held(impl, h, chosen, weights, experts, tile=tile,
                            **kwargs)
    want, want_load = _per_pair(h, chosen, weights, experts,
                                kwargs.get("first", 0))
    np.testing.assert_allclose(out, want, atol=1e-5)
    assert load.tolist() == want_load.tolist()
    # the rows computed: every expert's pairs padded to whole tiles
    assert int(rows) == sum(-(-n // tile) * tile for n in want_load)
    assert int(rows) >= want_load.sum() and int(rows) % tile == 0
    return out


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("n,k,tile", [(1, 2, 8), (16, 3, 8), (16, 3, 16),
                                      (37, 4, 8)])
def test_every_pair_once_whatever_the_batch(impl, n, k, tile):
    """N = 1 and 16, a batch that is no multiple of the tile; experts
    with more pairs than a tile, and (N = 1) experts with none."""
    h, chosen, weights = _inputs(n, k, seed=n + k)
    _check(impl, h, chosen, weights, _experts(), tile)


@pytest.mark.parametrize("impl", IMPLS)
def test_an_expert_with_no_pair_and_one_with_more_than_a_tile(impl):
    h, chosen, weights = _inputs(24, 2, seed=5, spread=3)   # experts 0-2
    chosen = jnp.where(chosen == 1, 4, chosen)      # ... 0, 2 and 4
    out, load, rows = _held(impl, h, chosen, weights, _experts(), tile=8)
    assert load.tolist()[1::2] == [0, 0, 0] and max(load.tolist()) > 8
    _check(impl, h, chosen, weights, _experts(), 8)


@pytest.mark.parametrize("impl", IMPLS)
def test_every_pair_on_one_expert_takes_more_than_one_chunk(impl):
    """A chunk is the rows one pair a token fills: 40 tokens with three
    held pairs each, the first of them all on expert 3, need several."""
    h, _, weights = _inputs(40, 3, seed=9)
    chosen = jnp.tile(jnp.asarray([[3, 0, 5]], jnp.int32), (40, 1))
    out = _check(impl, h, chosen, weights, _experts(), 8)
    # 120 pairs in chunks of 40 + 6 * 8 rows
    assert -(-120 // (40 + HELD * 8)) > 1 and np.abs(out).min() > 0


@pytest.mark.parametrize("impl", IMPLS)
def test_experts_held_elsewhere_are_left_out(impl):
    """``first`` > 0: ids 4-9 are this chip's experts 0-5; the others'
    pairs are neither computed nor counted."""
    h, chosen, weights = _inputs(16, 4, seed=11, spread=12)
    out = _check(impl, h, chosen, weights, _experts(), 8, first=4)
    nobody = np.all((np.asarray(chosen) < 4) | (np.asarray(chosen) > 9),
                    axis=1)
    assert np.all(np.asarray(out)[nobody] == 0)


@pytest.mark.parametrize("impl", IMPLS)
def test_rows_that_are_nobodys_tokens_are_neither_computed_nor_counted(impl):
    """The ``real`` mask, as the model applies it: ``chosen`` -1."""
    h, chosen, weights = _inputs(16, 3, seed=13)
    real = jnp.arange(16) % 3 != 1
    masked = jnp.where(real[:, None], chosen, -1)
    out = _check(impl, h, masked, weights, _experts(), 8)
    assert np.all(np.asarray(out)[~np.asarray(real)] == 0)
    # no token at all: nothing runs, nothing is counted
    out, load, rows = _held(impl, h, jnp.full_like(chosen, -1), weights,
                            _experts(), tile=8)
    assert not np.asarray(out).any() and not load.any() and int(rows) == 0


@pytest.mark.parametrize("impl", IMPLS)
def test_stacked_leaves_are_read_by_a_traced_layer_under_scan(impl):
    experts = _experts(stack=3)
    h, chosen, weights = _inputs(16, 3, seed=17)

    def one(carry, layer):
        out, load, rows = sharded_moe.dropless_held_experts(
            h, chosen, weights, experts, jax.nn.silu, tile=8, layer=layer,
            impl=impl, interpret=True)
        return carry + rows, (out, load)

    rows, (outs, loads) = jax.jit(lambda: jax.lax.scan(
        one, jnp.int32(0), jnp.arange(3)))()
    for layer in range(3):
        want, want_load = _per_pair(h, chosen, weights, experts,
                                    layer=layer)
        np.testing.assert_allclose(outs[layer], want, atol=1e-5)
        assert loads[layer].tolist() == want_load.tolist()
    assert int(rows) == 3 * sum(-(-n // 8) * 8 for n in want_load)


def test_bf16_operands_accumulate_in_float32():
    """The serving dtype: bf16 activations and weights, the products
    accumulated and the terms summed in float32: both forms agree to a
    bf16 rounding of the term, and with the float32 sum to three."""
    h, chosen, weights = _inputs(16, 3, seed=19)
    experts = _experts(dtype=jnp.bfloat16)
    h = h.astype(jnp.bfloat16)
    outs = [_held(impl, h, chosen, weights, experts, tile=16)[0]
            for impl in IMPLS]
    assert outs[0].dtype == jnp.float32
    np.testing.assert_allclose(outs[0], outs[1], atol=2e-2)
    want, _ = _per_pair(h, chosen, weights, jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float32), experts))
    np.testing.assert_allclose(outs[1], want, atol=4e-2)


@pytest.mark.parametrize("n,held,d,f,itemsize,rows,cols", [
    (16, 16, 2048, 1024, 2, 16, 1024),          # a decode batch
    (1024, 16, 2048, 1024, 2, 32, 1024),
    (4096, 16, 2048, 1024, 2, 128, 1024),
    (16384, 16, 2048, 1024, 2, 256, 1024),      # Trinity's longest bucket
    (16, 16, 6144, 2048, 2, 16, 512),           # GLM-5: an expert is 75 MB
    (12800, 16, 6144, 2048, 2, 256, 512),
])
def test_the_tiles_follow_the_shapes(n, held, d, f, itemsize, rows, cols):
    tiles = glu.pick_expert_tiles(n, held, d, f, itemsize)
    assert (tiles.rows, tiles.cols) == (rows, cols)
    assert tiles.vmem_bytes <= glu.VMEM_BUDGET and f % tiles.cols == 0
    assert glu.pick_expert_tiles(n, held, d, f, itemsize, rows=64).rows == 64
