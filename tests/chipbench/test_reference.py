"""Each plain float32 reference against the program at toy width, on
seeded weights with every norm weight and bias moved off its initial
value.  The references import nothing from the program."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)

from chipbench import cells
import tree
from tree import DATA, REPO

# Both sides in float32 on the CPU: they differ by rounding order alone,
# except that the program's converter maps GPT-NeoX's erf GELU to the tanh
# form (up to 5e-4 an activation, about 1e-3 on a logit of size 4).
TOLERANCE = {"tiny-neox": 3e-3, "tiny-olmo2": 1e-4}


@pytest.mark.parametrize("config_name", sorted(TOLERANCE))
def test_reference_matches_program(config_name):
    with open(os.path.join(DATA, config_name + ".json")) as f:
        config = json.load(f)
    cell = cells.Cell(config_name, 1, config, {}, [], [])
    model = CausalTransformerLM(TransformerConfig(
        **cell.family.transformer_kwargs(config), remat=False,
        attn_impl="reference"))
    params = model.init(jax.random.key(1))
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        if leaf.shape[-1] <= 256 else leaf
        for leaf, key in zip(leaves, keys)])
    ids = jax.random.randint(jax.random.key(3), (2, 48), 0,
                             config["vocab_size"])
    ours = model.apply(params, ids, train=False)
    want = cell.reference.logits(params, ids, config)
    assert float(jnp.max(jnp.abs(ours - want))) < TOLERANCE[config_name]
    last = cell.reference.logits(params, ids, config, last=5)
    assert jnp.allclose(last, want[:, -5:], atol=1e-5)


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_control_in_8_bit_floats_fails_the_serving_tolerance(seed):
    """``chipbench/control.py`` at toy width: the program serving weights
    rounded to float8 through the check's own path reads several times
    ``LOGIT_TOL`` against the reference on the seeded weights, where the
    program on those reads about 0.01: the comparison tells the two
    precisions apart (the chip's readings at OLMo-2 1B: PERF.md §4)."""
    from chipbench import control, serve_cell
    cell = cells.Cell("tiny-doc", 1, tree.data("tiny-olmo2"),
                      tree.data("tiny-closed"), [], [])
    check = control.control_error(cell, seed, jax.devices()[:1])
    assert check["logit_error"] > 2 * serve_cell.LOGIT_TOL
    assert check["rows_compared"] == 72 and not check["ok"]


def test_references_do_not_import_the_program():
    folder = os.path.join(REPO, "chipbench", "reference")
    for name in os.listdir(folder):
        if name.endswith(".py"):
            with open(os.path.join(folder, name)) as f:
                assert "deepspeed_tpu" not in f.read(), name
