"""The system under test, built from a configuration file: the program's
model with seeded weights made on the device in one jitted call."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)


def key_for(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def build_model(cell, **overrides):
    kwargs = cell.family.transformer_kwargs(cell.config)
    return CausalTransformerLM(TransformerConfig(**kwargs, **overrides))


def _spread(shapes, devices):
    """Shardings that spread each large leaf over ``devices`` along its
    largest divisible axis, so seeded weights larger than one chip can be
    made before the engine places them."""
    mesh = Mesh(np.asarray(devices), ("x",))

    def one(shape):
        axes = [i for i in np.argsort(shape.shape)[::-1]
                if shape.shape[i] % len(devices) == 0]
        if shape.size < (1 << 20) or not axes:
            return NamedSharding(mesh, P())
        spec = [None] * len(shape.shape)
        spec[axes[0]] = "x"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(one, shapes)


def seeded_weights(model, seed, dtype, devices):
    """All weights in one jitted call from the seed, in ``dtype``."""
    init = lambda key: model.init(key, dtype)   # noqa: E731
    key = key_for(seed)
    if len(devices) == 1:
        return jax.jit(init)(key)
    shardings = _spread(jax.eval_shape(init, key), devices)
    return jax.jit(init, out_shardings=shardings)(key)


def count_params(params):
    return int(sum(x.size for x in jax.tree_util.tree_leaves(params)))


DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
