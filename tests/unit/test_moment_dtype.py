"""bf16 Adam moments with stochastic rounding (``moment_dtype``).

TPU design note: reference ZeRO-Offload moves fp32 Adam state to host RAM to
fit big models (docs/_posts/2020-09-09-ZeRO-Offload.md); on one TPU chip the
host hop is the bottleneck, so the single-chip alternative is to shrink the
state itself — both moments stored bf16, accumulated fp32 each step, written
back with stochastic rounding (unbiased, unlike nearest-rounding which decays
the (1-b2)-scaled increments of the second moment).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime.optimizers import build_optimizer
from unit.simple_model import SimpleModel, base_config, random_batch

HIDDEN = 16


def _trajectory(moment_dtype, steps=30):
    model = SimpleModel(hidden_dim=HIDDEN)
    params = model.init(jax.random.key(0))
    cfg = base_config(0)
    cfg["optimizer"] = {"type": "AdamW",
                        "params": {"lr": 1e-2,
                                   "moment_dtype": moment_dtype}}
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=cfg)
    batch = random_batch(32, HIDDEN)
    return ([float(engine.train_batch(batch=batch)) for _ in range(steps)],
            engine)


def test_bf16_moments_track_fp32_trajectory():
    losses32, _ = _trajectory("float32")
    losses16, engine = _trajectory("bfloat16")
    # both must train; trajectories must stay close (bf16 SR is unbiased)
    assert losses16[-1] < losses16[0] * 0.9
    np.testing.assert_allclose(losses16[-1], losses32[-1],
                               rtol=0.1, atol=0.05)


def test_moment_state_is_actually_bf16():
    _, engine = _trajectory("bfloat16", steps=1)
    st = _find_adam_state(engine.state.opt_state)
    for leaf in jax.tree_util.tree_leaves((st.mu, st.nu)):
        assert leaf.dtype == jnp.bfloat16


def _find_adam_state(state):
    for s in jax.tree_util.tree_leaves(
            state, is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState)):
        if isinstance(s, optax.ScaleByAdamState):
            return s
    raise AssertionError("no ScaleByAdamState in optimizer state")


def test_sr_accumulation_does_not_decay_second_moment():
    """Constant small gradients: with b2=0.999 each nu increment is ~1e-3
    relative — below bf16's ~4e-3 nearest-rounding resolution near the fixed
    point, so nearest rounding stalls nu low.  SR must track the fp32 fixed
    point in expectation."""
    tx = build_optimizer("adamw", {"lr": 1e-3, "moment_dtype": "bfloat16"})
    params = {"w": jnp.zeros((4096,), jnp.float32)}
    state = tx.init(params)
    g = {"w": jnp.full((4096,), 1e-2, jnp.float32)}
    step = jax.jit(lambda s: tx.update(g, s, params)[1])
    for _ in range(400):
        state = step(state)
    nu = _find_adam_state(state).nu["w"].astype(jnp.float32)
    expect = (1 - 0.999 ** 400) * 1e-4          # fp32 fixed point
    got = float(jnp.mean(nu))
    assert abs(got - expect) / expect < 0.05, (got, expect)


def test_unknown_moment_dtype_raises():
    with pytest.raises(ValueError, match="moment_dtype"):
        build_optimizer("adamw", {"lr": 1e-3, "moment_dtype": "fp8"})
