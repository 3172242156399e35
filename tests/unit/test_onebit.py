"""1-bit compressed-communication tests.

Parity model: reference ``tests/unit/comm/test_coalesced_collectives.py`` +
``tests/onebit/`` (OnebitAdam convergence, compressed_allreduce vs plain
allreduce error bounds).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.runtime.optimizers import build_optimizer
from deepspeed_tpu.runtime.comm_compression import (
    compressed_allreduce, compressed_allreduce_bytes,
    error_feedback_compress, pack_signs, unpack_signs)
from unit.simple_model import SimpleModel, base_config, random_batch

HIDDEN = 16


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(128,)).astype(np.float32)
    signs = np.where(x >= 0, 1.0, -1.0).astype(np.float32)
    packed = jax.device_get(pack_signs(jnp.asarray(x)))
    assert packed.dtype == np.uint8 and packed.size == 16
    back = jax.device_get(unpack_signs(jnp.asarray(packed)))
    np.testing.assert_array_equal(back, signs)


def _run_compressed_allreduce(local_grads, worker_err, server_err):
    """local_grads: [world, n] — per-device gradients."""
    world, n = local_grads.shape
    devices = jax.devices()[:world]
    mesh = Mesh(np.array(devices), ("dp",))
    fn = shard_map(
        functools.partial(compressed_allreduce, axis_name="dp"),
        mesh=mesh,
        in_specs=(P("dp"), P("dp"), P("dp")),
        out_specs=(P("dp"), P("dp"), P("dp")))
    # give every device its own full-length grad row: shard the leading dim
    out, we, se = fn(local_grads.reshape(world, n),
                     worker_err.reshape(world, n),
                     server_err.reshape(world, n // world))
    return (np.asarray(out).reshape(world, n), np.asarray(we).reshape(world, n),
            np.asarray(se).reshape(world, n // world))


def test_compressed_allreduce_approximates_mean():
    world, n = 8, 8 * 64
    rng = np.random.default_rng(1)
    grads = rng.normal(size=(world, n)).astype(np.float32)
    we = np.zeros((world, n), np.float32)
    se = np.zeros((world, n // world), np.float32)
    out, we, se = _run_compressed_allreduce(grads, we, se)
    # every worker gets the same reduced vector
    for w in range(1, world):
        np.testing.assert_array_equal(out[0], out[w])
    # sign structure of the true mean is mostly preserved
    true_mean = grads.mean(axis=0)
    agree = np.mean(np.sign(out[0]) == np.sign(true_mean))
    assert agree > 0.7, f"sign agreement only {agree}"
    # error feedback captures the full residual: q + err == corrected
    corrected0 = grads[0] + 0.0
    scale0 = np.abs(corrected0).mean()
    np.testing.assert_allclose(
        we[0], corrected0 - scale0 * np.where(corrected0 >= 0, 1.0, -1.0),
        rtol=1e-5, atol=1e-6)


def test_compressed_allreduce_error_feedback_converges():
    """Averaging EF-compressed reductions over repeated steps of the SAME
    gradient converges toward the true mean (the EF guarantee)."""
    world, n = 4, 4 * 32
    rng = np.random.default_rng(2)
    grads = rng.normal(size=(world, n)).astype(np.float32)
    true_mean = grads.mean(axis=0)
    we = np.zeros((world, n), np.float32)
    se = np.zeros((world, n // world), np.float32)
    acc = np.zeros(n, np.float64)
    steps = 30
    for _ in range(steps):
        out, we, se = _run_compressed_allreduce(grads, we, se)
        acc += out[0]
    avg = acc / steps
    err = np.abs(avg - true_mean).mean() / np.abs(true_mean).mean()
    assert err < 0.25, f"EF average off by {err:.3f}"


def test_compression_ratio():
    n, world = 2 ** 20, 8
    compressed = compressed_allreduce_bytes(n, world)
    fp32 = 2 * 4 * n
    assert fp32 / compressed > 16, fp32 / compressed


def test_onebit_adam_warmup_matches_adam():
    """During warmup (count <= freeze_step) OnebitAdam == Adam exactly."""
    import optax
    tx1 = build_optimizer(
        "onebitadam", {"lr": 1e-2, "freeze_step": 100, "weight_decay": 0.0})
    tx2 = optax.adam(1e-2)
    params = {"w": jnp.ones((4, 4))}
    s1, s2 = tx1.init(params), tx2.init(params)
    rng = np.random.default_rng(3)
    p1 = p2 = params
    for _ in range(3):
        g = {"w": jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)}
        u1, s1 = tx1.update(g, s1, p1)
        u2, s2 = tx2.update(g, s2, p2)
        p1 = optax.apply_updates(p1, u1)
        p2 = optax.apply_updates(p2, u2)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               rtol=1e-6)


def test_onebit_adam_compression_stage_quantizes():
    """Past freeze_step the inner Adam sees sign-quantized grads."""
    tx = build_optimizer(
        "onebitadam", {"lr": 1e-2, "freeze_step": 1})
    params = {"w": jnp.zeros((8,))}
    state = tx.init(params)
    g = {"w": jnp.asarray(np.linspace(-1, 1, 8), jnp.float32)}
    _, state = tx.update(g, state, params)      # step 1: warmup
    u, state = tx.update(g, state, params)      # step 2: compressed
    ef_state = state[0]
    assert int(ef_state.count) == 2
    # error buffer is now non-zero (quantization residual)
    assert float(jnp.abs(ef_state.error["w"]).sum()) > 0


def test_engine_onebit_adam_trains():
    model = SimpleModel(hidden_dim=HIDDEN)
    params = model.init(jax.random.key(0))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config=base_config(
            stage=2,
            optimizer={"type": "OneBitAdam",
                       "params": {"lr": 1e-2, "freeze_step": 2,
                                  "weight_decay": 0.0}}))
    losses = [float(engine.train_batch(batch=random_batch(8, HIDDEN, seed=0)))
              for _ in range(8)]
    assert losses[-1] < losses[0]


# ----------------------------------------------------------------------
# EQuARX-style int8 quantized allreduce
# ----------------------------------------------------------------------
def test_quantized_allreduce_close_to_exact():
    from deepspeed_tpu.runtime.comm_compression import (
        quantized_allreduce, quantized_allreduce_bytes)

    world = 4
    mesh = Mesh(np.array(jax.devices()[:world]), ("dp",))
    rng = np.random.default_rng(0)
    n = world * 256 * 4
    locals_ = jnp.asarray(rng.normal(size=(world, n)), jnp.float32)

    @jax.jit
    def run(xs):
        def f(x):
            return quantized_allreduce(x[0], "dp", bits=8)[None]
        return shard_map(f, mesh=mesh, in_specs=P("dp", None),
                         out_specs=P("dp", None))(xs)

    out = np.asarray(run(locals_))
    exact = np.asarray(locals_.sum(axis=0))
    # every worker holds the same reduced vector
    for r in range(1, world):
        np.testing.assert_array_equal(out[r], out[0])
    # ~8-bit accurate (two quantization rounds)
    rel = np.abs(out[0] - exact).max() / np.abs(exact).max()
    assert rel < 0.02, rel
    # and 3-4x cheaper on the wire than fp32
    assert quantized_allreduce_bytes(n, world) < n * 4 * 2 * 0.3
