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
from deepspeed_tpu.runtime import optimizers
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


# ---- the rounding's noise itself (PR 55: a counter hash, no jax.random) ----

NOISE_SHAPE = (8, 256, 512)          # 2**20 elements
NOISE_STEPS = (1, 2, 3, 1000)


def _noise_of(count, ordinal=0):
    return optimizers._rounding_noise(
        optimizers._rounding_seed(count, ordinal), NOISE_SHAPE)


def _words(step, ordinal=0):
    """The uint32 word an element that rounds a leaf's moments at ``step``."""
    return np.asarray(jax.jit(lambda count: _noise_of(count, ordinal))(
        jnp.int32(step)))


def _r(a, b):
    return abs(float(np.corrcoef(np.ravel(a).astype(np.float64),
                                 np.ravel(b).astype(np.float64))[0, 1]))


def _halves(step):
    words = _words(step)
    return words & 0xFFFF, words >> 16


def _check_uniform():
    # chi-square over the top byte, 255 degrees of freedom: the 0.1 % point
    # is 330.5
    for step in NOISE_STEPS:
        for half in _halves(step):
            assert abs(half.mean() / 32767.5 - 1) < 2e-3, (step, half.mean())
            counts = np.bincount((half >> 8).ravel(), minlength=256)
            expect = half.size / 256
            assert ((counts - expect) ** 2 / expect).sum() < 330.5, step


def _check_uncorrelated():
    lo, hi = _halves(1)
    assert _r(lo, hi) < 0.01                      # mu's half against nu's
    for step in NOISE_STEPS[:3]:
        now, then = _halves(step), _halves(step + 1)
        for a, b in zip(now, then):               # one element, t and t + 1
            assert _r(a, b) < 0.01, step
        for half in now:                          # neighbours on every axis
            for axis in range(half.ndim):
                near = np.moveaxis(half, axis, 0)
                assert _r(near[:-1], near[1:]) < 0.01, (step, axis)


def _check_unbiased():
    # three eighths of the way from 1 to the next bf16 value: nearest
    # rounding is 2.9e-3 off
    value = np.float32(1 + 0.375 / 128)
    x32 = jnp.full(NOISE_SHAPE, value, jnp.float32)
    rounded = jax.jit(lambda count: optimizers._sr_cast(
        x32, _noise_of(count) & jnp.uint32(0xFFFF),
        jnp.bfloat16).astype(jnp.float32).mean())
    mean = np.mean([float(rounded(jnp.int32(t))) for t in range(1, 257)])
    assert abs(mean / value - 1) < 1e-4, mean
    nearest = float(jnp.asarray(value).astype(jnp.bfloat16))
    assert abs(nearest / value - 1) > 1e-3


def _check_leaves_differ():
    first, second = _words(1, ordinal=0), _words(1, ordinal=1)
    assert (first != second).mean() > 0.999
    assert _r(first & 0xFFFF, second & 0xFFFF) < 0.01
    # ... and through the transformation: two equal leaves, equal gradients
    tx = build_optimizer("adamw", {"lr": 1e-3, "moment_dtype": "bfloat16"})
    params = {"a": jnp.zeros((64, 128)), "b": jnp.zeros((64, 128))}
    g = {k: jnp.full((64, 128), 1 / 3, jnp.float32) for k in params}
    st = _find_adam_state(tx.update(g, tx.init(params), params)[1])
    assert (np.asarray(st.mu["a"]) != np.asarray(st.mu["b"])).mean() > 0.2


def _check_same_on_any_mesh():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    tx = build_optimizer("adamw", {"lr": 1e-3, "moment_dtype": "bfloat16"})
    shape = (8, 64, 128)
    params = {"w": jnp.zeros(shape, jnp.float32)}
    g = {"w": jax.random.normal(jax.random.key(1), shape, jnp.float32)}
    step = jax.jit(lambda g, s: tx.update(g, s, params)[1])

    def moments(sharding=None):
        place = (lambda t: t) if sharding is None else \
            (lambda t: jax.device_put(t, sharding))
        state = tx.init(params)
        for _ in range(2):
            state = step(place(g), jax.tree_util.tree_map(
                lambda x: place(x) if x.shape == shape else x, state))
        st = _find_adam_state(state)
        if sharding is not None:
            assert len(st.mu["w"].sharding.device_set) == 4
        return np.asarray(st.mu["w"]), np.asarray(st.nu["w"])

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("fsdp",))
    one = moments()
    for spec in (P("fsdp"), P(None, "fsdp")):
        for got, want in zip(moments(NamedSharding(mesh, spec)), one):
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("check", [
    _check_uniform, _check_uncorrelated, _check_unbiased,
    _check_leaves_differ, _check_same_on_any_mesh],
    ids=["each_half_uniform", "halves_steps_neighbours_uncorrelated",
         "rounding_unbiased", "leaves_differ", "same_on_one_device_and_four"])
def test_rounding_noise(check):
    check()


# ---- the cost cannot come back: counts only ----

ELEMENTWISE_CEILING = 60       # equations a leaf of tx.update; 50 in PR 55


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_update_draws_no_random_bits():
    tx = build_optimizer("adamw", {"lr": 1e-3, "moment_dtype": "bfloat16"})
    shape = (4, 64, 128)
    params = {"a": jnp.zeros(shape), "b": jnp.zeros(shape)}
    grads = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16), params)
    jaxpr = jax.make_jaxpr(lambda g, s, p: tx.update(g, s, p))(
        grads, tx.init(params), params)
    eqns = list(_equations(jaxpr.jaxpr))
    names = {e.primitive.name for e in eqns}
    assert not {n for n in names if "threefry" in n or "random" in n}, names
    on_leaves = [e for e in eqns
                 if any(getattr(v.aval, "shape", ()) == shape
                        for v in e.outvars)]
    assert 0 < len(on_leaves) / len(params) <= ELEMENTWISE_CEILING, \
        len(on_leaves) / len(params)
