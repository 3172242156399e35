"""MoE utils/mappings/experts tests.

Parity model: reference ``deepspeed/moe/{utils,mappings,experts}.py`` —
expert-vs-shared param splitting for optimizer groups, TP token
gather/drop duals, and the local expert bank.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.moe.experts import Experts
from deepspeed_tpu.moe.mappings import drop_tokens, gather_tokens
from deepspeed_tpu.moe.utils import (
    has_moe_layers, is_moe_param, moe_param_labels,
    split_params_grads_into_shared_and_expert_params,
    split_params_into_different_moe_groups_for_optimizer,
    split_params_into_shared_and_expert_params)

D = 8


def _params():
    rng = np.random.default_rng(0)
    return {
        "layers": {
            "wq": rng.normal(size=(2, D, D)).astype(np.float32),
            "moe": {"w_up": rng.normal(size=(4, D, D)).astype(np.float32),
                    "wg": rng.normal(size=(D, 4)).astype(np.float32)},
        },
        "lm_head": rng.normal(size=(D, 16)).astype(np.float32),
    }


def test_is_moe_param_path_predicate():
    assert is_moe_param("['layers']['moe']['w_up']")
    assert is_moe_param("['experts']['w_down']")
    assert not is_moe_param("['layers']['wq']")
    assert not is_moe_param("['smoean']['w']")     # no substring false hits


def test_has_moe_layers_on_params_and_model():
    has, n = has_moe_layers(_params())
    assert has and n == 4
    assert has_moe_layers({"layers": {"wq": np.zeros((2, D))}}) == (False, 0)

    class M:
        num_experts = 8
    assert has_moe_layers(M()) == (True, 8)


def test_has_moe_layers_expert_bank_4d_leaf():
    # an Experts bank stacks [E_local, ...] on the LEADING axis even when
    # the per-expert weight is itself >=3-D (e.g. per-head [H, dh, d]);
    # the expert count must come from axis 0, not an inner axis
    p = {"experts": np.zeros((4, 2, D, D), np.float32)}
    assert has_moe_layers(p) == (True, 4)
    # a model carrying a layers axis reports experts via config, not shapes

    class M:
        class config:
            moe_num_experts = 4
    assert has_moe_layers(M()) == (True, 4)


def test_split_shared_and_expert_params():
    p = _params()
    shared, expert = split_params_into_shared_and_expert_params(p)
    assert shared["layers"]["wq"] is not None
    assert shared["layers"]["moe"]["w_up"] is None
    assert expert["layers"]["moe"]["w_up"] is not None
    assert expert["lm_head"] is None
    # grads variant is the same split; the router gate is a SHARED param
    # (replicated/full-DP-reduced) even though it lives under the moe key
    gs, ge = split_params_grads_into_shared_and_expert_params(p)
    assert ge["layers"]["moe"]["wg"] is None
    assert gs["layers"]["moe"]["wg"] is not None
    assert gs["lm_head"] is not None


def test_moe_param_labels_for_optax():
    labels = moe_param_labels(_params())
    assert labels["layers"]["wq"] == "shared"
    assert labels["layers"]["moe"]["w_up"] == "moe"


def test_split_param_groups_for_optimizer():
    p = _params()
    flat = {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_leaves_with_path(p)}
    groups = split_params_into_different_moe_groups_for_optimizer(
        {"name": "base", "params": flat, "lr": 0.1})
    names = [g["name"] for g in groups]
    assert "base" in names
    moe_groups = [g for g in groups if g.get("moe")]
    assert len(moe_groups) == 1 and moe_groups[0]["lr"] == 0.1
    assert all(is_moe_param(k) for k in moe_groups[0]["params"])
    assert not any(is_moe_param(k) for k in groups[0]["params"])
    # max_group_size chunking: tiny cap → one group per expert leaf
    # (w_up only — the gate is shared)
    chunked = split_params_into_different_moe_groups_for_optimizer(
        {"name": "base", "params": flat}, max_group_size=1)
    assert len([g for g in chunked if g.get("moe")]) == 1


def test_gather_drop_tokens_duals():
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    x = jnp.arange(16.0, dtype=jnp.float32).reshape(8, 2)

    @jax.jit
    def run(x):
        def f(xs):
            full = gather_tokens(xs, dim=0)     # [8, 2] on every tp rank
            back = drop_tokens(full, dim=0)     # this rank's quarter again
            return full.sum() * 0 + back
        return jax.shard_map(f, mesh=mesh, in_specs=P("tp", None),
                             out_specs=P("tp", None), check_vma=False)(x)

    np.testing.assert_allclose(np.asarray(run(x)), np.asarray(x))

    # custom-vjp duals: d(gather)/dx slices, d(drop)/dx gathers
    @jax.jit
    def loss(x):
        def f(xs):
            full = gather_tokens(xs, dim=0)
            return jnp.sum(full ** 2)[None]
        return jax.shard_map(f, mesh=mesh, in_specs=P("tp", None),
                             out_specs=P("tp"), check_vma=False)(x).sum()

    g = jax.grad(loss)(x)
    # Megatron/reference convention: gather's backward is a plain drop (no
    # psum) because the downstream loss is assumed replicated across tp
    # ranks — each rank keeps only its own slice's grad, so d/dx = 2x even
    # though both tp ranks computed the same gathered tensor
    assert np.all(np.isfinite(np.asarray(g)))
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(x))


def test_gather_tokens_identity_outside_tp_scope():
    x = jnp.ones((4, 2))
    np.testing.assert_array_equal(np.asarray(gather_tokens(x)), 1.0)
    np.testing.assert_array_equal(np.asarray(drop_tokens(x)), 1.0)


def test_experts_bank_vmap():
    def init(rng):
        return {"w": jax.random.normal(rng, (D, D))}

    def apply(p, x):
        return x @ p["w"]

    bank = Experts(init, apply, num_local_experts=3)
    params = bank.init(jax.random.key(0))
    assert params["experts"]["w"].shape == (3, D, D)
    # independent inits per expert
    assert not np.allclose(np.asarray(params["experts"]["w"][0]),
                           np.asarray(params["experts"]["w"][1]))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 3, 5, D)),
                    jnp.float32)
    out = bank(params, x)
    assert out.shape == x.shape
    want = np.stack([np.asarray(x[:, e]) @ np.asarray(
        params["experts"]["w"][e]) for e in range(3)], axis=1)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=1e-5)


# ----------------------------------------------------------------------
# utils.tensor_fragment (debug access to master/opt/grads)
# ----------------------------------------------------------------------
def test_tensor_fragment_debug_access():
    import deepspeed_tpu
    from deepspeed_tpu.utils.tensor_fragment import (
        safe_get_full_fp32_param, safe_get_full_grad,
        safe_get_full_optimizer_state)
    from unit.simple_model import SimpleModel, base_config, random_batch

    model = SimpleModel(16)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.key(0)),
        config=base_config(stage=3))
    # master param: full fp32 global value regardless of fsdp sharding
    w = safe_get_full_fp32_param(engine, "layer_0.w")
    assert w is not None and w.shape == (16, 16) and w.dtype == np.float32
    assert safe_get_full_fp32_param(engine, "layer_9.w") is None
    # grads: None before forward, populated by the 3-call API
    assert safe_get_full_grad(engine, "layer_0.w") is None
    batch = random_batch(32, 16)
    engine.forward(batch)
    engine.backward()
    g = safe_get_full_grad(engine, "layer_0.w")
    assert g is not None and g.shape == (16, 16)
    engine.step()
    m = safe_get_full_optimizer_state(engine, "layer_0.w", "exp_avg")
    assert m is not None and m.shape == (16, 16)
    assert np.abs(m).sum() > 0
