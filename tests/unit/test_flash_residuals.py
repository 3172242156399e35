"""What a checkpointed layer keeps of its flash call (PR 52).  The call is
a ``custom_vjp`` round a ``pallas_call`` and no ``dot_general``, so a remat
policy that keeps matrix products (``dots_saveable``, the cells' files')
kept none of its residuals and the forward kernel ran a second time in
every layer's backward pass.  ``TransformerConfig.checkpoint_policy`` now
keeps the call's result and ``lse`` by name under such a policy
(``FLASH_RESIDUALS``), leaves ``nothing_saveable`` as it is, and both
stacks of ``CausalTransformerLM.apply`` take their policy from it.  The
kernel runs through the Pallas interpreter, float32, tiny shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from deepspeed_tpu.models.transformer import (ATTN_SAVED,
                                              PRODUCT_SAVING_POLICIES,
                                              CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.ops.pallas.flash_attention import (FLASH_RESIDUALS,
                                                      flash_attention)
from unit.test_mellum_training import _toy as _mellum_toy

SEQ = 128


def _dense(**kw):
    """The scanned stack (``params["layers"]`` one stacked tree)."""
    return CausalTransformerLM(TransformerConfig.tiny(
        n_layers=1, attn_impl="pallas", **kw))


def _listed(**kw):
    """The listed stack: expert layers, unrolled one by one."""
    return CausalTransformerLM(TransformerConfig.moe_tiny(
        attn_impl="pallas", **kw))


def _periods(**kw):
    """The scanned periods after an (empty) list: four layers a body."""
    return _mellum_toy("pallas", train={"model": kw})[1]


STACKS = {"scanned": (_dense, 1), "listed": (_listed, 2),
          "periods": (_periods, 4)}


def _ids(model, batch=2):
    return jnp.asarray(np.random.default_rng(0).integers(
        0, model.config.vocab_size, (batch, SEQ)), jnp.int32)


def _value_and_grad(model):
    params, ids = model.init(jax.random.key(0)), _ids(model)
    fn = jax.value_and_grad(lambda p: model.loss(p, ids))
    return fn, params


def _kernel_calls(jaxpr, kernel):
    """``pallas_call``s named ``kernel`` anywhere in ``jaxpr``."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found += eqn.params["name"] == kernel
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jex_core.Jaxpr):
                    found += _kernel_calls(sub, kernel)
    return found


@pytest.mark.parametrize("stack", list(STACKS))
@pytest.mark.parametrize("policy, passes", [
    ("dots_saveable", 1), ("dots_with_no_batch_dims_saveable", 1),
    ("checkpoint_dots", 1), ("nothing_saveable", 2)])
def test_the_forward_kernel_runs_once_a_layer_under_a_policy_that_keeps_dots(
        stack, policy, passes):
    """The gradient's jaxpr holds the forward kernel once for each layer
    of a checkpointed body where the policy keeps products, and twice (the
    forward pass, then again in the backward pass) under
    ``nothing_saveable``; the two backward kernels once each either way.
    Both stacks of ``apply`` (the scanned one; the listed one and the
    periods after it) go through the one helper."""
    make, layers = STACKS[stack]
    fn, params = _value_and_grad(make(remat=True, remat_policy=policy))
    jaxpr = jax.make_jaxpr(fn)(params).jaxpr
    assert _kernel_calls(jaxpr, "flash_attention_fwd") == passes * layers
    assert _kernel_calls(jaxpr, "flash_attention_dq") == layers
    assert _kernel_calls(jaxpr, "flash_attention_dkv") == layers


def test_a_layer_keeps_the_result_and_lse_and_under_nothing_saveable_neither():
    """``saved_residuals`` of one checkpointed layer (the listed stack: no
    scan hides it): under ``dots_saveable`` what it keeps beside its
    arguments includes the call's result and its ``lse`` as the kernel
    wrote them (``[B H, S, D]`` and ``[B H, 1, S]``, named ahead of the
    transpose), under ``nothing_saveable`` neither."""
    from jax._src.ad_checkpoint import saved_residuals

    def kept(policy):
        model = _listed(n_layers=1, remat=True, remat_policy=policy)
        params, ids = model.init(jax.random.key(0)), _ids(model)
        return {tuple(a.shape) for a, why in saved_residuals(
            lambda p: model.loss(p, ids), params) if "argument" not in why}

    c = _listed().config
    ours = {(2 * c.n_heads, SEQ, c.head_dim), (2 * c.n_heads, 1, SEQ)}
    assert ours <= kept("dots_saveable")
    assert not ours & kept("nothing_saveable")


@pytest.mark.parametrize("stack", list(STACKS))
def test_loss_and_gradients_are_the_plain_policys_to_the_bit(stack,
                                                             monkeypatch):
    """A kept array in place of an identical recomputed one: against the
    same model under ``jax.checkpoint_policies.dots_saveable`` alone (what
    the look-up gave before), and against the model without
    ``jax.checkpoint``, where a name is the identity."""
    make, _ = STACKS[stack]
    model = make(remat=True, remat_policy="dots_saveable")
    fn, params = _value_and_grad(model)
    got = jax.jit(fn)(params)
    monkeypatch.setattr(
        TransformerConfig, "checkpoint_policy",
        lambda self: getattr(jax.checkpoint_policies, self.remat_policy))
    plain = jax.jit(_value_and_grad(model)[0])(params)
    monkeypatch.undo()
    free = jax.jit(_value_and_grad(make(remat=False))[0])(params)
    for other in (plain, free):
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(other)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_outside_a_checkpoint_a_name_is_the_identity():
    """The kernel called alone, differentiated with no ``jax.checkpoint``
    round it: the jaxpr has the forward once and the values are those of
    a policy that lists the names."""
    q, k, v = (jax.random.normal(key, (1, SEQ, 2, 32))
               for key in jax.random.split(jax.random.key(1), 3))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=True) ** 2)

    grad = jax.grad(loss, argnums=(0, 1, 2))
    assert _kernel_calls(jax.make_jaxpr(grad)(q, k, v).jaxpr,
                         "flash_attention_fwd") == 1
    policy = jax.checkpoint_policies.save_only_these_names(*FLASH_RESIDUALS)
    kept = jax.grad(jax.checkpoint(loss, policy=policy), argnums=(0, 1, 2))
    assert _kernel_calls(jax.make_jaxpr(kept)(q, k, v).jaxpr,
                         "flash_attention_fwd") == 1
    for a, b in zip(grad(q, k, v), kept(q, k, v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", [
    "dots_saveable", "checkpoint_dots", "dots_with_no_batch_dims_saveable",
    "checkpoint_dots_with_no_batch_dims", "everything_saveable",
    "nothing_saveable", "offload_dot_with_no_batch_dims", "no_such_policy"])
def test_one_helper_names_the_policy(name):
    """A policy that keeps products is composed with the two names; every
    other name gives what ``jax.checkpoint_policies`` has under it (None
    for a name it lacks), untouched."""
    c = TransformerConfig.tiny(remat=True, remat_policy=name)
    plain = getattr(jax.checkpoint_policies, name, None)
    assert c.keeps_flash_residuals == (name in PRODUCT_SAVING_POLICIES)
    if name in PRODUCT_SAVING_POLICIES:
        assert c.checkpoint_policy() is not plain
    else:
        assert c.checkpoint_policy() is plain
    assert not TransformerConfig.tiny(
        remat=False, remat_policy=name).keeps_flash_residuals


@pytest.mark.parametrize("remat, policy, itemsize, keeps", [
    (True, "dots_saveable", 2, True), (True, "dots_saveable", 4, True),
    (True, "dots_with_no_batch_dims_saveable", 2, True),
    (True, "nothing_saveable", 2, False), (False, "dots_saveable", 2, False)])
def test_saved_bytes_of_a_layer_and_micro_batch(remat, policy, itemsize,
                                                keeps):
    """``B S H D`` of the compute dtype and ``B H S`` float32 where the
    policy keeps them, 0 where it keeps neither or nothing is
    checkpointed, None where the kernel does not run."""
    B, S, H, D = 2, 256, 4, 16
    model = _dense(remat=remat, remat_policy=policy, max_seq_len=S)
    assert model.saved_attention_bytes(B, S, itemsize) == \
        keeps * (B * S * H * D * itemsize + B * H * S * 4)
    other = CausalTransformerLM(TransformerConfig.tiny(
        remat=remat, remat_policy=policy, attn_impl="reference"))
    assert other.saved_attention_bytes(B, S, itemsize) is None
    assert other.attention_plan(B, S) is None


@pytest.mark.parametrize("remat", [True, False])
def test_the_trainer_sets_the_saved_bytes_as_a_gauge_once(remat, tmp_path):
    """Through the engine (bf16 compute, two micro-batches a step):
    ``train/attn/saved_residual_bytes`` is one layer's and one
    micro-batch's bytes over all shards, not a step's sum, set once beside
    the plan's gauges, and 0 without ``jax.checkpoint``; the stream passes
    the schema check."""
    import json
    import os
    import subprocess
    import sys

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import ATTN_PLAN
    from deepspeed_tpu.monitor.telemetry import get_telemetry
    model = _dense(remat=remat, remat_policy="dots_saveable")
    c = model.config
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.key(0)),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 2,
                "bf16": {"enabled": True},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "telemetry": {"enabled": True, "output_path": str(tmp_path),
                              "job_name": "kept", "hbm_gauges": False,
                              "stall_watchdog": False}})
    B = jax.device_count()               # one micro-batch, all shards
    ids = np.random.default_rng(0).integers(0, c.vocab_size, (2, B, SEQ),
                                            dtype=np.int32)
    losses = [float(engine.train_batch(batch={"input_ids": ids}))
              for _ in range(2)]
    assert np.isfinite(losses).all() and losses[1] < losses[0]
    gauges = get_telemetry().registry.snapshot()["gauges"]
    get_telemetry().close()
    H, D = c.n_heads, c.head_dim
    assert gauges["train/attn/" + ATTN_SAVED]["value"] == \
        remat * (B * SEQ * H * D * 2 + B * H * SEQ * 4)
    stream = tmp_path / "kept" / "events.jsonl"
    events = [json.loads(line) for line in open(stream)]
    names = [e["name"] for e in events if e["kind"] == "gauge"
             and e["name"].startswith("train/attn/")]
    assert sorted(names) == sorted(
        "train/attn/" + n for n in ATTN_PLAN + (ATTN_SAVED,))
    checker = os.path.join(os.path.dirname(__file__), "..", "..", "scripts",
                           "check_telemetry_schema.py")
    assert subprocess.run([sys.executable, checker, str(stream)]
                          ).returncode == 0
