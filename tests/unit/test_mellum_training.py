"""A model of the ``mellum`` family on the trainer's forward
(``CausalTransformerLM.apply`` scanning a period of three window layers
and a YaRN full layer over the dropless expert layer): ``loss`` and its
gradients against ``jax.grad`` of the plain reference
(``chipbench/reference/mellum.py:loss``) on seeded float32 weights, with
the balance term off and on; the rotary kinds against numbers written out
from the formula; ``num_params`` against the tree ``init`` makes.

Tolerance 5e-5 of each leaf's largest entry: both sides are float32 and
differ in the order of their sums alone (the flash kernel's blocks, the
sorted rows of the grouped product); bf16 operands anywhere would miss by
4e-3."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import mellum as family
from chipbench.reference import mellum as reference
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.ops.latent_attention import RopeYarn

DATA = os.path.join(os.path.dirname(__file__), "..", "chipbench", "data")
TOL = 5e-5


def _toy(attn_impl="reference", **changed):
    with open(os.path.join(DATA, "tiny-mellum.json")) as f:
        cfg = dict(json.load(f), **changed)
    model = CausalTransformerLM(TransformerConfig(
        **family.transformer_kwargs(cfg),
        **dict(cfg["train"]["model"], attn_impl=attn_impl)))
    return cfg, model


@pytest.mark.parametrize("coef", [0.0, 0.01])
def test_loss_and_gradients_are_the_plain_references(coef):
    cfg, model = _toy(moe_aux_loss_coef=coef)
    c = model.config
    assert c.layer_period == 4 and c.leading_layers == 0
    assert [c.layer_rotary(i) for i in range(4)] == [True] * 3 + ["yarn"]
    params = model.init(jax.random.key(3))
    assert params["layers"] == [] and len(params["periods"]) == 4
    ids = jax.random.randint(jax.random.key(4), (2, 64), 0, c.vocab_size)
    with jax.default_matmul_precision("highest"):
        (got, counters), grads = jax.jit(jax.value_and_grad(
            lambda p: model.loss(p, ids, counted=True), has_aux=True))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, ids, cfg, aux_coef=coef)))(params)
    assert abs(float(got) - float(want)) <= 2e-6 * float(want)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path
        assert float(jnp.max(jnp.abs(g - w))) <= TOL * scale, path
    # 2 x 64 tokens x 4 choices, a quarter of the experts held: the pairs
    # counted over the 4 layers, every one computed (rows >= pairs)
    pairs, load_max, rows = (int(v) for v in counters)
    assert model.train_counters == ("expert_pairs", "expert_load_max",
                                    "expert_rows")
    assert 0 < pairs <= 4 * 128 * 4 and pairs <= rows and load_max <= 128
    if coef:    # the term weighs: E sum f P is about 1 a layer
        plain = float(jax.jit(lambda p: reference.loss(p, ids, cfg))(params))
        assert 0.5 * 4 * coef < float(want) - plain < 2 * 4 * coef


@pytest.mark.parametrize("window", [16, 0])
def test_a_static_window_reaches_the_flash_kernel_under_its_kinds_name(
        window):
    """``mix_full`` with the layer's window as a Python int (a scanned
    period's place): the flash kernel through the interpreter, grouped
    queries, against XLA's attention under the same mask; the work is
    named by the layer's kind."""
    _, flash = _toy("pallas", )
    _, plain = _toy("reference")
    q, k, v = (jax.random.normal(key, (1, 64, heads, 32))
               for key, heads in zip(jax.random.split(jax.random.key(0), 3),
                                     (4, 2, 2)))
    layer = {"attn_window": window}
    run = lambda m: m.mix_full(q, k, v, layer, None)[0]     # noqa: E731
    assert np.allclose(jax.jit(lambda: run(flash))(), run(plain), atol=2e-5)
    text = jax.jit(lambda: run(plain)).lower().as_text(debug_info=True)
    assert ("attn_window" if window else "attn_full") in text
    assert ("attn_full" if window else "attn_window") not in text


def test_the_trainer_sets_the_flash_kernels_plan_as_gauges(tmp_path):
    """``train/attn/*``: what the flash kernels of one optimizer step
    visit, mask, compute and need, from ``flash_plan`` over the layers'
    static windows (three of 16, one full), the heads and both
    micro-batches; set once, and admitted by the schema check."""
    import subprocess
    import sys

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import ATTN_PLAN, ATTN_SAVED
    from deepspeed_tpu.monitor.telemetry import get_telemetry
    from deepspeed_tpu.ops.pallas.flash_attention import flash_plan
    from deepspeed_tpu.parallel import groups
    _, model = _toy("pallas")
    c = model.config
    assert _toy("reference")[1].attention_plan(2, 64) is None
    groups.reset_mesh()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.key(0)),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "telemetry": {"enabled": True, "output_path": str(tmp_path),
                              "job_name": "plan", "hbm_gauges": False,
                              "stall_watchdog": False}})
    seqs = 2 * jax.device_count()           # of one micro-batch, all shards
    ids = np.random.default_rng(0).integers(0, c.vocab_size, (2, seqs, 64),
                                            dtype=np.int32)
    for _ in range(2):
        engine.train_batch(batch={"input_ids": ids})
    gauges = get_telemetry().registry.snapshot()["gauges"]
    get_telemetry().close()
    groups.reset_mesh()
    window, full = (flash_plan(64, 64, 64, True, w) for w in (16, 0))
    calls = 3 * 2 * seqs * c.n_heads    # kernels, micro-batches, sequences
    for name in ATTN_PLAN:
        assert gauges["train/attn/" + name]["value"] == \
            calls * (3 * window[name] + full[name]), name
    assert window["pairs_needed"] < full["pairs_needed"] == 64 * 65 // 2
    stream = tmp_path / "plan" / "events.jsonl"
    events = [json.loads(line) for line in open(stream)]
    # the toy's layers are checkpointed under ``dots_saveable``: a layer
    # keeps a micro-batch's result (float32 here) and ``lse``
    assert gauges["train/attn/" + ATTN_SAVED]["value"] == \
        seqs * 64 * c.n_heads * (c.head_dim * 4 + 4)
    assert sum(e["kind"] == "gauge" and e["name"].startswith("train/attn/")
               for e in events) == len(ATTN_PLAN) + 1       # once, not a step
    checker = os.path.join(os.path.dirname(__file__), "..", "..", "scripts",
                           "check_telemetry_schema.py")
    assert subprocess.run([sys.executable, checker, str(stream)]
                          ).returncode == 0


def test_yarn_rotate_half_frequencies_and_magnitude():
    """The published full-attention rotary (theta 500,000, factor 16 over
    8,192, beta 32 / 1, head 128), from the formula by hand: dimension i
    turns 8192 theta^(-i/64) / 2 pi times; 32 turns at i = 18.08 and one
    at i = 34.99, so pairs 0-18 keep their frequency, pairs 35-63 are
    divided by 16, and pair 26 is (26 - 18) / 17 of the way."""
    theta, half = 500000.0, 64
    yarn = RopeYarn(16.0, 8192, 32.0, 1.0, 1.0, 0.0)
    got = np.asarray(yarn.inv_freq(128, theta))
    plain = theta ** (-np.arange(half) / half)
    assert np.allclose(got[:19], plain[:19], rtol=1e-12)
    assert np.allclose(got[35:], plain[35:] / 16, rtol=1e-12)
    ramp = 8 / 17
    assert np.isclose(got[26], plain[26] * (1 - ramp) + plain[26] / 16 * ramp,
                      rtol=1e-12)
    assert np.isclose(yarn.rotary_magnitude, 1.2772588722239782, rtol=1e-12)
    assert yarn.softmax_factor == 1.0
    # the reference's own table (HF's clamps) is the same table
    assert np.allclose(reference.yarn_inv_freq(128, theta, 16, 8192, 32, 1),
                       got, rtol=1e-12)
    # and the family hands the program exactly this specification
    with open(os.path.join(DATA, "..", "..", "..", "chipbench", "configs",
                           "mellum2-12b-ep4.json")) as f:
        kwargs = family.transformer_kwargs(json.load(f))
    assert np.allclose(kwargs["rope_yarn"], tuple(yarn), rtol=1e-12)
    assert kwargs["rope_theta"] == theta


def test_a_full_layers_q_and_k_are_the_magnitude_times_a_plain_layers():
    _, model = _toy()
    zero = jnp.zeros((1, 8), jnp.int32)      # position 0: no turn at all
    layer = {"wq": jnp.eye(64, 128), "wk": jnp.eye(64, 64),
             "wv": jnp.eye(64, 64), "q_norm": jnp.ones(32),
             "k_norm": jnp.ones(32)}
    h = jax.random.normal(jax.random.key(1), (1, 8, 64))
    plain, _, _ = model._qkv(h, layer, 1, 8, zero, rotary=True)
    full, _, _ = model._qkv(h, layer, 1, 8, zero, rotary="yarn")
    assert np.allclose(full, plain * 1.138629436111989, rtol=1e-6)


@pytest.mark.parametrize("kwargs", [
    dict(),                                             # dense
    dict(qk_norm="rms", n_kv_heads=2),
    dict(moe_num_experts=4, moe_top_k=2),               # the capacity layer
    dict(moe_num_experts=4, moe_top_k=2, moe_layer_freq=2),
])
def test_num_params_counts_what_init_makes(kwargs):
    config = TransformerConfig.tiny(**kwargs)
    params = CausalTransformerLM(config).init(jax.random.key(0))
    assert config.num_params() == sum(
        x.size for x in jax.tree_util.tree_leaves(params))


def test_num_params_counts_the_held_experts_of_a_dropless_model():
    _, model = _toy()
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert model.config.num_params() == sum(
        x.size for x in jax.tree_util.tree_leaves(shapes))
