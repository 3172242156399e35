"""A model whose layers are state-space (Mamba-2) or attention, over whole
sequences: ``CausalTransformerLM.apply`` against the plain reference of
``chipbench/reference/granitemoehybrid.py`` (the token-by-token recurrence)
at toy widths (``tests/chipbench/data/tiny-granite.json``), the seeding of
the recurrence's time constants, and what the configuration counts.

Tolerances: in float32 both sides differ by the order of sums (the chunked
scan against the recurrence): 2e-5 of the largest logit.  In bfloat16 the
program against the float32 reference ON THE SAME bf16 weights is held to
the harness's own limit, 0.04 of max(1, largest reference logit): eight
layers of bf16 residual adds read 0.01."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import granitemoehybrid as family
from chipbench.reference import granitemoehybrid as reference
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(REPO, "tests", "chipbench", "data",
                       "tiny-granite.json")) as f:
    CFG = json.load(f)


def _model(**changed):
    return CausalTransformerLM(TransformerConfig(**dict(
        family.transformer_kwargs(CFG), remat=False, attn_impl="reference",
        **changed)))


def _ids(shape, seed=3):
    return jax.random.randint(jax.random.key(seed), shape, 0,
                              CFG["vocab_size"])


@pytest.mark.parametrize("length", [45, 16, 7, 1])
def test_the_whole_sequence_forward_is_the_reference_in_float32(length):
    """Prompts of 45 (two chunks of 16 and 13 rows), a whole chunk, under a
    chunk, and one row (the one-row update)."""
    model = _model()
    params = model.init(jax.random.key(1))
    ids = _ids((2, length))
    ours = model.apply(params, ids, train=False)
    want = reference.logits(params, ids, CFG)
    assert ours.shape == want.shape == (2, length, CFG["vocab_size"])
    assert float(jnp.max(jnp.abs(ours - want)) / jnp.max(jnp.abs(want))) \
        < 2e-5


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_in_bfloat16_it_stays_inside_the_harnesss_tolerance(seed):
    from chipbench import sut
    model = _model()
    params = model.init(sut.key_for(seed), jnp.bfloat16)
    ids = _ids((2, 60), seed % 1000)
    ours = np.asarray(model.apply(params, ids, train=False), np.float32)
    want = np.asarray(reference.logits(params, ids, CFG))
    error = np.abs(ours - want).max() / max(1.0, np.abs(want).max())
    assert 1e-4 < error < 0.04, error


def test_the_recurrence_is_seeded_with_time_constants_of_one_to_a_thousand():
    """Mamba-2's own initialiser, not the values a checkpoint would
    overwrite (which forget within two tokens): dt log-uniform in [0.001,
    0.1] through dt_bias, A uniform in [1, 16], D ones, the conv within
    1 / sqrt(4); so a head keeps a token for 1 / (dt A): from under a token
    to a thousand."""
    cfg = dict(CFG, mamba_n_heads=64, mamba_d_head=8)
    model = CausalTransformerLM(TransformerConfig(
        **family.transformer_kwargs(cfg), remat=False))
    params = model.init(jax.random.key(5))
    assert params["layers"] == [] and len(params["periods"]) == 4
    assert "ssm" not in params["periods"][2]        # the attention layer
    for at in (0, 1, 3):
        w = params["periods"][at]["ssm"]
        dt = np.asarray(jax.nn.softplus(w["dt_bias"]))
        A = np.exp(np.asarray(w["A_log"]))
        assert dt.shape == A.shape == (2, 64)
        assert 0.001 <= dt.min() and dt.max() <= 0.1 + 1e-6
        assert dt.min() < 0.003 and dt.max() > 0.03
        assert 1.0 <= A.min() < 3 and 12 < A.max() <= 16.0
        keeps = 1.0 / (dt * A)
        assert keeps.min() < 3 and keeps.max() > 200
        assert np.all(np.asarray(w["D"]) == 1.0)
        assert np.all(np.asarray(w["norm"]) == 1.0)
        assert np.abs(np.asarray(w["conv_w"])).max() <= 0.5
        assert np.abs(np.asarray(w["conv_b"])).max() <= 0.5
        assert w["w_in"].shape == (2, 256, 2 * 512 + 2 * 32 + 64)
    # two periods from two keys: the stacked layers differ
    first = params["periods"][0]["ssm"]["w_in"]
    assert not np.allclose(np.asarray(first[0]), np.asarray(first[1]))


def test_what_the_configuration_counts():
    model = _model()
    c = model.config
    assert c.has_ssm and c.layers_listed and not c.counts_serving
    assert (c.layer_period, c.leading_layers) == (4, 0)
    assert [c.layer_ssm(i) for i in range(4)] == [True, True, False, True]
    assert c.ssm_inner == 2 * c.hidden_size
    assert c.ssm_conv_dim == c.ssm_inner + 2 * c.ssm_state
    params = model.init(jax.random.key(0))
    assert c.num_params() == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    assert len(model.layer_list(params)) == 8
    plain = TransformerConfig.tiny()
    assert not plain.has_ssm and not plain.layer_ssm(0) \
        and not plain.layers_listed


@pytest.mark.parametrize("changed,words", [
    ({"ssm_pattern": (True, False)}, "ssm_pattern has 2 entries"),
    ({"ssm_state": 0}, "needs its sizes"),
    ({"ssm_groups": 3}, "needs its sizes"),
    ({"local_attn_pattern": (0, 0, 8, 0) * 2}, "plain attention layers"),
    ({"ssm_pattern": (True, True, False, True, True, False, True, True)},
     "does not repeat"),
])
def test_a_pattern_the_model_cannot_hold_is_refused(changed, words):
    with pytest.raises(AssertionError) as refused:
        _model(**changed)
    assert words in str(refused.value)


def test_a_layer_pattern_needs_a_listed_stack_and_says_which():
    with pytest.raises(AssertionError) as refused:
        CausalTransformerLM(TransformerConfig.tiny(layer_period=2))
    for reason in ("expert layers", "latent attention",
                   "state-space layers"):
        assert reason in str(refused.value)


def test_the_dense_cache_path_is_refused_by_name():
    with pytest.raises(NotImplementedError) as refused:
        _model().init_caches(1, 32)
    assert "init_paged_caches" in str(refused.value)
    with pytest.raises(AssertionError):
        _model().init_paged_caches(9, 8)        # no state_slots


def test_a_gradient_goes_through_the_chunked_scan():
    model = _model()
    params = model.init(jax.random.key(2))
    loss, grads = jax.value_and_grad(model.loss)(params, _ids((2, 40)))
    assert np.isfinite(float(loss))
    norms = [float(jnp.linalg.norm(g)) for g in
             jax.tree_util.tree_leaves(grads["periods"][0]["ssm"])]
    assert all(np.isfinite(n) and n > 0 for n in norms)
