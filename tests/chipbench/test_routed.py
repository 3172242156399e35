"""``correct`` for a model that routes tokens to experts: the serving
check compares the rows that the reference's own routing leaves decided.
The made-up family of ``data/routed/`` (four experts, one a token, served
through ``ServingEngine`` in float32) stands for it; the cases call the
comparison itself, ``serve_cell._check_against_reference``, with weights
rigged by hand, and one case holds a dense family's result to the parent's
arithmetic, bit for bit."""

import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from chipbench import cells, reduce, serve_cell, sut, traffic
from chipbench.reducers import kernel_roofline

import tree
from tree import DATA

SEED = 2 ** 31 + 11


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def routed():
    """The made-up routed cell, as ``serve_cell`` sees one (its family and
    reference are files of the tests, loaded from where they lie)."""
    return types.SimpleNamespace(
        config=tree.data("tiny-routed"), mix=tree.data("tiny-closed"),
        family=_module(os.path.join(DATA, "routed", "family.py"), "family"),
        reference=_module(os.path.join(DATA, "routed", "reference.py"),
                          "reference"))


def _weights(cell, dtype=jnp.float32, seed=SEED):
    return sut.seeded_weights(sut.build_model(cell), seed, dtype,
                              jax.devices()[:1])


def _check(cell, served, reference_weights, check=None, seed=SEED):
    """The comparison, with the program serving ``served`` and the
    reference given ``reference_weights``."""
    dtype = cell.config["serve"]["dtype"]
    inference = deepspeed_tpu.init_inference(
        model=sut.build_model(cell), params=served, dtype=dtype)
    engine = inference.create_serving_engine(
        max_batch=int(cell.mix["max_batch"]), **cell.config["serve"]["engine"])
    return (check or serve_cell._check_against_reference)(
        cell, engine, reference_weights, seed)


def _with_router(params, layer, fn):
    """``params`` with the router of ``layer`` replaced by ``fn`` of it."""
    layers = list(params["layers"])
    moe = dict(layers[layer]["moe"])
    moe["wg"] = fn(moe["wg"])
    layers[layer] = dict(layers[layer], moe=moe)
    return dict(params, layers=layers)


def test_seeded_weights_are_compared_whole(routed):
    weights = _weights(routed)
    check = _check(routed, weights, weights)
    assert check["ok"] and check["logit_error"] < 1e-5
    assert check["rows_compared"] + check["rows_undecided"] == \
        serve_cell.CHECK_PROMPTS * serve_cell.CHECK_DECODE_TOKENS
    assert check["rows_compared"] >= 70     # MARGIN masks about 1 in 500


def test_near_tie_rows_are_masked_and_the_run_is_correct(routed):
    """The LAST layer's router scores experts 0 and 1 alike in the
    reference's weights (what it feeds reaches no other token); the
    program's copy differs by 1e-6, as two precisions differ by more.  Where
    those two lead, the program picks by the sign of noise and the
    reference picks expert 0: rows a whole expert apart, neither wrong."""
    weights = _weights(routed)
    last = len(weights["layers"]) - 1
    tied = _with_router(weights, last, lambda wg: wg.at[:, 1].set(wg[:, 0]))
    noise = 1e-6 * jax.random.normal(jax.random.key(5),
                                     tied["layers"][last]["moe"]["wg"][:, 1]
                                     .shape)
    served = _with_router(tied, last, lambda wg: wg.at[:, 1].add(noise))
    check = _check(routed, served, tied)
    assert check["ok"], check
    assert check["logit_error"] < 1e-4 < serve_cell.LOGIT_TOL
    assert 10 <= check["rows_undecided"] < check["rows_compared"]
    assert check["logit_error_undecided"] > serve_cell.LOGIT_TOL
    # the same run, held on every row as the parent's check held it, fails
    routed.reference.MARGIN = 0.0
    unmasked = _check(routed, served, tied)
    assert not unmasked["ok"] and unmasked["rows_undecided"] == 0
    assert unmasked["logit_error"] == check["logit_error_undecided"]


def test_swapped_experts_fail_on_decided_rows(routed):
    weights = _weights(routed)
    layers = list(weights["layers"])
    moe = dict(layers[0]["moe"])
    for name in ("w_up", "w_gate", "w_down"):
        moe[name] = moe[name].at[jnp.array([2, 3])].set(
            moe[name][jnp.array([3, 2])])
    layers[0] = dict(layers[0], moe=moe)
    check = _check(routed, dict(weights, layers=layers), weights)
    assert not check["ok"]
    assert check["logit_error"] > serve_cell.LOGIT_TOL
    assert check["rows_compared"] >= 70


@pytest.mark.parametrize("margin,compared", [
    (float("inf"), 0),      # a reference that masks every row
    (0.9, None),            # ... or most rows: under half are compared
])
def test_a_check_that_compares_too_little_is_not_correct(routed, margin,
                                                         compared):
    routed.reference.MARGIN = margin
    weights = _weights(routed)
    check = _check(routed, weights, weights)
    total = check["rows_compared"] + check["rows_undecided"]
    assert not check["ok"], check
    if compared is None:    # what was compared agreed; it was too little
        assert 0 < check["rows_compared"] < total / 2
        assert check["logit_error"] < 1e-5
    else:
        assert check["rows_compared"] == compared
        assert check["logit_error"] is None


def test_a_prompt_with_no_row_compared_is_not_correct(routed):
    """Most rows compared and all of them right, yet one prompt has none."""
    real = routed.reference.logits
    calls = []

    def logits(params, ids, cfg, last=None):
        want, decided = real(params, ids, cfg, last=last)
        calls.append(1)
        return want, decided & (len(calls) != 2)

    routed.reference.logits = logits
    weights = _weights(routed)
    check = _check(routed, weights, weights)
    assert not check["ok"]
    assert check["rows_compared"] >= 46 and check["logit_error"] < 1e-5


def test_only_a_routed_family_may_mask(routed):
    routed.family.ROUTED = False
    weights = _weights(routed)
    with pytest.raises(TypeError, match="does not declare ROUTED"):
        _check(routed, weights, weights)


def test_a_row_that_is_not_a_number_fails(routed):
    weights = _weights(routed)
    broken = dict(weights, final_norm=weights["final_norm"].at[0].set(
        jnp.nan))
    check = _check(routed, broken, weights)
    assert not check["ok"] and np.isnan(check["logit_error"])


# ---- served in bfloat16: a choice that flips in the CONTEXT -------------
BF16_MARGIN = 0.02      # flips seen at margins up to 0.0154 (reference.py)
SHORT = {"dist": "uniform", "min": 4, "max": 16}    # contexts of 4-40


def _bf16_checks(routed, seed, prompt_tokens=None):
    """The seeded program served in bfloat16, held (a) on every row whose
    OWN token's routing is decided, as ``serve_cell.py`` reckons is enough
    for a many-expert model, and (b) on the rows that have no undecided
    token before them either."""
    routed.config["serve"]["dtype"] = "bfloat16"
    if prompt_tokens:
        routed.mix["prompt_tokens"] = prompt_tokens
    weights = _weights(routed, jnp.bfloat16, seed)
    routed.reference.MARGIN = BF16_MARGIN
    own = _check(routed, weights, weights, seed=seed)
    routed.reference.CONTEXT_MARGIN = BF16_MARGIN
    return own, _check(routed, weights, weights, seed=seed)


@pytest.mark.parametrize("seed", [4, 5])
def test_bf16_context_flips_fail_rows_that_their_own_token_decides(routed,
                                                                   seed):
    """The one failure of the own-token rule that was ever seen: with one
    expert of four a token and this family's own prompts (20-120 tokens),
    a flip at an earlier token moves later rows past the tolerance, and a
    correct program fails on decided rows.  A reference that also masks
    the rows after an undecided token holds every row it leaves to the
    tolerance (the cure is a rule in the reference file, through the same
    pair), but leaves so few that the floor fails the run all the same: a
    family that needs the context rule at its cell's lengths cannot be
    judged by this check (PERF.md §7)."""
    own, context = _bf16_checks(routed, seed)
    assert own["rows_compared"] >= 60
    assert own["logit_error"] > serve_cell.LOGIT_TOL and not own["ok"]
    assert context["logit_error"] < serve_cell.LOGIT_TOL / 2
    assert 0 < context["rows_compared"] < 36 and not context["ok"]


@pytest.mark.parametrize("seed", [1, 7])
def test_bf16_context_rule_passes_the_floor_on_short_contexts(routed, seed):
    """Contexts of 4-40 tokens: a third of the rows have an undecided
    token before them, the rest are compared and right."""
    own, context = _bf16_checks(routed, seed, SHORT)
    assert context["ok"], context
    assert own["rows_undecided"] + 10 <= context["rows_undecided"] < 36
    assert context["logit_error"] < serve_cell.LOGIT_TOL / 2


def _parent_check(cell, engine, params, seed):
    """``_check_against_reference`` as PR 25's commit had it (the rows
    through ``_logits_rows``, which took its ``Probe``'s place)."""
    lengths = traffic.quantile_grid(cell.mix["prompt_tokens"],
                                    serve_cell.CHECK_PROMPTS)
    vocab = cell.config["vocab_size"]
    prompts = {}
    done = {}
    with serve_cell._logits_rows(engine) as kept:
        for i, n in enumerate(lengths):
            rid = f"check-{i}"
            prompts[rid] = traffic.rng_for(seed, 5, i).integers(
                0, vocab, int(n), dtype=np.int32)
            engine.add_request(rid, prompts[rid],
                               max_new_tokens=serve_cell.CHECK_DECODE_TOKENS)
        while len(done) < len(prompts):
            done.update(engine.step())
    worst = 0.0
    for rid, prompt in prompts.items():
        rows = np.stack(kept[rid])
        ids = np.asarray(done[rid], np.int32)[None, :-1]
        want = np.asarray(cell.reference.logits(
            params, jnp.asarray(ids), cell.config, last=len(rows)))[0]
        scale = max(1.0, float(np.max(np.abs(want))))
        worst = max(worst, float(np.max(np.abs(rows - want))) / scale)
    return worst


def test_a_dense_family_reads_what_the_parent_read():
    """Same rows, same tolerance on each, the same number to the bit."""
    cell = cells.Cell("tiny-doc", 1, tree.data("tiny-olmo2"),
                      tree.data("tiny-closed"), [], [])
    weights = _weights(cell, jnp.bfloat16)
    check = _check(cell, weights, weights)
    parent = _check(cell, weights, weights, check=_parent_check)
    assert check["logit_error"] == parent and 0 < parent < 0.04
    assert check == {"ok": True, "logit_error": parent,
                     "logit_error_undecided": None, "rows_compared": 72,
                     "rows_undecided": 0}


def test_reference_matches_the_program(routed):
    """The made-up reference against the program's own forward pass."""
    model = sut.build_model(routed, remat=False, attn_impl="reference")
    weights = _weights(routed)
    ids = jax.random.randint(jax.random.key(3), (2, 48), 0,
                             routed.config["vocab_size"])
    ours = model.apply(weights, ids, train=False)
    want, decided = routed.reference.logits(weights, ids, routed.config)
    assert float(jnp.max(jnp.abs(ours - want))) < 1e-5
    assert decided.shape == (2, 48) and decided.dtype == bool
    last, decided_last = routed.reference.logits(weights, ids, routed.config,
                                                 last=5)
    assert jnp.allclose(last, want[:, -5:], atol=1e-5)
    assert (decided_last == decided[:, -5:]).all()


# ---- kernel costs and a family's sizes, as files -----------------------
def test_kernel_roofline_reads_a_cost_file(routed, monkeypatch):
    """``kernel_roofline`` over a recorded TPU trace with a cost that is a
    file of its own, reading the family's ``model_sizes`` and the
    configuration's published widths."""
    import chipbench.costs
    monkeypatch.setattr(chipbench.costs, "__path__",
                        list(chipbench.costs.__path__)
                        + [os.path.join(DATA, "routed")])
    trace = reduce.load_xplane(os.path.join(DATA, "small_trace.xplane.pb"))
    cfg = routed.config
    steps = [{"tokens": 4096}] * 3
    run = cells.Run(
        chips=1, peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        model=routed.family.model_sizes(cfg, cfg["serve"]["engine"]),
        steps=steps, traced_steps=steps, samples={}, counters={},
        memory_peak_bytes=0, trace=trace, config=cfg)
    kernel_s = sum(reduce.op_seconds(trace, "pallas").values())
    flops = 2 * 3 * 64 * 128 * 3 * 4096 * 1
    nbytes = 2 * 3 * 64 * 128 * 4 * 3
    least = 2 * max(flops / 197e12, nbytes / 819e9)
    assert kernel_roofline.read(run, "expert_cost") == pytest.approx(
        100.0 * least / kernel_s)
    with pytest.raises(ValueError, match="no chipbench/costs/nothing.py"):
        kernel_roofline.read(run, "nothing")
