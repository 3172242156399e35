"""The routed ``granitemoehybrid`` family (``granite-4.0-h-small-ep2``:
Mamba-2 and position-free attention layers, each with a softmax-routed
dropless expert layer beside one shared SwiGLU, a chip's share of the
experts) at toy size on the CPU (``data/tiny-granite-routed.json``: 2 of 4
experts held, 2 a token, two periods of mamba, mamba, attention, mamba):
the family served by ``ServingEngine`` against the plain reference, the
shares tied to the uncut layer, the repo's own configuration held to the
published widths, to ``test_contract.py``'s rules and to the arithmetic of
its memory, its entries found BY NAME in ``BENCHMARK.json``, and the new
reader held to its count by hand."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tree
from chipbench import cells, sut, traffic
from chipbench.reducers import serve_mfu_hybrid, serve_mfu_hybrid_routed
from test_contract import share_faults

CELL = "serve-granite4-h-small-ep2-chatfull"
CONFIG = "granite-4.0-h-small-ep2"
# the entries of other cells whose lists this cell joined with PR 53: one
# traced run on the chip read each at PR 51's reading (PERF.md section 5)
JOINED = ["compiles", "idle_pct", "loop_host_ms", "prefill_pad_pct",
          "decode_ms", "prefill_ms_per_ktok", "ahead_pct", "experts_pct",
          "ragged_pct", "ssm_scan_pct", "ssm_conv_pct", "ssm_proj_pct",
          "state_live_pct", "decode_hbm_pct", "ragged_roofline"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TOY = tree.data("tiny-granite-routed")


def _toy_cell(**changed):
    return cells.Cell("tiny-granite-routed", 1, dict(TOY, **changed), {},
                      [], [])


def _toy_model(**changed):
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    cell = _toy_cell(**changed)
    return CausalTransformerLM(TransformerConfig(
        **cell.family.transformer_kwargs(cell.config), remat=False))


@pytest.fixture(scope="module")
def model():
    return _toy_model()


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(51), jnp.float32)


def _without(params, *path):
    """``params`` with the leaves at ``path`` under every layer zeroed."""
    def lose(at, leaf):
        keys = tuple(getattr(k, "key", None) for k in at)
        return leaf * 0 if keys[-len(path):] == path else leaf
    return jax.tree_util.tree_map_with_path(lose, params)


# ---- (a) served through the engine, against the reference ---------------
@pytest.mark.parametrize("fault,least,most", [
    (None, 0.0, 2e-5),
    # the held experts' term left out of every layer
    (("moe", "w_down"), 0.04, np.inf),
])
def test_served_rows_against_the_reference(model, params, fault, least, most):
    """Two slots of different lengths, prefill (29 tokens in a bucket of
    32, 53 in one of 64) and then 12 tokens decoded through state and
    pages: every logits row the engine sampled from against the
    reference's full forward over prompt + output, float32 on both sides
    (2e-5 of the largest reference logit; rows the reference's own routing
    leaves undecided are left out, and are few).  Served WITHOUT the held
    experts' term the same rows are not correct by ``serve_cell``'s 0.04.
    Both serving programs of the hybrid path hand back ``SERVE_COUNTERS``:
    a prefill counts its REAL rows' pairs on the two held experts, eight
    layers, and no padding row's; a decode step its live slots'."""
    from deepspeed_tpu.inference.serving import ServingEngine
    cell = _toy_cell()
    assert cell.family.ROUTED and \
        cell.family.REFERENCE == "granitemoehybrid_routed"
    served = params if fault is None else _without(params, *fault)
    engine = ServingEngine(model, served, max_batch=3, page_size=8,
                           max_seq=160, dtype=jnp.float32,
                           serving={"attention_backend": "jnp"})
    rows, sample = {}, engine._sample

    def keep(req, row):
        rows.setdefault(req.req_id, []).append(np.array(row, np.float32))
        return sample(req, row)

    engine._sample = keep
    rng = np.random.default_rng(51)
    for rid, n in (("a", 29), ("b", 53)):
        engine.add_request(rid, rng.integers(0, TOY["vocab_size"],
                                             n).tolist(), max_new_tokens=12)
    assert engine._counted and engine._stateful
    assert engine.experts_impl == "jnp" and engine.state_impl == "jnp"
    done, seen = {}, []
    while engine.n_active or engine.queue:
        done.update(engine.step())
        seen += engine.last_step["dispatches"]
    assert engine.leak_report() == {}
    first, second, *decodes = seen
    for prefill, real in ((first, 29), (second, 53)):
        assert prefill["phase"] == "prefill" and prefill["real"] == real
        # 2 experts a token x 8 layers chosen; about half held here
        assert 0 < prefill["expert_pairs"] < real * 2 * 8
        assert prefill["expert_load_max"] <= real
        assert prefill["expert_rows"] >= prefill["expert_pairs"]
        assert prefill["state_slots"] == 1
    assert {d["phase"] for d in decodes} == {"decode"}
    for d in decodes:       # two live slots of three
        assert 0 < d["expert_pairs"] <= 2 * 2 * 8 and d["state_slots"] == 2
        assert d["experts"] == "jnp" and d["state"] == "jnp"
    worst, decided_rows = 0.0, 0
    for rid, tokens in done.items():
        got = np.stack(rows[rid])
        ids = jnp.asarray(np.asarray(tokens, np.int32)[None, :-1])
        want, decided = cell.reference.logits(params, ids, TOY,
                                              last=len(got))
        want, decided = np.asarray(want)[0], np.asarray(decided)[0]
        decided_rows += int(decided.sum())
        worst = max(worst, float(np.abs(got - want)[decided].max()
                                 / np.abs(want).max()))
    assert decided_rows >= 20       # of 24
    assert least <= worst <= most


def test_the_parameter_count_is_the_seeded_trees(model, params):
    """``TransformerConfig.num_params`` for a model with state-space
    layers AND held experts, against the leaves of a seeded tree: the
    harness's ``n_params`` and ``mfu_pct.chatmoe`` lean on the sizes."""
    leaves = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert leaves == model.config.num_params()
    uncut = _toy_model(num_local_experts=4, published={})
    shapes = jax.eval_shape(lambda: uncut.init(jax.random.key(0)))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == \
        uncut.config.num_params()


def test_the_toy_cell_runs_through_the_command(tmp_path):
    """``tiny-granite-routed`` under ``tiny-closed`` added to the made-up
    tree as files and entries, as a PR adds a cell, and run traced: the
    harness takes the family's pair (logits, decided), compares the
    decided rows and reads the new reducer through a metric file of its
    own."""
    tmp = tree.make(tmp_path)
    with open(os.path.join(tmp, "chipbench", "configs",
                           "tiny-granite-routed.json"), "w") as f:
        json.dump(TOY, f)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-granite-routed", "source": TOY["source"],
        "file": "chipbench/configs/tiny-granite-routed.json",
        "reduced": TOY["reduced"], "why": "toy width"})
    bench["workloads"].append({
        "name": "tiny-granite-routed", "config": "tiny-granite-routed",
        "traffic": "tiny-closed", "chips": 1,
        "why": "made up for the tests"})
    for metric in bench["end_to_end"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append("tiny-granite-routed")
    with open(os.path.join(cells.ROOT, "chipbench", "layer_metrics",
                           "mfu_pct.chatmoe.json")) as f:
        spec = dict(json.load(f), name="tiny-granite-routed.mfu_pct",
                    workloads=["tiny-granite-routed"])
    with open(os.path.join(tmp, "chipbench", "layer_metrics",
                           spec["name"] + ".json"), "w") as f:
        json.dump(spec, f)
    bench["per_layer"].append({k: spec[k] for k in (
        "name", "unit", "better", "source", "layer", "moves", "workloads")})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    line, earlier = tree.run(tmp, "tiny-granite-routed", seed=2 ** 31 + 51,
                             trace=1)
    assert line["correct"] is True and line["failed"] == 0
    log = earlier[-1]
    assert log["logit_error"] <= 1e-4       # float32 on both sides
    assert log["rows_compared"] + log["rows_undecided"] == 72
    assert log["rows_compared"] >= 60 and log["compiles_in_window"] == 0
    assert log["lost"] == [] and log["leaks"] == {}
    assert 0 < line["metrics"]["tiny-granite-routed.mfu_pct"]["value"] < 100


# ---- (b) the share tied to the model ------------------------------------
def test_the_two_shares_add_up_to_the_uncut_layer():
    """Experts 0-1 and 2-3 of the four-expert toy, the shared SwiGLU
    counted once, are the uncut layer, and the uncut layer is the
    reference's."""
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  ServeCounts)
    from chipbench.reference import granitemoehybrid_routed as reference
    whole = _toy_model(num_local_experts=4, published={})
    stacked = whole.init(jax.random.key(7), jnp.float32)["periods"][0]
    layer = jax.tree_util.tree_map(lambda w: w[1], stacked)
    h = jax.random.normal(jax.random.key(5), (1, 37, TOY["hidden_size"]))
    uncut, _ = whole._mlp_delta(h, layer, train=False)
    counts, total = ServeCounts(jnp.ones((1, 37), bool)), 0.0
    share_config = _toy_model().config
    assert (share_config.moe_num_experts, share_config.experts_held) == (4, 2)
    for first in (0, 2):
        share = CausalTransformerLM(dataclasses.replace(
            share_config, moe_experts_first=first))
        moe = dict(layer["moe"], **{k: layer["moe"][k][first:first + 2]
                                    for k in ("w_gate", "w_up", "w_down")})
        if first:           # the shared SwiGLU on one chip alone
            moe.pop("shared")
        part, _ = share._mlp_delta(h, dict(layer, moe=moe), train=False,
                                   counts=counts)
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=1e-5)
    assert int(counts.counts["expert_pairs"]) == 37 * 2   # every pair, once
    with jax.default_matmul_precision("highest"):
        want, margin = reference._expert_layer(h[0], stacked["moe"], 1, 2, 4)
    np.testing.assert_allclose(uncut[0], want, atol=1e-5)
    assert margin.shape == (37,) and (margin >= 0).all()


# ---- (c) the configuration and its entries ------------------------------
def _config():
    with open(os.path.join(cells.ROOT, "chipbench", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_configuration_is_the_published_widths_and_a_stated_share():
    cfg = _config()
    mix = traffic.load_mix("chatfull-closed")
    model = sut.build_model(cells.Cell(name=CELL, chips=1, config=cfg,
                                       mix=mix, end_to_end=[], per_layer=[]))
    c = model.config
    assert (c.hidden_size, c.n_heads, c.kv_heads, c.head_dim) == \
        (4096, 32, 8, 128)
    assert (c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups,
            c.ssm_conv, c.ssm_chunk) == (128, 64, 128, 1, 4, 256)
    assert (c.ssm_inner, c.ssm_conv_dim) == (8192, 8448)
    assert (c.moe_num_experts, c.experts_held, c.moe_top_k,
            c.moe_ffn_hidden_size, c.moe_shared_experts) == \
        (72, 36, 10, 768, 2)        # one shared SwiGLU of 2 x 768 = 1,536
    assert c.moe_dropless and c.moe_scoring == "softmax" and \
        c.moe_norm_topk_prob and c.moe_experts_first == 0
    assert (c.n_layers, c.vocab_size, c.layer_period, c.leading_layers) == \
        (10, 50176, 10, 0)
    assert c.ssm_pattern == (True,) * 5 + (False,) + (True,) * 4
    assert c.rope_pattern == (False,) * 10
    assert (c.embed_scale, c.attn_scale, c.residual_scale,
            c.final_logit_scale) == (12.0, 0.0078125, 0.22, 0.0625)
    assert c.tie_embeddings and c.norm_eps == 1e-5
    # the cut, stated: exactly four keys, the published values beside them
    assert cfg["reduced"] == ["num_local_experts", "vocab_size",
                              "num_hidden_layers", "layer_types"]
    published = cfg["published"]
    assert (published["num_local_experts"], published["vocab_size"],
            published["num_hidden_layers"]) == (72, 100352, 40)
    assert published["layer_types"] == cfg["layer_types"] * 4
    assert "2 chips" in cfg["deployment"]
    entry = {"reduced": cfg["reduced"]}
    assert share_faults(entry, cfg) == []
    for key in ("source", "assumed", "deployment", "seeded_weights"):
        assert cfg[key]
    # ISSUE 51's count: nine mamba layers of 461.2 M, the attention layer
    # 400.9 M, half the tied table 205.5 M
    shapes = jax.eval_shape(lambda k: model.init(k, jnp.bfloat16),
                            jax.random.key(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n_params == c.num_params() == 4_757_211_776
    moe = shapes["periods"][0]["moe"]
    assert moe["wg"].shape == (1, 4096, 72)
    assert moe["w_gate"].shape == moe["w_up"].shape == (1, 36, 4096, 768)
    assert moe["w_down"].shape == (1, 36, 768, 4096)
    assert moe["shared"]["w_up"].shape == (1, 4096, 1536)
    assert shapes["periods"][0]["ssm"]["w_in"].shape == (1, 4096, 16768)
    assert shapes["periods"][5]["wk"].shape == (1, 4096, 1024)
    engine = cfg["serve"]["engine"]
    pools = jax.eval_shape(lambda: model.init_paged_caches(
        engine["num_pages"], engine["page_size"],
        state_slots=mix["max_batch"]))
    # heads of 128 fill a lane row: nothing packed
    assert pools.full.k_pages.shape == (1, 1025, 8, 128, 128)
    assert pools.ssm.state.shape == (9, 64, 128, 64, 128)
    assert pools.ssm.state.dtype == jnp.float32
    assert pools.ssm.conv.shape == (9, 64, 3 * 8448)
    held = 2 * n_params + sum(x.size * x.dtype.itemsize
                              for x in jax.tree_util.tree_leaves(pools))
    assert abs(held - 12.50e9) < 0.01e9         # 78 % of the chip
    sizes = cells.importlib.import_module(
        "chipbench.families.granitemoehybrid_routed").model_sizes(cfg, engine)
    assert (sizes["expert_ffn"], sizes["shared_ffn"], sizes["experts_held"],
            sizes["experts_published"], sizes["experts_per_token"]) == \
        (768, 1536, 36, 72, 10)
    assert (sizes["ssm_layers"], sizes["attn_layers"],
            sizes["state_bytes"]) == (9, 1, 128 * 64 * 128 * 4)
    # the micro's engine settings, to the letter: the two cells are a pair
    with open(os.path.join(cells.ROOT, "chipbench", "configs",
                           "granite-4.0-h-micro.json")) as f:
        micro = json.load(f)
    assert engine == micro["serve"]["engine"]
    assert cfg["seeded_weights"]["ssm"] == micro["seeded_weights"]["ssm"]


def test_the_benchmark_holds_the_cells_entries_by_name():
    """One configuration, one cell on one chip under the micro's mix, its
    name under ``serve_tok_s`` alone, ONE per-layer metric whose file
    agrees with its entry, beside the lists of other cells' entries it
    joined: found by the cell's membership of ``workloads``, wherever later
    PRs' additions put the end of the lists."""
    bench = _bench()
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "chatfull-closed", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    held = _config()
    assert held["reduced"] == config["reduced"]
    assert held["source"] == config["source"]
    assert config["file"] == f"chipbench/configs/{CONFIG}.json"
    assert [m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", ())] == ["serve_tok_s"]
    entries = tree.held_entries(CELL, moves="serve_tok_s")
    # its own entry, and the lists it joined (PR 53): each an entry whose
    # reducer and arguments hold for this cell as they stand
    (entry,) = [m for m in entries if m["workloads"] == [CELL]]
    assert entry["name"] == "mfu_pct.chatmoe"
    assert sorted(tree.base(m["name"]) for m in entries
                  if m is not entry) == sorted(JOINED)
    with open(os.path.join(cells.ROOT, "chipbench", "layer_metrics",
                           "mfu_pct.chatmoe.json")) as f:
        spec = json.load(f)
    assert {k: spec[k] for k in entry} == entry
    assert spec["reducer"] == "serve_mfu_hybrid_routed"
    assert len(bench["per_layer"]) <= 128        # the driver's contract
    loaded = cells.load_cell(CELL)
    assert [m["name"] for m in loaded.end_to_end] == ["serve_tok_s",
                                                      "setup_s"]
    assert "mfu_pct.chatmoe" in [m["name"] for m in loaded.per_layer]


# ---- (d) the reader on a recorded run -----------------------------------
def test_the_operations_count_the_pairs_held_not_the_pairs_chosen():
    """``serve_mfu_hybrid_routed`` on two made-up steps, against the count
    by hand: the mixers and the shared SwiGLU a token, the router's 72
    outputs, ``3 x 4096 x 768`` multiply-adds a pair of ``expert_pairs``
    (the dispatch's own count: about half of ten a token), the attention
    layer's scores and values, the recurrence, the head."""
    cfg = _config()
    sizes = cells.importlib.import_module(
        "chipbench.families.granitemoehybrid_routed").model_sizes(
            cfg, cfg["serve"]["engine"])
    steps = [
        {"t0": 0.0, "t1": 0.25, "dispatches": [
            {"phase": "prefill", "tokens": 512, "real": 300, "context": 300,
             "expert_pairs": 14_800}]},
        {"t0": 0.25, "t1": 0.3, "dispatches": [
            {"phase": "decode", "tokens": 1, "contexts": [301, 77],
             "expert_pairs": 97}]}]
    attention = 2 * 4096 * 4096 + 2 * 4096 * 1024
    mixer = 4096 * (8192 + 8448 + 128) + 8192 * 4096 + 4 * 8448
    token = attention + 9 * mixer + 10 * (3 * 4096 * 1536 + 4096 * 72)
    assert serve_mfu_hybrid.token_macs(sizes) == token - 10 * 4096 * 72
    pair = 3 * 4096 * 768
    keys = 300 * 301 // 2 + 301 + 77
    flops = 2 * (302 * token + keys * 2 * 32 * 128 + (14_800 + 97) * pair
                 + 3 * 4096 * 50176) + 302 * 9 * 6 * 8192 * 128
    run = cells.Run(chips=1, peaks=PEAKS, model=dict(sizes, n_params=0),
                    steps=steps, traced_steps=[], samples={}, counters={},
                    memory_peak_bytes=0)
    assert serve_mfu_hybrid_routed.read(run) == pytest.approx(
        100 * flops / 0.3 / 197e12)
    assert 0 < serve_mfu_hybrid_routed.read(run) < 100
    # ten a token, ten layers, would count twice the pairs: over a
    # quarter more operations than the chip did
    chosen = 2 * (302 * 10 * 10 - 14_897) * pair
    assert chosen > 0.25 * flops
    # a program whose dispatches carry no count: the pairs read as none,
    # and another family's sizes as nothing to read
    bare = [dict(s, dispatches=[{k: v for k, v in d.items()
                                 if k != "expert_pairs"}
                                for d in s["dispatches"]]) for s in steps]
    run.steps = bare
    assert serve_mfu_hybrid_routed.read(run) == pytest.approx(
        100 * (flops - 2 * 14_897 * pair) / 0.3 / 197e12)
    run.model = {k: v for k, v in sizes.items() if k != "expert_ffn"}
    assert serve_mfu_hybrid_routed.read(run) is None
