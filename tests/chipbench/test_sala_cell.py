"""The ``minicpm_sala`` family through the command on the CPU: a toy
configuration of the shape of MiniCPM-SALA's held slice
(``data/tiny-sala.json``: a block-sparse layer, two linear-attention
layers, a block-sparse layer; block 4, kernel 2 / 1, top-6, window 8,
``dense_len`` 32) is added to the made-up tree as files and entries, as a
PR adds a cell, and run untraced and traced; the family's reference is
held to the program, the repo's own configuration to the catalog row and
to the arithmetic of its memory, its entries to ISSUE 56's list as a
SUBSET of what the cell reports, and the new readers to their arithmetic
on made-up runs."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tree
from chipbench import cells, sut, traffic
from chipbench.reducers import (decode_hbm_sala, dispatch_counter_ratio,
                                program_spans, serve_mfu_sala)

CELL = "serve-minicpm-sala-longdoc"
# ISSUE 56's list, item 6, less ``ragged_roofline`` (no sparse layer runs
# through the ragged kernel in this cell's window: PERF.md section 7)
LONGDOC = ["mfu_pct", "lin_attn_pct", "block_select_pct", "sparse_attn_pct",
           "ckey_write_pct", "selected_share_pct", "state_live_pct",
           "decode_hbm_pct", "idle_pct", "compiles", "loop_host_ms",
           "decode_ms", "prefill_ms_per_ktok", "prefill_pad_pct",
           "ahead_pct", "peak_hbm_gb"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    with open(os.path.join(cells.ROOT, "chipbench", "configs",
                           "minicpm-sala.json")) as f:
        return json.load(f)


def _sizes():
    cfg = _config()
    return cells.importlib.import_module(
        "chipbench.families.minicpm_sala").model_sizes(
            cfg, cfg["serve"]["engine"])


N_PARAMS = 2_820_569_088


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """``tree.make``'s benchmark plus one cell: ``tiny-sala`` under
    ``tiny-closed`` (prompts 20-120: under and over the toy's
    ``dense_len`` 32), reading what the cell reads through files of its
    own."""
    return tree.add_cell(tree.make(tmp_path_factory.mktemp("sala_tree")),
                         "tiny-sala", CELL, "tiny-closed")


def test_the_toy_cell_runs_and_is_correct(checkout):
    # prompts of 20-120 rows in chunks of 8: the carried state between
    # chunks is part of every prefill, as at 16,384 rows in chunks of 256
    line, earlier = tree.run(checkout, "tiny-sala", seed=2 ** 31 + 56,
                             prelude=SHORT_CHUNKS)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    log = earlier[-1]
    assert log["logit_error"] <= 1e-4       # float32 on both sides
    assert log["lost"] == [] and log["leaks"] == {}
    assert log["rows_compared"] == 72 and log["rows_undecided"] == 0
    assert log["compiles_in_window"] == 0


def test_the_traced_toy_run_reads_the_programs_counters(checkout):
    line, _ = tree.run(checkout, "tiny-sala", trace=1)
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert 50 < metrics["tiny-sala.state_live_pct"] <= 100
    # contexts of 20-128 under top-6 blocks of 4: some under dense_len
    assert 15 < metrics["tiny-sala.selected_share_pct"] < 100
    assert 0 < metrics["tiny-sala.mfu_pct"] < 100
    assert 0 < metrics["tiny-sala.decode_hbm_pct"] < 100
    assert metrics["tiny-sala.prefill_pad_pct"] > 0
    assert metrics["tiny-sala.compiles"] == 0
    assert metrics["tiny-sala.decode_ms"] > 0
    # no device plane in a CPU trace: the scopes' shares read nothing and
    # are left out, as on a program without them
    assert "tiny-sala.lin_attn_pct" not in metrics


SHORT_CHUNKS = ("from deepspeed_tpu.ops import linear_attention as la; "
                "la.CHUNK = 8")
FORGET_STATE = (
    "from deepspeed_tpu.ops import linear_attention as la; "
    "step = la.linear_step; "
    "la.linear_step = lambda q, k, v, slopes, state, scale=1.0: "
    "step(q, k, v, slopes, state * 0, scale)")
FORCED_ALONE = (
    "from deepspeed_tpu.ops import block_sparse_attention as b; "
    "import jax.numpy as jnp; sel = b.select_blocks\n"
    "def forced(*a, **k):\n"
    "    s, v = sel(*a, **k)\n"
    "    f = jnp.isinf(s) & (s > 0)\n"
    "    return jnp.where(f, s, -jnp.inf), v & f\n"
    "b.select_blocks = forced")


@pytest.mark.parametrize("prelude,least", [(FORGET_STATE, 0.2),
                                           (FORCED_ALONE, 0.1)],
                         ids=["state zeroed", "forced blocks alone"])
def test_the_harness_fails_a_lost_mechanism(checkout, prelude, least):
    """The harness's own check with every decode dispatch made to start
    its slots from a zero state, and with the selection replaced by the
    forced blocks alone: ``correct`` false, at 0.39-0.45 and 0.13 of the
    toy's largest logit on two seeds, where a sound run reads under 1e-4
    and the limit is 0.04.  The seeded values make it so
    (``seeded_weights``: unit elements after ``scale_emb``, logits of unit
    spread after the muP divisor): at ``embedding_std`` 1.0 under a head of
    1 / sqrt(hidden) the same two read 0.036 and 0.0098 and passed.  What
    the check sees at the published widths is the chip's to say (PERF.md
    section 4, the three controls): there a sparse layer averages
    thousands of values and the selection moves little."""
    line, earlier = tree.run(checkout, "tiny-sala", prelude=prelude)
    assert line["correct"] is False
    assert earlier[-1]["logit_error"] > least > 2 * earlier[-1]["logit_tol"]


def test_the_reference_is_the_programs_forward_at_toy_size():
    """``test_reference.py``'s pattern: seeded weights with every small
    leaf (the norms) moved off its initial value; float32 on both sides,
    rounding order alone; a sequence under ``dense_len`` and one over it
    with more than twice top-k blocks."""
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    config = tree.data("tiny-sala")
    cell = cells.Cell("tiny-sala", 1, config, {}, [], [])
    assert cell.family.REFERENCE == "minicpm_sala" and \
        not getattr(cell.family, "ROUTED", False)
    model = CausalTransformerLM(TransformerConfig(
        **cell.family.transformer_kwargs(config), remat=False,
        attn_impl="reference"))
    params = model.init(jax.random.key(1))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    params = jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        if leaf.shape[-1] <= 64 and leaf.ndim == 2 else leaf
        for leaf, key in zip(leaves, keys)])
    for length in (27, 61):
        ids = jax.random.randint(jax.random.key(length), (2, length), 0,
                                 config["vocab_size"])
        ours = model.apply(params, ids, train=False)
        want = cell.reference.logits(params, ids, config)
        assert float(jnp.max(jnp.abs(ours - want))) < 1e-4
        last = cell.reference.logits(params, ids, config, last=5)
        assert jnp.allclose(last, want[:, -5:], atol=1e-5)


def test_the_configuration_is_the_catalog_row_with_depth_alone_cut():
    cfg = _config()
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "MiniCPM-SALA"]
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key == "num_hidden_layers":
                assert (cfg[key], value) == (8, 32)
            elif key == "mixer_types":
                assert cfg[key] == value[9:17]      # published 9 .. 16
                assert cfg["published"][key] == value
            else:
                assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert sorted(cfg["published"]) == ["mixer_types", "num_hidden_layers"]
    assert cfg["mixer_types"] == ["minicpm4"] + ["lightning-attn"] * 6 \
        + ["minicpm4"]
    assert cfg["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "topk": 64, "init_blocks": 1, "window_size": 2048,
        "dense_len": 8192}
    mix = traffic.load_mix("longdoc-closed")
    cell = cells.Cell(name=CELL, chips=1, config=cfg, mix=mix,
                      end_to_end=[], per_layer=[])
    model = sut.build_model(cell)
    c = model.config
    assert (c.hidden_size, c.n_layers, c.n_heads, c.kv_heads, c.head_dim,
            c.ffn_dim, c.vocab_size) == (4096, 8, 32, 2, 128, 16384, 73448)
    assert (c.lin_heads, c.lin_head_dim) == (32, 128)
    assert c.sparse == (64, 64, 32, 16, 1, 2048, 8192)
    assert c.layer_period == 8 and c.leading_layers == 0
    assert (c.embed_scale, c.residual_scale, c.final_logit_scale) == \
        (12.0, 1.4 / np.sqrt(32), 1 / 16)
    # unit elements after scale_emb, logits of unit spread after / 16
    assert c.init_embed_std * c.embed_scale == pytest.approx(1.0)
    assert c.init_head_std * c.final_logit_scale * 64 == 1.0
    assert not c.tie_embeddings \
        and c.norm_eps == 1e-6 and c.qk_norm == "rms" and c.attn_gate
    shapes = jax.eval_shape(lambda k: model.init(k, jnp.bfloat16),
                            jax.random.key(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    # ISSUE 56's count: 2 x 253.8 M + 6 x 285.2 M + 601.7 M
    assert n_params == c.num_params() == N_PARAMS
    assert abs(n_params - 2.82e9) < 0.005e9
    assert shapes["layers"] == [] and len(shapes["periods"]) == 8
    assert shapes["periods"][0]["wk"].shape == (1, 4096, 256)
    assert shapes["periods"][0]["wg_attn"].shape == (1, 4096, 4096)
    assert shapes["periods"][3]["lin"]["wq"].shape == (1, 4096, 4096)
    assert shapes["periods"][3]["lin"]["norm"].shape == (1, 4096)
    assert shapes["lm_head"].shape == (4096, 73448)
    # the pools: pages and compressed keys of the 2 sparse layers, the
    # matrix state of the 6 linear ones, a row a slot, in float32
    engine = cfg["serve"]["engine"]
    pools = jax.eval_shape(lambda: model.init_paged_caches(
        engine["num_pages"], engine["page_size"],
        state_slots=mix["max_batch"]))
    assert pools.full.k_pages.shape == (2, 2113, 2, 128, 128)
    assert pools.full.c_pages.shape == (2, 2113, 2 * 8, 128)
    assert pools.ssm.state.shape == (6, 16, 32, 128, 128)
    assert pools.ssm.state.dtype == jnp.float32
    nbytes = {name: sum(x.size * x.dtype.itemsize
                        for x in jax.tree_util.tree_leaves(part))
              for name, part in pools._asdict().items()}
    assert abs(nbytes["full"] - 0.571e9) < 0.002e9
    assert nbytes["ssm"] == 16 * 6 * _sizes()["state_bytes"] == 201326592
    held = 2 * n_params + sum(nbytes.values())
    assert abs(held - 6.41e9) < 0.01e9          # 40 % of the chip
    # the mix is ISSUE 56's table to the letter
    assert (mix["kind"], mix["max_batch"], mix["clients"], mix["cycle"],
            mix["pairing_seed"], mix["ramp_s"], mix["grace_s"]) == \
        ("closed_loop", 16, 24, 8, 0, 30, 0)
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 8192,
                                    "max": 16384}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 128,
                                    "max": 384}
    grid = traffic.quantile_grid(mix["prompt_tokens"], mix["cycle"])
    assert list(grid) == [8704, 9728, 10752, 11776, 12800, 13824, 14848,
                          15872] and grid.sum() == 98304
    assert grid.min() >= cfg["sparse_config"]["dense_len"]
    assert mix["sampling"] == {"temperature": 0.0}
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= engine["max_seq"]
    assert engine["num_pages"] == mix["max_batch"] * (
        engine["max_seq"] // engine["page_size"]) + 1
    for key in ("source", "assumed", "deployment", "seeded_weights"):
        assert cfg[key]
    assert "four pipeline stages" in cfg["deployment"]
    assert cfg["serve"]["state_dtype"] == "float32"


def test_the_cells_entries_hold_what_its_issue_listed():
    """One configuration, one cell on one chip, its name under
    ``serve_tok_s`` alone; the metrics ISSUE 56 lists are a SUBSET of
    what the cell reports (a ``benchmark`` PR may join and add)."""
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (config,) = [c for c in bench["configs"] if c["name"] == "minicpm-sala"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("minicpm-sala", "longdoc-closed", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert [m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", ())] == ["serve_tok_s"]
    held = _config()
    assert held["reduced"] == config["reduced"]
    assert held["source"] == config["source"]
    assert len(bench["per_layer"]) <= 128        # the driver's contract
    entries = tree.held_entries(CELL, moves="serve_tok_s")
    assert set(LONGDOC) <= {tree.base(m["name"]) for m in entries}


def _run(steps, model=None):
    return cells.Run(chips=1, peaks=PEAKS, model=model or dict(
        _sizes(), n_params=N_PARAMS), steps=steps, traced_steps=steps,
        samples={}, counters={}, memory_peak_bytes=0, trace=None)


def test_the_models_operations_are_counted_by_kind():
    """``serve_mfu_sala`` on two made-up steps, against the count by hand:
    two operations a weight a token; in the two sparse layers scores and
    values over the SELECTED keys and scores over the visible compressed
    keys; the recurrence; the head."""
    steps = [
        {"t0": 0.0, "t1": 1.0, "dispatches": [
            {"phase": "prefill", "tokens": 16384, "real": 9000,
             "context": 9000}]},
        {"t0": 1.0, "t1": 1.05, "dispatches": [
            {"phase": "decode", "tokens": 1, "contexts": [9001, 5000]}]}]
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256
    token = 2 * sparse + 6 * 5 * 4096 * 4096 + 8 * 3 * 4096 * 16384
    # every weight but the two tables and the norms
    assert abs(token - (N_PARAMS - 2 * 73448 * 4096)) < 1e5
    # the prefill: all causal keys up to 64 blocks, then 63 blocks and the
    # query's own part-filled one
    t = np.arange(9000)
    kept = np.where(t < 4096, t + 1, 63 * 64 + t % 64 + 1).sum()
    seen = np.maximum((t + 1 - 32) // 16 + 1, 0).sum()
    # the decode rows: one over dense_len (63 blocks and 41 keys), one
    # under it (every key, no compressed key)
    kept += 63 * 64 + 9000 % 64 + 1 + 5000
    seen += (9001 - 32) // 16 + 1
    assert serve_mfu_sala.attended([9000], 9001, _sizes()) == \
        (63 * 64 + 41, 561)
    assert serve_mfu_sala.attended([4999], 5000, _sizes()) == (5000, 0)
    flops = 2 * (9002 * token + 2 * (kept * 2 * 32 * 128
                                     + seen * 32 * 128)
                 + 3 * 4096 * 73448) + 9002 * 6 * 6 * 4096 * 128
    run = _run(steps)
    assert serve_mfu_sala.read(run) == pytest.approx(
        100 * flops / 1.05 / 197e12)
    assert 0 < serve_mfu_sala.read(run) < 100
    run.model = {"n_layers": 40, "ssm_layers": 36}      # another family's
    assert serve_mfu_sala.read(run) is None


def test_a_decode_steps_bytes_over_its_spans(monkeypatch):
    """``decode_hbm_sala``: every weight once, the dispatch's own
    ``state_bytes``, the keys and values it attended (its own
    ``selected``), the compressed keys of its contexts, over the median
    ``serve/decode`` span (less a nested prefill) a dispatch."""
    from deepspeed_tpu.monitor.telemetry import Span
    model = dict(_sizes(), n_params=N_PARAMS)
    slot = 6 * model["state_bytes"]
    assert slot == 12_582_912
    contexts = [12000] * 16
    selected = 2 * 16 * (63 * 64 + 11999 % 64 + 1)
    dispatches = [
        {"phase": "decode", "batch": 16, "tokens": 1, "contexts": contexts,
         "state_slots": 16, "state_bytes": 2 * 16 * slot,
         "selected": selected, "context_keys": 2 * 16 * 12000},
        {"phase": "prefill", "batch": 1, "tokens": 16384, "real": 9000,
         "context": 9000, "state_slots": 1, "state_bytes": 2 * slot,
         "selected": 0, "context_keys": 0}]
    steps = [{"t0": 0.0, "t1": 0.1, "dispatches": dispatches}]

    def span(i, name, t0, t1, parent=None):
        return Span(id=i, name=name, t0_ns=int(t0 * 1e9), t1_ns=int(t1 * 1e9),
                    parent=parent, key=None, attrs=None)

    spans = [span(1, "serve/decode", 0.0, 0.012),
             span(2, "serve/decode", 0.05, 0.1),
             span(3, "serve/prefill", 0.06, 0.098, parent=2),
             span(4, "serve/decode", 0.1, 0.102)]
    monkeypatch.setattr(program_spans, "window_spans", lambda run: spans)
    first = decode_hbm_sala.dispatch_bytes(dispatches[0], model)
    # 5.64 GB of weights, 0.40 GB of state read and written, 64 blocks of
    # K and V a slot, head and sparse layer, and the compressed keys
    assert first == 2 * N_PARAMS + 32 * slot \
        + (2 * selected + 2 * 16 * 750) * 2 * 128 * 2
    assert abs(first - 6.18e9) < 0.01e9
    share = decode_hbm_sala.read(_run(steps, model))
    # one dispatch at the median step, 0.012 s
    assert share == pytest.approx(100 * first / 0.012 / 819e9)
    assert 0 < share < 100
    # a program whose dispatches say nothing of state: nothing to read
    bare = [{"t0": 0.0, "t1": 0.1, "dispatches": [
        {"phase": "decode", "batch": 16, "tokens": 1, "contexts": [5]}]}]
    assert decode_hbm_sala.read(_run(bare, model)) is None
    kept = dispatch_counter_ratio.read(_run(steps, model), "selected",
                                       "context_keys", scale=100.0)
    assert kept == pytest.approx(100 * (63 * 64 + 32) / 12000)
    assert 25 < kept < 48
