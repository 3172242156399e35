"""The ``granitemoehybrid`` family through the command on the CPU: a toy
configuration of the same shape as ``granite-4.0-h-micro``
(``data/tiny-granite.json``: Mamba-2 layers with a recurrent state a slot
beside the pages of one attention layer a period, two periods) is added to
the made-up tree as files and entries, as a PR adds a cell, and run
untraced and traced; the family's reference is held to the program, the
repo's own configuration to the published widths and to the arithmetic of
its memory, its entries to ISSUE 47's list BY NAME, and the new readers to
their arithmetic on made-up runs."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tree
from chipbench import cells, reduce, sut, traffic
from chipbench.costs import ragged_hybrid_serve
from chipbench.reducers import (decode_hbm_hybrid, dispatch_counter_ratio,
                                kernel_roofline, program_spans,
                                serve_mfu_hybrid)

CELL = "serve-granite4-h-micro-chatfull"
# ISSUE 47's seventeen less two: ``per_layer`` holds at most 128 metrics and
# had 112, so ``attn_full_pct`` (reads what ``ragged_pct`` reads) and
# ``peak_hbm_gb`` (reads the harness's set-up) were left out
CHATFULL = ["mfu_pct", "decode_hbm_pct", "ssm_scan_pct", "ssm_conv_pct",
            "ssm_proj_pct", "ragged_pct", "ragged_roofline",
            "state_live_pct", "decode_ms", "prefill_ms_per_ktok",
            "prefill_pad_pct", "loop_host_ms", "idle_pct", "compiles",
            "ahead_pct"]
METRICS = os.path.join(cells.ROOT, "chipbench", "layer_metrics")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config():
    with open(os.path.join(cells.ROOT, "chipbench", "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def _sizes():
    cfg = _config()
    return cells.importlib.import_module(
        "chipbench.families.granitemoehybrid").model_sizes(
            cfg, cfg["serve"]["engine"])


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """``tree.make``'s benchmark plus one cell: ``tiny-granite`` under
    ``tiny-closed``, reading what the cell reads through files of its
    own."""
    return tree.add_cell(tree.make(tmp_path_factory.mktemp("granite_tree")),
                         "tiny-granite", CELL, "tiny-closed")


def test_the_toy_cell_runs_and_is_correct(checkout):
    line, earlier = tree.run(checkout, "tiny-granite", seed=2 ** 31 + 47)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    log = earlier[-1]
    assert log["logit_error"] <= 1e-4       # float32 on both sides
    assert log["lost"] == [] and log["leaks"] == {}
    assert log["rows_compared"] == 72 and log["rows_undecided"] == 0
    assert log["compiles_in_window"] == 0


def test_the_traced_toy_run_reads_the_programs_counters(checkout):
    line, _ = tree.run(checkout, "tiny-granite", trace=1)
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # six callers on four slots: the batch is full most of the time
    assert 50 < metrics["tiny-granite.state_live_pct"] <= 100
    assert 0 < metrics["tiny-granite.mfu_pct"] < 100
    assert 0 < metrics["tiny-granite.decode_hbm_pct"] < 100
    assert metrics["tiny-granite.prefill_pad_pct"] > 0
    assert metrics["tiny-granite.compiles"] == 0
    assert metrics["tiny-granite.decode_ms"] > 0
    assert metrics["tiny-granite.ahead_pct"] > 50
    # no device plane in a CPU trace: the kernel's and the scopes' shares
    # read nothing and are left out, as on a program without them
    assert "tiny-granite.ssm_scan_pct" not in metrics
    assert "tiny-granite.ragged_roofline" not in metrics


def test_a_decode_step_that_forgets_the_tail_is_not_correct(checkout):
    """The harness's own check, with every decode dispatch made to start
    its slots from a zero convolution tail: the run is not ``correct``
    (0.2 of the largest logit).  (With the recurrent STATE zeroed instead
    the toy reads 0.013, a thousand times a sound run's error yet under
    the harness's 0.04: a state of 32 a head element holding 20-120
    tokens is a small part of the toy's stream.  What the check sees at
    the published widths is the chip's to say: ``scripts/state_control.py``,
    PERF.md section 4.)"""
    prelude = (
        "from deepspeed_tpu.models import transformer as t; "
        "mixer = t.CausalTransformerLM._ssm_mixer; "
        "t.CausalTransformerLM._ssm_mixer = lambda self, h, w, state, tail,"
        " real=None: mixer(self, h, w, state, tail * (h.shape[1] > 1), real)")
    line, earlier = tree.run(checkout, "tiny-granite", prelude=prelude)
    assert line["correct"] is False and earlier[-1]["logit_error"] > 0.04


def test_the_reference_is_the_programs_forward_at_toy_size():
    """``test_reference.py``'s pattern: seeded weights with every small
    leaf (norms, the conv, A_log, dt_bias, D) moved off its initial value;
    float32 on both sides, rounding order alone."""
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    config = tree.data("tiny-granite")
    cell = cells.Cell("tiny-granite", 1, config, {}, [], [])
    assert cell.family.REFERENCE == "granitemoehybrid" and \
        not getattr(cell.family, "ROUTED", False)
    model = CausalTransformerLM(TransformerConfig(
        **cell.family.transformer_kwargs(config), remat=False,
        attn_impl="reference"))
    params = model.init(jax.random.key(1))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    params = jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        if leaf.shape[-1] <= 256 or leaf.ndim == 2 and leaf.shape[0] == 2
        else leaf for leaf, key in zip(leaves, keys)])
    ids = jax.random.randint(jax.random.key(3), (2, 45), 0,
                             config["vocab_size"])
    ours = model.apply(params, ids, train=False)
    want = cell.reference.logits(params, ids, config)
    assert float(jnp.max(jnp.abs(ours - want))) < 1e-4
    last = cell.reference.logits(params, ids, config, last=5)
    assert jnp.allclose(last, want[:, -5:], atol=1e-5)


def test_the_configuration_is_the_published_widths_with_nothing_cut():
    cfg = _config()
    mix = traffic.load_mix("chatfull-closed")
    cell = cells.Cell(name=CELL, chips=1, config=cfg, mix=mix,
                      end_to_end=[], per_layer=[])
    model = sut.build_model(cell)
    c = model.config
    assert (c.hidden_size, c.n_layers, c.n_heads, c.kv_heads, c.head_dim,
            c.ffn_dim, c.vocab_size) == (2048, 40, 32, 8, 64, 8192, 100352)
    assert (c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups,
            c.ssm_conv, c.ssm_chunk) == (64, 64, 128, 1, 4, 256)
    assert (c.ssm_inner, c.ssm_conv_dim) == (4096, 4352)
    period = (True,) * 5 + (False,) + (True,) * 4
    assert c.ssm_pattern == period * 4 and c.layer_period == 10
    assert [i for i in range(40) if not c.layer_ssm(i)] == [5, 15, 25, 35]
    assert c.rope_pattern == (False,) * 40 and c.leading_layers == 0
    assert (c.embed_scale, c.attn_scale, c.residual_scale,
            c.final_logit_scale) == (12.0, 0.015625, 0.22, 0.125)
    assert c.tie_embeddings and c.norm_eps == 1e-5
    shapes = jax.eval_shape(lambda k: model.init(k, jnp.bfloat16),
                            jax.random.key(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    # ISSUE 47's count: 36 x 76.2 M + 4 x 60.8 M + the tied table 205.5 M
    assert abs(n_params - 3.191e9) < 0.002e9 and n_params == c.num_params()
    assert shapes["layers"] == [] and len(shapes["periods"]) == 10
    assert shapes["periods"][0]["ssm"]["w_in"].shape == (4, 2048, 8512)
    assert shapes["periods"][0]["ssm"]["conv_w"].shape == (4, 4, 4352)
    assert shapes["periods"][5]["wq"].shape == (4, 2048, 2048)
    assert shapes["periods"][5]["wk"].shape == (4, 2048, 512)
    assert "lm_head" not in shapes
    # the pools: pages of the 4 attention layers, two heads of 64 a row of
    # 128 lanes; the state of the 36 others, a row a slot, in float32
    engine = cfg["serve"]["engine"]
    pools = jax.eval_shape(lambda: model.init_paged_caches(
        engine["num_pages"], engine["page_size"],
        state_slots=mix["max_batch"]))
    assert pools.full.k_pages.shape == (4, 1025, 4, 128, 128)
    assert pools.ssm.state.shape == (36, 64, 64, 64, 128)
    assert pools.ssm.state.dtype == jnp.float32
    assert pools.ssm.conv.shape == (36, 64, 3 * 4352)
    assert pools.ssm.conv.dtype == jnp.bfloat16
    nbytes = {name: sum(x.size * x.dtype.itemsize
                        for x in jax.tree_util.tree_leaves(part))
              for name, part in pools._asdict().items()}
    assert abs(nbytes["full"] - 1.075e9) < 0.001e9
    assert abs(nbytes["ssm"] - (4.832e9 + 0.060e9)) < 0.001e9
    held = 2 * n_params + sum(nbytes.values())
    assert abs(held - 12.35e9) < 0.01e9         # 77 % of the chip
    sizes = _sizes()
    assert (sizes["attn_layers"], sizes["ssm_layers"], sizes["n_layers"]) \
        == (4, 36, 40)
    assert 36 * 64 * (sizes["state_bytes"] + sizes["tail_bytes"]) == \
        nbytes["ssm"]
    # the mix is ISSUE 47's table
    assert (mix["kind"], mix["max_batch"], mix["clients"], mix["cycle"],
            mix["ramp_s"], mix["grace_s"]) == ("closed_loop", 64, 96, 32,
                                               20, 0)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.8, "min": 64, "max": 1024}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.6, "min": 64, "max": 512}
    assert mix["sampling"] == {"temperature": 0.0}
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= engine["max_seq"]
    assert engine["num_pages"] == mix["max_batch"] * (
        engine["max_seq"] // engine["page_size"]) + 1
    assert "decode_chunk" not in engine
    for key in ("source", "assumed", "deployment", "seeded_weights"):
        assert cfg[key]
    assert cfg["reduced"] == [] and cfg["published"] == {}
    assert cfg["serve"]["state_dtype"] == "float32"


def test_the_cells_entries_are_what_its_issue_listed():
    """One configuration, one cell on one chip, its name under
    ``serve_tok_s`` alone, and the fifteen metrics that list it, whose
    files agree with their entries and whose readers exist.  Found by the
    cell's membership of ``workloads``, wherever a later PR's additions
    put the end of the lists."""
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (config,) = [c for c in bench["configs"]
                 if c["name"] == "granite-4.0-h-micro"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("granite-4.0-h-micro", "chatfull-closed", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert [m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", ())] == ["serve_tok_s"]
    held = _config()
    assert held["reduced"] == config["reduced"] == []
    assert held["source"] == config["source"]
    assert len(bench["per_layer"]) <= 128        # the driver's contract
    entries = tree.held_entries(CELL, moves="serve_tok_s")
    assert sorted(tree.base(m["name"]) for m in entries) == sorted(CHATFULL)
    # what reads this family's own scopes and sizes keeps its ending (the
    # routed granite's cell joined those lists, PR 53); the rest the cell
    # reads through the ``.serve`` lists it shares
    assert {m["name"] for m in entries if ".chatfull" in m["name"]} == \
        {f[:-5] for f in os.listdir(METRICS) if f.endswith(".chatfull.json")}
    # the shares of device time read by launch TIME (``scope_pct``), which
    # reads under the monolithic policy with the run-ahead
    scopes = {name: json.load(open(os.path.join(
        METRICS, name + ".chatfull.json"))) for name in (
            "ssm_scan_pct", "ssm_conv_pct", "ssm_proj_pct")}
    assert all(s["reducer"] == "scope_pct" for s in scopes.values())
    assert [s["args"]["scope"] for s in scopes.values()] == \
        ["ssm_scan", "ssm_conv", "ssm_proj"]


def _run(steps, model=None, trace=None, traced=True):
    return cells.Run(chips=1, peaks=PEAKS, model=model or dict(
        _sizes(), n_params=3_191_396_096), steps=steps,
        traced_steps=steps if traced else [], samples={}, counters={},
        memory_peak_bytes=0, trace=trace)


def test_the_models_operations_are_counted_by_kind():
    """``serve_mfu_hybrid`` on two made-up steps, against the count by
    hand: two operations a weight a token, the four attention layers'
    scores and values over each row's context, the recurrence, the head."""
    steps = [
        {"t0": 0.0, "t1": 0.25, "dispatches": [
            {"phase": "prefill", "tokens": 512, "real": 300,
             "context": 300}]},
        {"t0": 0.25, "t1": 0.3, "dispatches": [
            {"phase": "decode", "tokens": 1, "contexts": [301, 77]}]}]
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    mixer = 2048 * (4096 + 4352 + 64) + 4096 * 2048 + 4 * 4352
    token = 4 * attention + 36 * mixer + 40 * 3 * 2048 * 8192
    # every weight but the tied table and the norms and per-head vectors
    assert abs(token - (3_191_396_096 - 100352 * 2048)) < 1e6
    keys = 300 * 301 // 2 + 301 + 77
    flops = 2 * (302 * token + 4 * keys * 2 * 32 * 64
                 + 3 * 2048 * 100352) + 302 * 36 * 6 * 4096 * 128
    run = _run(steps)
    assert serve_mfu_hybrid.read(run) == pytest.approx(
        100 * flops / 0.3 / 197e12)
    assert 0 < serve_mfu_hybrid.read(run) < 100
    run.model = {"n_layers": 16, "window_layers": 12}   # another family's
    assert serve_mfu_hybrid.read(run) is None


def test_a_decode_steps_bytes_over_its_spans(monkeypatch):
    """``decode_hbm_hybrid``: every weight once, the dispatch's own
    ``state_bytes``, its live contexts' K and V in the four attention
    layers, over the median ``serve/decode`` span (less a nested prefill)
    a dispatch."""
    from deepspeed_tpu.monitor.telemetry import Span
    model = dict(_sizes(), n_params=3_191_396_096)
    slot = 36 * (model["state_bytes"] + model["tail_bytes"])
    assert slot == 75_497_472 + 36 * 3 * 4352 * 2
    dispatches = [
        {"phase": "decode", "batch": 64, "tokens": 1, "contexts": [300] * 64,
         "state_slots": 64, "state_bytes": 2 * 64 * slot},
        {"phase": "prefill", "batch": 1, "tokens": 256, "real": 200,
         "context": 200, "state_slots": 1, "state_bytes": 2 * slot},
        {"phase": "decode", "batch": 64, "tokens": 1, "contexts": [9, 40],
         "state_slots": 2, "state_bytes": 2 * 2 * slot}]
    steps = [{"t0": 0.0, "t1": 0.1, "dispatches": dispatches}]

    def span(i, name, t0, t1, parent=None):
        return Span(id=i, name=name, t0_ns=int(t0 * 1e9), t1_ns=int(t1 * 1e9),
                    parent=parent, key=None, attrs=None)

    # three spans: a step, a step with a prefill nested in it, and a
    # launch alone (its dispatch was waited for by a prefill's fetch)
    spans = [span(1, "serve/decode", 0.0, 0.04),
             span(2, "serve/decode", 0.05, 0.1),
             span(3, "serve/prefill", 0.06, 0.08, parent=2),
             span(4, "serve/decode", 0.1, 0.102)]
    monkeypatch.setattr(program_spans, "window_spans", lambda run: spans)
    first = decode_hbm_hybrid.dispatch_bytes(dispatches[0], model)
    # 6.38 GB of weights, 9.66 GB of state read and written, and 64
    # contexts of 300 tokens of 8 KB: 16.3 GB a full step
    assert first == 2 * 3_191_396_096 + 128 * slot + 64 * 300 * 8192
    assert abs(first - 16.3e9) < 0.1e9
    last = decode_hbm_hybrid.dispatch_bytes(dispatches[2], model)
    share = decode_hbm_hybrid.read(_run(steps, model))
    # two dispatches at the median step, 0.03 s
    assert share == pytest.approx(100 * (first + last) / 0.06 / 819e9)
    assert 0 < share < 100
    # a program whose dispatches say nothing of state: nothing to read
    bare = [{"t0": 0.0, "t1": 0.1, "dispatches": [
        {"phase": "decode", "batch": 64, "tokens": 1, "contexts": [5]}]}]
    assert decode_hbm_hybrid.read(_run(bare, model)) is None
    live = dispatch_counter_ratio.read(_run(steps, model), "state_slots",
                                       "batch", scale=100.0)
    assert live == pytest.approx(100 * 67 / 129)


def test_the_kernels_cost_counts_the_attention_layers_alone():
    """``ragged_hybrid_serve``: one layer's cost x 4, where the dense
    families' file would multiply by all 40 layers and read ten times over
    the kernel's time."""
    from chipbench import roofline
    model = _sizes()
    steps = [{"t0": 0.0, "t1": 0.1, "traced": True, "dispatches": [
        {"phase": "prefill", "batch": 1, "tokens": 1024, "real": 700,
         "context": 700, "t0_ns": 0},
        {"phase": "decode", "batch": 64, "tokens": 1,
         "contexts": [701, 90, 1500], "t0_ns": 5e7}]}]
    labels = ["ragged_paged_attention_prefill.1:custom-call",
              "ragged_paged_attention_decode.2:custom-call",
              "fusion.3:fusion"]
    ms = lambda *xs: np.asarray(xs, np.float64) * 1e6   # noqa: E731
    trace = reduce.Trace(
        labels=labels, kinds=["pallas", "pallas", "xla"],
        ops=[reduce.DeviceLine(ms(1, 30, 60), ms(2, 0.4, 30),
                               np.asarray([0, 1, 2]))],
        annotations=[("chipbench/step", 0.0, 1e8)])
    run = _run(steps, model, trace)
    least = ragged_hybrid_serve.least_seconds(run)
    one = sum(roofline.bound_seconds(*cost, PEAKS)[0] for cost in (
        roofline.ragged_paged_dispatch(700, [700], model),
        roofline.ragged_paged_dispatch(1, [701, 90, 1500], model)))
    assert least == pytest.approx(4 * one)
    assert roofline.ragged_paged_serve_seconds(
        model, steps[0]["dispatches"], PEAKS) == pytest.approx(40 * one)
    # a decode row reads its context's pages of 8 heads x 64, K and V
    flops, nbytes = roofline.ragged_paged_dispatch(1, [701], model)
    assert flops == 4 * 701 * 32 * 64
    assert nbytes == 2 * 6 * 128 * 8 * 64 * 2 + 2 * 32 * 64 * 2
    share = kernel_roofline.read(run, "ragged_hybrid_serve",
                                 kernel="ragged_paged_attention_")
    assert share == pytest.approx(100 * least / 0.0024) and 0 < share < 100
