"""The ``glm_moe_dsa`` family through the command on the CPU: a toy
configuration of the same shape as ``glm-5-ep16`` (``data/tiny-glm.json``)
is added to the made-up tree as files and entries, as a PR adds a cell,
and run untraced and traced; the repo's own configuration is held to the
published widths and to the arithmetic of its cut.

The cell itself (``serve-glm5-ep16-longctx``) is in ``BENCHMARK.json``
with its thirteen metrics (five under ``.longctx``, eight through the
``.serve`` lists it shares); its entries are held to ISSUE 32's list
here.  The last tests repeat, at toy size, why the configuration seeds its
embeddings at unit spread (PERF.md section 6): under the old spread two
forwards that are not bit-equal select other keys and the
check cannot judge the model."""

import json
import os

import jax
import pytest

import tree
from chipbench import cells, sut, traffic

CELL = "serve-glm5-ep16-longctx"
LONGCTX = ["peak_hbm_gb", "idle_pct", "compiles", "loop_host_ms",
           "decode_ms", "prefill_ms_per_ktok", "prefill_pad_pct",
           "select_pct", "latent_attn_pct", "experts_pct",
           "selected_share_pct", "expert_load_ratio", "mfu_pct"]
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(cells.ROOT, "chipbench", "layer_metrics")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """``tree.make``'s benchmark plus one cell: ``tiny-glm`` under
    ``tiny-closed``, reading what the cell reads through files of its
    own."""
    return tree.add_cell(tree.make(tmp_path_factory.mktemp("glm_tree")),
                         "tiny-glm", CELL, "tiny-closed")


def test_the_toy_cell_runs_and_is_correct(checkout):
    line, earlier = tree.run(checkout, "tiny-glm", seed=2 ** 31 + 9)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    log = earlier[-1]
    assert log["logit_error"] <= log["logit_tol"]
    assert log["lost"] == [] and log["leaks"] == {}
    assert log["rows_compared"] + log["rows_undecided"] == 72
    assert log["rows_compared"] >= 36
    assert log["compiles_in_window"] == 0


def test_the_traced_toy_run_reads_the_programs_counters(checkout):
    line, _ = tree.run(checkout, "tiny-glm", trace=1)
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # contexts of 20-128 under a top-16: well under half the keys attended
    assert 5 < metrics["tiny-glm.selected_share_pct"] < 50
    assert metrics["tiny-glm.expert_load_ratio"] >= 1.0
    assert 0 < metrics["tiny-glm.mfu_pct"] < 100
    assert metrics["tiny-glm.prefill_pad_pct"] > 0
    assert metrics["tiny-glm.compiles"] == 0
    # no device plane in a CPU trace: the scope shares read nothing and
    # are left out, as on a program without the scopes
    assert "tiny-glm.select_pct" not in metrics


def test_the_configuration_is_the_published_widths_and_the_stated_cut():
    with open(os.path.join(cells.ROOT, "chipbench", "configs",
                           "glm-5-ep16.json")) as f:
        cfg = json.load(f)
    mix = traffic.load_mix("longctx-closed")
    cell = cells.Cell(name=CELL, chips=1, config=cfg, mix=mix,
                      end_to_end=[], per_layer=[])
    assert cell.family.ROUTED
    model = sut.build_model(cell)
    c = model.config
    assert (c.hidden_size, c.n_heads, c.q_lora_rank, c.kv_lora_rank) == \
        (6144, 64, 2048, 512)
    assert (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == \
        (192, 64, 256)
    assert (c.index_n_heads, c.index_head_dim, c.index_topk) == \
        (32, 128, 2048)
    assert (c.moe_num_experts, c.experts_held, c.moe_top_k) == (256, 16, 8)
    assert c.moe_dropless and c.moe_scoring == "sigmoid"
    shapes = jax.eval_shape(lambda k: model.init(k, jax.numpy.bfloat16),
                            jax.random.key(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    # ISSUE 32's table: 1 dense + 5 expert layers + embedding + head
    assert abs(n_params - 4.73e9) < 0.01e9
    # every context is at least twice the selection's width, and the
    # longest request fits the engine's pages
    engine = cfg["serve"]["engine"]
    assert mix["prompt_tokens"]["min"] >= 2 * cfg["index_topk"]
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= \
        engine["max_seq"]
    assert engine["num_pages"] - 1 == mix["max_batch"] * \
        engine["max_seq"] // engine["page_size"]


def test_the_benchmark_gains_what_the_issue_lists_and_no_more():
    """One configuration, one cell on one chip, its name under
    ``serve_tok_s`` alone, thirteen metrics whose files agree with their
    entries and whose readers exist: found by NAME and by the cell's
    membership of ``workloads``, wherever later PRs' additions and a
    ``benchmark`` PR's joins (``compiles.serve``) have put them."""
    bench = tree.bench()
    (config,) = [c for c in bench["configs"] if c["name"] == "glm-5-ep16"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (config["name"], "longctx-closed", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert [m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", ())] == ["serve_tok_s"]
    with open(os.path.join(cells.ROOT, config["file"])) as f:
        held = json.load(f)
    assert held["reduced"] == config["reduced"] == list(held["published"])
    assert held["source"] == config["source"]
    entries = tree.held_entries(CELL, moves="serve_tok_s")
    assert sorted(tree.base(m["name"]) for m in entries) == sorted(LONGCTX)
    # what only this cell reads keeps its ending
    assert {m["name"] for m in entries if m["workloads"] == [CELL]} == \
        {f[:-5] for f in os.listdir(METRICS) if f.endswith(".longctx.json")}


def test_scope_shares_give_each_operation_to_its_dispatchs_program(
        monkeypatch):
    """Two programs share the instruction name ``fusion.1``; the reader
    tells them apart by the dispatch an operation ran in."""
    import numpy as np
    from chipbench import reduce
    from chipbench.reducers import scope_pct
    from deepspeed_tpu.monitor import telemetry

    labels = ["fusion.1:fusion", "fusion.2:fusion", "while.3:while"]
    # a prefill (1 x 64) from 1,000 us, a decode (4 x 1) from 5,000 us
    start = np.asarray([1100, 2000, 1100, 5100, 5500], np.float64) * 1e3
    dur = np.asarray([800, 1000, 1900, 300, 100], np.float64) * 1e3
    label = np.asarray([0, 1, 2, 0, 1])
    trace = reduce.Trace(
        labels=labels, kinds=["xla"] * 3,
        ops=[reduce.DeviceLine(start, dur, label)],
        annotations=[("chipbench/step", 1e6, 4e6),
                     ("chipbench/step", 5e6, 6e6)])
    steps = [{"t0": 0.001, "t1": 0.004, "traced": True, "dispatches": [
                 {"phase": "prefill", "batch": 1, "tokens": 64,
                  "t0_ns": 1.0e6}]},
             {"t0": 0.005, "t1": 0.006, "traced": True, "dispatches": [
                 {"phase": "decode", "batch": 4, "tokens": 1,
                  "t0_ns": 5.0e6}]}]
    run = cells.Run(chips=1, peaks={}, model={}, steps=steps,
                    traced_steps=steps, samples={}, counters={},
                    memory_peak_bytes=0, trace=trace)
    tables = {("serve/prefill_fn", (1, 64)): {"fusion.1": "select",
                                              "fusion.2": "experts"},
              ("serve/step_fn", (4, 1)): {"fusion.1": "latent_attn"}}
    monkeypatch.setattr(
        telemetry, "op_scopes",
        lambda site, arg_shapes=None: tables[(site, arg_shapes[1])])
    seconds = scope_pct.by_scope(run)
    assert seconds == pytest.approx({"select": 0.8e-3, "experts": 1.0e-3,
                                     "latent_attn": 0.3e-3,
                                     "other": 0.1e-3})
    busy = reduce.busy_seconds(trace)       # the while spans its body
    assert scope_pct.read(run, "select") == pytest.approx(
        100 * 0.8e-3 / busy)
    # a program from before the scopes: nothing to read
    monkeypatch.delattr(telemetry, "SERVE_SCOPES")
    assert scope_pct.read(run, "select") is None


def _selection_diag():
    import importlib.util
    path = os.path.join(cells.ROOT, "scripts", "glm_selection_diag.py")
    spec = importlib.util.spec_from_file_location("glm_selection_diag", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_selection_of_ones_own_is_what_the_check_cannot_judge(dtype):
    """``scripts/glm_selection_diag.py`` on the toy: served in float32 the
    program selects the reference's keys and the two readings are one,
    while the reference against itself, its index scores off by half a
    percent, is past the tolerance with no program in sight; served in
    bfloat16 the program selects other keys and reads many times the
    tolerance.  (What the SHARED selection leaves is read at the real size
    on the chip, PERF.md section 6: at toy contexts a routing flip in the
    context still moves a row, tests/chipbench/test_routed.py.)"""
    diag = _selection_diag()
    reading, = diag.main([
        "--cpu", "--config", os.path.join(DATA, "tiny-glm.json"),
        "--traffic", os.path.join(DATA, "tiny-closed.json"),
        "--seeds", "5", "--dtype", dtype]
        + (["--noise", "0.005"] if dtype == "float32" else []))
    own, shared = reading["own"], reading["shared"]
    assert reading["leaks"] == {} and own["rows"] == 72
    assert reading["swaps"]["queries"] > 0
    if dtype == "float32":
        assert reading["swaps"]["mean"] == 0
        assert own["logit_error"] == shared["logit_error"] < 1e-4
        assert reading["noise"]["logit_error"] > 0.04
    else:
        assert reading["swaps"]["mean"] > 0
        assert own["logit_error"] > 0.04
        assert shared["rows_compared"] >= 36


def test_the_reference_alone_says_what_the_check_can_tell():
    """``--reference-only`` on the toy: no program, the reference against
    itself with its selection disturbed; ``--embedding-std`` reaches the
    seeded weights (0: the program's default, rows of norm 1)."""
    diag = _selection_diag()
    toy = ["--cpu", "--reference-only", "--rows", "24",
           "--config", os.path.join(DATA, "tiny-glm.json"),
           "--traffic", os.path.join(DATA, "tiny-closed.json"),
           "--seeds", "5"]
    plain, = diag.main(toy)
    unit, = diag.main(toy + ["--embedding-std", "1.0"])
    assert plain["embedding_std"] == pytest.approx(64 ** -0.5, rel=0.05)
    assert unit["embedding_std"] == pytest.approx(1.0, rel=0.05)
    for reading in (plain, unit):
        assert reading["prompt"] == 37 and reading["rows"] == 24
        assert reading["noise"]["sigma"] == 0.005
        # keys at random are another model: far past the tolerance
        assert reading["random_keys"]["logit_error"] > 0.04
        # the toy's contexts end before the page that is taken away
        assert reading["less_a_page"]["logit_error"] == 0.0


def test_the_margins_reading_is_the_references_own_rule(checkout):
    """``--margins`` on the toy: at the reference's ``MARGIN`` it leaves
    the rows the harness's check compared, with the same largest error;
    a smaller margin leaves more rows."""
    from chipbench.reference import glm_moe_dsa
    diag = _selection_diag()
    assert glm_moe_dsa.MARGIN in diag.MARGINS
    reading, = diag.main([
        "--cpu", "--margins", "--seeds", str(2 ** 31 + 9),
        "--config", os.path.join(DATA, "tiny-glm.json"),
        "--traffic", os.path.join(DATA, "tiny-closed.json")])
    _, earlier = tree.run(checkout, "tiny-glm", seed=2 ** 31 + 9)
    at = reading["by_margin"][str(glm_moe_dsa.MARGIN)]
    assert reading["rows"] == 72
    # the toy's four expert layers have one to four blocks before them
    assert reading["layer_scales"] == pytest.approx(
        [depth ** 0.5 for depth in (1, 2, 3, 4)])
    assert at["rows_decided"] == earlier[-1]["rows_compared"]
    assert at["logit_error"] == pytest.approx(earlier[-1]["logit_error"],
                                              rel=1e-3, abs=1e-7)
    decided = [reading["by_margin"][str(m)]["rows_decided"]
               for m in diag.MARGINS]
    assert decided == sorted(decided, reverse=True)


@pytest.mark.parametrize("depth,times", [(0, 1.0), (1, 1.0), (4, 2.0),
                                         (5, 5 ** 0.5)])
def test_an_expert_layers_margin_grows_as_the_root_of_its_depth(depth,
                                                                times):
    """What the program's rounding does to a router's score gap adds up
    over the blocks before it (the reference's docstring has the chip's
    readings); a layer with no block before it keeps ``MARGIN``."""
    from chipbench.reference import glm_moe_dsa
    assert glm_moe_dsa.layer_margin(depth) == pytest.approx(
        glm_moe_dsa.MARGIN * times)
