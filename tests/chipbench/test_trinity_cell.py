"""The ``afmoe`` family through the command on the CPU: a toy configuration
of the same shape as ``trinity-mini-ep8`` (``data/tiny-trinity.json``:
window layers in rings beside a full layer's growing tables, two periods,
gated group-8 attention, 4 of 16 routed experts) is added to the made-up
tree as files and entries, as a PR adds a cell, and run untraced and
traced; the repo's own configuration is held to the published widths and
to the arithmetic of its cut, its entries to ISSUE 37's list, and the
kernel's cost to its arithmetic and to a made-up trace."""

import json
import os

import jax
import numpy as np
import pytest

import tree
from chipbench import cells, reduce, sut, traffic
from chipbench.costs import ragged_window_serve
from chipbench.reducers import kernel_roofline, serve_mfu_window

CELL = "serve-trinity-mini-ep8-mixedq"
MIXEDQ = ["mfu_pct", "ragged_pct", "ragged_roofline", "attn_window_pct",
          "attn_full_pct", "experts_pct", "attended_share_pct",
          "expert_load_ratio", "decode_ms", "prefill_ms_per_ktok",
          "prefill_pad_pct", "loop_host_ms", "idle_pct", "compiles",
          "peak_hbm_gb"]
METRICS = os.path.join(cells.ROOT, "chipbench", "layer_metrics")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config():
    with open(os.path.join(cells.ROOT, "chipbench", "configs",
                           "trinity-mini-ep8.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """``tree.make``'s benchmark plus one cell: ``tiny-trinity`` under
    ``tiny-closed``, reading what the cell reads through files of its
    own."""
    return tree.add_cell(tree.make(tmp_path_factory.mktemp("trinity_tree")),
                         "tiny-trinity", CELL, "tiny-closed")


def test_the_toy_cell_runs_and_is_correct(checkout):
    line, earlier = tree.run(checkout, "tiny-trinity", seed=2 ** 31 + 9)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    log = earlier[-1]
    assert log["logit_error"] <= 1e-4       # float32 on both sides
    assert log["lost"] == [] and log["leaks"] == {}
    assert log["rows_compared"] + log["rows_undecided"] == 72
    assert log["rows_compared"] >= 36
    assert log["compiles_in_window"] == 0


def test_the_traced_toy_run_reads_the_programs_counters(checkout):
    line, _ = tree.run(checkout, "tiny-trinity", trace=1)
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # contexts of 20-128 under a window of 32 in 6 layers of 8
    assert 30 < metrics["tiny-trinity.attended_share_pct"] < 90
    assert metrics["tiny-trinity.expert_load_ratio"] >= 1.0
    assert 0 < metrics["tiny-trinity.mfu_pct"] < 100
    assert metrics["tiny-trinity.prefill_pad_pct"] > 0
    assert metrics["tiny-trinity.compiles"] == 0
    # no device plane in a CPU trace: the kernel's and the scopes' shares
    # read nothing and are left out, as on a program without them
    assert "tiny-trinity.attn_window_pct" not in metrics
    assert "tiny-trinity.ragged_roofline" not in metrics


def test_the_configuration_is_the_published_widths_and_the_stated_cut():
    cfg = _config()
    mix = traffic.load_mix("mixedq-closed")
    cell = cells.Cell(name=CELL, chips=1, config=cfg, mix=mix,
                      end_to_end=[], per_layer=[])
    assert cell.family.ROUTED and cell.family.REFERENCE == "afmoe"
    model = sut.build_model(cell)
    c = model.config
    assert (c.hidden_size, c.n_heads, c.kv_heads, c.head_dim, c.n_layers) \
        == (2048, 32, 4, 128, 16)
    assert (c.ffn_dim, c.moe_ffn_hidden_size, c.first_dense_layers) == \
        (6144, 1024, 2)
    assert (c.moe_num_experts, c.experts_held, c.moe_top_k) == (128, 16, 8)
    assert c.moe_dropless and c.moe_scoring == "sigmoid"
    assert c.moe_routed_scale == 2.826 and c.moe_route_norm_eps == 1e-20
    assert c.local_attn_pattern == (2048, 2048, 2048, 0) * 4
    assert c.rope_pattern == (True, True, True, False) * 4
    assert (c.layer_period, c.leading_layers, c.attn_window) == (4, 4, 2048)
    assert c.attn_gate and c.sandwich_norm and c.qk_norm == "rms"
    assert c.embed_scale == pytest.approx(2048 ** 0.5)
    shapes = jax.eval_shape(lambda k: model.init(k, jax.numpy.bfloat16),
                            jax.random.key(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    # ISSUE 37's count a layer, at its fallback depth of 16: attention
    # 27.26 M x 16, dense 37.75 M x 2, an expert layer 107.2 M x 14,
    # embedding and head 102.5 M (and norms)
    assert abs(n_params - 2.115e9) < 0.01e9
    assert len(shapes["layers"]) == 4 and len(shapes["periods"]) == 4
    assert shapes["periods"][3]["moe"]["w_up"].shape == (3, 16, 2048, 1024)
    assert shapes["layers"][0]["wg_attn"].shape == (2048, 4096)
    # the pools: 4 full layers x 1,024 pages of 1 MiB, 12 window layers x
    # 16 rings of 17 pages; the longest request fits the growing table
    engine = cfg["serve"]["engine"]
    pools = jax.eval_shape(lambda: model.init_paged_caches(
        engine["num_pages"], engine["page_size"],
        ring_slots=mix["max_batch"]))
    assert pools.full.k_pages.shape == (4, 1025, 4, 128, 128)
    assert pools.ring.k_pages.shape == (12, 16 * 17 + 1, 4, 128, 128)
    nbytes = sum(x.size * 2 for x in jax.tree_util.tree_leaves(pools))
    assert abs(nbytes - (1.075e9 + 0.859e9)) < 0.01e9
    lengths = traffic.quantile_grid(mix["prompt_tokens"], mix["cycle"])
    answers = traffic.quantile_grid(mix["output_tokens"], mix["cycle"])
    # ISSUE 37's cycle of 16: 7 prompts inside the window, 9 beyond it
    assert (lengths.min(), lengths.max(), lengths.sum()) == \
        (380, 12846, 58245)
    assert (answers.min(), answers.max(), answers.sum()) == (102, 598, 4492)
    assert int((lengths > cfg["sliding_window"]).sum()) == 9
    assert lengths.max() + answers.max() <= engine["max_seq"]
    # the harness warms each bucket up with the longest prompt the mix's
    # distribution allows (not the cycle's) and 2 new tokens: the engine
    # must take it (hence the clip at 16,382 under ``max_seq`` 16,384)
    assert mix["prompt_tokens"]["max"] + 2 <= engine["max_seq"] \
        <= cfg["max_position_embeddings"]
    # the whole cycle's reservations fit the full layers' pages
    assert (lengths.sum() + answers.sum()) // engine["page_size"] + 16 \
        < engine["num_pages"]
    for key in ("source", "published", "reduced", "assumed", "deployment"):
        assert cfg[key]
    assert "8 chips" in cfg["deployment"] and cfg["serve"]["departures"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    # the cut in depth keeps whole periods of the published pattern
    published = cfg["published"]
    assert (published["num_hidden_layers"], len(published["layer_types"])) \
        == (32, 32)
    assert cfg["layer_types"] == \
        published["layer_types"][:cfg["num_hidden_layers"]]
    assert cfg["num_hidden_layers"] % cfg["global_attn_every_n_layers"] == 0


# cell -> (configuration, traffic, the ending of the metrics only it
# reads, how many list it).  By the cell's membership of ``workloads``,
# wherever the entries stand: a later PR appends to the same lists and a
# ``benchmark`` PR joins a cell to an entry that already reads what it
# needs (PR 53: ``compiles.serve``, ``decode_ms.serve``, ...)
CELL_ENTRIES = {
    "serve-glm5-ep16-longctx": ("glm-5-ep16", "longctx-closed", ".longctx",
                                13),
    CELL: ("trinity-mini-ep8", "mixedq-closed", ".mixedq", len(MIXEDQ)),
}


@pytest.mark.parametrize("name", list(CELL_ENTRIES))
def test_a_cells_entries_are_what_its_issue_listed(name):
    """One configuration, one cell on one chip, its name under
    ``serve_tok_s`` alone, and the metrics that list it, whose files
    agree with their entries and whose readers exist; those that list it
    alone are the files of its ending."""
    config_name, mix, ending, count = CELL_ENTRIES[name]
    bench = tree.bench()
    (cell,) = [w for w in bench["workloads"] if w["name"] == name]
    (config,) = [c for c in bench["configs"] if c["name"] == config_name]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (config_name, mix, 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert [m["name"] for m in bench["end_to_end"]
            if name in m.get("workloads", ())] == ["serve_tok_s"]
    with open(os.path.join(cells.ROOT, config["file"])) as f:
        held = json.load(f)
    assert held["reduced"] == config["reduced"] == list(held["published"])
    assert held["source"] == config["source"]
    entries = tree.held_entries(name, moves="serve_tok_s")
    assert len(entries) == count
    assert {m["name"] for m in entries if m["workloads"] == [name]} == \
        {f[:-5] for f in os.listdir(METRICS) if f.endswith(ending + ".json")}


def test_the_control_rounds_in_place_and_reads_what_the_control_reads():
    """``chipbench/control_in_place.py`` is ``control.py`` with the seeded
    weights donated to the rounding (two copies of this model's do not fit
    the chip): the same rows, the same error, over the tolerance."""
    from chipbench import control, control_in_place, serve_cell
    cell = cells.Cell("tiny-trinity", 1, tree.data("tiny-trinity"),
                      tree.data("tiny-closed"), [], [])
    devices = jax.devices()[:1]
    ours = control_in_place.control_error(cell, 3, devices)
    assert ours == control.control_error(cell, 3, devices)
    assert ours["logit_error"] > 2 * serve_cell.LOGIT_TOL and not ours["ok"]


def test_this_cells_metrics_are_the_issues_fifteen():
    assert sorted(tree.base(m["name"])
                  for m in tree.entries_of(tree.bench(), CELL)) == \
        sorted(MIXEDQ)


def _sizes():
    cfg = _config()
    return cells.importlib.import_module(
        "chipbench.families.afmoe").model_sizes(cfg, cfg["serve"]["engine"])


def test_the_kernels_cost_is_the_windows_work():
    """A decode step at context 5,000: a full layer reads the 40 pages of
    the context, a window layer the 17 that hold its 2,048 keys; a prefill
    of 4,096 meets 2,048 keys a query past the window."""
    model = _sizes()
    assert (model["window_layers"], model["full_layers"], model["window"]) \
        == (12, 4, 2048)
    page_bytes = 2 * 128 * 4 * 128 * 2          # K and V, 4 heads of 128
    row = 2 * 32 * 128 * 2                      # a query in, a result out
    flops, nbytes = ragged_window_serve.call(1, [5000], model)
    assert (flops, nbytes) == (4 * 5000 * 32 * 128, 40 * page_bytes + row)
    flops, nbytes = ragged_window_serve.call(1, [5000], model, 2048)
    assert (flops, nbytes) == (4 * 2048 * 32 * 128, 17 * page_bytes + row)
    # inside the window the two kinds cost the same
    assert ragged_window_serve.call(1, [700, 2048], model, 2048) == \
        ragged_window_serve.call(1, [700, 2048], model)
    flops, nbytes = ragged_window_serve.call(4096, [4096], model, 2048)
    assert flops == 4 * (2048 * 2049 // 2 + 2048 * 2048) * 32 * 128
    assert nbytes == 32 * page_bytes + 4096 * row
    whole, _ = ragged_window_serve.call(4096, [4096], model)
    assert whole == 4 * (4096 * 4097 // 2) * 32 * 128


def test_the_cost_lies_under_its_kernels_time_on_a_made_up_trace():
    """``kernel_roofline`` with this cost over a made-up device line: two
    decode kernels and a prefill kernel of the ragged name, beside an XLA
    fusion it must not count."""
    model = _sizes()
    steps = [{"t0": 0.0, "t1": 0.1, "traced": True, "dispatches": [
        {"phase": "prefill", "batch": 1, "tokens": 4096, "real": 3000,
         "context": 3000, "t0_ns": 0},
        {"phase": "decode", "batch": 16, "tokens": 1,
         "contexts": [3001, 500, 9000], "t0_ns": 5e7}]}]
    labels = ["ragged_paged_attention_prefill.1:custom-call",
              "ragged_paged_attention_decode.2:custom-call",
              "fusion.3:fusion"]
    ms = lambda *xs: np.asarray(xs, np.float64) * 1e6   # noqa: E731
    trace = reduce.Trace(
        labels=labels, kinds=["pallas", "pallas", "xla"],
        ops=[reduce.DeviceLine(ms(1, 30, 60), ms(20, 4, 30),
                               np.asarray([0, 1, 2]))],
        annotations=[("chipbench/step", 0.0, 1e8)])
    run = cells.Run(chips=1, peaks=PEAKS, model=model, steps=steps,
                    traced_steps=steps, samples={}, counters={},
                    memory_peak_bytes=0, trace=trace)
    least = ragged_window_serve.least_seconds(run)
    # by hand: the prefill is compute-bound in both kinds, the decode
    # memory-bound; 4 full layers and 12 window layers
    def seconds(new, contexts, window):
        flops, nbytes = ragged_window_serve.call(new, contexts, model,
                                                 window)
        return max(flops / 197e12, nbytes / 819e9)
    want = sum(layers * (seconds(3000, [3000], w)
                         + seconds(1, [3001, 500, 9000], w))
               for layers, w in ((4, None), (12, 2048)))
    assert least == pytest.approx(want)
    assert 0 < least < 0.024
    share = kernel_roofline.read(run, "ragged_window_serve",
                                 kernel="ragged_paged_attention_")
    assert share == pytest.approx(100 * least / 0.024) and share < 100
    # another model's cost reads no window sizes: its own file
    assert "window_layers" not in {"n_layers": 16, "heads": 16}


def test_the_models_products_are_counted_by_kind():
    """``serve_mfu_window`` on two made-up steps, against the count by
    hand."""
    model = _sizes()
    steps = [
        {"t0": 0.0, "t1": 0.5, "dispatches": [
            {"phase": "prefill", "real": 3000, "context": 3000,
             "expert_pairs": 90000}]},
        {"t0": 0.5, "t1": 0.6, "dispatches": [
            {"phase": "decode", "contexts": [3001, 500],
             "expert_pairs": 60}]}]
    run = cells.Run(chips=1, peaks=PEAKS, model=model, steps=steps,
                    traced_steps=[], samples={}, counters={},
                    memory_peak_bytes=0)
    proj = 3 * 2048 * 4096 + 2 * 2048 * 512
    expert = 3 * 2048 * 1024
    token = 16 * proj + 2 * 3 * 2048 * 6144 + 14 * (2048 * 128 + expert)
    window_keys = 2048 * 2049 // 2 + 952 * 2048 + 2048 + 500
    full_keys = 3000 * 3001 // 2 + 3001 + 500
    macs = 3002 * token + (12 * window_keys + 4 * full_keys) * 2 * 32 * 128 \
        + 90060 * expert + 3 * 2048 * 25024
    assert serve_mfu_window.read(run) == pytest.approx(
        100 * 2 * macs / 0.6 / 197e12)
    assert 0 < serve_mfu_window.read(run) < 100
    run.model = {"n_layers": 16}        # another family's sizes
    assert serve_mfu_window.read(run) is None
