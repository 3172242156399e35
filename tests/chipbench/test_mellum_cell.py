"""The ``mellum`` family through the command on the CPU: a toy
configuration of the same shape as ``mellum2-12b-ep4``
(``data/tiny-mellum.json``: one period of three window layers and a YaRN
full layer, 4 of 16 softmax-routed experts held, trained in float32 under
ZeRO-3 in two micro-batches) is added to the made-up tree as files and
entries, as a PR adds a cell, and run untraced and traced; the repo's own
configuration is held to the published widths and to the arithmetic of its
cut, ``BENCHMARK.json`` is held to CONTAIN what ISSUE 43 lists (found by
name: later PRs append after it), and the two cost files to operations and
bytes counted by hand."""

import json
import os
import types

import jax
import pytest

import tree
from chipbench import cells, sut, traffic
from chipbench.costs import expert_glu_train, flash_window_train
from chipbench.reducers import moe_train_gauges, moe_train_mfu

CELL = "train-mellum2-12b-ep4-s8192"
MOETRAIN = ["step_ms", "peak_hbm_gb", "idle_pct", "compiles", "host_ms",
            "experts_pct", "router_pct", "attn_window_pct", "attn_full_pct",
            "loss_head_pct", "optimizer_pct", "remat_pct", "mfu_pct",
            "expert_load_ratio", "expert_pad_pct", "expert_glu_roofline",
            "flash_roofline", "fwd_pct", "bwd_pct", "flash_pct",
            "xla_ops_pct"]
# what a CPU run can read: its trace has no device plane and it has no
# memory statistics, so the shares of the device's time, the rooflines
# and the peak are left out, as on a program without the scopes
ON_THE_CPU = ["step_ms", "compiles", "host_ms", "mfu_pct",
              "expert_load_ratio", "expert_pad_pct"]
METRICS = os.path.join(cells.ROOT, "chipbench", "layer_metrics")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    with open(os.path.join(cells.ROOT, "chipbench", "configs",
                           "mellum2-12b-ep4.json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """``tree.make``'s benchmark plus one cell: ``tiny-mellum`` under
    ``tiny-pretrain``, reading what the cell reads through files of its
    own."""
    return tree.add_cell(tree.make(tmp_path_factory.mktemp("mellum_tree")),
                         "tiny-mellum", CELL, "tiny-pretrain")


def test_the_toy_cell_trains_and_is_correct(checkout):
    line, earlier = tree.run(checkout, "tiny-mellum", seed=2 ** 31 + 11)
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    log = earlier[-1]
    assert log["loss_rel_error"] <= 1e-5        # float32 on both sides
    assert log["compiles_in_window"] == 0 and log["gas"] == 2
    # the other half of ``correct`` compares two single batches of 128
    # tokens, 0.1 apart by noise, so it is true once some fifty steps fit
    # into the window, which a loaded machine does not always give: the
    # rule is held to the logged numbers, the fall to a window that shows it
    losses = log["losses"]
    assert line["correct"] is (losses[-1] < log["first_loss"])
    if len(losses) >= 40:
        assert sum(losses[-10:]) < sum(losses[:10])


def test_the_traced_toy_run_reads_every_moetrain_metric_a_cpu_has(checkout):
    line, _ = tree.run(checkout, "tiny-mellum", trace=1)
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    for name in ON_THE_CPU:
        assert isinstance(metrics[f"tiny-mellum.{name}"], float), name
    for name in set(MOETRAIN) - set(ON_THE_CPU):
        assert f"tiny-mellum.{name}" not in metrics, name
    assert metrics["tiny-mellum.expert_load_ratio"] >= 1.0
    assert 0 <= metrics["tiny-mellum.expert_pad_pct"] < 100
    assert 0 < metrics["tiny-mellum.mfu_pct"] < 100
    assert metrics["tiny-mellum.compiles"] == 0


def test_the_configuration_is_the_published_widths_and_the_stated_cut():
    cfg = _config()
    cell = cells.Cell(name=CELL, chips=1, config=cfg,
                      mix=traffic.load_mix("pretrain-s8192"),
                      end_to_end=[], per_layer=[])
    assert cell.family.REFERENCE == "mellum" \
        and not hasattr(cell.family, "ROUTED")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():    # every key as published
        assert cfg["published"].get(key, cfg[key]) == value, key
    assert set(cfg["reduced"]) == {"num_experts", "vocab_size",
                                   "num_hidden_layers", "layer_types",
                                   "mlp_layer_types"}
    layers = cfg["num_hidden_layers"]
    assert layers in (4, 8) and cfg["layer_types"] == \
        row["config"]["layer_types"][:layers]
    assert (cfg["num_experts"], cfg["vocab_size"]) == (16, 24576)
    assert "4 chips" in cfg["deployment"] and cfg["moe_aux_loss_coef"] == 0.0
    model = sut.build_model(cell, **cfg["train"]["model"])
    c = model.config
    assert (c.hidden_size, c.n_heads, c.kv_heads, c.head_dim) == \
        (2304, 32, 4, 128)
    assert (c.moe_num_experts, c.experts_held, c.moe_top_k,
            c.moe_ffn_hidden_size) == (64, 16, 8, 896)
    assert c.moe_dropless and c.moe_scoring == "softmax" \
        and c.moe_norm_topk_prob and not c.moe_shared_experts
    assert c.local_attn_pattern == (1024, 1024, 1024, 0) * (layers // 4)
    assert c.layer_period == 4 and c.qk_norm == "rms" and c.norm_eps == 1e-6
    shapes = jax.eval_shape(lambda k: model.init(k, jax.numpy.float32),
                            jax.random.key(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    # ISSUE 43's count: 120.47 M a layer and a quarter of the vocabulary
    assert abs(n_params - (layers * 120.47e6 + 113.2e6)) < 0.1e6 * layers
    assert n_params == c.num_params()
    assert shapes["periods"][3]["moe"]["w_up"].shape == \
        (layers // 4, 16, 2304, 896)
    assert shapes["periods"][0]["moe"]["wg"].shape == (layers // 4, 2304, 64)
    engine = cfg["train"]["engine"]
    assert engine["zero_optimization"]["stage"] == 3 \
        and engine["bf16"]["enabled"] \
        and cfg["train"]["micro_batch_per_chip"] == 1
    mix = cell.mix
    assert (mix["seq_len"], mix["sequences_per_step_per_chip"]) == (8192, 2)


def test_the_benchmark_holds_what_the_issue_lists():
    bench = _bench()
    config = next(c for c in bench["configs"]
                  if c["name"] == "mellum2-12b-ep4")
    assert config["file"] == "chipbench/configs/mellum2-12b-ep4.json"
    assert config["reduced"] == _config()["reduced"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("mellum2-12b-ep4", "pretrain-s8192", 1)
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == "train_tok_s_chip")
    assert CELL in moved["workloads"]
    # ten of the twenty-one the cell reads through the ``.train`` entries
    # it joined (PR 53: same reducer, same arguments); what needs this
    # family's own costs and scopes keeps the ending ``.moetrain``
    entries = tree.held_entries(CELL, moves="train_tok_s_chip")
    assert sorted(tree.base(m["name"]) for m in entries) == sorted(MOETRAIN)
    assert {m["name"] for m in entries if m["workloads"] == [CELL]} == \
        {f[:-5] for f in os.listdir(METRICS) if f.endswith(".moetrain.json")}
    loaded = cells.load_cell(CELL)
    assert {m["name"] for m in loaded.per_layer} >= {
        m["name"] for m in entries}


def test_the_costs_against_operations_and_bytes_counted_by_hand():
    cfg = _config()
    # one expert layer of one micro-batch that routed 16,384 pairs:
    # 3 products of 2 x 2304 x 896 a pair in each kernel
    cost = expert_glu_train.call_costs(cfg, 16384)
    flops = 3 * 2 * 2304 * 896 * 16384
    assert {k: v[0] for k, v in cost.items()} == dict.fromkeys(
        ("fwd", "dx", "dw"), flops)
    leaves, rows, narrow = 16 * 3 * 2304 * 896, 16384 * 2304 * 2, \
        16384 * 896 * 2
    assert cost["fwd"][1] == leaves * 2 + 2 * rows
    assert cost["dx"][1] == leaves * 2 + 3 * rows + 3 * narrow
    assert cost["dw"][1] == 2 * rows + 3 * narrow + leaves * 4
    # a window layer's query meets at most 1,024 keys, a full layer's all
    assert moe_train_mfu.attended_pairs(8192, 1024) == \
        1024 * 1025 // 2 + 7168 * 1024
    assert moe_train_mfu.attended_pairs(8192, 0) == 8192 * 8193 // 2
    window = flash_window_train.layer_costs(cfg, 1, 8192, 1024)
    product = 2 * 32 * 128 * (1024 * 1025 // 2 + 7168 * 1024)
    assert [window[k][0] for k in ("fwd", "dq", "dkv")] == \
        [2 * product, 3 * product, 4 * product]
    q, k = 8192 * 32 * 128 * 2, 8192 * 4 * 128 * 2
    assert window["fwd"][1] == 2 * q + 2 * k
    # the whole step's count: 6 a parameter of the projections, the router
    # and the head, 6 x 3 d f a pair, 12 H D a (query, key) pair
    layers = cfg["num_hidden_layers"]
    per_token = layers * (2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64) \
        + 2304 * 24576
    attended = (layers // 4) * (3 * (1024 * 1025 // 2 + 7168 * 1024)
                                + 8192 * 8193 // 2)
    assert moe_train_mfu.step_flops(cfg, 8192, 2, 262144) == \
        6 * per_token * 16384 + 18 * 2304 * 896 * 262144 \
        + 12 * 32 * 128 * attended * 2


def test_the_gauge_readers_return_nothing_without_gauges(monkeypatch):
    from deepspeed_tpu.monitor.telemetry import get_telemetry
    monkeypatch.setattr(get_telemetry(), "registry",
                        type(get_telemetry().registry)())
    run = types.SimpleNamespace(
        model={"n_layers": 8, "gas": 2, "seq": 8192, "micro_batch": 1},
        config=_config(), steps=[{"t0": 0.0, "t1": 1.0}],
        peaks={"bf16_flops_per_s": 1.97e14})
    assert moe_train_gauges.read(run, "pad_pct") is None
    assert moe_train_mfu.read(run) is None
    registry = get_telemetry().registry
    for name, value in (("expert_pairs", 262144.0),
                        ("expert_load_max", 1100.0),
                        ("expert_rows", 278528.0)):
        registry.gauge("train/moe/" + name).set(value)
    assert moe_train_gauges.read(run, "load_ratio") == \
        pytest.approx(1100 / 1024)
    assert moe_train_gauges.read(run, "pad_pct") == \
        pytest.approx(100 * 16384 / 278528)
    assert 0 < moe_train_mfu.read(run) < 100
