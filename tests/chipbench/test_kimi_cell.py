"""The ``kimi_k2`` family through the command on the CPU: a toy
configuration of the same shape as ``kimi-k2-ep32`` (``data/tiny-kimi.json``:
a dense first layer, latent attention with NO selection, YaRN, 3 of 12
routed experts, served under the chunked policy in chunks of 32 tokens) is
added to the made-up tree as files and entries, as a PR adds a cell, and
run untraced and traced; the repo's own configuration is held to the
published widths and to the arithmetic of its cut, and ``BENCHMARK.json``
is held to CONTAIN what ISSUE 41 lists (later PRs append after it)."""

import json
import os

import jax
import pytest

import tree
from chipbench import cells, sut, traffic
from chipbench.reducers import serve_mfu_dense_latent

CELL = "serve-kimi-k2-ep32-agentctx"
AGENTCTX = ["latent_attn_pct", "experts_pct", "expert_load_ratio",
            "decode_ms", "prefill_ms_per_ktok", "prefill_pad_pct",
            "prefill_share_pct", "loop_host_ms", "idle_pct", "compiles",
            "peak_hbm_gb", "ctx_reread", "mfu_pct"]
# ``latent_ctx_pct`` left with PR 53: since PR 45 the dense prefill is one
# Pallas kernel and no program of the cell has a ``latent_ctx`` scope
# what a CPU run can read: no device plane in its trace and no memory
# statistics, so what is read from the device's line (the shares of its
# time by scope, a chunk's device time: ``reducers/launch_order.py``) and
# the peak are left out, as on a program without the scopes
ON_THE_CPU = [n for n in AGENTCTX
              if n not in ("latent_attn_pct", "experts_pct",
                           "prefill_ms_per_ktok",
                           "prefill_share_pct", "idle_pct", "peak_hbm_gb")]
METRICS = os.path.join(cells.ROOT, "chipbench", "layer_metrics")


def _config():
    with open(os.path.join(cells.ROOT, "chipbench", "configs",
                           "kimi-k2-ep32.json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """``tree.make``'s benchmark plus one cell: ``tiny-kimi`` under
    ``tiny-closed``, reading what the cell reads through files of its
    own."""
    return tree.add_cell(tree.make(tmp_path_factory.mktemp("kimi_tree")),
                         "tiny-kimi", CELL, "tiny-closed")


def test_the_toy_cell_runs_chunked_and_is_correct(checkout):
    line, earlier = tree.run(checkout, "tiny-kimi", seed=2 ** 31 + 9)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    log = earlier[-1]
    assert log["logit_error"] <= 1e-4       # float32 on both sides
    assert log["lost"] == [] and log["leaks"] == {}
    assert log["rows_compared"] + log["rows_undecided"] == 72
    assert log["rows_compared"] >= 36
    assert log["compiles_in_window"] == 0
    # prompts of 20-120 tokens in chunks of 32: more prefill dispatches
    # than requests
    assert log["prefills_in_window"] > log["requests_in_window"]


def test_the_traced_toy_run_reads_every_agentctx_metric(checkout):
    line, _ = tree.run(checkout, "tiny-kimi", trace=1)
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    for name in ON_THE_CPU:
        assert isinstance(metrics[f"tiny-kimi.{name}"], float), name
    assert "tiny-kimi.latent_attn_pct" not in metrics
    # the toy's key blocks (512) are wider than its prompts: every chunk
    # after a prompt's first walks one whole block, so 0 < re-read
    assert 0 < metrics["tiny-kimi.ctx_reread"] < 512 / 20
    assert metrics["tiny-kimi.expert_load_ratio"] >= 1.0
    assert 0 < metrics["tiny-kimi.mfu_pct"] < 100
    assert metrics["tiny-kimi.prefill_pad_pct"] > 0
    assert "tiny-kimi.prefill_share_pct" not in metrics
    assert metrics["tiny-kimi.compiles"] == 0


def test_the_configuration_is_the_published_widths_and_the_stated_cut():
    cfg = _config()
    mix = traffic.load_mix("agentctx-closed")
    cell = cells.Cell(name=CELL, chips=1, config=cfg, mix=mix,
                      end_to_end=[], per_layer=[])
    assert cell.family.ROUTED and cell.family.REFERENCE == "kimi_k2"
    model = sut.build_model(cell)
    c = model.config
    assert (c.hidden_size, c.n_heads, c.q_lora_rank, c.kv_lora_rank) == \
        (7168, 64, 1536, 512)
    assert (c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim) == \
        (128, 64, 128)
    assert (c.index_topk, c.index_n_heads, c.n_layers) == (0, 0, 6)
    assert (c.ffn_dim, c.moe_ffn_hidden_size, c.first_dense_layers) == \
        (18432, 2048, 1)
    assert (c.moe_num_experts, c.experts_held, c.moe_top_k) == (384, 12, 8)
    assert c.moe_dropless and c.moe_scoring == "sigmoid"
    assert c.moe_routed_scale == 2.827 and c.moe_route_norm_eps == 1e-20
    assert c.rope_yarn == (32.0, 4096, 1.0, 1.0, 1.0, 1.0)
    assert c.rope_theta == 50000.0 and c.norm_eps == 1e-6
    shapes = jax.eval_shape(lambda k: model.init(k, jax.numpy.bfloat16),
                            jax.random.key(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    # ISSUE 41's count: 1 dense + 5 expert layers, embedding and head
    assert abs(n_params - 4.173e9) < 0.001e9
    assert "idx_wq" not in shapes["layers"][0]
    assert shapes["layers"][1]["moe"]["w_up"].shape == (12, 7168, 2048)
    assert shapes["layers"][1]["moe"]["wg"].shape == (7168, 384)
    # the pools: a row of 640 for the 576 values, NO index pool
    engine = cfg["serve"]["engine"]
    pools = jax.eval_shape(lambda: model.init_paged_caches(
        engine["num_pages"], engine["page_size"]))
    assert pools.latent_pages.shape == (6, 2177, 128, 640)
    assert pools.index_pages.size == 0
    chunk = engine["serving"]["scheduler"]["prefill_chunk_tokens"]
    assert engine["serving"]["scheduler"]["policy"] == "chunked"
    assert chunk % engine["page_size"] == 0
    # the longest request, and the warm-up's, fit a slot's pages
    longest = max(mix["prompt_tokens"]["max"] + 2,
                  int(traffic.quantile_grid(mix["prompt_tokens"],
                                            mix["cycle"]).max())
                  + mix["output_tokens"]["max"])
    assert longest <= engine["max_seq"]
    assert engine["num_pages"] - 1 == mix["max_batch"] * \
        engine["max_seq"] // engine["page_size"]
    # every prompt of the cycle is two to eight chunks
    grid = traffic.quantile_grid(mix["prompt_tokens"], mix["cycle"])
    assert [int(-(-n // chunk)) for n in grid] == [3, 4, 4, 5, 6, 7, 7, 8]
    assert int(grid.sum()) == 81920


def test_the_file_states_its_cut_and_keeps_every_published_width():
    cfg = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") \
            if os.path.exists(
                "/opt/skills/guides/model-configs/architectures.jsonl") \
            else open(os.devnull) as f:
        rows = [json.loads(line) for line in f if "Kimi-K2-Instruct" in line]
    assert cfg["reduced"] == list(cfg["published"]) == \
        ["n_routed_experts", "vocab_size", "num_hidden_layers"]
    assert (cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_hidden_layers"]) == (12, 20480, 6)
    assert cfg["published"] == {"n_routed_experts": 384,
                                "vocab_size": 163840,
                                "num_hidden_layers": 61}
    assert "32 chips" in cfg["deployment"]
    assert cfg["assumed"] and cfg["serve"]["departures"]
    for row in rows:        # where the catalog is installed: key by key
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key


def test_the_benchmark_contains_what_the_issue_lists():
    """One configuration, one cell on one chip, its name under
    ``serve_tok_s`` alone, thirteen metrics (fourteen less
    ``latent_ctx_pct``, PR 53) whose files agree with their entries and
    whose readers exist.  Found by the cell's membership of ``workloads``:
    a later PR appends after these and a ``benchmark`` PR joins lists."""
    bench = _bench()
    config, = [c for c in bench["configs"] if c["name"] == "kimi-k2-ep32"]
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("kimi-k2-ep32", "agentctx-closed", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert "32x" in cell["why"]
    assert [m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", ())] == ["serve_tok_s"]
    held = _config()
    assert held["reduced"] == config["reduced"]
    assert held["source"] == config["source"]
    assert config["file"] == "chipbench/configs/kimi-k2-ep32.json"
    entries = {tree.base(m["name"]): m
               for m in tree.held_entries(CELL, moves="serve_tok_s")}
    assert sorted(entries) == sorted(AGENTCTX)
    # what only this cell reads keeps its ending; the rest it reads
    # through the ``.serve`` lists it shares
    assert {m["name"] for m in entries.values()
            if m["workloads"] == [CELL]} == \
        {f[:-5] for f in os.listdir(METRICS) if f.endswith(".agentctx.json")}
    # under the chunked policy a chunk is launched and left: what is read
    # of the device's line is cut by launch order, not by launch time, and
    # a chunk's cost is its device time, not its ``serve/prefill`` span
    for name, reducer in (("latent_attn_pct", "scope_pct_in_order"),
                          ("experts_pct", "scope_pct_in_order"),
                          ("prefill_ms_per_ktok", "phase_device_ms_per_ktok"),
                          ("prefill_share_pct", "phase_device_share_pct")):
        assert entries[name]["name"] == name + ".agentctx"
        with open(os.path.join(METRICS, name + ".agentctx.json")) as f:
            assert json.load(f)["reducer"] == reducer
        assert entries[name]["source"] == "device_trace"
    assert entries["ctx_reread"]["source"] == "program_counter"
    # no four-chip cell was added
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_models_share_of_the_peak_counts_the_whole_causal_context():
    """A chunk's tokens meet every earlier key; by hand at toy sizes."""
    sizes = cells.Cell(name=CELL, chips=1, config=_config(), mix={},
                       end_to_end=[], per_layer=[]).family.model_sizes(
        _config(), _config()["serve"]["engine"])
    assert sizes["entry_bytes"] == 1280 and sizes["experts_held"] == 12
    d, H = 7168, 64
    proj = d * 1536 + 1536 * H * 192 + d * 576 + 512 * H * 256 + H * 128 * d
    assert proj == 101_122_048              # ISSUE 41's attention a layer
    expert = 3 * d * 2048
    per_token = 6 * proj + 3 * d * 18432 + 5 * (d * 384 + expert)
    chunk = {"phase": "prefill", "real": 2048, "context": 6144,
             "head_rows": 0, "expert_pairs": 500}
    decode = {"phase": "decode", "contexts": [5000, 9000],
              "expert_pairs": 1}
    last = dict(chunk, context=8192, head_rows=1, expert_pairs=400)
    keys = sum(range(4097, 6145))
    # nothing is sampled from the first chunk: five whole layers, and of
    # the sixth the joint latent projection its cache write reads; of the
    # pairs counted over five expert layers, four layers' share
    entries_only = 2048 * (per_token - (proj - d * 576)
                           - (d * 384 + expert)) \
        + 5 * keys * H * 320 + 500 * 4 / 5 * expert
    want = 2.0 * entries_only \
        + 2.0 * (2048 * per_token + 6 * sum(range(6145, 8193)) * H * 320
                 + 400 * expert + d * 20480) \
        + 2.0 * (2 * per_token + 6 * 14000 * H * 320 + expert
                 + 2 * d * 20480)
    steps = [{"t0": 0.0, "t1": 1.0, "dispatches": [chunk, last, decode]}]
    run = cells.Run(chips=1, peaks=PEAKS, model=sizes, steps=steps,
                    traced_steps=[], samples={}, counters={},
                    memory_peak_bytes=0)
    assert serve_mfu_dense_latent.read(run) == pytest.approx(
        100 * want / 197e12)
    # another family's sizes: nothing to read
    run.model = {"kv_rank": 512, "index_topk": 2048}
    assert serve_mfu_dense_latent.read(run) is None
