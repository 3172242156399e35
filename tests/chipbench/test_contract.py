"""BENCHMARK.json against the contract it is read by, and against the
benchmark's own files (the driver checks the first before any run; the
second is what keeps the data-driven harness whole).  Every check runs
twice: on the repo's benchmark, and on the made-up one that ``tree.py``
builds by adding cells as files, which is what a later PR's will look
like."""

import copy
import json
import os
import re

import pytest

import tree
from tree import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

# What may stand in a configuration's ``reduced`` (chipbench/README.md,
# "Adding things"; the model-configs guide, section 4): a COUNT, how many
# layers, experts, heads or rows of the vocabulary this chip holds of a
# stated deployment, and never a WIDTH.  A count is named here with what
# it counts; a key that reads like a width and is not one of these is
# refused.
COUNTS = {"num_hidden_layers": "layers",
          "n_routed_experts": "experts", "num_experts": "experts",
          "num_local_experts": "experts",
          "num_attention_heads": "heads", "num_key_value_heads": "heads",
          "vocab_size": "vocabulary"}
WIDTH = re.compile(r"_size$|_dim$|_rank$|experts_per_tok|top_?k$|window"
                   r"|expan|state|conv")
# sizes of a model that are widths, as their public configs name them
WIDTHS = ["hidden_size", "head_dim", "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "index_head_dim", "linear_key_head_dim",
          "intermediate_size", "moe_intermediate_size", "kv_lora_rank",
          "q_lora_rank", "num_experts_per_tok", "experts_top_k",
          "sliding_window", "index_topk", "linear_conv_kernel_dim"]
# the floors under a cut, so that what is left is still the model
MIN_VOCABULARY_SHARE = 8      # at least an eighth of the published rows
MIN_EXPERTS = 8
MIN_LAYERS = 4                # after the leading dense ones
CHIPS_STATED = re.compile(r"\b(\d+) chips\b")


def is_width(key):
    return key not in COUNTS and bool(WIDTH.search(key))


def what_it_counts(key):
    """``layers``, ``experts``, ``heads`` or ``vocabulary`` for a key of
    ``reduced`` that cuts one of them (``index_n_heads`` counts heads
    too), else None."""
    if key in COUNTS:
        return COUNTS[key]
    if is_width(key):
        return None
    return "experts" if "expert" in key else \
        "heads" if "head" in key else None


def share_faults(entry, held):
    """What is wrong with one configuration's cut: ``entry`` is its entry
    in ``BENCHMARK.json``, ``held`` its file.  An empty list when it is a
    chip's share of the model as the guide allows one."""
    faults = []
    reduced, published = entry["reduced"], held.get("published", {})
    if held.get("reduced") != reduced:
        faults.append("the file's `reduced` is not the entry's")
    if set(published) != set(reduced):
        faults.append("`published` does not give exactly the keys reduced")
    faults += [f"{key} is a width and may not be reduced"
               for key in reduced if is_width(key)]
    for key in reduced:
        if key not in held or key not in published:
            continue
        kind, here = what_it_counts(key), held[key]
        if kind and not 0 < here <= published[key]:
            faults.append(f"{key}: {here} held of {published[key]}")
        if kind == "vocabulary" and \
                here * MIN_VOCABULARY_SHARE < published[key]:
            faults.append(f"{key}: {here} rows are under an eighth of "
                          f"{published[key]}")
        if kind == "experts" and here < MIN_EXPERTS:
            faults.append(f"{key}: {here} experts are under {MIN_EXPERTS}")
        if kind == "layers" and \
                here - held.get("first_k_dense_replace", 0) < MIN_LAYERS:
            faults.append(f"{key}: under {MIN_LAYERS} layers after the "
                          f"leading dense ones")
    shared = sorted({what_it_counts(k) for k in reduced}
                    & {"experts", "heads", "vocabulary"})
    if shared and not CHIPS_STATED.search(str(held.get("deployment", ""))):
        faults.append(f"a share of the {', '.join(shared)} needs a "
                      f"`deployment` that says over how many chips each "
                      f"layer is divided (\"... N chips ...\")")
    return faults


@pytest.fixture(scope="module", params=["repo", "made-up tree"])
def root(request, tmp_path_factory):
    """The root of a benchmark: the repo's own, or the made-up one."""
    if request.param == "repo":
        return REPO
    return tree.make(tmp_path_factory.mktemp("contract_tree"))


@pytest.fixture(scope="module")
def bench(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_keys_names_and_lengths(bench, root):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 64 << 10
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), group,
                          entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200, (entry["name"], key)
                    assert "\n" not in entry[key] and "\t" not in entry[key]
    metric_names = [n for is_metric, _, n in names if is_metric]
    assert len(metric_names) == len(set(metric_names))
    for entry in bench["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert len(entry["reduced"]) <= 16
        assert all(NAME.match(key) for key in entry["reduced"])
    for entry in bench["workloads"]:
        assert set(entry) == {"name", "config", "traffic", "chips", "why"}
        assert entry["chips"] in (1, 4)
    for entry in bench["end_to_end"]:
        assert set(entry) - {"workloads"} == {"name", "unit", "better",
                                              "bound", "source"}
        assert 0.01 <= entry["bound"] <= 0.1
        assert entry["source"] in ("host_clock", "device_trace")
    for entry in bench["per_layer"]:
        assert set(entry) - {"workloads"} == {"name", "unit", "better",
                                              "source", "layer", "moves"}
        assert entry["source"] in SOURCES
    for entry in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(entry["unit"]) and entry["better"] in (
            "lower", "higher")


def test_cells_configs_and_shares(bench, root):
    configs = {c["name"]: c for c in bench["configs"]}
    cells = bench["workloads"]
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert {w["config"] for w in cells} == set(configs)
    assert len({c["file"] for c in configs.values()}) == len(configs)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    for config in configs.values():
        assert config["file"].startswith("chipbench/configs/")
        held = _json(root, config["file"])
        assert not share_faults(config, held), config["name"]
        assert held["source"] == config["source"]
    for w in cells:
        path = os.path.join(root, "chipbench", "traffic",
                            w["traffic"] + ".json")
        assert os.path.exists(path), path


def test_every_cell_reports_what_its_metrics_move(bench):
    def cells_of(metric):
        return set(metric.get("workloads",
                              [w["name"] for w in bench["workloads"]]))

    end_to_end = {m["name"]: cells_of(m) for m in bench["end_to_end"]}
    assert end_to_end["setup_s"] == {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        assert sum(w["name"] in c for c in end_to_end.values()) >= 2
        assert any(w["name"] in cells_of(m) for m in bench["per_layer"])
    for metric in bench["per_layer"]:
        assert cells_of(metric) <= end_to_end[metric["moves"]], metric["name"]


def test_layer_metric_files_agree_with_the_entries(bench, root):
    folder = os.path.join(root, "chipbench", "layer_metrics")
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert {f[:-5] for f in os.listdir(folder)} == set(entries)
    for name, entry in entries.items():
        spec = _json(folder, name + ".json")
        assert {k: spec[k] for k in entry} == entry
        assert os.path.exists(os.path.join(
            root, "chipbench", "reducers", spec["reducer"] + ".py"))
    if root == REPO:    # PERF.md's list of layers is of the repo's own
        layers = {m["layer"] for m in bench["per_layer"]}
        with open(os.path.join(REPO, "PERF.md")) as f:
            perf = f.read()
        assert not [layer for layer in layers if layer not in perf]


# ---- the repo's own per-layer list, an entry at a time -----------------
# One measurement an entry (chipbench/README.md, "A per-layer metric"):
# what makes a measurement is the reader, its arguments, what it moves and
# where it is filed.  Two entries that agree in all of it are copies; a
# ``benchmark`` PR joins the second one's cells to the first one's list
# (PR 53 joined 59 such entries into 20 and gave back 39 of 128 places).
SPEC_KEY = ("reducer", "args", "moves", "unit", "better", "source", "layer")
# The cells whose entries a ``benchmark`` PR has joined: a copy among THEIR
# entries is a fault.  A later PR that brings a cell may edit no entry, so
# it can only bring copies under new names; they pass here and wait for
# the next ``benchmark`` PR, which joins them and adds the cell to this set.
JOINED = {"train-pythia-1.4b-s2048", "serve-olmo2-1b-chat",
          "serve-olmo2-1b-docbatch", "train-pythia-6.9b-fsdp4",
          "serve-glm5-ep16-longctx", "serve-trinity-mini-ep8-mixedq",
          "serve-kimi-k2-ep32-agentctx", "train-mellum2-12b-ep4-s8192",
          "serve-granite4-h-micro-chatfull",
          "serve-granite4-h-small-ep2-chatfull"}
# A copy that has to stand: ``tests/unit/test_run_ahead.py`` (outside the
# benchmark's paths, so no ``benchmark`` PR may edit it) holds
# ``ahead_pct.docbatch`` to its name and its one cell, so the granite
# cells' ``ahead_pct.chatfull`` could not join it (PERF.md section 7).
PINNED = {"ahead_pct.docbatch"}
FOLDER = os.path.join(REPO, "chipbench", "layer_metrics")
OWN = _json(REPO, "BENCHMARK.json")
OWN_FILES = sorted(f[:-5] for f in os.listdir(FOLDER))


OWN_SPECS = {name: _json(FOLDER, name + ".json") for name in OWN_FILES}
SPEC_KEYS = {name: json.dumps([spec.get(k) for k in SPEC_KEY],
                              sort_keys=True)
             for name, spec in OWN_SPECS.items()}


@pytest.mark.parametrize("name", [m["name"] for m in OWN["per_layer"]])
def test_an_entry_is_a_measurement_of_its_own_and_agrees_with_its_file(name):
    """The entry has its file and the two agree field by field; the file
    has a reader; every cell it lists is a cell of the benchmark, once,
    in the benchmark's order; and no other entry of the ``JOINED`` cells
    is the same reader with the same arguments moving the same metric
    under the same layer."""
    (entry,) = [m for m in OWN["per_layer"] if m["name"] == name]
    spec = OWN_SPECS[name]
    assert set(spec) == set(entry) | {"reducer", "args"}
    assert {k: spec[k] for k in entry} == entry
    assert os.path.exists(os.path.join(
        REPO, "chipbench", "reducers", spec["reducer"] + ".py"))
    cells = [w["name"] for w in OWN["workloads"]]
    listed = entry.get("workloads", cells)
    assert listed == [c for c in cells if c in listed] and listed
    copies = [m["name"] for m in OWN["per_layer"]
              if SPEC_KEYS[m["name"]] == SPEC_KEYS[name]
              and JOINED & set(m.get("workloads", cells))
              and m["name"] not in PINNED - {name}]
    assert copies == [name] or not JOINED & set(listed) or name in PINNED


@pytest.mark.parametrize("name", OWN_FILES)
def test_a_layer_metric_file_has_its_entry(name):
    assert [m["name"] for m in OWN["per_layer"]].count(name) == 1
    assert OWN_SPECS[name]["name"] == name


def test_the_per_layer_list_keeps_room():
    """The driver admits 128 entries and refuses the 129th before any
    run; what stands is the count after PR 53's joins, and later PRs'
    additions show here."""
    assert len(OWN["per_layer"]) == len(OWN_FILES) <= 128


@pytest.mark.parametrize("key", list(COUNTS) + WIDTHS)
def test_reduced_may_name_a_count_and_never_a_width(key):
    """Each key alone in ``reduced``, held at half of what is published
    (64 of 128, above every floor): a count passes, a width is refused."""
    entry = {"reduced": [key]}
    held = {"reduced": [key], "published": {key: 128}, key: 64,
            "deployment": "made up: each layer is divided over 2 chips"}
    faults = share_faults(entry, held)
    if key in COUNTS:
        assert not faults and not is_width(key)
    else:
        assert faults == [f"{key} is a width and may not be reduced"]


# one chip's share of a 78-layer model of 256 experts and 154,880 rows of
# vocabulary, each layer divided over 16 chips: the shape that the next
# ``model_config`` PR brings (made-up name; no such file is in the repo)
SHARE = {
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "published": {"num_hidden_layers": 78, "n_routed_experts": 256,
                  "vocab_size": 154880},
    "num_hidden_layers": 6, "n_routed_experts": 16, "vocab_size": 19360,
    "moe_intermediate_size": 2048, "kv_lora_rank": 512,
    "num_experts_per_tok": 8,
    "deployment": "one of 16 chips that share each layer by expert "
                  "parallelism with data-parallel attention"}


def _share_faults(reduce_too=None, **changed):
    held = dict(copy.deepcopy(SHARE), **changed)
    if reduce_too:
        held["reduced"].append(reduce_too)
        held["published"][reduce_too] = 2 * held[reduce_too]
    return share_faults({"name": "made-up-ep16",
                         "reduced": list(held["reduced"])}, held)


@pytest.mark.parametrize("case,changed,fault", [
    ("sound", {}, None),
    ("dense-layers-kept", {"first_k_dense_replace": 1}, None),
    ("expert-width", {"reduce_too": "moe_intermediate_size"}, "is a width"),
    ("latent-rank", {"reduce_too": "kv_lora_rank"}, "is a width"),
    ("experts-per-token", {"reduce_too": "num_experts_per_tok"},
     "is a width"),
    ("vocabulary-4096", {"vocab_size": 4096}, "under an eighth"),
    ("more-than-published", {"vocab_size": 154881}, "held of"),
    ("experts-4", {"n_routed_experts": 4}, "experts are under 8"),
    ("layers-3", {"num_hidden_layers": 3}, "layers after the leading dense"),
    ("dense-layers-3-of-6", {"first_k_dense_replace": 3},
     "layers after the leading dense"),
    ("no-chips-stated", {"deployment": "a chip's share, by expert "
                                       "parallelism"}, "how many chips"),
    ("published-short", {"published": {"num_hidden_layers": 78}},
     "exactly the keys reduced"),
])
def test_a_chips_share_of_a_model(case, changed, fault):
    faults = _share_faults(**changed)
    if fault is None:
        assert not faults
    else:
        assert len(faults) == 1 and fault in faults[0], faults
