"""A copy of the benchmark in a scratch directory with made-up cells added
AS FILES ONLY: tiny configurations, tiny traffic mixes, a per-layer metric
and a reducer of their own, plus entries in ``BENCHMARK.json``.  Nothing
that the benchmark already has is edited, which is what a later PR that
brings a cell has to be able to do."""

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
METRICS = os.path.join(REPO, "chipbench", "layer_metrics")

CELLS = [   # name, configuration, traffic, a real cell whose metrics it takes
    ("tiny-train", "tiny-neox", "tiny-pretrain", "train-pythia-1.4b-s2048"),
    ("tiny-chat", "tiny-olmo2", "tiny-open", "serve-olmo2-1b-chat"),
    ("tiny-doc", "tiny-olmo2", "tiny-closed", "serve-olmo2-1b-docbatch"),
    # a family that routes tokens to experts, with its reference
    ("tiny-routed", "tiny-routed", "tiny-closed", "serve-olmo2-1b-docbatch"),
    # one chip's share of a model: depth cut and vocabulary sliced, both
    # listed in ``reduced`` with the published counts beside them
    ("tiny-share", "tiny-share", "tiny-closed", "serve-olmo2-1b-docbatch"),
]

# code a later PR might bring, as files of its own: a family with its
# reference, the operations of a kernel, a reducer (data/ -> chipbench/)
NEW_CODE = {
    "routed/family.py": "families/toy_routed.py",
    "routed/reference.py": "reference/toy_routed.py",
    "routed/expert_cost.py": "costs/made_up_experts.py",
    "routed/least_us.py": "reducers/made_up_least_us.py",
}

MADE_UP_REDUCER = '''"""A reducer a later PR might bring: steps in the window."""


def read(run, scale=1):
    return len(run.steps) * scale
'''


def make(tmp):
    tmp = str(tmp)
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(tmp, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _listing(tmp)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for source, target in NEW_CODE.items():
        shutil.copy(os.path.join(DATA, source),
                    os.path.join(tmp, "chipbench", target))
    for config in sorted({config for _, config, _, _ in CELLS}):
        shutil.copy(os.path.join(DATA, config + ".json"),
                    os.path.join(tmp, "chipbench", "configs"))
        held = data(config)     # the entry says what the file says
        bench["configs"].append({
            "name": config, "source": held["source"],
            "file": f"chipbench/configs/{config}.json",
            "reduced": held["reduced"], "why": "toy width"})
    for mix in ("tiny-pretrain", "tiny-open", "tiny-closed"):
        shutil.copy(os.path.join(DATA, mix + ".json"),
                    os.path.join(tmp, "chipbench", "traffic"))
    added = {}
    for name, config, mix, like in CELLS:
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": 1,
                                   "why": "made up for the tests"})
        for metric in bench["end_to_end"]:
            if like in metric.get("workloads", ()):
                metric["workloads"].append(name)
        # a per-layer metric is a file: one of its own for the new cell,
        # reading through a reducer the benchmark already has
        for metric in list(bench["per_layer"]):
            if like not in metric.get("workloads", ()):
                continue
            spec = _json(tmp, "chipbench", "layer_metrics",
                         metric["name"] + ".json")
            new = dict(spec, name=f"{name}.{metric['name']}",
                       workloads=[name])
            added[new["name"]] = new
    # ... and one with a reducer of its own
    added["tiny-train.window_steps"] = {
        "name": "tiny-train.window_steps", "layer": "made up",
        "unit": "count", "better": "higher", "source": "program_counter",
        "moves": "train_tok_s_chip", "workloads": ["tiny-train"],
        "reducer": "made_up_steps", "args": {"scale": 2}}
    # ... and one that reads a list of samples no metric of the benchmark
    # reads yet (time to first token left the end-to-end metrics, PERF.md)
    added["tiny-chat.ttft_p90_ms"] = {
        "name": "tiny-chat.ttft_p90_ms", "layer": "made up", "unit": "ms",
        "better": "lower", "source": "host_clock", "moves": "tpot_p90_ms",
        "workloads": ["tiny-chat"], "reducer": "sample_percentile",
        "args": {"sample": "ttft_ms", "q": 90}}
    # ... and one that reads the family's own sizes (``model_sizes``) and
    # the configuration's published ones through a kernel cost of its own
    added["tiny-routed.expert_least_us"] = {
        "name": "tiny-routed.expert_least_us", "layer": "made up",
        "unit": "us", "better": "lower", "source": "program_counter",
        "moves": "serve_tok_s", "workloads": ["tiny-routed"],
        "reducer": "made_up_least_us", "args": {"cost": "made_up_experts"}}
    with open(os.path.join(tmp, "chipbench", "reducers",
                           "made_up_steps.py"), "w") as f:
        f.write(MADE_UP_REDUCER)
    for spec in added.values():
        with open(os.path.join(tmp, "chipbench", "layer_metrics",
                               spec["name"] + ".json"), "w") as f:
            json.dump(spec, f)
        bench["per_layer"].append({k: spec[k] for k in (
            "name", "unit", "better", "source", "layer", "moves",
            "workloads")})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    after = _listing(tmp)
    changed = [p for p in before if before[p] != after.get(p)]
    assert not changed, f"adding cells edited existing files: {changed}"
    return tmp


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def data(name):
    """A toy configuration or traffic mix of ``data/``."""
    return _json(DATA, name + ".json")


def bench(root=REPO):
    return _json(root, "BENCHMARK.json")


def entries_of(bench, cell):
    """The per-layer entries that list ``cell``, in their order.  What a
    cell reports is asked of the lists and never of a name's ending: a
    ``benchmark`` PR joins a cell to the entry that already reads what it
    needs (``compiles.serve`` lists six cells), and every PR appends."""
    return [m for m in bench["per_layer"] if cell in m.get("workloads", ())]


def base(name):
    """``decode_ms`` of ``decode_ms.serve``: a metric's name less the
    ending that says whose it is."""
    return name.rpartition(".")[0]


def held_entries(cell, moves):
    """``entries_of`` the repo's own ``cell``, each held to its file, its
    reader, the end-to-end metric it moves and PERF.md's list of layers."""
    entries = entries_of(bench(), cell)
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for entry in entries:
        spec = _json(METRICS, entry["name"] + ".json")
        assert {k: spec[k] for k in entry} == entry
        assert entry["moves"] == moves
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "reducers", spec["reducer"] + ".py"))
        assert entry["layer"] in perf
    return entries


def add_cell(tmp, name, like, mix):
    """One more cell in a made-up tree, as files and entries: the toy
    configuration ``data/<name>.json`` under the traffic ``mix``,
    reporting what the repo's cell ``like`` reports, each per-layer metric
    through a file of its own named ``<name>.<base>``."""
    held = data(name)
    with open(os.path.join(tmp, "chipbench", "configs", name + ".json"),
              "w") as f:
        json.dump(held, f)
    made = bench(tmp)
    made["configs"].append({
        "name": name, "source": held["source"],
        "file": f"chipbench/configs/{name}.json",
        "reduced": held["reduced"], "why": "toy width"})
    made["workloads"].append({
        "name": name, "config": name, "traffic": mix, "chips": 1,
        "why": "made up for the tests"})
    for metric in made["end_to_end"]:
        if like in metric.get("workloads", ()):
            metric["workloads"].append(name)
    theirs = entries_of(bench(), like)
    assert len({base(m["name"]) for m in theirs}) == len(theirs)
    for metric in theirs:
        spec = dict(_json(METRICS, metric["name"] + ".json"),
                    name=f"{name}.{base(metric['name'])}", workloads=[name])
        with open(os.path.join(tmp, "chipbench", "layer_metrics",
                               spec["name"] + ".json"), "w") as f:
            json.dump(spec, f)
        made["per_layer"].append({k: spec[k] for k in (
            "name", "unit", "better", "source", "layer", "moves",
            "workloads")})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(made, f)
    return tmp


def _listing(tmp):
    out = {}
    for folder, _, files in os.walk(os.path.join(tmp, "chipbench")):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[path] = f.read()
    return out


def run(tmp, workload, seed=1, seconds=2, trace=0, timeout=240,
        want_stderr=False, prelude="pass"):
    """The command's ``main`` in a process of its own, allowed onto the
    CPU by the tests' own switch (``require_tpu=False``): the command line
    has no such option.  ``prelude`` is code run first (a test's way to
    break the program underneath)."""
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    code = (f"import sys; sys.path.insert(0, {tmp!r}); "
            f"from chipbench import run; {prelude}; "
            f"run.main({argv!r}, require_tpu=False)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="")
    done = subprocess.run([sys.executable, "-c", code], cwd=tmp, env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [line for line in done.stdout.splitlines() if line.strip()]
    out = (json.loads(lines[-1]), [json.loads(line) for line in lines
                                   if line.startswith("{")][:-1])
    return out + (done.stderr,) if want_stderr else out
