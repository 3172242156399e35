"""The command end to end on the CPU at toy sizes, kernels interpreted:
one run of each traffic kind through made-up cells that were added as
files only (``tree.py``), and the ways it must refuse to run."""

import os
import shutil
import subprocess
import sys

import pytest

import tree

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tree.make(tmp_path_factory.mktemp("chipbench_tree"))


def _check_line(line, metrics):
    assert set(line) - {"breakdown"} == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == set(metrics)
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])


@pytest.mark.parametrize("cell,metrics", [
    ("tiny-train", {"train_tok_s_chip", "setup_s"}),
    ("tiny-chat", {"tpot_p90_ms", "setup_s"}),
    ("tiny-doc", {"serve_tok_s", "setup_s"}),
    ("tiny-routed", {"serve_tok_s", "setup_s"}),    # a family that routes
    ("tiny-share", {"serve_tok_s", "setup_s"}),     # one chip's share
])
def test_one_run_of_each_traffic_kind(checkout, cell, metrics):
    line, earlier = tree.run(checkout, cell, seed=2 ** 31 + 5)
    _check_line(line, metrics)
    assert all(v["value"] > 0 for v in line["metrics"].values())
    log = earlier[-1]
    assert log["compiles_in_window"] == 0
    assert log["step_s"], "the per-step seconds are printed before the result"
    if cell == "tiny-train":
        assert log["loss_rel_error"] <= log["loss_rtol"]
    else:
        assert log["logit_error"] <= log["logit_tol"]
        assert log["lost"] == [] and log["leaks"] == {}
        # every row of the check is compared, but what a routed family's
        # reference finds undecided (about one row in 500 of this one's)
        assert log["rows_compared"] + log["rows_undecided"] == 72
        assert log["rows_undecided"] <= (2 if cell == "tiny-routed" else 0)
    if cell == "tiny-share":
        # the program was built on the share that ``reduced`` names, not
        # on ``published``: the layers held, and as many rows of embedding
        # and of head as the slice has (ids, logits and the reference are
        # over the slice)
        held = tree.data("tiny-share")
        d, f = held["hidden_size"], held["intermediate_size"]
        assert log["n_params"] == 2 * held["vocab_size"] * d + d + \
            held["num_hidden_layers"] * (4 * d * d + 3 * d * f + 4 * d)


def test_traced_run_reads_the_made_up_metrics(checkout):
    line, _ = tree.run(checkout, "tiny-train", trace=1)
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    metrics = line["metrics"]
    # read through reducers the benchmark has, from new files ...
    assert metrics["tiny-train.step_ms.train"]["value"] > 0
    assert metrics["tiny-train.compiles.train"]["value"] == 0
    # ... and through the reducer the made-up cell brought with it
    assert metrics["tiny-train.window_steps"]["value"] == \
        2 * line["attempted"]
    # a reader that finds nothing (no device plane in a CPU trace) returns
    # nothing and the metric is left out of the line
    assert "tiny-train.flash_roofline.train" not in metrics
    assert "step_ms.train" not in metrics       # another cell's metric


def test_traced_serving_run_reads_samples(checkout):
    line, earlier = tree.run(checkout, "tiny-chat", trace=1)
    metrics = line["metrics"]
    assert metrics["tiny-chat.step_ms.chat"]["value"] > 0
    assert metrics["tiny-chat.ttft_p90_ms"]["value"] == pytest.approx(
        earlier[-1]["ttft_ms"]["90"])


def test_routed_cell_brings_its_sizes_and_kernel_cost(checkout):
    """A family's ``model_sizes``, the configuration's published widths
    and a kernel cost that is a file of its own, read by a made-up
    per-layer metric; and the numbers compared, beside their limits, as
    the last lines of standard error."""
    line, _, stderr = tree.run(checkout, "tiny-routed", trace=1,
                               want_stderr=True)
    assert line["correct"] is True
    # 2 layers x the larger of flops over peak and weight bytes over peak
    assert line["metrics"]["tiny-routed.expert_least_us"]["value"] > 0
    assert "tiny-routed.step_ms.docbatch" in line["metrics"]
    last = stderr.strip().splitlines()[-6:]
    assert last[0].startswith("chipbench: logit_error ")
    assert last[0].endswith("(limit 0.04)")
    assert last[1].startswith("chipbench: rows_compared 7")
    assert last[-1] == "chipbench: correct True"


BROKEN = """
import deepspeed_tpu
_init = deepspeed_tpu.init_inference
def init_inference(model, params, dtype):
    # the program serves a head whose rows are in another order
    return _init(model=model, dtype=dtype,
                 params=dict(params, lm_head=params["lm_head"][::-1]))
deepspeed_tpu.init_inference = init_inference
"""


def test_a_broken_program_is_not_correct(checkout):
    """The rest of a run, with the program broken underneath: it serves,
    loses and leaks nothing, and ``correct`` comes out false."""
    line, earlier, stderr = tree.run(
        checkout, "tiny-doc", want_stderr=True,
        prelude=f"exec({BROKEN!r})")
    log = earlier[-1]
    assert line["correct"] is False and line["failed"] == 0
    assert log["logit_error"] > log["logit_tol"]
    assert log["lost"] == [] and log["leaks"] == {}
    assert stderr.strip().endswith("chipbench: correct False")


def _command(cwd, workload, **env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=cwd,
        env=dict(os.environ, **env), capture_output=True, text=True,
        timeout=240)


def test_no_tpu_is_an_error_not_a_fallback(checkout):
    done = _command(checkout, "tiny-train", JAX_PLATFORMS="cpu",
                    PYTHONPATH=tree.REPO)
    assert done.returncode != 0
    assert "no accelerator" in done.stderr
    assert '"correct"' not in done.stdout


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(os.path.join(tree.REPO, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tree.REPO, "BENCHMARK.json"), tmp_path)
    done = _command(tmp_path, "train-pythia-1.4b-s2048",
                    JAX_PLATFORMS="cpu", PYTHONPATH="")
    assert done.returncode != 0 and '"correct"' not in done.stdout


def test_unknown_workload_is_refused(checkout):
    done = _command(checkout, "no-such-cell", JAX_PLATFORMS="cpu",
                    PYTHONPATH=tree.REPO)
    assert done.returncode != 0 and "no workload" in done.stderr
