"""ds_bench suites (bin/ds_bench, deepspeed_tpu/benchmarks/): each runs
at its smallest size and hands results back.  No test reads a time."""

import ast
import importlib
import json
import os
import re
from fnmatch import fnmatch

import numpy as np
import pytest

from deepspeed_tpu.benchmarks.training import run_benchmark
from deepspeed_tpu.comm.topology_model import (device_peak_flops,
                                               hbm_peak_gbps)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _suites():
    """``SUITES`` as bin/ds_bench spells it (read, not imported: the
    script runs a suite when executed)."""
    with open(os.path.join(REPO, "bin", "ds_bench")) as f:
        tree = ast.parse(f.read())
    table, = [node.value for node in tree.body
              if isinstance(node, ast.Assign)
              and node.targets[0].id == "SUITES"]
    return ast.literal_eval(table)


@pytest.mark.parametrize("suite,module", sorted(_suites().items()))
def test_every_suite_ds_bench_names_has_a_main(suite, module):
    assert callable(importlib.import_module(module).main)


def test_train_bench_smoke_tiny():
    out = run_benchmark(model=dict(hidden_size=32, n_layers=2, n_heads=4),
                        batch=8, gas=1, seq=32, steps=1, vocab_size=64)
    assert out["tokens_per_sec_per_chip"] > 0
    assert np.isfinite(out["loss"])
    assert out["n_chips"] >= 1
    assert "mfu" not in out      # no peak off the TPU, so no utilisation


def test_train_bench_gas_and_blocks():
    out = run_benchmark(model=dict(hidden_size=32, n_layers=2, n_heads=4),
                        batch=8, gas=2, seq=32, steps=1, vocab_size=64,
                        attn_block_q=16, attn_block_k=16)
    assert np.isfinite(out["loss"])


def test_comm_bench_smoke():
    """ds_bench comm (the reference's default ds_bench role) runs a small
    collective sweep on the virtual mesh and reports algbw/busbw."""
    from deepspeed_tpu.benchmarks.communication import main
    res = main(["--collective", "all_reduce", "--size", "4096",
                "--trials", "2", "--warmups", "1"])
    assert res, "no results returned"


def test_aio_bench_smoke(tmp_path):
    """ds_bench aio: file round-trip throughput via the aio engine."""
    from deepspeed_tpu.benchmarks.aio import main
    res = main(["--file", str(tmp_path / "aio_bench.bin"),
                "--size-mb", "2", "--reps", "1"])
    assert res, "no results returned"


def test_cpu_adam_bench_smoke():
    """ds_bench cpu_adam: one row for the implementation in use (and one
    for the numpy fallback beside the fused pass)."""
    from deepspeed_tpu.benchmarks.cpu_adam import main
    rows = main(["--numel", str(1 << 21), "--reps", "1"])
    impls = [r["impl"] for r in rows if "impl" in r]
    assert impls and impls[-1] == "numpy"
    assert all(r["numel"] == 1 << 21 for r in rows if "impl" in r)


def test_offload_bench_smoke(tmp_path):
    """ds_bench offload: the NVMe-swapped optimizer step runs pipelined
    and serial over the same store and cleans its swap files up."""
    from deepspeed_tpu.benchmarks.offload import main
    rows = main(["--numel", str(1 << 20), "--sub-groups", "2",
                 "--reps", "1", "--swap-dir", str(tmp_path)])
    assert [r["mode"] for r in rows[:2]] == ["pipelined", "serial"]
    assert not os.listdir(tmp_path)


def _benchmark_peaks():
    with open(os.path.join(REPO, "chipbench", "peaks.json")) as f:
        return json.load(f)["by_device_kind"]


@pytest.mark.parametrize("kind", sorted(_benchmark_peaks()))
def test_package_peak_table_agrees_with_the_benchmarks(kind):
    """Two peak tables are left, the package's and the benchmark's; a
    device kind both list has one bf16 peak and one HBM bandwidth."""
    want = _benchmark_peaks()[kind]
    assert device_peak_flops(kind) == want["bf16_flops_per_s"]
    assert hbm_peak_gbps(kind) * 1e9 == want["hbm_bytes_per_s"]


def test_only_the_history_names_the_harness_that_went():
    """The benchmark is ``chipbench/`` and nothing else: the CPU
    wall-clock script, its ledger and its diff gate went in PR 46, and no
    file but the records of what was done may send a reader to them.
    (The names are spelled in pieces so that this file is not a hit.)"""
    gone = re.compile("|".join(("bench" + r"\.py", "BENCH_" + "LEDGER",
                                "ds_perf" + "_diff")))
    # PERF_LEDGER.jsonl is the driver's, and carries every PR's title
    history = {"CHANGES.md", "ROADMAP.md", "PERF.md", "ISSUE.md",
               "SURVEY.md", "PAPER.md", "PAPERS.md", "PERF_LEDGER.jsonl"}
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = [".git"] + [ln.strip().rstrip("/") for ln in f
                              if ln.strip() and not ln.startswith("#")]

    def kept(name):
        return not any(fnmatch(name, pat) for pat in ignored)

    hits = []
    for folder, dirs, files in os.walk(REPO):
        dirs[:] = filter(kept, dirs)
        for name in filter(kept, files):
            path = os.path.join(folder, name)
            if os.path.relpath(path, REPO) in history:
                continue
            with open(path, errors="ignore") as f:
                if gone.search(f.read()):
                    hits.append(os.path.relpath(path, REPO))
    assert not hits, hits
