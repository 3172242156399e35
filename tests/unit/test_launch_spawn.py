"""End-to-end spawn test: launch.py forks local processes with the
distributed env contract set (reference ``tests/unit/launcher`` +
``launch.py:129`` behavior)."""

import os
import subprocess
import sys

from deepspeed_tpu.launcher.runner import encode_world_info


def test_launch_spawns_processes_with_env(tmp_path):
    script = tmp_path / "probe.py"
    script.write_text(
        "import os, sys\n"
        # ONE atomic write: concurrent children interleave multi-chunk
        # prints mid-line ('RANKRANK 1 ...')
        "sys.stdout.write('RANK %s WS %s COORD %s\\n' % (\n"
        "    os.environ['RANK'], os.environ['WORLD_SIZE'],\n"
        "    os.environ['JAX_COORDINATOR_ADDRESS']))\n"
        "sys.stdout.flush()\n")
    world = encode_world_info({"localhost": [0, 1]})
    env = dict(os.environ)
    # keep the children off the chip: it belongs to one process at a time
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         f"--world_info={world}", "--node_rank=0",
         "--master_addr=localhost", "--master_port=29871", str(script)],
        capture_output=True, text=True, timeout=120, env=env,
        cwd="/root/repo")
    assert out.returncode == 0, out.stderr
    lines = sorted(l for l in out.stdout.splitlines() if l.startswith("RANK"))
    assert lines == [
        "RANK 0 WS 2 COORD localhost:29871",
        "RANK 1 WS 2 COORD localhost:29871",
    ]


def test_launch_propagates_child_failure(tmp_path):
    script = tmp_path / "boom.py"
    script.write_text("import sys; sys.exit(3)\n")
    world = encode_world_info({"localhost": [0]})
    out = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         f"--world_info={world}", "--node_rank=0",
         "--master_addr=localhost", "--master_port=29872", str(script)],
        capture_output=True, text=True, timeout=120, cwd="/root/repo")
    assert out.returncode == 3
