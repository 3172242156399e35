"""Multi-host (multi-process) training tests.

Parity model: the reference's multi-node paths (torch.distributed NCCL
process groups + per-DP-rank ZeRO partitions).  Here: two real OS
processes, each owning 4 virtual CPU devices, joined into one 8-device
mesh via ``jax.distributed`` — sharded state init, batch assembly from
process-local data, and per-host ZeRO-Offload partitions are all
exercised for real (not simulated on a single controller).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _free_port():
    """OS-assigned port so concurrent pytest runs never collide."""
    import socket
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]

_WORKER_TEMPLATE = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(coordinator_address="localhost:{port}",
                           num_processes=2, process_id=int(sys.argv[1]))
import numpy as np
import deepspeed_tpu
from deepspeed_tpu.models.transformer import CausalTransformerLM, TransformerConfig

pid = int(sys.argv[1])
cfg = TransformerConfig.tiny(n_layers=2, n_heads=4)
model = CausalTransformerLM(cfg)
params = model.init(jax.random.key(0))
engine, *_ = deepspeed_tpu.initialize(
    model=model, model_parameters=params,
    config={{"train_micro_batch_size_per_gpu": 4,
            "zero_optimization": {zero},
            "optimizer": {{"type": "AdamW", "params": {{"lr": 1e-2}}}}}})
{extra}
rng = np.random.default_rng(100 + pid)   # process-local batch slice
losses = []
for i in range(5):
    loss = engine.train_batch(
        batch={{"input_ids": rng.integers(0, cfg.vocab_size, (4, 32))}})
    losses.append(float(loss))
assert all(np.isfinite(l) for l in losses), losses
{post}
print("LOSSES", pid, " ".join(f"{{l:.6f}}" for l in losses), flush=True)
"""


def _run_two_procs(script: str, timeout=300):
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(script)
        path = f.name
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_PLATFORMS", None)
    procs = [subprocess.Popen([sys.executable, path, str(i)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True, env=env)
             for i in range(2)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=timeout)
        outs.append(out)
    os.unlink(path)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
    return outs


def _losses(out: str):
    for line in out.splitlines():
        if line.startswith("LOSSES"):
            return [float(x) for x in line.split()[2:]]
    raise AssertionError(f"no LOSSES line in:\n{out[-2000:]}")


@pytest.mark.slow
def test_two_process_zero3_training():
    """2 processes x 4 devices: sharded init, per-process batch slices,
    identical loss trajectory on both hosts."""
    script = _WORKER_TEMPLATE.format(port=_free_port(), zero='{"stage": 3}',
                                     extra="", post="")
    outs = _run_two_procs(script)
    l0, l1 = _losses(outs[0]), _losses(outs[1])
    np.testing.assert_allclose(l0, l1, rtol=1e-6)


@pytest.mark.slow
def test_two_process_zero_offload():
    """Multi-host ZeRO-Offload: each process hosts the fp32 master +
    moments for ONLY its addressable fsdp shards (ShardedFlatLayout),
    updates them with the C++ Adam, and reassembles the global device
    params — round-1 review item 10."""
    extra = textwrap.dedent("""\
        from deepspeed_tpu.runtime.zero.offload import ShardedFlatLayout
        assert isinstance(engine._offload.layout, ShardedFlatLayout)
        # the local master covers 1/2 of the model (4 of 8 fsdp shards)
        n_total = sum(int(np.prod(np.shape(x)))
                      for x in jax.tree_util.tree_leaves(params))
        assert engine._offload.layout.total < n_total, \\
            (engine._offload.layout.total, n_total)
    """)
    port = _free_port()
    post = textwrap.dedent(f"""\
        # checkpoint: per-rank host shards save + reload + continue
        ckpt = "/tmp/ds_mh_offload_ckpt_{port}"
        engine.save_checkpoint(ckpt, tag="t")
        engine.load_checkpoint(ckpt, tag="t")
        loss = engine.train_batch(
            batch={{"input_ids": rng.integers(0, cfg.vocab_size, (4, 32))}})
        losses.append(float(loss))
        import shutil
        if pid == 0:
            shutil.rmtree(ckpt, ignore_errors=True)
    """)
    script = _WORKER_TEMPLATE.format(
        port=port,
        # threshold 0: the tiny model's leaves are all under the default
        # persistence threshold (1e5) and would replicate instead of shard
        zero='{"stage": 3, "offload_optimizer": {"device": "cpu"}, '
             '"stage3_param_persistence_threshold": 0}',
        extra=extra, post=post)
    outs = _run_two_procs(script)
    l0, l1 = _losses(outs[0]), _losses(outs[1])
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    assert l0[-1] < l0[0] + 0.5   # training moves (5 tiny steps)


# ----------------------------------------------------------------------
# ShardedFlatLayout unit coverage (single process, 8-device mesh — the
# shard grouping/assembly logic is mesh-driven, not process-driven)
# ----------------------------------------------------------------------
def test_sharded_flat_layout_roundtrip():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.parallel.topology import TopologyConfig
    from deepspeed_tpu.runtime.zero.offload import ShardedFlatLayout

    groups.reset_mesh()
    mesh = groups.initialize_mesh(TopologyConfig(tp=2, fsdp=-1))
    tree = {
        "w": jax.device_put(jnp.arange(32.0).reshape(8, 4),
                            NamedSharding(mesh, P("fsdp", "tp"))),
        "b": jax.device_put(jnp.arange(4.0),
                            NamedSharding(mesh, P())),        # replicated
        "steps": jax.device_put(jnp.asarray(7, jnp.int32),
                                NamedSharding(mesh, P())),    # non-float
        # non-float AND sharded: every shard must keep its own values
        "ids": jax.device_put(jnp.arange(16, dtype=jnp.int32),
                              NamedSharding(mesh, P("fsdp"))),
    }
    lay = ShardedFlatLayout(tree)
    # single process: local shards cover the whole tree exactly once
    assert lay.total == 32 + 4
    flat = lay.flatten(tree)
    # mutate and reassemble
    flat2 = flat * 2.0
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, tree)
    out = lay.to_device(flat2, shardings)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(tree["w"]) * 2.0)
    np.testing.assert_allclose(np.asarray(out["b"]),
                               np.asarray(tree["b"]) * 2.0)
    assert int(out["steps"]) == 7
    np.testing.assert_array_equal(np.asarray(out["ids"]), np.arange(16))
    assert out["w"].sharding == tree["w"].sharding
    # pieces stream in strictly increasing offset order covering total
    offs = [(o, s) for o, s, _ in lay.pieces(tree)]
    assert offs[0][0] == 0 and sum(s for _, s in offs) == lay.total
    assert all(offs[i][0] + offs[i][1] == offs[i + 1][0]
               for i in range(len(offs) - 1))
    groups.reset_mesh()


@pytest.mark.slow
def test_two_process_client_state_broadcast():
    """Checkpoint ``client_state`` reaches every host after load.

    ``save`` writes ``client_state.json`` on process 0 only; on node-local
    storage the other hosts cannot read it, so ``load`` broadcasts process
    0's dict (``broadcast_client_state``).  Each process feeds a different
    dict into the broadcast and must come out holding process 0's; the
    end-to-end save→load then has to agree on ``global_steps`` everywhere."""
    port = _free_port()
    post = textwrap.dedent(f"""\
        from deepspeed_tpu.runtime.checkpoint_engine import \\
            broadcast_client_state
        fed = {{"global_steps": 41, "src": "p0"}} if pid == 0 \\
            else {{"stale": True}}
        got = broadcast_client_state(fed)
        assert got == {{"global_steps": 41, "src": "p0"}}, (pid, got)
        ckpt = "/tmp/ds_mh_cs_ckpt_{port}"
        engine.save_checkpoint(ckpt, tag="t")
        path, client = engine.load_checkpoint(ckpt, tag="t")
        assert path is not None, (pid, path)
        assert int(client["global_steps"]) == engine.global_steps == 5, \\
            (pid, client)
        loss = engine.train_batch(
            batch={{"input_ids": rng.integers(0, cfg.vocab_size, (4, 32))}})
        losses.append(float(loss))
        import shutil
        if pid == 0:
            shutil.rmtree(ckpt, ignore_errors=True)
    """)
    script = _WORKER_TEMPLATE.format(port=port, zero='{"stage": 3}',
                                     extra="", post=post)
    outs = _run_two_procs(script)
    l0, l1 = _losses(outs[0]), _losses(outs[1])
    np.testing.assert_allclose(l0, l1, rtol=1e-6)


@pytest.mark.slow
def test_two_process_param_stream():
    """Multi-host param-stream: host master/moments replicated per
    process; grads come back fully-replicated from the layer programs
    (XLA all-reduces over ICI), so every process applies the identical
    host Adam update — trajectories must match across hosts, and a
    checkpoint save/load continues identically."""
    extra = textwrap.dedent("""\
        assert engine._param_stream is not None
    """)
    port = _free_port()
    post = textwrap.dedent(f"""\
        ckpt = "/tmp/ds_mh_pstream_ckpt_{port}"
        engine.save_checkpoint(ckpt, tag="t")
        engine.load_checkpoint(ckpt, tag="t")
        loss = engine.train_batch(
            batch={{"input_ids": rng.integers(0, cfg.vocab_size, (4, 32))}})
        losses.append(float(loss))
        import shutil
        if pid == 0:
            shutil.rmtree(ckpt, ignore_errors=True)
    """)
    script = _WORKER_TEMPLATE.format(
        port=port,
        zero='{"stage": 3, '
             '"offload_param": {"device": "cpu"}, '
             '"offload_optimizer": {"device": "cpu"}, '
             '"stage3_param_persistence_threshold": 0}',
        extra=extra, post=post)
    outs = _run_two_procs(script)
    l0, l1 = _losses(outs[0]), _losses(outs[1])
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    assert l0[-1] < l0[0] + 0.5
