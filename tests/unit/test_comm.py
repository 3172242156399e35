"""Collective-verb tests (parity model: reference ``tests/unit/comm/``).

Each verb runs inside shard_map over the fsdp axis of an 8-device mesh and is
checked against the numpy-computed expectation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu.comm as dist
from deepspeed_tpu.comm.backend import ReduceOp


def _run(fn, x, mesh, in_spec, out_spec):
    sm = jax.shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                       out_specs=out_spec, check_vma=False)
    return jax.jit(sm)(x)


@pytest.fixture
def x8():
    return jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)


def test_all_reduce_sum(mesh_1d, x8):
    out = _run(lambda x: dist.all_reduce(x, group="fsdp"),
               x8, mesh_1d, P("fsdp", None), P("fsdp", None))
    expected = np.tile(x8.sum(axis=0), (8, 1)).reshape(8, 4)
    np.testing.assert_allclose(out, expected)


def test_all_reduce_max(mesh_1d, x8):
    out = _run(lambda x: dist.all_reduce(x, op=ReduceOp.MAX, group="fsdp"),
               x8, mesh_1d, P("fsdp", None), P("fsdp", None))
    np.testing.assert_allclose(out[0], x8.max(axis=0))


def test_all_reduce_avg(mesh_1d, x8):
    out = _run(lambda x: dist.all_reduce(x, op=ReduceOp.AVG, group="fsdp"),
               x8, mesh_1d, P("fsdp", None), P("fsdp", None))
    np.testing.assert_allclose(out[0], x8.mean(axis=0), rtol=1e-6)


def test_all_gather_base(mesh_1d, x8):
    out = _run(lambda x: dist.all_gather_base(x, group="fsdp"),
               x8, mesh_1d, P("fsdp", None), P(None, None))
    # every shard sees the full array; out_specs P(None) replicates → full
    np.testing.assert_allclose(out[:8], x8)


def test_reduce_scatter_base(mesh_1d, x8):
    out = _run(lambda x: dist.reduce_scatter_base(x, group="fsdp"),
               x8, mesh_1d, P(None, None), P("fsdp", None))
    # input replicated [8,4]; each device reduces (sum over 8 copies of its
    # row block): row i of result = 8 * x[i]
    np.testing.assert_allclose(out, 8 * np.asarray(x8))


def test_broadcast(mesh_1d, x8):
    out = _run(lambda x: dist.broadcast(x, src=3, group="fsdp"),
               x8, mesh_1d, P("fsdp", None), P("fsdp", None))
    expected = np.tile(np.asarray(x8)[3], (8, 1))
    np.testing.assert_allclose(out, expected)


def test_all_to_all_single(mesh_1d):
    """all_to_all re-shards: rows-sharded → cols-sharded, same global value
    (the Ulysses seq↔head swap primitive)."""
    x = jnp.arange(8 * 8, dtype=jnp.float32).reshape(8, 8)
    out = _run(lambda x: dist.all_to_all_single(x, group="fsdp",
                                                split_axis=1, concat_axis=0),
               x, mesh_1d, P("fsdp", None), P(None, "fsdp"))
    np.testing.assert_allclose(out, np.asarray(x))


def test_ppermute_shift(mesh_1d, x8):
    out = _run(lambda x: dist.ppermute_shift(x, shift=1, group="fsdp"),
               x8, mesh_1d, P("fsdp", None), P("fsdp", None))
    np.testing.assert_allclose(out, np.roll(np.asarray(x8), 1, axis=0))


def test_scatter(mesh_1d):
    x = jnp.arange(8.0)
    out = _run(lambda x: dist.scatter(x, src=0, group="fsdp"),
               x, mesh_1d, P(None), P("fsdp"))
    np.testing.assert_allclose(out, np.arange(8.0))


def test_world_size_and_rank():
    dist.init_distributed()
    assert dist.is_initialized()
    assert dist.get_rank() == 0
    assert dist.get_world_size() == 8


def test_capability_probes():
    assert dist.comm.has_allgather_base()
    assert dist.comm.has_reduce_scatter_base()


def test_comms_logger(mesh_1d, x8):
    dist.configure(enabled=True, verbose=False)
    dist.comm.comms_logger.reset()
    _run(lambda x: dist.all_reduce(x, group="fsdp"),
         x8, mesh_1d, P("fsdp", None), P("fsdp", None))
    rec = dist.comm.comms_logger.records
    assert "all_reduce" in rec
    assert rec["all_reduce"]["count"] >= 1
    dist.configure(enabled=False)
