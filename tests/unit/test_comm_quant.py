"""Quantized-collective tests (comm/quantize.py + the engine wiring).

Three layers of oracle:

* codec — blockwise int8 round trips restore shape/dtype, zero blocks
  are exact, the shard_map two-phase all-reduce / reduce-scatter match
  the fp32 psum within the codec's analytic error envelope;
* policy — the ``comm.quantization`` config block parses/validates, the
  dtype-aware fallback passes through integer / tiny / unlisted-verb
  tensors, and a disabled config is bit-for-bit the unquantized path
  (grad trees AND fleet payloads);
* trajectory — ZeRO-3 training with the int8 wire codec tracks the fp32
  trajectory within tolerance over 50+ steps at dp=2 AND dp=4 (the real
  shard_map collective in a data-parallel loop, plus the engine's
  trace-level QDQ wiring), while ``enabled: false`` reproduces the
  baseline exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.comm.quantize import (QUANT_GAUGES, QUANT_SCHEMES,
                                         QUANTIZABLE_VERBS, CommQuantizer,
                                         QuantizedPayload, blockwise_dequantize,
                                         blockwise_qdq, blockwise_quantize,
                                         get_scheme, pad_for_world,
                                         quant_bytes_saved,
                                         quant_payload_bytes,
                                         quantized_all_reduce,
                                         quantized_reduce_scatter)
from deepspeed_tpu.parallel import groups
from tests.unit.simple_model import SimpleModel, base_config, random_batch

HIDDEN = 16


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ----------------------------------------------------------------------
# codec
# ----------------------------------------------------------------------
def test_blockwise_round_trip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(4096), dtype=jnp.float32)
    codes, scales = blockwise_quantize(x, 64)
    assert codes.dtype == jnp.int8 and scales.dtype == jnp.float32
    out = blockwise_dequantize(codes, scales)
    # symmetric absmax: per-element error bounded by scale/2 per block
    err = np.abs(np.asarray(out - x)).reshape(-1, 64)
    bound = np.asarray(scales).reshape(-1, 1) / 2 + 1e-7
    assert (err <= bound).all()


def test_blockwise_zero_block_exact_and_qdq_preserves_shape_dtype():
    z = jnp.zeros((128,), jnp.float32)
    codes, scales = blockwise_quantize(z, 64)
    np.testing.assert_array_equal(np.asarray(scales), 1.0)
    np.testing.assert_array_equal(np.asarray(blockwise_dequantize(
        codes, scales)), 0.0)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((3, 50)),
                    dtype=jnp.bfloat16)
    out = blockwise_qdq(x, 64)        # numel 150: exercises padding
    assert out.shape == x.shape and out.dtype == x.dtype


@pytest.mark.parametrize("world", [2, 4])
def test_shard_map_collectives_match_psum(world):
    mesh = Mesh(np.array(jax.devices()[:world]), ("dp",))
    numel = world * 256 * 4
    rng = np.random.default_rng(world)
    x = jnp.asarray(rng.standard_normal((world, numel)) *
                    rng.choice([1e-2, 1.0], (world, numel)),
                    dtype=jnp.float32)
    exact = np.asarray(x).sum(axis=0)

    ar = _shard_map(lambda g: quantized_all_reduce(g[0], "dp", 256)[None],
                    mesh, (P("dp", None),), P(None, None))(x)
    ar_err = np.linalg.norm(np.asarray(ar)[0] - exact) / \
        np.linalg.norm(exact)
    assert ar_err < 0.05, ar_err

    rs = _shard_map(
        lambda g: quantized_reduce_scatter(g[0], "dp", 256)[None],
        mesh, (P("dp", None),), P("dp", None))(x)
    rs_err = np.linalg.norm(np.asarray(rs).reshape(-1) - exact) / \
        np.linalg.norm(exact)
    assert rs_err < 0.05, rs_err


def test_pad_for_world_and_wire_accounting():
    x = jnp.ones((1000,), jnp.float32)
    padded, n = pad_for_world(x, 4, 64)
    assert n == 1000 and padded.shape[0] % (4 * 64) == 0
    same, n2 = pad_for_world(padded, 4, 64)
    assert same is padded and n2 == padded.shape[0]
    # fp32 -> int8 + fp32/block scales: 4x shrink minus the sidecar
    assert quant_payload_bytes(1024, 256) == 1024 + 4 * 4
    assert quant_bytes_saved(1024, "float32", 256) == 4096 - 1040
    assert quant_bytes_saved(1024, "int8", 256) == 0   # clamped


# ----------------------------------------------------------------------
# policy + config
# ----------------------------------------------------------------------
def test_config_block_parses_and_validates():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 4,
                           "comm": {"quantization": {
                               "enabled": True, "block_size": 128,
                               "verbs": ["all_reduce"]}}})
    q = CommQuantizer.from_config(cfg.comm_quantization)
    assert q.active() and q.block_size == 128
    assert tuple(q.verbs) == ("all_reduce",)
    for bad in ({"scheme": "int4"}, {"dtype": "int4"},
                {"block_size": 4}, {"min_tensor_bytes": -1},
                {"verbs": ["all_to_all"]}):
        with pytest.raises(ValueError):
            DeepSpeedConfig({"train_micro_batch_size_per_gpu": 4,
                             "comm": {"quantization": bad}})


def test_policy_fallbacks():
    q = CommQuantizer(enabled=True, min_tensor_bytes=1024)
    assert q.should_quantize("float32", 4096, "all_reduce")
    assert not q.should_quantize("int32", 4096, "all_reduce")    # integer
    assert not q.should_quantize("float32", 512, "all_reduce")   # tiny
    assert not q.should_quantize("float32", 4096, "all_to_all")  # verb
    assert not q.should_quantize("float8_e4m3fn", 4096,
                                 "all_reduce")                   # <=1 byte
    assert not CommQuantizer.from_config(None).active()
    assert not CommQuantizer(enabled=True, scheme="onebit").active()


def test_qdq_tree_disabled_is_identity():
    tree = {"w": jnp.ones((64, 64), jnp.float32),
            "ids": jnp.arange(2048, dtype=jnp.int32)}
    q = CommQuantizer(enabled=False)
    out, saved = q.qdq_tree(tree, "all_reduce")
    assert saved == 0 and out["w"] is tree["w"] and out["ids"] is tree["ids"]
    qq = CommQuantizer(enabled=True, min_tensor_bytes=64)
    out, saved = qq.qdq_tree(tree, "all_reduce")
    assert saved == quant_bytes_saved(64 * 64, "float32", 256)
    assert out["ids"] is tree["ids"]           # integer leaf untouched
    assert qq.tree_bytes_saved(tree, "all_reduce") == saved


def test_payload_codec_round_trip_and_disabled_passthrough():
    rng = np.random.default_rng(3)
    payload = {"k": jnp.asarray(rng.standard_normal((2, 8, 16)),
                                dtype=jnp.bfloat16),
               "ids": jnp.arange(16, dtype=jnp.int32)}
    off = CommQuantizer(enabled=False)
    assert off.encode_payload(payload) is payload
    q = CommQuantizer(enabled=True, block_size=64, min_tensor_bytes=64)
    enc = q.encode_payload(payload)
    assert isinstance(enc, QuantizedPayload)
    assert enc.wire_bytes < enc.raw_bytes and enc.bytes_saved > 0
    dec = CommQuantizer.decode_payload(enc)
    assert dec["k"].shape == payload["k"].shape
    assert dec["k"].dtype == payload["k"].dtype
    np.testing.assert_array_equal(np.asarray(dec["ids"]),
                                  np.asarray(payload["ids"]))
    err = np.abs(np.asarray(dec["k"], np.float32) -
                 np.asarray(payload["k"], np.float32)).max()
    assert err < 0.05, err
    # raw payloads pass decode untouched
    assert CommQuantizer.decode_payload(payload) is payload


def test_scheme_registry():
    assert set(QUANT_SCHEMES) == set(
        ("none", "int8_block", "onebit"))
    assert get_scheme("int8_block").allreduce is quantized_all_reduce
    assert get_scheme("none").allreduce is None
    with pytest.raises(ValueError):
        get_scheme("int4")
    # analytic wire models: int8 beats fp32 ring, onebit beats int8
    numel, world = 1 << 20, 4
    none_b = get_scheme("none").wire_bytes(numel, world)
    int8_b = get_scheme("int8_block").wire_bytes(numel, world)
    assert int8_b < none_b / 3
    assert get_scheme("onebit").wire_bytes(numel, world) < int8_b


def test_quant_gauges_cover_quantizable_verbs():
    assert tuple(QUANT_GAUGES) == tuple(
        f"comm/{v}/quant_bytes_saved" for v in QUANTIZABLE_VERBS)


def test_autotuner_block_knob_prunes_non_divisors():
    from deepspeed_tpu.autotuning.knobs import (comm_quant_block_knob,
                                                default_training_knobs)
    assert comm_quant_block_knob(1024).values == [64, 128, 256, 512]
    assert comm_quant_block_knob(100).values == [256]   # fallback
    by = {k.name: k for k in default_training_knobs()}
    # default grad-bucket padding (500e6 = 2^8 * 5^9) excludes 512
    assert by["comm_quant_block_size"].values == [64, 128, 256]
    assert by["comm_quant_enabled"].path == "comm/quantization/enabled"


# ----------------------------------------------------------------------
# loss trajectory — the real collective at dp=2 and dp=4
# ----------------------------------------------------------------------
def _dp_train(world, quantized, steps=50, lr=2.0, block=64):
    """Manual data-parallel loop over a ``world``-device submesh: grads
    all-reduced through the REAL shard_map collective (fp32 psum vs the
    two-phase int8 codec)."""
    model = SimpleModel(hidden_dim=HIDDEN)
    params = model.init(jax.random.key(0))
    _, unravel = ravel_pytree(params)
    mesh = Mesh(np.array(jax.devices()[:world]), ("dp",))

    def step(p, x, y):
        def loss_fn(q):
            pred = model.apply(q, x)
            return jnp.mean(jnp.square(pred - y))
        loss, grads = jax.value_and_grad(loss_fn)(p)
        flat, _ = ravel_pytree(grads)
        if quantized:
            padded, n = pad_for_world(flat, world, block)
            red = quantized_all_reduce(padded, "dp", block)[:n]
        else:
            red = lax.psum(flat, "dp")
        g = unravel(red / world)
        new = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
        return new, lax.pmean(loss, "dp")

    fn = jax.jit(_shard_map(
        step, mesh,
        (P(), P("dp", None), P("dp", None)), (P(), P())))
    losses = []
    for i in range(steps):
        b = random_batch(8 * world, HIDDEN, seed=i)
        params, loss = fn(params, jnp.asarray(b["x"]), jnp.asarray(b["y"]))
        losses.append(float(loss))
    return losses


@pytest.mark.parametrize("world", [2, 4])
def test_loss_trajectory_int8_vs_fp32_shard_map(world):
    fp32 = _dp_train(world, quantized=False)
    int8 = _dp_train(world, quantized=True)
    assert len(fp32) == 50
    np.testing.assert_allclose(int8, fp32, rtol=0.1, atol=5e-3)
    # training must actually converge, not just agree
    assert fp32[-1] < 0.5 * fp32[0]
    assert int8[-1] < 0.5 * int8[0]


# ----------------------------------------------------------------------
# loss trajectory — the engine's ZeRO-3 wiring
# ----------------------------------------------------------------------
def _engine_train(steps=50, seed=0, **cfg_overrides):
    groups.reset_mesh()
    model = SimpleModel(hidden_dim=HIDDEN)
    params = model.init(jax.random.key(seed))
    config = base_config(3, **cfg_overrides)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=config)
    losses = []
    for i in range(steps):
        loss = engine.train_batch(batch=random_batch(32, HIDDEN, seed=i))
        losses.append(float(loss))
    return losses


@pytest.mark.parametrize("mesh", [{"dp": 2, "fsdp": 4},
                                  {"dp": 4, "fsdp": 2}])
def test_engine_zero3_trajectory_quantized_vs_fp32(mesh):
    quant = {"enabled": True, "block_size": 64, "min_tensor_bytes": 64}
    fp32 = _engine_train(mesh=mesh)
    int8 = _engine_train(mesh=mesh, comm={"quantization": quant})
    np.testing.assert_allclose(int8, fp32, rtol=0.1, atol=5e-3)
    assert fp32[-1] < 0.5 * fp32[0] and int8[-1] < 0.5 * int8[0]


def test_engine_disabled_config_is_bit_for_bit():
    base = _engine_train(steps=10)
    off = _engine_train(steps=10,
                        comm={"quantization": {"enabled": False}})
    np.testing.assert_array_equal(np.asarray(base), np.asarray(off))


def test_engine_census_books_wire_bytes(tmp_path):
    """With quantization on, the grad-reduce census event must book the
    reduced wire bytes and carry wire_dtype/bytes_saved (plus the frozen
    quant gauge in the registry); every emitted event stays
    schema-valid."""
    import importlib.util
    import json
    import os
    groups.reset_mesh()
    model = SimpleModel(hidden_dim=HIDDEN)
    params = model.init(jax.random.key(0))
    config = base_config(
        3,
        telemetry={"enabled": True, "output_path": str(tmp_path),
                   "job_name": "quant_census"},
        comm={"quantization": {"enabled": True, "block_size": 64,
                               "min_tensor_bytes": 64}})
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=config)
    engine.train_batch(batch=random_batch(32, HIDDEN, seed=0))
    engine.flush_telemetry()
    saved = engine.comm_quant.tree_bytes_saved(params, "reduce_scatter")
    assert saved > 0
    path = os.path.join(str(tmp_path), "quant_census", "events.jsonl")
    events = [json.loads(line) for line in open(path)]
    comm = [ev for ev in events if ev.get("kind") == "comm" and
            ev.get("name") == "reduce_scatter"]
    assert comm, "no grad-reduce census event"
    annotated = [ev for ev in comm if ev.get("bytes_saved")]
    assert annotated, comm[-1]
    assert annotated[-1]["wire_dtype"] == "int8"
    assert annotated[-1]["bytes_saved"] == saved
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    spec = importlib.util.spec_from_file_location(
        "checker", os.path.join(repo, "scripts",
                                "check_telemetry_schema.py"))
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    problems = [p for ev in events for p in checker.validate_event(ev)]
    assert not problems, problems[:3]
    from deepspeed_tpu.monitor.telemetry import get_telemetry
    gauge = get_telemetry().registry.gauge(
        "comm/reduce_scatter/quant_bytes_saved")
    assert gauge.value == saved
