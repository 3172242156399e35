"""Explicit comm/compute overlap (``zero_optimization.overlap``).

Four layers of guarantees:

* the SCHEDULE: ``simulate_forward_schedule`` + the attribution plane's
  interval algebra turn the old stage_plan docstring *claim* ("the
  gather of layer i+1 overlaps layer i's compute") into a checked
  invariant — the overlapped schedule has gather/compute overlap, the
  serial one reproduces the seed's back-to-back schedule, and both match
  the closed forms ``g/(g+c)`` (serial) and ``g/(g+L*c)`` (depth >= 1);
* the TRANSFORM: ``layer_scan`` without a context IS ``jax.lax.scan``,
  and under a context its values AND gradients stay bit-identical;
* the ENGINE: a 50-step ZeRO-3 run on the dp=2 x fsdp=4 CPU submesh
  matches the serial oracle (forward bitwise; full trajectory to ulp
  tolerance — the SPMD partitioner may re-stage the grad all-reduce,
  see test_engine_overlapped_trajectory_matches_serial), ``enabled=
  false`` is bit-for-bit the seed step, and the overlap gauges +
  all_gather census ride the telemetry stream schema-valid;
* the KNOBS: the autotuner space carries the overlap block and the
  control plane prunes gather depths whose buffers don't fit HBM.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.monitor.attribution import (decompose_step,
                                               overlap_length)
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.runtime.zero.config import (DeepSpeedZeroConfig,
                                               DeepSpeedZeroOverlapConfig)
from deepspeed_tpu.runtime.zero.stage_plan import (OVERLAP_GAUGES,
                                                   OverlapContext,
                                                   current_overlap,
                                                   layer_scan,
                                                   overlap_scope,
                                                   plan_reduce_buckets,
                                                   simulate_forward_schedule)
from tests.unit.simple_model import base_config

HIDDEN = 16
LAYERS = 4


# ----------------------------------------------------------------------
# schedule model: the docstring assertion as a checked invariant
# ----------------------------------------------------------------------
def test_serial_schedule_reproduces_seed_nothing_overlaps():
    s = simulate_forward_schedule(LAYERS, compute_ms=3.0, gather_ms=1.0,
                                  prefetch_depth=0)
    # seed schedule: gather k, compute k, back to back — zero overlap
    assert overlap_length(s["comm"], s["compute"]) == pytest.approx(0.0)
    assert s["exposed_comm_frac"] == pytest.approx(1.0 / (1.0 + 3.0))
    assert s["step_ms"] == pytest.approx(LAYERS * 4.0)


@pytest.mark.parametrize("depth", [1, 2])
def test_overlapped_schedule_gathers_run_under_compute(depth):
    s = simulate_forward_schedule(LAYERS, compute_ms=3.0, gather_ms=1.0,
                                  prefetch_depth=depth)
    # every gather but the prefill runs under a compute window
    ov = overlap_length(s["comm"], s["compute"])
    assert ov == pytest.approx((LAYERS - 1) * 1e-3, abs=1e-9)
    assert s["exposed_comm_ms"] == pytest.approx(1.0)
    assert s["exposed_comm_frac"] == pytest.approx(
        1.0 / (1.0 + LAYERS * 3.0))
    # the win is real step time, not accounting: g + L*c vs L*(g+c)
    assert s["step_ms"] == pytest.approx(1.0 + LAYERS * 3.0)


def test_schedule_agrees_with_attribution_decomposition():
    """The schedule model and decompose_step (the gauge's producer) must
    attribute the same exposure."""
    for depth in (0, 1):
        s = simulate_forward_schedule(6, compute_ms=2.0, gather_ms=1.0,
                                      prefetch_depth=depth)
        t1 = max(b for _, b in s["compute"])
        rec = decompose_step(0.0, t1, compute=s["compute"],
                             comm=s["comm"])
        assert rec["exposed_comm_ms"] == pytest.approx(
            s["exposed_comm_ms"], abs=1e-6)
        assert rec["comm_ms"] == pytest.approx(s["comm_ms"], abs=1e-6)


# ----------------------------------------------------------------------
# explicit schedule: same collectives, reordered issue, same bits
# ----------------------------------------------------------------------
def test_explicit_overlapped_schedule_is_bit_identical_to_serial():
    """A 4-rank ``shard_map`` ZeRO-3 run of a stacked MLP under two
    schedules built from the SAME explicit collectives: serial (gather
    layer k, compute layer k) and overlapped (layer k+1's tiled
    all_gather issued before layer k's compute).  The backward rides
    the transposed program, where each gather becomes a per-layer
    psum_scatter.  Every collective is placed by hand, so overlap
    reorders communication and never math: every step's loss is equal
    bit for bit, and the run trains."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    world, steps, batch, lr = 4, 50, 32, 0.5
    mesh = Mesh(np.array(jax.devices()[:world]), ("fsdp",))

    def gather(leaf):
        return jax.lax.all_gather(leaf, "fsdp", axis=0, tiled=True)

    def mse(h, yb):
        err = h - yb
        return jax.lax.psum(jnp.sum(err * err), "fsdp") / (batch * HIDDEN)

    def fwd_serial(wl, bl, xb, yb):
        h = xb
        for k in range(LAYERS):
            h = jnp.tanh(h @ gather(wl[k]) + gather(bl[k]))
        return mse(h, yb)

    def fwd_overlap(wl, bl, xb, yb):
        h = xb
        nxt = (gather(wl[0]), gather(bl[0]))
        for k in range(LAYERS):
            wk, bk = nxt
            if k + 1 < LAYERS:
                nxt = (gather(wl[k + 1]), gather(bl[k + 1]))
            h = jnp.tanh(h @ wk + bk)
        return mse(h, yb)

    w_spec, b_spec, x_spec = (P(None, "fsdp", None), P(None, "fsdp"),
                              P("fsdp", None))
    rng = np.random.default_rng(0)
    w0 = (rng.standard_normal((LAYERS, HIDDEN, HIDDEN))
          / np.sqrt(HIDDEN)).astype(np.float32)
    proj = (rng.standard_normal((HIDDEN, HIDDEN)) * 0.5).astype(np.float32)
    X = rng.standard_normal((steps, batch, HIDDEN)).astype(np.float32)
    Y = np.tanh(X @ proj)

    def run(fwd):
        loss_fn = jax.shard_map(
            fwd, mesh=mesh, in_specs=(w_spec, b_spec, x_spec, x_spec),
            out_specs=P(), check_vma=False)

        @jax.jit
        def step(wl, bl, xb, yb):
            loss, (gw, gb) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(wl, bl, xb, yb)
            return wl - lr * gw, bl - lr * gb, loss

        wl = jax.device_put(w0, NamedSharding(mesh, w_spec))
        bl = jax.device_put(np.zeros((LAYERS, HIDDEN), np.float32),
                            NamedSharding(mesh, b_spec))
        x_sh = NamedSharding(mesh, x_spec)
        losses = []
        for i in range(steps):
            wl, bl, loss = step(wl, bl, jax.device_put(X[i], x_sh),
                                jax.device_put(Y[i], x_sh))
            losses.append(np.asarray(loss, np.float32))
        return np.asarray(losses)

    serial, overlapped = run(fwd_serial), run(fwd_overlap)
    np.testing.assert_array_equal(serial, overlapped)
    assert serial[-1] < 0.7 * serial[0]   # actually trains


# ----------------------------------------------------------------------
# layer_scan: scan parity and bit-identical values/grads
# ----------------------------------------------------------------------
def _stacked_params(seed=0):
    k = jax.random.key(seed)
    k1, k2 = jax.random.split(k)
    return {
        "w": jax.random.normal(k1, (LAYERS, HIDDEN, HIDDEN)) * 0.1,
        "b": jax.random.normal(k2, (LAYERS, HIDDEN)) * 0.01,
    }


def _scan_loss(scan_fn, params, x):
    def body(h, layer):
        return jnp.tanh(h @ layer["w"] + layer["b"]), jnp.sum(h)
    h, aux = scan_fn(body, x, params)
    return jnp.sum(h * h) + jnp.sum(aux)


def test_layer_scan_without_context_is_lax_scan():
    assert current_overlap() is None
    params = _stacked_params()
    x = jax.random.normal(jax.random.key(1), (8, HIDDEN))
    ref = _scan_loss(jax.lax.scan, params, x)
    got = _scan_loss(layer_scan, params, x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


@pytest.mark.parametrize("depth", [1, 2, 7])
def test_layer_scan_pipelined_values_and_grads_bit_identical(depth):
    """Overlap may reorder communication, never math: loss AND the full
    grad tree (incl. the scatter-add transpose of the pipeline's
    dynamic_index gathers, and the dead clamped-tail gathers) must be
    bitwise equal to the serial scan."""
    params = _stacked_params()
    x = jax.random.normal(jax.random.key(1), (8, HIDDEN))
    ref_l, ref_g = jax.value_and_grad(
        lambda p: _scan_loss(jax.lax.scan, p, x))(params)
    ctx = OverlapContext(gather_prefetch_depth=depth,
                         param_persistence_threshold=0)
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]), ("fsdp",))
    with mesh, overlap_scope(ctx):
        got_l, got_g = jax.jit(jax.value_and_grad(
            lambda p: _scan_loss(layer_scan, p, x)))(params)
    np.testing.assert_array_equal(np.asarray(ref_l), np.asarray(got_l))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), ref_g, got_g)
    assert ctx.scans == 1
    assert ctx.layers == LAYERS
    assert ctx.pipelined_leaves == 2 and ctx.persistent_leaves == 0


def test_layer_scan_persistence_threshold_skips_small_leaves():
    params = _stacked_params()
    x = jax.random.normal(jax.random.key(1), (8, HIDDEN))
    ref = _scan_loss(jax.lax.scan, params, x)
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]), ("fsdp",))
    # b slices (16 floats) persist; w slices (256) ride the pipeline
    ctx = OverlapContext(gather_prefetch_depth=1,
                         param_persistence_threshold=100)
    with mesh, overlap_scope(ctx):
        got = _scan_loss(layer_scan, params, x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    assert ctx.pipelined_leaves == 1 and ctx.persistent_leaves == 1
    # everything persistent -> pipeline skipped, still exact
    ctx_all = OverlapContext(gather_prefetch_depth=1,
                             param_persistence_threshold=10_000)
    with mesh, overlap_scope(ctx_all):
        got2 = _scan_loss(layer_scan, params, x)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got2))
    assert ctx_all.pipelined_leaves == 0


# ----------------------------------------------------------------------
# reduce-scatter bucket planner
# ----------------------------------------------------------------------
def test_plan_reduce_buckets_reverse_order_and_cap():
    leaves = [np.zeros(n, np.float32) for n in (10, 20, 30, 40)]
    # 40 B, 80 B, 120 B, 160 B filled last-first under a 200 B cap:
    # 160 alone (160+120 overflows), then 120+80, then 40
    assert plan_reduce_buckets(leaves, 200) == [[3], [2, 1], [0]]
    # oversized leaf gets its own bucket, never dropped
    assert plan_reduce_buckets(leaves, 1) == [[3], [2], [1], [0]]
    # everything fits -> one bucket, reverse order
    assert plan_reduce_buckets(leaves, 10_000) == [[3, 2, 1, 0]]
    assert plan_reduce_buckets([], 100) == []


# ----------------------------------------------------------------------
# config validation
# ----------------------------------------------------------------------
def test_overlap_config_defaults_and_validation():
    zc = DeepSpeedZeroConfig({"stage": 3})
    assert isinstance(zc.overlap, DeepSpeedZeroOverlapConfig)
    assert zc.overlap.enabled is False
    assert zc.overlap.gather_prefetch_depth == 1
    assert zc.overlap.rs_bucket_bytes == 50_000_000
    on = DeepSpeedZeroConfig({"stage": 3, "overlap": {
        "enabled": True, "gather_prefetch_depth": 4,
        "rs_bucket_bytes": 1000}})
    assert on.overlap.enabled and on.overlap.gather_prefetch_depth == 4
    with pytest.raises(ValueError, match="gather_prefetch_depth"):
        DeepSpeedZeroConfig({"stage": 3,
                             "overlap": {"gather_prefetch_depth": 0}})
    with pytest.raises(ValueError, match="rs_bucket_bytes"):
        DeepSpeedZeroConfig({"stage": 3,
                             "overlap": {"rs_bucket_bytes": -1}})


# ----------------------------------------------------------------------
# the engine: trajectory bit-identity on the dp=2 x fsdp=4 submesh
# ----------------------------------------------------------------------
class StackedModel:
    """Scan-over-layers regression stack: the smallest model whose
    forward goes through ``layer_scan`` (SimpleModel unrolls its layers
    and never would)."""

    def __init__(self, hidden_dim=HIDDEN, n_layers=LAYERS):
        self.hidden_dim, self.n_layers = hidden_dim, n_layers

    def tp_rules(self):
        # ZeRO-3 partitioning of the stacked leaves: fsdp on the LAYER
        # dim, so every layer's block lives whole on one rank and the
        # per-layer gather is pure data movement.  Sharding a feature
        # dim instead would let the partitioner pick partial-sum matmul
        # strategies whose reduction order differs from the gathered
        # full dot — bit-identity between the serial and pipelined
        # schedules would then be unattainable by construction.
        from jax.sharding import PartitionSpec as P
        return [(r"\['w'\]$", P("fsdp")), (r"\['b'\]$", P("fsdp"))]

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        h, n = self.hidden_dim, self.n_layers
        return {
            "layers": {
                "w": jax.random.normal(k1, (n, h, h)) * 0.1,
                "b": jnp.zeros((n, h)),
            },
            "out": jax.random.normal(k2, (h, h)) * 0.1,
        }

    def apply(self, params, x):
        def body(h, layer):
            return jnp.tanh(h @ layer["w"] + layer["b"]), None
        h, _ = layer_scan(body, x, params["layers"])
        return h @ params["out"]

    def loss(self, params, batch, rng=None):
        x, y = jnp.asarray(batch["x"]), jnp.asarray(batch["y"])
        return jnp.mean(jnp.square(self.apply(params, x) - y))


def _stacked_batch(batch_size, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(batch_size, HIDDEN)).astype(np.float32)
    return {"x": x, "y": np.roll(x, 1, axis=-1) * 0.5}


def _stacked_train(steps=50, seed=0, zero=None, return_engine=False,
                   **cfg_overrides):
    groups.reset_mesh()
    model = StackedModel()
    params = model.init(jax.random.key(seed))
    config = base_config(3, mesh={"dp": 2, "fsdp": 4}, **cfg_overrides)
    if zero:
        config["zero_optimization"].update(zero)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=config)
    losses = []
    for i in range(steps):
        loss = engine.train_batch(batch=_stacked_batch(32, seed=i))
        losses.append(float(loss))
    return (losses, engine) if return_engine else losses


# every leaf rides the pipeline; tiny bucket cap forces real bucketing
_OVERLAP_ZERO = {
    "param_persistence_threshold": 0,
    "overlap": {"enabled": True, "gather_prefetch_depth": 1,
                "rs_bucket_bytes": 2048},
}


@pytest.mark.parametrize("depth", [1, 2])
def test_engine_overlapped_trajectory_matches_serial(depth):
    """50 overlapped steps on the simulated 8-device mesh vs the serial
    oracle.

    The FORWARD is bit-identical (step 0's loss, computed from identical
    params, must match exactly — the gather pipeline is pure data
    movement, proven bitwise for values AND grads in the layer_scan
    tests above).  The full trajectory is held to one-or-two-ulp
    agreement rather than bitwise: under jit the SPMD partitioner is
    free to STAGE the backward's 8-rank grad reduction differently per
    program (a flat [1,8] all-reduce for the serial scan vs a
    [2,4]-then-[4,2] two-stage reduce for the pipelined one — visible in
    the dumped HLO), which reorders the same 8-term sum.  That is the
    partitioner's own communication reordering, not a math change; the
    construction-level bit-identity bar — same collectives, reordered
    issue — is enforced where the schedule is explicit, in
    test_explicit_overlapped_schedule_is_bit_identical_to_serial."""
    zero_on = {k: (dict(v, gather_prefetch_depth=depth)
                   if k == "overlap" else v)
               for k, v in _OVERLAP_ZERO.items()}
    serial = _stacked_train(zero={"param_persistence_threshold": 0})
    overlapped = _stacked_train(zero=zero_on)
    assert serial[0] == overlapped[0]     # forward: bitwise
    np.testing.assert_allclose(np.asarray(serial), np.asarray(overlapped),
                               rtol=5e-6, atol=1e-7)
    assert serial[-1] < 0.7 * serial[0]   # actually trains


def test_engine_overlap_disabled_is_bit_for_bit_seed():
    """overlap.enabled=false must route through the exact seed code —
    same trajectory as a config that never mentions the block."""
    seed_run = _stacked_train(steps=10)
    off = _stacked_train(steps=10, zero={"overlap": {"enabled": False}})
    np.testing.assert_array_equal(np.asarray(seed_run), np.asarray(off))


def test_engine_overlap_gauges_and_census(tmp_path):
    """Overlapped run: the frozen comm/overlap/* gauges are emitted, the
    reduce-scatter is bucketed, the gather pipeline books an all_gather
    census record, and every event validates against the schema."""
    losses, engine = _stacked_train(
        steps=3, zero=_OVERLAP_ZERO, return_engine=True,
        telemetry={"enabled": True, "output_path": str(tmp_path),
                   "job_name": "overlap",
                   "attribution": {"enabled": True}})
    engine.flush_telemetry()
    assert engine._rs_buckets > 1, "rs_bucket_bytes=2048 must split"
    ctx = engine._overlap_ctx
    assert ctx is not None and ctx.scans >= 1
    assert ctx.layers == LAYERS and ctx.pipelined_leaves >= 1
    path = os.path.join(str(tmp_path), "overlap", "events.jsonl")
    events = [json.loads(line) for line in open(path)]
    gauges = {ev["name"] for ev in events if ev.get("kind") == "gauge"}
    for name in OVERLAP_GAUGES:
        assert name in gauges, f"missing overlap gauge {name}"
    comm = {ev["name"] for ev in events if ev.get("kind") == "comm"}
    assert "all_gather" in comm, "gather pipeline census missing"
    assert "reduce_scatter" in comm, "bucketed grad-reduce census missing"
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    spec = importlib.util.spec_from_file_location(
        "checker", os.path.join(repo, "scripts",
                                "check_telemetry_schema.py"))
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    problems = [p for ev in events for p in checker.validate_event(ev)]
    assert not problems, problems[:3]


def test_engine_serial_run_emits_no_overlap_gauges(tmp_path):
    _, engine = _stacked_train(
        steps=3, return_engine=True,
        telemetry={"enabled": True, "output_path": str(tmp_path),
                   "job_name": "serial",
                   "attribution": {"enabled": True}})
    engine.flush_telemetry()
    path = os.path.join(str(tmp_path), "serial", "events.jsonl")
    events = [json.loads(line) for line in open(path)]
    gauges = {ev["name"] for ev in events if ev.get("kind") == "gauge"}
    assert not (gauges & set(OVERLAP_GAUGES))


# ----------------------------------------------------------------------
# autotuner: knobs + HBM pruning of infeasible prefetch depths
# ----------------------------------------------------------------------
def test_default_training_knobs_carry_overlap_block():
    from deepspeed_tpu.autotuning.knobs import default_training_knobs
    by = {k.name: k for k in default_training_knobs()}
    assert by["overlap_enabled"].path == "zero_optimization/overlap/enabled"
    assert by["overlap_enabled"].values == [False, True]
    assert by["gather_prefetch_depth"].values == [1, 2, 4]
    assert by["rs_bucket_bytes"].path == \
        "zero_optimization/overlap/rs_bucket_bytes"
    # exposed_comm_frac already scores trials (objective weight -100)
    from deepspeed_tpu.autotuning.objective import (Objective,
                                                    SNAPSHOT_METRICS)
    assert Objective.DEFAULT_WEIGHTS["exposed_comm_frac"] == -100.0
    assert "exposed_comm_frac" in SNAPSHOT_METRICS


def test_controlplane_prunes_infeasible_gather_depth(tmp_path):
    from deepspeed_tpu.autotuning.autotuner import (gather_buffer_bytes,
                                                    model_memory_per_chip)
    from deepspeed_tpu.autotuning.controlplane import ControlPlane
    num_params, layers, dp = 1_000_000, 4, 4
    base = model_memory_per_chip(num_params, 3, dp)
    # budget fits the state + shallow buffers but not depth-4 buffers
    hbm = base + gather_buffer_bytes(num_params, layers, 1) + 1
    cp = ControlPlane(base_config={}, results_dir=str(tmp_path),
                      hbm_bytes=hbm, model_num_params=num_params,
                      model_num_layers=layers)
    cfg = {"zero_optimization": {"stage": 3}, "dp": dp}

    def with_depth(d):
        z = dict(cfg["zero_optimization"],
                 overlap={"enabled": True, "gather_prefetch_depth": d})
        return dict(cfg, zero_optimization=z)

    assert cp.prune_reason(cfg) is None                  # serial fits
    assert cp.prune_reason(with_depth(1)) is None        # shallow fits
    reason = cp.prune_reason(with_depth(4))
    assert reason is not None and reason.startswith("overlap_depth_hbm")
    # overlap disabled never prices buffers
    z_off = dict(cfg["zero_optimization"],
                 overlap={"enabled": False, "gather_prefetch_depth": 8})
    assert cp.prune_reason(dict(cfg, zero_optimization=z_off)) is None
