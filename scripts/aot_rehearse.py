#!/usr/bin/env python3
"""Rehearsal 3 of /opt/skills/guides/on-chip-measurement/SKILL.md §2: compile
the engine's OWN jitted train step and serving step, at ``chip_smoke.py``'s
sizes, for a TPU v5e that is described and not attached.

    python scripts/aot_rehearse.py [serve] [train1] [train4] [cell:<workload>]

Run it before a chip call that a change to those steps puts at stake — it
needs no chip, and raises what the chip's compiler would raise (a program
past 15.75 GiB of HBM, a Mosaic kernel XLA is asked to partition, ...).
Nothing runs: a compile that passes is not a chip run and says nothing about
results or times.  For each program it prints the compiler's memory analysis
(bytes per device) and counts the kernels and collectives in the compiled
text.  The kernel-sized cases live in tests/unit/test_aot_tpu_compile.py;
these whole-model compiles take 3-20 s each and stay out of tier-1.

Described devices hold no arrays, so the engine is handed shapes: its
``_init_state`` is replaced by one that builds the same TrainState out of
``jax.ShapeDtypeStruct`` with the plan's shardings, and code that asks
``jax.default_backend()`` is told "tpu" so it takes the branch it takes on
the chip.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

import chip_smoke
import deepspeed_tpu
import train_step_account
from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.parallel.topology import MESH_AXES
from deepspeed_tpu.runtime.engine import DeepSpeedEngine, TrainState
from deepspeed_tpu.runtime.loss_scaler import static_loss_scale_state

TOPOLOGY = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def shaped(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def report(name, compiled, t0):
    text = compiled.as_text()
    counts = {op: text.count(f" {op}") for op in
              ("all-gather", "reduce-scatter", "all-reduce")}
    print(f"{name}: compiled in {time.time() - t0:.1f} s; "
          f"tpu_custom_call x{text.count('tpu_custom_call')}, {counts}\n"
          f"  {compiled.memory_analysis()}", flush=True)


def train(n_devices, micro_batch, gas, seq=1024, model=None, config=None,
          name="GPT-1B"):
    """The engine's train step of ``model`` under ``config`` (default: the
    smoke's GPT-1B under its ZeRO-3 config) on an fsdp mesh of
    ``n_devices`` described chips."""
    axes = tuple(n_devices if a == "fsdp" else 1 for a in MESH_AXES)
    mesh = Mesh(np.asarray(TOPOLOGY.devices[:n_devices]).reshape(axes),
                MESH_AXES)
    model = model or CausalTransformerLM(TransformerConfig(
        **chip_smoke.GPT_1B, remat=True, remat_policy="dots_saveable"))
    config = config or chip_smoke._train_config(micro_batch, gas)
    abstract_state = {}

    def init_state_of_shapes(self, params):
        self._offload = self._param_stream = None
        params = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), params)
        params = jax.tree_util.tree_map(
            shaped, params,
            self.plan._to_sharding(self.plan.master_param_specs(params)))
        opt_state = jax.tree_util.tree_map(
            shaped, jax.eval_shape(self.tx.init, params),
            self.plan.opt_state_shardings(self.tx, params))
        everywhere = self.plan.replicated_sharding()
        loss_scale = jax.tree_util.tree_map(
            lambda x: shaped(x, everywhere), static_loss_scale_state(1.0))
        key = jax.eval_shape(lambda: jax.random.key(0))
        counter = shaped(np.zeros((), np.int32), everywhere)
        abstract_state["state"] = TrainState(
            params=params, opt_state=opt_state, loss_scale=loss_scale,
            global_step=counter, skipped_steps=counter,
            rng=shaped(key, everywhere))
        # the constructor reads int(state.global_step)
        return abstract_state["state"].replace(global_step=np.int32(0))

    DeepSpeedEngine._init_state = init_state_of_shapes
    groups.reset_mesh()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, mesh=mesh,
        model_parameters=jax.eval_shape(
            lambda: model.init(jax.random.key(0))),
        config=deepspeed_tpu.DeepSpeedConfig(
            dict(config, mesh={"fsdp": n_devices}), world_size=n_devices))
    spec = list(engine.plan.batch_spec(2))
    rows = (micro_batch * n_devices, seq)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (gas,) + rows if gas > 1 else rows, jnp.int32,
        sharding=NamedSharding(mesh, P(*([None] + spec if gas > 1
                                         else spec))))}
    t0 = time.time()
    with mesh:
        compiled = engine._get_compiled_train_step(gas).lower(
            abstract_state["state"], batch).compile()
    report(f"train {name} fsdp={n_devices} micro_batch={micro_batch} "
           f"gas={gas}", compiled, t0)
    clones = train_step_account.xla_clones(
        train_step_account.instruction_names(compiled.as_text()))
    print(f"  instructions named .remat: {len(clones)}", flush=True)


def serve():
    chip = SingleDeviceSharding(TOPOLOGY.devices[0])
    model = CausalTransformerLM(TransformerConfig(**chip_smoke.LLAMA_1B))
    params = jax.tree_util.tree_map(
        lambda x: shaped(x, chip),
        jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.bfloat16)))
    sizes = chip_smoke.LLAMA_1B_SERVE
    engine = ServingEngine(model, params, max_batch=sizes["max_batch"],
                           page_size=sizes["page_size"],
                           max_seq=sizes["max_seq"],
                           serving={"attention_backend": "auto"})
    assert engine.attention_impl == "pallas", engine.attention_impl
    caches = jax.tree_util.tree_map(lambda x: shaped(x, chip), engine.caches)
    pool = sum(x.nbytes for x in jax.tree_util.tree_leaves(engine.caches))
    print(f"page pool: {pool} bytes")

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    for name, (batch, tokens) in {"decode": (sizes["max_batch"], 1),
                                  "prefill 32": (1, 32),
                                  "prefill 512": (1, 512)}.items():
        t0 = time.time()
        # prefill and decode are two named jits over the one paged call
        # (a prefill takes the head on the one row it samples from, a
        # decode step the picks of the step before it)
        step_fn = engine._step_fn if tokens == 1 else engine._prefill_fn
        compiled = step_fn.lower(
            params, ints(batch, tokens), caches,
            ints(batch, engine.tables.shape[1]), ints(batch),
            ints(batch, 1)).compile()
        report(f"serve llama-1B {name}", compiled, t0)


def cell(workload):
    """A cell of the chip benchmark (``BENCHMARK.json``).  A training
    cell: the engine's train step, built as ``chipbench/train_cell.py``
    builds it.  A serving cell: its
    decode program and its largest and smallest prefill bucket at the
    cell's own sizes, as ``ServingEngine`` dispatches them (a model that
    counts its dispatches or keeps per-slot state is told a prefill's real
    rows and slot, as ``_run_step`` tells it)."""
    from chipbench import cells, sut
    loaded = cells.load_cell(workload)
    cfg, mix = loaded.config, loaded.mix
    if mix["kind"] == "pretrain":
        micro, gas, seq, config = train_step_account.step_of(loaded)
        return train(
            loaded.chips, micro, gas, seq, config=config, name=workload,
            model=sut.build_model(loaded, **cfg["train"]["model"]))
    chip = SingleDeviceSharding(TOPOLOGY.devices[0])
    model = sut.build_model(loaded)
    params = jax.tree_util.tree_map(
        lambda x: shaped(x, chip), jax.eval_shape(
            lambda: model.init(jax.random.key(0),
                               sut.DTYPES[cfg["serve"]["dtype"]])))
    # the engine makes its pools with jnp.zeros: hand it their shapes
    pools = model.init_paged_caches
    model.init_paged_caches = lambda *a, **k: jax.eval_shape(
        lambda: pools(*a, **k))
    batch = int(mix["max_batch"])
    engine = ServingEngine(model, params, max_batch=batch,
                           **cfg["serve"]["engine"])
    caches = jax.tree_util.tree_map(lambda x: shaped(x, chip), engine.caches)
    pool = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(engine.caches))
    print(f"{workload}: attention {engine.attention_impl}, pools {pool} "
          f"bytes, state a slot {engine.state_slot_bytes} bytes")

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    width = engine.tables.shape[1]
    # a prefill is told its real rows once (a counted or a state model's)
    # and a state model's its slot
    told = (engine._counted or engine._stateful) + engine._stateful
    lengths = mix["prompt_tokens"]
    buckets = sorted({engine.scheduler.prefill_pieces(int(n))[0]
                      for n in (lengths["min"], lengths["max"])})
    for name, tokens in [("decode", 1)] + [(f"prefill {n}", n)
                                           for n in buckets]:
        t0 = time.time()
        if tokens == 1:
            compiled = engine._step_fn.lower(
                params, ints(batch, 1), caches, ints(batch, width),
                ints(batch), ints(batch, 1),
                *[ints(batch)] * engine._counted).compile()
        else:
            compiled = engine._prefill_fn.lower(
                params, ints(1, tokens), caches, ints(1, width), ints(1),
                ints(1, 1), *[ints(1)] * told).compile()
        report(f"{workload} {name}", compiled, t0)


if __name__ == "__main__":
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"
    what = sys.argv[1:] or ["serve", "train1", "train4"]
    if "serve" in what:
        serve()
    if "train1" in what:
        train(1, 2, 4)
    if "train4" in what:
        train(4, 2, 1)
    for name in what:
        if name.startswith("cell:"):
            cell(name[len("cell:"):])
