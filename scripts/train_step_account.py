#!/usr/bin/env python3
"""What a training cell's compiled step holds and repeats, read on the chip:
the compiler's own memory account of the step program
(``compiled.memory_analysis()``, which is larger than what
``peak_hbm_gb.*`` reads), the instructions XLA cloned to fit it (names with
``.remat``: XLA rematerialises when ITS account is short, whatever
``jax.checkpoint`` was told), how often each flash kernel runs a layer and
micro-batch in a traced step, and a fingerprint of the losses and of the
whole train state after the first and the last step, to hold two trees to
the same arithmetic bit for bit (after one step Adam's first moment is the
gradient times a constant).

    python3 scripts/train_step_account.py --workload <train cell> --seed <n>

The engine is built as ``chipbench/train_cell.py`` builds it, from the
cell's own files.  One JSON line; it runs from any tree that has the
benchmark (copy it into the parent's to compare).
"""

import argparse
import gc
import hashlib
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KERNELS = ("flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv")


def step_of(cell):
    """(micro-batch a chip, micro-batches a step, positions, the engine's
    config) of a training cell, as ``chipbench/train_cell.py`` reads them
    from the cell's files."""
    train = cell.config["train"]
    micro = int(train["micro_batch_per_chip"])
    gas = int(cell.mix["sequences_per_step_per_chip"]) // micro
    return micro, gas, int(cell.mix["seq_len"]), dict(
        train["engine"], train_micro_batch_size_per_gpu=micro,
        gradient_accumulation_steps=gas)


def instruction_names(text):
    """The names of a compiled program's instructions."""
    return set(re.findall(r"%([\w.-]+) = ", text))


def xla_clones(names):
    """Those XLA cloned to fit ITS memory account (``.remat`` in the name:
    it rematerialises whatever ``jax.checkpoint`` was told)."""
    return sorted(n for n in names if ".remat" in n)


def fingerprint(tree):
    """sha256 over each leaf's wrapping sum and xor of its bit patterns,
    taken on the device: equal for equal bits, whatever the sharding."""
    import jax
    import jax.numpy as jnp

    def bits(x):
        if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
            x = jax.random.key_data(x)
        wide = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        u = jax.lax.bitcast_convert_type(x, wide).astype(jnp.uint32).ravel()
        return jnp.stack([jnp.sum(u, dtype=jnp.uint32),
                          jax.lax.reduce(u, jnp.uint32(0),
                                         jax.lax.bitwise_xor, (0,))])

    sums = jax.jit(lambda t: [bits(x) for x in jax.tree_util.tree_leaves(t)])
    digest = hashlib.sha256()
    for pair in jax.device_get(sums(tree)):
        digest.update(pair.tobytes())
    return digest.hexdigest()[:16]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=3,
                    help="steps fingerprinted (the first compiles)")
    ap.add_argument("--traced-steps", type=int, default=3)
    ap.add_argument("--ops", action="store_true",
                    help="every device operation's seconds a traced step")
    ap.add_argument("--cpu", action="store_true",
                    help="a toy cell off the chip (the tests' switch)")
    args = ap.parse_args()

    import jax
    import deepspeed_tpu
    from chipbench import cells, device, reduce, sut, tracing, traffic
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    cell = cells.load_cell(args.workload)
    cfg = cell.config
    devices = device.require_devices(cell.chips, not args.cpu)
    micro, gas, seq, engine_config = step_of(cell)
    rows = micro * len(devices)
    shape = (gas, rows, seq) if gas > 1 else (rows, seq)

    model = sut.build_model(cell, **cfg["train"]["model"])
    params = sut.seeded_weights(model, args.seed, jax.numpy.float32, devices)
    groups.reset_mesh()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=engine_config)
    del params
    gc.collect()

    def batch(n):
        return {"input_ids": traffic.pretrain_batch(cfg["vocab_size"],
                                                    args.seed, n, shape)}

    with engine.mesh:
        compiled = engine._get_compiled_train_step(gas).lower(
            engine.state, engine._shard_batch(
                batch(0), leading_gas_dim=gas > 1)).compile()
    account = compiled.memory_analysis()
    text = compiled.as_text()
    names = instruction_names(text)
    out = {"workload": args.workload, "seed": args.seed,
           "device": jax.devices()[0].device_kind,
           "compiled_bytes": {
               k: int(getattr(account, k + "_size_in_bytes"))
               for k in ("argument", "output", "alias", "temp",
                         "generated_code")},
           "remat_instructions": xla_clones(names),
           "kernel_calls_in_text": {k: sum(n.startswith(k) for n in names)
                                    for k in KERNELS}}
    # each instruction's source operation (its phase and scope), to file
    # the traced seconds of ``--ops`` under
    sources = dict(re.findall(r"%([\w.-]+) = [^\n]*?op_name=\"([^\"]*)\"",
                              text)) if args.ops else {}
    del compiled, text
    gc.collect()

    losses, states = [], {}
    for n in range(args.steps):
        losses.append(float(jax.block_until_ready(
            engine.train_batch(batch=batch(n)))))
        if n in (0, args.steps - 1):
            states[f"after_step_{n + 1}"] = fingerprint(engine.state)
    out["losses"] = [x.hex() for x in losses]
    out["state"] = states

    if args.traced_steps:
        tracer = tracing.Tracer(True, 1e9)
        tracer.tick()
        for n in range(args.steps, args.steps + args.traced_steps):
            with tracing.annotate("chipbench/step"):
                jax.block_until_ready(engine.train_batch(batch=batch(n)))
        trace = tracer.trace()
        per_step = {k: v / args.traced_steps
                    for k, v in reduce.op_seconds(trace).items()}
        layers = cfg["num_hidden_layers"]
        out["traced"] = {
            "steps": args.traced_steps,
            "device_s_a_step": sum(per_step.values()),
            "remat_s_a_step": {k: v for k, v in sorted(per_step.items())
                               if ".remat" in k},
            "kernel_calls_a_layer_and_micro_batch": {
                k: reduce.op_count(trace, "pallas", k)
                / (args.traced_steps * layers * gas) for k in KERNELS},
            "kernel_s_a_step": {
                k: sum(v for n, v in per_step.items() if k in n)
                for k in KERNELS}}
        if args.ops:
            out["traced"]["ops_s_a_step"] = per_step
            out["traced"]["op_names"] = {
                k: sources.get(k.rpartition(":")[0], "") for k in per_step}
    out["peak_bytes_in_use"] = device.device_report(devices).get(
        "memory_peak_bytes")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
