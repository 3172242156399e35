"""Traffic kind ``pretrain``: the program's trainer
(``deepspeed_tpu.initialize(...).train_batch``) on fixed-shape token
batches.  The arithmetic follows ``deepspeed_tpu/benchmarks/training.py``
(the clock stops after ``block_until_ready``; tokens = gas x batch x seq x
steps; MFU from 6N), on whole steps only."""

import gc
import time

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.parallel import groups

from chipbench import cells, device, sut, traffic, tracing
from chipbench.reference import common

# Reference (float32, full precision) against the engine's first loss
# (bf16 compute on float32 master weights), both at the seeded initial
# weights on the same first batch.  bf16 keeps 8 bits, so each logit is off
# by about 0.4 % of its size; the errors are unbiased and the mean over
# 8,192+ tokens averages them away: on the chip the two losses differ by
# 2e-6 relative (PERF.md section 6).  5e-5 at 8,192 tokens is 25 times
# that, and an 8-bit float forward (6 % a logit, 16 times bf16's error) or
# a missing term (dropping the attention residual moves the loss by
# > 1e-2) fails it.  Fewer tokens average less, so the tolerance widens
# with 1/sqrt(tokens) (the toy cells of the tests have 128).
LOSS_RTOL_AT_8192_TOKENS = 5e-5
WARM_STEPS = 2
TRACE_SECONDS = 6.0


def _reference_loss(cell, params, ids, devices):
    """Mean next-token loss of the plain float32 reference over ``ids``
    ([rows, seq]), ``len(devices)`` sequences at a time."""
    rows = len(devices)
    by_row = NamedSharding(Mesh(np.asarray(devices), ("x",)), P("x"))
    losses = []
    for i in range(0, ids.shape[0], rows):
        chunk = jax.device_put(ids[i:i + rows], by_row)
        logits = cell.reference.logits(params, chunk, cell.config)
        losses.append(float(common.next_token_loss(logits, chunk)))
    return float(np.mean(losses))


def run(cell, seed, seconds, trace, started, devices, peaks):
    cfg, mix = cell.config, cell.mix
    chips = len(devices)
    micro = int(cfg["train"]["micro_batch_per_chip"])
    per_chip = int(mix["sequences_per_step_per_chip"])
    if per_chip % micro:
        raise SystemExit(f"chipbench: {per_chip} sequences a chip do not "
                         f"split into micro-batches of {micro}")
    gas, seq = per_chip // micro, int(mix["seq_len"])
    rows = micro * chips
    shape = (gas, rows, seq) if gas > 1 else (rows, seq)
    tokens_per_step = gas * rows * seq
    vocab = cfg["vocab_size"]
    counter = device.CompileCounter()

    model = sut.build_model(cell, **cfg["train"]["model"])
    params = sut.seeded_weights(model, seed, jax.numpy.float32, devices)
    n_params = sut.count_params(params)
    first = traffic.pretrain_batch(vocab, seed, 0, shape)
    t0 = time.perf_counter()
    reference_loss = _reference_loss(cell, params, first.reshape(-1, seq),
                                     devices)
    reference_s = time.perf_counter() - t0

    engine_config = dict(cfg["train"]["engine"],
                         train_micro_batch_size_per_gpu=micro,
                         gradient_accumulation_steps=gas)
    groups.reset_mesh()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=engine_config)
    del params
    gc.collect()

    def step(n):
        with tracing.annotate("chipbench/input"):
            batch = {"input_ids": traffic.pretrain_batch(vocab, seed, n,
                                                         shape)}
        with tracing.annotate("chipbench/step"):
            return float(jax.block_until_ready(
                engine.train_batch(batch=batch)))

    warm_losses = [step(n) for n in range(WARM_STEPS)]
    compiles_before = counter.compiles
    setup_s = time.perf_counter() - started

    steps, losses = [], []
    tracer = tracing.Tracer(trace, TRACE_SECONDS)
    window_start = time.perf_counter()
    n = WARM_STEPS
    while True:
        tracer.tick()
        t0 = time.perf_counter()
        loss = step(n)
        t1 = time.perf_counter()
        if t1 - window_start > seconds and steps:
            break       # began inside the window, ended past it
        steps.append({"kind": "train", "t0": t0, "t1": t1,
                      "tokens": tokens_per_step,
                      "traced": tracer.active})
        losses.append(loss)
        n += 1
    tracer.stop()
    compiles = counter.compiles - compiles_before

    busy = sum(s["t1"] - s["t0"] for s in steps)
    tok_s_chip = len(steps) * tokens_per_step / busy / chips
    loss_error = abs(warm_losses[0] - reference_loss) / abs(reference_loss)
    loss_rtol = LOSS_RTOL_AT_8192_TOKENS * max(
        1.0, (8192 / tokens_per_step) ** 0.5)
    finite = bool(np.all(np.isfinite(warm_losses + losses)))
    correct = (finite and loss_error <= loss_rtol
               and losses[-1] < warm_losses[0])
    device.log(steps=len(steps),
               step_s=[round(s["t1"] - s["t0"], 5) for s in steps],
               losses=[round(x, 4) for x in warm_losses + losses],
               first_loss=warm_losses[0], reference_loss=reference_loss,
               loss_rel_error=loss_error, loss_rtol=loss_rtol,
               reference_s=round(reference_s, 2), n_params=n_params,
               gas=gas, micro_batch_per_chip=micro,
               compiles_in_window=compiles, cache_hits=counter.cache_hits,
               compiles_total=counter.compiles)

    report = device.device_report(devices)
    run_record = cells.Run(
        chips=chips, peaks=peaks, config=cfg,
        model={"n_params": n_params, "n_layers": cfg["num_hidden_layers"],
               "heads": cfg["num_attention_heads"],
               "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
               "seq": seq, "micro_batch": micro, "gas": gas},
        steps=steps, traced_steps=[s for s in steps if s["traced"]],
        samples={}, counters={"compiles": compiles},
        memory_peak_bytes=report["memory_peak_bytes"], trace=tracer.trace())
    end_to_end = {"train_tok_s_chip": tok_s_chip, "setup_s": setup_s}
    return {"correct": bool(correct), "attempted": len(steps),
            "failed": 0 if finite else len(steps),
            "end_to_end": end_to_end, "run": run_record, "device": report,
            "compared": [("loss_rel_error", loss_error, loss_rtol),
                         ("last_loss", losses[-1],
                          f"< first_loss {warm_losses[0]}")]}
