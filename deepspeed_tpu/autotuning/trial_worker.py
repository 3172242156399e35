"""Autotuning trial worker — one experiment in a fresh OS process.

Parity: reference ``autotuning/scheduler.py`` launches each experiment as
a separate DeepSpeed job so OOMs and allocator state can't leak between
trials (``ResourceManager.run_job``).  This worker is that job: it builds
the model from a serialisable spec, runs the timed trial, and prints ONE
JSON line for the parent's journal.

Usage (internal): python -m deepspeed_tpu.autotuning.trial_worker '<json>'

Spec format::

    {"model": {"kind": "causal_lm", "config": {...TransformerConfig}},
     "ds_config": {...}, "seq": 256, "seed": 0,
     "start_profile_step": 2, "end_profile_step": 5, "cpu": false}
"""

import json
import sys
import time

from deepspeed_tpu.utils.compile_cache import enable_compile_cache


def build_model(model_spec, overrides=None):
    kind = model_spec.get("kind", "causal_lm")
    cfg_kw = dict(model_spec["config"])
    cfg_kw.update(overrides or {})   # per-trial template model knobs
    if kind == "causal_lm":
        from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                      TransformerConfig)
        cfg = TransformerConfig(**cfg_kw)
        return CausalTransformerLM(cfg), cfg
    if kind == "bert":
        from deepspeed_tpu.models.bert import BertConfig, BertEncoder
        cfg = BertConfig(**cfg_kw)
        return BertEncoder(cfg), cfg
    raise ValueError(f"unknown model kind {kind!r}")


def timed_trial(engine, make_batch, start_profile_step, end_profile_step):
    """The measurement protocol shared by the in-process and subprocess
    runners.  ``make_batch`` is called once per step (warmup + timed) but
    all batches are generated BEFORE the timed region so host-side data
    generation never pollutes the throughput measurement."""
    import jax

    steps = max(1, end_profile_step - start_profile_step)
    batches = [make_batch() for _ in range(start_profile_step + steps)]
    for b in batches[:start_profile_step]:     # warmup + compile
        engine.train_batch(batch=b)
    t0 = time.time()
    loss = None
    for b in batches[start_profile_step:]:
        loss = engine.train_batch(batch=b)
    jax.block_until_ready(loss)
    dt = time.time() - t0
    return {
        "throughput": engine.train_batch_size() * steps / dt,
        "latency": dt / steps,
        "micro_batch": engine.train_micro_batch_size_per_gpu(),
        "zero_stage": engine.zero_stage,
        "loss": float(loss),
    }


def run_trial(spec):
    import jax
    if spec.get("cpu"):
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import deepspeed_tpu

    model, cfg = build_model(spec["model"], spec.get("model_overrides"))
    params = model.init(jax.random.key(spec.get("seed", 0)))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=spec["ds_config"])

    rng = np.random.default_rng(spec.get("seed", 0))
    seq = spec.get("seq", 256)
    gas = engine.gradient_accumulation_steps_

    def make_batch():
        # gas>1 steps consume [gas, micro*dp, S] stacks (the fused GAS scan)
        micro_total = engine.train_batch_size() // max(1, gas)
        shape = (gas, micro_total, seq) if gas > 1 else (
            engine.train_batch_size(), seq)
        return {"input_ids": rng.integers(0, cfg.vocab_size, shape)}

    out = timed_trial(engine, make_batch,
                      spec.get("start_profile_step", 2),
                      spec.get("end_profile_step", 5))
    if hasattr(cfg, "num_params"):
        # model-info for the stage-feasibility memory model (reference
        # autotuner.py:707 model-info run)
        out["n_params"] = int(cfg.num_params())
    out["gradient_accumulation_steps"] = gas
    return out


def main():
    enable_compile_cache()
    spec = json.loads(sys.argv[1])
    print(json.dumps(run_trial(spec)))


if __name__ == "__main__":
    main()
