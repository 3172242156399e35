"""Training throughput benchmark (``ds_bench train``).

Role: the training-side counterpart of the reference's benchmark harnesses
(the reference ships comm + inference benches; its training numbers come
from blog-post runs — BASELINE.md).  Measures tokens/s, model TFLOPs and
MFU for a GPT shape under the engine's ZeRO/bf16/remat configuration.

Timing rules: fresh token batches every step, `jax.block_until_ready` on
the final loss, warmup step excluded.  Token ids are tiny (KBs) so H2D does
not distort the numbers.

Usage::

    ds_bench train --model gpt_350m --batch 8 --gas 4 --seq 1024 \
        --zero-stage 3 --steps 10 [--remat-policy dots_saveable]
        [--attn-block-q 512 --attn-block-k 512] [--json]
"""

import argparse
import json
import time

MODELS = {
    "gpt2_125m": dict(hidden_size=768, n_layers=12, n_heads=12),
    "gpt_350m": dict(hidden_size=1024, n_layers=24, n_heads=16),
    "gpt_760m": dict(hidden_size=1536, n_layers=24, n_heads=16),
    # 1.01B: the largest shape whose full train state fits one 16 GB chip
    # with bf16 Adam moments (master 4B + mu 2B + nu 2B per param) — the
    # single-chip >=1B MFU config
    "gpt_1b": dict(hidden_size=2048, n_layers=18, n_heads=16),
    "gpt_1_1b": dict(hidden_size=2048, n_layers=20, n_heads=16),
    "gpt2_1_5b": dict(hidden_size=1600, n_layers=48, n_heads=25),
    "gpt_2_7b": dict(hidden_size=2560, n_layers=32, n_heads=32),
    # beyond-HBM ladder (param-stream: --offload-param cpu hosts the stack;
    # only the resident group + a working-set window live in HBM).  Host
    # Adam state is 16 B/param (fp32 master + 2 fp32 moments + bf16 mirror
    # + bf16 grad accum), so host RAM — not HBM — caps the ladder
    "gpt_5b": dict(hidden_size=4096, n_layers=24, n_heads=32),
    "gpt_6_7b": dict(hidden_size=4096, n_layers=32, n_heads=32),
    "gpt_8b": dict(hidden_size=4096, n_layers=40, n_heads=32),
    # north-star shapes (--arch llama: GQA + SwiGLU + RoPE + RMSNorm —
    # BASELINE.md's Llama-2-70B-class MFU target, scaled to chip)
    "llama_1b": dict(hidden_size=2048, n_layers=16, n_heads=16,
                     n_kv_heads=4, ffn_hidden_size=5632),
    "llama_3b": dict(hidden_size=3072, n_layers=26, n_heads=24,
                     n_kv_heads=8, ffn_hidden_size=8192),
    "llama_7b": dict(hidden_size=4096, n_layers=32, n_heads=32,
                     n_kv_heads=8, ffn_hidden_size=11008),
}


def run_benchmark(model="gpt_350m", batch=8, gas=1, seq=1024, steps=10,
                  zero_stage=3, offload=None, remat=True,
                  remat_policy="dots_saveable", attn_block_q=None,
                  attn_block_k=None, dtype="bf16", vocab_size=None,
                  moment_dtype="float32", grad_accum_dtype=None,
                  arch=None, offload_param=None, resident_layers=0,
                  buffer_count=None, serial_boundary=False):
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.comm.topology_model import device_peak_flops
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    from deepspeed_tpu.parallel import groups

    groups.reset_mesh()
    ndev = jax.device_count()
    if batch % ndev:
        import sys
        batch = ndev * max(1, round(batch / ndev))   # global batch must
        print(f"# batch rounded to {batch} (divisible by {ndev} devices)",
              file=sys.stderr)
    shape = MODELS[model] if isinstance(model, str) else dict(model)
    if arch is None:     # auto from the model name; explicit --arch wins
        arch = ("llama" if isinstance(model, str)
                and model.startswith("llama") else "gpt")
    over = {}
    if attn_block_q:
        over["attn_block_q"] = attn_block_q
    if attn_block_k:
        over["attn_block_k"] = attn_block_k
    if arch == "llama":
        # GQA + SwiGLU + RoPE + RMSNorm (the BASELINE.md north-star shape)
        arch_kw = dict(activation="silu", use_rmsnorm=True, use_rope=True,
                       tie_embeddings=False,
                       vocab_size=vocab_size or 32000)
    else:
        arch_kw = dict(activation="gelu", use_rmsnorm=False, use_rope=False,
                       tie_embeddings=True,
                       vocab_size=vocab_size or 50304)
    cfg = TransformerConfig(
        max_seq_len=seq, remat=remat, remat_policy=remat_policy,
        **arch_kw, **shape, **over)
    model_obj = CausalTransformerLM(cfg)

    zero = {"stage": zero_stage}
    if offload:
        zero["offload_optimizer"] = {"device": offload}
    if offload_param:
        pc = {"device": offload_param}
        if resident_layers:
            pc["resident_layers"] = resident_layers
        if buffer_count:
            pc["buffer_count"] = buffer_count
        zero["offload_param"] = pc
        # param-stream needs the host Adam; default its state host-side too
        zero.setdefault("offload_optimizer", {"device": "cpu"})
    ds_config = {"train_micro_batch_size_per_gpu": batch // ndev,
                 "gradient_accumulation_steps": gas,
                 "optimizer": {"type": "AdamW",
                               "params": {"lr": 1e-4,
                                          "moment_dtype": moment_dtype}},
                 dtype: {"enabled": True},
                 "zero_optimization": zero}
    if grad_accum_dtype:
        ds_config["data_types"] = {"grad_accum_dtype": grad_accum_dtype}
    if offload_param:
        # beyond-HBM init: run the initialiser on the HOST backend (the
        # full tree must never materialise in HBM — zero.Init
        # remote_device semantics), at compute dtype to halve host RAM
        import jax.numpy as jnp
        with jax.default_device(jax.devices("cpu")[0]):
            params0 = model_obj.init(
                jax.random.key(0),
                dtype=jnp.bfloat16 if dtype == "bf16" else jnp.float32)
        params0 = jax.tree_util.tree_map(np.asarray, params0)
    else:
        params0 = model_obj.init(jax.random.key(0))
    engine, *_ = deepspeed_tpu.initialize(
        model=model_obj, model_parameters=params0, config=ds_config)
    del params0
    if serial_boundary and getattr(engine, "_param_stream", None):
        engine._param_stream.boundary_pipelined = False   # ablation

    rng = np.random.default_rng(0)
    bshape = (gas, batch, seq) if gas > 1 else (batch, seq)

    def make_batch():
        return {"input_ids": rng.integers(0, cfg.vocab_size, bshape)}

    loss = engine.train_batch(batch=make_batch())          # compile+warmup
    jax.block_until_ready(loss)
    t0 = time.time()
    for _ in range(steps):
        loss = engine.train_batch(batch=make_batch())
    jax.block_until_ready(loss)
    dt = time.time() - t0

    n_chips = max(1, engine.mesh.size)
    tokens = gas * batch * seq * steps
    tps = tokens / dt
    flops = 6.0 * cfg.num_params() * tps / n_chips
    kind = getattr(jax.devices()[0], "device_kind", "")
    peak = device_peak_flops(kind)
    out = {
        "model": model if isinstance(model, str) else "custom",
        "n_params": cfg.num_params(),
        "batch": batch, "gas": gas, "seq": seq, "zero_stage": zero_stage,
        "steps": steps,
        "tokens_per_sec_per_chip": round(tps / n_chips, 1),
        "model_tflops_per_chip": round(flops / 1e12, 2),
        "loss": float(loss),
        "device_kind": kind, "n_chips": n_chips,
    }
    if moment_dtype != "float32":
        out["moment_dtype"] = moment_dtype
    if grad_accum_dtype:
        out["grad_accum_dtype"] = grad_accum_dtype
    if offload_param:
        out["offload_param"] = offload_param
        out["resident_layers"] = resident_layers
        out["boundary"] = "serial" if serial_boundary else "pipelined"
    if arch != "gpt":
        out["arch"] = arch
    if peak:
        out["mfu"] = round(flops / peak, 4)
    return out


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="ds_bench train", description=__doc__.splitlines()[0])
    p.add_argument("--model", default="gpt_350m", choices=sorted(MODELS))
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--gas", type=int, default=1)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--zero-stage", type=int, default=3)
    p.add_argument("--offload", choices=["cpu", "nvme"], default=None)
    p.add_argument("--offload-param", choices=["cpu", "nvme"], default=None,
                   help="host the parameter stack (param-stream): only the "
                        "resident group + a working-set window live in HBM")
    p.add_argument("--resident-layers", type=int, default=0)
    p.add_argument("--buffer-count", type=int, default=None)
    p.add_argument("--serial-boundary", action="store_true",
                   help="ablation: serial GAS-boundary walk instead of the "
                        "threaded Adam/H2D pipeline")
    p.add_argument("--arch", choices=["gpt", "llama"], default=None,
                   help="default: auto from the model name")
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--remat-policy", default="dots_saveable")
    p.add_argument("--attn-block-q", type=int, default=None)
    p.add_argument("--attn-block-k", type=int, default=None)
    p.add_argument("--dtype", choices=["bf16", "fp16"], default="bf16")
    p.add_argument("--moment-dtype", choices=["float32", "bfloat16"],
                   default="float32",
                   help="Adam moment storage dtype (bfloat16 halves "
                        "optimizer-state HBM; stochastic rounding)")
    p.add_argument("--grad-accum-dtype", choices=["float32", "bfloat16"],
                   default=None,
                   help="grad tree / GAS-carry dtype (data_types."
                        "grad_accum_dtype; bfloat16 halves grad HBM)")
    p.add_argument("--json", action="store_true",
                   help="print one JSON line instead of a table")
    a = p.parse_args(argv)
    out = run_benchmark(
        model=a.model, batch=a.batch, gas=a.gas, seq=a.seq, steps=a.steps,
        zero_stage=a.zero_stage, offload=a.offload, remat=not a.no_remat,
        remat_policy=a.remat_policy, attn_block_q=a.attn_block_q,
        attn_block_k=a.attn_block_k, dtype=a.dtype,
        moment_dtype=a.moment_dtype, grad_accum_dtype=a.grad_accum_dtype,
        arch=a.arch, offload_param=a.offload_param,
        resident_layers=a.resident_layers, buffer_count=a.buffer_count,
        serial_boundary=a.serial_boundary)
    if a.json:
        print(json.dumps(out))
    else:
        width = max(len(k) for k in out)
        for k, v in out.items():
            print(f"  {k:<{width}}  {v}")
    return out


if __name__ == "__main__":
    main()
