#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, phases in order, the first failure raises (exit code != 0):

    device   jax.devices() must be TPU (else a one-line error, no result)
    kernels  flash attention fwd+bwd and ragged paged attention (decode and
             prefill) on the chip against the repo's float32 jnp oracles
    train    GPT-1B (d 2048, 18 layers, seq 1024, ZeRO-3, bf16) through
             ``deepspeed_tpu.initialize(...).train_batch``
    serve    llama-1B widths through ``init_inference(...)
             .create_serving_engine(...)``, 16 requests stepped to completion,
             compared with a ``"jnp"``-backend engine

``--chips 4`` runs ONLY the sharded path and what it is compared with: the
same GPT-1B trainer on a ``{"fsdp": 4}`` mesh against one device.

Every phase prints one JSON object; the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Weights and data are random, made from ``SEED``.  Times, bytes and versions
printed here are information for whoever sizes benchmark cells — they are
not benchmark results.
"""

import argparse
import gc
import json
import sys
import time
from importlib import metadata

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.benchmarks.training import MODELS
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.ops.attention import reference_attention
from deepspeed_tpu.ops.paged_attention import (PagedKVCache,
                                               paged_decode_attention)
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.parallel.topology import build_mesh
from deepspeed_tpu.utils.compile_cache import enable_compile_cache

SEED = 0

# the configuration ``ds_bench train`` names gpt_1b: 1.01B parameters, the
# largest GPT whose whole bf16-moment train state fits one 16 GB chip
GPT_1B = dict(vocab_size=50304, max_seq_len=1024, activation="gelu",
              use_rmsnorm=False, use_rope=False, tie_embeddings=True,
              **MODELS["gpt_1b"])
GPT_1B_TRAIN = dict(seq=1024, micro_batch=2, gas=4, steps=5)
GPT_1B_SHARDED = dict(seq=1024, micro_batch=2, steps=3)
# llama-1B widths (GQA 16/4 at head_dim 128) with Llama's 32000 vocabulary
LLAMA_1B = dict(vocab_size=32000, max_seq_len=2048, **MODELS["llama_1b"])
LLAMA_1B_SERVE = dict(max_batch=8, page_size=16, max_seq=2048,
                      n_requests=16, prompt_range=(32, 512), new_tokens=64,
                      n_reference=4)

# Tolerances.  bfloat16 keeps 8 significant bits (one ulp = 2**-8 relative),
# so a bf16 kernel result may sit a few ulps off the float32 oracle; each
# error is the max abs difference over max(1, max |oracle|).
KERNEL_TOL = 2e-2
# serving logits leave the model as bf16: pallas and jnp engines run the same
# bf16 weights and differ only in the order of the attention arithmetic, so
# they may end a few bf16 ulps apart — 8 ulps of the largest logit (an ulp
# is at most 2**-7 of the value)
LOGIT_TOL = 8 * 2.0 ** -7
# fsdp=4 vs one device: same data and weights, bf16 gradients summed in a
# different order; relative difference of the per-step loss
SHARDED_LOSS_RTOL = 2e-3
MEMORY_BALANCE = 0.25     # bytes_in_use spread over the four devices


class SmokeError(RuntimeError):
    pass


def check(ok, message):
    if not ok:
        raise SmokeError(message)


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileCounter:
    """Counts XLA backend compilations and persistent-cache hits/misses
    through ``jax.monitoring`` (listeners stay for the process's life)."""

    def __init__(self):
        self.compiles = self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def _error(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))


def _compiled_with_kernel(what, jitted, *args, on_chip):
    """Fail unless the program ``jitted(*args)`` holds a Pallas kernel — on
    the chip as a Mosaic custom call in the compiled text (an interpreted
    kernel lowers to plain HLO).  Returns the compiled program; off the chip
    nothing is compiled (only the traced program can show the kernel
    there) and None is returned."""
    traced = jitted.trace(*args)
    check("pallas_call" in str(traced.jaxpr),
          f"{what}: no Pallas kernel in the traced program")
    if not on_chip:
        return None
    compiled = traced.lower().compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{what}: no tpu_custom_call in the compiled program")
    return compiled


# --------------------------------------------------------------------------
def phase_device(n_chips):
    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit("chip_smoke: no accelerator: " + str(e).splitlines()[0])
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no accelerator: jax found platform "
                 f"{devices[0].platform!r}, need 'tpu'")
    if n_chips > 1 and len(devices) != n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} devices, "
                 f"jax found {len(devices)}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    emit("device", **device, jax=jax.__version__,
         jaxlib=metadata.version("jaxlib"), libtpu=metadata.version("libtpu"))
    return device


# --------------------------------------------------------------------------
def phase_kernels(flash_shape=(2, 1024, 16, 128), heads=(16, 4),
                  head_dim=128, page_size=16, decode_batch=8,
                  max_pages=32, prefill_len=128, big_page=128,
                  big_prefill_len=1024, interpret=False):
    """Kernels alone, against the float32 oracles."""
    key = jax.random.key(SEED)
    f32 = lambda t: jax.tree_util.tree_map(   # noqa: E731
        lambda x: x.astype(jnp.float32), t)

    # flash attention, forward and backward
    q, k, v, g = (jax.random.normal(jax.random.fold_in(key, i), flash_shape,
                                    jnp.bfloat16) for i in range(4))

    def loss(attn, q, k, v):
        out = attn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32)), out

    flash = jax.jit(jax.value_and_grad(
        lambda q, k, v: loss(lambda *a: flash_attention(
            *a, causal=True, interpret=interpret), q, k, v),
        argnums=(0, 1, 2), has_aux=True))
    oracle = jax.jit(jax.value_and_grad(
        lambda q, k, v: loss(lambda *a: reference_attention(
            *a, causal=True), q, k, v),
        argnums=(0, 1, 2), has_aux=True))
    (_, out), grads = flash(q, k, v)
    (_, out_ref), grads_ref = oracle(*f32((q, k, v)))
    errors = {"flash_fwd": _error(out, out_ref)}
    for name, got, want in zip(("dq", "dk", "dv"), grads, grads_ref):
        errors[f"flash_bwd_{name}"] = _error(got, want)

    # ragged paged attention: a decode batch of uneven contexts and one
    # prefill, over one shuffled page pool — at the smoke server's page
    # size, and at the documented default page with a long prefill
    H, Hkv = heads
    n_pages = decode_batch * max_pages + 1
    rng = np.random.default_rng(SEED)
    for tag, page, plen in (("", page_size, prefill_len),
                            (f"_p{big_page}", big_page, big_prefill_len)):
        pool = PagedKVCache(*(jax.random.normal(
            jax.random.fold_in(key, 10 + i), (n_pages, Hkv, page, head_dim),
            jnp.bfloat16) for i in range(2)))
        tables = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                             .reshape(decode_batch, max_pages), jnp.int32)
        lengths = jnp.asarray(
            rng.integers(page + 1, max_pages * page, decode_batch),
            jnp.int32)
        cases = {
            f"ragged_decode{tag}": (
                jax.random.normal(jax.random.fold_in(key, 20),
                                  (decode_batch, 1, H, head_dim),
                                  jnp.bfloat16),
                tables, lengths),
            f"ragged_prefill{tag}": (
                jax.random.normal(jax.random.fold_in(key, 21),
                                  (1, plen, H, head_dim), jnp.bfloat16),
                tables[:1], jnp.full((1,), plen, jnp.int32)),
        }
        for name, (qr, tb, ln) in cases.items():
            got = paged_decode_attention(qr, pool, tb, ln, impl="pallas",
                                         interpret=interpret)
            want = paged_decode_attention(f32(qr), f32(pool), tb, ln,
                                          impl="jnp")
            errors[name] = _error(got, want)

    emit("kernels", max_abs_error=errors, tolerance=KERNEL_TOL,
         interpret=interpret)
    for name, err in errors.items():
        check(np.isfinite(err) and err <= KERNEL_TOL,
              f"kernel {name}: error {err} above {KERNEL_TOL}")
    return errors


# --------------------------------------------------------------------------
def _train_config(micro_batch, gas, mesh=None):
    cfg = {
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-4, "weight_decay": 0.0,
                                 "moment_dtype": "bfloat16"}},
        "bf16": {"enabled": True},
        "data_types": {"grad_accum_dtype": "bfloat16"},
        "zero_optimization": {"stage": 3},
    }
    if mesh:
        cfg["mesh"] = mesh
    return cfg


def _train_steps(engine, batch, steps, counter, on_chip):
    """Warm-up plus ``steps`` timed ``train_batch`` calls on one fixed
    batch; returns the losses, the compiled step's text and the times."""
    gas = engine.gradient_accumulation_steps()
    t0 = time.perf_counter()
    with engine.mesh:
        compiled = _compiled_with_kernel(
            "train step (flash attention)",
            engine._get_compiled_train_step(gas), engine.state,
            engine._shard_batch(batch, leading_gas_dim=gas > 1),
            on_chip=on_chip)
    text = compiled.as_text() if on_chip else ""
    del compiled
    losses = [float(jax.block_until_ready(engine.train_batch(batch=batch)))]
    compile_s = time.perf_counter() - t0
    compiles_before, step_s = counter.compiles, []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(jax.block_until_ready(
            engine.train_batch(batch=batch))))
        step_s.append(time.perf_counter() - t0)
    recompiles = counter.compiles - compiles_before
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(recompiles == 0, f"{recompiles} compilations after warm-up")
    return losses, text, {"compile_and_first_step_s": round(compile_s, 2),
                          "step_s": [round(s, 4) for s in step_s]}


def _token_batch(vocab, shape):
    return {"input_ids": np.random.default_rng(SEED).integers(
        0, vocab, shape, dtype=np.int32)}


def phase_train(counter, model_kw=GPT_1B, seq=1024, micro_batch=2, gas=4,
                steps=5, on_chip=True):
    cfg = TransformerConfig(**model_kw, remat=True,
                            remat_policy="dots_saveable")
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(SEED))
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config=_train_config(micro_batch, gas))
    del params
    # micro_batch rows per device: one device on the one-chip machine
    batch = _token_batch(cfg.vocab_size,
                         (gas, engine.train_batch_size() // gas, seq))
    losses, _, times = _train_steps(engine, batch, steps, counter, on_chip)
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    stats = jax.devices()[0].memory_stats() or {}
    emit("train", n_params=cfg.num_params(), n_layers=cfg.n_layers,
         zero_stage=engine.zero_stage, losses=[round(x, 4) for x in losses],
         **times, recompiles_after_warmup=0, flash_kernel_in_step=True,
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"))
    return losses


# --------------------------------------------------------------------------
def _serve(engine, prompts, new_tokens, record):
    """Run ``prompts`` to completion through the engine's public loop.
    Returns (tokens per request, for each request id in ``record`` the
    logits rows its tokens were sampled from: the prefill's last row, then
    one row per decode step)."""
    logits = {i: [] for i in record}
    sample = engine._sample

    def spy(req, row):
        if req.req_id in logits:
            logits[req.req_id].append(np.array(row, np.float32))
        return sample(req, row)

    engine._sample = spy
    outputs = {}
    for i, prompt in enumerate(prompts):
        engine.add_request(i, prompt, max_new_tokens=new_tokens)
    while engine.queue or engine.n_active:
        outputs.update(engine.step())
    engine._sample = sample
    return outputs, logits


def phase_serve(counter, model_kw=LLAMA_1B, max_batch=8, page_size=16,
                max_seq=2048, n_requests=16, prompt_range=(32, 512),
                new_tokens=64, n_reference=4, attention_backend="auto",
                on_chip=True):
    cfg = TransformerConfig(**model_kw)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(SEED), jnp.bfloat16)
    inference = deepspeed_tpu.init_inference(model=model, params=params,
                                             dtype="bfloat16")
    del params
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).tolist()
               for n in rng.integers(prompt_range[0], prompt_range[1] + 1,
                                     n_requests)]

    def engine_for(backend):
        return inference.create_serving_engine(
            max_batch=max_batch, page_size=page_size, max_seq=max_seq,
            serving={"attention_backend": backend})

    engine = engine_for(attention_backend)
    check(engine.attention_impl == "pallas",
          f"attention_backend={attention_backend!r} resolved to "
          f"{engine.attention_impl!r}, not the Pallas kernel")
    shape = jax.ShapeDtypeStruct
    compiled = _compiled_with_kernel(
        "decode step (ragged paged attention)", engine._step_fn,
        engine.params, shape((max_batch, 1), jnp.int32), engine.caches,
        shape(engine.tables.shape, jnp.int32),
        shape((max_batch,), jnp.int32), shape((max_batch, 1), jnp.int32),
        on_chip=on_chip)
    memory = compiled.memory_analysis() if on_chip else None
    del compiled

    t0 = time.perf_counter()
    compiles_before = counter.compiles
    compared = range(n_reference)
    outputs, logits = _serve(engine, prompts, new_tokens, compared)
    serve_s = time.perf_counter() - t0
    compiles = counter.compiles - compiles_before
    check(sorted(outputs) == list(range(n_requests)),
          f"finished {sorted(outputs)} of {n_requests} requests")
    check(all(len(outputs[i]) == len(prompts[i]) + new_tokens
              for i in outputs), "a request finished short of its budget")
    check(engine.leak_report() == {}, f"leaks: {engine.leak_report()}")

    # The same first requests through the jnp gather engine.  Greedy
    # decoding over random weights flips on near-ties, so tokens are only
    # reported; logits are held to the tolerance wherever both engines saw
    # the same context: the prefill, and every decode step up to the
    # request's first differing token.
    reference = engine_for("jnp")
    check(reference.attention_impl == "jnp", "reference engine is not jnp")
    ref_outputs, ref_logits = _serve(reference, prompts[:n_reference],
                                     new_tokens, compared)
    check(reference.leak_report() == {},
          f"reference leaks: {reference.leak_report()}")
    prefill_err = decode_err = 0.0
    same_context = matches = 0
    for i in compared:
        got = outputs[i][len(prompts[i]):]
        want = ref_outputs[i][len(prompts[i]):]
        agree = [a == b for a, b in zip(got, want)]
        matches += sum(agree)
        shared = min(agree.index(False) + 1 if False in agree
                     else new_tokens, new_tokens)
        same_context += shared
        errors = [_error(logits[i][k], ref_logits[i][k])
                  for k in range(shared)]
        check(np.all(np.isfinite(errors)), f"non-finite logits, request {i}")
        prefill_err = max(prefill_err, errors[0])
        decode_err = max([decode_err] + errors[1:])
    emit("serve", n_params=cfg.num_params(), requests_finished=len(outputs),
         prompt_tokens=[len(p) for p in prompts], new_tokens=new_tokens,
         attention_impl=engine.attention_impl,
         attention_backend=attention_backend,
         ragged_kernel_in_step=True, leaks={},
         requests_compared=n_reference,
         prefill_logits_max_abs_error=prefill_err,
         decode_logits_max_abs_error=decode_err, tolerance=LOGIT_TOL,
         decode_steps_with_same_context=same_context - n_reference,
         greedy_token_match_rate=round(matches / (n_reference * new_tokens),
                                       4),
         serve_s_including_compiles=round(serve_s, 2),
         compilations_while_serving=compiles,
         decode_step_temp_bytes=getattr(memory, "temp_size_in_bytes", None),
         decode_step_argument_bytes=getattr(memory, "argument_size_in_bytes",
                                            None),
         page_pool_bytes=int(sum(x.nbytes for x in
                                 jax.tree_util.tree_leaves(engine.caches))))
    check(max(prefill_err, decode_err) <= LOGIT_TOL,
          f"logits differ from the jnp engine by {prefill_err} (prefill) / "
          f"{decode_err} (decode), above {LOGIT_TOL}")


# --------------------------------------------------------------------------
def _free_engines():
    groups.reset_mesh()
    gc.collect()


def phase_sharded(counter, model_kw=GPT_1B, seq=1024, micro_batch=2,
                  steps=3, large_leaf=1 << 20, on_chip=True):
    """fsdp over every device against one device: same seeded weights, same
    ``micro_batch * n_devices`` sequences (one device takes them as
    ``n_devices`` accumulation steps)."""
    devices = jax.devices()
    n_devices = len(devices)
    cfg = TransformerConfig(**model_kw, remat=True,
                            remat_policy="dots_saveable")
    model = CausalTransformerLM(cfg)
    params = jax.tree_util.tree_map(np.asarray,
                                    model.init(jax.random.key(SEED)))
    ids = _token_batch(cfg.vocab_size,
                       (n_devices, micro_batch, seq))["input_ids"]

    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        mesh=build_mesh(devices=devices[:1]),
        config=deepspeed_tpu.DeepSpeedConfig(
            _train_config(micro_batch, n_devices), world_size=1))
    one_losses, _, one_times = _train_steps(
        engine, {"input_ids": ids}, steps, counter, on_chip)
    del engine
    _free_engines()

    # the engine builds this mesh itself, from jax.devices()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config=_train_config(micro_batch, 1, mesh={"fsdp": n_devices}))
    del params
    losses, text, times = _train_steps(
        engine, {"input_ids": ids.reshape(-1, seq)}, steps, counter, on_chip)

    rel = [abs(a - b) / abs(b) for a, b in zip(losses, one_losses)]
    large = [x for x in jax.tree_util.tree_leaves(
        (engine.state.params, engine.state.opt_state)) if x.size >= large_leaf]
    spread = [len(x.sharding.device_set) for x in large]
    replicated = sum(x.sharding.is_fully_replicated for x in large)
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices] \
        if on_chip else []
    collectives = {op: text.count(f" {op}") for op in
                   ("all-gather", "reduce-scatter", "all-reduce")}
    emit("sharded", mesh={"fsdp": n_devices}, n_params=cfg.num_params(),
         losses=[round(x, 4) for x in losses],
         one_device_losses=[round(x, 4) for x in one_losses],
         loss_rel_diff=[round(x, 6) for x in rel], rtol=SHARDED_LOSS_RTOL,
         large_leaves=len(large), replicated_large_leaves=replicated,
         bytes_in_use=in_use, collectives_in_step=collectives,
         **times, one_device=one_times)
    check(max(rel) <= SHARDED_LOSS_RTOL,
          f"fsdp={n_devices} losses {losses} leave the one-device "
          f"trajectory {one_losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(large and set(spread) == {n_devices} and replicated == 0,
          f"large state leaves are not sharded over {n_devices} devices: "
          f"device counts {sorted(set(spread))}, {replicated} replicated")
    if on_chip:     # off it: no compiled text, no memory_stats
        check(collectives["all-gather"] > 0 and
              collectives["reduce-scatter"] + collectives["all-reduce"] > 0,
              f"no collectives in the compiled step: {collectives}")
        check(max(in_use) - min(in_use) <= MEMORY_BALANCE * max(in_use),
              f"uneven device memory: {in_use}")
    return losses


# --------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the fsdp=4 path and its one-device "
                         "comparison (needs the four-chip host)")
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    t0 = time.perf_counter()
    device = phase_device(args.chips)
    if args.chips == 4:
        phase_sharded(counter, GPT_1B, **GPT_1B_SHARDED)
    else:
        phase_kernels()
        phase_train(counter, GPT_1B, **GPT_1B_TRAIN)
        _free_engines()
        phase_serve(counter, LLAMA_1B, **LLAMA_1B_SERVE)
    emit("summary", wall_s=round(time.perf_counter() - t0, 1),
         compile_cache_dir=cache_dir, compilations=counter.compiles,
         compile_cache_hits=counter.cache_hits,
         compile_cache_misses=counter.cache_misses)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
