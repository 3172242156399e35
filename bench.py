"""Benchmark: training throughput of the largest GPT that fits the chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Design notes:

* A chip belongs to one process at a time, so the parent process NEVER
  touches jax: every JAX touch happens in a worker subprocess with a
  timeout.
* The headline is a device measurement or nothing: a run that finds no TPU,
  meets a ``device_kind`` missing from the peak tables, or in which no
  training attempt succeeds prints a one-line error and exits non-zero.
* Model selection: largest GPT config whose ZeRO-3 + remat footprint fits in
  measured HBM (not a fixed 125M toy).
* Reported: tokens/s/chip (headline), achieved model TFLOPs, MFU vs the
  chip's actual bf16 peak, and a measured max-params-on-one-chip probe with
  host optimizer offload (analytic estimate if the probe can't run).

Baseline anchor: the reference's headline "ZeRO-3 Offload sustains up to
50 TFLOPs/GPU" (BASELINE.md, docs/_posts/2021-03-08-zero3-offload.md:65);
``vs_baseline`` = our achieved model TFLOPs/chip / 50.
"""

import json
import os
import subprocess
import sys
import time

_T0 = time.time()
_BUDGET_S = int(os.environ.get("BENCH_BUDGET_S", "520"))


def _remaining():
    return _BUDGET_S - (time.time() - _T0)

# ---------------------------------------------------------------------------
# chip tables (bf16 dense peak per jax device; HBM per device, used where
# the backend reports no bytes_limit)
# ---------------------------------------------------------------------------
_PEAK_TFLOPS = [
    ("v6e", 918.0), ("v6 lite", 918.0), ("v6", 918.0),
    ("v5p", 459.0), ("v5e", 197.0), ("v5 lite", 197.0), ("v5", 459.0),
    ("v4", 275.0), ("v3", 61.5), ("v2", 22.5),
]
_HBM_FALLBACK = [
    ("v6", 32e9), ("v5p", 95e9), ("v5e", 16e9), ("v5 lite", 16e9),
    ("v5", 95e9), ("v4", 32e9), ("v3", 16e9), ("v2", 8e9),
]


def _lookup(table, kind, default):
    k = (kind or "").lower()
    for sub, val in table:
        if sub in k:
            return val
    return default


# Mirrors TransformerConfig.loss_chunk_size's default (the parent process
# must not import jax — see module docstring); pinned by
# tests/unit/test_model.py::test_bench_loss_chunk_matches_config.
LOSS_CHUNK_TOKENS = 4096

# GPT ladder: (name, kwargs for TransformerConfig) — GPT-2/3 family shapes.
_LADDER = [
    ("gpt_6_7b", dict(vocab_size=50304, hidden_size=4096, n_layers=32,
                      n_heads=32, max_seq_len=2048, activation="gelu",
                      use_rmsnorm=False, use_rope=False, tie_embeddings=True)),
    ("gpt_2_7b", dict(vocab_size=50304, hidden_size=2560, n_layers=32,
                      n_heads=32, max_seq_len=2048, activation="gelu",
                      use_rmsnorm=False, use_rope=False, tie_embeddings=True)),
    ("gpt2_1_5b", dict(vocab_size=50304, hidden_size=1600, n_layers=48,
                       n_heads=25, max_seq_len=1024, activation="gelu",
                       use_rmsnorm=False, use_rope=False, tie_embeddings=True)),
    ("gpt_760m", dict(vocab_size=50304, hidden_size=1536, n_layers=24,
                      n_heads=16, max_seq_len=1024, activation="gelu",
                      use_rmsnorm=False, use_rope=False, tie_embeddings=True)),
    ("gpt_350m", dict(vocab_size=50304, hidden_size=1024, n_layers=24,
                      n_heads=16, max_seq_len=1024, activation="gelu",
                      use_rmsnorm=False, use_rope=False, tie_embeddings=True)),
    ("gpt2_125m", dict(vocab_size=50304, hidden_size=768, n_layers=12,
                       n_heads=12, max_seq_len=1024, activation="gelu",
                       use_rmsnorm=False, use_rope=False, tie_embeddings=True)),
]


def _n_params(kw):
    d, v, L = kw["hidden_size"], kw["vocab_size"], kw["n_layers"]
    f = 4 * d
    per_layer = 4 * d * d + 2 * d * f + 2 * d
    return L * per_layer + v * d + d + kw["max_seq_len"] * d


def _footprint(kw, batch, seq, n_chips=1):
    """ZeRO-3 per-chip training footprint: bf16 params + bf16 grads +
    fp32 master + 2x fp32 Adam moments = 18 B/param (all sharded over the
    fsdp axis), plus remat'd activations and the streamed loss chunk.
    The fp32 [B,S,V] logits tensor no longer appears: the model's chunked
    cross-entropy (models/transformer.py chunked_next_token_xent) streams
    logits in fixed-size token chunks under a remat'd scan."""
    n = _n_params(kw)
    states = 18.0 * n / n_chips
    b = max(1.0, batch / n_chips)
    acts = 2.0 * b * seq * kw["hidden_size"] * (kw["n_layers"] + 8)
    loss_chunk = 4.0 * LOSS_CHUNK_TOKENS * kw["vocab_size"] * 2  # + bwd copy
    return states + acts + loss_chunk


# ---------------------------------------------------------------------------
# workers (run in subprocesses; each prints one JSON line on stdout)
# ---------------------------------------------------------------------------

def _worker_probe():
    import jax
    d = jax.devices()[0]
    hbm = 0
    try:
        stats = d.memory_stats() or {}
        hbm = int(stats.get("bytes_limit", 0))
    except Exception:
        pass
    print(json.dumps({
        "platform": d.platform,
        "kind": getattr(d, "device_kind", ""),
        "n_devices": len(jax.devices()),
        "hbm": hbm,
    }))


def _worker_train(spec):
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    import jax

    cfg = TransformerConfig(**spec["model"], remat=spec["remat"],
                            remat_policy=spec.get("remat_policy",
                                                  "dots_saveable"))
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))

    gas = int(spec.get("gas", 1))
    opt_params = {"lr": 1e-4, "weight_decay": 0.0}
    if spec.get("moment_dtype"):
        opt_params["moment_dtype"] = spec["moment_dtype"]
    ds_config = {
        "train_micro_batch_size_per_gpu": spec["batch"],
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": opt_params},
        "bf16": {"enabled": True},
        "zero_optimization": dict(spec.get("zero", {"stage": 3})),
    }
    if spec.get("grad_accum_dtype"):
        ds_config["data_types"] = {
            "grad_accum_dtype": spec["grad_accum_dtype"]}
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds_config)
    del params

    rng = np.random.default_rng(0)
    batch, seq, steps = spec["batch"], spec["seq"], spec["steps"]

    def make_batch():
        shape = (gas, batch, seq) if gas > 1 else (batch, seq)
        return {"input_ids": rng.integers(0, cfg.vocab_size, shape)}

    engine.train_batch(batch=make_batch())       # compile + warmup
    jax.block_until_ready(engine.state.params)

    t0 = time.time()
    loss = None
    for _ in range(steps):
        loss = engine.train_batch(batch=make_batch())
    jax.block_until_ready(loss)
    dt = time.time() - t0

    print(json.dumps({
        "tokens_per_sec": gas * batch * seq * steps / dt,
        "n_params": cfg.num_params(),
        "loss": float(loss),
        "dt": dt,
    }))


def _worker_params_probe(spec):
    """One param-stream (training-time parameter offload) train step at the
    requested size; success means the model is trainable on this chip.
    The full tree never enters HBM: init runs on the HOST backend and the
    step streams a double-buffered per-layer working set."""
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    import jax
    import jax.numpy as jnp

    cfg = TransformerConfig(**spec["model"], remat=True)
    model = CausalTransformerLM(cfg)
    with jax.default_device(jax.devices("cpu")[0]):
        params = model.init(jax.random.key(0), dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(np.asarray, params)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "data_types": {"grad_accum_dtype": "bfloat16"},
            "zero_optimization": {
                "stage": 0,
                "offload_param": {"device": "cpu", "buffer_count": 2},
                "offload_optimizer": {"device": "cpu"},
            },
        })
    del params
    rng = np.random.default_rng(0)
    loss = engine.train_batch(
        batch={"input_ids": rng.integers(0, cfg.vocab_size, (1, spec["seq"]))})
    jax.block_until_ready(loss)
    print(json.dumps({"ok": bool(np.isfinite(float(loss))),
                      "n_params": cfg.num_params(),
                      "via": "param_stream"}))


def _dispatch_bench(spec=None):
    """CPU-runnable async-step-pipeline micro-bench (returns a dict so tests
    can call it in-process; the ``dispatch`` worker prints it).

    Measures steps/sec of a small jitted train loop fed by a generator with
    ``feed_delay_ms`` of injected host latency per batch, twice with
    telemetry enabled: (A) the synchronous baseline — inline feed plus a
    per-step metric readback (``sync_interval`` 1), so each step pays
    feed + compute; (B) the async pipeline — prefetch worker + deferred
    readback, so each step pays max(feed, compute).  This is the stall the
    tentpole removes, measurable with no TPU attached."""
    spec = spec or {}
    import copy
    import tempfile

    import numpy as np

    import deepspeed_tpu
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.monitor.telemetry import get_telemetry

    hidden = int(spec.get("hidden", 512))
    batch = int(spec.get("batch", 64))
    steps = int(spec.get("steps", 25))
    warmup = int(spec.get("warmup", 3))
    delay_ms = float(spec.get("feed_delay_ms", 10.0))
    depth = int(spec.get("prefetch_depth", 4))
    interval = int(spec.get("sync_interval", 8))

    def loss_fn(params, b, rng):
        h = b["x"]
        for w in params["w"]:
            h = jnp.tanh(h @ w)
        return jnp.mean((h - b["y"]) ** 2)

    prng = np.random.default_rng(0)
    params0 = {"w": [prng.standard_normal((hidden, hidden))
                     .astype(np.float32) * 0.05 for _ in range(4)]}

    def make_feed(n):
        r = np.random.default_rng(1)
        for _ in range(n):
            time.sleep(delay_ms / 1000.0)
            yield {"x": r.standard_normal((batch, hidden)).astype(np.float32),
                   "y": r.standard_normal((batch, hidden)).astype(np.float32)}

    def run(async_on):
        tmp = tempfile.mkdtemp(prefix="dispatch_bench_")
        cfg = {
            "train_micro_batch_size_per_gpu": batch,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "telemetry": {"enabled": True, "output_path": tmp,
                          "stall_watchdog": False, "hbm_gauges": False},
        }
        if async_on:
            cfg["async_pipeline"] = {"enabled": True,
                                     "prefetch_depth": depth,
                                     "sync_interval": interval}
        engine, *_ = deepspeed_tpu.initialize(
            model=loss_fn, model_parameters=copy.deepcopy(params0),
            config=cfg)
        feed = make_feed(steps + warmup)
        for _ in range(warmup):
            engine.train_batch(data_iter=feed)
        jax.block_until_ready(engine.state.params)
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = engine.train_batch(data_iter=feed)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        engine.flush_telemetry()
        get_telemetry().close()
        return steps / dt

    sync_sps = run(False)
    prefetch_sps = run(True)
    return {
        "steps_per_sec_sync": round(sync_sps, 2),
        "steps_per_sec_prefetch": round(prefetch_sps, 2),
        "prefetch_speedup": round(prefetch_sps / max(sync_sps, 1e-9), 3),
        "injected_feed_ms": delay_ms,
        "sync_interval": interval,
        "prefetch_depth": depth,
    }


def _worker_dispatch(spec):
    print(json.dumps(_dispatch_bench(spec)))


def _serving_bench(spec=None):
    """CPU-runnable serving-overload micro-bench (returns a dict so tests
    can call it in-process; the ``serving`` worker prints it).

    Drives the continuous-batching engine at an offered load well above
    capacity (``arrivals_per_step`` new requests per decode step against a
    small batch) with a bounded queue and the shed-oldest policy, and
    measures what the hardening layer is FOR: the shed rate under overload
    and the served-step latency tail (p50/p99) — plus a drive-by leak
    audit, which must come back empty."""
    spec = spec or {}
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.robustness import RequestRejected
    from deepspeed_tpu.inference.serving import ServingEngine
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig

    n_requests = int(spec.get("requests", 48))
    arrivals = int(spec.get("arrivals_per_step", 3))
    max_new = int(spec.get("max_new_tokens", 8))
    warmup_steps = int(spec.get("warmup_steps", 3))
    policy = spec.get("policy", "shed-oldest")

    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    tmp = tempfile.mkdtemp(prefix="serving_bench_")
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": tmp,
                         "job_name": "serving_bench"}), rank=0)
    eng = ServingEngine(
        model, params, max_batch=4, page_size=8, max_seq=64,
        dtype=jnp.float32, telemetry=tel,
        serving={"max_queue": int(spec.get("max_queue", 8)),
                 "overload_policy": policy,
                 "queue_high_watermark": 6, "queue_low_watermark": 2})
    rng = np.random.default_rng(0)
    # prompt lengths 3..7 share one prefill bucket (8), so the latency
    # tail measures scheduling, not a late XLA compile of a new shape
    prompts = [rng.integers(0, cfg.vocab_size, (int(n),)).tolist()
               for n in rng.integers(3, 8, n_requests)]
    rejected = 0
    step_ms = []
    finished = {}
    next_req, si = 0, 0
    while next_req < n_requests or eng.queue or eng.n_active:
        for _ in range(arrivals):
            if next_req >= n_requests:
                break
            try:
                eng.add_request(next_req, prompts[next_req],
                                max_new_tokens=max_new)
            except RequestRejected:
                rejected += 1
            next_req += 1
        t0 = time.perf_counter()
        finished.update(eng.step())
        dt = (time.perf_counter() - t0) * 1000.0
        if si >= warmup_steps:
            step_ms.append(dt)
        si += 1
    health = eng.health()
    tel.close()
    vals = sorted(step_ms) or [0.0]

    def pct(q):
        return vals[min(len(vals) - 1,
                        max(0, int(round(q / 100.0 * (len(vals) - 1)))))]

    shed = eng.stats["shed"]
    return {
        "offered_requests": n_requests,
        "served": eng.stats["finished"],
        "shed": shed,
        "rejected": rejected,
        "shed_rate": round((shed + rejected) / max(1, n_requests), 3),
        "step_p50_ms": round(pct(50), 2),
        "step_p99_ms": round(pct(99), 2),
        "steps": si,
        "policy": policy,
        "leaks": eng.leak_report(),
        "oldest_request_age_s": health["oldest_request_age_s"],
    }


def _worker_serving(spec):
    print(json.dumps(_serving_bench(spec)))


def _serving_prefix_bench(spec=None):
    """CPU-runnable prefix-cache micro-bench: a repeated shared-prompt
    workload (one long system prefix, distinct short suffixes — the agent
    / few-shot serving shape) served twice, cache off then on.  Reports
    the page-level hit rate, fresh pages allocated, and prompt tokens
    actually prefilled under each mode — and asserts the whole point:
    outputs are BIT-IDENTICAL, so the cache is purely a latency/FLOPs
    optimisation, never a quality knob."""
    spec = spec or {}
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.serving import ServingEngine
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig

    n_requests = int(spec.get("requests", 12))
    shared_len = int(spec.get("shared_prefix_tokens", 48))
    max_new = int(spec.get("max_new_tokens", 4))

    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, (shared_len,)).tolist()
    prompts = [shared + rng.integers(0, cfg.vocab_size, (int(n),)).tolist()
               for n in rng.integers(4, 9, n_requests)]

    def run(enabled):
        tmp = tempfile.mkdtemp(prefix="prefix_bench_")
        tel = Telemetry().configure(
            TelemetryConfig({"enabled": True, "output_path": tmp,
                             "job_name": "prefix_bench"}), rank=0)
        eng = ServingEngine(
            model, params, max_batch=4, page_size=8, max_seq=128,
            dtype=jnp.float32, telemetry=tel,
            serving={"prefix_cache": {"enabled": enabled}})
        t0 = time.perf_counter()
        outs = eng.generate(prompts, max_new_tokens=max_new)
        wall = time.perf_counter() - t0
        eng.health()   # push the serve/prefix_* gauges before close
        leaks = eng.leak_report()
        tel.close()
        prefilled = sum(len(p) for p in prompts)
        snap = {}
        if eng.prefix_cache is not None:
            snap = eng.prefix_cache.snapshot()
            prefilled -= snap["tokens_reused"]
        return {"outs": outs, "wall_s": wall, "leaks": leaks,
                "pages_allocated": eng.alloc.pages_taken,
                "prompt_tokens_prefilled": prefilled, "cache": snap}

    off = run(False)
    on = run(True)
    return {
        "requests": n_requests,
        "shared_prefix_tokens": shared_len,
        "bit_identical": on["outs"] == off["outs"],
        "prefix_hit_rate": on["cache"]["hit_rate"],
        "pages_reused": on["cache"]["pages_reused"],
        "tokens_reused": on["cache"]["tokens_reused"],
        "cow_copies": on["cache"]["cow_copies"],
        "pages_allocated_off": off["pages_allocated"],
        "pages_allocated_on": on["pages_allocated"],
        "prompt_tokens_prefilled_off": off["prompt_tokens_prefilled"],
        "prompt_tokens_prefilled_on": on["prompt_tokens_prefilled"],
        "wall_s_off": round(off["wall_s"], 3),
        "wall_s_on": round(on["wall_s"], 3),
        "leaks_off": off["leaks"],
        "leaks_on": on["leaks"],
    }


def _worker_serving_prefix(spec):
    print(json.dumps(_serving_prefix_bench(spec)))


def _fleet_bench(spec=None):
    """CPU-runnable fleet micro-bench: a shared-prefix workload (several
    prompt families, distinct suffixes) served by one replica and by a
    fleet, then again with a mid-flight injected ``replica_kill``.
    Reports aggregate decode throughput at each replica count (the
    scaling claim), per-replica prefix-cache hit rates (the affinity
    claim — fleet routing must keep them at single-engine levels), and
    the kill run's recovery cost (extra wall/steps over the no-fault
    fleet run) with zero lost requests."""
    spec = spec or {}
    import numpy as np

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.fleet import FleetRouter
    from deepspeed_tpu.inference.serving import ServingEngine
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    from deepspeed_tpu.runtime.resilience import FaultInjector

    n_replicas = int(spec.get("replicas", 3))
    n_requests = int(spec.get("requests", 18))
    max_new = int(spec.get("max_new_tokens", 6))
    prefix_len = int(spec.get("shared_prefix_tokens", 24))

    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    families = [rng.integers(0, cfg.vocab_size, (prefix_len,)).tolist()
                for _ in range(2 * n_replicas)]
    prompts = {
        f"q{i}": families[i % len(families)] +
        rng.integers(0, cfg.vocab_size, (4,)).tolist()
        for i in range(n_requests)}

    def factory(rid, epoch):
        return ServingEngine(
            model, params, max_batch=4, page_size=8, max_seq=128,
            dtype=jnp.float32, replica_epoch=epoch,
            serving={"prefix_cache": {"enabled": True}})

    def run(replicas, injector=None, health_interval=2):
        fleet = FleetRouter(
            factory,
            fleet={"replicas": replicas, "max_replicas": replicas + 1,
                   "health_interval": health_interval},
            injector=injector)
        # warm each engine's jit caches off the clock so the timed phase
        # measures serving, not per-replica compilation
        for rep in fleet.replicas.values():
            rep.engine.generate([prompts["q0"]], max_new_tokens=2)
        t0 = time.perf_counter()
        for rid, p in prompts.items():
            fleet.submit(rid, p, max_new_tokens=max_new)
        done = fleet.join(max_steps=2000)
        wall = time.perf_counter() - t0
        generated = sum(len(toks) - len(prompts[rid])
                        for rid, toks in done.items())
        hit_rates = [
            r["prefix_hit_rate"]
            for r in fleet.health()["replicas"].values()
            if r["prefix_hit_rate"] is not None and r["state"] == "healthy"]
        return {"fleet": fleet, "done": done, "wall_s": wall,
                "generated": generated,
                # replicas are parallel fault domains on real hardware but
                # step serially in this single process, so the scaling
                # claim is tokens per FLEET step (one round across all
                # replicas), not wall-clock
                "tokens_per_step": generated / max(fleet.steps, 1),
                "hit_rates": hit_rates, "steps": fleet.steps,
                "leaks": fleet.leak_report()}

    r1 = run(1)
    rn = run(n_replicas)
    kill = run(n_replicas, injector=FaultInjector(
        {"replica_kill": {"fail_at": [1], "msg": "bench chaos"}}))
    st = kill["fleet"].stats
    lost = st["submitted"] - st["finished"] - st["terminated"]
    return {
        "replicas": n_replicas,
        "requests": n_requests,
        "agg_tokens_per_step_single": round(r1["tokens_per_step"], 3),
        "agg_tokens_per_step_fleet": round(rn["tokens_per_step"], 3),
        "throughput_scale_frac": round(
            rn["tokens_per_step"] / max(r1["tokens_per_step"], 1e-9), 3),
        "prefix_hit_rate_single": r1["hit_rates"][0] if r1["hit_rates"]
        else 0.0,
        "prefix_hit_rate_fleet_min": min(rn["hit_rates"], default=0.0),
        "bit_identical": rn["done"] == r1["done"],
        "kill_bit_identical": kill["done"] == r1["done"],
        "kill_extra_wall_s": round(kill["wall_s"] - rn["wall_s"], 3),
        "kill_recovery_steps": kill["steps"] - rn["steps"],
        "kills": kill["fleet"].stats["kills"],
        "redispatches": kill["fleet"].stats["redispatches"],
        "respawns": kill["fleet"].stats["respawns"],
        "lost_requests": lost,
        "leaks_fleet": rn["leaks"],
        "leaks_kill": kill["leaks"],
    }


def _worker_fleet(spec):
    print(json.dumps(_fleet_bench(spec)))


def _fleet_disagg_bench(spec=None):
    """CPU-runnable disaggregated-fleet micro-bench: a mixed workload of
    long-prefill requests and short shared-prefix chat requests served
    once by a unified fleet and once by a prefill/decode-specialised
    fleet (transactional KV-page migration).  Reports chat TTFT p50/p99
    under each mode — the interference claim: long prefills on a
    dedicated pool must not sit in front of chat first tokens — plus the
    migration ledger (pages moved vs dedup-skipped, bytes saved by the
    content-addressed transport) and the zero-loss/bit-identity checks.
    Replicas step serially in this single process, so TTFT deltas are
    scheduling-order effects, not parallel-hardware speedups; the
    transferable numbers are the page/byte counts and the invariants."""
    spec = spec or {}
    import numpy as np

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.fleet import FleetRouter
    from deepspeed_tpu.inference.serving import ServingEngine
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)

    n_chat = int(spec.get("chat_requests", 12))
    n_long = int(spec.get("long_requests", 4))
    max_new = int(spec.get("max_new_tokens", 6))
    n_families = int(spec.get("chat_families", 3))

    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(7)
    # chat: 3-page shared prefixes so sibling migrations dedup; long: one
    # 96-token prefill that monopolises a step's prefill capacity
    families = [rng.integers(0, cfg.vocab_size, (24,)).tolist()
                for _ in range(n_families)]
    long_prefix = rng.integers(0, cfg.vocab_size, (96,)).tolist()
    prompts, kinds = {}, {}
    for i in range(n_chat):
        prompts[f"c{i}"] = families[i % n_families] + \
            rng.integers(0, cfg.vocab_size, (4,)).tolist()
        kinds[f"c{i}"] = "chat"
    for i in range(n_long):
        prompts[f"l{i}"] = long_prefix + \
            rng.integers(0, cfg.vocab_size, (8,)).tolist()
        kinds[f"l{i}"] = "long"

    def factory(rid, epoch):
        return ServingEngine(
            model, params, max_batch=4, page_size=8, max_seq=128,
            dtype=jnp.float32, replica_epoch=epoch,
            serving={"prefix_cache": {"enabled": True}})

    def run(fleet_cfg):
        fleet = FleetRouter(factory, fleet=dict(fleet_cfg))
        for rep in fleet.replicas.values():
            rep.engine.generate([prompts["c0"]], max_new_tokens=2)
        t_submit = {}
        t0 = time.perf_counter()
        for rid, p in prompts.items():
            # timestamp BEFORE submit: admission prefills inline when a
            # slot is free, so the first token can arrive during the call
            t_submit[rid] = time.monotonic()
            fleet.submit(rid, p, max_new_tokens=max_new,
                         temperature=0.7, seed=13)
        done = fleet.join(max_steps=4000)
        wall = time.perf_counter() - t0
        # fleet-level TTFT: submit instant (recorded above) to the first
        # engine-side first-token instant for that request.  Migrated
        # requests trace on both source and target engines — the min
        # picks the prefill-side sample, the true first token.
        first = {}
        for rep in fleet.replicas.values():
            traces = list(rep.engine.tracer.completed) + \
                list(rep.engine.tracer.open.values())
            for tr in traces:
                rid = str(tr.req_id).split(":", 1)[-1]
                if tr.t_first_token >= 0 and rid in t_submit:
                    prev = first.get(rid)
                    first[rid] = tr.t_first_token if prev is None \
                        else min(prev, tr.t_first_token)
        ttft_ms = {rid: (t - t_submit[rid]) * 1000.0
                   for rid, t in first.items()}
        chat = sorted(v for rid, v in ttft_ms.items()
                      if kinds[rid] == "chat")

        def pct(q):
            if not chat:
                return 0.0
            return chat[min(len(chat) - 1, int(q * (len(chat) - 1) + 0.5))]

        st = fleet.stats
        return {"fleet": fleet, "done": done, "wall_s": wall,
                "chat_ttft_p50_ms": pct(0.50), "chat_ttft_p99_ms": pct(0.99),
                "lost": st["submitted"] - st["finished"] - st["terminated"],
                "leaks": fleet.leak_report()}

    uni = run({"replicas": 3, "max_replicas": 4})
    dis = run({"roles": {"enabled": True, "prefill_replicas": 1,
                         "decode_replicas": 2}})
    st = dis["fleet"].stats
    return {
        "chat_requests": n_chat,
        "long_requests": n_long,
        "chat_ttft_p50_ms_unified": round(uni["chat_ttft_p50_ms"], 3),
        "chat_ttft_p99_ms_unified": round(uni["chat_ttft_p99_ms"], 3),
        "chat_ttft_p50_ms_disagg": round(dis["chat_ttft_p50_ms"], 3),
        "chat_ttft_p99_ms_disagg": round(dis["chat_ttft_p99_ms"], 3),
        "wall_s_unified": round(uni["wall_s"], 3),
        "wall_s_disagg": round(dis["wall_s"], 3),
        "migrations": st["migrations"],
        "migrated_pages": st["migrated_pages"],
        "dedup_skipped_pages": st["dedup_skipped_pages"],
        "migrate_bytes": st["migrate_bytes"],
        "migrate_bytes_saved": st["migrate_bytes_saved"],
        "local_prefills": st["local_prefills"],
        "bit_identical": dis["done"] == uni["done"],
        "lost_requests_unified": uni["lost"],
        "lost_requests_disagg": dis["lost"],
        "leaks_unified": uni["leaks"],
        "leaks_disagg": dis["leaks"],
    }


def _worker_fleet_disagg(spec):
    print(json.dumps(_fleet_disagg_bench(spec)))


def _fleet_xproc_bench(spec=None):
    """CPU-runnable cross-process-fleet micro-bench: the same workload
    served by an in-process fleet and by a fleet of real worker
    processes over the socket transport, then again with a real
    ``kill -9`` of one worker mid-decode.  Reports tokens per fleet
    step on both sides of the process boundary (the transport-overhead
    claim), the kill run's recovery latency (SIGKILL to respawned
    replica), and zero lost requests with survivors bit-identical to
    the no-kill run (the robustness claim)."""
    spec = spec or {}
    import os
    import signal

    from deepspeed_tpu.inference.fleet import FleetRouter
    from deepspeed_tpu.inference.fleet_worker import tiny_engine_factory

    n_replicas = int(spec.get("replicas", 2))
    n_requests = int(spec.get("requests", 8))
    max_new = int(spec.get("max_new_tokens", 6))
    worker_spec = {
        "factory":
        "deepspeed_tpu.inference.fleet_worker:tiny_engine_factory",
        "kwargs": {}}
    xproc = {"mode": "subprocess", "heartbeat_interval_s": 0.2,
             "heartbeat_deadline_s": 10.0}
    prompts = {f"q{i}": [1 + i, 2 + i, 3 + i, 4 + i]
               for i in range(n_requests)}

    def run(factory, transport=None, kill_rid=None):
        fleet_cfg = {"replicas": n_replicas,
                     "max_replicas": n_replicas + 1, "health_interval": 4}
        if transport:
            fleet_cfg["transport"] = dict(transport)
        router = FleetRouter(factory, fleet=fleet_cfg)
        try:
            # warm every engine's jit caches off the clock so the timed
            # phase measures serving + transport, not compilation
            for rep in router.replicas.values():
                rep.handle.generate([prompts["q0"]], max_new_tokens=2)
            t0 = time.perf_counter()
            for rid, p in sorted(prompts.items()):
                router.submit(rid, p, max_new_tokens=max_new,
                              temperature=0.7, seed=11)
            killed_at = recovery_s = None
            respawns0 = router.stats["respawns"]
            for step in range(600):
                if kill_rid and step == 3 and killed_at is None:
                    os.kill(router.replicas[kill_rid].handle.proc.pid,
                            signal.SIGKILL)
                    killed_at = time.perf_counter()
                router.step()
                if killed_at is not None and recovery_s is None and \
                        router.stats["respawns"] > respawns0:
                    recovery_s = time.perf_counter() - killed_at
                if not router._unresolved():
                    break
            wall = time.perf_counter() - t0
            done = dict(router.finished)
            term = router.pop_terminated()
            generated = sum(len(toks) - len(prompts[rid])
                            for rid, toks in done.items())
            st = router.stats
            return {"done": done, "term": term, "wall_s": wall,
                    "tokens_per_step": generated / max(router.steps, 1),
                    "steps": router.steps, "recovery_s": recovery_s,
                    "lost": (st["submitted"] - st["finished"] -
                             st["terminated"]),
                    "workers_lost": st["workers_lost"],
                    "respawns": st["respawns"],
                    "leaks": router.leak_report()}
        finally:
            router.close()

    inp = run(tiny_engine_factory)
    xp = run(worker_spec, transport=xproc)
    kill = run(worker_spec, transport=xproc, kill_rid="r0")
    survivors_identical = all(kill["done"][rid] == inp["done"][rid]
                              for rid in kill["done"])
    return {
        "replicas": n_replicas,
        "requests": n_requests,
        "agg_tokens_per_step_inproc": round(inp["tokens_per_step"], 3),
        "agg_tokens_per_step_xproc": round(xp["tokens_per_step"], 3),
        "transport_wall_overhead_frac": round(
            xp["wall_s"] / max(inp["wall_s"], 1e-9) - 1.0, 3),
        "bit_identical_xproc": xp["done"] == inp["done"],
        "kill_recovery_s": round(kill["recovery_s"] or 0.0, 3),
        "kill_extra_wall_s": round(kill["wall_s"] - xp["wall_s"], 3),
        "kill_extra_steps": kill["steps"] - xp["steps"],
        "workers_lost": kill["workers_lost"],
        "respawns": kill["respawns"],
        "survivors_bit_identical": survivors_identical,
        "lost_requests": (inp["lost"] + xp["lost"] + kill["lost"] +
                          len(xp["term"]) + len(inp["term"])),
        "leaks_xproc": xp["leaks"],
        "leaks_kill": kill["leaks"],
    }


def _worker_fleet_xproc(spec):
    print(json.dumps(_fleet_xproc_bench(spec)))


def _fleet_chaos_bench(spec=None):
    """CPU-runnable chaos-recovery micro-bench: replays the gate-10
    wire-fault scenarios (lost add_request ack, slow worker tripping the
    circuit breaker, torn commit_import ack) over a real 2-worker
    subprocess fleet via scripts/ds_chaos.py and reports per-scenario
    recovery wall time plus the retry / breaker / dedup counters.  Every
    scenario asserts the hard bar before returning: zero lost requests,
    one typed terminal per request, empty leak report, survivors
    bit-identical to a no-fault in-process reference, and checker-valid
    telemetry — so a green number here is also a correctness proof."""
    spec = spec or {}
    import importlib.util
    repo = os.path.dirname(os.path.abspath(__file__))
    sp = importlib.util.spec_from_file_location(
        "ds_chaos", os.path.join(repo, "scripts", "ds_chaos.py"))
    chaos = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(chaos)

    seed = int(spec.get("seed", 0))
    names = list(spec.get("scenarios") or
                 ("ack_loss", "slow_worker", "torn_commit"))
    out = {"seed": seed, "scenarios": len(names), "lost_requests": 0}
    totals = {"retries": 0, "rpc_timeouts": 0, "breaker_opens": 0,
              "breaker_closes": 0, "dup_calls_dropped": 0,
              "workers_lost": 0, "respawns": 0}
    for name in names:
        res = chaos.run_scenario(name, seed=seed)
        st = res["stats"]
        out[f"{name}_elapsed_s"] = round(res["elapsed_s"], 3)
        out["lost_requests"] += (st["submitted"] - st["finished"] -
                                 st["terminated"])
        for k in totals:
            totals[k] += st[k]
        if name == "slow_worker":
            opened = [e for e in res["events"]
                      if e.get("name") == "fleet/breaker_open"]
            closed = [e for e in res["events"]
                      if e.get("name") == "fleet/breaker_close"]
            if opened and closed:
                out["breaker_open_to_close_s"] = round(
                    closed[0]["ts"] - opened[0]["ts"], 3)
    for k, v in totals.items():
        out[f"{k}_total"] = v
    return out


def _worker_fleet_chaos(spec):
    print(json.dumps(_fleet_chaos_bench(spec)))


def _serving_attn_bench(spec=None):
    """CPU-runnable serving-attention micro-bench: the jnp gather path vs
    the fused ragged Pallas kernel (interpret mode) on ONE mixed
    prefill+decode batch over a shared paged pool.

    The gather path is how the engine served before the ragged kernel:
    host-side regrouping into per-prefill rectangular calls plus one
    batched decode call, each materialising a max_pages-padded [Hkv, S, D]
    view per sequence.  The ragged kernel serves the whole mix in one
    launch reading pages in place.  Interpret-mode wall time is NOT a TPU
    number (the interpreter is orders slower) — the transferable outputs
    are the equivalence check and the analytic bytes-moved-per-decoded-
    token roofline (docs/mfu_ceiling.md §5), recorded for the next
    on-chip round.  Also drives a tiny engine + ``serve/attn`` spans
    through one telemetry stream and reports
    ``ds_telemetry_report.serving_attention`` — proving attention's share
    of serve-step time is measurable from the frozen stream."""
    spec = spec or {}
    import importlib.util
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.serving import ServingEngine
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.ops.paged_attention import (PagedAllocator,
                                                   PagedKVCache,
                                                   paged_decode_attention)
    from deepspeed_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention
    from deepspeed_tpu.runtime.config import TelemetryConfig

    H, HKV, D, PAGE = 4, 2, 16, 16
    NPAGES = 64
    prefill_lens = list(spec.get("prefill_lens", [24, 17]))
    decode_ctx = list(spec.get("decode_ctx", [40, 33]))
    iters = int(spec.get("iters", 5))

    rng = np.random.default_rng(0)
    q_lens = prefill_lens + [1] * len(decode_ctx)
    ctx_lens = prefill_lens + decode_ctx
    alloc = PagedAllocator(NPAGES, PAGE, max_pages_per_seq=8,
                           reserve_scratch=True)
    for s, c in enumerate(ctx_lens):
        alloc.allocate(s, c)
    tables = jnp.asarray(alloc.block_table(list(range(len(ctx_lens)))))
    kp = jnp.asarray(rng.standard_normal((NPAGES, HKV, PAGE, D)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NPAGES, HKV, PAGE, D)),
                     jnp.float32)
    cache = PagedKVCache(kp, vp)
    q = jnp.asarray(rng.standard_normal((sum(q_lens), H, D)), jnp.float32)
    ctx = jnp.asarray(ctx_lens, jnp.int32)

    tmp = tempfile.mkdtemp(prefix="serving_attn_bench_")
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": tmp,
                         "job_name": "serving_attn_bench"}), rank=0)

    def gather_mixed():
        """Pre-kernel serving shape: one rectangular jnp call per prefill
        plus one batched call for the decodes."""
        outs, off = [], 0
        for s, ql in enumerate(prefill_lens):
            outs.append(paged_decode_attention(
                q[off:off + ql][None], cache, tables[s:s + 1],
                ctx[s:s + 1], impl="jnp")[0])
            off += ql
        nd = len(decode_ctx)
        dec = paged_decode_attention(
            q[off:].reshape(nd, 1, H, D), cache,
            tables[len(prefill_lens):], ctx[len(prefill_lens):],
            impl="jnp")
        outs.append(dec.reshape(nd, H, D))
        return jnp.concatenate(outs, axis=0)

    def fused_mixed():
        return ragged_paged_attention(q, kp, vp, tables, ctx, q_lens,
                                      interpret=True)

    def timed(fn, label):
        fn().block_until_ready()   # warmup/compile outside the timing
        best = float("inf")
        for _ in range(iters):
            with tel.span("serve/attn", attrs={"backend": label}):
                t0 = time.perf_counter()
                fn().block_until_ready()
                best = min(best, time.perf_counter() - t0)
        return best * 1000.0

    gather_ms = timed(gather_mixed, "jnp")
    fused_ms = timed(fused_mixed, "pallas-interpret")
    err = float(jnp.max(jnp.abs(gather_mixed() - fused_mixed())))

    # analytic HBM traffic per decoded token (fp32 here; ratio is
    # dtype-free): the gather path materialises the max_pages-padded K
    # and V views and reads them again through the softmax/PV einsums
    # (~3 passes), the fused kernel streams each sequence's true context
    # once.  docs/mfu_ceiling.md §5 carries the decomposition.
    bpe = 4
    S_pad = int(tables.shape[1]) * PAGE
    gather_bytes = 3 * 2 * S_pad * HKV * D * bpe
    mean_ctx = sum(decode_ctx) / len(decode_ctx)
    fused_bytes = 2 * mean_ctx * HKV * D * bpe
    # drive a tiny engine through the same stream so serve/backend +
    # serve/step land next to the serve/attn spans
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    eng = ServingEngine(model, params, max_batch=2, page_size=8,
                        max_seq=64, dtype=jnp.float32, telemetry=tel,
                        serving={"attention_backend": "jnp"})
    eng.generate([[1, 2, 3, 4, 5], [7, 8, 9]], max_new_tokens=3)
    leaks = eng.leak_report()
    tel.close()

    # attention's share of serve-step time, read back the way an operator
    # would: through ds_telemetry_report's serving_attention summary
    repo = os.path.dirname(os.path.abspath(__file__))
    rp = os.path.join(repo, "scripts", "ds_telemetry_report.py")
    sp = importlib.util.spec_from_file_location("ds_telemetry_report", rp)
    report = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(report)
    files = report.discover_files(os.path.join(tmp, "serving_attn_bench"))
    summary = report.summarize(report.aggregate(report.load_events(files)))

    return {
        "q_lens": q_lens,
        "ctx_lens": ctx_lens,
        "gather_jnp_ms": round(gather_ms, 3),
        "ragged_interpret_ms": round(fused_ms, 3),
        "max_abs_diff": err,
        "equivalent": err < 2e-5,
        "gather_bytes_per_decoded_token": gather_bytes,
        "fused_bytes_per_decoded_token": int(fused_bytes),
        "analytic_bytes_ratio": round(gather_bytes / fused_bytes, 1),
        "serving_attention_report": summary.get("serving_attention"),
        "leaks": leaks,
        "note": "interpret-mode wall time is not a TPU number; the "
                "equivalence + analytic roofline are the transferable "
                "outputs for the next on-chip round",
    }


def _worker_serving_attn(spec):
    print(json.dumps(_serving_attn_bench(spec)))


def _serving_slo_bench(spec=None):
    """CPU-runnable serving-SLO micro-bench: a mixed short/long-prompt
    workload (interactive vs batch shapes) with per-request deadlines,
    reporting the observability plane's own numbers — TTFT / TPOT / e2e /
    queue-wait p50/p99 from the registry histograms, SLO attainment and
    goodput from the deadline verdicts — plus a live scrape of the
    pull-based exporter (ephemeral port), validated against the
    Prometheus-exposition checker.  Wall-clock numbers are CPU numbers;
    the transferable outputs are the trace-completeness audit and the
    scrape-path proof."""
    spec = spec or {}
    import importlib.util
    import tempfile
    import urllib.request

    import numpy as np

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.robustness import RequestRejected
    from deepspeed_tpu.inference.serving import ServingEngine
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig

    n_requests = int(spec.get("requests", 24))
    arrivals = int(spec.get("arrivals_per_step", 2))
    max_new = int(spec.get("max_new_tokens", 6))
    deadline_s = float(spec.get("deadline_s", 60.0))

    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    tmp = tempfile.mkdtemp(prefix="serving_slo_bench_")
    tel = Telemetry().configure(
        TelemetryConfig({"enabled": True, "output_path": tmp,
                         "job_name": "serving_slo_bench",
                         "export": {"enabled": True, "port": 0}}), rank=0)
    eng = ServingEngine(
        model, params, max_batch=4, page_size=8, max_seq=64,
        dtype=jnp.float32, telemetry=tel,
        serving={"max_queue": int(spec.get("max_queue", 12)),
                 "overload_policy": "shed-oldest"})
    rng = np.random.default_rng(0)
    # interactive (short) vs batch (long) prompt mix; both classes carry
    # a deadline so every terminal yields an SLO verdict
    prompts = []
    for i in range(n_requests):
        n = int(rng.integers(3, 7)) if i % 2 == 0 else \
            int(rng.integers(24, 33))
        prompts.append(rng.integers(0, cfg.vocab_size, (n,)).tolist())
    rejected = 0
    next_req = 0
    while next_req < n_requests or eng.queue or eng.n_active:
        for _ in range(arrivals):
            if next_req >= n_requests:
                break
            try:
                eng.add_request(next_req, prompts[next_req],
                                max_new_tokens=max_new,
                                deadline_s=deadline_s)
            except RequestRejected:
                rejected += 1
            next_req += 1
        eng.step()
    health = eng.health()    # populates the latency section
    leaks = eng.leak_report()

    # live scrape through the exporter, validated with the checker
    host, port = tel.exporter.address
    prom = urllib.request.urlopen(
        f"http://{host}:{port}/metrics", timeout=5).read().decode()
    repo = os.path.dirname(os.path.abspath(__file__))
    cp = os.path.join(repo, "scripts", "check_telemetry_schema.py")
    sp = importlib.util.spec_from_file_location("check_telemetry_schema",
                                                cp)
    checker = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(checker)
    prom_problems = checker.validate_prom_exposition(prom)
    tel.close()

    def pcts(name):
        s = health["latency"][name]
        return {"count": s["count"],
                "p50_ms": round(s["p50"], 3) if s["p50"] is not None
                else None,
                "p99_ms": round(s["p99"], 3) if s["p99"] is not None
                else None}

    slo = health["slo"]
    verdicts = slo["attained"] + slo["missed"]
    return {
        "offered_requests": n_requests,
        "served": eng.stats["finished"],
        "shed": eng.stats["shed"],
        "rejected": rejected,
        "ttft": pcts("serve/ttft_ms"),
        "tpot": pcts("serve/tpot_ms"),
        "e2e": pcts("serve/e2e_ms"),
        "queue_wait": pcts("serve/queue_wait_ms"),
        "slo_attained": slo["attained"],
        "slo_missed": slo["missed"],
        "slo_attainment": (round(slo["attained"] / verdicts, 3)
                           if verdicts else None),
        "goodput_tokens": slo["goodput_tokens"],
        "traces": health["traces"],
        "exporter_scrape_ok": not prom_problems and
        "ds_serve_ttft_ms" in prom,
        "leaks": leaks,
    }


def _worker_serving_slo(spec):
    print(json.dumps(_serving_slo_bench(spec)))


def _serving_sched_bench(spec=None):
    """CPU-runnable scheduler micro-bench: one mixed workload (long
    throughput-class prompts arriving alongside short latency-class chat)
    replayed through the monolithic, chunked, and chunked+speculative
    schedulers on a simulated dispatch clock — every device dispatch
    charges ``overhead + per_token * ids.size`` simulated seconds (the
    draft model at a quarter of the target's per-token rate), so the
    TTFT/interleaving numbers measure the SCHEDULING policy, not CPU
    wall-clock or compile skew.  Reports chat TTFT p99 per policy (the
    head-of-line-blocking number chunking exists to fix), decode
    tokens-per-step (the regression guard), speculative acceptance, and
    the cross-policy bit-identity verdicts — greedy outputs must match
    token-for-token across all three schedulers."""
    spec = spec or {}
    import numpy as np

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.serving import ServingEngine
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)

    n_requests = int(spec.get("requests", 18))
    max_new = int(spec.get("max_new_tokens", 16))
    long_len = int(spec.get("long_prompt_tokens", 320))
    chunk = int(spec.get("prefill_chunk_tokens", 64))
    max_chunks = int(spec.get("max_prefill_chunks_per_step", 3))
    gamma = int(spec.get("num_draft_tokens", 3))
    noise = float(spec.get("draft_noise", 3e-3))
    overhead_s = float(spec.get("dispatch_overhead_s", 5e-4))
    per_tok_s = float(spec.get("per_token_s", 5e-5))

    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    # imperfect-but-correlated proposer: the target's own weights plus
    # seeded noise — acceptance lands strictly between 0 and 1, and the
    # verify/correction path has to earn the bit-identity verdict
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    draft_params = jax.tree_util.tree_unflatten(
        treedef, [l + noise * jax.random.normal(k, l.shape, l.dtype)
                  for l, k in zip(leaves, keys)])

    rng = np.random.default_rng(0)
    prompts, classes, arrival = [], [], []
    for i in range(n_requests):
        if i % 3 == 0:      # batch job: long prompt, throughput class
            n, cls = long_len, "throughput"
        else:               # interactive chat: short prompt, latency class
            n, cls = int(rng.integers(4, 9)), "latency"
        prompts.append(rng.integers(0, cfg.vocab_size, (n,)).tolist())
        classes.append(cls)
        arrival.append(i * 3e-3)

    class SimClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

    def run(policy, speculative):
        clk = SimClock()
        sched_cfg = {"policy": policy}
        if policy == "chunked":
            sched_cfg["prefill_chunk_tokens"] = chunk
            sched_cfg["max_prefill_chunks_per_step"] = max_chunks
        if speculative:
            sched_cfg["speculative"] = {"enabled": True,
                                        "num_draft_tokens": gamma}
        eng = ServingEngine(
            model, params, max_batch=4, page_size=16, max_seq=512,
            dtype=jnp.float32, clock=clk,
            serving={"scheduler": sched_cfg},
            draft_model=model if speculative else None,
            draft_params=draft_params if speculative else None)
        real_step = eng._run_step

        def charged_step(ids, tables, lengths, phase="decode"):
            clk.t += overhead_s + per_tok_s * float(ids.size)
            return real_step(ids, tables, lengths, phase=phase)

        eng._run_step = charged_step
        if speculative:
            sched = eng.scheduler
            real_draft = sched._run_draft

            def charged_draft(ids, tables, lengths, phase):
                clk.t += overhead_s + per_tok_s / 4.0 * float(ids.size)
                return real_draft(ids, tables, lengths, phase)

            sched._run_draft = charged_draft
            real_propose = sched._propose_fn

            def charged_propose(params, caches, tables, lengths, last):
                clk.t += overhead_s + per_tok_s / 4.0 * \
                    float(last.shape[0] * (gamma + 1))
                return real_propose(params, caches, tables, lengths, last)

            sched._propose_fn = charged_propose

        outputs = {}
        next_req = 0
        while next_req < n_requests or eng.queue or eng.n_active:
            clk.t += 1e-4          # host loop tick: progress when idle
            while next_req < n_requests and \
                    arrival[next_req] <= clk.t:
                eng.add_request(next_req, prompts[next_req],
                                max_new_tokens=max_new,
                                slo_class=classes[next_req])
                next_req += 1
            for rid, toks in eng.step().items():
                outputs.setdefault(rid, []).extend(toks)
        leaks = eng.leak_report()
        stats = dict(eng.scheduler.sched_stats)
        snap = eng.scheduler.snapshot()
        chat_ttfts = sorted(
            t.ttft_ms() for t in eng.tracer.completed
            if classes[t.req_id] == "latency" and t.ttft_ms() is not None)
        return {"outputs": outputs, "leaks": leaks, "stats": stats,
                "snapshot": snap, "sim_s": round(clk.t, 4),
                "chat_ttft_p50_ms": _pct_of(chat_ttfts, 50),
                "chat_ttft_p99_ms": _pct_of(chat_ttfts, 99)}

    mono = run("monolithic", False)
    chunked = run("chunked", False)
    spec_run = run("chunked", True)

    def tok_per_step(r):
        steps = r["stats"].get("decode_steps", 0)
        return round(r["stats"].get("decode_tokens", 0) / steps, 3) \
            if steps else None

    reduction = (round(1.0 - chunked["chat_ttft_p99_ms"] /
                       mono["chat_ttft_p99_ms"], 4)
                 if mono["chat_ttft_p99_ms"] else None)
    out = {
        "requests": n_requests,
        "long_prompt_tokens": long_len,
        "prefill_chunk_tokens": chunk,
        "num_draft_tokens": gamma,
        "monolithic_chat_ttft_p99_ms": mono["chat_ttft_p99_ms"],
        "chunked_chat_ttft_p99_ms": chunked["chat_ttft_p99_ms"],
        "chunked_spec_chat_ttft_p99_ms": spec_run["chat_ttft_p99_ms"],
        "monolithic_chat_ttft_p50_ms": mono["chat_ttft_p50_ms"],
        "chunked_chat_ttft_p50_ms": chunked["chat_ttft_p50_ms"],
        # 1 - chunked/monolithic: >= 0.5 is the ">= 2x reduction" gate
        "chunked_ttft_p99_reduction_frac": reduction,
        "monolithic_decode_tokens_per_step": tok_per_step(mono),
        "chunked_decode_tokens_per_step": tok_per_step(chunked),
        "chunked_spec_decode_tokens_per_step": tok_per_step(spec_run),
        # makespan: total simulated seconds to drain the whole workload —
        # the overall-throughput guard (per-step width alone punishes
        # chunking for starting decode EARLIER, during prefill)
        # decode width under chunking relative to monolithic: prefill
        # chunks hold a slot mid-fill, so a few percent below 1.0 is the
        # expected price; the makespan rows show the overall-throughput
        # story (chunked drains the same workload FASTER)
        "chunked_decode_width_ratio_frac":
            (round(tok_per_step(chunked) / tok_per_step(mono), 4)
             if tok_per_step(mono) else None),
        "monolithic_makespan_s": mono["sim_s"],
        "chunked_makespan_s": chunked["sim_s"],
        "chunked_spec_makespan_s": spec_run["sim_s"],
        "spec_acceptance_rate":
            spec_run["snapshot"].get("spec_acceptance_rate"),
        "prefill_chunks": chunked["stats"].get("prefill_chunks", 0),
        "bit_identical_chunked": chunked["outputs"] == mono["outputs"],
        "bit_identical_spec": spec_run["outputs"] == mono["outputs"],
        "leaks": {"monolithic": mono["leaks"],
                  "chunked": chunked["leaks"],
                  "chunked_spec": spec_run["leaks"]},
        "note": "simulated dispatch clock (overhead + per-token charge); "
                "TTFT ratios and bit-identity are the transferable "
                "outputs, not CPU wall time",
    }
    return out


def _pct_of(sorted_vals, q):
    if not sorted_vals:
        return None
    n = len(sorted_vals)
    idx = min(n - 1, max(0, int(round(q / 100.0 * (n - 1)))))
    return round(sorted_vals[idx], 3)


def _worker_serving_sched(spec):
    print(json.dumps(_serving_sched_bench(spec)))


def _autotune_bench(spec=None):
    """CPU-runnable closed-loop autotuner micro-bench: an end-to-end tune
    over a small serving knob grid (prefill chunk tokens x speculative
    draft length) on the same simulated-dispatch-clock workload as the
    scheduler bench.  The ControlPlane prunes the infeasible corner
    (draft + 1 > page_size), scores every surviving trial from its own
    Telemetry snapshot, ledgers each trial as a ``tune-<id>`` run under
    bench ``autotune``, and persists the winner as a provenance-stamped
    overlay.  The bench then replays the DEFAULT config (chunk=256,
    no draft) and the overlay-merged config through the identical
    harness and asserts the tuned point beats the default on >= 2
    ledgered metrics with zero regressions, that the overlay round-trips
    through ``create_serving_engine``, and that the tune artifacts pass
    ``check_telemetry_schema --tune`` / ``--ledger`` and a rehearsal
    ``ds_perf_diff --check``."""
    spec = spec or {}
    import subprocess as sp
    import tempfile

    import numpy as np

    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.autotuning import (ControlPlane, Knob, KnobSpace,
                                          Objective, apply_overlay,
                                          load_overlay)
    from deepspeed_tpu.inference.serving import ServingEngine
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    from deepspeed_tpu.monitor.telemetry import Telemetry

    n_requests = int(spec.get("requests", 12))
    max_new = int(spec.get("max_new_tokens", 12))
    long_len = int(spec.get("long_prompt_tokens", 192))
    overhead_s = float(spec.get("dispatch_overhead_s", 5e-4))
    per_tok_s = float(spec.get("per_token_s", 5e-5))
    chunk_grid = [int(v) for v in spec.get("chunk_grid", [32, 64])]
    # 16 is the deliberately infeasible corner: draft + 1 > page_size,
    # so the memory-model pruner (not the engine) must reject it
    draft_grid = [int(v) for v in spec.get("draft_grid", [0, 3, 16])]
    page_size = int(spec.get("page_size", 16))

    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    draft_params = jax.tree_util.tree_unflatten(
        treedef, [l + 3e-3 * jax.random.normal(k, l.shape, l.dtype)
                  for l, k in zip(leaves, keys)])

    rng = np.random.default_rng(0)
    prompts, classes, arrival = [], [], []
    for i in range(n_requests):
        if i % 3 == 0:
            n, cls = long_len, "throughput"
        else:
            n, cls = int(rng.integers(4, 9)), "latency"
        prompts.append(rng.integers(0, cfg.vocab_size, (n,)).tolist())
        classes.append(cls)
        arrival.append(i * 3e-3)

    class SimClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

    def run_workload(trial_cfg, tel):
        serving = dict(trial_cfg.get("serving") or {})
        sched_blk = dict(serving.get("scheduler") or {})
        gamma = int(dict(sched_blk.get("speculative") or {})
                    .get("num_draft_tokens", 0))
        clk = SimClock()
        sched_cfg = {"policy": "chunked",
                     "prefill_chunk_tokens":
                         int(sched_blk.get("prefill_chunk_tokens", 256)),
                     "max_prefill_chunks_per_step":
                         int(sched_blk.get("max_prefill_chunks_per_step",
                                           3))}
        if gamma > 0:
            sched_cfg["speculative"] = {"enabled": True,
                                        "num_draft_tokens": gamma}
        eng = ServingEngine(
            model, params, max_batch=4,
            page_size=int(serving.get("page_size", page_size)),
            max_seq=512, dtype=jnp.float32, clock=clk,
            serving={"scheduler": sched_cfg}, telemetry=tel,
            draft_model=model if gamma > 0 else None,
            draft_params=draft_params if gamma > 0 else None)
        real_step = eng._run_step

        def charged_step(ids, tables, lengths, phase="decode"):
            clk.t += overhead_s + per_tok_s * float(ids.size)
            return real_step(ids, tables, lengths, phase=phase)

        eng._run_step = charged_step
        if gamma > 0:
            sch = eng.scheduler
            real_draft = sch._run_draft

            def charged_draft(ids, tables, lengths, phase):
                clk.t += overhead_s + per_tok_s / 4.0 * float(ids.size)
                return real_draft(ids, tables, lengths, phase)

            sch._run_draft = charged_draft
            real_propose = sch._propose_fn

            def charged_propose(params, caches, tables, lengths, last):
                clk.t += overhead_s + per_tok_s / 4.0 * \
                    float(last.shape[0] * (gamma + 1))
                return real_propose(params, caches, tables, lengths, last)

            sch._propose_fn = charged_propose

        total = 0
        next_req = 0
        while next_req < n_requests or eng.queue or eng.n_active:
            clk.t += 1e-4
            while next_req < n_requests and arrival[next_req] <= clk.t:
                eng.add_request(next_req, prompts[next_req],
                                max_new_tokens=max_new,
                                slo_class=classes[next_req])
                next_req += 1
            for toks in eng.step().values():
                total += len(toks)
        # TTFT/TPOT/e2e histograms (simulated ms) land in ``tel`` via the
        # engine; tokens/s over the simulated clock is harness-computed
        return {"tokens_per_sec": round(total / clk.t, 3)
                if clk.t else 0.0}

    base_cfg = {"serving": {"page_size": page_size,
                            "scheduler": {
                                "policy": "chunked",
                                "prefill_chunk_tokens": 256,
                                "max_prefill_chunks_per_step": 3}}}
    space = KnobSpace([
        Knob("prefill_chunk_tokens",
             "serving/scheduler/prefill_chunk_tokens", chunk_grid),
        Knob("num_draft_tokens",
             "serving/scheduler/speculative/num_draft_tokens", draft_grid),
    ])
    objective = Objective({"tokens_per_sec": 1.0,
                           "ttft_p99_ms": -0.05,
                           "tpot_p99_ms": -0.5})

    results_dir = tempfile.mkdtemp(prefix="dstpu_autotune_")
    trial_ledger = os.path.join(results_dir, "trial_ledger.jsonl")
    cp = ControlPlane(base_config=base_cfg, knob_space=space,
                      objective=objective, results_dir=results_dir,
                      ledger_path=trial_ledger, bench="autotune")
    summary = cp.tune(run_workload)
    payload = load_overlay(summary["overlay_path"])
    winner = ((payload or {}).get("provenance") or {}).get("knobs") or {}

    def measure(cfg_d):
        tel = Telemetry()
        tel.enabled = True   # registry-only: accumulate, no event sink
        extra = run_workload(cfg_d, tel)
        return objective.metrics(tel.snapshot(), extra)

    default_vec = measure(base_cfg)
    tuned_vec = measure(apply_overlay(base_cfg, payload))

    directions = {"tokens_per_sec": 1, "ttft_p50_ms": -1,
                  "ttft_p99_ms": -1, "tpot_p50_ms": -1, "tpot_p99_ms": -1,
                  "e2e_p99_ms": -1, "queue_wait_p99_ms": -1}
    improved, regressed = [], []
    for name, sign in directions.items():
        d, t = default_vec.get(name), tuned_vec.get(name)
        if d is None or t is None:
            continue
        delta = sign * (t - d)
        if delta > 0.01 * abs(d):
            improved.append(name)
        elif delta < -0.01 * abs(d):
            regressed.append(name)

    # consumption path: the overlay must round-trip through
    # create_serving_engine (autotuning.overlay_path in the ds config)
    eng = deepspeed_tpu.create_serving_engine(
        model, params,
        config={"max_batch": 4, "max_seq": 512,
                "serving": base_cfg["serving"],
                "autotuning": {"overlay_path": summary["overlay_path"]}},
        dtype=jnp.float32)
    consumed = (getattr(eng, "overlay_provenance", None) is not None and
                getattr(eng.scheduler, "chunk", None) ==
                int(winner.get("prefill_chunk_tokens", -1)))

    scripts_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts")
    real_ledger = os.environ.get(
        "BENCH_LEDGER",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_LEDGER.jsonl"))
    with open(trial_ledger) as f:
        trial_rows_text = f.read()
    trial_rows = [ln for ln in trial_rows_text.splitlines() if ln.strip()]
    # perf-diff rehearsal: history + this tune's trial runs + a candidate
    # run carrying the summary metrics the parent will ledger — proves
    # the tune rows never trip the gate before touching the real ledger
    check_ledger = os.path.join(results_dir, "check_ledger.jsonl")
    ts = time.time()
    with open(check_ledger, "w") as f:
        if os.path.exists(real_ledger):
            with open(real_ledger) as src:
                f.write(src.read())
        f.write(trial_rows_text)
        for metric, value in (
                ("tuned_tokens_per_sec", tuned_vec.get("tokens_per_sec")),
                ("tuned_ttft_p99_ms", tuned_vec.get("ttft_p99_ms")),
                ("default_tokens_per_sec",
                 default_vec.get("tokens_per_sec")),
                ("default_ttft_p99_ms", default_vec.get("ttft_p99_ms"))):
            if isinstance(value, (int, float)):
                f.write(json.dumps(
                    {"ts": ts, "run": f"run-tunecheck-{int(ts)}",
                     "bench": "cpu_autotune", "metric": metric,
                     "value": value}) + "\n")

    def _rc(args):
        try:
            return sp.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=120).returncode
        except Exception:
            return -1

    checker = os.path.join(scripts_dir, "check_telemetry_schema.py")
    tune_gate_rc = _rc([checker, "--tune", results_dir])
    ledger_gate_rc = _rc([checker, "--ledger", check_ledger])
    perf_diff_rc = _rc([os.path.join(scripts_dir, "ds_perf_diff.py"),
                        check_ledger, "--check"])

    beats = len(improved) >= 2 and not regressed
    problems = []
    if summary.get("best") is None:
        problems.append("no winning trial")
    if not beats:
        problems.append(
            f"tuned does not beat default: improved={improved} "
            f"regressed={regressed}")
    if not consumed:
        problems.append("overlay not consumed by create_serving_engine")
    if tune_gate_rc != 0:
        problems.append(f"--tune gate rc={tune_gate_rc}")
    if ledger_gate_rc != 0:
        problems.append(f"--ledger gate rc={ledger_gate_rc}")
    if perf_diff_rc != 0:
        problems.append(f"ds_perf_diff --check rc={perf_diff_rc}")
    if problems:
        raise RuntimeError("autotune bench failed: " + "; ".join(problems))

    # trial rows reach the real ledger only after every gate passed — a
    # failed tune must never pollute the perf baseline
    appended = 0
    try:
        with open(real_ledger, "a") as f:
            f.write(trial_rows_text)
        appended = len(trial_rows)
    except OSError:
        pass

    def _r(v):
        return round(v, 3) if isinstance(v, (int, float)) else None

    return {
        "trials": summary["trials"],
        "pruned_trials": summary["pruned"],
        "winner_chunk": int(winner.get("prefill_chunk_tokens", 0)),
        "winner_draft": int(winner.get("num_draft_tokens", 0)),
        "winner_objective": _r((summary.get("best") or {})
                               .get("objective")),
        "default_tokens_per_sec": _r(default_vec.get("tokens_per_sec")),
        "tuned_tokens_per_sec": _r(tuned_vec.get("tokens_per_sec")),
        "default_ttft_p99_ms": _r(default_vec.get("ttft_p99_ms")),
        "tuned_ttft_p99_ms": _r(tuned_vec.get("ttft_p99_ms")),
        "default_tpot_p99_ms": _r(default_vec.get("tpot_p99_ms")),
        "tuned_tpot_p99_ms": _r(tuned_vec.get("tpot_p99_ms")),
        "default_e2e_p99_ms": _r(default_vec.get("e2e_p99_ms")),
        "tuned_e2e_p99_ms": _r(tuned_vec.get("e2e_p99_ms")),
        "improved_metric_count": len(improved),
        "regressed_metric_count": len(regressed),
        "improved": improved,
        "regressed": regressed,
        "tuned_beats_default": beats,
        "overlay_consumed": consumed,
        "tune_gate_rc": tune_gate_rc,
        "ledger_gate_rc": ledger_gate_rc,
        "perf_diff_rc": perf_diff_rc,
        "trial_rows_appended": appended,
        "note": "simulated dispatch clock; tuned-vs-default deltas and "
                "gate rcs are the transferable outputs, not CPU wall "
                "time",
    }


def _worker_autotune(spec):
    print(json.dumps(_autotune_bench(spec)))


def _comm_census_bench(spec=None):
    """CPU-runnable distributed-telemetry micro-bench: a simulated 4-rank
    run (N threads, each owning its own Telemetry configured with a
    distinct rank — the same shard layout N real processes produce) with
    synthetic timed collectives and one deliberately delayed rank.
    Reports the observability plane's own numbers: the aggregator's
    per-collective achieved-bandwidth accounting checked against the
    hand-computed bytes/duration, the cross-rank skew table, the
    straggler verdict, plus schema-checker validation of every shard and
    a live scrape of the rank-0 exporter's rank-labelled /metrics and
    /cluster endpoints.  Durations are synthetic by design — the
    accounting chain, not the wire, is what this bench measures."""
    spec = spec or {}
    import importlib.util
    import tempfile
    import threading
    import urllib.request

    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig

    n_ranks = int(spec.get("ranks", 4))
    steps = int(spec.get("steps", 12))
    step_ms = float(spec.get("step_ms", 20.0))
    straggler_ms = 4.0 * step_ms              # 4x median, threshold 2x
    comm_bytes = int(spec.get("comm_bytes", 4 << 20))
    comm_dur_ms = float(spec.get("comm_dur_ms", 2.0))
    tmp = tempfile.mkdtemp(prefix="comm_census_bench_")

    def _cfg():
        return TelemetryConfig(
            {"enabled": True, "output_path": tmp,
             "job_name": "comm_census",
             "export": {"enabled": True, "port": 0},
             "distributed": {"enabled": True, "skew_threshold": 2.0,
                             "straggler_window": steps}})

    tels = [None] * n_ranks

    def _run_rank(rank):
        tel = Telemetry().configure(_cfg(), rank=rank)
        tels[rank] = tel
        for step in range(1, steps + 1):
            ms = straggler_ms if rank == n_ranks - 1 else step_ms
            tel.emit("heartbeat", "engine/heartbeat", step=step,
                     step_ms=ms)
            tel.collective("all_reduce", comm_bytes, "fsdp",
                           dtype="float32", dur_ms=comm_dur_ms,
                           world=n_ranks)
            tel.collective("all_gather", comm_bytes // 4, "fsdp",
                           dtype="bfloat16", dur_ms=comm_dur_ms / 2,
                           world=n_ranks)

    threads = [threading.Thread(target=_run_rank, args=(r,))
               for r in range(n_ranks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    shard_dir = os.path.join(tmp, "comm_census")
    repo = os.path.dirname(os.path.abspath(__file__))
    sp = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(repo, "scripts", "check_telemetry_schema.py"))
    checker = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(checker)
    shard_problems, n_shards = checker.validate_shard_dir(shard_dir)

    # rank 0 owns the aggregator and the exporter; scrape both surfaces
    tels[0].cluster.refresh(force=True)
    host, port = tels[0].exporter.address
    prom = urllib.request.urlopen(
        f"http://{host}:{port}/metrics", timeout=5).read().decode()
    snap = json.loads(urllib.request.urlopen(
        f"http://{host}:{port}/cluster", timeout=5).read())
    prom_problems = checker.validate_prom_exposition(prom)
    cluster_problems = checker.validate_cluster_payload(snap)
    for tel in tels:
        tel.close()

    # bandwidth accounting: the aggregated achieved GB/s must reproduce
    # the hand-computed sum(bytes)/sum(duration) of the injected events
    expect = comm_bytes / (comm_dur_ms / 1e3) / 1e9
    row = snap["collectives"]["all_reduce"]
    achieved = row["achieved_gbps"] or 0.0
    skew = snap["step_skew"]
    return {
        "ranks": n_ranks,
        "steps_aligned": snap["steps"]["aligned"],
        "shards_validated": n_shards,
        "shard_problems": len(shard_problems),
        "cluster_payload_ok": not cluster_problems,
        "exporter_scrape_ok": not prom_problems and 'rank="0"' in prom,
        "all_reduce_calls": row["calls"],
        "achieved_gbps": achieved,
        "expected_gbps": round(expect, 4),
        "bandwidth_rel_err": round(abs(achieved - expect) / expect, 6),
        "busbw_gbps": row["busbw_gbps"],
        "step_skew_ms": {"p50": skew["p50_spread_ms"],
                         "max": skew["max_spread_ms"]},
        "straggler_rank": snap["straggler"]["rank"],
        "straggler_metric": snap["straggler"]["metric"],
        "straggler_detected": snap["straggler"]["rank"] == n_ranks - 1,
        "note": "synthetic durations: this bench proves the shard -> "
                "aggregate -> scrape accounting chain, not wire speed",
    }


def _worker_comm_census(spec):
    print(json.dumps(_comm_census_bench(spec)))


def _comm_quant_bench(spec=None):
    """CPU-runnable quantized-collective micro-bench: a simulated 4-rank
    grad reduce (shard_map over forced host devices) comparing the fp32
    baseline against the blockwise-int8 two-phase codec in
    comm/quantize.py.  Reports the wire accounting the comm census books
    (bytes-saved ratio vs the analytic int8+scales model), the codec's
    relative error on both verbs, wire-bandwidth rows computed from the
    REDUCED wire bytes, and schema-checker validation of the annotated
    ``comm`` events + frozen quant gauges.  CPU timings are compute-bound
    by design — the codec's numerics and the accounting chain, not wire
    speed, are what this bench measures."""
    spec = spec or {}
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    import importlib.util
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.comm.quantize import (QUANT_GAUGES,
                                             quant_bytes_saved,
                                             quant_payload_bytes,
                                             quantized_all_reduce,
                                             quantized_reduce_scatter)
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig

    world = int(spec.get("ranks", 4))
    numel = int(spec.get("numel", 1 << 20))     # fp32 grad shard, 4 MiB
    block = int(spec.get("block_size", 256))
    iters = int(spec.get("iters", 8))
    assert numel % (world * block) == 0
    devices = jax.devices()[:world]
    assert len(devices) == world, \
        f"need {world} host devices, have {len(devices)}"
    mesh = Mesh(np.array(devices), ("dp",))

    def _smap(f, out_specs):
        return jax.shard_map(f, mesh=mesh, in_specs=(P("dp", None),),
                             out_specs=out_specs, check_vma=False)

    rng = np.random.default_rng(0)
    # per-rank grad shards with realistic mixed magnitudes
    x = (rng.standard_normal((world, numel)) *
         rng.choice([1e-3, 1e-1, 1.0], (world, numel))).astype(np.float32)
    x = jax.device_put(
        jnp.asarray(x), NamedSharding(mesh, P("dp", None)))

    fp32_ar = jax.jit(_smap(
        lambda g: jax.lax.psum(g, "dp"), P(None, None)))
    int8_ar = jax.jit(_smap(
        lambda g: quantized_all_reduce(g[0], "dp", block)[None],
        P(None, None)))
    int8_rs = jax.jit(_smap(
        lambda g: quantized_reduce_scatter(g[0], "dp", block)[None],
        P("dp", None)))

    def _time(fn):
        fn(x).block_until_ready()            # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(x).block_until_ready()
        return out, (time.perf_counter() - t0) / iters * 1e3

    exact, fp32_ms = _time(fp32_ar)
    quant, int8_ms = _time(int8_ar)
    scattered, rs_ms = _time(int8_rs)
    exact_np = np.asarray(exact)[0]
    ar_err = float(np.linalg.norm(np.asarray(quant)[0] - exact_np) /
                   np.linalg.norm(exact_np))
    rs_full = np.asarray(scattered).reshape(-1)
    rs_err = float(np.linalg.norm(rs_full - exact_np) /
                   np.linalg.norm(exact_np))

    # wire accounting, census semantics: payload bytes per collective
    raw_bytes = numel * 4
    wire_bytes = quant_payload_bytes(numel, block)
    saved = quant_bytes_saved(numel, "float32", block)
    ratio = raw_bytes / wire_bytes

    # the annotated census chain: emit what the engine wiring emits and
    # schema-check every event, including the frozen quant gauges
    tmp = tempfile.mkdtemp(prefix="comm_quant_bench_")
    tel = Telemetry().configure(TelemetryConfig(
        {"enabled": True, "output_path": tmp,
         "job_name": "comm_quant"}), rank=0)
    tel.collective("all_reduce", raw_bytes, "dp", dtype="float32",
                   dur_ms=fp32_ms, world=world)
    tel.collective("all_reduce", wire_bytes, "dp", dtype="float32",
                   dur_ms=int8_ms, world=world,
                   wire_dtype="int8", bytes_saved=saved)
    tel.collective("reduce_scatter", wire_bytes, "dp", dtype="float32",
                   dur_ms=rs_ms, world=world,
                   wire_dtype="int8", bytes_saved=saved)
    for g in QUANT_GAUGES:
        tel.gauge(g, float(saved), step=1)
    tel.close()

    repo = os.path.dirname(os.path.abspath(__file__))
    sp = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(repo, "scripts", "check_telemetry_schema.py"))
    checker = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(checker)
    problems, n_events = [], 0
    with open(os.path.join(tmp, "comm_quant", "events.jsonl")) as f:
        for line in f:
            n_events += 1
            problems += checker.validate_event(json.loads(line))

    assert ratio >= 3.0, f"bytes-saved ratio {ratio:.3f} below 3x"
    assert ar_err < 0.05 and rs_err < 0.05, (ar_err, rs_err)
    assert not problems, problems[:3]
    return {
        "ranks": world,
        "numel": numel,
        "block_size": block,
        "raw_bytes": raw_bytes,
        "wire_bytes": wire_bytes,
        "bytes_saved": int(saved),
        "bytes_saved_ratio": round(ratio, 4),
        "analytic_ratio": round(raw_bytes /
                                quant_payload_bytes(numel, block), 4),
        "allreduce_rel_err": round(ar_err, 6),
        "reduce_scatter_rel_err": round(rs_err, 6),
        "fp32_allreduce_ms": round(fp32_ms, 3),
        "int8_allreduce_ms": round(int8_ms, 3),
        "int8_reduce_scatter_ms": round(rs_ms, 3),
        "busbw_gbps_fp32": round(raw_bytes / (fp32_ms / 1e3) / 1e9, 4),
        "busbw_gbps_int8_wire": round(wire_bytes / (int8_ms / 1e3) / 1e9,
                                      4),
        "events_validated": n_events,
        "schema_problems": len(problems),
        "note": "CPU timings are compute-bound; the codec numerics and "
                "the bytes-saved accounting chain are what this bench "
                "measures",
    }


def _worker_comm_quant(spec):
    print(json.dumps(_comm_quant_bench(spec)))


def _compile_churn_bench(spec=None):
    """CPU-runnable profiling-plane micro-bench: a jitted kernel driven
    through a deliberately shape-churned workload so every new shape is a
    jit-cache miss.  Reports the observability plane's own numbers: the
    CompileWatcher's miss census against the known churn count, the
    recompile-storm verdict, schema-checker validation of the emitted
    ``compile/*`` events, the mem/roofline gauge path (allocator stats
    injected — CPU has none), and a live scrape of /metrics + /healthz.
    The churn is synthetic by design — the trace -> verdict -> scrape
    chain, not XLA compile speed, is what this bench measures."""
    spec = spec or {}
    import importlib.util
    import tempfile
    import urllib.request

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig

    n_shapes = int(spec.get("shapes", 6))
    repeat = int(spec.get("repeat", 3))
    shapes = [(1, 8 * (i + 1)) for i in range(n_shapes)]
    tmp = tempfile.mkdtemp(prefix="compile_churn_bench_")
    tel = Telemetry().configure(TelemetryConfig(
        {"enabled": True, "output_path": tmp, "job_name": "compile_churn",
         "export": {"enabled": True, "port": 0},
         "profiling": {"enabled": True, "storm_threshold": 3,
                       "storm_window_s": 60.0}}))
    plane = tel.profiling

    @jax.jit
    def kernel(x):
        return (x * 2.0 + 1.0).sum()

    wrapped = plane.wrap(kernel, "bench/churn")
    t0 = time.perf_counter()
    for _ in range(repeat):
        for shape in shapes:
            wrapped(jnp.ones(shape, jnp.float32))
    churn_wall_s = time.perf_counter() - t0
    # hot-path tax: every fingerprint is now cached, so this pass prices
    # the wrapper's per-call dict lookup
    t0 = time.perf_counter()
    for shape in shapes:
        wrapped(jnp.ones(shape, jnp.float32))
    hot_us = (time.perf_counter() - t0) / n_shapes * 1e6
    snap = plane.compile_snapshot()

    # mem attribution + roofline ride the same stream: CPU has no
    # allocator stats, so inject a growing fake and pin the peaks
    state = {"n": 0}

    def fake_stats():
        state["n"] += 1
        return {"bytes_in_use": (1 << 20) + state["n"] * 4096,
                "peak_bytes_in_use": (1 << 20) + state["n"] * 8192}

    plane.hbm.stats_fn = fake_stats
    with plane.track("serve_step"):
        wrapped(jnp.ones(shapes[0], jnp.float32))
    plane.peak_hbm_gbps = 819.0
    plane.roofline("train_batch", 0.01, flops=1e9, bytes_moved=1e8,
                   peak_flops=1e12, step=1)

    host, port = tel.exporter.address
    prom = urllib.request.urlopen(
        f"http://{host}:{port}/metrics", timeout=5).read().decode()
    health = json.loads(urllib.request.urlopen(
        f"http://{host}:{port}/healthz", timeout=5).read())
    tel.close()

    repo = os.path.dirname(os.path.abspath(__file__))
    sp = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(repo, "scripts", "check_telemetry_schema.py"))
    checker = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(checker)
    events_path = os.path.join(tmp, "compile_churn", "events.jsonl")
    problems = checker.validate_file(events_path)
    prom_problems = checker.validate_prom_exposition(prom)
    misses = storms = mem_gauges = roofline_gauges = 0
    with open(events_path) as f:
        for line in f:
            try:
                ev = json.loads(line)
            except ValueError:
                continue
            if ev.get("kind") == "compile":
                if ev.get("name") == "compile/storm":
                    storms += 1
                else:
                    misses += 1
            elif ev.get("kind") == "gauge":
                if ev.get("name", "").startswith("mem/"):
                    mem_gauges += 1
                elif ev.get("name", "").startswith("roofline/"):
                    roofline_gauges += 1
    return {
        "recompiles": snap["total_misses"],
        "expected_recompiles": n_shapes,
        "storm_flagged": bool(snap["storm_active"]),
        "storm_events": storms,
        "miss_events": misses,
        "mem_gauge_events": mem_gauges,
        "roofline_gauge_events": roofline_gauges,
        "events_ok": not problems,
        "schema_problems": len(problems),
        "exporter_scrape_ok": (not prom_problems and
                               "ds_compile_misses" in prom),
        "healthz_storm": bool(health.get("recompile_storm")),
        "churn_wall_s": round(churn_wall_s, 4),
        "hot_call_overhead_us": round(hot_us, 2),
        "note": "synthetic shape churn: this bench proves the miss -> "
                "event -> storm -> scrape chain, not XLA compile speed",
    }


def _worker_compile_churn(spec):
    print(json.dumps(_compile_churn_bench(spec)))


def _incident_bench(spec=None):
    """CPU-runnable incident-plane micro-bench: prices the always-on
    flight recorder (ring-buffer record ns/event — the tax every emit
    pays once incidents are enabled), then drives a deadline-missing
    serving workload under an injected recompile storm and proves the
    verdict -> bundle chain: the storm onset and the SLO burn-rate
    alerter each write exactly one incident bundle, both validate
    against the frozen bundle schema, and the /incidents endpoint
    serves them.  The workload is synthetic by design — the trigger ->
    bundle -> scrape chain, not model speed, is what this measures."""
    spec = spec or {}
    import importlib.util
    import tempfile
    import urllib.request

    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig

    n_events = int(spec.get("events", 20000))
    tmp = tempfile.mkdtemp(prefix="incident_bench_")
    tel = Telemetry().configure(TelemetryConfig(
        {"enabled": True, "output_path": tmp, "job_name": "incident",
         "export": {"enabled": True, "port": 0},
         "profiling": {"enabled": True, "storm_threshold": 3,
                       "storm_window_s": 60.0},
         "incidents": {"enabled": True, "ring_capacity": 4096,
                       "burn_windows": [[60.0, 0.3]],
                       "burn_min_requests": 4, "cooldown_s": 0.0}}))
    incidents = tel.incidents

    # flight-recorder tax: ring.record() is on every emit path, so its
    # per-event cost is the plane's standing overhead
    ev = {"ts": time.time(), "kind": "counter", "name": "bench/tick",
          "value": 1}
    t0 = time.perf_counter()
    for _ in range(n_events):
        incidents.record(ev)
    ring_record_ns = (time.perf_counter() - t0) / n_events * 1e9

    # deadline workload: admitted requests that miss their SLO, with the
    # lifecycle traces + counters the correlation pass joins on
    base = time.time()
    for i in range(6):
        tel.emit("serve", "serve/request/admitted",
                 attrs={"req_id": f"req-{i}", "deadline": 1})
        tel.emit("serve", "serve/request/deadline",
                 attrs={"req_id": f"req-{i}", "slo": "miss"}, step=i)
        tel.count("serve/slo_missed")
    # injected recompile storm: 4 distinct non-cold-diffable fingerprints
    # (the first miss is "cold" and excluded from the storm window)
    for i in range(4):
        tel.profiling.compiles.note_miss(
            "bench/incident", ("f", ((f"s{i}", "f32"),)), 0.01, step=i)
    # SLO burn: rate over the injected misses trips the single window
    t0 = time.perf_counter()
    burn = incidents.observe_slo(now=base + 1.0)
    trigger_wall_ms = (time.perf_counter() - t0) * 1e3

    host, port = tel.exporter.address
    scraped = json.loads(urllib.request.urlopen(
        f"http://{host}:{port}/incidents", timeout=5).read())
    bundle_dir = incidents.bundle_dir
    snap = incidents.snapshot()
    tel.close()

    repo = os.path.dirname(os.path.abspath(__file__))
    sp = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(repo, "scripts", "check_telemetry_schema.py"))
    checker = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(checker)
    problems, bundles = checker.validate_incidents_path(bundle_dir)
    stream_problems = checker.validate_file(
        os.path.join(tmp, "incident", "events.jsonl"))
    return {
        "ring_record_ns": round(ring_record_ns, 1),
        "ring_events_recorded": n_events,
        "bundles_written": bundles,
        "expected_bundles": 2,          # storm onset + slo_burn
        "slo_burn_fired": bool(burn),
        "slo_burn_trigger_ms": round(trigger_wall_ms, 3),
        "bundles_ok": not problems,
        "bundle_problems": len(problems),
        "events_ok": not stream_problems,
        "incidents_scrape_ok": (
            len(scraped.get("incidents", [])) == bundles),
        "ring_occupancy": int(snap["ring"]["events"]),
        "note": "synthetic deadline workload + injected storm: this "
                "bench proves the trigger -> bundle -> scrape chain and "
                "prices the always-on ring buffer",
    }


def _worker_incident(spec):
    print(json.dumps(_incident_bench(spec)))


def _step_attr_bench(spec=None):
    """CPU-runnable attribution-plane micro-bench: prices the per-event
    record tap and the interval-algebra close, then pins the algebra to
    an analytically constructed workload — a simulated 4-rank step with
    known compute/collective overlap where the collective's only exposed
    window is the 5 ms gap between forward and backward, so the expected
    exposed fraction is EXACTLY 5/100 regardless of per-rank skew (the
    skew shifts overlap between the two compute spans but never changes
    its total).  The serving half round-trips one migrated request
    through capture_handoff -> import_ctx on a fake clock and checks the
    stage sum equals e2e exactly."""
    spec = spec or {}
    import importlib.util
    import tempfile

    from deepspeed_tpu.monitor.attribution import (RequestAttributor,
                                                   decompose_step)
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig

    ranks = int(spec.get("ranks", 4))
    n_record = int(spec.get("events", 20000))
    tmp = tempfile.mkdtemp(prefix="step_attr_bench_")
    tel = Telemetry().configure(TelemetryConfig(
        {"enabled": True, "output_path": tmp, "job_name": "step_attr",
         "attribution": {"enabled": True}}))
    plane = tel.attribution

    # tap tax: record() sits on every emit path once the plane is on
    ev = {"ts": time.time(), "kind": "span", "name": "engine/forward",
          "dur_ms": 1.0}
    t0 = time.perf_counter()
    for _ in range(n_record):
        plane.record(ev)
    record_ns = (time.perf_counter() - t0) / n_record * 1e9
    plane._compute.clear()      # drop the priming intervals

    # analytic workload: window 100 ms, input_wait [0,10], forward
    # [10,40], backward [45,85], all_reduce [30+k, 60+k] for per-rank
    # skew k in 0..3 ms.  The collective's overlap with compute is
    # (10-k) + (15+k) = 25 ms for every k: exposed = 5 ms, frac = 0.05.
    expected_frac = 0.05
    base = time.time()
    for s in range(ranks):
        w0 = base + s
        skew = 0.001 * s
        for name, end_s, dur_ms in (
                ("engine/input_wait", 0.010, 10.0),
                ("engine/forward", 0.040, 30.0),
                ("engine/backward", 0.085, 40.0)):
            plane.record({"ts": w0 + end_s, "kind": "span",
                          "name": name, "dur_ms": dur_ms})
        plane.record({"ts": w0 + 0.060 + skew, "kind": "comm",
                      "name": "all_reduce", "dur_ms": 30.0})
        plane.record({"ts": w0 + 0.100, "kind": "heartbeat",
                      "name": "engine/step", "step": s,
                      "step_ms": 100.0})
    fracs = [r["exposed_comm_frac"] for r in plane.history]
    rel_err = max(abs(f - expected_frac) / expected_frac for f in fracs) \
        if fracs else 1.0
    assert rel_err < 0.02, \
        f"exposed fraction off by {rel_err:.4f} rel: {fracs}"

    # algebra price: one decompose over the same interval mix
    iters = 2000
    t0 = time.perf_counter()
    for _ in range(iters):
        decompose_step(0.0, 0.1,
                       compute=[(0.010, 0.040), (0.045, 0.085)],
                       comm=[(0.030, 0.060)],
                       input_wait=[(0.000, 0.010)])
    decompose_ns = (time.perf_counter() - t0) / iters * 1e9

    # serving half: one migrated request on a fake clock — the stage sum
    # must equal e2e exactly (the gap stage absorbs the residual)
    clock = [0.0]
    src = RequestAttributor(clock=lambda: clock[0])
    src.admit("req-m")
    clock[0] = 0.040
    src.prefill_start("req-m")
    src.chunk("req-m", 25.0)
    clock[0] = 0.080
    wire = src.capture_handoff("req-m")
    dst = RequestAttributor(clock=lambda: clock[0])
    clock[0] = 0.095
    dst.import_ctx("req-m", wire)
    clock[0] = 0.100
    dst.first_token("req-m")
    clock[0] = 0.200
    attrs = dst.finalize("req-m", "finish")
    stage_sum = sum(attrs[f"{k}_ms"] for k in
                    ("queue", "prefill", "migrate", "gap", "decode"))
    sum_err_ms = abs(stage_sum - attrs["e2e_ms"])
    assert sum_err_ms < 1e-6, f"stage sum {stage_sum} != e2e {attrs}"
    # feed the attr event back through emit: schema-checks the frozen
    # event and lands it in the plane's serve history for /attribution
    tel.emit("serve", "serve/request/attr", attrs=attrs)
    snap = plane.snapshot()
    tel.close()

    repo = os.path.dirname(os.path.abspath(__file__))
    sp = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(repo, "scripts", "check_telemetry_schema.py"))
    checker = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(checker)
    stream = os.path.join(tmp, "step_attr", "events.jsonl")
    stream_problems = checker.validate_file(stream)
    with open(stream) as f:
        events = [json.loads(line) for line in f if line.strip()]
    attr_gauges = sum(1 for ev in events if ev.get("kind") == "gauge"
                      and str(ev.get("name", "")).startswith("step/attr/"))
    return {
        "record_ns": round(record_ns, 1),
        "decompose_ns": round(decompose_ns, 1),
        "steps_attributed": len(fracs),
        "exposed_comm_frac": round(sum(fracs) / len(fracs), 6),
        "exposed_rel_err": round(rel_err, 6),
        "attr_gauges_emitted": attr_gauges,
        "events_ok": not stream_problems,
        "serve_queue_ms": attrs["queue_ms"],
        "serve_prefill_ms": attrs["prefill_ms"],
        "serve_migrate_ms": attrs["migrate_ms"],
        "serve_gap_ms": attrs["gap_ms"],
        "serve_decode_ms": attrs["decode_ms"],
        "serve_e2e_ms": attrs["e2e_ms"],
        "serve_stage_sum_err_ms": round(sum_err_ms, 9),
        "serve_migrated": attrs["migrated"],
        "serve_paths_snapshotted": len(snap["requests"]),
        "note": "analytic 4-rank step: skewed collective overlaps 25 ms "
                "of compute at every skew, so exposed frac is exactly "
                "0.05; serving half round-trips one migration on a fake "
                "clock",
    }


def _worker_step_attr(spec):
    print(json.dumps(_step_attr_bench(spec)))


def _overlap_bench(spec=None):
    """CPU-runnable comm/compute-overlap micro-bench: a simulated 4-rank
    shard_map ZeRO-3 run (forced host devices) training the same stacked
    MLP with two schedules built from the SAME explicit collectives — a
    serial step (gather layer k, compute layer k, back to back) and an
    overlapped step (layer k+1's all_gather issued before layer k's
    compute, the double-buffered layer_scan schedule).  Because every
    collective is explicitly placed under shard_map, overlap reorders
    communication but never math: the 50-step loss trajectory must be
    BIT-IDENTICAL between the two schedules, asserted elementwise.  The
    backward rides the transposed program, where each tiled all_gather
    becomes an explicit per-layer psum_scatter — the ZeRO-3 grad
    reduce-scatter.  The exposure win is priced analytically
    (CPU executes collectives inline, so wall-clock overlap is
    unmeasurable here): ``simulate_forward_schedule`` emits both
    schedules' comm/compute intervals, the closed forms g/(g+c) vs
    g/(g+L*c) pin them, and ``decompose_step`` (the PR-16 interval
    algebra) must reproduce the simulator's own exposed fraction from
    the raw intervals.  The frozen ``comm/overlap/*`` gauges, the
    ``step/attr/exposed_comm_frac`` gauge, and busbw-carrying census
    rows for the gather/reduce-scatter wire bytes are emitted through
    Telemetry and the stream is schema-checker validated."""
    spec = spec or {}
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    import importlib.util
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.monitor.attribution import decompose_step
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.runtime.zero.stage_plan import (
        OVERLAP_GAUGES, simulate_forward_schedule)

    world = int(spec.get("ranks", 4))
    hidden = int(spec.get("hidden", 16))
    layers = int(spec.get("layers", 4))
    steps = int(spec.get("steps", 50))
    lr = float(spec.get("lr", 0.5))
    batch = int(spec.get("batch", 32))
    assert hidden % world == 0 and batch % world == 0
    devices = jax.devices()[:world]
    assert len(devices) == world, \
        f"need {world} host devices, have {len(devices)}"
    mesh = Mesh(np.array(devices), ("fsdp",))

    def _smap(f, in_specs, out_specs):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _gather(leaf):
        # tiled all_gather: the explicit ZeRO-3 param gather; its
        # transpose is psum_scatter — the explicit grad reduce-scatter
        return jax.lax.all_gather(leaf, "fsdp", axis=0, tiled=True)

    def fwd_serial(wl, bl, xb, yb):
        h = xb
        for k in range(layers):
            wk, bk = _gather(wl[k]), _gather(bl[k])
            h = jnp.tanh(h @ wk + bk)
        err = h - yb
        return jax.lax.psum(jnp.sum(err * err), "fsdp") / (batch * hidden)

    def fwd_overlap(wl, bl, xb, yb):
        # depth-1 double buffer: layer k+1's gather is ISSUED before
        # layer k's compute — same collectives, same operands, reordered
        h = xb
        nxt = (_gather(wl[0]), _gather(bl[0]))
        for k in range(layers):
            cur = nxt
            if k + 1 < layers:
                nxt = (_gather(wl[k + 1]), _gather(bl[k + 1]))
            wk, bk = cur
            h = jnp.tanh(h @ wk + bk)
        err = h - yb
        return jax.lax.psum(jnp.sum(err * err), "fsdp") / (batch * hidden)

    in_specs = (P(None, "fsdp", None), P(None, "fsdp"),
                P("fsdp", None), P("fsdp", None))

    def make_step(fwd):
        loss_fn = _smap(fwd, in_specs, P())

        def step_fn(wl, bl, xb, yb):
            loss, (gw, gb) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(wl, bl, xb, yb)
            return wl - lr * gw, bl - lr * gb, loss
        return jax.jit(step_fn)

    rng = np.random.default_rng(0)
    w0 = (rng.standard_normal((layers, hidden, hidden)) /
          np.sqrt(hidden)).astype(np.float32)
    b0 = np.zeros((layers, hidden), np.float32)
    proj = (rng.standard_normal((hidden, hidden)) * 0.5).astype(np.float32)
    X = rng.standard_normal((steps, batch, hidden)).astype(np.float32)
    Y = np.tanh(X @ proj)

    w_sh = NamedSharding(mesh, P(None, "fsdp", None))
    b_sh = NamedSharding(mesh, P(None, "fsdp"))
    x_sh = NamedSharding(mesh, P("fsdp", None))

    def run(fwd):
        step_fn = make_step(fwd)
        wl = jax.device_put(jnp.asarray(w0), w_sh)
        bl = jax.device_put(jnp.asarray(b0), b_sh)
        losses = []
        for i in range(steps):
            xb = jax.device_put(jnp.asarray(X[i]), x_sh)
            yb = jax.device_put(jnp.asarray(Y[i]), x_sh)
            wl, bl, loss = step_fn(wl, bl, xb, yb)
            losses.append(np.asarray(loss, np.float32))
        return np.asarray(losses, np.float32)

    t0 = time.perf_counter()
    ser_losses = run(fwd_serial)
    ovl_losses = run(fwd_overlap)
    train_s = time.perf_counter() - t0
    bit_identical = int(np.sum(ser_losses == ovl_losses))
    assert bit_identical == steps, (
        f"overlap reordered math: {steps - bit_identical}/{steps} steps "
        f"diverge, first at step "
        f"{int(np.argmin(ser_losses == ovl_losses))}")
    assert ser_losses[-1] < 0.7 * ser_losses[0], \
        f"run did not train: {ser_losses[0]} -> {ser_losses[-1]}"

    # analytic exposure: serial vs depth-1, pinned to the closed forms
    # and cross-checked through the interval algebra
    c_ms, g_ms, depth = 3.0, 1.0, 1
    ser = simulate_forward_schedule(layers, c_ms, g_ms, 0)
    ovl = simulate_forward_schedule(layers, c_ms, g_ms, depth)
    expected = {"serial": g_ms / (g_ms + c_ms),
                "overlap": g_ms / (g_ms + layers * c_ms)}
    analytic_rel_err = max(
        abs(ser["exposed_comm_frac"] - expected["serial"])
        / expected["serial"],
        abs(ovl["exposed_comm_frac"] - expected["overlap"])
        / expected["overlap"])
    assert analytic_rel_err < 1e-9, \
        f"schedule off the closed form by {analytic_rel_err}"
    algebra_rel_err = 0.0
    for sched in (ser, ovl):
        dec = decompose_step(0.0, sched["step_ms"] / 1e3,
                             compute=sched["compute"], comm=sched["comm"])
        algebra_rel_err = max(
            algebra_rel_err,
            abs(dec["exposed_comm_frac"] - sched["exposed_comm_frac"])
            / max(sched["exposed_comm_frac"], 1e-12))
    # decompose_step rounds its fraction to 6 decimals, so the algebra
    # agrees to quantization (1/13 carries ~1e-6 rel), not exactly
    assert algebra_rel_err < 1e-5, \
        f"interval algebra disagrees by {algebra_rel_err}"
    frac_drop = ser["exposed_comm_frac"] - ovl["exposed_comm_frac"]
    assert frac_drop > 0, "overlap did not reduce exposed comm"

    # book the run: frozen overlap gauges, the step-attr fraction, and
    # busbw census rows for the explicit gather / reduce-scatter wire
    tmp = tempfile.mkdtemp(prefix="overlap_bench_")
    tel = Telemetry().configure(TelemetryConfig(
        {"enabled": True, "output_path": tmp, "job_name": "overlap"}))
    layer_bytes = (hidden * hidden + hidden) * 4
    gauge_vals = {
        "comm/overlap/exposed_ms": ovl["exposed_comm_ms"],
        "comm/overlap/overlapped_ms":
            ovl["comm_ms"] - ovl["exposed_comm_ms"],
        "comm/overlap/gather_buckets": 2 * layers,
        "comm/overlap/rs_buckets": 2 * layers,
        "comm/overlap/prefetch_depth": depth,
    }
    for name in OVERLAP_GAUGES:
        tel.gauge(name, gauge_vals[name])
    tel.gauge("step/attr/exposed_comm_frac", ovl["exposed_comm_frac"])
    for op in ("all_gather", "reduce_scatter"):
        tel.collective(op, layer_bytes * layers, "fsdp", dtype="float32",
                       dur_ms=g_ms * layers, world=world)
    tel.close()

    repo = os.path.dirname(os.path.abspath(__file__))
    sp = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(repo, "scripts", "check_telemetry_schema.py"))
    checker = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(checker)
    stream = os.path.join(tmp, "overlap", "events.jsonl")
    stream_problems = checker.validate_file(stream)
    with open(stream) as f:
        events = [json.loads(line) for line in f if line.strip()]
    overlap_gauges = sum(
        1 for ev in events if ev.get("kind") == "gauge"
        and str(ev.get("name", "")).startswith("comm/overlap/"))
    census_rows = sum(1 for ev in events if ev.get("kind") == "comm"
                      and "busbw_gbps" in ev)
    return {
        "ranks": world,
        "layers": layers,
        "trajectory_steps": steps,
        "bit_identical_steps": bit_identical,
        "loss_first": float(ser_losses[0]),
        "loss_last": float(ser_losses[-1]),
        "train_s": round(train_s, 3),
        "serial_exposed_comm_frac": round(ser["exposed_comm_frac"], 6),
        "overlap_exposed_comm_frac": round(ovl["exposed_comm_frac"], 6),
        "exposed_frac_drop": round(frac_drop, 6),
        "analytic_rel_err": round(analytic_rel_err, 12),
        "algebra_rel_err": round(algebra_rel_err, 9),
        "overlap_gauges_emitted": overlap_gauges,
        "census_rows": census_rows,
        "events_ok": not stream_problems,
        "note": "4-rank shard_map ZeRO-3: serial vs depth-1 overlapped "
                "schedule from the same explicit collectives — 50-step "
                "trajectory bit-identical by construction; exposure "
                "priced analytically (serial g/(g+c) vs overlapped "
                "g/(g+L*c)) and cross-checked through decompose_step",
    }


def _worker_overlap(spec):
    print(json.dumps(_overlap_bench(spec)))


def _tiered_bench(spec):
    """Tiered-memory-engine micro-bench (runtime/tiered_store.py): a
    synthetic layer stack LARGER than a simulated HBM budget streams
    through host + NVMe tiers behind the schedule-driven prefetch
    engine.  Asserts the fp32 placement round-trips bit-identical, the
    int8 placement stays inside the codec's absmax/127 block bound while
    shrinking the NVMe tier ~4x, the HBM working set respects the budget
    (evictions fired), the sealed directory fscks COMMITTED, the frozen
    ``tier/*`` gauge stream schema-validates, and the bench's own rows
    rehearse the ledger + ds_perf_diff gates."""
    spec = spec or {}
    import importlib.util
    import subprocess as sp
    import tempfile

    import numpy as np

    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime import resilience
    from deepspeed_tpu.runtime.config import TelemetryConfig
    from deepspeed_tpu.runtime.tiered_store import (PlacementPolicy,
                                                    PrefetchEngine,
                                                    TieredStore)

    layers = int(spec.get("layers", 16))
    hidden = int(spec.get("hidden", 64))
    passes = int(spec.get("passes", 3))
    layer_bytes = hidden * hidden * 4
    # the point of the exercise: the model does NOT fit the device
    hbm_budget = 3 * layer_bytes
    model_bytes = layers * layer_bytes
    assert model_bytes > 4 * hbm_budget

    rng = np.random.default_rng(0)
    W = [(rng.standard_normal((hidden, hidden)) / np.sqrt(hidden))
         .astype(np.float32) for _ in range(layers)]

    tmp = tempfile.mkdtemp(prefix="tiered_bench_")
    tel = Telemetry().configure(TelemetryConfig(
        {"enabled": True, "output_path": tmp, "job_name": "tiered"}))
    # patch the store's process-global telemetry hook onto this bench's
    # sink so publish_gauges lands in our stream
    import deepspeed_tpu.monitor.telemetry as _telmod
    _saved = _telmod._telemetry
    _telmod._telemetry = tel

    def run_store(name, quantize):
        store = TieredStore(
            name=name, nvme_dir=tmp,
            policy=PlacementPolicy(default_tier="nvme",
                                   quantize=quantize),
            hbm_budget_bytes=hbm_budget)
        for i, w in enumerate(W):
            # alternate host/NVMe so both beyond-HBM tiers carry load
            store.put(f"L{i}", w, tier="host" if i % 2 else "nvme")
        store.commit()
        sched = [[f"L{i}"] for i in range(layers)]
        eng = PrefetchEngine(store, sched, depth=1)
        t0 = time.perf_counter()
        for _ in range(passes):
            for i in range(layers):
                eng.access(i, device=True)
        dur = time.perf_counter() - t0
        return store, dur

    fp32_store, fp32_s = run_store("bench_fp32", quantize=False)
    int8_store, int8_s = run_store("bench_int8", quantize=True)

    # fp32: tiers are bit-transparent
    exact = sum(int(np.array_equal(fp32_store.fetch(f"L{i}"), W[i]))
                for i in range(layers))
    assert exact == layers, f"fp32 round trip lost bits: {exact}/{layers}"
    # int8: error bounded by the codec's per-block scale (absmax/127)
    int8_max_err, int8_bound = 0.0, 0.0
    for i, w in enumerate(W):
        got = int8_store.fetch(f"L{i}")
        int8_max_err = max(int8_max_err,
                           float(np.max(np.abs(got - w))))
        int8_bound = max(int8_bound, float(np.max(np.abs(w))) / 127.0)
    assert int8_max_err <= int8_bound, (int8_max_err, int8_bound)

    fp32_stats = fp32_store.stats()
    int8_stats = int8_store.stats()
    quant_ratio = int8_stats["nvme_bytes"] / max(fp32_stats["nvme_bytes"],
                                                 1)
    assert quant_ratio < 0.5, f"int8 tier not smaller: {quant_ratio}"
    assert fp32_stats["hbm_bytes"] <= hbm_budget, fp32_stats
    assert fp32_stats["evictions"] > 0, "budget never forced an eviction"
    assert fp32_stats["prefetch_hits"] > fp32_stats["prefetch_misses"], \
        fp32_stats
    committed = sum(
        int(s.validate()[0] == resilience.COMMITTED)
        for s in (fp32_store, int8_store))
    assert committed == 2, "tier dirs did not fsck COMMITTED"

    fp32_store.publish_gauges()
    int8_store.publish_gauges()
    tel.close()
    _telmod._telemetry = _saved

    repo = os.path.dirname(os.path.abspath(__file__))
    scripts_dir = os.path.join(repo, "scripts")
    sp_ = importlib.util.spec_from_file_location(
        "check_telemetry_schema",
        os.path.join(scripts_dir, "check_telemetry_schema.py"))
    checker = importlib.util.module_from_spec(sp_)
    sp_.loader.exec_module(checker)
    stream = os.path.join(tmp, "tiered", "events.jsonl")
    stream_problems = checker.validate_file(stream)
    with open(stream) as f:
        events = [json.loads(line) for line in f if line.strip()]
    tier_gauges = sum(1 for ev in events if ev.get("kind") == "gauge"
                      and str(ev.get("name", "")).startswith("tier/"))
    assert tier_gauges >= len(checker.TIER_GAUGES), tier_gauges

    # ledger + perf-diff rehearsal on a scratch ledger (two runs so the
    # diff has a median to gate against)
    check_ledger = os.path.join(tmp, "ledger.jsonl")
    with open(check_ledger, "w") as f:
        for run in ("run-a", "run-b"):
            for metric, value in (("fp32_pass_s", fp32_s / passes),
                                  ("int8_pass_s", int8_s / passes),
                                  ("quant_ratio", quant_ratio)):
                f.write(json.dumps(
                    {"ts": time.time(), "run": run, "bench": "cpu_tiered",
                     "metric": metric, "value": value}) + "\n")

    def _rc(argv):
        try:
            return sp.run([sys.executable] + argv, capture_output=True,
                          timeout=60).returncode
        except Exception:
            return -1

    ledger_gate_rc = _rc([os.path.join(scripts_dir,
                                       "check_telemetry_schema.py"),
                          "--ledger", check_ledger])
    perf_diff_rc = _rc([os.path.join(scripts_dir, "ds_perf_diff.py"),
                        check_ledger, "--check"])
    assert ledger_gate_rc == 0, f"--ledger gate rc={ledger_gate_rc}"
    assert perf_diff_rc == 0, f"ds_perf_diff --check rc={perf_diff_rc}"

    return {
        "layers": layers,
        "model_mib": round(model_bytes / 2**20, 3),
        "hbm_budget_mib": round(hbm_budget / 2**20, 3),
        "passes": passes,
        "fp32_pass_s": round(fp32_s / passes, 4),
        "int8_pass_s": round(int8_s / passes, 4),
        "fp32_bit_identical_layers": exact,
        "int8_max_err": round(int8_max_err, 6),
        "int8_err_bound": round(int8_bound, 6),
        "quant_ratio": round(quant_ratio, 4),
        "prefetch_hit_rate": fp32_stats["prefetch_hit_rate"],
        "evictions": fp32_stats["evictions"],
        "manifests_committed": committed,
        "tier_gauges_emitted": tier_gauges,
        "events_ok": not stream_problems,
        "ledger_gate_rc": ledger_gate_rc,
        "perf_diff_rc": perf_diff_rc,
        "note": "16-layer stack 4x over a simulated HBM budget streamed "
                "via host+NVMe tiers with depth-1 prefetch: fp32 "
                "bit-identical, int8 inside the absmax/127 block bound "
                "at ~4x smaller NVMe tier, dirs sealed COMMITTED, "
                "tier/* gauges schema-valid",
    }


def _worker_tiered(spec):
    print(json.dumps(_tiered_bench(spec)))


# ---------------------------------------------------------------------------
# parent orchestration
# ---------------------------------------------------------------------------

def _run_worker(name, spec=None, timeout=600, cpu=False, reserve=45):
    # never let one worker spend past the global budget (the driver kills
    # the whole run at its own deadline — a partial result beats rc=124);
    # with the budget exhausted, don't launch at all: the max(...) floor
    # would otherwise keep granting 30s slices past the deadline.
    # ``reserve``: callers of cheap steps pass a small reserve so they are
    # not starved out of the budget entirely
    if _remaining() < reserve:
        return None, "budget exhausted"
    # never grant a slice that outlives the budget: below 35s remaining the
    # 30s floor would push a hung subprocess past the global deadline
    timeout = min(timeout, max(5, _remaining() - 5))
    if _remaining() >= 35:
        timeout = max(30, timeout)
    cmd = [sys.executable, os.path.abspath(__file__), "--worker", name]
    cmd.append(json.dumps(spec) if spec is not None else "null")
    if cpu:
        cmd.append("--cpu")
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout)
    except subprocess.TimeoutExpired:
        return None, "timeout"
    if out.returncode != 0:
        return None, (out.stderr or "")[-2000:]
    for line in reversed(out.stdout.strip().splitlines()):
        try:
            return json.loads(line), None
        except json.JSONDecodeError:
            continue
    return None, "no json in worker output"


def _attach_dispatch(out):
    """Attach the async-pipeline micro-bench under the stable key
    ``cpu_dispatch`` (runs on CPU).  Budget-gated; a failure is recorded
    in notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "dispatch", {}, timeout=max(60, min(240, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_dispatch"] = res
    else:
        out.setdefault("notes", {})["dispatch"] = (err or "")[:200]
    return out


def _attach_serving(out):
    """Attach the serving-overload micro-bench under the stable key
    ``cpu_serving`` (CPU-runnable like the dispatch bench).  Budget-gated;
    a failure is recorded in notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "serving", {}, timeout=max(60, min(240, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_serving"] = res
    else:
        out.setdefault("notes", {})["serving"] = (err or "")[:200]
    return out


def _attach_serving_prefix(out):
    """Attach the prefix-cache micro-bench under the stable key
    ``cpu_serving_prefix`` (CPU-runnable: hit rate / pages saved).
    Budget-gated; a failure is recorded in notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "serving_prefix", {},
        timeout=max(60, min(240, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_serving_prefix"] = res
    else:
        out.setdefault("notes", {})["serving_prefix"] = (err or "")[:200]
    return out


def _attach_serving_attn(out):
    """Attach the serving-attention micro-bench under the stable key
    ``cpu_serving_attn`` (CPU-runnable: jnp gather vs interpret-mode
    ragged kernel on a mixed batch, equivalence + analytic roofline).
    Budget-gated; a failure is recorded in notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "serving_attn", {},
        timeout=max(60, min(240, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_serving_attn"] = res
    else:
        out.setdefault("notes", {})["serving_attn"] = (err or "")[:200]
    return out


def _attach_serving_slo(out):
    """Attach the serving-SLO micro-bench under the stable key
    ``cpu_serving_slo`` (CPU-runnable: TTFT/TPOT/e2e percentiles, SLO
    attainment, exporter scrape proof).  Budget-gated; a failure is
    recorded in notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "serving_slo", {},
        timeout=max(60, min(240, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_serving_slo"] = res
    else:
        out.setdefault("notes", {})["serving_slo"] = (err or "")[:200]
    return out


def _attach_serving_sched(out):
    """Attach the scheduler micro-bench under the stable key
    ``cpu_serving_sched`` (CPU-runnable: chat TTFT p99 monolithic vs
    chunked vs chunked+speculative on a simulated dispatch clock, decode
    tokens-per-step, spec acceptance, cross-policy bit-identity).
    Budget-gated; a failure is recorded in notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "serving_sched", {},
        timeout=max(60, min(300, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_serving_sched"] = res
    else:
        out.setdefault("notes", {})["serving_sched"] = (err or "")[:200]
    return out


def _attach_comm_census(out):
    """Attach the distributed-telemetry micro-bench under the stable key
    ``cpu_comm_census`` (CPU-runnable: simulated 4-rank shard run,
    bandwidth accounting vs hand-computed, straggler verdict, checker
    validation).  Budget-gated; a failure is recorded in notes, never
    fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "comm_census", {},
        timeout=max(60, min(240, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_comm_census"] = res
    else:
        out.setdefault("notes", {})["comm_census"] = (err or "")[:200]
    return out


def _attach_comm_quant(out):
    """Attach the quantized-collective micro-bench under the stable key
    ``cpu_comm_quant`` (CPU-runnable: 4-rank shard_map grad reduce, fp32
    vs blockwise int8, bytes-saved ratio vs the analytic model, codec
    error bound, checker-validated annotated events).  Budget-gated; a
    failure is recorded in notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "comm_quant", {},
        timeout=max(60, min(240, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_comm_quant"] = res
    else:
        out.setdefault("notes", {})["comm_quant"] = (err or "")[:200]
    return out


def _attach_compile_churn(out):
    """Attach the profiling-plane micro-bench under the stable key
    ``cpu_compile_churn`` (CPU-runnable: shape-churned jit workload,
    compile/* event validation, storm verdict, /metrics + /healthz
    scrape).  Budget-gated; a failure is recorded in notes, never
    fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "compile_churn", {},
        timeout=max(60, min(240, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_compile_churn"] = res
    else:
        out.setdefault("notes", {})["compile_churn"] = (err or "")[:200]
    return out


def _attach_fleet(out):
    """Attach the fleet-failover micro-bench under the stable key
    ``cpu_fleet`` (CPU-runnable: aggregate throughput vs replica count,
    per-replica prefix hit rates, and kill-recovery cost).  Budget-gated;
    a failure is recorded in notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "fleet", {},
        timeout=max(60, min(300, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_fleet"] = res
    else:
        out.setdefault("notes", {})["fleet"] = (err or "")[:200]
    return out


def _attach_fleet_disagg(out):
    """Attach the disaggregated-fleet micro-bench under the stable key
    ``cpu_fleet_disagg`` (CPU-runnable: chat TTFT p99 unified vs
    prefill/decode-specialised, migrated vs dedup-skipped page counts,
    zero-loss + bit-identity).  Budget-gated; a failure is recorded in
    notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "fleet_disagg", {},
        timeout=max(60, min(300, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_fleet_disagg"] = res
    else:
        out.setdefault("notes", {})["fleet_disagg"] = (err or "")[:200]
    return out


def _attach_fleet_xproc(out):
    """Attach the cross-process-fleet micro-bench under the stable key
    ``cpu_fleet_xproc`` (CPU-runnable: tokens/fleet-step in-process vs
    real worker processes over the socket transport, kill -9 recovery
    latency, zero-loss + survivors bit-identical).  Budget-gated; a
    failure is recorded in notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "fleet_xproc", {},
        timeout=max(60, min(300, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_fleet_xproc"] = res
    else:
        out.setdefault("notes", {})["fleet_xproc"] = (err or "")[:200]
    return out


def _attach_fleet_chaos(out):
    """Attach the chaos-recovery micro-bench under the stable key
    ``cpu_fleet_chaos`` (CPU-runnable: gate-10 wire-fault scenarios —
    ack loss, slow worker breaker trip, torn commit — per-scenario
    recovery wall time, retry/breaker/dedup counters, zero-loss +
    bit-identity asserted inside each scenario).  Budget-gated; a
    failure is recorded in notes, never fatal."""
    if _remaining() < 120:
        return out
    res, err = _run_worker(
        "fleet_chaos", {},
        timeout=max(90, min(360, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_fleet_chaos"] = res
    else:
        out.setdefault("notes", {})["fleet_chaos"] = (err or "")[:200]
    return out


def _attach_incident(out):
    """Attach the incident-plane micro-bench under the stable key
    ``cpu_incident`` (CPU-runnable: ring-buffer record overhead, injected
    storm + deadline workload -> bundle chain, /incidents scrape).
    Budget-gated; a failure is recorded in notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "incident", {},
        timeout=max(60, min(240, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_incident"] = res
    else:
        out.setdefault("notes", {})["incident"] = (err or "")[:200]
    return out


def _attach_step_attr(out):
    """Attach the attribution-plane micro-bench under the stable key
    ``cpu_step_attr`` (CPU-runnable: record-tap/decompose pricing, the
    analytic 4-rank exposed-comm fraction check, and one fake-clock
    migrated request whose stage sum must equal e2e).  Budget-gated; a
    failure is recorded in notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "step_attr", {},
        timeout=max(60, min(240, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_step_attr"] = res
    else:
        out.setdefault("notes", {})["step_attr"] = (err or "")[:200]
    return out


def _attach_overlap(out):
    """Attach the comm/compute-overlap micro-bench under the stable key
    ``cpu_overlap`` (CPU-runnable: simulated 4-rank shard_map ZeRO-3 run,
    serial vs double-buffered schedule with a bit-identical 50-step loss
    trajectory, analytic exposed-comm-fraction drop cross-checked through
    the interval algebra, frozen overlap gauges schema-validated).
    Budget-gated; a failure is recorded in notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "overlap", {},
        timeout=max(60, min(300, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_overlap"] = res
    else:
        out.setdefault("notes", {})["overlap"] = (err or "")[:200]
    return out


def _attach_tiered(out):
    """Attach the tiered-memory micro-bench under the stable key
    ``cpu_tiered`` (CPU-runnable: layer stack 4x over a simulated HBM
    budget streamed through host/NVMe tiers, fp32 bit-identical vs int8
    error-bounded, manifest fsck, tier/* gauges schema-validated, ledger
    + perf-diff rehearsal).  Budget-gated; a failure is recorded in
    notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "tiered", {},
        timeout=max(60, min(300, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_tiered"] = res
    else:
        out.setdefault("notes", {})["tiered"] = (err or "")[:200]
    return out


def _attach_autotune(out):
    """Attach the closed-loop autotuner micro-bench under the stable key
    ``cpu_autotune`` (CPU-runnable: end-to-end tune over a serving knob
    grid on the simulated dispatch clock, tuned-vs-default verdict,
    overlay round-trip, tune/ledger/perf-diff gate rcs).  Budget-gated;
    a failure is recorded in notes, never fatal."""
    if _remaining() < 90:
        return out
    res, err = _run_worker(
        "autotune", {},
        timeout=max(60, min(300, int(_remaining()) - 10)),
        cpu=True, reserve=20)
    if res:
        out["cpu_autotune"] = res
    else:
        out.setdefault("notes", {})["autotune"] = (err or "")[:200]
    return out


def _append_ledger(out):
    """Append this run's numeric bench metrics to the perf-regression
    ledger (``BENCH_LEDGER`` env override; default BENCH_LEDGER.jsonl
    next to this file).  One row per (bench, metric) scalar — the frozen
    row schema lives in scripts/check_telemetry_schema.py (--ledger) and
    scripts/ds_perf_diff.py gates later runs against the medians.  Best
    effort: a read-only checkout must not fail the bench."""
    path = os.environ.get(
        "BENCH_LEDGER",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_LEDGER.jsonl"))
    ts = time.time()
    run = f"run-{int(ts)}"
    rows = []

    def _rows_from(bench, rec):
        for metric, value in rec.items():
            if isinstance(value, bool) or not isinstance(value,
                                                         (int, float)):
                continue
            rows.append({"ts": ts, "run": run, "bench": bench,
                         "metric": metric, "value": value})

    if isinstance(out.get("value"), (int, float)) and out.get("metric"):
        rows.append({"ts": ts, "run": run, "bench": "train",
                     "metric": str(out["metric"]),
                     "value": float(out["value"]),
                     "unit": str(out.get("unit", ""))})
    for key, rec in out.items():
        if key.startswith("cpu_") and isinstance(rec, dict):
            _rows_from(key, rec)
    if not rows:
        return out
    try:
        with open(path, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
        out["ledger"] = {"path": path, "run": run, "rows": len(rows)}
    except OSError as e:
        out.setdefault("notes", {})["ledger"] = str(e)[:200]
    return out


def _fail(reason):
    """One line on stderr, non-zero exit: there is no result to print."""
    sys.exit("bench.py: " + " ".join(str(reason).split())[-400:])


def main():
    errors = {}

    # 1. backend probe: a measurement path that finds no chip fails
    probe, err = _run_worker("probe", timeout=150)
    if not probe:
        _fail(f"backend probe failed: {err}")
    if probe["platform"] != "tpu":
        _fail(f"no TPU: jax found platform {probe['platform']!r}")

    kind = probe.get("kind", "")
    n_chips = max(1, probe.get("n_devices", 1))
    peak = _lookup(_PEAK_TFLOPS, kind, None)
    hbm = probe.get("hbm") or _lookup(_HBM_FALLBACK, kind, None)
    if peak is None or hbm is None:
        _fail(f"unknown device_kind {kind!r}: add it to the chip tables")

    # 2. best-known single-chip config first: gpt_1b (1.01B params) with
    # bf16 Adam moments (SR) + bf16 grad accum — the full >=1B train state
    # fits one 16 GB chip with NO host offload (ONCHIP_r03/big_1b.json,
    # 2026-07-31).  The footprint-driven ladder follows if it does not run
    # (e.g. smaller-HBM chip).
    train, name, spec = None, None, None
    if hbm >= 15e9 and n_chips == 1:
        name = "gpt_1b"
        kw = dict(vocab_size=50304, hidden_size=2048, n_layers=18,
                  n_heads=16, max_seq_len=1024, activation="gelu",
                  use_rmsnorm=False, use_rope=False, tie_embeddings=True)
        spec = {"model": kw, "batch": 2, "seq": 1024, "steps": 12,
                "remat": True, "gas": 4, "zero": {"stage": 3},
                "moment_dtype": "bfloat16", "grad_accum_dtype": "bfloat16"}
        train, err = _run_worker("train", spec, timeout=1800)
        if not train:
            errors["train_gpt_1b"] = err

    # 2b. footprint-driven ladder --------------------------------------
    if not train:
        seq, steps = 1024, 12
        choice = None
        for lname, kw in _LADDER:
            batch = 8 * n_chips
            while batch >= n_chips and \
                    _footprint(kw, batch, seq, n_chips) > 0.82 * hbm:
                batch //= 2
            if batch >= n_chips:
                choice = (lname, kw, batch)
                break
        if choice is None:
            choice = ("gpt2_125m", dict(_LADDER[-1][1]), 1)
        name, kw, batch = choice
        # gas=4 fuses four microbatches into one dispatch
        spec = {"model": kw, "batch": batch, "seq": seq, "steps": steps,
                "remat": True, "gas": 4, "zero": {"stage": 3}}
        train, err = _run_worker("train", spec, timeout=1800)
        if not train:
            errors[f"train_{name}"] = err
    if not train:
        # one retry, one rung down, shorter leash (don't walk the whole
        # ladder at 1800 s each)
        idx = [n for n, _ in _LADDER].index(name)
        if idx + 1 < len(_LADDER):
            smaller, kw2 = _LADDER[idx + 1]
            train, err = _run_worker("train", dict(spec, model=kw2),
                                     timeout=900)
            if train:
                name = smaller
            else:
                errors[f"train_{smaller}"] = err
    if not train:
        _fail(f"no training attempt succeeded: {errors}")

    tps = train["tokens_per_sec"]
    n_params = train["n_params"]
    tflops = 6.0 * n_params * tps / 1e12 / n_chips

    # 3. max-params-on-one-chip probe (param-stream) --------------------
    max_params = None
    max_params_kind = None
    # with param-stream the stack lives on the HOST: the binding
    # constraint is host RAM at 16 B/param (fp32 master + 2 fp32
    # moments + bf16 mirror + bf16 grad accum), not HBM
    try:
        host_ram = (os.sysconf("SC_PHYS_PAGES") *
                    os.sysconf("SC_PAGE_SIZE"))
    except (ValueError, OSError):
        host_ram = 64e9
    analytic = int(0.8 * host_ram / 16.0)
    if _remaining() > 150:
        # short seq: the probe establishes the model FITS and steps;
        # long-seq throughput is the training bench's job
        for frac in (0.75, 0.55):
            target = int(analytic * frac)
            # scale a GPT shape to the target count: params ~ 12 L d^2
            d = 4096
            L = max(4, int(target / (12 * d * d)))
            probe_kw = dict(vocab_size=50304, hidden_size=d, n_layers=L,
                            n_heads=32, max_seq_len=1024,
                            activation="gelu", use_rmsnorm=False,
                            use_rope=False, tie_embeddings=True)
            res, err = _run_worker(
                "params_probe", {"model": probe_kw, "seq": 256},
                timeout=420)
            if res and res.get("ok"):
                max_params, max_params_kind = res["n_params"], "measured"
                break
            errors[f"params_probe_{frac}"] = err
            if _remaining() < 150:
                break
    if max_params is None:
        # probes couldn't run to completion in budget: report the
        # analytic bound, clearly labeled (never passed off as measured)
        max_params, max_params_kind = analytic, "analytic"

    result = {
        "metric": f"train_tokens_per_sec_per_chip_{name}_bf16_zero3_seq"
                  f"{spec['seq']}",
        "value": round(tps / n_chips, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tflops / 50.0, 3),
        "model_tflops_per_chip": round(tflops, 1),
        "n_params": n_params,
        "device_kind": kind,
        "n_chips": n_chips,
    }
    for k in ("moment_dtype", "grad_accum_dtype"):
        if spec.get(k):
            result[k] = spec[k]
    result["mfu"] = round(tflops / peak, 4)
    result["peak_tflops_bf16"] = peak
    result["max_params_single_chip"] = max_params
    result["max_params_kind"] = max_params_kind
    if errors:
        result["notes"] = {k: (v or "")[:200] for k, v in errors.items()}
    print(json.dumps(_append_ledger(_attach_tiered(_attach_overlap(_attach_autotune(_attach_step_attr(_attach_incident(_attach_fleet_chaos(_attach_fleet_xproc(_attach_fleet_disagg(_attach_fleet(_attach_compile_churn(_attach_comm_quant(_attach_comm_census(_attach_serving_sched(_attach_serving_slo(_attach_serving_attn(_attach_serving_prefix(_attach_serving(_attach_dispatch(result)))))))))))))))))))))


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--worker":
        which = sys.argv[2]
        spec = json.loads(sys.argv[3]) if len(sys.argv) > 3 else None
        import jax
        from deepspeed_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        if "--cpu" in sys.argv:
            jax.config.update("jax_platforms", "cpu")
        if which == "probe":
            _worker_probe()
        elif which == "train":
            _worker_train(spec)
        elif which == "params_probe":
            _worker_params_probe(spec)
        elif which == "dispatch":
            _worker_dispatch(spec)
        elif which == "serving":
            _worker_serving(spec)
        elif which == "serving_prefix":
            _worker_serving_prefix(spec)
        elif which == "fleet":
            _worker_fleet(spec)
        elif which == "fleet_disagg":
            _worker_fleet_disagg(spec)
        elif which == "fleet_xproc":
            _worker_fleet_xproc(spec)
        elif which == "fleet_chaos":
            _worker_fleet_chaos(spec)
        elif which == "serving_attn":
            _worker_serving_attn(spec)
        elif which == "serving_slo":
            _worker_serving_slo(spec)
        elif which == "serving_sched":
            _worker_serving_sched(spec)
        elif which == "comm_census":
            _worker_comm_census(spec)
        elif which == "comm_quant":
            _worker_comm_quant(spec)
        elif which == "compile_churn":
            _worker_compile_churn(spec)
        elif which == "incident":
            _worker_incident(spec)
        elif which == "step_attr":
            _worker_step_attr(spec)
        elif which == "autotune":
            _worker_autotune(spec)
        elif which == "overlap":
            _worker_overlap(spec)
        elif which == "tiered":
            _worker_tiered(spec)
        else:
            raise SystemExit(f"unknown worker {which}")
    else:
        main()
