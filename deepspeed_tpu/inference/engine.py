"""Inference engine.

Parity: reference ``inference/engine.py:35`` (``InferenceEngine``: dtype
conversion, TP group creation ``_create_model_parallel_group:201``, kernel
injection ``_apply_injection_policy:349``, CUDA-graph capture ``:479``,
``forward:541``, ``_generate:571``).

TPU design: "kernel injection" and "CUDA graphs" collapse into jitting the
decode step — XLA compiles the whole token step into one program (the graph)
with fused kernels.  Auto-TP is a sharding plan: model ``tp_rules`` place the
weights over the ``tp`` axis and XLA inserts the row-parallel all-reduces the
reference performs explicitly after attention/MLP.  The KV cache is a
static-shape ring buffer (``ops/decode_attention.py``) so decode never
retraces.
"""

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu import comm as dist
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.monitor.telemetry import in_setup_span
from deepspeed_tpu.ops.decode_attention import init_cache
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.parallel.topology import TP_AXIS, TopologyConfig
from deepspeed_tpu.runtime.zero.stage_plan import ZeroShardingPlan
from deepspeed_tpu.utils.logging import log_dist, logger

DTYPES = {"float32": jnp.float32, "fp32": jnp.float32,
          "float16": jnp.float16, "fp16": jnp.float16, "half": jnp.float16,
          "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
          "int8": jnp.int8}


class InferenceEngine:
    """Wraps a model (our ``CausalTransformerLM`` or any object exposing
    ``apply_with_cache``/``init_caches``) for sharded generation."""

    @in_setup_span("setup/engine", kind="inference")
    def __init__(self, model, config: DeepSpeedInferenceConfig, params=None,
                 mesh=None):
        self.module = model
        self._config = config
        self.dtype = DTYPES.get(str(config.dtype), jnp.bfloat16)

        dist.init_distributed()
        # TP mesh (reference _create_model_parallel_group)
        if mesh is None:
            tp = max(1, config.tp_size)
            mesh = groups.initialize_mesh(
                TopologyConfig(tp=tp, fsdp=-1))
        self.mesh = mesh

        self.params = None
        self._streaming = False
        if params is None and config.checkpoint:
            params = self.load_model_with_checkpoint(config.checkpoint)
        if params is not None:
            self.set_params(params)
        elif hasattr(model, "params"):
            self.set_params(model.params)

        self._compiled_prefill = None
        self._compiled_decode = None
        self._compiled_generate = {}
        log_dist(f"InferenceEngine ready: dtype={self.dtype.__name__} "
                 f"tp={config.tp_size} mesh={dict(self.mesh.shape)}", ranks=[0])

    # ------------------------------------------------------------------
    @in_setup_span("setup/engine/weights")
    def set_params(self, params):
        """Cast + shard weights (reference dtype convert + weight slicing in
        module_inject; here: device_put with TP/fsdp shardings).

        With int8/quantized configs the weights are stored groupwise int8 +
        scales (reference ``GroupQuantizer``/ZeroQuant weight-only path) and
        dequantised inside the jitted step — XLA fuses the dequant into the
        consuming matmul, so HBM holds 1 byte/weight."""
        tp_rules = (self.module.tp_rules()
                    if hasattr(self.module, "tp_rules") else None)
        # stage-3-style sharding over fsdp for memory, + tp rules: this is
        # ZeRO-Inference (reference engine.py:1581 offload-for-inference)
        plan = ZeroShardingPlan(self.mesh, stage=3, tp_rules=tp_rules,
                                param_persistence_threshold=0)
        self.plan = plan
        # quant policy resolved ONCE, before the offload branch, so the
        # streaming and dense paths cannot disagree (and the int8→bf16
        # compute-dtype fix lands before any np_dtype derivation)
        qc = self._config.quant
        self._quantized = bool(qc.enabled) or str(
            self._config.dtype) in ("int8", "torch.int8")
        if self._quantized:
            self._quant_bits = int(qc.num_bits)
            self._quant_group_size = int(qc.group_size)
            if self.dtype == jnp.int8:      # int8 stores, bf16 computes
                self.dtype = jnp.bfloat16
        offload = dict(self._config.zero or {}).get("offload_param") or {}
        if offload.get("device") in ("cpu", "nvme"):
            return self._set_params_streaming(params, offload)
        if self._quantized:
            cast = self._quantize_tree(params)
        else:
            cast = jax.tree_util.tree_map(
                lambda x: x.astype(self.dtype)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
                else jnp.asarray(x), params)
        with self.mesh:
            self.params = jax.device_put(cast, plan.param_shardings(cast))

    # ---- ZeRO-Inference weight streaming ------------------------------
    def _set_params_streaming(self, params, offload):
        """ZeRO-Inference for models larger than HBM: transformer-layer
        weights live on the host (or NVMe) and stream to the device
        layer-by-layer, double-buffered so the transfer of layer i+1
        overlaps layer i's compute (reference: ZeRO-3 param offload reused
        for inference, docs 2022-09-10-zero-inference.md)."""
        assert all(hasattr(self.module, name)
                   for name in ("config", "embed", "block", "logits")), \
            "weight streaming needs a CausalTransformerLM-style module"
        c = self.module.config
        np_dtype = np.dtype(jnp.bfloat16 if self.dtype == jnp.bfloat16
                            else np.float32)
        # int8 weight streaming (quant policy resolved by set_params): the
        # per-layer H2D upload is THE bottleneck of streamed inference —
        # groupwise int8 + scales halves it vs bf16 (reference:
        # ZeRO-Inference composes with ZeroQuant weight quantization for
        # exactly this reason).  int8 composes with NVMe too: the tiered
        # store keeps qv/qs/qz as separate manifest-listed files, so the
        # per-group scale sidecars survive the disk round trip.

        def host_cast(x):
            x = np.asarray(x)
            return x.astype(np_dtype) \
                if jnp.issubdtype(x.dtype, jnp.floating) else x

        def host_leaf(k, x):
            """One layer leaf: quantize matmul weights when int8 streaming
            is on (on the HOST backend), cast the rest."""
            x = np.asarray(x)
            if self._quantized and \
                    jnp.issubdtype(x.dtype, jnp.floating) and \
                    self._is_linear_weight([k], x):
                from deepspeed_tpu.ops.quantizer import quantize
                groups = (x.size // self._quant_group_size
                          if x.size % self._quant_group_size == 0 else 1)
                with jax.default_device(jax.devices("cpu")[0]):
                    qt = quantize(x, groups=max(1, groups),
                                  num_bits=self._quant_bits)
                return {"qv": np.asarray(qt.values),
                        "qs": np.asarray(qt.scale),
                        "qz": np.asarray(qt.zero_point)}
            return host_cast(x)

        layers = params["layers"]
        assert not isinstance(layers, (list, tuple)), \
            "streaming expects the stacked-layer layout"
        self._n_layers = c.n_layers
        host_layers = [
            {k: host_leaf(k, v[i]) for k, v in layers.items()}
            for i in range(c.n_layers)]
        self._tiered = None
        if offload.get("device") == "nvme":
            from deepspeed_tpu.runtime.tiered_store import (PlacementPolicy,
                                                            TieredStore)
            # read-only placement over the tiered store: every layer leaf
            # is one NVMe entry (int8 leaves are {qv,qs,qz} multi-file
            # entries — the scale sidecars land in the manifest), and the
            # store seals the directory with the checkpoint protocol's
            # manifest + marker so ds_ckpt_fsck classifies a torn weight
            # file before it serves garbage tokens
            self._tiered = TieredStore(
                name="zero_inference_params",
                nvme_dir=str(offload.get("nvme_path") or "/tmp"),
                policy=PlacementPolicy(default_tier="nvme", read_only=True),
                aio_config=dict(offload.get("aio") or {}))
            self._layer_keys = [sorted(host_layers[0].keys())] * c.n_layers
            for i, hl in enumerate(host_layers):
                for k, v in hl.items():
                    self._tiered.put(f"L{i}.{k}", v, tier="nvme")
            self._tiered.commit()
            self._host_layers = None
            log_dist(f"ZeRO-Inference: {c.n_layers} layers on NVMe at "
                     f"{self._tiered.nvme_path}", ranks=[0])
        else:
            self._host_layers = host_layers
            log_dist(f"ZeRO-Inference: {c.n_layers} layers in host RAM",
                     ranks=[0])

        rest = {k: v for k, v in params.items() if k != "layers"}
        cast = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x).astype(self.dtype)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
            else jnp.asarray(x), rest)
        with self.mesh:
            self.params = jax.device_put(cast,
                                         self.plan.param_shardings(cast))
        self._streaming = True
        self._jit_layer = None
        self._jit_embed = None
        self._jit_head = None

    def _layer_entry_keys(self, i):
        return [f"L{i}.{k}" for k in self._layer_keys[i]]

    def _issue_layer_reads(self, i):
        """Queue async NVMe reads for layer ``i`` (they run while the
        device crunches earlier layers)."""
        if self._tiered is None or not (0 <= i < self._n_layers):
            return
        self._tiered.prefetch(self._layer_entry_keys(i))

    def _fetch_layer(self, i):
        """Host/NVMe → device.  Host path: device_put returns before the
        transfer completes, so it overlaps compute.  NVMe path: reads were
        issued earlier by ``_issue_layer_reads`` (a cold fetch is a demand
        miss the ``tier/*`` gauges expose) and land here, after the
        previous layer's compute was dispatched."""
        if self._host_layers is not None:
            return jax.device_put(self._host_layers[i])
        keys = self._layer_entry_keys(i)
        self._issue_layer_reads(i)
        host = self._tiered.fetch_group(keys)
        dev = jax.device_put(host)
        for k in keys:
            # drop staging caches so host RAM holds at most the prefetch
            # window, not the model — the NVMe files stay authoritative
            self._tiered.evict(k)
        return dev

    def _streaming_apply_with_cache(self, input_ids, caches):
        """Layer-streamed twin of ``CausalTransformerLM.apply_with_cache``,
        made of the same embedding, block and head (list-of-caches layout;
        weights fetched per layer)."""
        model = self.module
        input_ids = jnp.asarray(input_ids, jnp.int32)
        start = caches[0].length

        if self._jit_embed is None:
            def embed(rest, ids, start):
                positions = start + jnp.broadcast_to(
                    jnp.arange(ids.shape[1])[None, :], ids.shape)
                return model.embed(rest, ids, positions), positions
            self._jit_embed = jax.jit(embed)

            def layer_step(layer, x, cache, positions):
                layer = self._maybe_dequant(layer)   # int8 streams dequant
                x, cache, _ = model.block(x, layer, positions,
                                          model.mix_cached, cache,
                                          train=False)
                return x, cache
            self._jit_layer = jax.jit(layer_step)
            self._jit_head = jax.jit(model.logits)

        x, positions = self._jit_embed(self.params, input_ids, start)
        new_caches = []
        nxt = self._fetch_layer(0)
        self._issue_layer_reads(1)
        for i in range(self._n_layers):
            # dispatch layer i (async on device), THEN wait for layer
            # i+1's host/NVMe transfer — so I/O overlaps compute
            x, cache = self._jit_layer(nxt, x, caches[i], positions)
            new_caches.append(cache)
            if i + 1 < self._n_layers:
                nxt = self._fetch_layer(i + 1)
                self._issue_layer_reads(i + 2)
        if self._tiered is not None:
            self._tiered.publish_gauges()
        return self._jit_head(self.params, x), new_caches

    def _streaming_generate(self, input_ids, max_new_tokens):
        c = self.module.config
        input_ids = jnp.asarray(input_ids, jnp.int32)
        B, S = input_ids.shape
        caches = [init_cache(B, S + max_new_tokens, c.kv_heads, c.head_dim,
                             self.dtype) for _ in range(self._n_layers)]
        logits, caches = self._streaming_apply_with_cache(input_ids, caches)
        toks = [jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)]
        for _ in range(max_new_tokens - 1):
            logits, caches = self._streaming_apply_with_cache(
                toks[-1][:, None], caches)
            toks.append(jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32))
        return jnp.concatenate([input_ids] +
                               [t[:, None] for t in toks], axis=1)

    # ---- weight-only quantization ------------------------------------
    @staticmethod
    def _is_qleaf(x):
        return isinstance(x, dict) and "qv" in x and "qs" in x

    @staticmethod
    def _is_linear_weight(path, x):
        """Weight-only quantization targets matmul weights only — the
        reference ZeroQuant path never quantizes norm scales/biases or
        embeddings (doing so needlessly degrades accuracy)."""
        name = str(path[-1]).strip("'[]") if path else ""
        lname = name.lower()
        if "norm" in lname or "embed" in lname or lname.endswith("_b") \
                or "bias" in lname:
            return False
        if lname == "wg":
            # MoE router gate: kept fp32 by the model for routing
            # precision — quantizing it can flip expert assignments
            return False
        # stacked layout: linear weights are [L, in, out] (3-D) or plain
        # [in, out] (2-D, e.g. lm_head / per-layer MoE dicts)
        return x.ndim >= 2

    def _quantize_tree(self, params):
        from deepspeed_tpu.ops.quantizer import quantize

        def q(path, x):
            x = jnp.asarray(x)
            if not jnp.issubdtype(x.dtype, jnp.floating):
                return x
            name = (str(path[-1]).strip("'[]") if path else "").lower()
            if name == "wg":
                return x  # router gate stays in its fp32 compute dtype
            if self._is_linear_weight(path, x):
                groups = (x.size // self._quant_group_size
                          if x.size % self._quant_group_size == 0 else 1)
                qt = quantize(x, groups=max(1, groups),
                              num_bits=self._quant_bits)
                return {"qv": qt.values, "qs": qt.scale, "qz": qt.zero_point}
            return x.astype(self.dtype)
        return jax.tree_util.tree_map_with_path(q, params)

    def _maybe_dequant(self, params):
        """Inside-jit dequant of quantized leaves (fused by XLA)."""
        if not getattr(self, "_quantized", False):
            return params
        from deepspeed_tpu.ops.quantizer import QuantizedTensor, dequantize

        def dq(x):
            if self._is_qleaf(x):
                qt = QuantizedTensor(
                    values=x["qv"], scale=x["qs"], zero_point=x["qz"],
                    num_bits=self._quant_bits, group_shape=x["qv"].shape,
                    symmetric=True)
                return dequantize(qt, dtype=self.dtype)
            return x
        return jax.tree_util.tree_map(dq, params, is_leaf=self._is_qleaf)

    # ------------------------------------------------------------------
    def load_model_with_checkpoint(self, checkpoint: str):
        """Load weights from a training checkpoint dir (orbax layout) or a
        universal-checkpoint dir (reference ``load_model_with_checkpoint:292``
        sharded-checkpoint loading)."""
        import os
        if os.path.exists(os.path.join(checkpoint, "universal_meta.json")):
            from deepspeed_tpu.checkpoint import load_universal_checkpoint
            flat = load_universal_checkpoint(checkpoint)
            log_dist(f"loaded universal checkpoint: {len(flat)} tensors",
                     ranks=[0])
            template = (self.module.init(jax.random.key(0))
                        if hasattr(self.module, "init") else None)
            if template is not None:
                return load_universal_checkpoint(checkpoint,
                                                 template=template)
            return flat
        from deepspeed_tpu.checkpoint import load_checkpoint_tree
        state = load_checkpoint_tree(checkpoint)
        params = state.get("params", state)
        log_dist(f"loaded checkpoint params from {checkpoint}", ranks=[0])
        return params

    # ------------------------------------------------------------------
    def forward(self, input_ids, caches=None):
        """Single forward (prefill if caches empty).  Returns logits."""
        input_ids = jnp.asarray(input_ids)
        if self._streaming:
            if caches is None:
                c = self.module.config
                caches = [init_cache(input_ids.shape[0],
                                     self._config.max_out_tokens,
                                     c.kv_heads, c.head_dim, self.dtype)
                          for _ in range(self._n_layers)]
            with self.mesh:
                return self._streaming_apply_with_cache(input_ids, caches)
        if not hasattr(self.module, "apply_with_cache"):
            # encoder-style model (e.g. BertEncoder): plain forward
            if self._compiled_prefill is None:
                def enc(params, ids):
                    return self.module.apply(self._maybe_dequant(params),
                                             ids, train=False)
                self._compiled_prefill = jax.jit(enc)
            with self.mesh:
                return self._compiled_prefill(self.params, input_ids), None
        if caches is None:
            caches = self.module.init_caches(
                input_ids.shape[0], self._config.max_out_tokens, self.dtype)
        if self._compiled_prefill is None:
            def prefill(params, ids, caches):
                return self.module.apply_with_cache(
                    self._maybe_dequant(params), ids, caches)
            self._compiled_prefill = jax.jit(prefill)
        with self.mesh:
            logits, caches = self._compiled_prefill(self.params, input_ids, caches)
        return logits, caches

    __call__ = forward

    # ------------------------------------------------------------------
    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k: Optional[int] = None, seed=0, eos_token_id=None):
        """Greedy/temperature sampling decode loop, fully jitted: prefill once,
        then ``lax.scan`` over decode steps (the XLA analogue of the
        reference's CUDA-graph replay per token)."""
        input_ids = jnp.asarray(input_ids, jnp.int32)
        B, S = input_ids.shape
        if self._streaming:
            assert not temperature, \
                "weight-streaming generate is greedy-only"
            with self.mesh:
                return self._streaming_generate(input_ids, max_new_tokens)
        max_seq = S + max_new_tokens
        key = (max_new_tokens, bool(temperature), top_k, B, S)

        if key not in self._compiled_generate:
            def gen(params, ids, rng):
                params = self._maybe_dequant(params)
                caches = self.module.init_caches(B, max_seq, self.dtype)
                logits, caches = self.module.apply_with_cache(params, ids, caches)
                last = logits[:, -1]

                def sample(logits, rng):
                    if temperature and temperature > 0:
                        l = logits / temperature
                        if top_k:
                            kth = jnp.sort(l, axis=-1)[:, -top_k][:, None]
                            l = jnp.where(l < kth, -1e30, l)
                        return jax.random.categorical(rng, l)
                    return jnp.argmax(logits, axis=-1)

                def step(carry, _):
                    last_logits, caches, rng = carry
                    rng, sub = jax.random.split(rng)
                    tok = sample(last_logits, sub).astype(jnp.int32)
                    logits, caches = self.module.apply_with_cache(
                        params, tok[:, None], caches)
                    return (logits[:, -1], caches, rng), tok

                (_, _, _), toks = jax.lax.scan(
                    step, (last, caches, rng), None, length=max_new_tokens)
                return jnp.swapaxes(toks, 0, 1)  # [B, T_new]
            self._compiled_generate[key] = jax.jit(gen)

        with self.mesh:
            new_tokens = self._compiled_generate[key](
                self.params, input_ids, jax.random.key(seed))
        out = jnp.concatenate([input_ids, new_tokens], axis=1)
        if eos_token_id is not None:
            out = np.asarray(out)
            for b in range(out.shape[0]):
                hits = np.where(out[b, S:] == eos_token_id)[0]
                if hits.size:
                    out[b, S + hits[0] + 1:] = eos_token_id
        return out

    _generate = generate  # parity alias

    # ------------------------------------------------------------------
    def create_serving_engine(self, max_batch: int = 8,
                              page_size: int = 128,
                              num_pages: Optional[int] = None,
                              max_seq: int = 2048,
                              eos_token_id: Optional[Any] = None,
                              decode_chunk: int = 1, **kwargs):
        """Build a continuous-batching ``ServingEngine`` over this
        engine's model/params, wiring the config's ``serving`` hardening
        block (admission control, deadlines, load shedding, fault
        injection).  Not available for weight-streaming or quantized
        engines — the paged decode step consumes raw dense weights."""
        if self._streaming:
            raise NotImplementedError(
                "paged serving does not compose with ZeRO-Inference "
                "weight streaming")
        if getattr(self, "_quantized", False):
            raise NotImplementedError(
                "paged serving expects dense weights; disable weight-only "
                "quantization")
        from deepspeed_tpu.inference.serving import ServingEngine
        kwargs.setdefault("serving", getattr(self._config, "serving", None))
        return ServingEngine(self.module, self.params,
                             max_batch=max_batch, page_size=page_size,
                             num_pages=num_pages, max_seq=max_seq,
                             dtype=self.dtype, eos_token_id=eos_token_id,
                             tp_size=max(1, self._config.tp_size),
                             ep_size=max(1, self._config.ep_size),
                             decode_chunk=decode_chunk, **kwargs)

    # ------------------------------------------------------------------
    def profile_model_time(self, use_cuda_events=False):
        logger.warning("use jax.profiler for per-op timing")

    def destroy(self):
        self._compiled_prefill = None
        self._compiled_generate = {}
