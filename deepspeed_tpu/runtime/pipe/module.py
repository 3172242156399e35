"""PipelineModule — layer-spec model assembly for pipeline parallelism.

Parity: reference ``runtime/pipe/module.py`` (``LayerSpec``, ``TiedLayerSpec``,
``PipelineModule:88`` with ``partition_method`` uniform/parameters, tied
layers) and the partitioning helpers in ``runtime/utils.py``
(``partition_uniform``/``partition_balanced``).

TPU-first redesign: the reference assigns each stage's layers to a different
*process* and moves activations with p2p NCCL.  Here all stages live in one
SPMD program — stage assignment is a **sharding**: the homogeneous run of
layers (the transformer body) is stacked to ``[L, ...]`` leaves and the
leading dim is sharded over the ``pp`` mesh axis, ``L/P`` layers per stage.
Layers before/after the homogeneous body (embedding, final norm + head) run
unpipelined (their compute is replicated over ``pp``, sharded over the data
axes — they are a tiny fraction of FLOPs).

Tied layers (reference ``TiedLayerSpec``, e.g. embedding/LM-head weight
tying): tied params live once in ``params["tied"][key]`` and every consumer
reads them; gradient summation across uses is automatic under autodiff —
replacing the reference's ``ReduceTiedGrads`` all-reduce.
"""

import math
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.transformer import CausalTransformerLM, TransformerConfig
from deepspeed_tpu.parallel.topology import PP_AXIS, TP_AXIS
from deepspeed_tpu.runtime.pipe.pipeline import (pipeline_interleaved,
                                                 pipeline_spmd,
                                                 pipeline_train_1f1b,
                                                 stack_interleaved_params,
                                                 stack_stage_params)
from deepspeed_tpu.utils.logging import logger


# ----------------------------------------------------------------------
# Partitioning helpers (parity: reference runtime/utils.py)
# ----------------------------------------------------------------------
def partition_uniform(num_items: int, num_parts: int) -> List[int]:
    """Boundaries of ``num_parts`` near-equal chunks of ``num_items``."""
    parts = [0] * (num_parts + 1)
    chunk = num_items // num_parts
    rem = num_items % num_parts
    for p in range(num_parts):
        parts[p + 1] = parts[p] + chunk + (1 if p < rem else 0)
    return parts


def partition_balanced(weights: List[float], num_parts: int) -> List[int]:
    """Boundaries minimising the heaviest part (reference
    ``ds_utils.partition_balanced`` — binary search over the bottleneck)."""
    n = len(weights)
    prefix = [0.0]
    for w in weights:
        prefix.append(prefix[-1] + w)

    def parts_for(bottleneck: float) -> Optional[List[int]]:
        parts = [0]
        for _ in range(num_parts):
            start = parts[-1]
            # furthest end with sum <= bottleneck
            lo, hi = start, n
            while lo < hi:
                mid = (lo + hi + 1) // 2
                if prefix[mid] - prefix[start] <= bottleneck:
                    lo = mid
                else:
                    hi = mid - 1
            if lo == start and start < n:
                return None  # single item exceeds bottleneck
            parts.append(lo)
            if lo == n:
                break
        if parts[-1] != n:
            return None
        while len(parts) < num_parts + 1:
            parts.append(n)
        return parts

    lo = max(weights) if weights else 0.0
    hi = sum(weights)
    best = parts_for(hi)
    for _ in range(64):
        mid = (lo + hi) / 2
        cand = parts_for(mid)
        if cand is not None:
            best, hi = cand, mid
        else:
            lo = mid
    return best


# ----------------------------------------------------------------------
# Layer specs (parity: reference pipe/module.py LayerSpec/TiedLayerSpec)
# ----------------------------------------------------------------------
class LayerSpec:
    """Lazy layer constructor so a module list can be declared without
    building params (reference builds only the local stage's layers; we
    build all — they are shardings, not copies)."""

    def __init__(self, typename, *module_args, **module_kwargs):
        self.typename = typename
        self.module_args = module_args
        self.module_kwargs = module_kwargs
        if not issubclass(typename, object):
            raise RuntimeError("LayerSpec expects a class")

    def build(self, log=False):
        if log:
            logger.info(f"building {repr(self)}")
        return self.typename(*self.module_args, **self.module_kwargs)

    def __repr__(self):
        return f"LayerSpec({self.typename.__name__})"


class TiedLayerSpec(LayerSpec):
    def __init__(self, key, typename, *module_args, forward_fn=None,
                 tied_weight_attr="tok_embed", **module_kwargs):
        super().__init__(typename, *module_args, **module_kwargs)
        self.key = key
        self.forward_fn = forward_fn
        self.tied_weight_attr = tied_weight_attr


# ----------------------------------------------------------------------
# Pipeline layer classes for the transformer family
# ----------------------------------------------------------------------
class EmbeddingPipe:
    """Token (+ learned position) embedding.  Input: microbatch dict with
    ``input_ids`` (or a raw ids array); output: hidden states."""

    def __init__(self, config: TransformerConfig):
        self.config = config
        self._model = CausalTransformerLM(config)

    def init(self, rng, dtype=jnp.float32):
        c = self.config
        params = {}
        if not c.tie_embeddings:
            # untied: the embedding matrix is a local param; tied models get
            # it from tied_init via the "embed" tied group instead
            params.update(self.tied_init(rng, dtype))
        if not c.use_rope and not c.use_alibi:
            params["pos_embed"] = (
                jax.random.normal(jax.random.fold_in(rng, 1),
                                  (c.max_seq_len, c.hidden_size), jnp.float32)
                / math.sqrt(c.hidden_size)).astype(dtype)
        if c.embed_norm:
            params["embed_norm"] = jnp.ones((c.hidden_size,), dtype)
        return params

    def tied_init(self, rng, dtype=jnp.float32):
        c = self.config
        return {"tok_embed": (
            jax.random.normal(rng, (c.vocab_size, c.hidden_size), jnp.float32)
            / math.sqrt(c.hidden_size)).astype(dtype)}

    def __call__(self, params, batch, tied=None):
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        positions = jnp.broadcast_to(jnp.arange(ids.shape[-1]), ids.shape)
        return self._model.embed({**params, **(tied or {})}, ids, positions)


class TransformerBlockPipe:
    """One transformer block — the homogeneous pipelined body unit.
    Reuses the flagship model's block math (attention + MLP).

    MoE bodies (pp × ep composition) need ``moe_layer_freq == 1`` so the
    body stays homogeneous (every block carries an expert bank); the
    block then reports ``has_aux`` and returns ``(x, gate_aux)``."""

    def __init__(self, config: TransformerConfig):
        if config.is_moe and config.moe_layer_freq != 1:
            raise ValueError(
                "pipelined MoE needs moe_layer_freq=1 (a homogeneous "
                "body); mixed dense/MoE stacks cannot stack into one scan")
        self.config = config
        self.has_aux = config.is_moe
        self._model = CausalTransformerLM(config)

    def init(self, rng, dtype=jnp.float32):
        c = self.config
        d, f = c.hidden_size, c.ffn_dim
        dh, H, Hkv = c.head_dim, c.n_heads, c.kv_heads
        ks = jax.random.split(rng, 8)

        def dense(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32) /
                    math.sqrt(fan_in)).astype(dtype)

        layer = {
            "attn_norm": jnp.ones((d,), dtype),
            "wq": dense(ks[0], (d, H * dh), d),
            "wk": dense(ks[1], (d, Hkv * dh), d),
            "wv": dense(ks[2], (d, Hkv * dh), d),
            "wo": dense(ks[3], (H * dh, d), H * dh),
            "mlp_norm": jnp.ones((d,), dtype),
        }
        if c.is_moe:
            E = c.moe_num_experts
            layer["moe"] = {
                "wg": dense(ks[4], (d, E), d).astype(jnp.float32),
                "w_up": dense(ks[5], (E, d, f), d),
                "w_down": dense(ks[6], (E, f, d), f),
            }
            return layer
        layer["w_up"] = dense(ks[4], (d, f), d)
        layer["w_down"] = dense(ks[5], (f, d), f)
        if c.gated:
            layer["w_gate"] = dense(ks[6], (d, f), d)
        return layer

    def __call__(self, params, x, tied=None):
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x, aux = self._model._layer(x, params, positions, train=True)
        return (x, aux) if self.has_aux else x

    def tp_rules(self):
        """Single-layer Megatron split (PipelineModule prepends the pp dim)."""
        if self.config.is_moe:
            from deepspeed_tpu.parallel.topology import EP_AXIS
            return [
                (r"moe.*w_up", P(EP_AXIS, None, TP_AXIS)),
                (r"moe.*w_down", P(EP_AXIS, TP_AXIS, None)),
                (r"moe.*wg", P()),
                (r"wq|wk|wv", P(None, TP_AXIS)),
                (r"wo", P(TP_AXIS, None)),
            ]
        return [
            (r"wq|wk|wv|w_up|w_gate", P(None, TP_AXIS)),
            (r"wo|w_down", P(TP_AXIS, None)),
        ]


class LMHeadPipe:
    """Final norm + LM head; emits fp32 logits.  Tied variant reads the
    embedding matrix from the tied group."""

    def __init__(self, config: TransformerConfig):
        self.config = config
        self._model = CausalTransformerLM(config)

    def init(self, rng, dtype=jnp.float32):
        c = self.config
        params = {"final_norm": jnp.ones((c.hidden_size,), dtype)}
        if not c.tie_embeddings:
            params["lm_head"] = (
                jax.random.normal(rng, (c.hidden_size, c.vocab_size),
                                  jnp.float32)
                / math.sqrt(c.hidden_size)).astype(dtype)
        return params

    def __call__(self, params, x, tied=None):
        return self._model.logits({**params, **(tied or {})}, x)


def lm_loss_fn(logits, batch):
    """Default next-token cross-entropy — the same function the dense model
    uses (``models/transformer.py next_token_xent``), so pipeline-vs-dense
    trajectories cannot diverge."""
    from deepspeed_tpu.models.transformer import next_token_xent
    return next_token_xent(logits, batch)


# ----------------------------------------------------------------------
# PipelineModule
# ----------------------------------------------------------------------
class PipelineModule:
    """Assembles a layer list into (pre | pipelined body | post).

    Parity: reference ``pipe/module.py:88`` — same spec-list construction,
    ``partition_method`` and tied-layer surface.  ``num_stages`` defaults to
    the ``pp`` degree of the active mesh.

    The params pytree::

        {"pre":  [per-layer params ...],
         "body": stacked [L, ...] leaves (leading dim sharded over pp),
         "post": [per-layer params ...],
         "tied": {key: params}}

    ``loss(params, microbatched_batch, rng)`` runs the full pipelined
    forward + loss; the microbatch dim is the pipeline clock.
    """

    def __init__(self, layers, num_stages: Optional[int] = None,
                 topology=None, loss_fn: Optional[Callable] = None,
                 partition_method: str = "parameters",
                 activation_checkpoint_interval: int = 0,
                 seed_layers: bool = False,
                 schedule: str = "1f1b",
                 num_virtual_stages: int = 1):
        if topology is not None and num_stages is None:
            num_stages = topology.get_dim("pipe") or topology.get_dim("pp")
        # num_stages=None resolves lazily from the active mesh's pp axis.
        # Resolving eagerly here would install a default (pp=1) mesh when the
        # module is built before deepspeed_tpu.initialize — silently
        # disabling pipelining.
        self._num_stages = int(num_stages) if num_stages is not None else None
        self.loss_fn = loss_fn or lm_loss_fn
        if partition_method not in ("uniform", "parameters"):
            raise ValueError(
                f"unsupported partition_method '{partition_method}' "
                "(uniform|parameters)")
        # uniform == parameters here: the pipelined body is homogeneous, so
        # equal layer counts ARE equal parameter counts (partition_balanced
        # is exported for grid-planning parity)
        self.partition_method = partition_method
        self.activation_checkpoint_interval = activation_checkpoint_interval
        # "1f1b" = TRUE interleaved fwd/bwd (reference TrainSchedule): O(P)
        # in-flight residuals, no recompute.  "1f1b-remat" = GPipe order
        # with chunked remat (O(P) residuals bought with one fwd replay).
        # "gpipe" stores all M.  "interleaved" = Megatron virtual stages
        # (num_virtual_stages chunks per device, ~V x smaller bubble;
        # autodiff backward).
        self.schedule = schedule
        self.num_virtual_stages = int(num_virtual_stages)
        if schedule == "interleaved" and self.num_virtual_stages < 2:
            raise ValueError(
                "schedule='interleaved' needs num_virtual_stages >= 2")
        if schedule != "interleaved" and self.num_virtual_stages > 1:
            raise ValueError(
                "num_virtual_stages > 1 needs schedule='interleaved'")

        self._specs = list(layers)
        self._layers = [s.build() if isinstance(s, LayerSpec) else s
                        for s in self._specs]
        self._tied_keys = [s.key if isinstance(s, TiedLayerSpec) else None
                           for s in self._specs]
        self._split = None      # (body_start, body_end) — set in init()

    @property
    def num_stages(self) -> int:
        if self._num_stages is None:
            from deepspeed_tpu.parallel import groups
            if not groups.mesh_is_initialized():
                raise ValueError(
                    "PipelineModule: num_stages was not given and no device "
                    "mesh is initialized yet — pass num_stages=/topology=, or "
                    "call deepspeed_tpu.initialize (or "
                    "groups.initialize_mesh) before using the module")
            self._num_stages = max(groups.get_pipe_parallel_world_size(), 1)
        return self._num_stages

    # -- structure ------------------------------------------------------
    def _layer_signature(self, i, rng):
        shapes = jax.eval_shape(self._layers[i].init, rng)
        return jax.tree_util.tree_map(
            lambda x: (x.shape, str(x.dtype)), shapes)

    def _find_body(self, rng):
        sigs = [str(self._layer_signature(i, rng))
                for i in range(len(self._layers))]
        classes = [type(l) for l in self._layers]
        best = (0, 0)
        i = 0
        while i < len(sigs):
            j = i
            while (j < len(sigs) and sigs[j] == sigs[i]
                   and classes[j] is classes[i]
                   and self._tied_keys[j] is None):
                j += 1
            if j - i > best[1] - best[0]:
                best = (i, j)
            i = max(j, i + 1)
        start, end = best
        n = end - start
        assert n >= 1, "no homogeneous run of layers to pipeline"
        assert n % self.num_stages == 0, (
            f"pipelined body has {n} layers, not divisible by "
            f"num_stages={self.num_stages}")
        return start, end

    # -- params ---------------------------------------------------------
    def init(self, rng, dtype=jnp.float32) -> Dict[str, Any]:
        self._split = self._find_body(rng)
        start, end = self._split
        if self.schedule == "interleaved":
            n = end - start
            pv = self.num_stages * self.num_virtual_stages
            if n % pv:
                raise ValueError(
                    f"interleaved schedule: {n} body layers not divisible "
                    f"by num_stages*num_virtual_stages = {pv}")
        keys = jax.random.split(rng, len(self._layers) + 1)
        tied: Dict[str, Any] = {}
        pre, post = [], []
        body_layers = []
        for i, layer in enumerate(self._layers):
            p = layer.init(keys[i], dtype)
            key = self._tied_keys[i]
            if key is not None and key not in tied and \
                    hasattr(layer, "tied_init"):
                tied[key] = layer.tied_init(keys[i], dtype)
            if i < start:
                pre.append(p)
            elif i < end:
                body_layers.append(p)
            else:
                post.append(p)
        body = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *body_layers)
        return {"pre": pre, "body": body, "post": post, "tied": tied}

    @property
    def body_range(self):
        return self._split

    # -- sharding rules -------------------------------------------------
    def tp_rules(self):
        """Sharding rules for the pipeline params: body leaves lead with the
        pp axis; per-layer TP rules (from the body layer class) get the pp
        dim prepended.  Pre/post/tied params follow the data-parallel plan
        (fsdp added by the ZeRO plan)."""
        start, _ = self._split if self._split else (0, 0)
        body_layer = self._layers[start] if self._layers else None
        rules = []
        if body_layer is not None and hasattr(body_layer, "tp_rules"):
            for pat, spec in body_layer.tp_rules():
                rules.append((r"body.*(" + pat + r")",
                              P(*([PP_AXIS] + list(spec)))))
        rules.append((r"body", P(PP_AXIS)))
        return rules

    # -- execution ------------------------------------------------------
    def _call_layer(self, i, params, x, tied):
        key = self._tied_keys[i]
        t = tied.get(key) if key is not None else None
        return self._layers[i](params, x, tied=t)

    @property
    def _body_has_aux(self) -> bool:
        start = self._split[0] if self._split else 0
        return bool(getattr(self._layers[start], "has_aux", False)) \
            if self._layers else False

    def _stage_fn(self):
        start, end = self._split
        layer = self._layers[start]
        remat = self.activation_checkpoint_interval > 0
        has_aux = self._body_has_aux

        if has_aux:
            def apply_one(carry, lp):
                x, aux = carry
                y, a = layer(lp, x)
                return (y, aux + a), None
        else:
            def apply_one(x, lp):
                return layer(lp, x), None
        if remat:
            apply_one = jax.checkpoint(apply_one)

        def stage_fn(chunk_params, x):
            if has_aux:
                (y, aux), _ = jax.lax.scan(apply_one, (x, jnp.float32(0.0)),
                                           chunk_params)
                return y, aux
            x, _ = jax.lax.scan(apply_one, x, chunk_params)
            return x
        return stage_fn

    def forward_mbs(self, params, batch_mbs):
        """Pipelined forward over microbatched input (leading dim M).
        Returns the post-layer outputs ``[M, ...]``."""
        assert self._split is not None, "call init() first"
        start, end = self._split
        tied = params["tied"]

        def pre_fn(x):
            for j in range(start):
                x = self._call_layer(j, params["pre"][j], x, tied)
            return x

        x = jax.vmap(pre_fn)(batch_mbs)
        has_aux = self._body_has_aux
        if self.schedule == "interleaved" and not has_aux:
            x = pipeline_interleaved(
                self._stage_fn(),
                stack_interleaved_params(params["body"], self.num_stages,
                                         self.num_virtual_stages),
                x, self.num_stages, self.num_virtual_stages)
        else:
            stage_params = stack_stage_params(params["body"],
                                              self.num_stages)
            sched = ("1f1b-remat" if self.schedule == "interleaved"
                     else self.schedule)
            x = pipeline_spmd(self._stage_fn(), stage_params, x,
                              self.num_stages, schedule=sched,
                              with_aux=has_aux)
            if has_aux:
                x, _ = x          # aux is a training-only term

        def post_fn(h):
            for j in range(end, len(self._layers)):
                h = self._call_layer(j, params["post"][j - end], h, tied)
            return h
        # lax.map bounds logits memory to one microbatch at a time
        return jax.lax.map(post_fn, x)

    def loss(self, params, batch, rng=None, loss_scale=None):
        """Pipelined loss.  ``batch`` MUST carry a leading microbatch dim
        (the engine stacks GAS microbatches; M is the pipeline clock).

        ``loss_scale``: when given, the returned loss is PRE-scaled and the
        1f1b schedule seeds its interleaved backward with the scale, so
        fp16 cotangents ride the pipe amplified (reference semantics:
        scale before backward, not after)."""
        assert self._split is not None, "call init() first"
        start, end = self._split
        tied = params["tied"]

        inputs = batch

        # run pre layers (the first consumes the microbatch itself)
        def pre_fn(mb):
            x = mb
            for j in range(start):
                x = self._call_layer(j, params["pre"][j], x, tied)
            return x
        x = jax.vmap(pre_fn)(inputs)

        # _stage_fn already checkpoints per layer when activation
        # checkpointing is on — no second stage-level remat wrap
        has_aux = self._body_has_aux
        schedule = self.schedule
        if has_aux and schedule == "interleaved":
            # MoE bodies emit the gate aux loss per (stage, microbatch);
            # the interleaved clock does not plumb it yet
            raise ValueError(
                "MoE pipeline bodies need schedule='1f1b-remat', '1f1b' "
                "or 'gpipe' (the gate aux loss is not threaded through "
                "'interleaved' yet)")
        if has_aux and schedule == "1f1b":
            # the hand-threaded 1F1B VJP doesn't carry the aux either;
            # the chunked-remat schedule keeps the O(P) residual cap and
            # lets autodiff own the aux gradients
            schedule = "1f1b-remat"
        if schedule == "interleaved":
            x = pipeline_interleaved(
                self._stage_fn(),
                stack_interleaved_params(params["body"], self.num_stages,
                                         self.num_virtual_stages),
                x, self.num_stages, self.num_virtual_stages)
            return self._post_loss_tail(params, x, inputs, tied, end,
                                        loss_scale)

        stage_params = stack_stage_params(params["body"], self.num_stages)

        if schedule == "1f1b" and self.num_stages > 1:
            # TRUE 1F1B: the loss head runs inside the interleaved scan so
            # each microbatch's backward starts the tick its forward exits
            # (reference TrainSchedule, runtime/pipe/schedule.py:184) —
            # O(P) live residuals, no recompute
            post_params, n_layers, end_ = params["post"], len(self._layers), end

            def head_fn(head_params, h, mb):
                post, tied_hp = head_params
                for j in range(end_, n_layers):
                    h = self._call_layer(j, post[j - end_], h, tied_hp)
                return self.loss_fn(h, mb)

            return pipeline_train_1f1b(
                self._stage_fn(), head_fn, self.num_stages,
                stage_params, (post_params, tied), x, inputs,
                loss_ct=loss_scale)

        out = pipeline_spmd(self._stage_fn(), stage_params, x,
                            self.num_stages, schedule=schedule,
                            with_aux=has_aux)
        if has_aux:
            x, aux_sum = out
            coef = getattr(self._layers[start].config, "moe_aux_loss_coef",
                           0.0)
            # microbatched semantics (same as the dense GAS scan): mean over
            # microbatches of (ce_m + coef * aux_m)
            extra = coef * aux_sum / x.shape[0]
            return self._post_loss_tail(params, x, inputs, tied, end,
                                        loss_scale, extra=extra)
        return self._post_loss_tail(params, out, inputs, tied, end,
                                    loss_scale)

    def _post_loss_tail(self, params, x, inputs, tied, end, loss_scale,
                        extra=None):
        """Shared post-layers + loss over pipelined outputs (one
        definition for every autodiff schedule).  ``extra``: additive loss
        terms computed inside the pipeline (MoE gate aux)."""
        def mb_loss(args):
            h, mb = args
            for j in range(end, len(self._layers)):
                h = self._call_layer(j, params["post"][j - end], h, tied)
            return self.loss_fn(h, mb)
        mean = jnp.mean(jax.lax.map(mb_loss, (x, inputs)))
        if extra is not None:
            mean = mean + extra
        return mean if loss_scale is None else mean * loss_scale

    def partition_layers(self):
        """Report layer→stage assignment (reference logs the same at
        construction).  Pre/post layers are 'replicated'."""
        start, end = self._split if self._split else self._find_body(
            jax.random.key(0))
        out = []
        if self.schedule == "interleaved":
            # round-robin chunks: global chunk c lives on stage c mod P
            k = (end - start) // (self.num_stages * self.num_virtual_stages)
            for i in range(len(self._layers)):
                if i < start or i >= end:
                    out.append((i, type(self._layers[i]).__name__,
                                "replicated"))
                else:
                    chunk = (i - start) // k
                    out.append((i, type(self._layers[i]).__name__,
                                f"stage{chunk % self.num_stages}"
                                f"v{chunk // self.num_stages}"))
            return out
        per = (end - start) // self.num_stages
        for i in range(len(self._layers)):
            if i < start or i >= end:
                out.append((i, type(self._layers[i]).__name__, "replicated"))
            else:
                out.append((i, type(self._layers[i]).__name__,
                            f"stage{(i - start) // per}"))
        return out


def transformer_pipeline(config: TransformerConfig,
                         num_stages: Optional[int] = None,
                         loss_fn: Optional[Callable] = None,
                         activation_checkpoint_interval: int = 0,
                         schedule: str = "1f1b",
                         num_virtual_stages: int = 1) -> PipelineModule:
    """GPT2ModelPipe-style convenience: embedding → N blocks → norm+head
    (parity: Megatron-DeepSpeed ``GPT2ModelPipe`` construction)."""
    specs: List[LayerSpec] = []
    if config.tie_embeddings:
        specs.append(TiedLayerSpec("embed", EmbeddingPipe, config))
    else:
        specs.append(LayerSpec(EmbeddingPipe, config))
    specs += [LayerSpec(TransformerBlockPipe, config)
              for _ in range(config.n_layers)]
    if config.tie_embeddings:
        specs.append(TiedLayerSpec("embed", LMHeadPipe, config))
    else:
        specs.append(LayerSpec(LMHeadPipe, config))
    return PipelineModule(
        specs, num_stages=num_stages, loss_fn=loss_fn,
        activation_checkpoint_interval=activation_checkpoint_interval,
        schedule=schedule, num_virtual_stages=num_virtual_stages)
