"""Continuous-batching serving engine over the paged KV cache.

Parity role: the reference serves decode through a per-request contiguous
KV workspace inside ``InferenceEngine`` (``inference_context.h`` workspace
management) — every request pays max-length allocation and batches must
line up.  The TPU-native upgrade is vLLM-style serving (PAPERS.md ragged
paged attention): fixed-size pages shared across sequences through block
tables, slot-based continuous batching (a finished request's pages free
immediately and the next prompt is admitted mid-flight), and one jitted
decode step for the whole active batch regardless of ragged lengths.

Host/device split: page allocation, admission, sampling bookkeeping are
host control flow (``PagedAllocator``); prefill and the batched decode
step are jitted device programs over ``CausalTransformerLM.
apply_with_paged_cache``.  Prefill lengths are bucketed to powers of two
to bound recompilation.

Hardening (``inference/robustness.py``): ``add_request`` raises typed
:class:`RequestRejected` instead of asserts; a bounded queue with
watermark admission control sheds/rejects/blocks under overload;
per-request deadlines cancel queued and mid-flight work at step
boundaries; a per-slot fault (sampler exception or injected
``serve_sample``) evicts ONE request with its partial output while the
rest of the batch keeps serving; ``drain()`` quiesces the engine and
``health()`` snapshots its state onto the telemetry registry.  Injected
``serve_step`` / ``page_alloc`` faults are retried without mutating any
request state, so recovered requests stay bit-identical.
"""

import collections
import contextlib
import functools
import math
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.robustness import (
    EVICT_FAULT, REJECT_BAD_REQUEST, REJECT_BAD_SAMPLING, REJECT_DRAINING,
    REJECT_DUPLICATE, REJECT_INFEASIBLE, REJECT_OVERLOADED,
    REJECT_OVERSIZED, REJECT_QUEUE_FULL, SHED_DEADLINE, SHED_DRAIN,
    SHED_OLDEST, AdmissionController, RequestRejected, RequestResult,
    RequestTracer, ServingRobustnessConfig, ServingStalled,
    ServingUnsupported)
from deepspeed_tpu.comm.quantize import CommQuantizer
from deepspeed_tpu.inference.prefix_cache import PrefixCache, PrefixMatch
from deepspeed_tpu.inference.scheduler import SLO_CLASSES, create_scheduler
from deepspeed_tpu.models.transformer import SERVE_COUNTERS
from deepspeed_tpu.monitor.attribution import RequestAttributor
from deepspeed_tpu.monitor.telemetry import (get_telemetry, in_setup_span,
                                             register_compiled, setup_span)
from deepspeed_tpu.ops.latent_attention import (PREFILL_BLOCK_K,
                                                context_entries)
from deepspeed_tpu.ops.paged_attention import (PageAllocationError,
                                               PagedAllocator,
                                               kv_lane_pack,
                                               resolve_attention_backend,
                                               resolve_paged_impl,
                                               ring_pages)
from deepspeed_tpu.ops.pallas.latent_attention import pick_latent_tiles
from deepspeed_tpu.ops.pallas.ragged_paged_attention import (
    pick_tiles, rect_grid_steps)
from deepspeed_tpu.runtime.resilience import FaultInjector
from deepspeed_tpu.utils.logging import logger


# RequestResult statuses -> lifecycle-trace terminal names (the tail of
# the frozen serve/request/* vocabulary).  "drained" folds into "shed":
# from the request's point of view a drain IS a shed, just engine-initiated.
_TERMINAL_BY_STATUS = {"shed": "shed", "drained": "shed",
                       "deadline": "deadline", "evicted": "evict"}


# per-step reports ``ServingEngine.step_reports()`` keeps: ten minutes of
# 140 ms steps
STEP_REPORTS_KEPT = 4096


# what a model with sliding-window layers adds to each prefill and decode
# dispatch of ``last_step`` and to its ``serve/step`` span
# (``ServingEngine._window_counts``; frozen in
# scripts/check_telemetry_schema.py)
WINDOW_COUNTS = ("context_keys", "attended_keys", "pages_full",
                 "pages_ring")

# what a PREFILL dispatch of ``last_step`` and its ``serve/step`` span say
# of a prompt served in chunks (frozen in
# scripts/check_telemetry_schema.py): ``ctx_entries``, of a
# latent-attention model without a selection, the pool entries one layer's
# attention walks for the chunk from what was cached before it (reckoned
# in ``ServingEngine._dispatch``); ``chunk``, of the chunked policy
# whatever the model, the chunk's index in its prompt (the scheduler's)
CHUNK_COUNTS = ("ctx_entries", "chunk")
# what a dispatch's record says of what it compiled to, each "pallas" or
# "jnp": the write of the page pools (every dispatch), a dropless expert
# layer's grouped product (every dispatch of such a model), a latent
# model's prefill over the pool (its prefill dispatches), a state-space
# layer's one-row recurrence on the state pool (the decode dispatches of a
# model with such layers; a linear-attention layer's runs in XLA: "jnp")
# (scripts/check_telemetry_schema.py DISPATCH_IMPLS, frozen)
DISPATCH_IMPLS = ("kv_write", "experts", "latent", "state")
# what a model with state-space or linear-attention layers adds to each
# prefill and decode dispatch of ``last_step`` and to its ``serve/step``
# span, from the host (frozen in scripts/check_telemetry_schema.py):
# ``state_slots``, the rows whose recurrent state the dispatch advanced (a
# prefill's one slot, a decode step's served slots), and ``state_bytes``,
# the bytes of state (and, of a state-space layer, of the convolution's
# last inputs) it had to read and write for them, all such layers (from
# shapes and dtypes)
STATE_COUNTS = ("state_slots", "state_bytes")

# A whole-prompt prefill longer than this pads to the next multiple of it,
# not to the next power of two, and goes as pieces that are powers of two
# no smaller (``MonolithicScheduler.prefill_pieces``).  The v5e's ridge is
# 197 TFLOP/s / 819 GB/s = 240 rows of bf16 weights: at twice that a
# piece's matmuls still hide the read of the weights.  Not a config key:
# a smaller piece is a shape more to compile for a dispatch the weights'
# read bounds.  A multiple of the page size, so a piece starts on a page.
PREFILL_PIECE_ROWS = 512


def greedy_token(logits: np.ndarray, width: int = 1024) -> int:
    """``int(np.argmax(logits))`` of one vocabulary row, by the maximum of
    each block of ``width`` and two short argmaxes: the same index, ties
    and NaNs included (the first block that holds the maximum, the first
    place in it).  ``np.argmax`` over a 100k-entry float32 row runs at
    one of two speeds on the serving host, 40 or 160 us a row, set for a
    whole run by where the process's buffers happen to lie; a ``maximum``
    reduction does not, and a decode step of 16 slots waits for 16 picks
    with the device idle (PERF.md §6, PR 33)."""
    tops = np.maximum.reduceat(logits, np.arange(0, len(logits), width))
    start = int(np.argmax(tops)) * width
    return start + int(np.argmax(logits[start:start + width]))


class StepLogits:
    """The logits ``[B, R, V]`` of one dispatch of a serving program, left
    where the program wrote them: on the device.  What a step needs of
    them is ``picks`` ``[B, R]``, the greedy token of every row, which the
    program takes itself: ``device_picks`` is what the next decode
    dispatch is fed where the host has not seen them yet, and
    ``ServingEngine._fetch`` brings them to the host as ``picks`` (None
    until a fetch of this dispatch, or of a later one, has: a decode
    dispatch may still be running when the ``step()`` that launched it
    returns).  The float32 block crosses only if something reads
    it: :meth:`host` is ``np.asarray`` of the array the program returned
    (a copy, no program), made once and shared by the dispatch's rows
    (the speculative verify window reads its rows of it).  ``block[b,
    r]`` is that token's :class:`LogitsRow`, once the picks are here.
    ``record`` / ``attrs`` are the dispatch's entry in ``last_step`` and
    the attributes of its ``serve/step`` span, where :meth:`count` keeps
    ``picked`` and ``host_rows`` and :meth:`bump` a decode dispatch's
    ``ahead`` and ``redone``."""

    __slots__ = ("device", "device_picks", "picks", "counters", "record",
                 "attrs", "_host")

    def __init__(self, device, device_picks, counters, record, attrs):
        self.device, self.device_picks = device, device_picks
        self.counters, self.record, self.attrs = counters, record, attrs
        self.picks = self._host = None

    def host(self) -> np.ndarray:
        if self._host is None:
            self._host = np.asarray(self.device)
        return self._host

    def __getitem__(self, at):
        return LogitsRow(self, at)

    def count(self, picked: int, host_rows: int):
        """``picked`` tokens were chosen from this dispatch, ``host_rows``
        of them with their logits row read on the host."""
        self.bump("picked", picked)
        self.bump("host_rows", host_rows)

    def bump(self, counter: str, n: int = 1):
        for kept in (self.record, self.attrs):
            kept[counter] += n


class LogitsRow:
    """One token's logits row, wherever it lies: what ``_sample(req,
    row)`` is handed.  ``np.asarray(row)`` / ``np.array(row, np.float32)``
    is the float32 row (the first read of a dispatch fetches its block);
    ``pick`` is the program's own ``argmax`` of it; ``read`` says whether
    anything looked."""

    __slots__ = ("block", "at", "pick", "read")

    def __init__(self, block: StepLogits, at):
        self.block, self.at, self.read = block, at, False
        self.pick = int(block.picks[at])

    def __array__(self, dtype=None, copy=None):
        self.read = True
        return np.array(self.block.host()[self.at], dtype=dtype, copy=copy)


def _round_ms(v):
    return None if v is None else round(v, 3)


@dataclass
class _Request:
    req_id: Any
    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    seed: int = 0
    top_k: int = 0              # 0 = off
    top_p: float = 1.0          # 1.0 = off
    out: List[int] = field(default_factory=list)
    last_token: Optional[int] = None
    submit_time: float = 0.0
    deadline: float = 0.0       # absolute clock time; 0.0 = no deadline
    slo_class: str = "throughput"   # scheduler SLO class (SLO_CLASSES)
    # chunked-prefill progress: prompt tokens already written to the
    # target / draft KV cache (the monolithic policy never reads these)
    prefilled: int = 0
    draft_filled: int = 0
    # disaggregated fleets: this replica only prefills — the engine
    # captures a PrefillHandoff at prefill completion instead of decoding
    prefill_only: bool = False


@dataclass
class PrefillHandoff:
    """Everything a decode replica needs to continue a request whose
    prefill ran elsewhere: the sampling recipe, the first token (sampled
    on the source — its logits came off the prefill dispatch), the host
    sampler's RNG stream state, and the SOURCE page ids of the prompt's
    KV pages.  The pages stay pinned under the source allocator (keyed by
    ``req_id``) until :meth:`ServingEngine.release_handoff` — the commit
    acknowledgement — so a kill of either side mid-migration always
    leaves one consistent copy to redispatch from."""
    req_id: Any
    prompt: List[int]
    max_new_tokens: int
    temperature: float
    seed: int
    top_k: int
    top_p: float
    slo_class: str
    last_token: int
    out: List[int]
    rng_state: Optional[dict]
    pages: List[int]
    # wire-serialized TraceContext (monitor/attribution.py): the source
    # leg's timing history rides the handoff as plain primitives, so the
    # decode side's serve/request/attr event reports the FULL critical
    # path — queue and prefill on the source, the migration wait, then
    # decode here — not just the decode leg
    trace_ctx: Optional[dict] = None

    def to_wire(self) -> dict:
        """JSON-safe envelope for the cross-process fleet transport
        (``inference/transport.py``), stamped with the wire version.
        Every field is already plain primitives except ``rng_state``
        (numpy bit-generator state — MT19937 carries an ndarray key)."""
        from deepspeed_tpu.inference.transport import (WIRE_VERSION,
                                                       pack_value)
        return {
            "v": list(WIRE_VERSION),
            "req_id": pack_value(self.req_id),
            "prompt": [int(t) for t in self.prompt],
            "max_new_tokens": int(self.max_new_tokens),
            "temperature": float(self.temperature),
            "seed": int(self.seed),
            "top_k": int(self.top_k),
            "top_p": float(self.top_p),
            "slo_class": str(self.slo_class),
            "last_token": int(self.last_token),
            "out": [int(t) for t in self.out],
            "rng_state": pack_value(self.rng_state),
            "pages": [int(p) for p in self.pages],
            "trace_ctx": pack_value(self.trace_ctx),
        }

    @classmethod
    def from_wire(cls, d: dict) -> "PrefillHandoff":
        """Inverse of :meth:`to_wire`.  Rejects an unknown MAJOR wire
        version with the typed ``WireVersionError`` before reading any
        field — a decode replica must never guess at an envelope from a
        newer incompatible router."""
        from deepspeed_tpu.inference.transport import (check_wire_version,
                                                       unpack_value)
        check_wire_version(d.get("v"), "PrefillHandoff")
        return cls(
            req_id=unpack_value(d["req_id"]),
            prompt=[int(t) for t in d["prompt"]],
            max_new_tokens=int(d["max_new_tokens"]),
            temperature=float(d["temperature"]),
            seed=int(d["seed"]),
            top_k=int(d["top_k"]),
            top_p=float(d["top_p"]),
            slo_class=str(d["slo_class"]),
            last_token=int(d["last_token"]),
            out=[int(t) for t in d["out"]],
            rng_state=unpack_value(d["rng_state"]),
            pages=[int(p) for p in d["pages"]],
            trace_ctx=unpack_value(d.get("trace_ctx")),
        )


class ServingEngine:
    """``add_request`` → ``step`` until ``finished`` — or just
    ``generate(prompts, max_new_tokens)``.

    One decode ``step()`` advances EVERY active slot by one token; slots
    free and refill from the queue as requests finish (continuous
    batching).  Inactive slots point at the reserved scratch page and
    their outputs are ignored.
    """

    @in_setup_span("setup/engine", kind="serving")
    def __init__(self, model, params, max_batch: int = 8,
                 page_size: int = 128, num_pages: Optional[int] = None,
                 max_seq: int = 2048, dtype=jnp.bfloat16,
                 eos_token_id: Optional[int] = None, tp_size: int = 1,
                 ep_size: int = 1, decode_chunk: int = 1,
                 serving=None, telemetry=None, injector=None, clock=None,
                 replica_epoch=None, draft_model=None, draft_params=None,
                 comm_quant=None):
        """``serving``: a :class:`ServingRobustnessConfig` or its dict —
        defaults keep pre-hardening behaviour (unbounded queue, no
        deadlines).  ``injector``: a ``FaultInjector`` for the serving
        sites (built from ``serving.fault_injection`` when omitted).
        ``clock``: monotonic-seconds callable, injectable so deadline
        tests don't sleep.  ``telemetry``: explicit Telemetry instance;
        None uses the process singleton at event time.  ``replica_epoch``:
        set by the fleet front-end — namespaces request ids in the tracer
        so a respawned replica re-serving a redispatched id cannot read as
        a double admit in a merged audit.  ``draft_model``/``draft_params``:
        the speculative-decoding proposer (``serving.scheduler.speculative``
        — inference/scheduler.py); ignored unless that block enables it.
        ``comm_quant``: wire codec for KV-page migration payloads — a
        :class:`CommQuantizer`, the ``comm.quantization`` config block,
        or None (off); only the EXPORT side consults it, imports decode
        the self-describing payload regardless."""
        self.model = model
        self.config = model.config
        self.max_batch = max_batch
        self.page_size = page_size
        self.max_pages_per_seq = -(-max_seq // page_size)
        if num_pages is None:
            num_pages = max_batch * self.max_pages_per_seq + 1
        self.mesh = None
        if isinstance(serving, ServingRobustnessConfig):
            self.serving = serving
        else:
            self.serving = ServingRobustnessConfig(serving or {})
        self._refuse_unsupported(tp_size, ep_size, decode_chunk)
        # a model with sliding-window layers keeps their keys and values
        # in a ring of pages a slot, beside the growing tables of its
        # full-attention layers (ops/paged_attention.py): each row of
        # ``tables`` ends in the slot's ring
        window = int(getattr(self.config, "attn_window", 0) or 0)
        self.ring_pages = ring_pages(window, page_size) if window else 0
        # a model with state-space or linear-attention layers keeps their
        # recurrent state beside the page pools, a row a SLOT (ops/ssm.py
        # HybridKVCache; its ``ssm`` member holds either kind's leaves):
        # no allocator, nothing to leak
        self._stateful = bool(getattr(self.config, "has_state", False))
        # block-sparse attention layers keep compressed keys beside their
        # pages, under the same tables (ops/block_sparse_attention.py)
        self._sparse = bool(getattr(self.config, "has_sparse", False))
        with setup_span("setup/engine/pools"):
            caches = model.init_paged_caches(
                num_pages, page_size, dtype=dtype,
                **({"ring_slots": max_batch} if window else {}),
                **({"state_slots": max_batch} if self._stateful else {}))
        if ep_size > 1:
            assert getattr(self.config, "is_moe", False), \
                "ep_size > 1 needs an MoE model"
            assert self.config.moe_num_experts % ep_size == 0, \
                "ep_size must divide the expert count"
        if tp_size > 1 or ep_size > 1:
            # tensor/expert-parallel serving: weights per the model's
            # tp_rules (expert leaves carry the ep axis on their leading
            # [E, ...] dim — reference megatron_gpt_moe EP containers), KV
            # pages sharded over the kv-head dim ([L, P, Hkv, page, D])
            from jax.sharding import NamedSharding, PartitionSpec as P
            from deepspeed_tpu.parallel import groups
            from deepspeed_tpu.parallel.topology import TopologyConfig
            from deepspeed_tpu.runtime.zero.stage_plan import ZeroShardingPlan
            assert self.config.kv_heads % tp_size == 0, \
                "tp_size must divide the kv-head count for paged serving"
            groups.reset_mesh()
            self.mesh = groups.initialize_mesh(
                TopologyConfig(tp=tp_size, ep=ep_size, fsdp=-1))
            plan = ZeroShardingPlan(self.mesh, stage=0,
                                    tp_rules=model.tp_rules())
            with self.mesh:
                params = jax.device_put(
                    params, plan._to_sharding(plan.param_specs(params)))
                caches = jax.device_put(
                    caches, NamedSharding(self.mesh,
                                          P(None, None, "tp", None, None)))
        self.params = params
        self.caches = caches
        self.cache_dtype = dtype
        # bytes of recurrent state (and of the convolution's last inputs)
        # a slot holds, all layers that keep one; 0 for a model without
        self.state_slot_bytes = sum(
            leaf.size * jnp.dtype(leaf.dtype).itemsize // max_batch
            for leaf in caches.ssm) if self._stateful else 0
        if injector is None:
            injector = FaultInjector.from_config(
                self.serving.fault_injection)
        self.injector = injector
        self.alloc = PagedAllocator(num_pages, page_size,
                                    self.max_pages_per_seq,
                                    reserve_scratch=True,
                                    injector=injector,
                                    ring_pages=self.ring_pages,
                                    ring_slots=max_batch)
        # content-hashed KV-page reuse (inference/prefix_cache.py): the
        # namespace pins cached pages to this model shape / cache dtype /
        # page size, so a differently-configured engine can never attach
        # a foreign page even through a shared registry
        self.prefix_cache = None
        pc_cfg = self.serving.prefix_cache
        if getattr(pc_cfg, "enabled", False):
            mc = self.config
            ns = (f"{type(model).__name__}/"
                  f"L{getattr(mc, 'n_layers', 0)}"
                  f"h{getattr(mc, 'hidden_size', 0)}"
                  f"q{getattr(mc, 'n_heads', 0)}"
                  f"kv{getattr(mc, 'kv_heads', 0)}"
                  f"v{getattr(mc, 'vocab_size', 0)}/"
                  f"{jnp.dtype(dtype).name}/page{page_size}")
            self.prefix_cache = PrefixCache(
                self.alloc, page_size, namespace=ns,
                max_cached_pages=int(pc_cfg.max_cached_pages),
                min_prefix_tokens=int(pc_cfg.min_prefix_tokens),
                on_evict=self._on_prefix_evict)
        self._copy_page_fn = None   # compiled COW page copy (lazy)
        # KV-page migration plumbing (disaggregated fleets): compiled
        # gather/scatter over page ids (lazy), handed-off prefills whose
        # pages stay pinned here, and imports awaiting their commit
        self._gather_pages_fn = None
        self._scatter_pages_fn = None
        self._kv_page_bytes = None
        self.comm_quant = (comm_quant
                           if isinstance(comm_quant, CommQuantizer)
                           else CommQuantizer.from_config(comm_quant))
        self.handoffs: Dict[Any, PrefillHandoff] = {}
        self._new_handoffs: List[Any] = []
        self._pending_imports: Dict[Any, Any] = {}
        self.eos = eos_token_id
        if not self.config.use_rope and not self.config.use_alibi:
            # learned positions: gathers past the table CLAMP under jit
            # (silent garbage), so bound the serve length up front
            assert max_seq <= self.config.max_seq_len, (
                f"max_seq {max_seq} exceeds the model's position table "
                f"({self.config.max_seq_len})")
        self.max_seq = max_seq

        self.slots: List[Optional[_Request]] = [None] * max_batch
        self.queue: List[_Request] = []
        self.finished: Dict[Any, List[int]] = {}
        # terminal records for requests that did NOT finish normally
        # (shed / deadline / evicted / drained) — the caller's delivery
        # channel for partial outputs; drain with pop_terminated()
        self.terminated: Dict[Any, RequestResult] = {}
        self.lengths = np.zeros(max_batch, np.int32)
        # +1 overrun column, permanently the scratch page (page 0): when a
        # reservation fills the whole table (prompt + max_new == max_seq),
        # the final chunk's last write indexes one page past the
        # reservation — this column catches it ON SCRATCH by construction
        # instead of relying on OOB-gather clamping (which would overwrite
        # the request's own last real page)
        self.tables = np.zeros(
            (max_batch, self.max_pages_per_seq + 1 + self.ring_pages),
            np.int32)
        # attention backend: "auto" (Pallas kernel on TPU, jnp elsewhere),
        # "jnp" (gather oracle), "pallas", or "pallas-interpret" (the exact
        # kernel path through the interpreter — CPU CI).  Bound as static
        # kwargs BEFORE jit so every compiled shape uses one backend.
        self.attention_backend = self.serving.attention_backend
        attn_impl, attn_interpret = resolve_attention_backend(
            self.attention_backend)
        # resolve "auto" ONCE, here, so what the engine reports
        # (``attention_impl``, the serve/backend event) is what every
        # compiled shape runs; softcapped models take the jnp path
        # (ops/paged_attention.py)
        self.attention_impl = resolve_paged_impl(
            attn_impl, getattr(self.config, "attn_logit_softcap", None))
        # a dropless expert layer's grouped product takes the same
        # choice (the kernel on TPU or where the backend says so), and no
        # softcap stands in its way; None for a model without one
        self.experts_impl = resolve_paged_impl(attn_impl) if getattr(
            self.config, "moe_dropless", False) else None
        # a state-space layer's recurrence in a decode dispatch runs on the
        # dispatch's one backend (models/transformer.py mix_ssm_paged: the
        # ``ssm_decode_update`` kernel or the jnp slice, step and masked
        # write); None for a model without such layers
        # a linear-attention layer's update is XLA's whatever the backend
        self.state_impl = None if not self._stateful else \
            self.attention_impl if getattr(self.config, "has_ssm", False) \
            else "jnp"
        latent = bool(getattr(self.config, "is_latent", False))
        if latent:
            # the latent pools are written, and read by a decode step and
            # by a prefill under a selection, in XLA whatever the backend
            # asked for (models/transformer.py mix_latent)
            self.attention_impl = "jnp"
        # no selection: every query attends over its whole context, a
        # prefill may start from entries already in the pool, and nothing
        # is ``selected`` (mix_latent_dense)
        self._latent_dense = latent and not getattr(
            self.config, "index_topk", 0)
        # such a prefill's read of the pool takes the backend's choice as
        # the expert layers do (ops/pallas/latent_attention.py: one kernel
        # over the cached entries and the chunk itself); a selection's
        # prefill stays in XLA; None for a model without latent attention
        self.latent_impl = None if not latent else \
            resolve_paged_impl(attn_impl) if self._latent_dense else "jnp"
        # the least rows of a piece of a long prompt's prefill, each piece
        # started on what the one before it wrote; 0, and every prompt one
        # bucket, where no prefill onto a context already in the pool is
        # built: a window layer's ring is filled from an empty context
        # (_refuse_for_ring), a selection over cached index keys or over
        # cached compressed keys takes one query (_refuse_unsupported,
        # _refuse_for_sparse)
        self.prefill_piece_rows = PREFILL_PIECE_ROWS if (
            not self.ring_pages and (not latent or self._latent_dense)
            and not self._sparse
            and PREFILL_PIECE_ROWS % page_size == 0) else 0
        self._paged_call = functools.partial(
            self.model.apply_with_paged_cache,
            attn_backend=self.attention_impl, attn_interpret=attn_interpret,
            expert_backend=self.experts_impl,
            latent_backend=self.latent_impl)
        # a model that counts on the device what a dispatch did (keys
        # selected, expert pairs: transformer.SERVE_COUNTERS) is told
        # which rows are tokens and hands the counts back beside the
        # logits; they come to the host in the fetch the step makes
        # anyway (_fetch)
        self._counted = bool(getattr(self.config, "counts_serving", False))
        self._prefill_sizes = None
        self._prefill_slot = None
        # dispatches of the two serving programs that no fetch has
        # brought yet, in launch order (a prefill chunk, or a piece of a
        # long prompt, that is not sampled from is never waited for, a
        # decode step is left running behind the next one's launch): a
        # fetch of a later dispatch brings their picks and counters along
        self._unfetched = []

        # two named jits over the one call, so a device trace's
        # ``XLA Modules`` line tells prefill (B=1, bucketed T:
        # ``jit_serve_prefill``) from decode (B=max_batch, T=1, and the
        # speculative verify window: ``jit_serve_decode``); each caches a
        # compilation per input shape
        # Both end with the greedy pick of every row they took the head
        # on, ``np.argmax``'s (the first index of the maximum, a NaN
        # counts as the maximum) over the float32 logits they return: a
        # step fetches those ids, and the block only if a row of it is
        # read (StepLogits)
        def with_picks(out):
            return out + (jnp.argmax(out[0], axis=-1).astype(jnp.int32),)

        # what ``_run_step`` hands a program after its fixed arguments,
        # in this order: how many of each sequence's rows are tokens (a
        # counted model's dispatches; a state model's prefill), and the
        # slot whose state a prefill starts from and leaves advanced (a
        # state model's; its decode step takes both from ``lengths``)
        told = ("real_lengths", "state_slots")
        # the two programs close over the model's call and not over this
        # engine: their scope tables are read after the engine is dropped
        # (telemetry.register_compiled(keep=True))
        paged_call = self._paged_call

        def serve_prefill(params, ids, caches, tables, lengths, rows, *real):
            # the head on ``rows`` alone: the row its caller samples from
            return with_picks(paged_call(
                params, ids, caches, tables, lengths, head_rows=rows,
                **dict(zip(told, real))))

        def serve_decode(params, ids, caches, tables, lengths, fed, *real):
            # a row whose id is negative is fed ``fed``'s: the pick of the
            # decode dispatch before this one, which has not left the
            # device (the scheduler launches a step before the host has
            # the ids of the one before: scheduler._decode_once)
            return with_picks(paged_call(
                params, jnp.where(ids < 0, fed, ids), caches, tables,
                lengths, **dict(zip(told, real))))

        self._prefill_fn = jax.jit(serve_prefill, donate_argnums=(2,))
        self._step_fn = jax.jit(serve_decode, donate_argnums=(2,))
        # what a decode dispatch is handed as ``fed``: the ``picks`` of
        # the decode dispatch before it, where they lie; before any,
        # zeros that no row reads, placed as a dispatch's results are
        # (their type carries the mesh of the weights' placement), or the
        # second decode step would trace and compile the program again
        placed = getattr(jax.tree_util.tree_leaves(params)[0], "sharding",
                         None)
        self._picks = jax.device_put(
            np.zeros((max_batch, 1), np.int32),
            jax.sharding.NamedSharding(placed.mesh,
                                       jax.sharding.PartitionSpec())
            if isinstance(placed, jax.sharding.NamedSharding) else None)
        self._rng = {}
        # multi-token decode: one device program advances every slot
        # ``decode_chunk`` tokens (sampling included) per host round-trip,
        # amortising the per-dispatch host cost over the chunk.
        self.decode_chunk = int(decode_chunk)
        assert self.decode_chunk >= 1

        self._clock = clock if clock is not None else time.monotonic
        self._telemetry = telemetry
        # the judge of each ``serve/loop`` and the sampler behind it, on
        # whether or not telemetry is enabled (monitor/telemetry.py)
        self.telemetry.watchdog.start()
        # profiling plane (monitor/profiling.py): route the serving jit
        # entry points through the CompileWatcher — shape-bucket churn
        # shows up as compile/* events, and a recompile storm flips
        # health()["recompile_storm"].  Telemetry must be bound first.
        self._storm_flagged = False
        self._step_fn = self._wrap_compiled(self._step_fn, "serve/step_fn",
                                            keep=True)
        self._prefill_fn = self._wrap_compiled(self._prefill_fn,
                                               "serve/prefill_fn", keep=True)
        # the engine's own account of each step(): what it dispatched and
        # which tokens reached the host when (docs/telemetry.md).  The
        # open report collects from the moment the last step() returned,
        # so a prefill that add_request ran inline is in the next one
        self._report = self._new_report()
        self.last_step = None
        self._kernel_tiles = {}
        self._reports = collections.deque(maxlen=STEP_REPORTS_KEPT)
        self._admission = AdmissionController(self.serving)
        # per-request lifecycle traces on the SAME injectable clock as the
        # deadline machinery — always on (host dict ops), so the
        # trace-completeness invariant in leak_report() holds even with
        # telemetry disabled
        self.replica_epoch = replica_epoch
        self.tracer = RequestTracer(clock=self._clock, epoch=replica_epoch)
        # critical-path attribution on the same clock — always on like
        # the tracer (host dict ops); each terminal pairs with one
        # frozen serve/request/attr event whose stage sum equals the
        # traced e2e by construction
        self.attrib = RequestAttributor(clock=self._clock)
        self._consec_step_faults = 0
        self.draining = False
        self.stats = {"admitted": 0, "rejected": 0, "shed": 0,
                      "deadline": 0, "evicted": 0, "finished": 0,
                      "step_faults": 0, "drains": 0, "prefix_hits": 0,
                      "prefix_cow_copies": 0, "prefix_evictions": 0,
                      "slo_attained": 0, "slo_missed": 0,
                      "goodput_tokens": 0,
                      "prefill_handoffs": 0, "imports": 0,
                      "state_redone": 0}
        # one frozen event per engine records which attention path every
        # serve/step span of this stream ran (ds_telemetry_report keys
        # its serving-attention table off it)
        self._serve_event("serve/backend",
                          attention_backend=self.attention_backend,
                          impl=self.attention_impl,
                          interpret=int(attn_interpret))
        # pluggable step scheduler (inference/scheduler.py): the
        # serving.scheduler block picks the policy; "monolithic" keeps
        # the pre-scheduler behaviour bit-for-bit.  One frozen
        # serve/sched event per engine records the policy the stream ran.
        self.scheduler = create_scheduler(self, self.serving.scheduler,
                                          draft_model=draft_model,
                                          draft_params=draft_params)
        self._serve_event("serve/sched", **self.scheduler.meta())
        if self._stateful:
            # one frozen event an engine: the state beside the pages (a
            # state-space layer's, with its convolution's last inputs and
            # their ``conv_dtype``, or a linear-attention layer's matrix
            # alone, ``kind: "linear"``), and what a dropped decode row
            # costs (``_redo_state``)
            conv = getattr(caches.ssm, "conv", None)
            self._serve_event(
                "serve/state", layers=int(caches.ssm.state.shape[0]),
                slot_bytes=int(self.state_slot_bytes),
                dtype=jnp.dtype(caches.ssm.state.dtype).name,
                **({"kind": "linear"} if conv is None else
                   {"conv_dtype": jnp.dtype(conv.dtype).name}),
                redo="prefill_from_zero")
        # incident plane: bundles snapshot this engine's health() and its
        # in-flight request traces alongside the flight-recorder dump
        incidents = getattr(self.telemetry, "incidents", None)
        if incidents is not None:
            incidents.add_context("serving_health", self.health)
            incidents.add_context("inflight_traces",
                                  self.tracer.snapshot_open)

    def _refuse_unsupported(self, tp_size, ep_size, decode_chunk=1):
        """What a latent-attention model, one with sliding-window layers,
        one with state-space or linear-attention layers (a recurrent
        state a slot beside the pages) or one with block-sparse attention
        (compressed keys beside the pages) cannot be served with until
        someone builds it, refused by name when the engine is made."""
        if getattr(self.config, "attn_window", 0):
            self._refuse_for_ring(tp_size, ep_size)
        if getattr(self.config, "has_state", False):
            self._refuse_for_state(tp_size, ep_size, decode_chunk)
        if getattr(self.config, "has_sparse", False):
            self._refuse_for_sparse(tp_size, ep_size)
        if not getattr(self.config, "is_latent", False):
            return
        selects = bool(getattr(self.config, "index_topk", 0))
        if getattr(self.serving.prefix_cache, "enabled", False):
            raise ServingUnsupported(
                "prefix_cache with latent attention",
                "a prefill can start from cached latent pages only "
                "without a selection, and sharing and copying such pages "
                "between requests has not been served against a "
                "reference")
        sched = self.serving.scheduler
        policy = getattr(sched, "policy", "monolithic")
        if policy != "monolithic" and selects:
            raise ServingUnsupported(
                f"scheduler.policy {policy!r} with latent attention and a "
                "key selection (index_topk)",
                "a prefill chunk attends from a context already in the "
                "pool, and the selection over cached index keys for T > 1 "
                "queries is not built; only whole-prompt prefills "
                "(monolithic) and T=1 decode steps are")
        if getattr(getattr(sched, "speculative", None), "enabled", False):
            raise ServingUnsupported(
                "scheduler.speculative with latent attention",
                "a verify window over latent pages has not been served "
                "against a reference")
        if tp_size > 1 or ep_size > 1:
            raise ServingUnsupported(
                "tp_size / ep_size > 1 with latent attention",
                "the latent pools have no head axis to shard, and the "
                "expert layer holds a fixed share (moe_experts_held)")

    def _refuse_for_ring(self, tp_size, ep_size):
        """A window layer's ring holds the last ``window`` keys of ONE
        sequence and a prefill fills it from an empty context: whatever
        shares pages between sequences, or brings more than a few rows to
        a context already there, is not built."""
        if getattr(self.serving.prefix_cache, "enabled", False):
            raise ServingUnsupported(
                "prefix_cache with sliding-window layers",
                "a ring page holds one sequence's newest keys and is "
                "overwritten as it decodes: it cannot be shared, and a "
                "prefill onto cached full-attention pages would find the "
                "ring empty")
        sched = self.serving.scheduler
        if getattr(sched, "policy", "monolithic") != "monolithic":
            raise ServingUnsupported(
                f"scheduler.policy {sched.policy!r} with sliding-window "
                "layers",
                "a prefill chunk or a speculative verify window onto a "
                "context already in the ring may bring no more rows than "
                "the ring's slack (ring x page - window + 1); "
                "only whole-prompt prefills (monolithic) and T=1 decode "
                "steps are built")
        if tp_size > 1 or ep_size > 1:
            raise ServingUnsupported(
                "tp_size / ep_size > 1 with sliding-window layers",
                "the scanned periods' stacked weights have no sharding "
                "rules yet")

    def _refuse_for_sparse(self, tp_size, ep_size):
        """A block-sparse attention layer selects a query's blocks from
        compressed keys: a prefill computes them from the keys it brings
        and selects over those, from an empty context; a decode step
        selects over the pool's for its one query."""
        if getattr(self.serving.prefix_cache, "enabled", False):
            raise ServingUnsupported(
                "prefix_cache with block-sparse attention",
                "a prefill onto shared pages would select over compressed "
                "keys already in the pool (the selection for T > 1 queries "
                "on a pooled context is not built), and a page's last "
                "compressed key averages keys of the page after it, which "
                "another request's continuation would not share")
        sched = self.serving.scheduler
        policy = getattr(sched, "policy", "monolithic")
        if policy != "monolithic":
            raise ServingUnsupported(
                f"scheduler.policy {policy!r} with block-sparse attention",
                "a prefill chunk attends from a context already in the "
                "pool, and the selection over cached compressed keys for "
                "T > 1 queries is not built; only whole-prompt prefills "
                "(monolithic) and T=1 decode steps are")
        if getattr(getattr(sched, "speculative", None), "enabled", False):
            raise ServingUnsupported(
                "scheduler.speculative with block-sparse attention",
                "a verify window is T > 1 queries on a pooled context, "
                "and a rejected draft's compressed keys would have to be "
                "taken back")
        if tp_size > 1 or ep_size > 1:
            raise ServingUnsupported(
                "tp_size / ep_size > 1 with block-sparse attention",
                "the compressed-key pool and the block gather have no "
                "sharding rules yet")

    def _state_layers_name(self):
        """The kind of layers that keep a recurrent state, for a refusal
        to name."""
        return "state-space layers" if getattr(self.config, "has_ssm",
                                               False) \
            else "linear-attention layers"

    def _refuse_for_state(self, tp_size, ep_size, decode_chunk):
        """A state-space or linear-attention layer's state is the whole of
        one sequence's past in one row a slot: it can be started from
        zero and advanced, not shared, rolled back or cut at a page
        boundary."""
        layers = self._state_layers_name()
        if getattr(self.serving.prefix_cache, "enabled", False):
            raise ServingUnsupported(
                f"prefix_cache with {layers}",
                "a prefill onto shared pages would need the recurrent "
                "state as it stood at the page boundary, and no snapshot "
                "of it is kept")
        sched = self.serving.scheduler
        if getattr(getattr(sched, "speculative", None), "enabled", False):
            raise ServingUnsupported(
                f"scheduler.speculative with {layers}",
                "a verify window advances the state by every drafted "
                "token, and a rejected draft would have to roll it back")
        if int(decode_chunk) > 1:
            raise ServingUnsupported(
                f"decode_chunk > 1 with {layers}",
                "the device scan runs on past a request's last token, "
                "and the state it advanced there cannot be taken back")
        if tp_size > 1 or ep_size > 1:
            raise ServingUnsupported(
                f"tp_size / ep_size > 1 with {layers}",
                "the state pools and the scanned periods' stacked weights "
                "have no sharding rules yet")

    def _refuse_migration(self, what):
        """KV-page migration moves the pages of the growing tables; a
        window model's rings, and a state-space model's recurrent state,
        would stay behind."""
        if self.ring_pages:
            raise ServingUnsupported(
                f"{what} with sliding-window layers",
                "a handed-off or imported request would need its ring's "
                "pages moved and re-seated in the receiving slot's ring")
        if self._stateful:
            raise ServingUnsupported(
                f"{what} with {self._state_layers_name()}",
                "a handed-off or imported request would need its slot's "
                "recurrent state moved with its pages")
        if self._sparse:
            raise ServingUnsupported(
                f"{what} with block-sparse attention",
                "a page's last compressed key averages keys of the page "
                "after it: pages moved one by one would not carry it")

    def _seat(self, slot: int, req_id):
        """Point ``slot``'s row of the tables at the request's pages (and
        its ring, in the row's last columns)."""
        pages = self.alloc.seq_pages[req_id]
        self.tables[slot, :] = 0
        self.tables[slot, :len(pages)] = pages
        if self.ring_pages:
            self.tables[slot, -self.ring_pages:] = \
                self.alloc.seq_rings[req_id]

    # -- telemetry -------------------------------------------------------
    @property
    def telemetry(self):
        return self._telemetry if self._telemetry is not None \
            else get_telemetry()

    @property
    def _profiling(self):
        tel = self.telemetry
        return getattr(tel, "profiling", None) if tel is not None else None

    def _wrap_compiled(self, fn, site, keep=False):
        """Register the jitted entry point under its site name
        (``telemetry.op_scopes``; ``keep``: readable after this engine is
        dropped), and compile-trace it with the profiling plane on."""
        fn = register_compiled(fn, site, mesh=self.mesh, keep=keep)
        prof = self._profiling
        return prof.wrap(fn, site) if prof is not None else fn

    # -- the per-step report ---------------------------------------------
    @staticmethod
    def _new_report():
        return {"t0_ns": None, "t1_ns": None, "dispatches": [],
                "emitted": [], "prompt_tokens": 0, "active": 0, "queued": 0,
                "state_redone": 0}

    def _emit(self, req_id, n=1, t_ns=None):
        """``n`` output tokens of ``req_id`` are on the host (as of
        ``t_ns``, default now): stamped into the open report
        (``perf_counter_ns``) and the request's lifecycle trace (the
        engine clock)."""
        self._report["emitted"].append(
            (req_id, n, time.perf_counter_ns() if t_ns is None else t_ns))
        self.tracer.tokens(req_id, n)

    def step_reports(self):
        """The last ``STEP_REPORTS_KEPT`` per-step reports, oldest first
        (``last_step`` is the newest).  Each: ``t0_ns``/``t1_ns`` of the
        ``step()`` call; ``dispatches`` (``phase``, ``batch``, ``tokens``,
        ``t0_ns``, ``t1_ns``; a prefill adds ``real`` and ``context``, a
        decode ``contexts``) and ``emitted`` (``(req_id, n_tokens,
        t_host_ns)``, stamped when the tokens reached the host) since the
        previous ``step()`` returned; ``prompt_tokens`` whose keys and
        values became available; ``active`` and ``queued`` at the end;
        ``state_redone``, of a model with state-space layers, the slots
        whose recurrent state was built again after a dropped decode row
        (``_redo_state``)."""
        return list(self._reports)

    def _prof_track(self, span):
        """HBM attribution context for serve_step/prefill spans."""
        prof = self._profiling
        return prof.track(span) if prof is not None \
            else contextlib.nullcontext()

    def _serve_event(self, name, **attrs):
        tel = self.telemetry
        if tel is None or not tel.enabled:
            return
        clean = {k: (v if isinstance(v, (int, float, str)) else str(v))
                 for k, v in attrs.items() if v is not None and v != ""}
        tel.serve(name, attrs=clean or None)

    def _observe_ms(self, name, ms):
        """Record one latency sample into registry histogram ``name``
        (telemetry-gated; None samples — state never reached — drop)."""
        if ms is None:
            return
        tel = self.telemetry
        if tel is not None and tel.enabled:
            tel.registry.histogram(name).observe(ms)

    def _close_trace(self, req: _Request, terminal: str, reason: str = ""):
        """Close a request's lifecycle trace with its terminal: bump SLO /
        goodput counters from the deadline verdict, land the latency
        histogram samples, and emit the frozen ``serve/request/<terminal>``
        trace event carrying every derived latency."""
        tr = self.tracer.terminal(req.req_id, terminal,
                                  n_generated=len(req.out), reason=reason)
        if tr is None:   # leak_report() will surface the tracer error
            return
        slo = tr.slo()
        if slo == "ok":
            self.stats["slo_attained"] += 1
        elif slo == "miss":
            self.stats["slo_missed"] += 1
        if terminal == "finish":
            self.stats["goodput_tokens"] += len(req.out)
        tel = self.telemetry
        if tel is not None and tel.enabled:
            if slo == "ok":
                tel.count("serve/slo_attained")
            elif slo == "miss":
                tel.count("serve/slo_missed")
            if terminal == "finish":
                # decode-rate and end-to-end distributions track SUCCESSFUL
                # requests; abnormal terminals would skew them downward
                tel.count("serve/goodput_tokens", len(req.out))
                self._observe_ms("serve/tpot_ms", tr.tpot_ms())
                self._observe_ms("serve/e2e_ms", tr.e2e_ms())
        self._serve_event(
            f"serve/request/{terminal}", req_id=req.req_id,
            slot=(tr.slot if tr.slot >= 0 else None),
            reason=reason, n_generated=len(req.out),
            queue_wait_ms=_round_ms(tr.queue_wait_ms()),
            ttft_ms=_round_ms(tr.ttft_ms()),
            tpot_ms=_round_ms(tr.tpot_ms()),
            e2e_ms=_round_ms(tr.e2e_ms()), slo=slo,
            slo_class=req.slo_class)
        # critical-path attribution rides adjacent to the terminal: one
        # frozen serve/request/attr event whose ordered stage breakdown
        # sums to e2e_ms.  Closed at the tracer's terminal timestamp so
        # both events agree on when the request ended.
        attrs = self.attrib.finalize(req.req_id, terminal,
                                     now=tr.t_terminal)
        if attrs is not None:
            self._serve_event("serve/request/attr", **attrs)

    # -- host control flow ---------------------------------------------
    def _reject(self, req_id, reason, detail=""):
        self.stats["rejected"] += 1
        self._serve_event("serve/reject", req_id=req_id, reason=reason,
                          detail=detail)
        raise RequestRejected(req_id, reason, detail)

    def add_request(self, req_id, prompt_ids, max_new_tokens: int = 32,
                    temperature: float = 0.0, seed: int = 0,
                    top_k: int = 0, top_p: float = 1.0,
                    deadline_s: Optional[float] = None,
                    slo_class: Optional[str] = None,
                    prefill_only: bool = False,
                    arrived_at: Optional[float] = None):
        """Validate and enqueue one request.  Raises
        :class:`RequestRejected` (typed reason, engine state untouched)
        instead of asserting; ``deadline_s`` is a TTL from now — the
        request is cancelled at the next step boundary once it expires,
        queued or mid-flight.  ``slo_class`` ("latency" | "throughput",
        default ``serving.scheduler.slo_class_default``) orders admission
        and prefill-chunk scheduling under the chunked policy and picks
        the per-class TTL default when ``deadline_s`` is omitted.
        ``prefill_only`` (disaggregated fleets): validate and reserve
        exactly as a full request — same buckets, same feasibility — but
        capture a :class:`PrefillHandoff` at prefill completion instead
        of decoding; collect with :meth:`pop_prefilled`.  ``arrived_at``
        (engine-clock seconds): when the request reached the front end,
        so its trace's queue wait and TTFT count from arrival, not from
        this call."""
        cfg = self.serving
        if prefill_only:
            self._refuse_migration("prefill_only requests")
        if self.draining:
            self._reject(req_id, REJECT_DRAINING,
                         "engine is draining; admission stopped")
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not prompt or int(max_new_tokens) <= 0:
            self._reject(req_id, REJECT_BAD_REQUEST,
                         f"prompt len {len(prompt)}, "
                         f"max_new_tokens {max_new_tokens}")
        if len(prompt) + max_new_tokens > self.max_seq:
            self._reject(req_id, REJECT_OVERSIZED,
                         f"prompt {len(prompt)} + budget {max_new_tokens} "
                         f"exceeds max_seq {self.max_seq}")
        if cfg.max_prompt_tokens and len(prompt) > int(cfg.max_prompt_tokens):
            self._reject(req_id, REJECT_OVERSIZED,
                         f"prompt {len(prompt)} exceeds "
                         f"serving.max_prompt_tokens {cfg.max_prompt_tokens}")
        total = len(prompt) + max_new_tokens
        # worst-case reservation (no cached prefix), using the SAME
        # padding the scheduler will request at slot-fill time
        padded = self.scheduler.prefill_padded_len(len(prompt))
        need = -(-min(max(total, padded),
                      self.max_pages_per_seq * self.page_size)
                 // self.page_size)
        usable = self.alloc.num_pages - 1   # minus the scratch page
        if need > usable:
            self._reject(req_id, REJECT_INFEASIBLE,
                         f"needs {need} pages but the pool only has "
                         f"{usable}; it would deadlock the queue "
                         "head-of-line")
        if req_id in self.alloc.seq_pages or req_id in self.finished or \
                any(r.req_id == req_id for r in self.queue):
            self._reject(req_id, REJECT_DUPLICATE,
                         "req_id already queued, active, or undelivered")
        if not (0.0 < top_p <= 1.0) or top_k < 0 or temperature < 0.0:
            self._reject(req_id, REJECT_BAD_SAMPLING,
                         f"top_k={top_k}, top_p={top_p}, "
                         f"temperature={temperature}")
        sched_cfg = cfg.scheduler
        if slo_class is None:
            slo_class = sched_cfg.slo_class_default
        if slo_class not in SLO_CLASSES:
            self._reject(req_id, REJECT_BAD_REQUEST,
                         f"slo_class {slo_class!r} is not one of "
                         f"{SLO_CLASSES}")
        self._apply_admission_policy(req_id)
        now = self._clock()
        # TTL precedence: explicit deadline_s > the SLO class's default
        # (serving.scheduler.slo_classes) > serving.default_deadline_s
        ttl = deadline_s if deadline_s is not None \
            else (sched_cfg.class_deadline_s(slo_class)
                  or float(cfg.default_deadline_s) or None)
        deadline = (now + ttl) if ttl else 0.0
        self.queue.append(_Request(req_id, prompt, max_new_tokens,
                                   temperature, seed, top_k, top_p,
                                   submit_time=now, deadline=deadline,
                                   slo_class=slo_class,
                                   prefill_only=bool(prefill_only)))
        self.stats["admitted"] += 1
        # lifecycle trace opens HERE: admission is the promise leak_report
        # audits — exactly one serve/request/* terminal closes it
        self.tracer.admit(req_id, deadline=deadline, now=now,
                          arrived_at=arrived_at)
        self.attrib.admit(req_id, now=now)
        self._serve_event("serve/admit", req_id=req_id,
                          queue_depth=len(self.queue),
                          free_pages=self.alloc.free_page_count)
        self._serve_event("serve/request/admitted", req_id=req_id,
                          queue_depth=len(self.queue),
                          prompt_tokens=len(prompt),
                          max_new_tokens=int(max_new_tokens),
                          deadline=int(bool(deadline)),
                          slo_class=slo_class)
        self._admit()

    def _admission_pressure(self):
        cfg = self.serving
        hard_full = bool(cfg.max_queue) and \
            len(self.queue) >= int(cfg.max_queue)
        # reclaimable (cached, ref-0) pages are one eviction away from the
        # free list — counting them stops a warm prefix cache from reading
        # as page pressure and shedding admissible traffic
        overloaded = self._admission.update(len(self.queue),
                                            self.alloc.available_page_count)
        return hard_full, overloaded

    def _apply_admission_policy(self, req_id):
        """Admission control for one arrival: no-op until the hard queue
        cap or a watermark trips, then apply ``serving.overload_policy``
        — ``reject`` raises, ``shed-oldest`` displaces the oldest queued
        request, ``block`` synchronously steps the engine until pressure
        clears or ``block_max_steps`` is spent (then rejects)."""
        hard_full, overloaded = self._admission_pressure()
        if not hard_full and not overloaded:
            return
        policy = self.serving.overload_policy
        if policy == "block":
            for _ in range(int(self.serving.block_max_steps)):
                if not (self.queue or self.n_active):
                    break
                # requests finishing while the arrival blocks stay
                # retrievable from ``finished`` — the caller isn't in its
                # step() loop to catch them
                self.finished.update(self.step())
                hard_full, overloaded = self._admission_pressure()
                if not hard_full and not overloaded:
                    return
        elif policy == "shed-oldest" and self.queue:
            # the newcomer displaces the oldest QUEUED request (head of
            # line), so queue depth is unchanged and admission proceeds;
            # pure page-pressure overload with an empty queue still
            # rejects — shedding queued work frees no pages
            victim = self.queue.pop(0)
            self._terminate(victim, "shed", SHED_OLDEST,
                            detail=f"displaced by {req_id!r}")
            self.stats["shed"] += 1
            self._serve_event("serve/shed", req_id=victim.req_id,
                              reason=SHED_OLDEST)
            return
        reason = REJECT_QUEUE_FULL if hard_full else REJECT_OVERLOADED
        self._reject(req_id, reason,
                     f"queue_depth={len(self.queue)}, "
                     f"free_pages={self.alloc.free_page_count}, "
                     f"policy={policy}")

    def _bucket(self, n: int) -> int:
        return 1 << max(3, math.ceil(math.log2(max(n, 1))))

    def _terminate(self, req: _Request, status: str, reason: str,
                   detail: str = ""):
        """Record the typed terminal result for a request leaving the
        engine abnormally; the partial output (prompt + generated) rides
        in the record.  Pages are the caller's job (queued requests own
        none)."""
        self._rng.pop(req.req_id, None)
        self.terminated[req.req_id] = RequestResult(
            req_id=req.req_id, status=status, reason=reason,
            tokens=list(req.prompt) + list(req.out),
            n_generated=len(req.out), detail=detail)
        self._close_trace(req, _TERMINAL_BY_STATUS[status], reason=reason)

    def _evict_slot(self, slot: int, status: str, reason: str,
                    detail: str = ""):
        """Remove ONE active request: free its pages immediately, zero its
        table row and length, record the terminal result.  The rest of the
        batch is untouched."""
        req = self.slots[slot]
        self.scheduler.release_slot(slot, req)
        self.alloc.free_sequence(req.req_id)
        self.slots[slot] = None
        self.lengths[slot] = 0
        self.tables[slot, :] = 0
        self._terminate(req, status, reason, detail)

    def _expire_deadlines(self):
        """Cancel every expired request at this step boundary — queued
        requests are dropped from the queue, mid-flight ones are evicted
        with their pages freed immediately."""
        now = self._clock()
        keep, expired = [], []
        for req in self.queue:
            (expired if req.deadline and now >= req.deadline
             else keep).append(req)
        self.queue = keep
        for req in expired:
            self._terminate(req, "deadline", SHED_DEADLINE,
                            detail="expired while queued")
            self.stats["deadline"] += 1
            self._serve_event("serve/deadline", req_id=req.req_id,
                              reason=SHED_DEADLINE, where="queued")
        evicted = False
        for slot, req in enumerate(self.slots):
            if req is not None and req.deadline and now >= req.deadline:
                rid = req.req_id
                self._evict_slot(slot, "deadline", SHED_DEADLINE,
                                 detail="expired mid-flight")
                self.stats["deadline"] += 1
                self._serve_event("serve/deadline", req_id=rid,
                                  reason=SHED_DEADLINE, where="active")
                evicted = True
        if evicted:
            self._admit()

    def _admit(self):
        # policy hook: the chunked scheduler stable-sorts latency-class
        # requests ahead of throughput-class ones (FIFO within a class)
        self.scheduler.order_queue()
        for slot in range(self.max_batch):
            if not self.queue or self.slots[slot] is not None:
                continue
            req = self.queue[0]
            total = len(req.prompt) + req.max_new_tokens
            # prefix cache: attach every fully-cached prefix page without
            # prefill; a partial next-page match copies on write.  The
            # lookup is a pure read — nothing is pinned until allocate().
            match = (self.prefix_cache.lookup(req.prompt)
                     if self.prefix_cache is not None else PrefixMatch())
            cached = match.cached_tokens(self.page_size)
            # the scheduler owns the prefill shape: the monolithic policy
            # pads the suffix to a power-of-two bucket, or a long one to
            # whole pieces (prefill_piece_rows), the chunked one to a
            # whole number of prefill chunks
            padded = self.scheduler.prefill_padded_len(
                len(req.prompt) - cached)
            # reservation covers the budget AND the padded suffix prefill;
            # the cap keeps an unaligned cached prefix from pushing the
            # padding past the table — padding writes past the reservation
            # land on the sacrificial scratch page (clamped/zero columns)
            need_tokens = min(max(total, cached + padded),
                              self.max_pages_per_seq * self.page_size)
            shared = list(match.pages)
            protect = (match.cow_src,) if match.cow_src is not None else ()
            need_fresh = -(-need_tokens // self.page_size) - len(shared)
            pinned = set(shared) | set(protect)
            evictable = sum(1 for p in self.alloc.reclaimable
                            if p not in pinned)
            if need_fresh > self.alloc.free_page_count + evictable \
                    or not self.alloc.ring_available():
                return          # head-of-line: keep FIFO order
            # full reservation (prompt + budget) at admission: an admitted
            # request can NEVER deadlock on pages mid-flight (no vLLM-style
            # preemption/recompute machinery needed); only bucket-padding
            # surplus is returned after prefill.  Allocate BEFORE popping:
            # an injected page_alloc fault leaves nothing mutated — shared
            # refcounts untouched, nothing half-attached — and the request
            # retries from the queue on the next step, unchanged.
            try:
                pages = self.alloc.allocate(req.req_id, need_tokens,
                                            shared=shared, protect=protect)
            except PageAllocationError as e:
                self.stats["step_faults"] += 1
                self._serve_event("serve/fault", req_id=req.req_id,
                                  site="page_alloc", error=str(e))
                return
            if cached:
                self.stats["prefix_hits"] += 1
                self._serve_event("serve/prefix_hit", req_id=req.req_id,
                                  pages_reused=len(shared),
                                  tokens_reused=cached,
                                  cow=int(match.cow_src is not None))
            self.queue.pop(0)
            self._seat(slot, req.req_id)
            self.lengths[slot] = 0
            self.slots[slot] = req
            tr = self.tracer.prefill_start(req.req_id, slot)
            self.attrib.prefill_start(req.req_id)
            if tr is not None:
                self._observe_ms("serve/queue_wait_ms", tr.queue_wait_ms())
                self._serve_event("serve/request/prefill_start",
                                  req_id=req.req_id, slot=slot,
                                  pages=len(pages), cached_tokens=cached,
                                  queue_wait_ms=_round_ms(
                                      tr.queue_wait_ms()))
            try:
                if match.cow_src is not None:
                    # the request's first owned page inherits the partial
                    # match's content; its divergent tail is overwritten
                    # by the suffix prefill, so the shared source page is
                    # never touched
                    self._copy_page(match.cow_src, pages[len(shared)])
                    self.stats["prefix_cow_copies"] += 1
                    self._serve_event("serve/prefix_cow",
                                      req_id=req.req_id,
                                      src=int(match.cow_src),
                                      dst=int(pages[len(shared)]),
                                      tokens=int(match.cow_tokens))
                complete = self.scheduler.fill_slot(slot, req, cached)
            except Exception as e:   # fault isolation: only THIS request
                logger.warning(f"evicting request {req.req_id!r} after "
                               f"prefill fault: {e}")
                self._evict_slot(slot, "evicted", EVICT_FAULT,
                                 detail=str(e))
                self.stats["evicted"] += 1
                self._serve_event("serve/evict", req_id=req.req_id,
                                  reason=EVICT_FAULT, error=str(e))
                continue
            if complete:
                # monolithic: the whole prefill ran inside fill_slot;
                # chunked defers both the prefill and this completion to
                # later step() calls (_complete_prefill at the last chunk)
                self._complete_prefill(slot, req)

    def _complete_prefill(self, slot: int, req: _Request):
        """Admission tail once the prompt is fully in cache: trim the
        padded reservation to the true need and index the prompt's full
        pages into the prefix cache."""
        self._trim_reservation(slot, req)
        if self.prefix_cache is not None:
            added = self.prefix_cache.insert(
                req.prompt, self.alloc.seq_pages[req.req_id])
            if added:
                self._serve_event("serve/prefix_insert",
                                  req_id=req.req_id, pages=added)
        if req.prefill_only:
            self._capture_handoff(slot, req)

    def _capture_handoff(self, slot: int, req: _Request):
        """Prefill-only admission tail: the prompt is fully in cache and
        the first token is sampled, so capture everything a decode
        replica needs, shrink the reservation to the prompt pages, and
        keep them PINNED under this request id until
        :meth:`release_handoff`.  The slot frees immediately for the next
        prefill — that asymmetry is the whole point of the role split."""
        self.alloc.shrink(req.req_id, len(req.prompt))
        rng = self._rng.pop(req.req_id, None)
        # serialize the timing context BEFORE the trace closes below —
        # finalize pops it; the handoff-capture stamp starts the migrate
        # stage the decode side's import will close
        trace_ctx = self.attrib.capture_handoff(req.req_id)
        self.handoffs[req.req_id] = PrefillHandoff(
            req_id=req.req_id, prompt=list(req.prompt),
            max_new_tokens=req.max_new_tokens,
            temperature=req.temperature, seed=req.seed,
            top_k=req.top_k, top_p=req.top_p, slo_class=req.slo_class,
            last_token=int(req.last_token), out=list(req.out),
            rng_state=(rng.bit_generator.state if rng is not None
                       else None),
            pages=list(self.alloc.seq_pages[req.req_id]),
            trace_ctx=trace_ctx)
        self._new_handoffs.append(req.req_id)
        self.scheduler.release_slot(slot, req)
        self.slots[slot] = None
        self.lengths[slot] = 0
        self.tables[slot, :] = 0
        self.stats["prefill_handoffs"] += 1
        self._close_trace(req, "finish", reason="prefill_handoff")

    def _page_pools(self):
        """The leaves of the caches that hold PAGES (a state-space
        model's recurrent state, a row a slot, is none of them)."""
        return jax.tree_util.tree_leaves(
            self.caches.full if self._stateful else self.caches)

    # -- KV-page migration (disaggregated fleets) ------------------------
    @property
    def kv_page_bytes(self) -> int:
        """Analytic bytes of ONE KV page across every cache leaf (all
        layers, K and V) — the unit the fleet's page-transfer budget and
        bytes-saved accounting multiply by."""
        if self._kv_page_bytes is None:
            self._kv_page_bytes = sum(
                int(np.prod(leaf.shape[:1] + leaf.shape[2:])) *
                jnp.dtype(leaf.dtype).itemsize
                for leaf in self._page_pools())
        return self._kv_page_bytes

    @staticmethod
    def _pad_pow2(ids) -> np.ndarray:
        """Page-id vector padded to a power-of-two length with the
        scratch page (0): bounds the gather/scatter jit cache to log2
        distinct shapes, and pad traffic lands on the sacrificial scratch
        page by construction."""
        n = max(1, len(ids))
        out = np.zeros(1 << (n - 1).bit_length(), np.int32)
        out[:len(ids)] = ids
        return out

    def pop_prefilled(self) -> Dict[Any, PrefillHandoff]:
        """Hand back the handoffs captured since the last call (req_id →
        :class:`PrefillHandoff`).  Pages stay pinned under this engine's
        allocator until :meth:`release_handoff` — the fleet releases only
        AFTER the decode side commits, so a kill of either replica
        mid-migration leaves one consistent copy to redispatch from."""
        out = {rid: self.handoffs[rid] for rid in self._new_handoffs
               if rid in self.handoffs}
        self._new_handoffs = []
        return out

    def release_handoff(self, req_id) -> bool:
        """Unpin a handed-off request's prompt pages (the decode side
        acknowledged, or the fleet abandoned the migration).  The full
        prompt pages were indexed into this replica's prefix cache at
        capture, so they park in the reclaimable tier — the hot prefix
        stays warm for the next prefill instead of dissolving."""
        if self.handoffs.pop(req_id, None) is None:
            return False
        self.alloc.free_sequence(req_id)
        return True

    def export_pages(self, page_ids):
        """Device-gather the KV content of ``page_ids`` (every layer,
        every cache leaf) into a standalone payload pytree shaped like
        the cache with P = pow2-padded ``len(page_ids)`` — the migration
        wire format.  Pure read, no donation."""
        self._refuse_migration("export_pages")
        padded = self._pad_pow2(page_ids)
        if self._gather_pages_fn is None:
            def gather(caches, ids):
                return jax.tree_util.tree_map(
                    lambda leaf: leaf[:, ids], caches)
            self._gather_pages_fn = self._wrap_compiled(
                jax.jit(gather), "serve/export_pages")
        if self.mesh is not None:
            with self.mesh:
                return self._gather_pages_fn(self.caches,
                                             jnp.asarray(padded))
        return self._gather_pages_fn(self.caches, jnp.asarray(padded))

    def import_pages(self, payload, page_ids):
        """Scatter an exported payload into this engine's ``page_ids``
        (the :meth:`export_pages` counterpart; donation makes it an
        in-place page write).  Payload pad lanes beyond ``len(page_ids)``
        scatter onto the sacrificial scratch page.  Quantized payloads
        (the source replica's ``comm_quant`` wire codec) are
        self-describing and dequantize here — the destination needs no
        matching config."""
        self._refuse_migration("import_pages")
        payload = CommQuantizer.decode_payload(payload)
        leaves = jax.tree_util.tree_leaves(payload)
        padded = np.zeros(leaves[0].shape[1], np.int32)
        padded[:len(page_ids)] = page_ids
        if self._scatter_pages_fn is None:
            def scatter(caches, payload, ids):
                return jax.tree_util.tree_map(
                    lambda leaf, pay: leaf.at[:, ids].set(pay),
                    caches, payload)
            self._scatter_pages_fn = self._wrap_compiled(
                jax.jit(scatter, donate_argnums=(0,)),
                "serve/import_pages")
        if self.mesh is not None:
            with self.mesh:
                self.caches = self._scatter_pages_fn(
                    self.caches, payload, jnp.asarray(padded))
        else:
            self.caches = self._scatter_pages_fn(self.caches, payload,
                                                 jnp.asarray(padded))

    def import_request(self, handoff: PrefillHandoff, payload=None,
                       shared_pages=(), deadline_s=None) -> bool:
        """Install a migrated request directly into a decode slot: full
        reservation (prompt + budget) attaching ``shared_pages`` (pages
        already resident here by content — the dedup plan from
        ``prefix_cache.resident_prefix``), scatter ``payload`` (the
        source's exported non-shared prompt pages) into freshly owned
        pages, and restore the sampler stream.  NOTHING observable —
        tracer, events, stats, prefix index — mutates until
        :meth:`commit_import`, and :meth:`cancel_import` rolls the
        installation back to nothing, so the fleet's ``migrate_commit``
        fault site is all-or-nothing.  Returns True when installed, False
        when this engine cannot take it right now (draining, no free
        slot, page pressure, id collision)."""
        self._refuse_migration("import_request")
        if self.draining:
            return False
        slot = next((s for s in range(self.max_batch)
                     if self.slots[s] is None), None)
        if slot is None:
            return False
        rid = handoff.req_id
        if rid in self.alloc.seq_pages or rid in self.finished or \
                any(r.req_id == rid for r in self.queue):
            return False
        total = len(handoff.prompt) + handoff.max_new_tokens
        shared = list(shared_pages)
        try:
            pages = self.alloc.allocate(rid, total, shared=shared)
        except PageAllocationError:
            return False
        try:
            n_import = len(handoff.pages) - len(shared)
            if n_import > 0:
                self.import_pages(
                    payload, pages[len(shared):len(shared) + n_import])
        except Exception:
            self.alloc.free_sequence(rid)
            raise
        req = _Request(rid, list(handoff.prompt),
                       handoff.max_new_tokens, handoff.temperature,
                       handoff.seed, handoff.top_k, handoff.top_p,
                       out=list(handoff.out),
                       last_token=handoff.last_token,
                       submit_time=self._clock(),
                       slo_class=handoff.slo_class,
                       prefilled=len(handoff.prompt))
        if deadline_s is not None:
            req.deadline = self._clock() + float(deadline_s)
        if handoff.rng_state is not None:
            rng = np.random.default_rng(handoff.seed)
            rng.bit_generator.state = handoff.rng_state
            self._rng[rid] = rng
        self._seat(slot, rid)
        self.lengths[slot] = len(handoff.prompt)
        self.slots[slot] = req
        self._pending_imports[rid] = (slot, handoff, len(shared))
        return True

    def commit_import(self, req_id):
        """Make an installed import observable: open the lifecycle trace
        (admit → prefill_start → first_token; the source already sampled
        the first token), bump counters, and index the prompt pages into
        this replica's prefix cache so the NEXT request sharing the
        prefix skips its transfer entirely (migrate-once-per-replica)."""
        slot, handoff, n_shared = self._pending_imports.pop(req_id)
        req = self.slots[slot]
        self.stats["admitted"] += 1
        self.stats["imports"] += 1
        self.tracer.admit(req_id, deadline=req.deadline,
                          now=self._clock())
        # adopt the migrated timing context: the attr event at this
        # replica's terminal reports the FULL path (source queue +
        # prefill, the migration wait closed by this import, decode here)
        self.attrib.import_ctx(req_id, handoff.trace_ctx)
        self._serve_event("serve/admit", req_id=req_id,
                          queue_depth=len(self.queue),
                          free_pages=self.alloc.free_page_count)
        self._serve_event("serve/request/admitted", req_id=req_id,
                          queue_depth=len(self.queue),
                          prompt_tokens=len(req.prompt),
                          max_new_tokens=int(req.max_new_tokens),
                          deadline=int(bool(req.deadline)),
                          slo_class=req.slo_class)
        tr = self.tracer.prefill_start(req_id, slot)
        if tr is not None:
            self._serve_event("serve/request/prefill_start",
                              req_id=req_id, slot=slot,
                              pages=len(self.alloc.seq_pages[req_id]),
                              cached_tokens=n_shared * self.page_size,
                              queue_wait_ms=_round_ms(tr.queue_wait_ms()))
        self._note_first_token(slot, req)
        if self.prefix_cache is not None:
            added = self.prefix_cache.insert(
                req.prompt, self.alloc.seq_pages[req_id])
            if added:
                self._serve_event("serve/prefix_insert", req_id=req_id,
                                  pages=added, at="import")
        return req

    def cancel_import(self, req_id) -> bool:
        """Roll an installed (uncommitted) import back to nothing: free
        the pages, clear the slot, drop the restored RNG.  No trace was
        opened and no event fired, so a faulted ``migrate_commit`` leaves
        this engine exactly as it was (all-or-nothing)."""
        entry = self._pending_imports.pop(req_id, None)
        if entry is None:
            return False
        slot, _, _ = entry
        self.scheduler.release_slot(slot, self.slots[slot])
        self.attrib.discard(req_id)
        self.alloc.free_sequence(req_id)
        self._rng.pop(req_id, None)
        self.slots[slot] = None
        self.lengths[slot] = 0
        self.tables[slot, :] = 0
        return True

    def _trim_reservation(self, slot: int, req: _Request):
        """Trim the slot's reservation to the request's TRUE page need.

        Bucketed prefill over-allocates to the padded suffix length; the
        surplus used to be returned only when ``need_tokens > total``,
        leaving the invariant to the caller.  Trimming unconditionally —
        and asserting the result — is what lets the ragged kernel, the
        block tables, and the allocator all agree on true lengths
        (``leak_report`` audits the same invariant engine-wide)."""
        total = len(req.prompt) + req.max_new_tokens
        self.alloc.shrink(req.req_id, total)
        pages = self.alloc.seq_pages[req.req_id]
        expected = max(1, -(-total // self.page_size))
        assert len(pages) == expected, (
            f"request {req.req_id!r}: {len(pages)} pages held after trim, "
            f"expected {expected} for {total} tokens "
            f"(page_size {self.page_size})")
        self._seat(slot, req.req_id)

    def _prefill_next(self, real: int, context: int, sample: bool = True,
                      chunk: Optional[int] = None,
                      slot: Optional[int] = None):
        """Sizes of the prefill dispatch about to be launched (``_run_step``
        keeps the signature its wrappers replace, so they come ahead of
        it): ``real`` prompt tokens under its padded ``tokens``, the
        ``context`` its keys and values then reach, whether its caller
        will ``sample`` from its last real row (a chunk that is not the
        prompt's last reads nothing, and its program takes no head), and
        the ``chunk``'s index in its prompt where the prompt comes in
        chunks (the chunked policy; reported with the dispatch).  ``slot``:
        the slot the rows belong to, which a model with state-space layers
        is told (its recurrent state is a row a slot)."""
        self._prefill_sizes = {"real": int(real), "context": int(context),
                               "head_rows": int(bool(sample))}
        if chunk is not None:
            self._prefill_sizes["chunk"] = int(chunk)
        self._prefill_slot = slot

    def _run_step(self, ids, tables, lengths, phase="decode"):
        """One dispatch of the paged step: the launch only, nothing here
        waits for the device.  ``lengths`` comes as the host's numpy array
        (the call places it): ``kernel_grid`` is reckoned from it.  A
        prefill (sizes from ``_prefill_next``) takes the head on the one
        row it samples from, or on none (logits [1, 1 | 0, V]); every
        other phase on all its rows, and a row whose id is negative
        starts from the pick the decode dispatch before this one made for
        its slot (``serve_decode``'s ``fed``: that dispatch need not have
        ended).  Returns ``(logits, caches, lengths
        + T)``, the logits as the :class:`StepLogits` of the dispatch,
        still on the device.  A model that counts its
        dispatches is also told how many of each sequence's rows are
        tokens (a prefill's prompt under its bucket; else every row of a
        slot that holds a context, so none of a decode batch's idle
        slots).  A model with state-space layers is told a PREFILL's real
        rows and its slot (``_prefill_next``); its decode step takes the
        slots it serves from ``lengths``."""
        step_fn, sizes = self._step_fn, {}
        args = (self.params, ids, self.caches, tables, lengths)
        if phase == "prefill":
            step_fn = self._prefill_fn
            sizes, self._prefill_sizes = self._prefill_sizes, None
            # [B, 1 | 0]: the prompt's last row, or no row
            args += (np.full((ids.shape[0], sizes["head_rows"]),
                             sizes["real"] - 1, np.int32),)
        else:
            args += (self._picks,)
        if self._counted or (self._stateful and sizes):
            real = (np.full(ids.shape[0], sizes["real"]) if sizes else
                    np.where(np.asarray(lengths) > 0, ids.shape[1], 0))
            args += (np.asarray(real, np.int32),)
        if self._stateful and sizes:
            args += (np.full(ids.shape[0], self._prefill_slot, np.int32),)
        out = self._dispatch(step_fn, args, phase, *ids.shape,
                             starts=np.asarray(lengths), **sizes)
        self._report["prompt_tokens"] += sizes.get("real", 0)
        return out

    def kernel_grid(self, phase, batch, tokens, starts, config=None,
                    real=None):
        """(run, full) grid steps of the ragged paged-attention kernel in
        one dispatch, every layer of ``config`` (default the target
        model): the (q tile, kv step) pairs that hold keys, reckoned from
        the tokens each sequence starts from as the kernel's item map
        reckons them, and the rectangle of the tile picker's choice for
        this compiled shape they are drawn from.  Both 0 on the jnp path.
        ``decode_chunk`` and ``spec_draft`` run ``tokens`` T=1 forwards,
        each one token further.  A window layer's call reads its ring (a
        decode step) or the prefill's own rows as pages, from the first
        key inside the window.  A block-sparse attention layer's call reads
        the sequences under ``sparse.dense_len`` alone (``real``: a
        prefill's tokens, where its context ends)."""
        if self.attention_impl != "pallas":
            return 0, 0
        config = config or self.config
        calls, T = ((int(tokens), 1) if phase in ("decode_chunk",
                                                  "spec_draft")
                    else (1, int(tokens)))
        batch = int(batch)
        windows = [w for w in getattr(config, "local_attn_pattern", None)
                   or () if w]
        ring = self.ring_pages if windows else 0
        ctx = np.asarray(starts)[None, :] \
            + T * np.arange(1, calls + 1)[:, None]
        if getattr(config, "has_sparse", False):
            context = ctx if real is None else ctx - T + int(real)
            ctx = np.where(context < config.sparse.dense_len, ctx, 0)

        def steps(n_layers, ctx, width, window=None, ring=None):
            key = (batch, T, id(config), width, window)
            if key not in self._kernel_tiles:
                # the heads as the pools hold them (kv_lane_pack)
                pack = kv_lane_pack(config.kv_heads, config.head_dim)
                self._kernel_tiles[key] = pick_tiles(
                    [T] * batch, config.n_heads // config.kv_heads * pack,
                    config.kv_heads // pack, self.page_size,
                    config.head_dim * pack, width,
                    jnp.dtype(self.cache_dtype).itemsize, window=window,
                    ring=ring)
            tiles = self._kernel_tiles[key]
            return np.array([
                n_layers * rect_grid_steps(tiles, batch, T, ctx,
                                           self.page_size, window),
                n_layers * calls * tiles.grid_steps])

        state_layers = getattr(config, "state_layers", 0)
        run = steps(config.n_layers - len(windows) - state_layers, ctx,
                    self.tables.shape[1] - ring)
        for window in sorted(set(windows)):
            n = windows.count(window)
            run = run + (steps(n, ctx, ring, window, ring) if T == 1 else
                         steps(n, np.full_like(ctx, T),
                               -(-T // self.page_size), window))
        return int(run[0]), int(run[1])

    def _latent_walk_keys(self, tokens):
        """Keys a step of a dense latent prefill's walk of the pool: the
        kernel's own choice for ``tokens`` rows a sequence, or the XLA
        walk's block."""
        if self.latent_impl != "pallas":
            return PREFILL_BLOCK_K
        c, pool = self.config, self.caches.latent_pages
        return self.page_size * pick_latent_tiles(
            int(tokens), c.n_heads, c.qk_nope_head_dim, c.v_head_dim,
            c.kv_lora_rank, pool.shape[-1], self.page_size,
            self.tables.shape[1], pool.dtype.itemsize).pages

    def _dispatch(self, fn, args, phase, batch, tokens, *, starts,
                  backend=None, config=None, head_rows=None, **sizes):
        """Launch jitted ``fn(*args)`` as one ``serve/step`` span and one
        entry of the open report's ``dispatches`` (the target model's
        steps, the chunked decode scan, the draft model's — ``config``
        is the model whose layers the dispatch runs; ``starts`` the
        tokens each of its sequences holds before it, on the host;
        ``head_rows`` the rows of a sequence the head ran on, all
        ``tokens`` unless given; ``sizes`` a prefill's ``real`` and
        ``context``, for the record)."""
        head_rows = int(tokens if head_rows is None else head_rows)
        t0_ns = time.perf_counter_ns()
        kernel_grid, kernel_grid_full = self.kernel_grid(
            phase, batch, tokens, starts, config, sizes.get("real"))
        # what the dispatch compiled: the draft model's call binds no
        # backend, so it resolves its own
        kv_write = self.attention_impl if config is None else \
            resolve_paged_impl(None, config.attn_logit_softcap)
        attrs = {"backend": backend or self.attention_backend,
                 "phase": phase, "batch": int(batch), "tokens": int(tokens),
                 "kernel_grid": kernel_grid,
                 "kernel_grid_full": kernel_grid_full,
                 "head_rows": head_rows}
        counted = {}
        if self.ring_pages and config is None:
            counted = self._window_counts(phase, tokens, starts, sizes)
        elif self._latent_dense and phase == "prefill":
            # what its attention walks of the pool: whole blocks of keys
            # over what was cached before the chunk
            block = self._latent_walk_keys(tokens)
            counted = {"ctx_entries": max(
                context_entries(n, self.page_size, block) for n in starts)}
        elif self._stateful and config is None:
            # the rows whose state it advances: a prefill's one slot, a
            # decode step's served slots; each reads and writes its state
            # (and its convolution's last inputs) in every such layer
            rows = int(batch) if phase == "prefill" else \
                int(np.count_nonzero(np.asarray(starts)))
            counted = dict(zip(STATE_COUNTS, (
                rows, 2 * rows * self.state_slot_bytes)))
        attrs.update(counted, **{k: sizes[k] for k in ("chunk",)
                                 if k in sizes})
        with self.telemetry.span("serve/step", attrs=attrs), \
                self._prof_track("prefill" if phase == "prefill"
                                 else "serve_step"), \
                (self.mesh if self.mesh is not None
                 else contextlib.nullcontext()):
            out = fn(*args)
        record = {"phase": phase, "batch": int(batch), "tokens": int(tokens),
                  "kernel_grid": kernel_grid,
                  "kernel_grid_full": kernel_grid_full, "kv_write": kv_write,
                  "head_rows": head_rows, **sizes, **counted,
                  "t0_ns": t0_ns, "t1_ns": time.perf_counter_ns()}
        if self.experts_impl and config is None:
            # what the target model's expert layers compiled to
            record["experts"] = self.experts_impl
        if self.latent_impl and phase == "prefill" and config is None:
            # what the prefill's read of the latent pool compiled to
            record["latent"] = self.latent_impl
        if self.state_impl and phase == "decode" and config is None:
            # what the state-space layers' recurrence compiled to
            record["state"] = self.state_impl
        self._report["dispatches"].append(record)
        if fn in (self._prefill_fn, self._step_fn):
            # the two serving programs: their logits stay on the device
            # with the picks and a counted model's own counters, which
            # land in this record and on the span when the step fetches
            logits, caches, lengths, *counters, picks = out
            for kept in (record, attrs):
                kept.update(picked=0, host_rows=0)
            out = (StepLogits(logits, picks, counters[0] if counters
                              else None, record, attrs), caches, lengths)
            self._unfetched.append(out[0])
            if phase == "decode":
                # of the tokens picked from it, those the next dispatch
                # had been fed before the host held them; rows of it
                # launched for nothing (scheduler._decode_once)
                for kept in (record, attrs):
                    kept.update(ahead=0, redone=0)
                self._picks = picks
        return out

    def _window_counts(self, phase, tokens, starts, sizes):
        """A window model's account of one dispatch, on the host (all of
        it follows from the lengths): ``context_keys``, the causal keys of
        every real query summed over the layers, and ``attended_keys``,
        those inside the layer's window; the pages in use of both kinds
        (``pages_full`` of the growing tables, ``pages_ring``)."""
        starts = np.asarray(starts, np.int64)
        if phase == "prefill":      # positions starts .. starts + real - 1
            rows = np.full_like(starts, sizes["real"])
        else:       # one row a step a live slot, ``tokens`` steps
            steps = int(tokens) if phase in ("decode_chunk",
                                             "spec_draft") else 1
            rows = np.where(starts > 0, steps, 0)
        ends = starts + rows

        def keys(window):
            """Keys the queries at contexts starts + 1 .. ends meet under
            ``window``: sum of min(context, window)."""
            def upto(n):
                m = np.minimum(n, window)
                return m * (m + 1) // 2 + (n - m) * window
            return int((upto(ends) - upto(starts)).sum())

        pattern = self.config.local_attn_pattern
        causal = keys(int(ends.max(initial=0)) + 1)     # no window at all
        return dict(zip(WINDOW_COUNTS, (
            len(pattern) * causal,
            sum(pattern.count(w) * (keys(w) if w else causal)
                for w in set(pattern)),
            self.alloc.num_pages - 1 - self.alloc.available_page_count,
            self.alloc.ring_pages_in_use)))

    def _fetch(self, logits: StepLogits) -> StepLogits:
        """Wait for a dispatch of a serving program and bring what a step
        needs of it to the host: its ``picks``, one int32 a row.  A
        counted model's ``SERVE_COUNTERS`` of that dispatch ride in the
        same transfer (one ``device_get`` of both, no second wait) into
        the dispatch's record in ``last_step`` and the attributes of its
        ``serve/step`` span.  So does every dispatch launched before it
        that nothing has fetched (an unsampled prefill chunk; the decode
        step a prefill was launched behind): it has run by the time this
        one has.  One launched after it is left running.  The logits
        block stays where it is until a row of it is read."""
        if logits.picks is not None:    # a later dispatch's fetch brought it
            return logits
        upto = self._unfetched.index(logits) + 1
        fetched, self._unfetched = (self._unfetched[:upto],
                                    self._unfetched[upto:])
        got = jax.device_get([(d.device_picks, d.counters)
                              for d in fetched])
        for dispatch, (picks, counters) in zip(fetched, got):
            dispatch.picks = picks
            if counters is None:
                continue
            values = dict(zip(SERVE_COUNTERS, (int(v) for v in counters)))
            if self.ring_pages or self._latent_dense:
                # a window model's keys are counted on the host
                # (_dispatch); neither it nor a latent model without a
                # selection has a ``selected`` to publish
                values = {k: v for k, v in values.items()
                          if k not in dispatch.record and k != "selected"}
            dispatch.record.update(values)
            dispatch.attrs.update(values)
        return logits

    # -- prefix-cache plumbing ------------------------------------------
    def _on_prefix_evict(self, page: int):
        """Allocator reclaimed a cached page for a fresh allocation (the
        cache already dropped its index entries)."""
        self.stats["prefix_evictions"] += 1
        self._serve_event("serve/prefix_evict", page=int(page))

    def _copy_page(self, src: int, dst: int):
        """Copy-on-write: device-copy one KV page (every layer, every
        cache leaf) into the request's own fresh page.  Donating the
        cache buffers makes this an in-place page write, not a full-cache
        copy."""
        if self._copy_page_fn is None:
            def copy(caches, src, dst):
                return jax.tree_util.tree_map(
                    lambda leaf: leaf.at[:, dst].set(leaf[:, src]), caches)
            self._copy_page_fn = self._wrap_compiled(
                jax.jit(copy, donate_argnums=(0,)), "serve/copy_page")
        if self.mesh is not None:
            with self.mesh:
                self.caches = self._copy_page_fn(
                    self.caches, jnp.int32(src), jnp.int32(dst))
        else:
            self.caches = self._copy_page_fn(
                self.caches, jnp.int32(src), jnp.int32(dst))

    def _prefill_rows(self, slot: int, req: _Request, start: int,
                      shape: int, sample: bool = True,
                      chunk: Optional[int] = None,
                      seq: Optional[List[int]] = None) -> StepLogits:
        """Launch one prefill dispatch of ``shape`` rows for the prompt's
        tokens ``[start, start + shape)`` (fewer at the prompt's end, the
        rest padding) at position ``start``: a whole suffix, a piece of
        one (``_prefill``) or a chunk of the chunked policy.  Causal
        attention reads what lies before ``start`` through the block
        table, so the rows are those of a prefill of the whole prompt.
        ``sample`` and ``chunk`` as ``_prefill_next`` has them.  The slot
        then holds the prompt as far as these rows reach.  ``seq``: the
        tokens to run in the prompt's place (``_redo_state``: the prompt
        and the output so far, which the slot holds already)."""
        tokens = (req.prompt if seq is None else seq)[start:start + shape]
        with self.telemetry.span("serve/prefill/build"):
            ids = np.zeros((1, shape), np.int32)
            ids[0, :len(tokens)] = tokens
            args = (jnp.asarray(ids),
                    jnp.asarray(self.tables[slot:slot + 1]),
                    np.full((1,), start, np.int32))
        t0 = self._clock()
        self._prefill_next(len(tokens), start + len(tokens), sample=sample,
                           chunk=chunk, slot=slot)
        logits, self.caches, _ = self._run_step(*args, phase="prefill")
        # the dispatch's active wall time feeds the critical path's
        # prefill stage; the wait BETWEEN the chunked policy's chunks lands
        # in the gap stage — the split that separates scheduler wins from
        # kernel wins
        if seq is None:
            self.attrib.chunk(req.req_id, (self._clock() - t0) * 1000.0)
            req.prefilled = start + len(tokens)
        self.lengths[slot] = start + len(tokens)
        return logits

    def _redo_state(self, slot: int, req: _Request):
        """A decode row of ``slot`` ran and was dropped (the host's token
        was not the device's pick: ``scheduler._take_picks``), and the
        request goes on.  The K/V row it wrote is overwritten by the row
        that feeds the slot again at the same length; the recurrent state
        it advanced cannot be taken back.  So the slot's state is built
        again from zero, by a prefill of the prompt and the output so far
        from position 0 (the pages take the same rows again), launched
        behind the dropped row and ahead of the one that feeds the slot
        again; nothing of it is fetched.  It costs no memory and no
        operand a step; the event is rare (a row is only in flight for a
        greedy request, whose token IS the device's pick unless a sampler
        says otherwise).  Counted in ``stats`` and the step's report."""
        seq = req.prompt + req.out
        assert len(seq) == int(self.lengths[slot])
        pieces = self.scheduler.prefill_pieces(len(seq))
        with self.telemetry.span("serve/prefill", req_id=req.req_id,
                                 attrs={"bucket": sum(pieces),
                                        "real": len(seq), "cached": 0,
                                        "pieces": len(pieces), "redo": 1}):
            start = 0
            for shape in pieces:
                self._prefill_rows(slot, req, start, shape, seq=seq)
                start += shape
        # the rows are no new prompt tokens: the step's report counts none
        self._report["prompt_tokens"] -= len(seq)
        self.stats["state_redone"] += 1
        self._report["state_redone"] += 1

    def _prefill(self, slot: int, req: _Request, pieces: List[int],
                 cached: int = 0):
        """Prefill the UNCACHED suffix: the first ``cached`` prompt tokens
        already sit in attached (or COW-copied) pages, so the device runs
        only the remaining tokens from position ``cached``, as dispatches
        of the shapes ``pieces`` launched back to back, each where the one
        before it ended (one bucket, or the scheduler's pieces of a long
        prompt).  Only the last, which holds the prompt's last row and the
        padding, is fetched and sampled from; the others run the same
        program their shape always has and their one head row is never
        read.  ``cached`` is capped at ``len(prompt) - 1`` upstream: the
        last prompt token always prefills, because its logits seed
        sampling."""
        tel = self.telemetry
        with tel.span("serve/prefill", req_id=req.req_id,
                      attrs={"bucket": sum(pieces),
                             "real": len(req.prompt) - cached,
                             "cached": cached, "pieces": len(pieces)}):
            start = cached
            for shape in pieces:
                logits = self._prefill_rows(slot, req, start, shape)
                start += shape
            with tel.span("serve/prefill/fetch"):
                # [1, 1]: the program took the head on this row alone;
                # the pieces before it have run by the time it has
                row = self._fetch(logits)[0, 0]
            with tel.span("serve/prefill/sample"):
                req.last_token = self._sample(req, row)
                # the first output token exists as of the sample above —
                # a sampler fault raises before this line, so an
                # evicted-at-prefill request correctly reports no TTFT
                self._note_first_token(slot, req)

    def _note_first_token(self, slot: int, req: _Request):
        """TTFT bookkeeping shared by the monolithic prefill and the
        chunked policy's final prefill chunk."""
        tr = self.tracer.first_token(req.req_id)
        self.attrib.first_token(req.req_id)
        if tr is not None:
            self._observe_ms("serve/ttft_ms", tr.ttft_ms())
            self._serve_event("serve/request/first_token",
                              req_id=req.req_id, slot=slot,
                              ttft_ms=_round_ms(tr.ttft_ms()))

    def _sample(self, req: _Request, logits) -> int:
        """The one place a token is chosen, once a token: from its logits
        row, a :class:`LogitsRow` (still on the device unless something
        read it) or a float32 ``np.ndarray`` already here.  The token is
        emitted (``_emit``) once it exists, stamped with the time its
        pick was here: a sampler fault emits nothing.  Its dispatch
        counts it ``picked``, and among its ``host_rows`` if the row had
        been read by then."""
        t_ns = time.perf_counter_ns()
        token = self._sample_host(req, logits)
        self._emit(req.req_id, 1, t_ns)
        if isinstance(logits, LogitsRow):
            logits.block.count(1, logits.read)
        return token

    def _sample_host(self, req: _Request, logits) -> int:
        if self.injector is not None:
            self.injector.check("serve_sample")
        if req.temperature <= 0.0:
            # the program's own argmax of the same float32 row, where the
            # row brings one
            pick = getattr(logits, "pick", None)
            return greedy_token(np.asarray(logits)) if pick is None \
                else pick
        logits = np.asarray(logits)
        rng = self._rng.setdefault(req.req_id,
                                   np.random.default_rng(req.seed))
        l = logits.astype(np.float64) / req.temperature
        V = len(l)
        if req.top_k or req.top_p < 1.0:
            # rank-based filtering — EXACTLY cut tokens survive, stable
            # tie order, mirroring the device sampler's policy
            order = np.argsort(-l, kind="stable")
            ranks = np.empty(V, np.int64)
            ranks[order] = np.arange(V)
            k_eff = req.top_k if 0 < req.top_k < V else V
            l = np.where(ranks < k_eff, l, -np.inf)
            p = np.exp(l - l.max())
            p = p / p.sum()
            if req.top_p < 1.0:
                cs = np.cumsum(p[order])
                # smallest prefix whose mass reaches top_p
                cut = int(np.searchsorted(cs, req.top_p) + 1)
                p = np.where(ranks < cut, p, 0.0)
                p = p / p.sum()
        else:
            p = np.exp(l - l.max())
            p = p / p.sum()
        return int(rng.choice(V, p=p))

    def _finish(self, slot: int):
        req = self.slots[slot]
        self.finished[req.req_id] = req.prompt + req.out
        if self.prefix_cache is not None:
            # index the finished sequence's full pages (prompt AND
            # generated tokens — an agent turn's output is the next turn's
            # prompt) BEFORE the refcounts drop, so they park in the
            # reclaimable tier instead of dissolving into the free list
            added = self.prefix_cache.insert(
                req.prompt + req.out, self.alloc.seq_pages[req.req_id])
            if added:
                self._serve_event("serve/prefix_insert",
                                  req_id=req.req_id, pages=added,
                                  at="finish")
        self.scheduler.release_slot(slot, req)
        self.alloc.free_sequence(req.req_id)
        self._rng.pop(req.req_id, None)
        self.slots[slot] = None
        self.lengths[slot] = 0
        self.tables[slot, :] = 0
        self.stats["finished"] += 1
        self._serve_event("serve/finish", req_id=req.req_id,
                          n_generated=len(req.out))
        self._close_trace(req, "finish")
        self._admit()

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def _check_compile_storm(self):
        """Rising-edge serve event when the CompileWatcher flags a
        recompile storm: serving shape-bucket churn is an operator error
        (bucketing misconfigured), so it lands in the frozen serve/*
        stream next to shed/fault events, not just the compile/* stream."""
        prof = self._profiling
        if prof is None:
            return
        active = bool(prof.storm_active)
        if active and not self._storm_flagged:
            snap = prof.compile_snapshot()
            self._serve_event("serve/compile_storm",
                              misses=int(snap.get("total_misses", 0)))
        self._storm_flagged = active

    # -- the batched decode step ---------------------------------------
    def step(self) -> Dict[Any, List[int]]:
        """Advance the engine by one scheduler step — under the default
        monolithic policy, every active request by one token
        (``decode_chunk`` tokens when configured); under the chunked
        policy, up to ``max_prefill_chunks_per_step`` prefill chunks
        first, then one decode (or speculative draft+verify) dispatch for
        every fully-prefilled slot.  Returns ONLY the requests that
        finished during this step (req_id → full tokens).  Expired
        deadlines are cancelled first; an injected ``serve_step`` fault
        returns {} WITHOUT mutating any request (the retry serves
        identically; a dispatch in flight is left where it is), and
        raises only after ``serving.step_fault_limit`` consecutive
        faults.

        A one-token decode step may return with its dispatch still
        running (``scheduler._decode_once``: while every request it
        serves is greedy, the next step is launched on the device's own
        picks before this one's reach the host).  What the engine shows
        between steps follows the LAUNCHES: ``lengths``, ``tables``,
        ``req.out``, ``n_active`` and ``queue`` are those of the next
        dispatch, and a request is handed back once its last token is
        known and fed, which its final dispatch need not have ended for.
        Only the newest token of each decoding request is owed: it is
        emitted, and ``req.last_token`` set, by the step after the one
        that launched its dispatch (a request that is owed a token is
        active).  Nothing has to be settled for ``drain()``, ``health()``,
        ``leak_report()``, page export or import: whatever is launched
        later is ordered behind the dispatch in flight on the device, and
        an eviction drops the row it has there."""
        report = self._report
        report["t0_ns"] = time.perf_counter_ns()
        try:
            with self.telemetry.step_span("serve/loop", report=report,
                                          owner=self):
                return self._step()
        finally:
            report["t1_ns"] = time.perf_counter_ns()
            report["active"] = self.n_active
            report["queued"] = len(self.queue)
            self.last_step = report
            self._reports.append(report)
            self._report = self._new_report()

    def _step(self):
        # serve/admit: deadlines, admission and slot fill; a prefill that
        # slot fill runs is its own serve/prefill span beneath it
        with self.telemetry.span("serve/admit"):
            self._expire_deadlines()
            if self.injector is not None:
                try:
                    self.injector.check("serve_step")
                except Exception as e:
                    self._consec_step_faults += 1
                    self.stats["step_faults"] += 1
                    self._serve_event("serve/fault", site="serve_step",
                                      error=str(e))
                    if self._consec_step_faults > \
                            int(self.serving.step_fault_limit):
                        raise
                    return {}
                self._consec_step_faults = 0
            self._admit()
            self._check_compile_storm()
            incidents = getattr(self.telemetry, "incidents", None)
            if incidents is not None:
                # SLO burn-rate sweep on the engine's (injectable) clock —
                # a sustained multi-window miss fraction opens one incident
                incidents.observe_slo(now=self._clock())
        done = self.scheduler.run_step()
        if self._counted and self._unfetched and not self.n_active:
            # the engine empties, so no later fetch would bring along the
            # counters of a dispatch that nothing waited for (the one that
            # fed every request its last token)
            self._fetch(self._unfetched[-1])
        return done

    # -- lifecycle / introspection --------------------------------------
    def pop_terminated(self) -> Dict[Any, RequestResult]:
        """Hand back (and clear) every terminal :class:`RequestResult`
        accumulated since the last call — the shed/deadline/evicted
        counterpart of the per-step finished dict."""
        out = self.terminated
        self.terminated = {}
        return out

    def drain(self, timeout_s: Optional[float] = None,
              max_steps: Optional[int] = None) -> Dict[str, Any]:
        """Gracefully quiesce: stop admission, shed everything still
        queued, then step until in-flight work finishes or the budget
        (``max_steps``, default = the largest remaining token budget;
        ``timeout_s`` wall-clock) runs out — whatever is left is shed
        with its partial output.  Returns
        ``{"finished", "shed", "steps", "health"}``; afterwards the
        engine holds zero active slots and zero allocated pages."""
        self.draining = True
        # handed-off prefills: unpin their pages — the fleet owns those
        # requests' lifecycles and re-homes them after the drain
        for rid in list(self.handoffs):
            self.release_handoff(rid)
        self._new_handoffs = []
        shed_ids = []
        for req in list(self.queue):
            self._terminate(req, "drained", SHED_DRAIN,
                            detail="shed from queue by drain()")
            self.stats["shed"] += 1
            self._serve_event("serve/shed", req_id=req.req_id,
                              reason=SHED_DRAIN)
            shed_ids.append(req.req_id)
        self.queue = []
        if max_steps is None:
            remaining = [r.max_new_tokens - len(r.out)
                         for r in self.slots if r is not None]
            max_steps = (-(-max(remaining) // self.decode_chunk) + 4) \
                if remaining else 0
            # chunked policy: in-flight prefills consume whole steps
            # before any decode happens — budget them in
            max_steps += self.scheduler.pending_prefill_steps()
        start = self._clock()
        finished: Dict[Any, List[int]] = {}
        steps = 0
        while self.n_active and steps < max_steps:
            if timeout_s is not None and \
                    self._clock() - start >= timeout_s:
                break
            finished.update(self.step())
            steps += 1
        for slot, req in enumerate(self.slots):
            if req is not None:
                rid = req.req_id
                self._evict_slot(slot, "drained", SHED_DRAIN,
                                 detail="drain budget exhausted")
                self.stats["shed"] += 1
                self._serve_event("serve/shed", req_id=rid,
                                  reason=SHED_DRAIN)
                shed_ids.append(rid)
        # prefill_only requests that completed DURING the drain steps
        # captured fresh handoffs — unpin those too
        for rid in list(self.handoffs):
            self.release_handoff(rid)
        self._new_handoffs = []
        self.stats["drains"] += 1
        self._serve_event("serve/drain", finished=len(finished),
                          shed=len(shed_ids), steps=steps)
        return {"finished": finished, "shed": shed_ids, "steps": steps,
                "health": self.health()}

    def health(self) -> Dict[str, Any]:
        """Operational snapshot; gauges are mirrored onto the telemetry
        registry (``serving/*``) so scrapers see them without calling
        in."""
        now = self._clock()
        live = list(self.queue) + [r for r in self.slots if r is not None]
        snap = {
            "free_pages": self.alloc.free_page_count,
            # free + reclaimable: what admission actually sees
            "available_pages": self.alloc.available_page_count,
            "total_pages": self.alloc.num_pages - 1,
            "queue_depth": len(self.queue),
            "active_slots": self.n_active,
            "max_batch": self.max_batch,
            "oldest_request_age_s": float(max(
                (now - r.submit_time for r in live), default=0.0)),
            "draining": self.draining,
            "overloaded": self._admission.overloaded,
            "undelivered_terminated": len(self.terminated),
            "handoffs_pinned": len(self.handoffs),
            "counters": dict(self.stats),
            "slo": {"attained": self.stats["slo_attained"],
                    "missed": self.stats["slo_missed"],
                    "goodput_tokens": self.stats["goodput_tokens"]},
            "traces": {"open": len(self.tracer.open),
                       "admitted": self.tracer.admitted,
                       "closed": self.tracer.closed,
                       "terminals": dict(self.tracer.terminals)},
        }
        snap["scheduler"] = self.scheduler.snapshot()
        if self._stateful:
            # the recurrent state beside the pages: constant, whatever the
            # contexts (a row a slot)
            snap["state"] = {
                "slot_bytes": self.state_slot_bytes,
                "bytes": self.state_slot_bytes * self.max_batch,
                "redone": self.stats["state_redone"]}
        if self.prefix_cache is not None:
            snap["prefix_cache"] = self.prefix_cache.snapshot()
        prof = self._profiling
        if prof is not None:
            # compile health: a recompile storm means serving latency is
            # going to compile, not tokens — operators page on this flag
            snap["compile"] = prof.compile_snapshot()
            snap["recompile_storm"] = bool(prof.storm_active)
        tel = self.telemetry
        if tel is not None and tel.enabled:
            # windowed latency distributions (ms) with p50/p90/p99 — the
            # same histograms the exporter serves as summary quantiles
            snap["latency"] = {
                name: tel.registry.histogram(name).summary()
                for name in ("serve/queue_wait_ms", "serve/ttft_ms",
                             "serve/tpot_ms", "serve/e2e_ms")}
            for key in ("free_pages", "available_pages", "queue_depth",
                        "active_slots", "oldest_request_age_s"):
                tel.registry.gauge(f"serving/{key}").set(snap[key])
            if self.prefix_cache is not None:
                pc = snap["prefix_cache"]
                # frozen serve/* gauge names (docs/serving.md)
                for gauge, key in (("serve/prefix_hit_rate", "hit_rate"),
                                   ("serve/prefix_tokens_reused",
                                    "tokens_reused"),
                                   ("serve/prefix_cow_copies", "cow_copies"),
                                   ("serve/prefix_evictions", "evictions"),
                                   ("serve/prefix_cached_pages",
                                    "cached_pages")):
                    tel.registry.gauge(gauge).set(pc[key])
            if "spec_acceptance_rate" in snap["scheduler"]:
                tel.registry.gauge("serve/spec_acceptance_rate").set(
                    snap["scheduler"]["spec_acceptance_rate"])
        if tel is not None and getattr(tel, "cluster", None) is not None:
            # distributed telemetry: cross-rank skew/straggler view rides
            # along on the same health surface operators already poll
            snap["cluster"] = tel.cluster.snapshot()
        return snap

    def leak_report(self) -> Dict[str, Any]:
        """Invariant audit: every page, RNG stream, and table row must be
        owned by a live slot, refcounts must match the held multiplicity
        (pages are SHARED under the prefix cache, so naive page counting
        would double-book them), and the prefix-cache index must agree
        with the allocator's cached set.  Returns {} when clean — every
        exit path (finish, shed, deadline, evict, drain) must keep it
        that way."""
        # handed-off prefills own their pinned prompt pages by design —
        # the fleet's migration transaction is their live owner
        active = {r.req_id for r in self.slots if r is not None} | \
            set(self.handoffs)
        leaks: Dict[str, Any] = {}
        stray_pages = sorted(set(self.alloc.seq_pages) - active, key=str)
        if stray_pages:
            leaks["stray_page_owners"] = stray_pages
        stray_rng = sorted(set(self._rng) - active, key=str)
        if stray_rng:
            leaks["stray_rng"] = stray_rng
        leaks.update(self.alloc.audit())
        # one allocator addresses every pool (K and V; a latent model's
        # entries and its indexer keys, of unlike widths): each leaf must
        # have the allocator's pages on its page axis
        # (a window model's ring stack: the allocator's rings and their
        # scratch page)
        ring_leaves = [id(leaf) for leaf in jax.tree_util.tree_leaves(
            getattr(self.caches, "ring", ()))]
        pools = {i: tuple(leaf.shape) for i, leaf in enumerate(
            self._page_pools())
            if leaf.shape[1] != (self.alloc.ring_pool + 1
                                 if id(leaf) in ring_leaves
                                 else self.alloc.num_pages)}
        if pools:
            leaks["pool_page_mismatch"] = pools
        if self._stateful:
            # a recurrent state is a row a slot: nothing to allocate or
            # free, so all there is to audit is that the pools hold
            # ``max_batch`` rows (``health()`` says the bytes)
            ssm = self.caches.ssm
            if any(leaf.shape[1] != self.max_batch for leaf in ssm):
                leaks["state_slot_mismatch"] = {
                    "slot_bytes": self.state_slot_bytes,
                    **{name: tuple(leaf.shape)
                       for name, leaf in ssm._asdict().items()}}
        unseated = [s for s, req in enumerate(self.slots)
                    if req is not None and self.ring_pages and list(
                        self.tables[s, -self.ring_pages:])
                    != self.alloc.seq_rings.get(req.req_id)]
        if unseated:
            leaks["ring_not_seated"] = unseated
        if self.prefix_cache is not None:
            leaks.update(self.prefix_cache.audit())
        dirty = [s for s in range(self.max_batch)
                 if self.slots[s] is None and
                 (self.lengths[s] != 0 or self.tables[s].any())]
        if dirty:
            leaks["dirty_inactive_slots"] = dirty
        # every active slot's reservation must equal its TRUE page need
        # (prompt + budget) — _trim_reservation's invariant, the lengths
        # the ragged attention kernel and the allocator both work from.
        # It holds from the prompt's LAST chunk on (_complete_prefill
        # trims): under the chunked policy a request between chunks is
        # held to what admission reserved, its prompt padded to whole
        # chunks from where it stands (chunks are whole, so that is the
        # admission's ``cached + padded``) or prompt + budget, the larger
        over = {}
        for s, req in enumerate(self.slots):
            if req is None:
                continue
            total = len(req.prompt) + req.max_new_tokens
            left = len(req.prompt) - req.prefilled
            if left > 0:
                total = min(max(total, req.prefilled
                                + self.scheduler.prefill_padded_len(left)),
                            self.max_pages_per_seq * self.page_size)
            expected = max(1, -(-total // self.page_size))
            held = len(self.alloc.seq_pages.get(req.req_id, ()))
            if held != expected:
                over[str(req.req_id)] = {"held": held, "expected": expected}
        if over:
            leaks["over_reserved_slots"] = over
        # scheduler-held state (speculative draft allocator): pages owned
        # by requests no longer active, allocator-internal inconsistencies
        leaks.update(self.scheduler.leak_report())
        # trace completeness: every admitted request is either still live
        # (queued/active) or reached exactly one serve/request/* terminal
        # — a handoff's trace CLOSED at capture, so it is not live here
        live = {r.req_id for r in self.queue} | \
            {r.req_id for r in self.slots if r is not None}
        leaks.update(self.tracer.audit(live))
        # HBM leak detector (profiling plane): monotonic live-byte growth
        # across snapshots — device memory the page allocator can't see
        prof = self._profiling
        if prof is not None:
            leaks.update(prof.leak_report())
        if leaks:
            incidents = getattr(self.telemetry, "incidents", None)
            if incidents is not None:
                # a broken invariant is an incident: one bundle per
                # episode (the manager's per-kind cooldown dedups the
                # supervisor's repeated polls)
                incidents.trigger("leak", source="serving/leak_report",
                                  detail=",".join(sorted(leaks)))
        return leaks

    # -- convenience ----------------------------------------------------
    def generate(self, prompts, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0) -> List[List[int]]:
        """Serve a list of prompts (continuous batching when
        len(prompts) > max_batch); returns full token lists in order.
        Requests terminated mid-flight (deadline/eviction) contribute
        their partial tokens in place; a genuine stall raises
        :class:`ServingStalled` carrying every already-completed result
        instead of destroying them."""
        for i, p in enumerate(prompts):
            self.add_request(i, p, max_new_tokens, temperature,
                             top_k=top_k, top_p=top_p)
        steps = 0
        results: Dict[Any, List[int]] = {}
        limit = (max(len(p) for p in prompts) + max_new_tokens + 4) * \
            (len(prompts) + 1)
        if self.scheduler.policy == "chunked":
            # prefill chunks (and the draft's own prefill under
            # speculative decoding) consume whole steps before a slot
            # decodes — the monolithic bound already covers one step per
            # prompt token, so 3x covers target + draft chunks with slack
            limit *= 3
        while (self.queue or self.n_active) and steps < limit:
            results.update(self.step())
            steps += 1
        if self.queue or self.n_active:
            stuck = [r.req_id for r in self.queue] + \
                [r.req_id for r in self.slots if r is not None]
            raise ServingStalled(results, stuck,
                                 self.alloc.free_page_count,
                                 len(self.queue), steps)
        out = []
        for i in range(len(prompts)):
            if i in results:
                out.append(results[i])
            elif i in self.finished:   # finished inside a blocked add
                out.append(self.finished.pop(i))
            else:   # terminated mid-flight: partial tokens, in place
                out.append(self.terminated.pop(i).tokens)
        return out


def create_serving_engine(model, params, config=None, overlay_path=None,
                          **kwargs):
    """Build a :class:`ServingEngine` from a ds-style config dict.

    ``config`` is the combined config the autotuner sweeps: engine
    geometry (``max_batch`` / ``page_size`` / ``num_pages`` / ``max_seq``
    / ``decode_chunk`` / ``tp_size`` / ``ep_size``) may sit at top level
    or inside the ``serving`` block; everything else in ``serving``
    (watermarks, scheduler, fleet) passes through as the engine's
    robustness config.  When ``config["autotuning"]["overlay_path"]`` (or
    the explicit ``overlay_path``) names a persisted overlay, the tuned
    fragment is deep-merged over ``config`` first — the serving twin of
    the ``deepspeed.initialize()`` hook.  Explicit ``**kwargs`` win over
    everything (caller overrides).  The applied overlay's provenance is
    exposed as ``engine.overlay_provenance`` (None when no overlay)."""
    from deepspeed_tpu.autotuning.overlay import maybe_apply_overlay
    cfg = dict(config or {})
    cfg, provenance = maybe_apply_overlay(cfg, overlay_path)
    serving = dict(cfg.get("serving") or {})
    geometry = ("max_batch", "page_size", "num_pages", "max_seq",
                "decode_chunk", "tp_size", "ep_size", "eos_token_id")
    eng_kwargs = {}
    for key in geometry:
        if key in cfg:
            eng_kwargs[key] = cfg[key]
        if key in serving:   # the serving block wins over top level
            eng_kwargs[key] = serving.pop(key)
    eng_kwargs["serving"] = serving
    quant_cfg = (cfg.get("comm") or {}).get("quantization")
    if quant_cfg:
        eng_kwargs["comm_quant"] = quant_cfg
    eng_kwargs.update(kwargs)
    engine = ServingEngine(model, params, **eng_kwargs)
    engine.overlay_provenance = provenance
    return engine
