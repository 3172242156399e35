"""Pluggable serving schedulers: monolithic vs chunked prefill, plus
draft-model speculative decoding.

Parity role: the reference schedules inference as one monolithic
prefill-then-decode loop per batch (``InferenceEngine.forward``); modern
TPU serving (PAPERS.md: Gemma-on-TPU TTFT/throughput comparison, vLLM
chunked prefill) interleaves prefill CHUNKS with the running decode batch
so one long prompt cannot stall every in-flight request.  The ragged
paged-attention kernel (PR 6) already serves mixed prefill+decode
batches with per-request ragged lengths, so a prefill chunk — or a
speculative verify window — is just another ragged dispatch.

The split: :class:`~deepspeed_tpu.inference.serving.ServingEngine` keeps
admission, page reservation, deadlines, tracing, and the device
primitives (``_run_step`` / ``_sample`` / ``_prefill``); the scheduler
owns WHAT each step dispatches:

- ``monolithic`` (default): the whole prompt prefills at admission, in
  one bucketed dispatch or, a long one, in a few pieces launched back to
  back; decode advances every slot per step.
- ``chunked``: prefill runs ``prefill_chunk_tokens`` at a time,
  interleaved with decode; per-request SLO classes (``latency`` vs
  ``throughput``) order both queue admission and chunk scheduling, and
  deadlines are checked at every chunk boundary (not just whole steps).
- ``chunked`` + ``speculative``: a draft model proposes
  ``num_draft_tokens`` greedy tokens per slot through its OWN paged
  allocator; the target verifies the whole window in one ragged
  dispatch.  Greedy accept keeps the output bit-identical to the
  non-speculative oracle: every accepted token equals the target's
  argmax given the true prefix, and the first mismatch is replaced by
  that argmax (the "bonus" token).  Rejected draft positions need no
  rollback — stale KV entries beyond ``lengths`` are never read and are
  overwritten by the next sequential write.
"""

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel
from deepspeed_tpu.utils.logging import logger

SCHEDULER_POLICIES = ("monolithic", "chunked")

# SLO classes order admission and chunk scheduling under the chunked
# policy: "latency" requests jump the queue and prefill first.  The
# class rides the frozen serve/request/* events (slo_class attr) so the
# report can split TTFT/TPOT percentiles per class.
SLO_CLASSES = ("latency", "throughput")
_SLO_PRIORITY = {c: i for i, c in enumerate(SLO_CLASSES)}


class SpeculativeConfig(DeepSpeedConfigModel):
    """``serving.scheduler.speculative``: draft-model speculative
    decoding on top of the chunked policy."""

    enabled = False
    # draft tokens proposed (and verified) per decode step; the verify
    # window writes up to num_draft_tokens past the reservation tail, so
    # it must fit the +1 scratch overrun column: num_draft_tokens + 1
    # <= page_size (checked at scheduler construction, where the engine
    # page size is known)
    num_draft_tokens = 4

    def _validate(self):
        n = int(self.num_draft_tokens)
        if n < 0:
            raise ValueError(
                "serving.scheduler.speculative.num_draft_tokens must be "
                ">= 0")
        if n == 0:
            # 0 is the "speculation off" point — the autotuner's
            # draft-length knob sweeps it alongside real draft lengths
            self.enabled = False


class SchedulerConfig(DeepSpeedConfigModel):
    """The ``serving.scheduler`` config block."""

    policy = "monolithic"
    # chunked policy: tokens per prefill chunk (one ragged dispatch each)
    prefill_chunk_tokens = 256
    # prefill chunk dispatches interleaved per engine step, before decode
    max_prefill_chunks_per_step = 1
    # class applied when add_request passes no slo_class
    slo_class_default = "throughput"
    # per-class deadline defaults: {"latency": {"default_deadline_s": 2.0}}
    # — applied when add_request passes no deadline_s, before falling back
    # to serving.default_deadline_s
    slo_classes = {}
    speculative = {}

    def _validate(self):
        if isinstance(self.speculative, dict):
            self.speculative = SpeculativeConfig(self.speculative)
        if self.policy not in SCHEDULER_POLICIES:
            raise ValueError(
                f"serving.scheduler.policy must be one of "
                f"{SCHEDULER_POLICIES}")
        if int(self.prefill_chunk_tokens) < 1:
            raise ValueError(
                "serving.scheduler.prefill_chunk_tokens must be >= 1")
        if int(self.max_prefill_chunks_per_step) < 1:
            raise ValueError(
                "serving.scheduler.max_prefill_chunks_per_step must be "
                ">= 1")
        if self.slo_class_default not in SLO_CLASSES:
            raise ValueError(
                f"serving.scheduler.slo_class_default must be one of "
                f"{SLO_CLASSES}")
        for cls in self.slo_classes:
            if cls not in SLO_CLASSES:
                raise ValueError(
                    f"serving.scheduler.slo_classes key {cls!r} is not "
                    f"one of {SLO_CLASSES}")

    def class_deadline_s(self, slo_class: str) -> Optional[float]:
        """Per-class default TTL, or None when the class has none."""
        spec = self.slo_classes.get(slo_class)
        if not isinstance(spec, dict):
            return None
        ttl = spec.get("default_deadline_s")
        return float(ttl) if ttl else None


class _InFlight:
    """A decode dispatch whose picks the host has not taken: ``logits``
    its :class:`~deepspeed_tpu.inference.serving.StepLogits`, ``owed``
    the request of each slot that still gets a token from it (slot order),
    ``overlaps`` whether the dispatch before it was still to be fetched
    when this one was launched (its picks then count as ``ahead``)."""

    __slots__ = ("logits", "owed", "overlaps")

    def __init__(self, logits, owed, overlaps):
        self.logits, self.owed, self.overlaps = logits, owed, overlaps

    def drop(self, slot, req):
        """The row of ``slot`` was launched for nothing, if ``req`` is
        still owed a token from it: its pick is thrown away."""
        if self.owed.get(slot) is req:
            del self.owed[slot]
            self.logits.bump("redone")


class SchedulerBase:
    """Decode machinery shared by every policy.

    The decode dispatches mask NON-READY slots (empty, or still
    prefilling under the chunked policy) by feeding them a zeroed block
    table row and length 0: their writes land on the reserved scratch
    page and the host loop skips their outputs.  Under the monolithic
    policy every active slot is ready, so the masked arrays equal the
    engine's own tables/lengths.  The one-token step (``_decode_once``,
    both policies) runs one dispatch ahead of the host while every
    request it serves is greedy; the speculative window and
    ``decode_chunk > 1`` wait for their ids.
    """

    policy = "base"

    def __init__(self, engine, cfg: SchedulerConfig):
        self.engine = engine
        self.cfg = cfg
        self._chunk_fns = {}   # use_filters(bool) -> compiled chunk fn
        # one-token decode dispatches whose picks the host has not taken,
        # oldest first: at most one between steps (_decode_once)
        self._ahead: List[_InFlight] = []
        self.sched_stats = {"prefill_chunks": 0, "prefill_pieces": 0,
                            "prefills_split": 0,
                            "decode_steps": 0, "decode_tokens": 0}

    # -- admission hooks (called by ServingEngine._admit) ----------------
    def order_queue(self):
        """Reorder the waiting queue before slot filling (policy hook)."""

    def prefill_padded_len(self, suffix_tokens: int) -> int:
        """Padded device length the prefill of ``suffix_tokens`` will
        write — the engine sizes the page reservation from it."""
        raise NotImplementedError

    def prefill_pieces(self, suffix_tokens: int) -> List[int]:
        """Shapes of the dispatches that prefill ``suffix_tokens`` in one
        go, in launch order: one power-of-two bucket (the monolithic
        policy may send a long one as pieces)."""
        eng = self.engine
        return [min(eng._bucket(suffix_tokens), eng.max_seq)]

    def fill_slot(self, slot: int, req, cached: int) -> bool:
        """A queued request just landed in ``slot`` (pages reserved,
        COW done).  Returns True when the prefill ran to completion
        here (the engine then trims the reservation and indexes the
        prefix); False when it was deferred to later ``step()`` calls."""
        raise NotImplementedError

    def release_slot(self, slot: int, req):
        """The request in ``slot`` is leaving the engine (finish, evict,
        deadline, drain) — drop any scheduler-held state for it: a row
        in flight for it is thrown away when it arrives, and a dispatch
        that owes nobody a token is in flight no more."""
        for dispatch in self._ahead:
            dispatch.drop(slot, req)
        self._ahead = [d for d in self._ahead if d.owed]

    # -- step hooks ------------------------------------------------------
    def run_step(self) -> Dict[Any, List[int]]:
        raise NotImplementedError

    def pending_prefill_steps(self) -> int:
        """Upper bound on extra step() calls needed to finish every
        in-flight prefill (drain budget sizing)."""
        return 0

    def meta(self) -> Dict[str, Any]:
        """Attrs for the one frozen ``serve/sched`` event per engine."""
        return {"policy": self.policy,
                "prefill_chunk_tokens": int(self.cfg.prefill_chunk_tokens),
                "speculative": 0}

    def snapshot(self) -> Dict[str, Any]:
        return {"policy": self.policy, **self.sched_stats}

    def leak_report(self) -> Dict[str, Any]:
        return {}

    # -- shared decode bodies -------------------------------------------
    def _ready_slots(self) -> List[int]:
        eng = self.engine
        return [s for s, r in enumerate(eng.slots)
                if r is not None and r.last_token is not None
                and self._slot_ready(s, r)]

    def _slot_ready(self, slot: int, req) -> bool:
        return True

    def _decode_once(self, ready: List[int]) -> Dict[Any, List[int]]:
        """One token for every ready slot, the host one decode step
        behind the device where it can be.

        A step LAUNCHES the next dispatch, then takes the picks of the one
        before it: a slot whose token that older dispatch still owes is
        fed it on the device (``serve_decode``'s ``fed``), so the build,
        the launch, the fetch's round trip and the sampling below all run
        while the device computes.  The dispatch just launched stays in
        flight (``_ahead``) when every request it owes a token is greedy:
        the program's pick is then the token.  Otherwise (a sampled
        request in the batch) its picks are taken here as well, and that
        is the whole of a step with nothing in flight: launch, nothing
        older to take, fetch, sample.

        The host's books follow the LAUNCHES: a token joins ``req.out``
        and the slot's length when it is both known and fed (``_fed``),
        so ``eng.lengths``, the tables and the budget are those of the
        next dispatch, and a request ends, and is handed back, as soon as
        its last token is known and launched (EOS is tested on the token
        fed, so neither it nor the budget costs a row).  What can waste
        a row is a pick the host does not take: a sampler that returns
        another token, a sampler fault, a deadline or an eviction while
        the row is in flight.  The row is dropped (``redone``), the length
        stays, and the slot is fed from the host again at the same
        position, which overwrites what the dropped row wrote.  (A K/V
        row written twice is the same row; a recurrent state advanced
        twice is wrong, so a model with state-space layers builds the
        slot's state again first: ``ServingEngine._redo_state``.)"""
        eng = self.engine
        tel = eng.telemetry
        done_slots: List[int] = []
        with tel.span("serve/decode",
                      attrs={"batch": eng.max_batch, "ready": len(ready),
                             "tokens": 1}):
            if ready:
                self._launch(ready, done_slots)
            while self._ahead:
                dispatch = self._ahead[0]
                if not dispatch.owed:       # every request it fed ended
                    self._ahead.remove(dispatch)
                    continue
                if len(self._ahead) == 1 and all(
                        req.temperature <= 0.0
                        for req in dispatch.owed.values()):
                    break       # its picks are its tokens: run ahead
                with tel.span("serve/decode/fetch"):
                    # the wait for the device and one int32 a slot
                    eng._fetch(dispatch.logits)
                with tel.span("serve/decode/sample"):
                    self._take_picks(dispatch, done_slots)
            with tel.span("serve/decode/sample"):
                return self._finish_slots(done_slots)

    def _launch(self, ready, done_slots):
        """Launch one decode dispatch for the ``ready`` slots behind what
        is in flight, and book the rows fed from the host."""
        eng = self.engine
        older = self._ahead[-1] if self._ahead else None
        with eng.telemetry.span("serve/decode/build"):
            last = np.zeros((eng.max_batch, 1), np.int32)
            tables = np.zeros_like(eng.tables)
            lengths = np.zeros_like(eng.lengths)
            for slot in ready:
                req = eng.slots[slot]
                # the token the older dispatch owes is fed where it lies
                owed = older is not None and older.owed.get(slot) is req
                last[slot, 0] = -1 if owed else req.last_token
                tables[slot] = eng.tables[slot]
                lengths[slot] = eng.lengths[slot]
            args = (jnp.asarray(last), jnp.asarray(tables), lengths)
        logits, eng.caches, _ = eng._run_step(*args)
        self._decode_sizes(lengths, ready)
        self.sched_stats["decode_steps"] += 1
        newer = _InFlight(
            logits, {slot: eng.slots[slot] for slot in ready},
            overlaps=older is not None and older.logits.picks is None)
        self._ahead.append(newer)
        for slot in ready:
            if last[slot, 0] >= 0:
                self._fed(slot, newer, done_slots)

    def _fed(self, slot, dispatch, done_slots):
        """``req.last_token`` is known and ``dispatch`` feeds it at the
        slot's length: it is part of the sequence.  A request that ends
        with it is owed no further token."""
        eng = self.engine
        req = eng.slots[slot]
        req.out.append(req.last_token)
        eng.lengths[slot] += 1
        self.sched_stats["decode_tokens"] += 1
        if (eng.eos is not None and req.last_token == eng.eos) or \
                len(req.out) >= req.max_new_tokens:
            del dispatch.owed[slot]
            done_slots.append(slot)

    def _decode_sizes(self, lengths, ready):
        """Into the report's newest dispatch: the context each ready slot
        attends over, the new token included."""
        self.engine._report["dispatches"][-1]["contexts"] = \
            [int(lengths[s]) + 1 for s in ready]

    def _take_picks(self, dispatch, done_slots):
        """The host half of a one-token decode step, for the oldest
        dispatch in flight, fetched: sample the next token of every slot
        it owes one (from its row of the ``StepLogits``) and evict the
        slots whose sampler failed.  Where the dispatch after it was
        launched on the pick, the token is booked there if it is the
        pick, and that row dropped if it is not."""
        from deepspeed_tpu.inference.robustness import EVICT_FAULT
        eng = self.engine
        self._ahead.remove(dispatch)
        following = self._ahead[0] if self._ahead else None
        logits = dispatch.logits
        fault_slots = []
        for slot, req in dispatch.owed.items():
            if eng.slots[slot] is not req:
                continue
            try:
                req.last_token = eng._sample(req, logits[slot, 0])
            except Exception as e:   # per-slot fault isolation
                fault_slots.append((slot, str(e)))
                continue
            if following is None or following.owed.get(slot) is not req:
                continue        # fed from the host by the next launch
            if req.last_token == int(logits.picks[slot, 0]):
                logits.bump("ahead", int(following.overlaps))
                self._fed(slot, following, done_slots)
            else:
                following.drop(slot, req)
                if eng._stateful:
                    # the dropped row advanced the slot's recurrent state
                    eng._redo_state(slot, req)
        for slot, err in fault_slots:
            rid = eng.slots[slot].req_id
            logger.warning(f"evicting request {rid!r} after sampler "
                           f"fault: {err}")
            eng._evict_slot(slot, "evicted", EVICT_FAULT, detail=err)
            eng.stats["evicted"] += 1
            eng._serve_event("serve/evict", req_id=rid,
                             reason=EVICT_FAULT, error=err)
        if fault_slots:
            eng._admit()

    def _finish_slots(self, done_slots):
        """Finish the requests that ended in this step (which may admit,
        and prefill, queued requests: after the step's own sampling, so a
        mid-step admission is never mistaken for a slot it served)."""
        eng = self.engine
        done_now: Dict[Any, List[int]] = {}
        for slot in done_slots:
            rid = eng.slots[slot].req_id
            eng._finish(slot)
            # hand the result back ONCE: a long-running server must not
            # accumulate every finished token list forever
            done_now[rid] = eng.finished.pop(rid)
        return done_now

    # -- the chunked decode step (K tokens per dispatch) ----------------
    def _build_chunk_fn(self, use_filters: bool):
        eng = self.engine
        K = eng.decode_chunk
        paged_call = eng._paged_call   # backend-bound apply_with_paged_cache

        def chunk(params, caches, tables, lengths, last, temps, seeds,
                  gen_counts, top_ks, top_ps):
            """K decode iterations in one device program.  Emits the K
            sampled tokens per slot; the host truncates past EOS /
            max_new_tokens (overrun writes land on the reserved scratch
            page — admission reserved every page a live request can
            validly reach, vLLM-style multi-step scheduling).  Sampling
            keys on (request seed, tokens generated so far), so a
            request's random stream is independent of slot assignment
            and arrival order — the per-token engine's req.seed contract."""
            def one_sample(key, l, temp, top_k, top_p):
                """One slot's filtered sampler: temperature -> top-k ->
                top-p (nucleus) -> categorical.  Rank-based like the host
                sampler: a single stable descending argsort; exactly
                ``cut`` ranked tokens survive each stage (top_k=0 /
                top_p=1.0 gate their stage off explicitly)."""
                V = l.shape[-1]
                l = l / jnp.maximum(temp, 1e-6)
                order = jnp.argsort(-l, stable=True)
                ranks = jnp.zeros(V, jnp.int32).at[order].set(
                    jnp.arange(V, dtype=jnp.int32))
                k_eff = jnp.where((top_k > 0) & (top_k < V), top_k, V)
                l = jnp.where(ranks < k_eff, l, -1e30)
                p = jax.nn.softmax(l)
                cs = jnp.cumsum(p[order])
                # smallest prefix reaching top_p mass (searchsorted+1)
                cut = jnp.where(top_p < 1.0, jnp.sum(cs < top_p) + 1, V)
                l = jnp.where(ranks < cut, l, -1e30)
                return jax.random.categorical(key, l).astype(jnp.int32)

            def one(carry, t):
                caches, lengths, last = carry
                logits, caches, _ = paged_call(
                    params, last[:, None], caches, tables, lengths)
                lg = logits[:, 0]
                greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                keys = jax.vmap(
                    lambda s, g: jax.random.fold_in(jax.random.key(s),
                                                    g + t))(seeds, gen_counts)
                if use_filters:
                    sampled = jax.vmap(one_sample)(keys, lg, temps,
                                                   top_ks, top_ps)
                else:   # plain temperature: no vocab sorts in the loop
                    sampled = jax.vmap(
                        lambda k, l, tt: jax.random.categorical(
                            k, l / jnp.maximum(tt, 1e-6)))(
                        keys, lg, temps).astype(jnp.int32)
                nxt = jnp.where(temps > 0, sampled, greedy)
                return (caches, lengths + 1, nxt), nxt

            (caches, lengths, last), toks = jax.lax.scan(
                one, (caches, lengths, last), jnp.arange(K))
            return toks.T, caches   # [B, K]

        return jax.jit(chunk, donate_argnums=(1,))

    def _decode_scan(self, ready: List[int]) -> Dict[Any, List[int]]:
        eng = self.engine
        K = eng.decode_chunk
        use_filters = any(eng.slots[s].top_k or eng.slots[s].top_p < 1.0
                          for s in ready)
        if self._chunk_fns.get(use_filters) is None:
            self._chunk_fns[use_filters] = eng._wrap_compiled(
                self._build_chunk_fn(use_filters),
                f"serve/decode_chunk:{int(use_filters)}")
        chunk_fn = self._chunk_fns[use_filters]
        tel = eng.telemetry
        with tel.span("serve/decode",
                      attrs={"batch": eng.max_batch, "ready": len(ready),
                             "tokens": K}):
            with tel.span("serve/decode/build"):
                last = np.zeros(eng.max_batch, np.int32)
                temps = np.zeros(eng.max_batch, np.float32)
                seeds = np.zeros(eng.max_batch, np.uint32)
                gen_counts = np.zeros(eng.max_batch, np.int32)
                top_ks = np.zeros(eng.max_batch, np.int32)
                top_ps = np.ones(eng.max_batch, np.float32)
                tables = np.zeros_like(eng.tables)
                lengths = np.zeros_like(eng.lengths)
                for slot in ready:
                    req = eng.slots[slot]
                    last[slot] = req.last_token
                    temps[slot] = max(0.0, req.temperature)
                    seeds[slot] = np.uint32(req.seed)
                    gen_counts[slot] = len(req.out)
                    top_ks[slot] = req.top_k
                    top_ps[slot] = req.top_p
                    tables[slot] = eng.tables[slot]
                    lengths[slot] = eng.lengths[slot]
                args = (eng.params, eng.caches, jnp.asarray(tables),
                        jnp.asarray(lengths), jnp.asarray(last),
                        jnp.asarray(temps), jnp.asarray(seeds),
                        jnp.asarray(gen_counts), jnp.asarray(top_ks),
                        jnp.asarray(top_ps))
            toks, eng.caches = eng._dispatch(chunk_fn, args, "decode_chunk",
                                             eng.max_batch, K,
                                             starts=lengths)
            self._decode_sizes(lengths, ready)
            with tel.span("serve/decode/fetch"):
                toks = np.asarray(toks)
            self.sched_stats["decode_steps"] += 1
            with tel.span("serve/decode/sample"):
                return self._commit_chunk(ready, toks)

    def _commit_chunk(self, ready, toks):
        """The host half of a chunked decode step: append each ready
        slot's tokens up to EOS or its budget, finish the done ones."""
        eng = self.engine
        done_slots, done_now = [], {}
        for slot in ready:
            req = eng.slots[slot]
            # tokens appended to the cache this chunk: the pre-chunk last
            # token, then the first K-1 samples; sample K-1 is the next
            # chunk's carry (per-token step() semantics, K times)
            seq = [req.last_token] + toks[slot, :-1].tolist()
            finished = False
            before = len(req.out)
            for tok in seq:
                req.out.append(int(tok))
                eng.lengths[slot] += 1
                self.sched_stats["decode_tokens"] += 1
                if (eng.eos is not None and int(tok) == eng.eos) or \
                        len(req.out) >= req.max_new_tokens:
                    finished = True
                    break
            # sampled on the device, on the host as of the fetch: the
            # first appended token was emitted a chunk ago, the carry
            # (when the request goes on) is new
            new = len(req.out) - before - 1 + (not finished)
            if new:
                eng._emit(req.req_id, new)
            if finished:
                done_slots.append(slot)
            else:
                req.last_token = int(toks[slot, -1])
        for slot in done_slots:
            rid = eng.slots[slot].req_id
            eng._finish(slot)
            done_now[rid] = eng.finished.pop(rid)
        return done_now


class MonolithicScheduler(SchedulerBase):
    """The whole (uncached) prompt prefills at slot-fill time, fetched and
    sampled there; every active slot decodes every step, by
    ``_decode_once`` or the ``decode_chunk`` scan.

    A prompt is one dispatch of a power-of-two bucket.  Where the engine
    builds a prefill onto a context already in the pool
    (``engine.prefill_piece_rows``), a suffix longer than that many rows
    pads to the next multiple of them instead and is sent as that
    length's binary expansion, largest piece first (3,584 = 2,048 + 1,024
    + 512): every piece a power of two, so a bucket's compiled program,
    and no new shape for the rows that the next power of two would have
    padded."""

    policy = "monolithic"

    def prefill_pieces(self, suffix_tokens: int) -> List[int]:
        """Shapes of the dispatches that prefill ``suffix_tokens``, in
        launch order.  A ``max_seq`` that is no multiple of a piece's rows
        is a shape of its own, as it is a bucket's cap."""
        eng = self.engine
        rows = eng.prefill_piece_rows
        if rows and suffix_tokens > rows:
            padded = -(-suffix_tokens // rows) * rows
            if padded <= eng.max_seq:
                return [1 << bit
                        for bit in reversed(range(padded.bit_length()))
                        if padded >> bit & 1]
        return super().prefill_pieces(suffix_tokens)

    def prefill_padded_len(self, suffix_tokens: int) -> int:
        return sum(self.prefill_pieces(suffix_tokens))

    def fill_slot(self, slot: int, req, cached: int) -> bool:
        pieces = self.prefill_pieces(len(req.prompt) - cached)
        self.sched_stats["prefill_pieces"] += len(pieces)
        self.sched_stats["prefills_split"] += len(pieces) > 1
        self.engine._prefill(slot, req, pieces, cached)
        return True

    def meta(self) -> Dict[str, Any]:
        return {**super().meta(),
                "prefill_piece_rows": self.engine.prefill_piece_rows}

    def run_step(self) -> Dict[Any, List[int]]:
        eng = self.engine
        if eng.n_active == 0:
            return {}
        ready = self._ready_slots()
        if eng.decode_chunk > 1:
            return self._decode_scan(ready)
        return self._decode_once(ready)


class ChunkedScheduler(SchedulerBase):
    """Chunked prefill interleaved with decode, SLO-class ordering, and
    (optionally) draft-model speculative decoding.

    Per engine step: up to ``max_prefill_chunks_per_step`` prefill-chunk
    dispatches run first — ordered (SLO class, submit time) — with a
    deadline sweep after EVERY chunk boundary; then one decode dispatch
    advances the slots whose prefill (target AND draft) is complete.
    """

    policy = "chunked"

    def __init__(self, engine, cfg: SchedulerConfig,
                 draft_model=None, draft_params=None):
        super().__init__(engine, cfg)
        self.chunk = int(cfg.prefill_chunk_tokens)
        self.max_chunks = int(cfg.max_prefill_chunks_per_step)
        self.spec = bool(cfg.speculative.enabled)
        self.sched_stats.update(prefill_chunk_tokens=self.chunk)
        if self.spec:
            self._init_spec(draft_model, draft_params)

    # -- speculative state ----------------------------------------------
    def _init_spec(self, draft_model, draft_params):
        from deepspeed_tpu.ops.paged_attention import PagedAllocator
        eng = self.engine
        if draft_model is None or draft_params is None:
            raise ValueError(
                "serving.scheduler.speculative.enabled needs "
                "ServingEngine(draft_model=..., draft_params=...)")
        if eng.decode_chunk != 1:
            raise ValueError(
                "speculative decoding replaces decode_chunk batching; "
                "use decode_chunk=1")
        if eng.mesh is not None:
            raise ValueError(
                "speculative decoding is single-host only (tp/ep mesh "
                "unsupported)")
        self.gamma = int(self.cfg.speculative.num_draft_tokens)
        if self.gamma + 1 > eng.page_size:
            # the verify window (and the draft's sync write of the same
            # tokens) overruns the reservation tail by up to gamma
            # positions — the +1 scratch column absorbs exactly one page
            raise ValueError(
                f"num_draft_tokens + 1 ({self.gamma + 1}) must fit one "
                f"page (page_size {eng.page_size})")
        self.draft_model = draft_model
        self.draft_params = draft_params
        # the draft runs through its OWN paged allocator/caches/tables —
        # sized so a full batch of max-length reservations can never
        # fail, because there is no draft-side prefix sharing to lean on
        draft_pages = eng.max_batch * eng.max_pages_per_seq + 1
        self.draft_alloc = PagedAllocator(draft_pages, eng.page_size,
                                          eng.max_pages_per_seq,
                                          reserve_scratch=True)
        self.draft_caches = draft_model.init_paged_caches(
            draft_pages, eng.page_size, dtype=eng.cache_dtype)
        self.draft_tables = np.zeros_like(eng.tables)
        self.draft_lengths = np.zeros(eng.max_batch, np.int32)
        self._spec_slots = set()
        import functools
        self._draft_call = functools.partial(
            draft_model.apply_with_paged_cache)

        def draft_prefill(params, ids, caches, tables, lengths):
            # the draft's prompt chunks fill its cache and are never
            # sampled from: no head
            return self._draft_call(
                params, ids, caches, tables, lengths,
                head_rows=jnp.zeros((ids.shape[0], 0), jnp.int32))

        self._draft_step_fn = eng._wrap_compiled(
            jax.jit(draft_prefill, donate_argnums=(2,)),
            "serve/spec_draft_fn")
        self._propose_fn = eng._wrap_compiled(
            self._build_propose_fn(), "serve/spec_propose")
        self.sched_stats.update(spec_windows=0, spec_proposed=0,
                                spec_accepted=0, spec_rejected=0)

    def _build_propose_fn(self):
        """Greedy draft proposal: a scan of ``gamma + 1`` single-token
        decode iterations.  The extra iteration writes the LAST proposed
        token into the draft cache, so an accept-all verify leaves no
        hole — the draft cache stays valid through every position the
        target may commit, and rejection needs no rollback at all."""
        G = self.gamma
        draft_call = self._draft_call

        def propose(params, caches, tables, lengths, last):
            def one(carry, _):
                caches, lengths, last = carry
                logits, caches, _ = draft_call(
                    params, last[:, None], caches, tables, lengths)
                nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                return (caches, lengths + 1, nxt), nxt

            (caches, _, _), toks = jax.lax.scan(
                one, (caches, lengths, last), None, length=G + 1)
            return toks.T, caches   # [B, G+1]; only the first G are used

        return jax.jit(propose, donate_argnums=(1,))

    def _run_draft(self, ids, tables, lengths, phase):
        _, self.draft_caches, _ = self.engine._dispatch(
            self._draft_step_fn,
            (self.draft_params, ids, self.draft_caches, tables, lengths),
            phase, *ids.shape, starts=np.asarray(lengths), backend="draft",
            config=self.draft_model.config, head_rows=0)

    # -- admission hooks -------------------------------------------------
    def order_queue(self):
        # stable: latency-class requests first, FIFO within a class
        self.engine.queue.sort(
            key=lambda r: _SLO_PRIORITY.get(r.slo_class, 1))

    def prefill_padded_len(self, suffix_tokens: int) -> int:
        return -(-max(suffix_tokens, 1) // self.chunk) * self.chunk

    def fill_slot(self, slot: int, req, cached: int) -> bool:
        eng = self.engine
        req.prefilled = cached
        req.draft_filled = 0
        eng.lengths[slot] = cached
        if len(req.prompt) - cached > self.chunk:
            self.sched_stats["prefills_split"] += 1
        if self.spec and req.temperature <= 0.0:
            # full draft reservation up front, like the target's: an
            # admitted spec request can never deadlock on draft pages
            total = len(req.prompt) + req.max_new_tokens
            padded = self.prefill_padded_len(len(req.prompt))
            need = min(max(total, padded),
                       eng.max_pages_per_seq * eng.page_size)
            pages = self.draft_alloc.allocate(req.req_id, need)
            self.draft_tables[slot, :] = 0
            self.draft_tables[slot, :len(pages)] = pages
            self.draft_lengths[slot] = 0
            self._spec_slots.add(slot)
        return False

    def release_slot(self, slot: int, req):
        super().release_slot(slot, req)
        if self.spec and slot in self._spec_slots:
            self._spec_slots.discard(slot)
            self.draft_alloc.free_sequence(req.req_id)
            self.draft_tables[slot, :] = 0
            self.draft_lengths[slot] = 0

    # -- prefill chunk scheduling ----------------------------------------
    def _prefill_pending(self, slot: int, req) -> bool:
        if req.prefilled < len(req.prompt):
            return True
        return self.spec and slot in self._spec_slots and \
            req.draft_filled < len(req.prompt)

    def _next_prefill_slot(self) -> Optional[int]:
        eng = self.engine
        best, best_key = None, None
        for slot, req in enumerate(eng.slots):
            if req is None or not self._prefill_pending(slot, req):
                continue
            key = (_SLO_PRIORITY.get(req.slo_class, 1), req.submit_time,
                   slot)
            if best_key is None or key < best_key:
                best, best_key = slot, key
        return best

    def _prefill_chunk_unit(self, slot: int, req):
        """One prefill-chunk dispatch for ``slot``: the target prompt
        first, then (spec slots) the draft's own full-prompt prefill.
        The final target chunk samples the first token and completes the
        admission sequence (trim + prefix insert)."""
        eng = self.engine
        tel = eng.telemetry
        P = len(req.prompt)
        if req.prefilled < P:
            start = req.prefilled
            n = min(self.chunk, P - start)
            chunk = start // self.chunk     # its index in the prompt
            with tel.span("serve/prefill", req_id=req.req_id,
                          attrs={"bucket": self.chunk, "real": n,
                                 "cached": start, "chunk": chunk,
                                 "context": start + n}):
                # only the prompt's last chunk is sampled from: the ones
                # before it take no head at all (a second program a chunk
                # shape, for the table's bytes a chunk: docs/serving.md)
                logits = eng._prefill_rows(slot, req, start, self.chunk,
                                           sample=start + n >= P,
                                           chunk=chunk)
                self.sched_stats["prefill_chunks"] += 1
                eng._serve_event("serve/prefill_chunk", req_id=req.req_id,
                                 slot=slot, start=start, tokens=n,
                                 remaining=P - req.prefilled,
                                 slo_class=req.slo_class)
                if req.prefilled >= P:
                    # the last prompt token's logits seed sampling — same
                    # contract as the monolithic prefill
                    with tel.span("serve/prefill/fetch"):
                        row = eng._fetch(logits)[0, 0]
                    with tel.span("serve/prefill/sample"):
                        req.last_token = eng._sample(req, row)
                        eng._note_first_token(slot, req)
                        eng._complete_prefill(slot, req)
            return
        # target done -> catch the draft up on its own cache
        start = req.draft_filled
        toks = req.prompt[start:start + self.chunk]
        n = len(toks)
        ids = np.zeros((1, self.chunk), np.int32)
        ids[0, :n] = toks
        self._run_draft(jnp.asarray(ids),
                        jnp.asarray(self.draft_tables[slot:slot + 1]),
                        np.full((1,), start, np.int32),
                        phase="spec_prefill")
        req.draft_filled = start + n
        self.draft_lengths[slot] = req.draft_filled
        if req.draft_filled >= P:
            # drop the draft's padding surplus, mirroring the target trim
            total = P + req.max_new_tokens
            self.draft_alloc.shrink(req.req_id, total)
            pages = self.draft_alloc.seq_pages[req.req_id]
            self.draft_tables[slot, :] = 0
            self.draft_tables[slot, :len(pages)] = pages

    def _run_prefill_chunks(self):
        from deepspeed_tpu.inference.robustness import EVICT_FAULT
        eng = self.engine
        for _ in range(self.max_chunks):
            slot = self._next_prefill_slot()
            if slot is None:
                return
            req = eng.slots[slot]
            try:
                self._prefill_chunk_unit(slot, req)
            except Exception as e:   # fault isolation: only THIS request
                logger.warning(f"evicting request {req.req_id!r} after "
                               f"prefill-chunk fault: {e}")
                eng._evict_slot(slot, "evicted", EVICT_FAULT,
                                detail=str(e))
                eng.stats["evicted"] += 1
                eng._serve_event("serve/evict", req_id=req.req_id,
                                 reason=EVICT_FAULT, error=str(e))
                continue
            # deadline/TTL granularity fix: a multi-chunk prefill is no
            # longer one opaque dispatch — every chunk boundary cancels
            # expired requests, queued or mid-flight (including the one
            # that was just prefilling)
            eng._expire_deadlines()

    # -- decode ----------------------------------------------------------
    def _slot_ready(self, slot: int, req) -> bool:
        if req.prefilled < len(req.prompt):
            return False
        if self.spec and slot in self._spec_slots:
            return req.draft_filled >= len(req.prompt)
        return True

    def run_step(self) -> Dict[Any, List[int]]:
        eng = self.engine
        self._run_prefill_chunks()
        ready = self._ready_slots()
        if not ready:
            return {}
        if self.spec:
            return self._spec_decode(ready)
        if eng.decode_chunk > 1:
            return self._decode_scan(ready)
        return self._decode_once(ready)

    def pending_prefill_steps(self) -> int:
        eng = self.engine
        pending = 0
        for slot, req in enumerate(eng.slots):
            if req is None:
                continue
            if req.prefilled < len(req.prompt):
                pending += -(-(len(req.prompt) - req.prefilled)
                             // self.chunk)
            if self.spec and slot in self._spec_slots:
                pending += -(-(len(req.prompt) - req.draft_filled)
                             // self.chunk)
        return pending

    def meta(self) -> Dict[str, Any]:
        m = super().meta()
        m["speculative"] = int(self.spec)
        if self.spec:
            m["num_draft_tokens"] = self.gamma
        return m

    def snapshot(self) -> Dict[str, Any]:
        snap = super().snapshot()
        snap["prefilling_slots"] = sum(
            1 for s, r in enumerate(self.engine.slots)
            if r is not None and self._prefill_pending(s, r))
        if self.spec:
            prop = snap.get("spec_proposed", 0)
            snap["spec_acceptance_rate"] = (
                snap.get("spec_accepted", 0) / prop if prop else 0.0)
        return snap

    def leak_report(self) -> Dict[str, Any]:
        if not self.spec:
            return {}
        eng = self.engine
        leaks: Dict[str, Any] = {}
        active = {r.req_id for r in eng.slots if r is not None}
        stray = sorted(set(self.draft_alloc.seq_pages) - active, key=str)
        if stray:
            leaks["spec_stray_draft_owners"] = stray
        for k, v in self.draft_alloc.audit().items():
            leaks[f"spec_draft_{k}"] = v
        return leaks

    # -- speculative decode ---------------------------------------------
    def _spec_decode(self, ready: List[int]) -> Dict[Any, List[int]]:
        """Draft-propose + single-dispatch verify for every ready slot.

        Greedy slots accept the longest draft prefix matching the
        target's argmaxes, then take the argmax at the first mismatch as
        the bonus token — bit-identical to the per-token greedy oracle
        by construction.  Sampled (temperature > 0) slots and slots with
        a 1-token remaining budget ride the same verify dispatch at
        window 0: position 0 of the ragged window is causally identical
        to a T=1 decode, so their host sampling (and its RNG stream) is
        untouched."""
        eng = self.engine
        tel = eng.telemetry
        G = self.gamma
        # one span tree like every decode step; this one has two
        # dispatches (draft proposal, target verify), each with its fetch
        with tel.span("serve/decode",
                      attrs={"batch": eng.max_batch, "ready": len(ready),
                             "tokens": 1 + G}):
            with tel.span("serve/decode/build"):
                win = np.zeros(eng.max_batch, np.int32)
                specs = []
                for s in ready:
                    req = eng.slots[s]
                    if s in self._spec_slots and req.temperature <= 0.0:
                        w = min(G, req.max_new_tokens - len(req.out) - 1)
                        if w > 0:
                            win[s] = w
                            specs.append(s)
                props = np.zeros((eng.max_batch, G), np.int32)
            if specs:
                self._propose(specs, props)
            ids = np.zeros((eng.max_batch, 1 + G), np.int32)
            tables = np.zeros_like(eng.tables)
            lengths = np.zeros_like(eng.lengths)
            for s in ready:
                ids[s, 0] = eng.slots[s].last_token
                tables[s] = eng.tables[s]
                lengths[s] = eng.lengths[s]
            for s in specs:
                ids[s, 1:1 + win[s]] = props[s, :win[s]]
            logits, eng.caches, _ = eng._run_step(
                jnp.asarray(ids), jnp.asarray(tables), lengths,
                phase="spec_verify")
            self._decode_sizes(lengths, ready)
            with tel.span("serve/decode/fetch"):
                logits = eng._fetch(logits)
            self.sched_stats["decode_steps"] += 1
            with tel.span("serve/decode/sample"):
                return self._accept_and_finish(ready, specs, win, props,
                                               logits)

    def _propose(self, specs, props):
        """The draft model's ``gamma`` greedy proposals for the ``specs``
        slots, into ``props``."""
        eng = self.engine
        G = self.gamma
        dlast = np.zeros(eng.max_batch, np.int32)
        dtables = np.zeros_like(self.draft_tables)
        dlengths = np.zeros(eng.max_batch, np.int32)
        for s in specs:
            dlast[s] = eng.slots[s].last_token
            dtables[s] = self.draft_tables[s]
            dlengths[s] = self.draft_lengths[s]
        toks, self.draft_caches = eng._dispatch(
            self._propose_fn,
            (self.draft_params, self.draft_caches, jnp.asarray(dtables),
             jnp.asarray(dlengths), jnp.asarray(dlast)),
            "spec_draft", eng.max_batch, G + 1, starts=dlengths,
            backend="draft", config=self.draft_model.config)
        with eng.telemetry.span("serve/decode/fetch"):
            props[:, :] = np.asarray(toks)[:, :G]
        eng._serve_event("serve/spec_draft", slots=len(specs), window=G)

    def _accept_and_finish(self, ready, specs, win, props, logits):
        """The host half of a speculative step: per-token semantics for
        the slots that rode at window 0 (their row of the verify
        dispatch's ``StepLogits``), longest-matching-prefix accept for
        the speculating ones, which read their window's rows of the
        whole block on the host, then evictions and finishes."""
        from deepspeed_tpu.inference.robustness import EVICT_FAULT
        eng = self.engine
        G = self.gamma
        done_slots, fault_slots = [], []
        done_now: Dict[Any, List[int]] = {}
        accepted_total = rejected_total = 0
        for s in ready:
            req = eng.slots[s]
            if s not in specs:
                # per-token semantics on window position 0
                req.out.append(req.last_token)
                eng.lengths[s] += 1
                self.sched_stats["decode_tokens"] += 1
                ended = (eng.eos is not None and req.last_token == eng.eos)
                if ended or len(req.out) >= req.max_new_tokens:
                    done_slots.append(s)
                else:
                    try:
                        req.last_token = eng._sample(req, logits[s, 0])
                    except Exception as e:
                        fault_slots.append((s, str(e)))
                continue
            w = int(win[s])
            g = np.argmax(logits.host()[s, :w + 1],
                          axis=-1).astype(np.int32)
            req.out.append(req.last_token)
            eng.lengths[s] += 1
            self.sched_stats["decode_tokens"] += 1
            finished = (eng.eos is not None and req.last_token == eng.eos) \
                or len(req.out) >= req.max_new_tokens
            m = 0
            while not finished and m < w and int(props[s, m]) == int(g[m]):
                tok = int(props[s, m])
                req.out.append(tok)
                eng.lengths[s] += 1
                self.sched_stats["decode_tokens"] += 1
                m += 1
                finished = (eng.eos is not None and tok == eng.eos) or \
                    len(req.out) >= req.max_new_tokens
            accepted_total += m
            rejected_total += w - m
            self.sched_stats["spec_proposed"] += w
            self.sched_stats["spec_accepted"] += m
            self.sched_stats["spec_rejected"] += w - m
            # on the host as of the verify fetch: the m accepted tokens
            # and, when the request goes on, the token after them
            n = m + (not finished)
            if n:
                eng._emit(req.req_id, n)
                logits.count(n, n)
            if finished:
                done_slots.append(s)
            else:
                # accept boundary: g[m] is the target's argmax given the
                # accepted prefix — the bonus (m == w) or the correction
                # at the first mismatch (m < w)
                req.last_token = int(g[m])
            # the draft cache holds every committed position (the extra
            # propose iteration wrote the final proposal too): resume it
            # at the target's new length, stale tail entries are simply
            # overwritten by the next sequential writes
            self.draft_lengths[s] = eng.lengths[s]
        if specs:
            self.sched_stats["spec_windows"] += 1
            eng._serve_event("serve/spec_verify", slots=len(specs),
                             window=G, accepted=accepted_total,
                             rejected=rejected_total)
            tel = eng.telemetry
            if tel is not None and tel.enabled:
                if accepted_total:
                    tel.count("serve/spec_accepted_tokens", accepted_total)
                if rejected_total:
                    tel.count("serve/spec_rejected_tokens", rejected_total)
        for slot, err in fault_slots:
            rid = eng.slots[slot].req_id
            logger.warning(f"evicting request {rid!r} after sampler "
                           f"fault: {err}")
            eng._evict_slot(slot, "evicted", EVICT_FAULT, detail=err)
            eng.stats["evicted"] += 1
            eng._serve_event("serve/evict", req_id=rid,
                             reason=EVICT_FAULT, error=err)
        if fault_slots:
            eng._admit()
        for slot in done_slots:
            rid = eng.slots[slot].req_id
            eng._finish(slot)
            done_now[rid] = eng.finished.pop(rid)
        return done_now


def create_scheduler(engine, cfg: SchedulerConfig,
                     draft_model=None, draft_params=None) -> SchedulerBase:
    """Build the policy the ``serving.scheduler`` block selects."""
    if not isinstance(cfg, SchedulerConfig):
        cfg = SchedulerConfig(cfg or {})
    if cfg.policy == "chunked":
        return ChunkedScheduler(engine, cfg, draft_model=draft_model,
                                draft_params=draft_params)
    if cfg.speculative.enabled:
        raise ValueError(
            "serving.scheduler.speculative needs policy='chunked'")
    if draft_model is not None:
        logger.warning("draft_model ignored: scheduler policy is "
                       f"{cfg.policy!r} without speculative decoding")
    return MonolithicScheduler(engine, cfg)
