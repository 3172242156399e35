"""Scheduler v2 (``serving.scheduler``): chunked prefill + speculative
decoding under SLO classes.

Contracts under test:

* the default ``monolithic`` policy IS the pre-scheduler engine — greedy
  outputs across policies stay token-exact vs the dense oracle;
* the ``chunked`` policy splits long prefills into fixed-token chunks
  interleaved with decode, so a long prompt no longer stalls every
  in-flight decode (max inter-token gap shrinks) and latency-class chat
  TTFT drops on a simulated dispatch clock;
* greedy speculative decoding is bit-identical to the non-speculative
  oracle for a perfect draft (acceptance 1.0) AND an uncorrelated cold
  draft (acceptance near 0) — the verify/correction path earns it;
* SLO classes order admission and chunk scheduling; unknown classes are
  rejected at admission time;
* deadlines are checked at prefill-chunk boundaries: a TTL can cancel a
  request MID-prefill — even between chunks inside one ``step()`` — and
  the engine drains to zero with no page, draft-page, or trace leaks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.robustness import RequestRejected
from deepspeed_tpu.inference.scheduler import SchedulerConfig
from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, dt=1.0):
        self.t += dt


def _dense_greedy(model, params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        logits = model.apply(params, jnp.asarray(seq)[None, :],
                             train=False)
        seq.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return seq


def _prompts(cfg, seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).tolist()
            for n in lengths]


def _charge_dispatches(eng, cost=1.0):
    """Route every target dispatch through the engine clock: each
    ``_run_step`` call advances the injected FakeClock by ``cost``
    (optionally scaled per token), so scheduling latencies are measured
    in deterministic simulated dispatch time, not CPU wall time."""
    real = eng._run_step

    def charged(ids, tables, lengths, phase="decode"):
        eng._clock.t += cost(ids) if callable(cost) else cost
        return real(ids, tables, lengths, phase=phase)

    eng._run_step = charged


# ----------------------------------------------------------------------
# config + wiring
# ----------------------------------------------------------------------
def test_config_validation():
    with pytest.raises(ValueError):
        SchedulerConfig({"policy": "round-robin"})
    with pytest.raises(ValueError):
        SchedulerConfig({"prefill_chunk_tokens": 0})
    with pytest.raises(ValueError):
        SchedulerConfig({"slo_class_default": "gold"})
    with pytest.raises(ValueError):
        SchedulerConfig({"slo_classes": {"platinum": {}}})
    with pytest.raises(ValueError):
        SchedulerConfig({"speculative": {"enabled": True,
                                         "num_draft_tokens": -1}})
    # 0 draft tokens is "speculation off" (the autotuner's draft-length
    # knob sweeps it), not an error
    off = SchedulerConfig({"speculative": {"enabled": True,
                                           "num_draft_tokens": 0}})
    assert not off.speculative.enabled
    cfg = SchedulerConfig({"slo_classes":
                           {"latency": {"default_deadline_s": 2.0}}})
    assert cfg.class_deadline_s("latency") == 2.0
    assert cfg.class_deadline_s("throughput") is None


def test_default_policy_is_monolithic(tiny):
    cfg, model, params = tiny
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=32, dtype=jnp.float32)
    assert eng.scheduler.policy == "monolithic"
    assert eng.scheduler.meta()["speculative"] == 0
    assert eng.health()["scheduler"]["policy"] == "monolithic"


def test_speculative_requires_chunked(tiny):
    cfg, model, params = tiny
    with pytest.raises(ValueError, match="chunked"):
        ServingEngine(
            model, params, max_batch=1, page_size=8, max_seq=32,
            dtype=jnp.float32,
            serving={"scheduler": {"speculative": {"enabled": True}}},
            draft_model=model, draft_params=params)


# ----------------------------------------------------------------------
# chunked prefill: bit-identity + latency behavior
# ----------------------------------------------------------------------
def test_chunked_bit_identical_to_oracle(tiny):
    """Mixed prompt lengths (multi-chunk and sub-chunk) through the
    chunked policy: token-exact vs the dense oracle, clean leak report,
    and the stats prove prefills actually split."""
    cfg, model, params = tiny
    prompts = _prompts(cfg, 0, (5, 20, 3, 33))
    eng = ServingEngine(
        model, params, max_batch=4, page_size=8, max_seq=64,
        dtype=jnp.float32,
        serving={"scheduler": {"policy": "chunked",
                               "prefill_chunk_tokens": 8}})
    outs = eng.generate(prompts, max_new_tokens=6)
    for p, got in zip(prompts, outs):
        assert got == _dense_greedy(model, params, p, 6), p
    assert eng.leak_report() == {}
    stats = eng.scheduler.sched_stats
    assert stats["prefills_split"] == 2          # the 20- and 33-token
    assert stats["prefill_chunks"] > len(prompts)


def test_chunked_interleaves_decode_with_long_prefill(tiny):
    """The head-of-line number: an in-flight chat decode's max
    inter-token gap when a 48-token prompt lands mid-stream.
    Monolithic prefills it as ONE dispatch (the chat's next token waits
    out its whole simulated cost); chunked bounds the stall at one
    8-token chunk per step — max gap at least 2x smaller."""
    cfg, model, params = tiny
    long_p, chat_p = _prompts(cfg, 1, (48, 4))

    def run(sched_cfg):
        clk = FakeClock()
        eng = ServingEngine(model, params, max_batch=2, page_size=8,
                            max_seq=64, dtype=jnp.float32, clock=clk,
                            serving={"scheduler": sched_cfg})
        _charge_dispatches(eng, cost=lambda ids: 0.1 + 0.01 * ids.size)
        eng.add_request("chat", chat_p, max_new_tokens=10)
        eng.step()                       # chat admitted + decoding
        chat = eng.slots[0]
        assert chat is not None and chat.req_id == "chat"
        seen, t_last = len(chat.out), clk.t
        # the long prompt lands now — monolithic charges its whole
        # prefill before control returns; chunked trickles it
        eng.add_request("long", long_p, max_new_tokens=2)
        gaps = []
        while eng.queue or eng.n_active:
            eng.step()
            n = len(chat.out) if eng.slots[0] is chat else 10
            if n > seen:
                gaps.append(clk.t - t_last)
                seen, t_last = n, clk.t
        assert eng.leak_report() == {}
        return max(gaps)

    mono_gap = run({"policy": "monolithic"})
    chunk_gap = run({"policy": "chunked", "prefill_chunk_tokens": 8})
    assert chunk_gap * 2 <= mono_gap, (mono_gap, chunk_gap)


def test_latency_class_ttft_beats_monolithic_on_sim_clock(tiny):
    """The bench's acceptance claim in miniature: a latency-class chat
    request queued behind a long throughput-class prompt on a busy
    engine.  Monolithic admission is class-blind FIFO — the chat's TTFT
    eats the long prompt's one-shot prefill and full service; chunked
    orders admission and chunk scheduling by SLO class, so the chat
    prefills first.  At least 2x lower in simulated dispatch time."""
    cfg, model, params = tiny
    busy_p, long_p, chat_p = _prompts(cfg, 2, (4, 48, 4))

    def run(sched_cfg):
        clk = FakeClock()
        eng = ServingEngine(model, params, max_batch=1, page_size=8,
                            max_seq=64, dtype=jnp.float32, clock=clk,
                            serving={"scheduler": sched_cfg})
        _charge_dispatches(eng, cost=lambda ids: 0.1 + 0.01 * ids.size)
        eng.add_request("busy", busy_p, max_new_tokens=3)
        # both queue behind the busy slot; admit time stamps here
        eng.add_request("long", long_p, max_new_tokens=2,
                        slo_class="throughput")
        eng.add_request("chat", chat_p, max_new_tokens=4,
                        slo_class="latency")
        while eng.queue or eng.n_active:
            eng.step()
        tr = {t.req_id: t for t in eng.tracer.completed}
        assert eng.leak_report() == {}
        return tr["chat"].ttft_ms()

    mono = run({"policy": "monolithic"})
    chunked = run({"policy": "chunked", "prefill_chunk_tokens": 8})
    assert chunked * 2 <= mono, (mono, chunked)


def test_slo_class_orders_admission_and_rejects_unknown(tiny):
    """With one slot busy, a later latency-class arrival is admitted
    ahead of an earlier throughput-class one; an unknown class is a
    typed admission-time rejection."""
    cfg, model, params = tiny
    pa, pb, pc = _prompts(cfg, 3, (4, 5, 6))
    eng = ServingEngine(
        model, params, max_batch=1, page_size=8, max_seq=32,
        dtype=jnp.float32,
        serving={"scheduler": {"policy": "chunked",
                               "prefill_chunk_tokens": 8}})
    eng.add_request("busy", pa, max_new_tokens=2)
    eng.step()
    eng.add_request("batch", pb, max_new_tokens=2,
                    slo_class="throughput")
    eng.add_request("chat", pc, max_new_tokens=2, slo_class="latency")
    while eng.queue or eng.n_active:
        eng.step()
    done = [t.req_id for t in eng.tracer.completed]
    assert done.index("chat") < done.index("batch")
    with pytest.raises(RequestRejected) as e:
        eng.add_request("x", pa, max_new_tokens=2, slo_class="gold")
    assert e.value.reason == "bad_request"


# ----------------------------------------------------------------------
# deadlines at chunk boundaries (satellite: TTL mid-prefill)
# ----------------------------------------------------------------------
def test_deadline_cancels_mid_prefill_and_drains_to_zero(tiny):
    """A 33-token prompt prefilling 8 tokens per step with a 2.5 s TTL
    on a fake clock ticking 1 s per step: the deadline fires BETWEEN
    chunks, the trace closes with the ``deadline`` terminal before any
    first token, and every page and trace is released."""
    cfg, model, params = tiny
    (p,) = _prompts(cfg, 4, (33,))
    clk = FakeClock()
    eng = ServingEngine(
        model, params, max_batch=1, page_size=8, max_seq=64,
        dtype=jnp.float32, clock=clk,
        serving={"scheduler": {"policy": "chunked",
                               "prefill_chunk_tokens": 8}})
    eng.add_request("r", p, max_new_tokens=4, deadline_s=2.5)
    for _ in range(8):
        clk.tick(1.0)
        eng.step()
        if not eng.n_active:
            break
    assert eng.n_active == 0 and not eng.queue
    assert eng.stats["deadline"] == 1
    tr = list(eng.tracer.completed)[-1]
    assert tr.terminal == "deadline" and tr.t_first_token < 0
    # the prefill was cancelled partway: fewer chunks ran than the
    # prompt needs (ceil(33/8) = 5)
    assert 0 < eng.scheduler.sched_stats["prefill_chunks"] < 5
    assert eng.leak_report() == {}
    # every page back in circulation except the reserved scratch page
    assert eng.alloc.available_page_count == eng.alloc.num_pages - 1


def test_deadline_checked_between_chunks_within_one_step(tiny):
    """The chunk-boundary regression: with
    ``max_prefill_chunks_per_step`` covering the whole prompt, all six
    chunks would run inside ONE ``step()`` — the TTL check at each
    chunk boundary must still stop the prefill partway through that
    step, not at the next step boundary."""
    cfg, model, params = tiny
    (p,) = _prompts(cfg, 5, (48,))
    clk = FakeClock()
    eng = ServingEngine(
        model, params, max_batch=1, page_size=8, max_seq=64,
        dtype=jnp.float32, clock=clk,
        serving={"scheduler": {"policy": "chunked",
                               "prefill_chunk_tokens": 8,
                               "max_prefill_chunks_per_step": 8}})
    _charge_dispatches(eng, cost=1.0)    # each chunk costs 1 s
    eng.add_request("r", p, max_new_tokens=2, deadline_s=2.5)
    eng.step()
    assert eng.n_active == 0
    assert eng.stats["deadline"] == 1
    # expired after the chunk that crossed t=2.5 — chunks 4..6 never ran
    assert eng.scheduler.sched_stats["prefill_chunks"] == 3
    assert eng.leak_report() == {}


def test_class_default_ttl_applies(tiny):
    """``slo_classes.latency.default_deadline_s`` stamps a deadline on
    latency-class requests that pass none; throughput requests stay
    deadline-free."""
    cfg, model, params = tiny
    pa, pb = _prompts(cfg, 6, (4, 5))
    clk = FakeClock()
    eng = ServingEngine(
        model, params, max_batch=1, page_size=8, max_seq=32,
        dtype=jnp.float32, clock=clk,
        serving={"scheduler": {
            "policy": "chunked", "prefill_chunk_tokens": 8,
            "slo_classes": {"latency": {"default_deadline_s": 2.0}}}})
    eng.add_request("busy", pa, max_new_tokens=8,
                    slo_class="throughput")
    eng.step()
    eng.add_request("chat", pb, max_new_tokens=2, slo_class="latency")
    for _ in range(10):
        clk.tick(1.0)
        eng.step()
        if not (eng.queue or eng.n_active):
            break
    # the chat request expired in the queue behind the busy slot; the
    # throughput request (no TTL) ran to its full budget
    tr = {t.req_id: t for t in eng.tracer.completed}
    assert tr["chat"].terminal == "deadline"
    assert tr["busy"].terminal == "finish" and \
        tr["busy"].n_generated == 8
    assert eng.leak_report() == {}


# ----------------------------------------------------------------------
# speculative decoding
# ----------------------------------------------------------------------
def test_spec_bit_identical_perfect_and_cold_draft(tiny):
    """Greedy spec-decode vs the dense oracle under a PERFECT draft
    (the target itself: every window accepted, decode steps collapse)
    and a COLD draft (fresh init: acceptance collapses, the correction
    token carries every step) — outputs must be token-exact in both."""
    cfg, model, params = tiny
    cold = model.init(jax.random.key(9))
    prompts = _prompts(cfg, 7, (5, 12, 3))
    oracle = [_dense_greedy(model, params, p, 8) for p in prompts]

    def run(draft_params):
        eng = ServingEngine(
            model, params, max_batch=4, page_size=8, max_seq=64,
            dtype=jnp.float32,
            serving={"scheduler": {
                "policy": "chunked", "prefill_chunk_tokens": 8,
                "speculative": {"enabled": True,
                                "num_draft_tokens": 3}}},
            draft_model=model, draft_params=draft_params)
        outs = eng.generate(prompts, max_new_tokens=8)
        assert eng.leak_report() == {}
        return outs, eng.scheduler.snapshot()

    perfect_outs, perfect = run(params)
    cold_outs, cold_snap = run(cold)
    assert perfect_outs == oracle
    assert cold_outs == oracle
    assert perfect["spec_acceptance_rate"] == 1.0
    assert cold_snap["spec_acceptance_rate"] < 0.5
    # a perfect draft commits whole windows: far fewer decode rounds
    assert perfect["decode_steps"] < cold_snap["decode_steps"]


def test_spec_sampling_requests_ride_nonspeculative(tiny):
    """Temperature > 0 requests keep the host RNG stream: they decode
    token-by-token (window 0) next to speculative greedy neighbours,
    and their outputs match the non-speculative engine bit-for-bit."""
    cfg, model, params = tiny
    pa, pb = _prompts(cfg, 8, (6, 7))

    def run(sched_cfg, spec):
        eng = ServingEngine(
            model, params, max_batch=2, page_size=8, max_seq=64,
            dtype=jnp.float32, serving={"scheduler": sched_cfg},
            draft_model=model if spec else None,
            draft_params=params if spec else None)
        eng.add_request("greedy", pa, max_new_tokens=6)
        eng.add_request("sampled", pb, max_new_tokens=6,
                        temperature=0.8, seed=123)
        out = {}
        while eng.queue or eng.n_active:
            for rid, toks in eng.step().items():
                out.setdefault(rid, []).extend(toks)
        assert eng.leak_report() == {}
        return out

    base = run({"policy": "chunked", "prefill_chunk_tokens": 8}, False)
    spec = run({"policy": "chunked", "prefill_chunk_tokens": 8,
                "speculative": {"enabled": True,
                                "num_draft_tokens": 3}}, True)
    assert spec == base
