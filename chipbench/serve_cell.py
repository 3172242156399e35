"""Traffic kinds ``open_loop`` and ``closed_loop``: the program's server
(``init_inference(...).create_serving_engine(...)``: ``add_request`` and
``step``) driven from one thread, as its own host loop is.

The benchmark keeps its own clock: a request is timed from when it was
DUE, not from when it was admitted.  What happened inside a ``step()`` it
takes from the program's public account of it, ``engine.last_step``
(docs/telemetry.md): every output token with the time it reached the host
(``emitted``), every device dispatch with its sizes (``dispatches``) and
the prompt tokens whose keys and values became available
(``prompt_tokens``), all on ``time.perf_counter_ns``, the clock of this
file.  No method of the engine is replaced while traffic runs, so a step
that takes other arguments, yields several tokens a request or samples on
the device is counted like any other.  Only the reference check, in
set-up, wraps ``_sample`` to see the logits rows the program sampled from.
"""

import contextlib
import time
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.inference.robustness import RequestRejected

from chipbench import cells, device, sut, traffic, tracing

# Engine (bf16 weights, bf16 activations, keys and values stored as bf16
# pages, logits returned in float32) against the float32 reference on the
# same weights: prefill, then CHECK_DECODE_TOKENS decode steps through the
# paged cache, compared on logits rows as max |difference| over the largest
# |reference logit| of the row set.  bf16 carries 8 bits (2**-8 = 0.4 % a
# rounding); over 16 layers of residual adds the rows land 1-2 % off on the
# chip (PERF.md §6).  4 % passes that and fails a cache that drops or
# misplaces a page (errors of the order of the logits themselves) or 8-bit
# weights.  Every compared row is held to it, whatever the family.
#
# A family that routes tokens to experts (its file says ``ROUTED = True``)
# may hand back, beside the logits, which rows its own float32 routing
# leaves decided (chipbench/README.md, "A family that routes").  A top-k is
# discontinuous: where a token's last chosen and first unchosen selection
# scores lie closer than the program's rounding, program and reference may
# each pick another expert and the row's hidden state moves by a whole
# expert's output, several times the tolerance, with neither side wrong.
# Such a row is undecided at the stated precision and is left out; it is
# the reference alone that says so, from its own scores, never from
# anything the program put out.
#
# The harness takes whatever mask the reference hands it; the least rule,
# which asks the ROW'S OWN token alone, rests on a reckoning that is
# UNVERIFIED AT ANY SIZE.  A flip at an earlier token of the context
# reaches the row through attention alone: that token's keys and values in
# the later layers move by the swapped expert's share of its hidden state
# (for 8 experts a token of 256, one weight of about 1/8 of the routed
# output: several percent, say 5 %), and the row reads it through a
# softmax over T keys that random weights leave near uniform, so the row's
# attention output moves by about 5 % / T: 0.05 % at T = 100, 0.005 % at
# 1024, against the 4 % above and the 1-2 % that bf16 alone gives.  If so,
# a context flip stays inside the tolerance and needs no mask.  The only
# experiment there is contradicts it, at toy size: one expert of four a
# token, contexts of 100, served in bf16 on the CPU, decided rows read up
# to 0.6 after a flip in their context (tests/chipbench/test_routed.py).
# There the reference masks the rows after an undecided token as well,
# every row it leaves then reads under 0.02, and so few are left that the
# floor below fails the run: such a family cannot be judged by this check
# at those lengths.  A sharply peaked attention on a flipped token would
# break the reckoning too.  Either shows as a failing DECIDED row: the cure
# is then a wider rule in the reference (the same pair carries it), never
# a wider tolerance; whoever adds the first routed cell reads both rules
# on the chip first (chipbench/README.md).
#
# A check that compares nothing is no check: ``correct`` is also false
# when fewer than MIN_COMPARED_SHARE of all rows were compared, or no row
# of some prompt.
LOGIT_TOL = 0.04
MIN_COMPARED_SHARE = 0.5
CHECK_PROMPTS = 3
CHECK_DECODE_TOKENS = 24
TRACE_SECONDS = 6.0


clock = time.perf_counter     # seconds of the clock the engine stamps in ns


class StepReader:
    """The engine's own per-step reports, read after every ``step()``:
    the host time of each output token by request, and what the step
    dispatched."""

    def __init__(self, engine):
        self.engine = engine
        self.token_times = {}       # req_id -> [host time of each token]

    def read(self):
        """``engine.last_step``, the report of the ``step()`` that just
        returned (with whatever ``add_request`` ran inline since the one
        before): (tokens generated, prompt tokens made available, the
        dispatches)."""
        report = self.engine.last_step
        generated = 0
        for rid, n, t_ns in report["emitted"]:
            self.token_times.setdefault(rid, []).extend([t_ns / 1e9] * n)
            generated += n
        return generated, report["prompt_tokens"], report["dispatches"]


def _prefill_starts(engine, since_ns):
    """req_id -> host time its first prefill began: the program's
    ``serve/prefill`` spans since ``since_ns``."""
    starts = {}
    for span in engine.telemetry.spans(since_ns):
        if span.name == "serve/prefill":
            starts.setdefault(span.key, span.t0_ns / 1e9)
    return starts


@contextlib.contextmanager
def _logits_rows(engine):
    """While open, the float32 logits row of every token the engine
    samples on the host, by request: ``_sample(req, row)`` is wrapped, for
    the reference check alone.  The window never runs under it."""
    rows = {}
    original = engine._sample

    def sample(req, row):
        rows.setdefault(req.req_id, []).append(np.array(row, np.float32))
        return original(req, row)

    engine._sample = sample
    try:
        yield rows
    finally:
        engine._sample = original


def _reference_rows(cell, params, ids, last):
    """The reference's logits rows ``[last, vocab]`` of one prompt and
    which of them its routing leaves decided ``[last]`` (all, unless the
    family routes and its reference says otherwise)."""
    out = cell.reference.logits(params, jnp.asarray(ids), cell.config,
                                last=last)
    if not isinstance(out, tuple):
        return np.asarray(out)[0], np.ones(last, bool)
    if not getattr(cell.family, "ROUTED", False):
        raise TypeError(
            f"chipbench/reference/{cell.family.REFERENCE}.py returned "
            f"(logits, decided), but chipbench/families/"
            f"{cell.config['family']}.py does not declare ROUTED = True")
    want, decided = out
    decided = np.asarray(decided)
    if decided.dtype != bool or decided.shape != (1, last):
        raise TypeError(f"decided must be a boolean [1, {last}], got "
                        f"{decided.dtype} {decided.shape}")
    return np.asarray(want)[0], decided[0]


def _serve_check_prompts(cell, engine, seed):
    """The check's prompts (quantiles of the mix's lengths, tokens from
    the seed) prefilled and decoded through the paged cache: a request's
    ids ``[1, S]`` and the logits rows the program sampled from."""
    lengths = traffic.quantile_grid(cell.mix["prompt_tokens"], CHECK_PROMPTS)
    vocab = cell.config["vocab_size"]
    rids = [f"check-{i}" for i in range(len(lengths))]
    done = {}
    with _logits_rows(engine) as rows:
        for i, (rid, n) in enumerate(zip(rids, lengths)):
            engine.add_request(rid, traffic.rng_for(seed, 5, i).integers(
                0, vocab, int(n), dtype=np.int32),
                max_new_tokens=CHECK_DECODE_TOKENS)
        while len(done) < len(rids):
            done.update(engine.step())
    return [(np.asarray(done[rid], np.int32)[None, :-1],
             np.stack(rows[rid])) for rid in rids]


def _compare_with_reference(cell, served, params):
    """What ``_serve_check_prompts`` served against the reference's full
    forward pass on ``params``, on logits: the largest error over the rows
    the reference's routing leaves decided, how many rows those are, and
    whether that is a check (``ok``)."""
    errors = {True: [], False: []}      # decided? -> each prompt's largest
    rows_total = rows_compared = 0
    every_prompt = True
    for ids, rows in served:
        want, decided = _reference_rows(cell, params, ids, len(rows))
        scale = max(1.0, float(np.max(np.abs(want))))
        for kind in (True, False):
            if np.any(decided == kind):
                errors[kind].append(float(np.max(
                    np.abs(rows - want)[decided == kind])) / scale)
        rows_total += len(rows)
        rows_compared += int(decided.sum())
        every_prompt = every_prompt and bool(decided.any())
    # np.max, unlike max(), keeps a NaN: a row that is not a number fails
    worst = {kind: float(np.max(e)) if e else None
             for kind, e in errors.items()}
    ok = (every_prompt and worst[True] <= LOGIT_TOL
          and rows_compared >= MIN_COMPARED_SHARE * rows_total)
    return {"ok": bool(ok), "logit_error": worst[True],
            "logit_error_undecided": worst[False],
            "rows_compared": rows_compared,
            "rows_undecided": rows_total - rows_compared}


def _check_against_reference(cell, engine, params, seed):
    return _compare_with_reference(
        cell, _serve_check_prompts(cell, engine, seed), params)


def _warm_up(cell, engine, seed):
    """One prefill of every padded length the engine's scheduler gives the
    mix's prompts (the check's prompts have compiled some already), and
    the decode shape."""
    dist = cell.mix["prompt_tokens"]
    longest = {}    # padded length -> the longest prompt that pads to it
    for n in range(int(dist["min"]), int(dist["max"]) + 1):
        longest[engine.scheduler.prefill_padded_len(n)] = n
    pending = set()
    for i, n in enumerate(longest.values()):
        ids = traffic.rng_for(seed, 6, i).integers(
            0, cell.config["vocab_size"], n, dtype=np.int32)
        engine.add_request(f"warm-{i}", ids, max_new_tokens=2)
        pending.add(f"warm-{i}")
    while pending:
        pending -= set(engine.step())


@dataclass
class Served:
    """What the traffic loop saw, on the benchmark's clock."""
    window: tuple = (None, None)
    end: float = 0.0
    compiles: int = 0
    iterations: list = field(default_factory=list)
    due: dict = field(default_factory=dict)         # req_id -> due time
    cycle: dict = field(default_factory=dict)       # req_id -> stream cycle
    submitted: dict = field(default_factory=dict)   # req_id -> submit time
    refused: set = field(default_factory=set)
    finished: set = field(default_factory=set)


def _serve_traffic(mix, stream, engine, reader, seed, seconds, tracer,
                   counter):
    """Ramp (part of set-up), window, grace: one thread submits what is
    due and steps the engine.  Open loop: requests are due on the stream's
    schedule and the window is the schedule's cycle 1.  Closed loop: each
    of ``clients`` callers sends its next request when the last came back,
    and the window opens at the first loop boundary after the ramp."""
    open_loop = mix["kind"] == "open_loop"
    ramp_s, grace_s = float(mix["ramp_s"]), float(mix["grace_s"])
    temperature = mix.get("sampling", {}).get("temperature", 0.0)
    out = Served()
    outstanding = 0
    start = clock()
    next_request, next_due = next(stream), start
    window_start = start + ramp_s if open_loop else None
    compiles_before = None

    def submit(request, due_at):
        rid, cycle, ids, answer, _ = request
        out.due[rid], out.cycle[rid] = due_at, cycle
        out.submitted[rid] = clock()
        try:
            engine.add_request(rid, ids, max_new_tokens=answer,
                               temperature=temperature,
                               seed=int(seed) % (2 ** 31) + rid)
        except RequestRejected:
            out.refused.add(rid)

    while True:
        it0 = clock()
        with tracing.annotate("chipbench/submit"):
            if open_loop:       # a request's gap is the wait AFTER it
                while next_due <= clock():
                    submit(next_request, next_due)
                    next_due += next_request[4]
                    next_request = next(stream)
            else:
                while outstanding < int(mix["clients"]):
                    submit(next_request, clock())
                    next_request = next(stream)
                    outstanding += 1
        now = clock()
        if window_start is None and now - start >= ramp_s:
            window_start = now
        if window_start is not None and now >= window_start:
            if compiles_before is None:
                compiles_before = counter.compiles
            tracer.tick()
            if now >= window_start + seconds:
                waiting = [r for r, c in out.cycle.items() if c == 1
                           and r not in reader.token_times
                           and r not in out.refused]
                if not open_loop or not waiting or \
                        now >= window_start + seconds + grace_s:
                    break
        if not engine.queue and not engine.n_active:
            with tracing.annotate("chipbench/wait"):
                time.sleep(max(0.0, next_due - clock()))
            continue
        s0 = clock()
        with tracing.annotate("chipbench/step"):
            done = engine.step()
        it1 = clock()
        # the benchmark's own book-keeping, outside the iteration's time
        generated, prompt_tokens, dispatches = reader.read()
        out.finished.update(done)
        outstanding -= len(done)
        out.iterations.append({
            "kind": "serve", "t0": it0, "t1": it1, "step_s": it1 - s0,
            "tokens": generated + prompt_tokens, "generated": generated,
            "dispatches": dispatches,
            "active": engine.n_active, "queued": len(engine.queue),
            "traced": tracer.active})
    tracer.stop()
    out.end = clock()
    out.window = (window_start, window_start + seconds)
    out.compiles = counter.compiles - compiles_before
    return out


def _dense_sizes(cfg, engine_cfg):
    """``Run.model`` of a family that brings no ``model_sizes`` of its own:
    plain multi-head or grouped attention, heads of hidden / heads, bf16
    pages."""
    return {"n_layers": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "page_size": engine_cfg["page_size"], "kv_bytes": 2}


def _ms(values, q):
    return float(np.percentile(values, q)) * 1000.0 if values else None


def run(cell, seed, seconds, trace, started, devices, peaks):
    cfg, mix = cell.config, cell.mix
    counter = device.CompileCounter()
    model = sut.build_model(cell)
    dtype = cfg["serve"]["dtype"]
    params = sut.seeded_weights(model, seed, sut.DTYPES[dtype], devices)
    n_params = sut.count_params(params)
    inference = deepspeed_tpu.init_inference(model=model, params=params,
                                             dtype=dtype)
    engine = inference.create_serving_engine(
        max_batch=int(mix["max_batch"]), **cfg["serve"]["engine"])

    t0 = clock()
    check = _check_against_reference(cell, engine, params, seed)
    reference_s = clock() - t0
    _warm_up(cell, engine, seed)
    engine.pop_terminated()
    compiles_warm = counter.compiles

    open_loop = mix["kind"] == "open_loop"
    tracer = tracing.Tracer(trace, TRACE_SECONDS)
    reader = StepReader(engine)
    traffic_began_ns = time.perf_counter_ns()
    served = _serve_traffic(
        mix, traffic.RequestStream(mix, cfg["vocab_size"], seed, seconds),
        engine, reader, seed, seconds, tracer, counter)
    window_start, window_end = served.window
    setup_s = window_start - started

    # ---- what the window holds ----------------------------------------
    steps = [it for it in served.iterations
             if it["t0"] >= window_start and it["t1"] <= window_end]
    terminated = engine.pop_terminated()
    live = {r.req_id for r in engine.queue} | \
        {r.req_id for r in engine.slots if r is not None}
    lost = [r for r in served.submitted
            if r not in served.finished and r not in live
            and r not in terminated and r not in served.refused]
    leaks = engine.leak_report()
    token_times = reader.token_times
    prefill_start = _prefill_starts(engine, traffic_began_ns)

    due = served.due
    in_window = [r for r, c in served.cycle.items() if c == 1] if open_loop \
        else [r for r, t in served.submitted.items()
              if window_start <= t < window_end]
    failed = [r for r in in_window
              if r in served.refused or r in terminated
              or (open_loop and r not in token_times)]
    worst = served.end - min((due[r] for r in in_window),
                             default=served.end)
    ttft = [(token_times[r][0] - due[r]) if r not in failed else worst
            for r in in_window if r in failed or r in token_times]
    queue_wait = [prefill_start[r] - due[r] for r in in_window
                  if r in prefill_start]
    gaps = [b - a for times in token_times.values()
            for a, b in zip(times, times[1:])
            if window_start <= b <= window_end]
    lateness = [served.submitted[r] - due[r] for r in in_window]
    busy = sum(s["t1"] - s["t0"] for s in steps)
    tokens = sum(s["tokens"] for s in steps)

    end_to_end = {"setup_s": setup_s}
    if open_loop:
        end_to_end["tpot_p90_ms"] = _ms(gaps, 90)
        end_to_end["tpot_p95_ms"] = _ms(gaps, 95)
    else:
        end_to_end["serve_tok_s"] = tokens / busy if busy else None
    correct = (check.pop("ok") and not lost and not leaks
               and not failed and bool(steps))

    device.log(
        iterations=len(steps), requests_in_window=len(in_window),
        failed=len(failed), lost=lost, leaks=leaks,
        **check, logit_tol=LOGIT_TOL,
        reference_s=round(reference_s, 2), n_params=n_params,
        tokens_in_window=tokens,
        generated_in_window=sum(s["generated"] for s in steps),
        prefills_in_window=sum(
            d["phase"] == "prefill" for s in steps for d in s["dispatches"]),
        ttft_ms={**{q: _ms(ttft, q) for q in (50, 90, 99)},
                 "mean": float(np.mean(ttft)) * 1000.0 if ttft else None},
        tpot_ms={q: _ms(gaps, q) for q in (50, 90, 92.5, 95, 97.5, 99)}, token_gaps=len(gaps),
        queue_wait_ms={q: _ms(queue_wait, q) for q in (50, 90)},
        generator_late_ms={q: _ms(lateness, q) for q in (50, 100)},
        active_and_queued=[(s["active"], s["queued"])
                           for s in steps[::max(1, len(steps) // 16)]],
        step_s=[round(s["t1"] - s["t0"], 4) for s in steps][:400],
        step_tokens=[s["tokens"] for s in steps][:400],
        compiles_in_window=served.compiles,
        compiles_in_warm_up=compiles_warm,
        cache_hits=counter.cache_hits, compiles_total=counter.compiles)

    report = device.device_report(devices)
    sizes = getattr(cell.family, "model_sizes", _dense_sizes)(
        cfg, cfg["serve"]["engine"])
    run_record = cells.Run(
        chips=len(devices), peaks=peaks, config=cfg,
        model={"n_params": n_params, **sizes},
        steps=steps, traced_steps=[s for s in steps if s["traced"]],
        samples={"queue_wait_ms": [w * 1000.0 for w in queue_wait],
                 "ttft_ms": [t * 1000.0 for t in ttft]},
        counters={"compiles": served.compiles},
        memory_peak_bytes=report["memory_peak_bytes"], trace=tracer.trace())
    return {"correct": bool(correct), "attempted": len(in_window),
            "failed": len(failed), "end_to_end": end_to_end,
            "run": run_record, "device": report,
            "compared": [("logit_error", check["logit_error"], LOGIT_TOL),
                         ("rows_compared", check["rows_compared"],
                          f">= {MIN_COMPARED_SHARE} of "
                          f"{check['rows_compared'] + check['rows_undecided']}"),
                         ("lost", len(lost), 0), ("leaks", len(leaks), 0),
                         ("failed", len(failed), 0)]}
