"""``serve_cell.StepReader``: token times, counts and dispatch sizes from
the engine's public per-step report, held against the patch of
``_run_step`` and ``_sample`` that it replaced (PR 23's ``Probe``, kept
here as the reference), through the traffic loop itself on a toy engine;
and a toy engine at ``decode_chunk`` 2, whose tokens the patch never saw."""

import jax
import jax.numpy as jnp
import deepspeed_tpu
from chipbench import cells, device, serve_cell, sut, tracing, traffic

import tree

SEED = 2 ** 31 + 28


class PatchProbe:
    """What the parent's ``Probe`` recorded, by its means: ``_run_step``
    and ``_sample`` replaced on the engine object."""

    def __init__(self, engine):
        self.engine = engine
        self.token_times, self.dispatches = {}, []
        self.prompt_tokens = 0
        self._run_step, self._sample = engine._run_step, engine._sample
        engine._run_step, engine._sample = self.run_step, self.sample

    def run_step(self, ids, tables, lengths, phase="decode"):
        record = {"phase": phase, "tokens": ids.shape[1]}
        if phase != "prefill":
            lengths = self.engine.lengths
            record["contexts"] = (lengths[lengths > 0] + 1).tolist()
        self.dispatches.append(record)
        return self._run_step(ids, tables, lengths, phase=phase)

    def sample(self, req, row):
        now = serve_cell.clock()
        times = self.token_times.setdefault(req.req_id, [])
        if not times:       # the prefill that just ran was this request's
            last = self.dispatches[-1]
            last["real"] = min(len(req.prompt), last["tokens"])
            last["context"] = len(req.prompt)
            self.prompt_tokens += len(req.prompt)
        times.append(now)
        return self._sample(req, row)


def _serve(decode_chunk):
    """Two seconds of the toy closed loop through ``_serve_traffic``, then
    one more ``step()`` so that the last submissions are accounted for.
    Returns (reader, patch probe, iterations, answer lengths, served)."""
    config, mix = tree.data("tiny-olmo2"), tree.data("tiny-closed")
    config["serve"]["engine"]["decode_chunk"] = decode_chunk
    cell = cells.Cell("toy", 1, config, mix, [], [])
    model = sut.build_model(cell)
    params = sut.seeded_weights(model, SEED, jnp.bfloat16, jax.devices()[:1])
    engine = deepspeed_tpu.init_inference(
        model=model, params=params, dtype="bfloat16").create_serving_engine(
        max_batch=int(mix["max_batch"]), **config["serve"]["engine"])
    serve_cell._warm_up(cell, engine, SEED)      # compile outside the loop
    probe = PatchProbe(engine)
    reader = serve_cell.StepReader(engine)

    def stream():
        return traffic.RequestStream(mix, config["vocab_size"], SEED, 2.0)

    served = serve_cell._serve_traffic(
        mix, stream(), engine, reader, SEED, 2.0, tracing.Tracer(False, 0),
        device.CompileCounter())
    iterations = list(served.iterations)
    engine.step()
    generated, prompt_tokens, dispatches = reader.read()
    iterations.append({"generated": generated, "dispatches": dispatches,
                       "tokens": generated + prompt_tokens})
    answers = {}
    for rid, _, _, answer, _ in stream():
        if rid > max(served.submitted):
            break
        answers[rid] = answer
    assert engine.eos is None       # so a finished request has its budget
    assert served.finished and not served.refused
    return reader, probe, iterations, answers, served


def test_the_report_reads_what_the_patch_read():
    reader, probe, iterations, answers, served = _serve(1)
    assert set(reader.token_times) == set(probe.token_times) <= \
        set(served.submitted)       # six callers on four slots: two wait
    for rid, times in probe.token_times.items():
        ours = reader.token_times[rid]
        assert len(ours) == len(times)
        # the engine stamps a token inside the call the patch wrapped
        assert all(0 <= b - a < 1e-3 for a, b in zip(times, ours))
        if rid in served.finished:
            assert len(ours) == answers[rid]
    assert sum(it["generated"] for it in iterations) == \
        sum(len(t) for t in probe.token_times.values())
    assert sum(it["tokens"] - it["generated"] for it in iterations) == \
        probe.prompt_tokens
    ours = [d for it in iterations for d in it["dispatches"]]
    assert len(ours) == len(probe.dispatches) > 20
    for mine, theirs in zip(ours, probe.dispatches):
        assert {k: mine[k] for k in theirs} == theirs
    assert {d["phase"] for d in ours} == {"prefill", "decode"}


def test_tokens_of_a_chunked_decode_are_counted():
    """``decode_chunk`` 2 samples on the device: ``_sample`` runs once a
    request, for the prefill's token, and ``_run_step`` only for
    prefills, so the patch saw one token a request and no decode; the
    report has them all."""
    reader, probe, iterations, answers, served = _serve(2)
    assert all(len(t) == 1 for t in probe.token_times.values())
    assert {d["phase"] for d in probe.dispatches} == {"prefill"}
    for rid in served.finished:
        assert len(reader.token_times[rid]) == answers[rid] >= 4
    assert sum(it["generated"] for it in iterations) == \
        sum(len(t) for t in reader.token_times.values()) > \
        3 * len(probe.token_times)
    phases = {d["phase"] for it in iterations for d in it["dispatches"]}
    assert phases == {"prefill", "decode_chunk"}
    assert all(d["contexts"] for it in iterations for d in it["dispatches"]
               if d["phase"] == "decode_chunk")


def test_logits_rows_are_kept_only_while_asked():
    """``_logits_rows`` wraps ``_sample`` for the check and puts it back."""
    class Engine:
        def _sample(self, req, row):
            return 7

    class Request:
        req_id = "r"

    engine = Engine()
    with serve_cell._logits_rows(engine) as rows:
        assert engine._sample(Request(), [1.0, 2.0]) == 7
        assert rows["r"][0].dtype == "float32" and rows["r"][0][1] == 2.0
    assert engine._sample(Request(), [3.0]) == 7 and len(rows["r"]) == 1
