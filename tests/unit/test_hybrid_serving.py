"""State-space layers on the serving path: a recurrent state a slot beside
the page pools.  Toy sizes of granite-4.0-h-micro's shape
(``tests/chipbench/data/tiny-granite.json``: two periods of mamba, mamba,
attention, mamba; inner width 2 x hidden, one group, kernel 4, a chunk of
16 that the prompts do not divide into), in float32 on the CPU, against
the plain reference of ``chipbench/reference/granitemoehybrid.py`` (the
token-by-token recurrence) on LOGITS, prefill and then 24 decoded tokens.

The five rules of the state (docs/serving.md) each have a case here that
fails when the rule is broken; the two controls at the end show that the
comparison sees a lost state on this seeding.

Tolerance: float32 on both sides, the reference at full matmul precision:
rows agree to rounding, 2e-5 of the largest reference logit.  A state that
is lost, stale, doubled or another slot's reads 1e-2 and more.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import granitemoehybrid as family
from chipbench.reference import granitemoehybrid as reference
from deepspeed_tpu.inference import serving
from deepspeed_tpu.inference.robustness import ServingUnsupported
from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.monitor import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(REPO, "tests", "chipbench", "data",
                       "tiny-granite.json")) as f:
    CFG = json.load(f)
TOL = 2e-5
DECODED = 24


@pytest.fixture(scope="module")
def model():
    return CausalTransformerLM(TransformerConfig(
        remat=False, **family.transformer_kwargs(CFG)))


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(7), jnp.float32)


def _engine(model, params, slots=3, **kwargs):
    kwargs.setdefault("serving", {"attention_backend": "jnp"})
    return ServingEngine(model, params, max_batch=slots, page_size=8,
                         max_seq=160, dtype=jnp.float32, **kwargs)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], n).tolist()


class Rows:
    """The logits rows the engine sampled from, by request
    (``_sample(req, row)`` wrapped); ``swap`` = (request, n, token): the
    request's n-th sample returns ``token`` whatever the row says."""

    def __init__(self, engine, swap=None, fail=None):
        self.rows, self.engine, self.original = {}, engine, engine._sample
        engine._sample = self.sample
        self.swap, self.fail = swap, fail

    def sample(self, req, row):
        seen = self.rows.setdefault(req.req_id, [])
        seen.append(np.array(row, np.float32))
        token = self.original(req, row)
        if self.fail == (req.req_id, len(seen)):
            raise RuntimeError("made-up sampler fault")
        if self.swap and self.swap[:2] == (req.req_id, len(seen)):
            return self.swap[2]
        return token

    def errors(self, params, done):
        """id -> largest row error over the largest reference logit,
        against the reference's full forward over prompt + output."""
        out = {}
        for rid, tokens in done.items():
            got = np.stack(self.rows[rid])
            ids = jnp.asarray(np.asarray(tokens, np.int32)[None, :-1])
            want = np.asarray(reference.logits(params, ids, CFG,
                                               last=len(got)))[0]
            out[rid] = float(np.abs(got - want).max() / np.abs(want).max())
        return out


def _run(engine, done=None, until=None):
    done = {} if done is None else done
    while engine.n_active or engine.queue:
        done.update(engine.step())
        if until is not None and until():
            break
    return done


# ---- rules 1 and 2: a prefill is told its slot and its real length ------
def test_a_prompt_in_one_bucket_with_padding(model, params):
    """(a) 29 tokens in a bucket of 32: the padding advances nothing, the
    tail is the last three real inputs; then 24 tokens through the
    state."""
    engine = _engine(model, params)
    rows = Rows(engine)
    engine.add_request("a", _prompt(1, 29), max_new_tokens=DECODED)
    done = _run(engine)
    assert len(rows.rows["a"]) == DECODED
    assert rows.errors(params, done)["a"] < TOL
    assert engine.leak_report() == {}


@pytest.mark.parametrize("length,pieces", [(34, [32, 16]), (45, [32, 16]),
                                           (67, [64, 16])])
def test_a_prompt_sent_as_pieces(model, params, monkeypatch, length, pieces):
    """(b) PR 44's pieces: the second starts from the state the first
    left in the slot (rule 1 at ``start > 0``), and of its 16 rows 2, 13
    or 3 are real: fewer than the conv's three in the first case, so the
    tail keeps a row of the piece before (rule 2)."""
    monkeypatch.setattr(serving, "PREFILL_PIECE_ROWS", 16)
    engine = _engine(model, params)
    assert engine.scheduler.prefill_pieces(length) == pieces
    rows = Rows(engine)
    engine.add_request("b", _prompt(2, length), max_new_tokens=DECODED)
    prefills = [d for d in engine._report["dispatches"]
                if d["phase"] == "prefill"]
    assert [d["tokens"] for d in prefills] == pieces
    assert [d["state_slots"] for d in prefills] == [1, 1]
    done = _run(engine)
    assert rows.errors(params, done)["b"] < TOL


def test_the_second_request_of_a_slot_starts_from_zero(model, params):
    """(c) one slot, two requests one after the other: at ``start == 0``
    the prefill begins from a zero state and a zero tail whatever the slot
    held (rule 1)."""
    engine = _engine(model, params, slots=1)
    rows = Rows(engine)
    engine.add_request("first", _prompt(3, 40), max_new_tokens=DECODED)
    engine.add_request("second", _prompt(4, 21), max_new_tokens=DECODED)
    done = _run(engine)
    errors = rows.errors(params, done)
    assert errors["first"] < TOL and errors["second"] < TOL


# ---- rule 3: a decode dispatch advances the slots it serves, no other ----
def test_idle_slots_and_a_slot_admitted_while_others_decode(model, params):
    """(d) three slots: one decodes alone (two idle), a second is admitted
    while it does, the third stays idle throughout and its rows of both
    pools keep their bits."""
    engine = _engine(model, params)
    rows = Rows(engine)
    engine.add_request("early", _prompt(5, 23), max_new_tokens=DECODED)
    for _ in range(6):
        engine.step()
    # a made-up state in the idle slot 2: no dispatch may touch it
    marked = jax.tree_util.tree_map(
        lambda pool: pool.at[:, 2].set(1.5), engine.caches.ssm)
    engine.caches = engine.caches._replace(ssm=marked)
    engine.add_request("late", _prompt(6, 50), max_new_tokens=DECODED)
    done = _run(engine)
    errors = rows.errors(params, done)
    assert errors["early"] < TOL and errors["late"] < TOL
    for pool in engine.caches.ssm:
        assert bool(jnp.all(pool[:, 2] == 1.5))
    decodes = [d for r in engine.step_reports() for d in r["dispatches"]
               if d["phase"] == "decode"]
    assert {d["state_slots"] for d in decodes} == {1, 2}
    assert all(d["state_bytes"] == 2 * d["state_slots"]
               * engine.state_slot_bytes for d in decodes)


def test_the_chunked_policy_is_rules_one_and_three_together(model, params):
    """A chunk, decode steps of other slots, the next chunk: the slot whose
    prompt is part-way through its chunks is given length 0 by the decode
    dispatches between them and keeps its state; each chunk starts from
    what the one before left."""
    engine = _engine(model, params, serving={
        "attention_backend": "jnp",
        "scheduler": {"policy": "chunked", "prefill_chunk_tokens": 16,
                      "max_prefill_chunks_per_step": 1}})
    rows = Rows(engine)
    engine.add_request("decoding", _prompt(8, 12), max_new_tokens=DECODED)
    for _ in range(3):
        engine.step()
    engine.add_request("in chunks", _prompt(9, 53), max_new_tokens=DECODED)
    done = _run(engine)
    phases = [d["phase"] for r in engine.step_reports()
              for d in r["dispatches"]]
    first = phases.index("prefill", 1)
    assert phases[first:first + 8] == ["prefill", "decode"] * 4
    errors = rows.errors(params, done)
    assert errors["decoding"] < TOL and errors["in chunks"] < TOL
    assert engine.leak_report() == {}


# ---- rule 4: a dropped row does not advance the state twice -------------
def _dropped_row(model, params, redo=True, backend="jnp"):
    engine = _engine(model, params, serving={"attention_backend": backend})
    if not redo:
        engine._redo_state = lambda slot, req: None
    # the fifth sample of the request is not the device's pick: the row
    # launched on that pick is dropped and the slot fed again
    rows = Rows(engine, swap=("e", 5, 3))
    engine.add_request("other", _prompt(10, 17), max_new_tokens=DECODED)
    engine.add_request("e", _prompt(11, 26), max_new_tokens=DECODED)
    done = _run(engine)
    assert done["e"][26 + 4] == 3
    redone = sum(d.get("redone", 0) for r in engine.step_reports()
                 for d in r["dispatches"])
    assert redone == 1
    return engine, rows.errors(params, done)


def test_a_dropped_and_redone_row_leaves_the_state_right(model, params):
    """(e) a sampler returns another token once: the slot's state is built
    again from zero by a prefill of prompt and output so far, counted."""
    engine, errors = _dropped_row(model, params)
    assert errors["e"] < TOL and errors["other"] < TOL
    assert engine.stats["state_redone"] == 1
    assert sum(r["state_redone"] for r in engine.step_reports()) == 1
    assert engine.health()["state"]["redone"] == 1
    # the rebuild's rows are no new prompt tokens
    assert sum(r["prompt_tokens"] for r in engine.step_reports()) == 17 + 26
    assert engine.leak_report() == {}


def test_a_dropped_row_under_the_state_kernel(model, params):
    """(e) again with the decode step's recurrence in the
    ``ssm_decode_update`` kernel (its interpreter): the dropped row
    advanced the state in place in the pool, and the rebuild from zero
    puts it right all the same."""
    engine, errors = _dropped_row(model, params,
                                  backend="pallas-interpret")
    assert engine.state_impl == "pallas"
    assert errors["e"] < TOL and errors["other"] < TOL
    assert engine.stats["state_redone"] == 1


def test_without_the_redo_the_dropped_row_shows(model, params):
    """The same serve with the rebuild taken out: the state advanced
    twice, and every later row of that request fails the tolerance a
    hundred times over (the other request's rows do not move)."""
    _, errors = _dropped_row(model, params, redo=False)
    assert errors["e"] > 100 * TOL and errors["other"] < TOL


def test_an_eviction_and_a_deadline_in_flight_then_a_new_request(model,
                                                                 params):
    """(f) a sampler fault evicts one request and a deadline another while
    their rows are in flight; the requests that take their slots start
    from zero and agree with the reference."""
    now = [0.0]
    engine = _engine(model, params, slots=2, clock=lambda: now[0])
    rows = Rows(engine, fail=("faulty", 4))
    engine.add_request("faulty", _prompt(12, 30), max_new_tokens=DECODED)
    engine.add_request("late", _prompt(13, 19), max_new_tokens=DECODED,
                       deadline_s=5.0)
    for _ in range(8):
        engine.step()
    assert engine.pop_terminated()["faulty"].status == "evicted"
    engine.add_request("after fault", _prompt(14, 33),
                       max_new_tokens=DECODED)
    engine.step()
    now[0] = 10.0
    engine.step()
    assert engine.pop_terminated()["late"].status == "deadline"
    engine.add_request("after deadline", _prompt(15, 11),
                       max_new_tokens=DECODED)
    done = _run(engine)
    errors = rows.errors(params, done)
    assert set(done) == {"after fault", "after deadline"}
    assert max(errors.values()) < TOL
    assert engine.leak_report() == {}


# ---- rule 5: what cannot carry state is refused by name -----------------
@pytest.mark.parametrize("kwargs,feature", [
    ({"serving": {"prefix_cache": {"enabled": True}}}, "prefix_cache"),
    ({"serving": {"scheduler": {"policy": "chunked", "speculative": {
        "enabled": True, "num_draft_tokens": 2}}}},
     "scheduler.speculative"),
    ({"decode_chunk": 4}, "decode_chunk"),
    ({"tp_size": 2}, "tp_size"),
    ({"ep_size": 2}, "ep_size"),
])
def test_what_cannot_carry_state_is_refused_by_name(model, params, kwargs,
                                                    feature):
    with pytest.raises(ServingUnsupported) as refused:
        _engine(model, params, **kwargs)
    assert feature in refused.value.feature
    assert "state-space" in refused.value.feature


@pytest.mark.parametrize("call", ["prefill_only", "export_pages",
                                  "import_pages", "import_request"])
def test_migration_is_refused_when_called(model, params, call):
    engine = _engine(model, params)
    with pytest.raises(ServingUnsupported) as refused:
        if call == "prefill_only":
            engine.add_request("x", [1, 2, 3], prefill_only=True)
        elif call == "export_pages":
            engine.export_pages([1])
        elif call == "import_pages":
            engine.import_pages(None, [1])
        else:
            engine.import_request(None)
    assert "state-space" in refused.value.feature


# ---- the account of the state ------------------------------------------
def test_the_state_is_counted_where_the_docs_say(model, params):
    engine = _engine(model, params)
    c = model.config
    per_layer = c.ssm_inner * c.ssm_state * 4 \
        + (c.ssm_conv - 1) * c.ssm_conv_dim * 4      # a float32 engine
    assert engine.state_slot_bytes == sum(c.ssm_pattern) * per_layer
    state = engine.health()["state"]
    assert state == {"slot_bytes": engine.state_slot_bytes,
                     "bytes": 3 * engine.state_slot_bytes, "redone": 0}
    pools = engine.caches
    assert pools.ssm.state.shape == (6, 3, c.ssm_heads, c.ssm_head_dim,
                                     c.ssm_state)
    assert pools.ssm.state.dtype == jnp.float32
    assert pools.ssm.conv.shape == (6, 3, 3 * c.ssm_conv_dim)
    # the attention layers alone have pages: heads of 32 share rows by four
    assert pools.full.k_pages.shape == (2, 61, 1, 8, 128)
    assert engine.kv_page_bytes == 2 * 2 * 8 * 128 * 4
    # a pool of another size is a leak
    engine.caches = pools._replace(ssm=pools.ssm._replace(
        conv=pools.ssm.conv[:, :2]))
    assert "state_slot_mismatch" in engine.leak_report()


def test_counts_scopes_and_the_event_are_the_frozen_ones(model, params,
                                                         tmp_path):
    path = os.path.join(REPO, "scripts", "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("checker", path)
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    assert tuple(checker.STATE_COUNTS) == tuple(serving.STATE_COUNTS)
    assert tuple(checker.DISPATCH_IMPLS) == tuple(serving.DISPATCH_IMPLS)
    assert checker.DISPATCH_IMPLS[-1] == "state"
    assert "serve/state" in checker.SERVE_EVENTS
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig
    tel = Telemetry().configure(TelemetryConfig({
        "enabled": True, "output_path": str(tmp_path),
        "job_name": "hybrid"}), rank=0)
    engine = _engine(model, params, telemetry=tel)
    engine.generate([list(range(20))], max_new_tokens=3)
    tel.close()
    with open(os.path.join(str(tmp_path), "hybrid", "events.jsonl")) as f:
        events = [json.loads(line) for line in f]
    (state,) = [e for e in events if e.get("name") == "serve/state"]
    assert state["attrs"] == {
        "layers": 6, "slot_bytes": engine.state_slot_bytes,
        "dtype": "float32", "conv_dtype": "float32",
        "redo": "prefill_from_zero"}
    steps = [e for e in events if e.get("name") == "serve/step"]
    assert steps and all(set(serving.STATE_COUNTS) <= set(e["attrs"])
                         for e in steps)
    want = {"ssm_proj", "ssm_conv", "ssm_scan", "attn_full"}
    assert want <= set(telemetry.SERVE_SCOPES)
    assert want <= set(telemetry.op_scopes("serve/step_fn").values())
    assert want <= set(telemetry.op_scopes(
        "serve/prefill_fn", arg_shapes={1: (1, 32)}).values())
    assert telemetry.phase_of("jit(f)/while/body/ssm_scan/mul") == "ssm_scan"


def test_the_scope_tables_outlive_the_engine(model, params):
    """The benchmark asks for the serving programs' scopes after the run
    that made the engine handed it back: the engine (a cycle of
    references) is gone once the collector has run, the tables are not
    (``telemetry.register_compiled(keep=True)``: the programs' closures
    hold the model and no engine), until a newer engine's take their
    place."""
    import gc
    import weakref
    engine = _engine(model, params)
    engine.generate([list(range(20))], max_new_tokens=3)
    gone = weakref.ref(engine)
    del engine
    gc.collect()
    assert gone() is None
    assert "ssm_scan" in set(telemetry.op_scopes("serve/step_fn").values())
    assert "ssm_conv" in set(telemetry.op_scopes(
        "serve/prefill_fn", arg_shapes={1: (1, 32)}).values())
    assert telemetry.op_scopes("serve/prefill_fn",
                               arg_shapes={1: (1, 4096)}) == {}


def test_the_kernel_reads_packed_heads_and_agrees(model, params):
    """The same serve with the ragged kernel through its interpreter: the
    attention layers' pools hold four heads of 32 a row of 128."""
    engine = _engine(model, params, serving={
        "attention_backend": "pallas-interpret"})
    rows = Rows(engine)
    engine.add_request("k", _prompt(16, 29), max_new_tokens=6)
    engine.add_request("l", _prompt(17, 9), max_new_tokens=6)
    done = _run(engine)
    assert max(rows.errors(params, done).values()) < TOL
    decode = engine.last_step["dispatches"][-1]
    assert decode["kernel_grid"] > 0 and decode["kv_write"] == "pallas"


def _serve_three(model, params, backend):
    """Three slots: two requests from the start, a third admitted while
    they decode, one slot idle at the end; the rows sampled from, the
    tokens and every decode dispatch's record."""
    engine = _engine(model, params, serving={"attention_backend": backend})
    rows = Rows(engine)
    engine.add_request("m", _prompt(19, 27), max_new_tokens=DECODED)
    engine.add_request("n", _prompt(20, 13), max_new_tokens=10)
    for _ in range(4):
        engine.step()
    engine.add_request("o", _prompt(21, 41), max_new_tokens=DECODED)
    done = _run(engine)
    decodes = [d for r in engine.step_reports() for d in r["dispatches"]
               if d["phase"] == "decode"]
    return engine, rows, done, decodes


def test_the_state_kernel_serves_what_the_jnp_backend_serves(model, params):
    """The decode step's recurrence through ``ssm_decode_update`` (its
    interpreter) against the jnp slice, ``ssm_step`` and masked write: the
    same tokens, every sampled row's logits to the file's tolerance, and
    each decode dispatch's record says which of the two it compiled to."""
    engine, rows, done, decodes = _serve_three(model, params,
                                               "pallas-interpret")
    oracle, want, want_done, want_decodes = _serve_three(model, params, "jnp")
    assert engine.state_impl == "pallas" and oracle.state_impl == "jnp"
    assert done == want_done
    for rid in done:
        got, ref = np.stack(rows.rows[rid]), np.stack(want.rows[rid])
        assert np.abs(got - ref).max() <= TOL * np.abs(ref).max(), rid
    assert max(rows.errors(params, done).values()) < TOL
    assert decodes and {d["state"] for d in decodes} == {"pallas"}
    assert want_decodes and {d["state"] for d in want_decodes} == {"jnp"}
    # a decode step served one, two and three of the three slots
    assert {d["state_slots"] for d in decodes} == {1, 2, 3}
    prefills = [d for r in engine.step_reports() for d in r["dispatches"]
                if d["phase"] == "prefill"]
    assert prefills and all("state" not in d for d in prefills)


# ---- the controls: the comparison can see a lost state ------------------
@pytest.mark.parametrize("lost", ["state", "conv"])
def test_a_state_zeroed_after_the_prefill_fails_by_a_wide_factor(
        model, params, lost):
    """The same serve with the recurrent state, or the convolution's
    tail, zeroed after the prefill.  At these toy sizes (a state of 32 a
    head element, 29 tokens of it) the rows read 0.0096 of the largest
    logit without the state and 0.17 without the tail: 480 and 8,700
    times this file's tolerance.  (Against the harness's 0.04 the first
    would pass at TOY size; PERF.md section 4 has the chip's reading at
    the published widths, where a state of 128 holds 64-1,024 tokens.)"""
    engine = _engine(model, params)
    rows = Rows(engine)
    engine.add_request("z", _prompt(18, 29), max_new_tokens=DECODED)
    ssm = engine.caches.ssm
    engine.caches = engine.caches._replace(ssm=ssm._replace(
        **{lost: jnp.zeros_like(getattr(ssm, lost))}))
    done = _run(engine)
    assert rows.errors(params, done)["z"] > 100 * TOL
