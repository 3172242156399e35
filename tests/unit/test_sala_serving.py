"""Linear-attention layers and block-sparse attention on the serving path:
a matrix state a slot beside the pages, compressed keys beside the keys.
Toy sizes of MiniCPM-SALA's held slice
(``tests/chipbench/data/tiny-sala.json``: a sparse layer, two linear
layers, a sparse layer; block 4, kernel 2 / 1, top-6, window 8,
``dense_len`` 32; a second shape with kernel 4 / 2, so that only every
second token completes a compressed key), in float32 on the CPU, against
the plain reference of ``chipbench/reference/minicpm_sala.py`` (the
token-by-token recurrence, every query's own block set) on LOGITS, prefill
and then the decoded tokens.

Tolerance: float32 on both sides, the reference at full matmul precision:
rows agree to rounding, 2e-5 of the largest reference logit (logits of
this family are small: ``dim_model_base / hidden_size``, so the largest
is about 1).  A state that is lost or a selection that is not the
reference's reads 1e-3 and more (the two controls at the end).
"""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import minicpm_sala as family
from chipbench.reference import minicpm_sala as reference
from deepspeed_tpu.inference.robustness import ServingUnsupported
from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)
from deepspeed_tpu.ops import block_sparse_attention as bsa

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(REPO, "tests", "chipbench", "data",
                       "tiny-sala.json")) as f:
    CFG = json.load(f)
# only every second token completes a compressed key, of four keys
WIDE = copy.deepcopy(CFG)
WIDE["sparse_config"].update(kernel_size=4, kernel_stride=2)
CFGS = {"stride1": CFG, "stride2": WIDE}
TOL = 2e-5
DENSE_LEN = CFG["sparse_config"]["dense_len"]


def _served(cfg):
    model = CausalTransformerLM(TransformerConfig(
        remat=False, **family.transformer_kwargs(cfg)))
    return cfg, model, model.init(jax.random.key(7), jnp.float32)


@pytest.fixture(scope="module")
def served():
    return _served(CFG)


@pytest.fixture(scope="module", params=sorted(CFGS))
def served_both(request):
    """Both shapes of the compressed keys: the serve of one prompt and its
    decoded tokens is what the stride changes."""
    return _served(CFGS[request.param])


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

    def __init__(self, engine, swap=None):
        self.rows, self.engine, self.original = {}, engine, engine._sample
        engine._sample = self.sample
        self.swap, self.prompts = swap, {}

    def add(self, rid, prompt, new):
        self.prompts[rid] = len(prompt)
        self.engine.add_request(rid, prompt, max_new_tokens=new)

    def sample(self, req, row):
        seen = self.rows.setdefault(req.req_id, [])
        seen.append(np.array(row, np.float32))
        token = self.original(req, row)
        if self.swap and self.swap[:2] == (req.req_id, len(seen)):
            return self.swap[2]
        return token

    def errors(self, cfg, params, done):
        """id -> largest row error over the largest reference logit,
        against the reference's full forward over prompt + output, each
        position at the context it was dispatched with."""
        out = {}
        for rid, tokens in done.items():
            got = np.stack(self.rows[rid])
            ids = np.asarray(tokens, np.int32)[None, :-1]
            n = self.prompts[rid]
            contexts = np.maximum(np.arange(ids.shape[1]) + 1, n)
            want = np.asarray(reference.logits(
                params, jnp.asarray(ids), cfg, last=len(got),
                contexts=contexts))[0]
            out[rid] = float(np.abs(got - want).max() / np.abs(want).max())
        return out


def _run(engine, done=None):
    done = {} if done is None else done
    while engine.n_active or engine.queue:
        done.update(engine.step())
    return done


# ---- (b) prefill, then decode through pages, compressed keys and state ---
@pytest.mark.parametrize("length,new,what", [
    (13, 10, "all of it under dense_len"),
    (27, 12, "over top-k blocks; the context crosses dense_len at 32"),
    (75, 24, "over dense_len, 19 blocks = more than twice top-k"),
    (32, 6, "a prompt of exactly dense_len"),
])
def test_prefill_then_decode_equals_the_references_forward(served_both,
                                                           length, new,
                                                           what):
    cfg, model, params = served_both
    engine = _engine(model, params)
    rows = Rows(engine)
    rows.add("a", _prompt(length, length), new)
    done = _run(engine)
    assert len(rows.rows["a"]) == new
    assert rows.errors(cfg, params, done)["a"] < TOL, what
    assert engine.leak_report() == {}
    decodes = [d for r in engine.step_reports() for d in r["dispatches"]
               if d["phase"] == "decode"]
    # the decode dispatches count what they attended, summed over the two
    # sparse layers: everything under dense_len, top-k blocks over it
    for d in decodes:
        (context,) = d["contexts"]
        assert d["context_keys"] == 2 * context
        blocks = -(-context // 4)
        kept = context if context < DENSE_LEN or blocks <= 6 \
            else 5 * 4 + (context - 1) % 4 + 1
        assert d["selected"] == 2 * kept, (context, d)
        assert d["state_slots"] == 1 and d["state"] == "jnp"


# ---- (d) slots of different lengths, a slot reused, the redo -------------
def test_two_slots_of_different_lengths_and_a_slot_reused(served):
    """Two requests of unlike lengths decode in one batch (one sparse, one
    dense); a third takes the first freed slot and starts from a zero
    state, whatever the slot held."""
    cfg, model, params = served
    engine = _engine(model, params, slots=2)
    rows = Rows(engine)
    rows.add("long", _prompt(1, 70), 8)
    rows.add("short", _prompt(2, 11), 14)
    rows.add("next", _prompt(3, 45), 9)        # waits for a slot
    done = _run(engine)
    errors = rows.errors(cfg, params, done)
    assert max(errors.values()) < TOL, errors
    assert engine.leak_report() == {}
    assert engine.health()["state"]["slot_bytes"] == 2 * 4 * 16 * 16 * 4


def test_an_idle_slots_state_keeps_its_bits(served):
    cfg, model, params = served
    engine = _engine(model, params)
    rows = Rows(engine)
    rows.add("only", _prompt(5, 40), 6)
    marked = engine.caches.ssm.state.at[:, 2].set(1.5)
    engine.caches = engine.caches._replace(
        ssm=type(engine.caches.ssm)(marked))
    done = _run(engine)
    assert rows.errors(cfg, params, done)["only"] < TOL
    assert bool(jnp.all(engine.caches.ssm.state[:, 2] == 1.5))


def _dropped_row(served, redo=True):
    cfg, model, params = served
    engine = _engine(model, params)
    if not redo:
        engine._redo_state = lambda slot, req: None
    rows = Rows(engine, swap=("e", 5, 3))
    rows.add("other", _prompt(10, 17), 12)
    rows.add("e", _prompt(11, 50), 12)
    done = _run(engine)
    assert done["e"][50 + 4] == 3
    return engine, rows.errors(cfg, params, done)


def test_a_dropped_and_redone_row_leaves_the_state_right(served):
    """A sampler returns another token once: the row launched on the
    device's pick is dropped, and the slot's state is built again from
    zero by a prefill of prompt and output so far (``_redo_state``)."""
    engine, errors = _dropped_row(served)
    assert errors["e"] < TOL and errors["other"] < TOL
    assert engine.stats["state_redone"] == 1
    assert engine.leak_report() == {}


def test_without_the_redo_the_dropped_row_shows(served):
    """The control: the same serve with the rebuild taken out; the state
    advanced twice, and the request's later rows fail the tolerance."""
    _, errors = _dropped_row(served, redo=False)
    assert errors["e"] > 100 * TOL and errors["other"] < TOL


def test_a_zeroed_state_shows(served):
    """The control of the state: zeroed after the prefill, every decoded
    row fails."""
    cfg, model, params = served
    engine = _engine(model, params)
    rows = Rows(engine)
    rows.add("z", _prompt(12, 41), 8)
    engine.caches = engine.caches._replace(ssm=jax.tree_util.tree_map(
        jnp.zeros_like, engine.caches.ssm))
    done = _run(engine)
    assert rows.errors(cfg, params, done)["z"] > 100 * TOL


def test_the_forced_blocks_alone_show(served, monkeypatch):
    """The control of the selection: the program's selection replaced by
    the forced blocks alone (every other block's score the least), the
    context over dense_len and over twice top-k blocks: the rows fail."""
    cfg, model, params = served
    original = bsa.select_blocks

    def forced_only(*args, **kwargs):
        score, valid = original(*args, **kwargs)
        return jnp.where(jnp.isinf(score) & (score > 0), score,
                         -jnp.inf), valid & jnp.isinf(score) & (score > 0)

    monkeypatch.setattr(bsa, "select_blocks", forced_only)
    engine = _engine(model, params)
    rows = Rows(engine)
    rows.add("f", _prompt(13, 75), 8)
    done = _run(engine)
    assert rows.errors(cfg, params, done)["f"] > 100 * TOL


# ---- (e) the refusals, by name ------------------------------------------
@pytest.mark.parametrize("kwargs,named", [
    ({"serving": {"prefix_cache": {"enabled": True}}},
     "prefix_cache with linear-attention layers"),
    ({"serving": {"scheduler": {"policy": "chunked", "speculative": {
        "enabled": True, "num_draft_tokens": 2}}}},
     "scheduler.speculative with linear-attention layers"),
    ({"decode_chunk": 4}, "decode_chunk > 1 with linear-attention layers"),
    ({"tp_size": 2}, "tp_size / ep_size > 1 with linear-attention layers"),
    ({"serving": {"scheduler": {"policy": "chunked"}}},
     "scheduler.policy 'chunked' with block-sparse attention"),
])
def test_what_is_not_built_is_refused_by_name(served, kwargs, named):
    _, model, params = served
    with pytest.raises(ServingUnsupported) as refused:
        _engine(model, params, **kwargs)
    assert named in str(refused.value)


@pytest.mark.parametrize("call", ["export_pages", "import_pages",
                                  "import_request", "prefill_only"])
def test_migration_is_refused_by_name(served, call):
    _, model, params = served
    engine = _engine(model, params)
    with pytest.raises(ServingUnsupported) as refused:
        if call == "prefill_only":
            engine.add_request("m", _prompt(1, 9), max_new_tokens=2,
                               prefill_only=True)
        elif call == "export_pages":
            engine.export_pages("m")
        elif call == "import_pages":
            engine.import_pages("m", None)
        else:
            engine.import_request(None)
    assert "linear-attention layers" in str(refused.value)


def test_a_model_of_sparse_layers_alone_is_refused_the_same_things():
    """No linear layer, so no state: the selection's own refusals."""
    cfg = copy.deepcopy(CFG)
    cfg["mixer_types"] = ["minicpm4"] * 4
    model = CausalTransformerLM(TransformerConfig(
        remat=False, **family.transformer_kwargs(cfg)))
    params = model.init(jax.random.key(1), jnp.float32)
    with pytest.raises(ServingUnsupported) as refused:
        _engine(model, params, serving={"prefix_cache": {"enabled": True}})
    assert "prefix_cache with block-sparse attention" in str(refused.value)
    engine = _engine(model, params)
    rows = Rows(engine)
    rows.add("s", _prompt(4, 60), 6)
    done = _run(engine)
    assert rows.errors(cfg, params, done)["s"] < TOL
    with pytest.raises(ServingUnsupported) as refused:
        engine.export_pages("s")
    assert "block-sparse attention" in str(refused.value)


# ---- (f) the held slice keeps the published depth's residual scale -------
def test_the_held_slice_keeps_the_published_depths_residual_scale():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "minicpm-sala.json")) as f:
        cfg = json.load(f)
    kwargs = family.transformer_kwargs(cfg)
    assert kwargs["n_layers"] == 8 and kwargs["layer_period"] == 8
    assert kwargs["residual_scale"] == 1.4 / np.sqrt(32)
    assert kwargs["final_logit_scale"] == 1 / 16
    assert kwargs["lin_pattern"] == (False,) + (True,) * 6 + (False,)
    assert kwargs["rope_pattern"] == kwargs["lin_pattern"]


def test_the_scopes_the_event_and_the_counters_are_the_frozen_ones(
        served, tmp_path):
    import importlib.util
    from deepspeed_tpu.inference import serving
    from deepspeed_tpu.monitor import telemetry
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.runtime.config import TelemetryConfig
    path = os.path.join(REPO, "scripts", "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("checker", path)
    checker = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(checker)
    assert tuple(checker.SERVE_SCOPES) == tuple(telemetry.SERVE_SCOPES)
    assert set(telemetry.SERVE_SCOPES) >= {"lin_attn", "block_select",
                                           "ckey_write", "sparse_attn"}
    _, model, params = served
    tel = Telemetry().configure(TelemetryConfig({
        "enabled": True, "output_path": str(tmp_path),
        "job_name": "sala"}), rank=0)
    engine = _engine(model, params, telemetry=tel)
    engine.generate([list(range(40))], max_new_tokens=3)
    tel.close()
    with open(os.path.join(str(tmp_path), "sala", "events.jsonl")) as f:
        events = [json.loads(line) for line in f]
    (state,) = [e for e in events if e.get("name") == "serve/state"]
    assert state["attrs"] == {
        "layers": 2, "slot_bytes": engine.state_slot_bytes,
        "dtype": "float32", "kind": "linear", "redo": "prefill_from_zero"}
    steps = [e["attrs"] for e in events if e.get("name") == "serve/step"]
    assert steps and all(set(serving.STATE_COUNTS) <= set(a) for a in steps)
    # the device's counters reach a dispatch's record at its fetch
    decodes = [d for r in engine.step_reports() for d in r["dispatches"]
               if d["phase"] == "decode" and "selected" in d]
    assert decodes and all(0 < d["selected"] <= d["context_keys"]
                           for d in decodes)
    # both programs name the four scopes
    for site, phase in (("serve/prefill_fn", "prefill"),
                        ("serve/step_fn", "decode")):
        scopes = set(telemetry.op_scopes(site).values())
        assert {"lin_attn", "block_select", "ckey_write",
                "sparse_attn"} <= scopes, (phase, scopes)
