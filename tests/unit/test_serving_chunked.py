"""Chunked on-device decode (``ServingEngine(decode_chunk=K)``).

K decode iterations ride one device program (``lax.scan`` over
``apply_with_paged_cache`` + on-device sampling), cutting host↔device
round trips — and passes through the host's step loop — per token by K.
Semantics contract: greedy
chunked decode must be token-exact vs the per-token engine, including
mid-chunk EOS, budgets that are not multiples of K, and continuous
batching (overrun tokens land on the reserved scratch page and are
discarded on the host — vLLM-style multi-step scheduling).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.serving import ServingEngine
from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                              TransformerConfig)


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _dense_greedy(model, params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        logits = model.apply(params, jnp.asarray(seq)[None, :], train=False)
        seq.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return seq


@pytest.mark.parametrize("chunk,max_new", [(4, 6), (4, 8), (8, 5), (3, 7)])
def test_chunked_matches_dense_greedy(tiny, chunk, max_new):
    """Budgets above, below, and not multiples of K — every output must be
    token-exact vs the dense oracle (truncation of chunk overrun)."""
    cfg, model, params = tiny
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).tolist()
               for n in (5, 11, 3, 17)]
    eng = ServingEngine(model, params, max_batch=4, page_size=8,
                        max_seq=64, dtype=jnp.float32, decode_chunk=chunk)
    outs = eng.generate(prompts, max_new_tokens=max_new)
    for p, got in zip(prompts, outs):
        assert got == _dense_greedy(model, params, p, max_new), (chunk, p)


def test_chunked_continuous_batching(tiny):
    """8 requests through 2 slots with K=4: slots free mid-chunk-sequence
    and refill; admission happens at chunk boundaries; outputs exact."""
    cfg, model, params = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).tolist()
               for n in (4, 9, 6, 12, 5, 7, 10, 3)]
    eng = ServingEngine(model, params, max_batch=2, page_size=8,
                        max_seq=64, dtype=jnp.float32, decode_chunk=4)
    outs = eng.generate(prompts, max_new_tokens=5)
    assert eng.n_active == 0 and not eng.queue
    for p, got in zip(prompts, outs):
        assert got == _dense_greedy(model, params, p, 5), p


def test_chunked_eos_mid_chunk(tiny):
    """EOS lands mid-chunk: output truncates exactly there; every page
    returns to the pool (the overrun tokens never leak allocations)."""
    cfg, model, params = tiny
    rng = np.random.default_rng(3)
    p = rng.integers(0, cfg.vocab_size, (5,)).tolist()
    ref = _dense_greedy(model, params, p, 20)
    eos = ref[len(p) + 2]          # 3rd generated token
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=64, dtype=jnp.float32, eos_token_id=eos,
                        decode_chunk=8)
    eng.add_request("x", p, max_new_tokens=20)
    done = {}
    for _ in range(10):
        done.update(eng.step())
        if "x" in done:
            break
    got = done["x"]
    assert got[-1] == eos and len(got) == len(p) + 3
    assert got == ref[:len(p) + 3]
    assert len(eng.alloc.free) == eng.alloc.num_pages - 1


def test_chunked_temperature_seed_contract(tiny):
    """Temperature sampling on device keys on (req.seed, tokens generated
    so far): tokens are in-vocab, the stream reproduces for the same seed
    REGARDLESS of slot assignment / co-resident requests, and differs for
    a different seed."""
    cfg, model, params = tiny
    rng = np.random.default_rng(4)
    p = rng.integers(0, cfg.vocab_size, (6,)).tolist()
    other = rng.integers(0, cfg.vocab_size, (4,)).tolist()

    def run(seed, crowd):
        eng = ServingEngine(model, params, max_batch=2, page_size=8,
                            max_seq=64, dtype=jnp.float32, decode_chunk=4)
        if crowd:      # occupy slot 0 so "x" lands in a different slot
            eng.add_request("crowd", other, max_new_tokens=3,
                            temperature=0.5, seed=99)
        eng.add_request("x", p, max_new_tokens=9, temperature=0.8,
                        seed=seed)
        done = {}
        for _ in range(20):
            done.update(eng.step())
            if "x" in done and (not crowd or "crowd" in done):
                break
        return done["x"]

    a = run(7, crowd=False)
    b = run(7, crowd=True)        # different slot, different co-batch
    c = run(8, crowd=False)
    assert a == b                 # seed contract survives slot assignment
    assert a != c
    assert len(a) == len(p) + 9
    assert all(0 <= t < cfg.vocab_size for t in a[len(p):])


def test_topk_topp_sampling_support(tiny):
    """top-k / top-p on both sampler paths: every sampled token must lie
    in the allowed support computed offline from the dense logits, for
    the per-token host sampler AND the on-device chunked sampler."""
    cfg, model, params = tiny
    rng = np.random.default_rng(6)
    p = rng.integers(0, cfg.vocab_size, (5,)).tolist()
    p32 = jax.tree_util.tree_map(jnp.asarray, params)

    def allowed(seq, top_k, top_p, temperature):
        logits = np.asarray(model.apply(
            p32, jnp.asarray(seq)[None, :], train=False)[0, -1],
            dtype=np.float64) / temperature
        if top_k:
            thresh = np.sort(logits)[-top_k]
            logits = np.where(logits < thresh, -np.inf, logits)
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        order = np.argsort(-probs)
        cs = np.cumsum(probs[order])
        cut = int(np.searchsorted(cs, top_p) + 1)
        return set(int(t) for t in order[:cut])

    for chunk in (1, 4):
        eng = ServingEngine(model, params, max_batch=1, page_size=8,
                            max_seq=64, dtype=jnp.float32,
                            decode_chunk=chunk)
        eng.add_request("x", p, max_new_tokens=8, temperature=1.5,
                        seed=3, top_k=3, top_p=0.9)
        done = {}
        for _ in range(12):
            done.update(eng.step())
            if "x" in done:
                break
        got = done["x"]
        assert len(got) == len(p) + 8
        seq = list(p)
        for tok in got[len(p):]:
            assert tok in allowed(seq, 3, 0.9, 1.5), (chunk, tok)
            seq.append(tok)


def test_topk_one_equals_greedy_chunked(tiny):
    """top_k=1 with any temperature must reproduce greedy exactly on the
    chunked device sampler."""
    cfg, model, params = tiny
    rng = np.random.default_rng(8)
    p = rng.integers(0, cfg.vocab_size, (6,)).tolist()
    eng = ServingEngine(model, params, max_batch=1, page_size=8,
                        max_seq=64, dtype=jnp.float32, decode_chunk=4)
    greedy = eng.generate([p], max_new_tokens=6)[0]
    eng2 = ServingEngine(model, params, max_batch=1, page_size=8,
                         max_seq=64, dtype=jnp.float32, decode_chunk=4)
    topk1 = eng2.generate([p], max_new_tokens=6, temperature=0.7,
                          top_k=1)[0]
    assert topk1 == greedy
