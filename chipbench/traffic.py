"""The one traffic generator: every mix is a data file under
``chipbench/traffic/`` that this module turns into work.

Steadiness rule (PERF.md §2): a seed never changes WHAT is offered, only
the order.  Lengths are the midpoint quantiles of the stated distribution
(a fixed grid), prompt and answer lengths are paired by a permutation fixed
in the mix, and ``--seed`` only shuffles the order of the pairs; arrival
gaps are the quantile grid of the exponential, scaled to the exact rate and
shuffled.  So every seed offers the same multiset of requests at the same
total rate, in another order and still in bursts.
"""

import json
import math
import os
from statistics import NormalDist

import numpy as np

KINDS = ("pretrain", "open_loop", "closed_loop")
HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"traffic {name!r}: kind {mix.get('kind')!r} is "
                         f"not one of {KINDS}")
    return mix


def rng_for(seed, *stream):
    """A generator for one stream of one seed; ``seed`` is any whole
    number (the driver's are above 2**31)."""
    return np.random.default_rng([int(seed), *(int(s) for s in stream)])


def _quantile(dist, q):
    lo, hi = float(dist["min"]), float(dist["max"])
    kind = dist["dist"]
    if kind == "uniform":
        return lo + q * (hi - lo)
    if kind == "lognormal":
        # truncated to [min, max]: the grid covers the mass between them
        mu, sigma = math.log(dist["median"]), float(dist["sigma"])
        normal = NormalDist(mu, sigma)
        f_lo, f_hi = normal.cdf(math.log(lo)), normal.cdf(math.log(hi))
        return math.exp(normal.inv_cdf(f_lo + q * (f_hi - f_lo)))
    if kind == "fixed":
        return float(dist["value"])
    raise ValueError(f"unknown distribution {kind!r}")


def quantile_grid(dist, n):
    """``n`` whole-number lengths: the midpoint quantiles of ``dist``."""
    if dist["dist"] == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    values = [_quantile(dist, (i + 0.5) / n) for i in range(n)]
    return np.clip(np.rint(values), dist["min"], dist["max"]).astype(np.int64)


def request_cycle(mix, n, seed, cycle):
    """``n`` (prompt_len, answer_len) pairs: the same multiset for every
    seed and cycle, in an order drawn from them."""
    prompts = quantile_grid(mix["prompt_tokens"], n)
    answers = quantile_grid(mix["output_tokens"], n)
    pairing = rng_for(mix.get("pairing_seed", 0), n).permutation(n)
    order = rng_for(seed, 1, cycle).permutation(n)
    return [(int(prompts[i]), int(answers[pairing[i]])) for i in order]


def arrival_gaps(span_s, n, seed, cycle):
    """``n`` gaps in seconds that sum to exactly ``span_s``: the quantile
    grid of the exponential, shuffled."""
    grid = -np.log1p(-(np.arange(n) + 0.5) / n)
    grid *= span_s / grid.sum()
    return grid[rng_for(seed, 2, cycle).permutation(n)]


def cycle_sizes(mix, seconds):
    """(requests, seconds) of each cycle; the last repeats.  Open loop: the
    ramp is cycle 0 and the window exactly cycle 1, so every seed's window
    holds the same requests over the same span.  Closed loop: the mix's
    small fixed cycle, so a window holds several whole cycles whatever the
    speed."""
    if mix["kind"] == "open_loop":
        return [(max(1, round(mix["rate_rps"] * span)), float(span))
                for span in (mix["ramp_s"], seconds)]
    return [(int(mix["cycle"]), None)]


class RequestStream:
    """Endless stream of requests for the two serving kinds.  Request
    ``k`` is ``(k, cycle, prompt token ids, answer length, gap before
    it)``; the gap is None in a closed loop."""

    def __init__(self, mix, vocab_size, seed, seconds):
        self.mix, self.vocab, self.seed = mix, int(vocab_size), seed
        self.sizes = cycle_sizes(mix, seconds)
        self.cycle = 0
        self.index = 0
        self._pending = []

    def _refill(self):
        n, span = self.sizes[min(self.cycle, len(self.sizes) - 1)]
        pairs = request_cycle(self.mix, n, self.seed, self.cycle)
        gaps = ([None] * n if span is None
                else arrival_gaps(span, n, self.seed, self.cycle))
        self._pending = [(self.cycle, pair, gap)
                         for pair, gap in zip(pairs, gaps)]
        self.cycle += 1

    def __iter__(self):
        return self

    def __next__(self):
        if not self._pending:
            self._refill()
        cycle, (prompt_len, answer_len), gap = self._pending.pop(0)
        k = self.index
        self.index += 1
        ids = rng_for(self.seed, 3, k).integers(
            0, self.vocab, prompt_len, dtype=np.int32)
        return (k, cycle, ids, answer_len,
                None if gap is None else float(gap))


def pretrain_batch(vocab_size, seed, step, shape):
    """Token ids of optimizer step ``step``, made on the host as an input
    pipeline would; every step and seed has the same shape."""
    return rng_for(seed, 4, step).integers(0, vocab_size, shape,
                                           dtype=np.int32)
