"""The quantile-grid generators: a seed changes the order of the work,
never the work."""

import numpy as np
import pytest

from chipbench import traffic

SEEDS = (1, 2 ** 31 + 12345)     # the driver's seeds pass 32 signed bits


@pytest.mark.parametrize("mix_name,n", [("chat-open", 48),
                                        ("docbatch-closed", 8)])
def test_same_multiset_other_order(mix_name, n):
    mix = traffic.load_mix(mix_name)
    a, b = (traffic.request_cycle(mix, n, seed, 1) for seed in SEEDS)
    assert sorted(a) == sorted(b) and a != b
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= p <= hi for p, _ in a)
    assert len({p for p, _ in a}) > n // 2      # a spread, not one length


def test_arrival_gaps_keep_the_rate_and_the_bursts():
    a, b = (traffic.arrival_gaps(40.0, 96, seed, 1) for seed in SEEDS)
    assert np.isclose(a.sum(), 40.0) and np.isclose(b.sum(), 40.0)
    assert np.allclose(np.sort(a), np.sort(b)) and not np.allclose(a, b)
    assert a.max() > 4 * np.median(a)       # exponential: long gaps exist


def test_open_loop_window_is_one_whole_cycle():
    mix = traffic.load_mix("chat-open")
    sizes = traffic.cycle_sizes(mix, 40)
    streams = [traffic.RequestStream(mix, 1000, seed, 40) for seed in SEEDS]
    windows = []
    for stream in streams:
        requests = [next(stream) for _ in range(sizes[0][0] + sizes[1][0])]
        window = [r for r in requests if r[1] == 1]
        assert np.isclose(sum(r[4] for r in window), 40.0)
        assert all(0 <= r[2].min() and r[2].max() < 1000 for r in window)
        windows.append(sorted((len(r[2]), r[3]) for r in window))
    assert windows[0] == windows[1]


def test_pretrain_batches_follow_seed_and_step():
    one = traffic.pretrain_batch(512, SEEDS[1], 3, (2, 2, 16))
    assert one.shape == (2, 2, 16) and one.dtype == np.int32
    assert (one == traffic.pretrain_batch(512, SEEDS[1], 3, (2, 2, 16))).all()
    assert (one != traffic.pretrain_batch(512, SEEDS[1], 4, (2, 2, 16))).any()
