"""Analytic WORK metrics for the perf-motivated schedules.

Round-3 review item 9: zig-zag ring and interleaved PP had correctness
evidence (output equality) but nothing asserting the *work* distribution
they exist to improve.  These tests pin the analytic invariants:

* zig-zag SP: per-device computed causal work is balanced (the contiguous
  layout's device n-1 does ~n× device 0's FLOPs — the whole point of the
  permutation, ``ops/ring_attention.py`` zigzag_perm);
* interleaved PP: the bubble shrinks ~V× vs plain scheduling at the same
  (M, P) (reference Megatron interleaved 1F1B claim; ``pipeline.py``
  pipeline_interleaved clock).
"""

import numpy as np
import pytest

from deepspeed_tpu.ops.ring_attention import zigzag_perm


# ----------------------------------------------------------------------
# zig-zag ring: causal work balance
# ----------------------------------------------------------------------
def _causal_pairs_per_device(perm, n, S):
    """Exact causal (q >= k) pair count each device computes when device d
    owns permuted-token slice [d*S/n, (d+1)*S/n) and sees every kv chunk
    over the ring (the ring rotates all kv past all devices, so device
    work = causal pairs with q in its slice, k anywhere)."""
    c = S // n
    counts = []
    for d in range(n):
        q_glob = perm[d * c:(d + 1) * c]          # global positions owned
        counts.append(int(sum(q + 1 for q in q_glob)))  # k <= q, all kv
    return counts


def _computed_subblocks_per_device(n):
    """Block-level work under the kernel's skip rule: device d holds
    chunks (d, 2n-1-d); a (q_chunk, k_chunk) sub-block is computed iff
    q_cid >= k_cid (fully-future blocks are lax.cond-skipped —
    ``_zz_fwd_local``).  Over a full ring pass every kv chunk visits
    every device."""
    counts = []
    for d in range(n):
        q_cids = (d, 2 * n - 1 - d)
        computed = sum(1 for q_cid in q_cids for k_cid in range(2 * n)
                       if q_cid >= k_cid)
        counts.append(computed)
    return counts


@pytest.mark.parametrize("n", [2, 4, 8])
def test_zigzag_block_work_balanced(n):
    zz = _computed_subblocks_per_device(n)
    # every device computes exactly 2n+1 of its 4n sub-blocks
    assert all(c == 2 * n + 1 for c in zz), zz
    # contiguous layout (device d = chunk d of n): d+1 computed blocks →
    # device n-1 does n× device 0's block work
    contiguous = [d + 1 for d in range(n)]
    assert max(contiguous) == n * min(contiguous)


@pytest.mark.parametrize("n,S", [(2, 32), (4, 64), (8, 128)])
def test_zigzag_pair_work_balanced(n, S):
    """FLOP-level balance from the ACTUAL permutation: max/min causal-pair
    imbalance stays within one chunk's self-block, while contiguous is
    ~(2n-1)×."""
    perm, inv = zigzag_perm(S, n)
    # sanity: perm is a permutation and inv inverts it
    assert sorted(perm.tolist()) == list(range(S))
    np.testing.assert_array_equal(perm[inv], np.arange(S))

    zz = _causal_pairs_per_device(perm.tolist(), n, S)
    assert max(zz) - min(zz) <= (S // (2 * n)) ** 2, zz
    contiguous = _causal_pairs_per_device(list(range(S)), n, S)
    assert max(contiguous) / min(contiguous) > (2 * n - 1) * 0.9
    # both layouts cover the identical causal triangle
    assert sum(zz) == sum(contiguous) == S * (S + 1) // 2


# ----------------------------------------------------------------------
# interleaved PP: bubble ticks shrink ~V× (simulated on the real clock)
# ----------------------------------------------------------------------
def _simulate_interleaved_busy(M, Pn, V):
    """Replay ``pipeline_interleaved``'s tick rule with validity flags:
    counts per-stage ticks holding a REAL microbatch activation, plus
    checks the exit-tick formula."""
    groups_inject = -(-M // Pn)
    T = (groups_inject * V) * Pn + (Pn - 1)
    valid = np.zeros(Pn, bool)            # does slot s hold a live mb?
    mb_of = np.full(Pn, -1)               # which mb
    chunk_of = np.full(Pn, -1)            # which virtual chunk
    busy = np.zeros(Pn, int)
    exits = {}                            # mb -> tick its chunk V-1 exited
    for t in range(T):
        G, r = divmod(t, Pn)
        mb_new = (G // V) * Pn + r
        inject = (G % V == 0) and (mb_new < M)
        if inject:
            valid[0], mb_of[0], chunk_of[0] = True, mb_new, 0
        elif valid[0]:
            chunk_of[0] += 1              # wraparound: next virtual chunk
        busy += valid
        # exit: slot P-1 finishing chunk V-1
        if valid[Pn - 1] and chunk_of[Pn - 1] == V - 1:
            exits.setdefault(int(mb_of[Pn - 1]), t)
        # roll: slot s -> s+1; slot P-1 wraps into slot 0
        valid = np.roll(valid, 1)
        mb_of = np.roll(mb_of, 1)
        chunk_of = np.roll(chunk_of, 1)
        if valid[0] and chunk_of[0] >= V - 1 and V > 1:
            # chunk V-1 wrapped around after exiting: slot 0 must not
            # treat it as live unless it still has chunks to run
            valid[0] = chunk_of[0] < V - 1 or False
        chunk_of[0] = chunk_of[0] if valid[0] else -1
    return T, busy, exits


@pytest.mark.parametrize("M,Pn,V", [(8, 4, 2), (16, 4, 4), (8, 2, 2)])
def test_interleaved_bubble_shrinks_vx(M, Pn, V):
    T, busy, exits = _simulate_interleaved_busy(M, Pn, V)
    assert T == (-(-M // Pn) * V) * Pn + (Pn - 1)
    # every stage runs M·V useful chunk-ticks
    assert busy.max() == M * V, busy
    # normalized time units: interleaved tick costs 1/V of a plain tick
    # (1/V of the layers) → total wall = T/V, useful = M, bubble:
    bubble_int = (T - M * V) / V
    bubble_plain = (M + Pn - 1) - M        # gpipe/1F1B fwd clock: P-1
    assert bubble_int == pytest.approx(bubble_plain / V), \
        (bubble_int, bubble_plain)
    # exit-tick formula used by pipeline_interleaved to slice outputs
    for m in range(M):
        want = ((m // Pn) * V + V - 1) * Pn + (m % Pn) + (Pn - 1)
        assert exits[m] == want, (m, exits[m], want)


def test_true_1f1b_residual_ring_bound():
    """True 1F1B's documented memory contract: the VJP residual ring holds
    2P-1 slots regardless of M (vs gpipe's O(M)) — the analytic form of
    the compiled-memory test (test_pipe.py asserts the compiled bytes)."""
    for Pn in (2, 4, 8):
        K = 2 * Pn - 1
        # residual for (stage s, microbatch m) lives 2(P-1-s) ticks; the
        # longest-lived (s=0) fits the ring with one slot to spare
        max_live = 2 * (Pn - 1) + 1
        assert max_live <= K
        # and M does not appear: the bound is M-independent by construction
