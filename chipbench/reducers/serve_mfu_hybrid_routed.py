"""Model FLOP/s utilisation of a server whose layers are state-space
(Mamba-2) or attention AND hold a share of routed experts beside one
shared SwiGLU: ``serve_mfu_hybrid``'s reading (two operations a weight of
every mixer a token passes, QK^T and PV over an attention layer's context,
the recurrence, the head a sampled row; its feed-forward term is the
shared SwiGLU here, the family's ``ffn``) plus, a layer, the router over
all PUBLISHED experts a token and ``3 x hidden x expert_ffn`` multiply-adds
for every (token, expert) pair computed HERE: the program's own count over
its real tokens, ``expert_pairs`` of the dispatch's record, not
``experts_per_token`` a token (a pair whose expert another chip holds is
no work of this one).  Sizes from the family's ``model_sizes``; the same
steady steps.  None without dispatches or for a model of another shape."""

from chipbench.reducers import serve_mfu_hybrid
from chipbench.reducers.serve_mfu_pct import _steady


def read(run):
    m = run.model
    mixers = serve_mfu_hybrid.read(run)
    if mixers is None or "expert_ffn" not in m:
        return None
    router = m["n_layers"] * m["hidden"] * m["experts_published"]
    pair = 3 * m["hidden"] * m["expert_ffn"]
    steps = _steady(run.steps)
    macs = 0
    for step in steps:
        for disp in step["dispatches"]:
            if disp["phase"] == "prefill" and "real" in disp:
                tokens = disp["real"]
            elif disp["phase"] == "decode" and "contexts" in disp:
                tokens = len(disp["contexts"])
            else:
                continue
            macs += tokens * router + disp.get("expert_pairs", 0) * pair
    busy = sum(s["t1"] - s["t0"] for s in steps)
    return mixers + 100.0 * 2.0 * macs / busy / run.chips \
        / run.peaks["bf16_flops_per_s"]
