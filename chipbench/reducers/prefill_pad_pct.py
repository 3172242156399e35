"""Padded over real prompt tokens dispatched to prefill in the window:
what the power-of-two buckets add, in %."""


def read(run):
    prefills = [d for s in run.steps for d in s["dispatches"]
                if d["phase"] == "prefill" and "real" in d]
    real = sum(d["real"] for d in prefills)
    if not real:
        return None
    return 100.0 * sum(d["tokens"] - d["real"] for d in prefills) / real
