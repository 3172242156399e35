"""The ratio of two of the program's own per-dispatch counters
(``engine.last_step["dispatches"][i]``: ``selected``, ``context_keys``,
``expert_pairs``, ``expert_load_max``), each summed over the window's
dispatches, times ``scale``.  ``under_split`` names sizes of the model
(``run.model``) the lower sum is divided by first: the fullest held expert
over the pairs per held expert per expert layer is a load ratio.  None
where the program's dispatches carry no such counters."""


def read(run, over, under, scale=1.0, under_split=()):
    found = [d for s in run.steps for d in s["dispatches"]
             if over in d and under in d]
    lower = float(sum(d[under] for d in found))
    for size in under_split:
        lower /= run.model[size]
    if not lower:
        return None
    return scale * sum(d[over] for d in found) / lower
