"""Share of the window's wall time (whole steps, first ``t0`` to last
``t1``) spent inside the program's ``name`` spans, in %: for
``serve/prefill`` the time every decoding request waits."""

from chipbench.reducers import program_spans


def read(run, name):
    spans = program_spans.window_spans(run)
    if spans is None:
        return None
    wall = (run.steps[-1]["t1"] - run.steps[0]["t0"]) * 1e9
    inside = sum(s.t1_ns - s.t0_ns for s in spans if s.name == name)
    return 100.0 * inside / wall if wall > 0 else None
