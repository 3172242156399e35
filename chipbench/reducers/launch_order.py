"""Which dispatch each device operation ran in, by LAUNCH ORDER, for a
serve loop that does not wait for every dispatch.

``scope_pct`` gives an operation to the dispatch launched last before it,
which is right while each dispatch is fetched before the next is launched.
The chunked policy launches a prefill chunk that is not sampled from
(``head_rows`` 0) and never waits for it: the decode step goes out a
millisecond later and the chunk's operations run on under the decode's
launch time.  What always holds is the order: one device runs its
programs one at a time in the order they were launched.  So the device's
line is cut at the FIRST operation of each execution:

- a program's first operation is one instruction of its entry computation,
  the same at every execution, and the trace keeps operations apart by
  their whole text (a label a text), so that label marks the program;
- it is learned from the run: where the dispatch before was waited for
  (anything but an unsampled prefill) the device is idle at the launch,
  and the first operation to start after it is this dispatch's; the
  commonest such label over a program's dispatches is its marker (a
  program launched only behind unwaited work has none: nothing is read);
- execution ``i`` then starts at the first marker of its program from the
  dispatch's launch on (less ``SLACK_NS`` for the two clocks) and after
  the start of execution ``i - 1``, and runs to the start of the next.

Shared by the readers beside it; no ``read`` of its own."""

from dataclasses import dataclass

import numpy as np

from chipbench import reduce
from chipbench.reducers import program_spans

SITES = {"prefill": "serve/prefill_fn", "decode": "serve/step_fn"}
SLACK_NS = 1e6


def program_of(dispatch):
    """What tells the compiled programs apart: phase and shape, and for a
    prefill whether it takes the head (two programs a chunk shape)."""
    return (dispatch["phase"], dispatch["batch"], dispatch["tokens"],
            min(int(dispatch.get("head_rows", 1)), 1))


def waited_for(dispatch):
    """False for the one dispatch the serve loop launches and leaves: a
    prefill chunk that nothing is sampled from."""
    return not (dispatch["phase"] == "prefill"
                and dispatch.get("head_rows", 1) == 0)


@dataclass
class Executions:
    """The first device's operations in order of their start, cut into
    the executions of the traced dispatches."""
    dispatches: list        # in launch order
    first: np.ndarray       # per dispatch: its first operation, or the
    #                         number of operations where it ran past the trace
    owner: np.ndarray       # per operation: its dispatch, or -1
    start: np.ndarray       # per operation, nanoseconds, cut to the window
    end: np.ndarray
    label: np.ndarray       # per operation: index into ``Trace.labels``


def markers(dispatches, launched, start, end, label):
    """{program: label of its first operation}, from the dispatches that
    were launched at an idle device behind waited-for work."""
    seen = {}
    busy_until = np.maximum.accumulate(end) if len(end) else end
    for i, d in enumerate(dispatches):
        if i and not waited_for(dispatches[i - 1]):
            continue
        k = int(np.searchsorted(start, launched[i], side="left"))
        if k >= len(start) or (k and busy_until[k - 1] > launched[i]):
            continue        # nothing after it, or the device was not idle
        if i + 1 < len(launched) and start[k] >= launched[i + 1]:
            continue        # this dispatch's operations are not in the trace
        seen.setdefault(program_of(d), []).append(int(label[k]))
    return {p: max(set(found), key=found.count) for p, found in seen.items()}


def executions(run):
    """:class:`Executions` of a traced serving run, or None without a
    trace, traced dispatches, a clock fit, or a marker for every program
    the traced dispatches ran."""
    if run.trace is None or not run.trace.ops or not run.traced_steps:
        return None
    offset = program_spans.clock_offset_ns(run)
    if offset is None:
        return None
    dispatches = sorted((d for s in run.traced_steps
                         for d in s["dispatches"] if d["phase"] in SITES),
                        key=lambda d: d["t0_ns"])
    if not dispatches:
        return None
    line = run.trace.ops[0]
    lo, hi = run.trace.window
    order = np.argsort(line.start, kind="stable")
    start, label = line.start[order], line.label[order]
    end = start + line.dur[order]
    launched = np.asarray([d["t0_ns"] for d in dispatches]) + offset
    marker = markers(dispatches, launched, start, end, label)
    if any(program_of(d) not in marker for d in dispatches):
        return None
    where = {p: np.flatnonzero(label == m) for p, m in marker.items()}
    first = np.full(len(dispatches), len(start))
    after = 0
    for i, d in enumerate(dispatches):
        found = where[program_of(d)]
        found = found[(found >= after)
                      & (start[found] >= launched[i] - SLACK_NS)]
        if not len(found):
            break           # the trace ends before this dispatch ran
        first[i] = found[0]
        after = found[0] + 1
    owner = np.searchsorted(first, np.arange(len(start)), side="right") - 1
    return Executions(dispatches, first, owner, np.clip(start, lo, hi),
                      np.clip(end, lo, hi), label)


def device_seconds(run, phase):
    """(seconds the first device was busy inside executions of ``phase``
    dispatches, those dispatches) over the traced window; None as above."""
    ex = executions(run)
    if ex is None:
        return None
    mine = np.asarray([d["phase"] == phase for d in ex.dispatches], bool)
    ran = mine & (ex.first < len(ex.start))
    keep = (ex.owner >= 0) & mine[np.maximum(ex.owner, 0)]
    busy = reduce._length(*reduce._union(ex.start[keep], ex.end[keep]))
    return busy / 1e9, [d for d, r in zip(ex.dispatches, ran) if r]
