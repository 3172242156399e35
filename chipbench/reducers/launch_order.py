"""Which dispatch each device operation ran in, by LAUNCH ORDER, for a
serve loop that does not wait for every dispatch.

``scope_pct`` gives an operation to the dispatch launched last before it,
which is right while each dispatch is fetched before the next is launched.
The chunked policy launches a prefill chunk that is not sampled from
(``head_rows`` 0) and never waits for it, and since the loop runs a decode
step ahead a decode dispatch is left running too: a chunk is always
launched behind the decode in flight and its operations run on under later
launch times.  What always holds is the order: one device runs its
programs one at a time in the order they were launched.  The device's
``XLA Modules`` line (``reduce.Trace.modules``) has one event an executed
program, named by its jit, that spans the program's operations, so:

- the k-th event of the two serving programs from the window's first
  traced dispatch on IS the k-th traced dispatch, and an operation belongs
  to the event it starts in;
- where the window's first traced dispatch lies on that line is a shift
  the trace does not state (the profiler starts a step before the window's
  first whole step, with that step's dispatches on the line): the least
  shift at which every event is the dispatch's phase, starts no earlier
  than the dispatch was launched (less ``SLACK_NS`` for the two clocks),
  and each event name (a jit and the fingerprint of one compiled program:
  a chunk shape's two prefill programs have two) goes with one program of
  the dispatches and one only.

A trace without that line reads nothing.  Shared by the readers beside it;
no ``read`` of its own."""

import re
from dataclasses import dataclass

import numpy as np

from chipbench import reduce
from chipbench.reducers import program_spans

SITES = {"prefill": "serve/prefill_fn", "decode": "serve/step_fn"}
# the serve loop's two named jits (``inference/serving.py``), as the
# ``XLA Modules`` line names their events: ``jit_serve_decode(<digits>)``
MODULES = {"jit_serve_prefill": "prefill", "jit_serve_decode": "decode"}
SLACK_NS = 1e6


def program_of(dispatch):
    """What tells the compiled programs apart: phase and shape, and for a
    prefill whether it takes the head (two programs a chunk shape)."""
    return (dispatch["phase"], dispatch["batch"], dispatch["tokens"],
            min(int(dispatch.get("head_rows", 1)), 1))


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


def serving_events(trace):
    """(start, end, phase, name) of the first device's executions of the
    two serving programs, in order of their start: arrays, ``phase`` a
    key of ``SITES`` and ``name`` an index into ``trace.module_names``."""
    line = trace.modules[0]
    phase = np.asarray([MODULES.get(re.sub(r"\(\d+\)$", "", name), "")
                        for name in trace.module_names])
    order = np.argsort(line.start, kind="stable")
    order = order[phase[line.label[order]] != ""]
    start, name = line.start[order], line.label[order]
    return start, start + line.dur[order], phase[name], name


def shift_of(programs, launched, start, phase, name):
    """The event that is the first dispatch: the least ``j`` at which
    event ``j + i`` can be dispatch ``i`` for every ``i`` the line reaches
    (the module docstring's three conditions); None where there is none
    that covers half of the dispatches."""
    phases = np.asarray([p[0] for p in programs])
    for j in range(len(start)):
        n = min(len(programs), len(start) - j)
        if 2 * n < len(programs):
            return None
        if np.any(phase[j:j + n] != phases[:n]) or \
                np.any(start[j:j + n] < launched[:n] - SLACK_NS):
            continue
        pairs = set(zip(programs[:n], name[j:j + n].tolist()))
        if len({p for p, _ in pairs}) == len({m for _, m in pairs}) \
                == len(pairs):
            return j
    return None


def executions(run):
    """:class:`Executions` of a traced serving run, or None without a
    trace that has the ``XLA Modules`` line, traced dispatches, a clock
    fit, or a place on that line where the dispatches fit."""
    if run.trace is None or not run.trace.ops or not run.trace.modules \
            or not run.traced_steps:
        return None
    offset = program_spans.clock_offset_ns(run)
    if offset is None:
        return None
    dispatches = sorted((d for s in run.traced_steps
                         for d in s["dispatches"] if d["phase"] in SITES),
                        key=lambda d: d["t0_ns"])
    if not dispatches:
        return None
    launched = np.asarray([d["t0_ns"] for d in dispatches]) + offset
    ran_from, ran_to, phase, name = serving_events(run.trace)
    shift = shift_of([program_of(d) for d in dispatches], launched,
                     ran_from, phase, name)
    if shift is None:
        return None
    ran_from, ran_to = (x[shift:shift + len(dispatches)]
                        for x in (ran_from, ran_to))
    line = run.trace.ops[0]
    lo, hi = run.trace.window
    order = np.argsort(line.start, kind="stable")
    start, label = line.start[order], line.label[order]
    first = np.full(len(dispatches), len(start))
    first[:len(ran_from)] = np.searchsorted(start, ran_from, side="left")
    owner = np.searchsorted(ran_from, start, side="right") - 1
    owner[start >= ran_to[np.maximum(owner, 0)]] = -1   # another program's
    return Executions(dispatches, first, owner, np.clip(start, lo, hi),
                      np.clip(start + line.dur[order], lo, hi), label)


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
