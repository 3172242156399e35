"""The readers that cut the device's line by LAUNCH ORDER
(``chipbench/reducers/launch_order.py``): under the chunked policy a prefill
chunk nothing is sampled from is launched and left, the decode step goes
out a millisecond later, and the chunk's operations run on under the
decode's launch time.  A made-up trace of that shape, with two programs
that share instruction names: the readers give every operation to its own
program, where ``scope_pct`` (by launch TIME) loses most of the chunk.
Since the loop runs a decode step ahead EVERY chunk is launched behind the
decode in flight (the shape that read nothing until PR 53): the executions
are the events of the device's ``XLA Modules`` line, taken in order."""

import numpy as np
import pytest

from chipbench import cells, reduce
from chipbench.reducers import (launch_order, phase_device_ms_per_ktok,
                                phase_device_share_pct, scope_pct,
                                scope_pct_in_order)
from deepspeed_tpu.monitor import telemetry

US = 1e3
# label -> text: the trace keeps operations apart by their whole text, so
# the chunk's ``fusion.1`` and the decode's are two labels of one name
LABELS = ["fusion.1:fusion", "fusion.2:fusion", "while.4:while",
          "fusion.1:fusion", "fusion.3:fusion", "fusion.9:fusion"]
CHUNK_FIRST, CHUNK_CTX, CHUNK_LOOP, DECODE_FIRST, DECODE_REST, HEAD = range(6)
# the ``XLA Modules`` line names an event by its jit and the fingerprint of
# the compiled program: a chunk shape's two prefill programs have two
MODULES = ["jit_serve_decode(11)", "jit_serve_prefill(22)",
           "jit_serve_prefill(33)", "jit_copy_page(44)"]
DECODE, CHUNK, CHUNK_HEAD, COPY = range(4)
TABLES = {
    ("serve/prefill_fn", (1, 64), (1, 0)): {
        "fusion.1": "latent_attn", "fusion.2": "latent_ctx",
        "while.4": "latent_ctx"},
    ("serve/prefill_fn", (1, 64), (1, 1)): {
        "fusion.1": "latent_attn", "fusion.2": "latent_ctx",
        "while.4": "latent_ctx", "fusion.9": "experts"},
    ("serve/step_fn", (4, 1), None): {
        "fusion.1": "experts", "fusion.3": "latent_attn"},
}


def _op_scopes(site, arg_shapes=None):
    return TABLES[(site, arg_shapes[1], arg_shapes.get(5))]


def _dispatch(phase, at_us, head_rows=None):
    batch, tokens = (1, 64) if phase == "prefill" else (4, 1)
    return {"phase": phase, "batch": batch, "tokens": tokens,
            "head_rows": tokens if head_rows is None else head_rows,
            "t0_ns": at_us * US, "t1_ns": (at_us + 200) * US}


class _Device:
    """Operations and module events of a made-up device, in microseconds."""

    def __init__(self):
        self.ops, self.modules = [], []

    def decode(self, at):
        self.ops.extend([(at, 300, DECODE_FIRST),
                         (at + 300, 200, DECODE_REST)])
        self.modules.append((at, 500, DECODE))

    def chunk(self, at, head):
        self.ops.extend([(at, 1000, CHUNK_FIRST),
                         (at + 1000, 8000, CHUNK_LOOP),
                         (at + 1000, 4000, CHUNK_CTX),
                         (at + 5000, 4000, CHUNK_CTX)])
        if head:
            self.ops.append((at + 9000, 500, HEAD))
        self.modules.append((at, 9500 if head else 9000,
                             CHUNK_HEAD if head else CHUNK))

    def run(self, steps, first=0, modules=True):
        """``steps``: each a step's dispatches, 20 ms a step from step
        ``first`` on."""
        steps = [{"t0": (first + i) * 0.02, "t1": (first + i + 1) * 0.02,
                  "traced": True, "dispatches": d}
                 for i, d in enumerate(steps)]

        def line(events):
            start, dur, label = (np.asarray(c, np.float64)
                                 for c in zip(*events))
            return reduce.DeviceLine(start * US, dur * US, label.astype(int))

        trace = reduce.Trace(
            labels=LABELS, kinds=["xla"] * len(LABELS),
            ops=[line(self.ops)],
            modules=[line(self.modules)] if modules else [],
            module_names=MODULES if modules else [],
            annotations=[("chipbench/step", s["t0"] * 1e9, s["t1"] * 1e9)
                         for s in steps])
        return cells.Run(chips=1, peaks={}, model={}, steps=steps,
                         traced_steps=steps, samples={}, counters={},
                         memory_peak_bytes=0, trace=trace)


def _made_up_run(decode_alone=True, modules=True):
    """Steps of 20 ms.  Step 0: a decode alone.  Step 1: an unsampled
    chunk (launched at 20,000 us, busy 20,300-29,300) and the decode
    launched at 21,000 us, which runs behind it (29,300-29,800).  Step 2:
    a sampled chunk, fetched, then its decode."""
    device, steps = _Device(), []
    if decode_alone:
        device.decode(300)
        steps.append([_dispatch("decode", 0)])
    device.chunk(20_300, head=False)
    device.decode(29_300)
    steps.append([_dispatch("prefill", 20_000, head_rows=0),
                  _dispatch("decode", 21_000)])
    device.chunk(40_300, head=True)
    device.decode(51_300)
    steps.append([_dispatch("prefill", 40_000, head_rows=1),
                  _dispatch("decode", 51_000)])
    return device.run(steps, first=0 if decode_alone else 1, modules=modules)


def _every_chunk_behind_a_decode():
    """Today's agentctx shape.  A step launches a chunk and a decode and
    fetches the decode of the step BEFORE, so the device is never idle at
    a launch: chunk k runs 10,000 k + 300 .. + 9,300 (+ 500 with the
    head), decode k behind it, and step k + 1 launches its two while
    chunk k has just begun.  The profiler starts a step early, so the
    line holds a chunk and a decode (and a page copy between programs)
    that no traced step launched."""
    device, steps = _Device(), []
    for k in range(5):
        head = k == 3
        device.chunk(10_000 * k + 300, head)
        device.decode(10_000 * k + (9_800 if head else 9_300))
        if k == 2:
            device.modules.append((29_850, 100, COPY))
            device.ops.append((29_850, 100, DECODE_REST))
        if k:       # launched as chunk k - 1 began: both wait a whole step
            at = 10_000 * (k - 1) + 1_000
            steps.append([_dispatch("prefill", at, head_rows=int(head)),
                          _dispatch("decode", at + 1_000)])
    run = device.run(steps)
    # steps of 10 ms here, from the launch of the first traced chunk
    for k, step in enumerate(run.traced_steps):
        step["t0"], step["t1"] = 0.001 + 0.01 * k, 0.001 + 0.01 * (k + 1)
    # ... and stops in a step the window leaves out, the device running on
    run.trace.annotations = [
        ("chipbench/step", (0.001 + 0.01 * k) * 1e9,
         (0.001 + 0.01 * (k + 1)) * 1e9) for k in range(5)]
    return run


def test_an_unwaited_chunks_operations_stay_with_the_chunk(monkeypatch):
    monkeypatch.setattr(telemetry, "op_scopes", _op_scopes)
    run = _made_up_run()
    ex = launch_order.executions(run)
    # five executions, cut at each program's first operation
    assert [LABELS[ex.label[k]] for k in ex.first] == ["fusion.1:fusion"] * 5
    assert list(ex.label[ex.first]) == [DECODE_FIRST, CHUNK_FIRST,
                                        DECODE_FIRST, CHUNK_FIRST,
                                        DECODE_FIRST]
    seconds = scope_pct_in_order.by_scope(run)
    assert seconds == pytest.approx({
        "latent_ctx": 2 * 8.0e-3,               # both chunks' two halves
        "latent_attn": 2 * 1.0e-3 + 3 * 0.2e-3,  # chunks' first, decodes'
        "experts": 3 * 0.3e-3 + 0.5e-3})        # decodes' first, the head
    busy = reduce.busy_seconds(run.trace)
    assert busy == pytest.approx(2 * 9.0e-3 + 0.5e-3 + 3 * 0.5e-3)
    assert scope_pct_in_order.read(run, "latent_ctx") == pytest.approx(
        100 * 16.0e-3 / busy)


def test_by_launch_time_the_same_trace_loses_the_chunk(monkeypatch):
    """What ``scope_pct`` reads of it, for the record: the unsampled
    chunk's operations after the decode's launch are looked up in the
    decode's table."""
    monkeypatch.setattr(
        telemetry, "op_scopes",
        lambda site, arg_shapes=None: _op_scopes(
            site, {**arg_shapes, 5: (1, 0)} if "prefill" in site
            else arg_shapes))
    run = _made_up_run()
    by_time = scope_pct.by_scope(run)["latent_ctx"]
    assert by_time == pytest.approx(8.0e-3)     # the sampled chunk alone
    assert scope_pct_in_order.by_scope(run)["latent_ctx"] == \
        pytest.approx(2 * by_time)


def test_the_prefill_metrics_read_the_device_not_the_launch(monkeypatch):
    monkeypatch.setattr(telemetry, "op_scopes", _op_scopes)
    run = _made_up_run()
    # two chunks of 64 positions: 9.0 and 9.5 ms of device time
    assert phase_device_ms_per_ktok.read(run, "prefill") == pytest.approx(
        18.5 / (128 / 1024))
    assert phase_device_share_pct.read(run, "prefill") == pytest.approx(
        100 * 18.5e-3 / 60e-3)
    assert phase_device_share_pct.read(run, "decode") == pytest.approx(
        100 * 1.5e-3 / 60e-3)


def test_chunks_always_launched_behind_a_decode_in_flight_are_read(
        monkeypatch):
    """No dispatch is launched at an idle device and none is the first of
    its program on the line: every operation still goes to its own
    program, and the three readers read numbers."""
    monkeypatch.setattr(telemetry, "op_scopes", _op_scopes)
    run = _every_chunk_behind_a_decode()
    ex = launch_order.executions(run)
    assert len(ex.dispatches) == 8
    # the untraced chunk and decode in front are nobody's, nor is the copy
    assert list(ex.owner[:6]) == [-1] * 6 and int(np.sum(ex.owner < 0)) == 7
    assert list(ex.label[ex.first]) == [CHUNK_FIRST, DECODE_FIRST] * 4
    assert [launch_order.program_of(ex.dispatches[o])[0]
            for o in ex.owner[ex.owner >= 0]] == (
        ["prefill"] * 4 + ["decode"] * 2) * 2 + ["prefill"] * 5 + \
        ["decode"] * 2 + ["prefill"] * 4 + ["decode"] * 2
    lo, hi = run.trace.window
    assert (lo, hi) == (1e6, 51e6)
    seconds = scope_pct_in_order.by_scope(run)
    assert seconds == pytest.approx({
        "latent_ctx": 4 * 8.0e-3, "latent_attn": 4 * 1.0e-3 + 4 * 0.2e-3,
        "experts": 4 * 0.3e-3 + 0.5e-3})
    busy = reduce.busy_seconds(run.trace)
    assert scope_pct_in_order.read(run, "latent_attn") == pytest.approx(
        100 * 4.8e-3 / busy)
    assert phase_device_ms_per_ktok.read(run, "prefill") == pytest.approx(
        (3 * 9.0 + 9.5) / (4 * 64 / 1024))
    assert phase_device_share_pct.read(run, "prefill") == pytest.approx(
        100 * 36.5e-3 / 50e-3)
    assert phase_device_share_pct.read(run, "decode") == pytest.approx(
        100 * 2.0e-3 / 50e-3)


def test_a_program_never_launched_at_an_idle_device_reads_nothing(
        monkeypatch):
    """What is left of that silence: a trace without the ``XLA Modules``
    line (no profiler here writes one) gives no executions, whatever the
    launches looked like; nor does a line on which the dispatches fit
    nowhere (another program's events under the serving jits' names)."""
    monkeypatch.setattr(telemetry, "op_scopes", _op_scopes)
    assert len(launch_order.executions(_made_up_run()).first) == 5
    run = _made_up_run(modules=False)
    assert launch_order.executions(run) is None
    assert scope_pct_in_order.read(run, "latent_ctx") is None
    assert phase_device_ms_per_ktok.read(run, "prefill") is None
    assert phase_device_share_pct.read(run, "prefill") is None
    run = _made_up_run()
    run.trace.modules[0].label[:] = DECODE      # five decodes on the line
    assert launch_order.executions(run) is None
    assert phase_device_share_pct.read(run, "prefill") is None


def test_no_trace_or_no_scopes_reads_nothing(monkeypatch):
    run = _made_up_run()
    run.trace = None
    assert scope_pct_in_order.read(run, "latent_ctx") is None
    assert phase_device_share_pct.read(run, "prefill") is None
    run = _made_up_run()
    monkeypatch.delattr(telemetry, "SERVE_SCOPES")
    assert scope_pct_in_order.read(run, "latent_ctx") is None
