"""The readers that cut the device's line by LAUNCH ORDER
(``chipbench/reducers/launch_order.py``): under the chunked policy a prefill
chunk nothing is sampled from is launched and left, the decode step goes
out a millisecond later, and the chunk's operations run on under the
decode's launch time.  A made-up trace of that shape, with two programs
that share instruction names: the readers give every operation to its own
program, where ``scope_pct`` (by launch TIME) loses most of the chunk."""

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


def _made_up_run(decode_alone=True):
    """Steps of 20 ms.  Step 0: a decode alone.  Step 1: an unsampled
    chunk (launched at 20,000 us, busy 20,300-29,300) and the decode
    launched at 21,000 us, which runs behind it (29,300-29,800).  Step 2:
    a sampled chunk, fetched, then its decode."""
    ops, steps = [], []

    def decode(at):
        ops.extend([(at, 300, DECODE_FIRST), (at + 300, 200, DECODE_REST)])

    def chunk(at, head):
        ops.extend([(at, 1000, CHUNK_FIRST), (at + 1000, 8000, CHUNK_LOOP),
                    (at + 1000, 4000, CHUNK_CTX),
                    (at + 5000, 4000, CHUNK_CTX)])
        if head:
            ops.append((at + 9000, 500, HEAD))

    if decode_alone:
        decode(300)
        steps.append([_dispatch("decode", 0)])
    chunk(20_300, head=False)
    decode(29_300)
    steps.append([_dispatch("prefill", 20_000, head_rows=0),
                  _dispatch("decode", 21_000)])
    chunk(40_300, head=True)
    decode(51_300)
    steps.append([_dispatch("prefill", 40_000, head_rows=1),
                  _dispatch("decode", 51_000)])
    first = 0 if decode_alone else 1
    steps = [{"t0": (first + i) * 0.02, "t1": (first + i + 1) * 0.02,
              "traced": True, "dispatches": d} for i, d in enumerate(steps)]
    start, dur, label = (np.asarray(c, np.float64) for c in zip(*ops))
    trace = reduce.Trace(
        labels=LABELS, kinds=["xla"] * len(LABELS),
        ops=[reduce.DeviceLine(start * US, dur * US, label.astype(int))],
        annotations=[("chipbench/step", s["t0"] * 1e9, s["t1"] * 1e9)
                     for s in steps])
    return cells.Run(chips=1, peaks={}, model={}, steps=steps,
                     traced_steps=steps, samples={}, counters={},
                     memory_peak_bytes=0, trace=trace)


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


def test_a_program_never_launched_at_an_idle_device_reads_nothing(
        monkeypatch):
    """Without a decode step of its own the decode program's first
    operation cannot be learned behind the unsampled chunk... but it is
    behind the sampled one, which is waited for."""
    monkeypatch.setattr(telemetry, "op_scopes", _op_scopes)
    run = _made_up_run(decode_alone=False)
    assert len(launch_order.executions(run).first) == 4
    # take the sampled chunk's step away: no decode follows a wait
    run.traced_steps = run.traced_steps[:1]
    assert launch_order.executions(run) is None
    assert scope_pct_in_order.read(run, "latent_ctx") is None
    assert phase_device_ms_per_ktok.read(run, "prefill") is None
    assert phase_device_share_pct.read(run, "prefill") is None


def test_no_trace_or_no_scopes_reads_nothing(monkeypatch):
    run = _made_up_run()
    run.trace = None
    assert scope_pct_in_order.read(run, "latent_ctx") is None
    assert phase_device_share_pct.read(run, "prefill") is None
    run = _made_up_run()
    monkeypatch.delattr(telemetry, "SERVE_SCOPES")
    assert scope_pct_in_order.read(run, "latent_ctx") is None
