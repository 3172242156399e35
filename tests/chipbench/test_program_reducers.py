"""The per-layer readers that read the program's own account of its work
(``chipbench/reducers/span_*.py``, ``idle_by_span_pct.py``,
``phase_pct.py``), each on a hand-made ``reduce.Trace`` and a hand-made
span ring; then one traced toy run of each kind through ``tree.py``'s
made-up cells, which take the real cells' metric files."""

import types

import numpy as np
import pytest

import tree
from chipbench import cells, reduce
from chipbench.reducers import (idle_by_span_pct, phase_pct, program_spans,
                                span_ms, span_ms_per_ktok, span_share_pct)
from deepspeed_tpu.monitor.telemetry import Span

MS = 1_000_000      # nanoseconds


class Ring:
    """Stands in for ``get_telemetry()``: hand-made spans."""

    def __init__(self, spans):
        self._spans = sorted(spans, key=lambda s: s.t0_ns)

    def spans(self, since_ns=None, until_ns=None):
        return [s for s in self._spans
                if (since_ns is None or s.t0_ns >= since_ns)
                and (until_ns is None or s.t1_ns <= until_ns)]


def _span(i, parent, name, t0_ms, t1_ms, key=None, attrs=None):
    return Span(i, parent, name, int(t0_ms * MS), int(t1_ms * MS), key, attrs)


def _run(steps, trace=None, traced=None):
    return cells.Run(chips=1, peaks={}, model={}, steps=steps,
                     traced_steps=steps if traced is None else traced,
                     samples={}, counters={}, memory_peak_bytes=0,
                     trace=trace)


@pytest.fixture
def ring(monkeypatch):
    def install(spans):
        fake = Ring(spans)
        monkeypatch.setattr(program_spans, "telemetry", lambda: fake)
        return fake
    return install


# two serving steps of 100 ms each, 1000..1100 and 1100..1200 ms on the
# host's clock; the second holds a prefill let in by a finished request
SERVE_STEPS = [{"t0": 1.000, "t1": 1.100, "step_s": 0.099},
               {"t0": 1.100, "t1": 1.200, "step_s": 0.098}]
SERVE_SPANS = [
    _span(1, None, "serve/loop", 1001, 1100),
    _span(2, 1, "serve/admit", 1001, 1003),
    _span(3, 1, "serve/decode", 1003, 1100,
          attrs={"batch": 4, "ready": 2, "tokens": 1}),
    _span(4, 3, "serve/decode/build", 1003, 1005),
    _span(5, 3, "serve/step", 1005, 1006, attrs={"phase": "decode"}),
    _span(6, 3, "serve/decode/fetch", 1006, 1090),
    _span(7, 3, "serve/decode/sample", 1090, 1100),
    _span(11, None, "serve/loop", 1102, 1200),
    _span(12, 11, "serve/admit", 1102, 1103),
    _span(13, 11, "serve/decode", 1103, 1200),
    _span(14, 13, "serve/decode/build", 1103, 1104),
    _span(15, 13, "serve/step", 1104, 1105, attrs={"phase": "decode"}),
    _span(16, 13, "serve/decode/fetch", 1105, 1150),
    _span(17, 13, "serve/decode/sample", 1150, 1200),
    _span(18, 17, "serve/prefill", 1152, 1192, key="r7",
          attrs={"bucket": 512, "real": 300, "cached": 0}),
    _span(19, 18, "serve/prefill/build", 1152, 1153),
    _span(20, 18, "serve/step", 1153, 1155, attrs={"phase": "prefill"}),
    _span(21, 18, "serve/prefill/fetch", 1155, 1190),
    _span(22, 18, "serve/prefill/sample", 1190, 1192),
    # outside the window: never read
    _span(30, None, "serve/loop", 1300, 1400),
    _span(31, None, "serve/prefill", 900, 990, attrs={"bucket": 4096}),
]


def test_window_is_the_whole_steps(ring):
    ring(SERVE_SPANS)
    got = program_spans.window_spans(_run(SERVE_STEPS))
    assert {s.id for s in got} == {s.id for s in SERVE_SPANS} - {30, 31}
    assert program_spans.window_spans(_run([])) is None


def test_a_program_without_a_ring_reads_nothing(monkeypatch):
    monkeypatch.setattr(program_spans, "telemetry",
                        lambda: types.SimpleNamespace(enabled=False))
    run = _run(SERVE_STEPS)
    assert span_ms.read(run, "serve/loop") is None
    assert span_ms_per_ktok.read(run, "serve/prefill", "bucket") is None
    assert span_share_pct.read(run, "serve/prefill") is None
    assert idle_by_span_pct.read(run, ["*/sample"]) is None


@pytest.mark.parametrize("name,less,want", [
    # loop less its dispatches and fetches: 99 - 1 - 84 = 14 and
    # 98 - 1 - 45 - (2 + 35) = 15; the median of two is their mean
    ("serve/loop", ["serve/step", "*/fetch"], 14.5),
    # decode less the prefill nested in it: 97 and 97 - 40 = 57
    ("serve/decode", ["serve/prefill"], 77.0),
    ("serve/decode", [], 97.0),
    ("serve/prefill", [], 40.0),
    ("engine/train_batch", [], None),
])
def test_span_ms(ring, name, less, want):
    ring(SERVE_SPANS)
    got = span_ms.read(_run(SERVE_STEPS), name, less)
    assert got == (pytest.approx(want) if want is not None else None)


def test_prefill_cost_and_share(ring):
    ring(SERVE_SPANS)
    run = _run(SERVE_STEPS)
    # 40 ms for a 512-token bucket = 80 ms a thousand; 40 of 200 ms
    assert span_ms_per_ktok.read(run, "serve/prefill", "bucket") == \
        pytest.approx(80.0)
    assert span_share_pct.read(run, "serve/prefill") == pytest.approx(20.0)
    assert span_ms_per_ktok.read(run, "serve/decode", "bucket") is None


# ---- the ring's clock on the trace's ---------------------------------
OFFSET = 5_000_000_000 + 123_456      # trace clock - perf_counter, ns


def _trace(busy_ms, window_ms, annotations):
    """One device busy over ``busy_ms`` ([start, end] on the HOST's clock,
    shifted here), under ``annotations`` given the same way."""
    start = np.asarray([a * MS + OFFSET for a, _ in busy_ms], np.float64)
    end = np.asarray([b * MS + OFFSET for _, b in busy_ms], np.float64)
    trace = reduce.Trace(
        labels=["fusion.1:fusion"], kinds=["xla"],
        ops=[reduce.DeviceLine(start, end - start,
                               np.zeros(len(start), np.int64))],
        annotations=[(n, a * MS + OFFSET, b * MS + OFFSET)
                     for n, a, b in annotations])
    assert trace.window == (window_ms[0] * MS + OFFSET,
                            window_ms[1] * MS + OFFSET)
    return trace


def test_clock_offset_is_recovered_from_the_step_annotations(ring):
    ring(SERVE_SPANS)
    # the trace also holds the step before the window's first (its
    # iteration began before the window did) and jitters by microseconds
    annotations = [("chipbench/submit", 900.0, 900.5),
                   ("chipbench/step", 900.5, 999.0),
                   ("chipbench/step", 1001.0, 1099.996),
                   ("chipbench/decode", 1005.0, 1006.0),
                   ("chipbench/step", 1102.0, 1199.998)]
    trace = _trace([(1010, 1090)], (900.0, 1199.998), annotations)
    fit = program_spans.clock_fit(_run(SERVE_STEPS, trace))
    assert fit["offset_ns"] == pytest.approx(OFFSET - 0.003 * MS,
                                             abs=0.002 * MS)
    assert fit["steps"] == 2 and fit["worst_ns"] <= 0.002 * MS
    assert program_spans.clock_offset_ns(_run(SERVE_STEPS, trace)) == \
        fit["offset_ns"]
    assert program_spans.clock_offset_ns(_run(SERVE_STEPS)) is None
    assert program_spans.clock_offset_ns(
        _run(SERVE_STEPS, _trace([(1010, 1090)], (1001.0, 1006.0), [
            ("chipbench/decode", 1001.0, 1006.0)]))) is None


def test_idle_is_shared_out_among_the_innermost_program_spans(ring):
    ring(SERVE_SPANS)
    annotations = [("chipbench/step", 1001.0, 1100.0),
                   ("chipbench/step", 1102.0, 1200.0)]
    # device busy 1006..1088, 1105.5..1149, 1155..1189; idle elsewhere
    trace = _trace([(1006, 1088), (1105.5, 1149), (1155, 1189)],
                   (1001.0, 1200.0), annotations)
    run = _run(SERVE_STEPS, trace)
    by_span = idle_by_span_pct.gaps_by_span(run)
    # the gaps, cut at the span boundaries (milliseconds):
    # 1001..1006    admit 2, decode/build 2, step 1
    # 1088..1105.5  decode/fetch 2, decode/sample 10, between the loops 2,
    #               admit 1, decode/build 1, step 1, decode/fetch 0.5
    # 1149..1155    decode/fetch 1, decode/sample 2, prefill/build 1,
    #               step (the prefill's) 2
    # 1189..1200    prefill/fetch 1, prefill/sample 2, decode/sample 8
    want_ms = {"serve/admit": 3, "serve/decode/build": 3, "serve/step": 4,
               "serve/decode/fetch": 3.5, "serve/decode/sample": 20,
               "_no_span_": 2, "serve/prefill/build": 1,
               "serve/prefill/fetch": 1, "serve/prefill/sample": 2}
    assert by_span == {k: pytest.approx(v / 1e3) for k, v in want_ms.items()}
    idle = reduce.window_seconds(trace) - reduce.busy_seconds(trace)
    assert sum(by_span.values()) == pytest.approx(idle) == \
        pytest.approx(0.0395)
    window = reduce.window_seconds(trace)
    sample = idle_by_span_pct.read(run, ["*/sample"])
    build = idle_by_span_pct.read(run, ["*/build", "serve/admit"])
    xfer = idle_by_span_pct.read(run, ["serve/step", "*/fetch"])
    assert sample == pytest.approx(100 * 0.022 / window)
    assert build == pytest.approx(100 * 0.007 / window)
    assert xfer == pytest.approx(100 * 0.0085 / window)
    from chipbench.reducers import idle_pct
    rest = 100 * 0.002 / window         # under no span: between the loops
    assert sample + build + xfer + rest == pytest.approx(idle_pct.read(run))


def test_idle_outside_every_span_stays_with_the_rest(ring):
    ring([_span(1, None, "serve/loop", 1001, 1050),
          _span(2, 1, "serve/decode/fetch", 1010, 1040)])
    trace = _trace([(1020, 1030)], (1001.0, 1100.0),
                   [("chipbench/step", 1001.0, 1100.0)])
    step = [{"t0": 1.0, "t1": 1.1, "step_s": 0.099}]
    by_span = idle_by_span_pct.gaps_by_span(_run(step, trace))
    # idle 1001..1020: 9 ms under the loop alone, 10 in the fetch; idle
    # 1030..1100: 10 in the fetch, 10 under the loop, 50 under no span
    assert by_span == {"serve/loop": pytest.approx(0.019),
                       "serve/decode/fetch": pytest.approx(0.020),
                       "_no_span_": pytest.approx(0.050)}


# ---- device time by phase --------------------------------------------
def test_phase_pct_reads_the_programs_table(monkeypatch):
    from deepspeed_tpu.monitor import telemetry
    table = {"fusion.1": "fwd", "fusion.2": "bwd", "fusion.2.remat": "remat",
             "flash_attention_dq.7": "bwd", "fusion.9": "optimizer"}
    asked = []
    monkeypatch.setattr(telemetry, "op_scopes",
                        lambda site=None: asked.append(site) or table)
    labels = ["fusion.1:fusion", "fusion.2:fusion", "fusion.2.remat:fusion",
              "flash_attention_dq.7:custom-call", "fusion.9:fusion",
              "copy.3:copy", "while.1:while"]
    # 10 ms each, back to back, then the loop over all of it (left out)
    start = np.arange(6) * 10.0 * MS
    line = reduce.DeviceLine(
        np.concatenate((start, [0.0])),
        np.concatenate((np.full(6, 10.0 * MS), [60.0 * MS])),
        np.arange(7))
    trace = reduce.Trace(labels=labels, kinds=["xla"] * 7, ops=[line],
                         annotations=[("chipbench/step", 0.0, 80.0 * MS)])
    run = _run([{"t0": 0.0, "t1": 0.08}], trace)
    phases = phase_pct.by_phase(run, "engine/train_step")
    assert phases == {"fwd": pytest.approx(0.01), "bwd": pytest.approx(0.02),
                      "remat": pytest.approx(0.01),
                      "optimizer": pytest.approx(0.01),
                      "other": pytest.approx(0.01)}
    assert phase_pct.read(run, "bwd") == pytest.approx(100 * 2 / 6)
    assert phase_pct.read(run, "loss_head") == 0.0
    assert sum(phase_pct.read(run, p) for p in phases) == pytest.approx(100)
    assert set(asked) == {"engine/train_step"}
    monkeypatch.setattr(telemetry, "op_scopes", lambda site=None: {})
    assert phase_pct.read(run, "bwd") is None       # nothing compiled there
    monkeypatch.delattr(telemetry, "op_scopes")
    assert phase_pct.read(run, "bwd") is None       # an older program
    assert phase_pct.read(_run([{"t0": 0.0, "t1": 0.08}]), "bwd") is None


# ---- the real metric files through toy cells -------------------------
@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tree.make(tmp_path_factory.mktemp("program_metrics_tree"))


def test_traced_toy_serving_run_reports_the_span_metrics(checkout):
    line, earlier = tree.run(checkout, "tiny-chat", trace=1)
    metrics = line["metrics"]
    for name in ("prefill_ms_per_ktok.chat", "prefill_share_pct.chat",
                 "loop_host_ms.chat", "decode_ms.chat"):
        assert metrics["tiny-chat." + name]["value"] > 0, name
    assert metrics["tiny-chat.prefill_share_pct.chat"]["value"] < 100
    # the host's own work is part of the step the benchmark times
    assert metrics["tiny-chat.loop_host_ms.chat"]["value"] < \
        metrics["tiny-chat.step_ms.chat"]["value"]
    # no device plane in a CPU trace: the device's readers find nothing
    assert "tiny-chat.ragged_pct.chat" not in metrics
    assert "tiny-chat.idle_pct.chat" not in metrics


def test_traced_toy_training_run_reports_the_span_metrics(checkout):
    line, _ = tree.run(checkout, "tiny-train", trace=1)
    metrics = line["metrics"]
    host = metrics["tiny-train.host_ms.train"]["value"]
    assert 0 < host <= metrics["tiny-train.step_ms.train"]["value"]
    assert "tiny-train.fwd_pct.train" not in metrics    # no device plane
