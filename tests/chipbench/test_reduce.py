"""``chipbench/reduce.py`` on a small trace recorded on a TPU v5e
(``data/small_trace.xplane.pb``: three rounds of a flash-attention
backward pass, a ragged paged-attention decode call and a matrix product,
under ``chipbench/...`` annotations) and on made-up intervals."""

import os
import sys
import types

import numpy as np
import pytest

from chipbench import cells, reduce
from chipbench.reducers import device_share_pct, kernel_roofline
from tree import DATA


@pytest.fixture(scope="module")
def trace():
    return reduce.load_xplane(os.path.join(DATA, "small_trace.xplane.pb"))


def test_parse_op():
    fusion = ("%fusion.3 = (bf16[2,8]{1,0:T(8,128)(2,1)}, f32[2]{0}) "
              "fusion(bf16[2,8]{1,0} %p.1), kind=kLoop, calls=%f")
    assert reduce.parse_op(fusion) == ("fusion.3", "fusion")
    assert reduce.parse_op("%a.1 = f32[8]{0} add(f32[8] %x, f32[8] %y)") \
        == ("a.1", "add")
    assert reduce.parse_op("fusion.815") == ("fusion.815", "")
    kernel = ('%k = bf16[8]{0} custom-call(bf16[8] %q), '
              'custom_call_target="tpu_custom_call"')
    assert reduce.op_kind(kernel) == "pallas"
    assert reduce.op_kind("%all-gather-start.2 = (f32[8]) "
                          "all-gather-start(f32[2] %x)") == "collective"
    assert reduce.op_kind(fusion) == "xla"


def test_busy_union_and_idle_share(trace):
    window, busy = reduce.window_seconds(trace), reduce.busy_seconds(trace)
    assert len(trace.ops) == 1 and len(trace.annotations) == 9
    assert window == pytest.approx(0.052494313)
    assert busy == pytest.approx(0.011257366, rel=1e-6)
    summed = sum(reduce.op_seconds(trace).values())
    assert busy <= summed * (1 + 1e-9)      # a union never exceeds the sum
    assert 0 < busy < window


def test_the_modules_line_has_one_event_an_executed_program(trace):
    """Three rounds of three jits: nine events under three names, each
    spanning operations of ``XLA Ops`` and none overlapping the next
    (``reducers/launch_order.py`` cuts the device's line by them)."""
    (line,) = trace.modules
    assert [n.partition("(")[0] for n in trace.module_names] == \
        ["jit__lambda"] * 3 and len(set(trace.module_names)) == 3
    assert list(line.label) == [0, 1, 2] * 3
    start, end = line.start, line.start + line.dur
    assert np.all(start[1:] >= end[:-1])
    ops = trace.ops[0]
    inside = (ops.start[:, None] >= start) & (ops.start[:, None] < end)
    assert np.all(inside.sum(axis=1) == 1)      # every operation in one
    assert np.all(inside.sum(axis=0) > 0)
    # the operations' labels are not the programs': two lists
    assert len(trace.labels) == 45 and not any(
        "jit_" in label for label in trace.labels)


def test_per_kernel_time(trace):
    kernels = reduce.op_seconds(trace, "pallas")
    # forward, dq and dk/dv of flash attention, and the ragged decode call
    assert len(kernels) == 4 and all(":custom-call" in k for k in kernels)
    assert sum(kernels.values()) == pytest.approx(
        reduce.busy_seconds(trace, "pallas"), rel=1e-6)
    assert reduce.collective_seconds(trace) == (0.0, 0.0)
    top = reduce.breakdown(trace, top=3)
    assert len(top["device_ops"]) == 3
    assert top["device_ops"][0][1] >= top["device_ops"][1][1]


def test_gap_attribution(trace):
    gaps = reduce.idle_gaps(trace)
    idle = reduce.window_seconds(trace) - reduce.busy_seconds(trace)
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    # the script slept outside any annotation between rounds
    assert max(gaps, key=gaps.get) == "_no_annotation_"
    assert {"chipbench/step", "chipbench/mm"} <= set(gaps)


def test_collectives_and_exposure_on_made_up_intervals():
    t = reduce.Trace(labels=["c:fusion", "ag:all-gather", "ar:all-reduce-start"],
                     kinds=["xla", "collective", "collective"])
    ns = lambda *xs: np.asarray(xs, np.float64) * 1e9   # noqa: E731
    # compute 0-4 and 6-8; a synchronous all-gather 4-6 (all exposed)
    t.ops.append(reduce.DeviceLine(ns(0, 4, 6), ns(4, 2, 2),
                                   np.asarray([0, 1, 0])))
    # an asynchronous all-reduce 7-9: one second under compute, one bare
    t.async_ops.append(reduce.DeviceLine(ns(7), ns(2), np.asarray([2])))
    t.annotations = [("chipbench/step", 0.0, 10e9)]
    total, exposed = reduce.collective_seconds(t)
    assert total == pytest.approx(4.0) and exposed == pytest.approx(3.0)
    assert reduce.busy_seconds(t) == pytest.approx(8.0)
    assert reduce.idle_gaps(t) == {"chipbench/step": pytest.approx(2.0)}


# the recorded trace's four Pallas calls by name, seconds as recorded: the
# three flash-attention kernels of a backward pass (``jvp__``,
# ``transpose_jvp___`` twice) and the ragged decode call (``_lambda_``);
# the program's kernels carry fixed names since, the mechanism is the same
KERNEL_SECONDS = {None: 0.007991031, "transpose_jvp_": 0.003236301,
                  "_lambda_": 0.003878, "jvp__": 0.00087673}


@pytest.mark.parametrize("kernel", list(KERNEL_SECONDS) + ["no_such_"])
def test_kernel_readers_pick_a_kernel_by_name(trace, kernel, monkeypatch):
    """``kernel_roofline`` and ``device_share_pct`` with ``kernel``: one of
    the trace's kernels by the beginning of its name; without it, every
    Pallas call, as before the argument was there."""
    monkeypatch.setitem(sys.modules, "chipbench.costs.a_millisecond",
                        types.SimpleNamespace(least_seconds=lambda run: 1e-3))
    run = cells.Run(chips=1, peaks={}, model={}, steps=[{}], traced_steps=[{}],
                    samples={}, counters={}, memory_peak_bytes=0, trace=trace)
    args = {} if kernel is None else {"kernel": kernel}
    roofline = kernel_roofline.read(run, "a_millisecond", **args)
    share = device_share_pct.read(run, "pallas", **args)
    if kernel == "no_such_":
        assert roofline is None and share is None
        return
    seconds = KERNEL_SECONDS[kernel]
    assert roofline == pytest.approx(100.0 * 1e-3 / seconds, rel=1e-9)
    # the kernels do not overlap on the device's line: union = sum
    assert share == pytest.approx(
        100.0 * seconds / reduce.busy_seconds(trace), rel=1e-6)
    # (the first forward call began before the first annotation)
    assert reduce.op_count(trace, "pallas", kernel) == \
        {None: 11, "transpose_jvp_": 6, "_lambda_": 3, "jvp__": 2}[kernel]
    if kernel is None:      # today's reading, to the digit
        assert roofline == 100.0 * 1e-3 / sum(
            reduce.op_seconds(trace, "pallas").values())
        assert share == 100.0 * reduce.busy_seconds(trace, "pallas") / \
            reduce.busy_seconds(trace)
        outside = device_share_pct.read(run, "pallas", outside=True)
        assert outside == 100.0 - share
