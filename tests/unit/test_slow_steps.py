"""The slow-step record (PR 54; docs/telemetry.md "The slow-step record"):
the judge at a step's close, the sampler behind it and the record they
leave, on clocks the tests hand in.  No test here compiles a program or
races the wall clock."""

import importlib.util
import json
import os
import threading
import time

import pytest

from deepspeed_tpu.monitor import telemetry as telemetry_module
from deepspeed_tpu.monitor.telemetry import (
    SAMPLE_EVERY_NS, SAMPLES_MAX, SLOW_STEP_EXCESS_NS, SLOW_STEP_MEDIANS,
    SLOW_STEP_MIN_KIND, SLOW_STEP_WHERE, SLOW_STEPS_KEPT, StepStallWatchdog,
    Telemetry, read_machine, read_tasks, where_of)
from deepspeed_tpu.runtime.config import TelemetryConfig

MS = 1_000_000
S = 1_000_000_000
DECODE = ("engine", ("decode", 16, 1))
PREFILL = ("engine", ("prefill", 1, 16384), ("decode", 16, 1))


def _fed(kind, median_ns, n=SLOW_STEP_MIN_KIND, tel=None):
    """A watchdog whose ``kind`` has ``n`` steps of ``median_ns``."""
    wd = (tel or Telemetry()).watchdog
    for i in range(n):
        assert wd.judge("step", i, kind, i * S, i * S + median_ns) is None
    return wd


# ----------------------------------------------------------------------
# the judge
# ----------------------------------------------------------------------
def test_the_constants_are_the_issues():
    assert (SLOW_STEP_MEDIANS, SLOW_STEP_EXCESS_NS, SLOW_STEP_MIN_KIND) == \
        (3, 250 * MS, 8)
    assert (SLOW_STEPS_KEPT, SAMPLE_EVERY_NS, SAMPLES_MAX) == \
        (64, 50 * MS, 200)


@pytest.mark.parametrize("median_ms, took_ms, slow", [
    (410, 1200, False),     # under three medians (the mellum cell's step)
    (410, 1900, True),      # the stalls that cell met: 1.9-3.4 s
    (5, 200, False),        # forty medians, but under a quarter second
    (5, 300, True),         # over the median by a quarter second
])
def test_both_thresholds_at_the_records_own_numbers(median_ms, took_ms, slow):
    wd = _fed(("engine", "train"), median_ms * MS)
    record = wd.judge("engine/train_batch", 8, ("engine", "train"),
                      100 * S, 100 * S + took_ms * MS, cpu_ns=3 * MS)
    assert (record is not None) == slow
    assert len(wd.records) == int(slow)
    if slow:
        assert record["median_ns"] == median_ms * MS
        assert record["t1_ns"] - record["t0_ns"] == took_ms * MS
        assert record["kind"] == "train" and record["key"] == 8


@pytest.mark.parametrize("took_ms, slow", [(447, False), (1500, True)])
def test_a_loop_is_compared_with_its_own_kind(took_ms, slow):
    """A loop that holds a 16,384-row prefill is not slow beside the
    decode-only loops (mixedq: 7.6 ms and 0.447 s)."""
    wd = _fed(DECODE, int(7.6 * MS), n=40)
    for i in range(SLOW_STEP_MIN_KIND):
        assert wd.judge("serve/loop", None, PREFILL, (50 + i) * S,
                        (50 + i) * S + 447 * MS) is None
    record = wd.judge("serve/loop", None, PREFILL, 90 * S,
                      90 * S + took_ms * MS)
    assert (record is not None) == slow
    if slow:
        assert record["kind"] == "prefill:1x16384+decode:16x1"
        assert record["median_ns"] == 447 * MS
    # the same 0.447 s in a decode-only loop is sixty medians
    assert wd.judge("serve/loop", None, DECODE, 95 * S,
                    95 * S + 447 * MS)["kind"] == "decode:16x1"


def test_under_eight_steps_of_a_kind_nothing_is_judged():
    wd = _fed(DECODE, 5 * MS, n=SLOW_STEP_MIN_KIND - 1)
    assert wd.judge("serve/loop", None, DECODE, 20 * S, 30 * S) is None
    assert not wd.records
    # that long step was the eighth; the median of the eight is still 5 ms
    assert wd.judge("serve/loop", None, DECODE, 40 * S, 50 * S) is not None


def test_the_wait_follows_the_largest_median_of_any_kind():
    wd = _fed(DECODE, 8 * MS)
    assert wd._wait_ns == SLOW_STEP_EXCESS_NS + 3 * 8 * MS
    _fed(PREFILL, 447 * MS, tel=wd.telemetry)
    assert wd._wait_ns == SLOW_STEP_EXCESS_NS + 3 * 447 * MS


def test_the_store_keeps_the_newest_records():
    wd = _fed(DECODE, 5 * MS)
    for i in range(SLOW_STEPS_KEPT + 6):
        wd.judge("serve/loop", i, DECODE, (100 + i) * S, (101 + i) * S)
        for _ in range(3):      # one loop in four: the median stays
            wd.judge("serve/loop", None, DECODE, 0, 5 * MS)
    kept = wd.telemetry.slow_steps()
    assert len(kept) == SLOW_STEPS_KEPT and kept[-1]["key"] == 69
    assert [r["key"] for r in wd.telemetry.slow_steps(
        since_ns=160 * S, until_ns=163 * S)] == [60, 61, 62]


# ----------------------------------------------------------------------
# the step spans
# ----------------------------------------------------------------------
def test_a_training_period_that_nothing_closes_leaves_no_record():
    tel = Telemetry()
    owner = object()
    for step in range(SLOW_STEP_MIN_KIND + 2):
        with tel.step_span("engine/train_batch", step=step, period=True,
                           owner=owner):
            pass
    steps, median, n = tel.watchdog._kinds[(id(owner), "train")]
    assert n == SLOW_STEP_MIN_KIND + 1      # the last period is open
    # however long the caller stays away, and whoever else opens a step
    tel.watchdog._local.period.t0 -= 3600 * S
    with tel.step_span("engine/train_batch", step=0, period=True,
                       owner=object()):
        pass
    assert not tel.slow_steps()
    assert tel.watchdog._kinds[(id(owner), "train")][2] == n


def test_a_period_holds_the_callers_wait_and_says_what_lay_outside(
        monkeypatch):
    # the account is the process's: whatever another test of this worker
    # compiled in the last two seconds is not this step's
    monkeypatch.setattr(telemetry_module, "_account",
                        telemetry_module.CompileAccount())
    tel = Telemetry()
    owner = object()
    for step in range(SLOW_STEP_MIN_KIND + 1):
        with tel.step_span("engine/train_batch", step=step, period=True,
                           owner=owner):
            with tel.span("engine/dispatch", step=step):
                pass
    # the caller waited two seconds for the loss of the last step
    tel.watchdog._local.period.t0 -= 2 * S
    with tel.step_span("engine/train_batch", step=9, period=True,
                       owner=owner):
        pass
    (record,) = tel.slow_steps()
    assert record["name"] == "engine/train_batch" and record["key"] == 8
    assert record["t1_ns"] - record["t0_ns"] >= 2 * S
    assert [s["name"] for s in record["spans"]] == ["engine/train_batch",
                                                    "engine/dispatch"]
    own = record["spans"][0]
    assert record["outside_ns"] == \
        record["t1_ns"] - record["t0_ns"] - (own["t1_ns"] - own["t0_ns"])
    assert record["where"] == "caller"
    assert "dispatches" not in record


def test_child_spans_and_the_ring_are_what_they_were():
    """The same spans through ``span`` and through ``step_span`` leave the
    same ring: names, nesting, keys and attributes."""
    def drive(tel, outer):
        report = {"dispatches": []}
        for i in range(3):
            with outer(tel, report):
                with tel.span("serve/admit"):
                    pass
                with tel.span("serve/decode", attrs={"n": i}):
                    with tel.span("serve/step", req_id=f"r{i}"):
                        pass
        first = tel.spans()[0].id
        return [(s.id - first, s.parent and s.parent - first, s.name,
                 s.key, s.attrs) for s in tel.spans()]

    plain = drive(Telemetry(), lambda tel, report: tel.span("serve/loop"))
    judged = drive(Telemetry(), lambda tel, report: tel.step_span(
        "serve/loop", report=report, owner=report))
    assert plain == judged and len(plain) == 12
    tel = Telemetry()
    with tel.step_span("serve/loop", report={"dispatches": []}):
        pass
    (span,) = tel.ring._spans
    assert len(span) == 7 and span[1] is None and span[5:] == (None, None)
    assert tel.watchdog.armed is None


def test_a_loops_kind_is_what_it_launched():
    tel = Telemetry()
    wd = tel.watchdog
    report = {"dispatches": [
        {"phase": "prefill", "batch": 1, "tokens": 512, "t0_ns": 0},
        {"phase": "decode", "batch": 16, "tokens": 1,
         "t0_ns": time.perf_counter_ns() + 3600 * S}]}
    owner = object()
    with tel.step_span("serve/loop", report=report, owner=owner) as span:
        assert wd.armed[:2] == (span.id, span.t0)
        assert wd.armed[2] == span.t0 + wd._wait_ns
        assert wd.armed[3] == threading.get_ident()
    # the prefill ran inline in add_request, ahead of the loop
    assert list(wd._kinds) == [(id(owner), ("decode", 16, 1))]


# ----------------------------------------------------------------------
# the sampler
# ----------------------------------------------------------------------
class Machine:
    """Stands in for ``/proc``: canned tasks and counters, and how often
    each was read."""

    def __init__(self):
        self.tasks = {11: ("python", "S", 100, 0), 12: ("tpu-rt", "R", 50, 0)}
        self.counters = {"steal_s": 1.0, "iowait_s": 2.0, "load1": 0.5}
        self.read = {"tasks": 0, "machine": 0}

    def read_tasks(self, only=None, first=(), budget_ns=None):
        self.read["tasks"] += only is None
        return {tid: task for tid, task in self.tasks.items()
                if only is None or tid in only}

    def read_machine(self, tid=None):
        self.read["machine"] += 1
        return dict(self.counters)


def _sampler(median_ns=5 * MS):
    wd = _fed(DECODE, median_ns)
    machine = Machine()
    wd.read_tasks, wd.read_machine = machine.read_tasks, machine.read_machine
    wd.process_cpu_ns = lambda: 0
    return wd, machine


def test_the_sampler_sleeps_while_no_step_is_late():
    """Ten seconds of 5 ms loops on the injected clock: the sampler wakes
    about four times a second (at the deadline of the step that was armed
    when it last looked, which has long closed), reads no thread's state
    and samples nothing."""
    wd, machine = _sampler()
    now, wake_at, span_id = 0, 0, 0
    while now < 10 * S:
        if now >= wake_at:
            wake_at = wd.wake(now)
        span_id += 1
        wd.armed = (span_id, now, now + wd._wait_ns, 1)     # a loop opens
        now += 5 * MS
        wd.armed = None                                     # ... and closes
        wd.judge("serve/loop", None, DECODE, now - 5 * MS, now)
    assert 36 <= wd.wakes <= 40
    assert wd._sampled is None and not wd.records
    assert machine.read == {"tasks": 0, "machine": 0}
    # with no step armed it sleeps one whole wait
    assert wd.wake(now) == now + wd._wait_ns


def _elsewhere(fn, *args):
    """``fn(*args)`` on a thread of its own, as the sampler runs: a sample
    holds every thread's stack but the sampler's."""
    out = []
    thread = threading.Thread(target=lambda: out.append(fn(*args)))
    thread.start()
    thread.join()
    return out[0]


def test_a_late_step_is_sampled_until_it_closes_and_the_record_has_it():
    wd, machine = _sampler()
    t0 = 100 * S
    wd.armed = (77, t0, t0 + wd._wait_ns, threading.get_ident())
    assert wd.wake(t0 + 10 * MS) == t0 + wd._wait_ns    # not late yet
    now = t0 + wd._wait_ns
    for i in range(5):
        machine.tasks[12] = ("tpu-rt", "R", 50 + 4 * i, 0)
        assert _elsewhere(wd.wake, now) == now + SAMPLE_EVERY_NS
        now += SAMPLE_EVERY_NS
    assert machine.read == {"tasks": 5, "machine": 1}
    machine.counters.update(steal_s=1.25, load1=3.0)
    wd.armed = None
    record = wd.judge("serve/loop", None, DECODE, t0, now, cpu_ns=2 * MS,
                      span_id=77)
    assert len(record["samples"]) == 5
    assert [s["t_ns"] for s in record["samples"]] == [
        t0 + wd._wait_ns + i * SAMPLE_EVERY_NS for i in range(5)]
    me = threading.current_thread().name
    assert record["thread"] == me
    stack = record["samples"][0]["stacks"][me]
    (waiting,) = [f for f in stack if f.endswith(" _elsewhere")]
    assert len(stack) <= 12 and "test_slow_steps.py:" in waiting
    # a stack that did not move is kept once
    assert record["samples"][1]["stacks"][me] is \
        record["samples"][2]["stacks"][me]
    # CPU over the sampled part by native thread, the busiest first
    assert record["threads"][0] == {"tid": 12, "comm": "tpu-rt",
                                    "python": None, "cpu_s": 0.16}
    assert record["machine"] == {"steal_s": 0.25, "iowait_s": 0.0,
                                 "load1": 3.0}
    # the next look finds the step closed and lets go of the samples
    wd.wake(now)
    assert wd._sampled is None


def test_the_samples_of_a_step_that_was_not_slow_are_dropped():
    wd, _ = _sampler()
    _fed(PREFILL, 447 * MS, tel=wd.telemetry)
    t0 = 200 * S
    wd.armed = (5, t0, t0 + 260 * MS, 1)    # armed before the kind was known
    wd.wake(t0 + 260 * MS)
    wd.wake(t0 + 310 * MS)
    assert len(wd._sampled.samples) == 2
    wd.armed = None
    assert wd.judge("serve/loop", None, PREFILL, t0, t0 + 450 * MS,
                    span_id=5) is None
    wd.wake(t0 + 460 * MS)
    assert wd._sampled is None and not wd.records


def test_a_step_gets_at_most_two_hundred_samples():
    wd, machine = _sampler()
    wd.armed = (9, 0, wd._wait_ns, 1)
    now = wd._wait_ns
    for _ in range(SAMPLES_MAX + 20):
        now = wd.wake(now)
    assert len(wd._sampled.samples) == SAMPLES_MAX
    assert machine.read["tasks"] == SAMPLES_MAX


def test_the_sampler_notes_how_late_it_woke():
    """A process stopped for a second: the sampler meant to wake at the
    deadline and woke a second later, and nobody of the process ran."""
    wd, _ = _sampler()
    t0 = 300 * S
    wd.armed = (3, t0, t0 + wd._wait_ns, threading.get_ident())
    assert wd.wake(t0) == t0 + wd._wait_ns
    woke = t0 + wd._wait_ns + 1 * S
    wd.wake(woke)
    (sample,) = wd._sampled.samples
    assert sample["late_ns"] == 1 * S
    wd.armed = None
    record = wd.judge("serve/loop", None, DECODE, t0, woke + MS, span_id=3)
    assert [n["late_ns"] for n in record["late"]] == [1 * S]
    assert record["late"][0]["process_cpu_ns"] == 0
    assert record["where"] == "descheduled"


def test_the_thread_sleeps_on_its_event_and_goes_with_its_telemetry():
    tel = Telemetry()
    wd = tel.watchdog.start()
    thread = wd._thread
    assert thread.daemon and thread.name == "ds-stall-watchdog"
    assert wd.start()._thread is thread         # one thread a telemetry
    wd.stop()
    assert not thread.is_alive() and wd._thread is None


# ----------------------------------------------------------------------
# the record's ``where``
# ----------------------------------------------------------------------
ME, TID = "MainThread", 11
JAX = ("/site-packages/jax/_src/array.py:630 _value",
       "/repo/chipbench/train_cell.py:86 step")
OWN = ("/repo/chipbench/train_cell.py:86 step",)


def _canned(state="S", stack=JAX, n=6, **fields):
    """A record of a 1.5 s step on a median of 0.4 s (excess 1.1 s) whose
    ``n`` samples all show the stepping thread in ``state`` at ``stack``."""
    samples = [{"t_ns": 10 * S + i * SAMPLE_EVERY_NS, "late_ns": 0,
                "span": None, "stacks": {ME: stack},
                "python": {ME: TID},
                "tasks": {TID: ("python", state, 100, 0)}}
               for i in range(n)]
    record = {"name": "engine/train_batch", "key": 7, "kind": "train",
              "t0_ns": 9 * S, "t1_ns": 9 * S + 1500 * MS,
              "median_ns": 400 * MS, "cpu_ns": 5 * MS, "thread": ME,
              "tid": TID, "spans": [], "compiles": [], "samples": samples,
              "threads": [], "machine": {}, "late": []}
    record.update(fields)
    return record


def _with(record, **per_sample):
    for sample in record["samples"]:
        for key, value in per_sample.items():
            sample[key].update(value)
    return record


def _faulted():
    record = _canned(stack=OWN, n=2)
    record["samples"][1]["tasks"] = {TID: ("python", "S", 100, 4)}
    return record


LATE = {"from_ns": 9 * S + 300 * MS, "to_ns": 9 * S + 1400 * MS,
        "late_ns": 1100 * MS}
CASES = {
    "compile": _canned(compiles=[{"name": "jit_step", "site": None}],
                       cpu_ns=1400 * MS),
    "host_python": _canned(state="R", stack=OWN, cpu_ns=600 * MS),
    "descheduled": _canned(n=1, late=[dict(LATE, process_cpu_ns=10 * MS,
                                           threads={ME: 0})]),
    "blocked_io": _canned(state="D", stack=OWN),
    "runtime_wait": _canned(),
    "other_thread": _with(
        _canned(stack=OWN),
        stacks={"loader": ("/repo/data.py:9 collate",)},
        python={"loader": 12}, tasks={12: ("python", "R", 7, 0)}),
    "caller": _canned(stack=OWN, outside_ns=1450 * MS),
    "unknown": _canned(stack=OWN, outside_ns=20 * MS),
}


def test_where_is_one_of_the_words_and_the_checker_has_them(checker):
    assert tuple(CASES) == SLOW_STEP_WHERE == tuple(checker.SLOW_STEP_WHERE)


@pytest.mark.parametrize("word", SLOW_STEP_WHERE)
def test_where_by_the_rules(word):
    assert where_of(CASES[word]) == word


@pytest.mark.parametrize("word, record", [
    # runnable in most samples with no CPU to show, and the machine's
    # steal rose or the thread was switched out against its will
    ("descheduled", _canned(state="R", stack=OWN,
                            machine={"steal_s": 0.8})),
    ("descheduled", _canned(state="R", stack=OWN,
                            machine={"nonvoluntary": 3})),
    ("unknown", _canned(state="R", stack=OWN, machine={"steal_s": 0.0})),
    # the sampler overslept while another Python thread burned the CPU:
    # that thread held the interpreter's lock
    ("other_thread", _canned(n=1, stack=OWN, late=[dict(
        LATE, process_cpu_ns=1050 * MS,
        threads={ME: 0, "hog": 1000 * MS})])),
    # ... while the stepping thread itself did: its own Python code
    ("host_python", _canned(n=1, stack=OWN, cpu_ns=1000 * MS, late=[dict(
        LATE, process_cpu_ns=1050 * MS, threads={ME: 1000 * MS})])),
    # a sampler that was a little late says nothing
    ("runtime_wait", _canned(late=[dict(LATE, late_ns=60 * MS,
                                        process_cpu_ns=0, threads={})])),
    # the thread's own major faults rose between two samples
    ("blocked_io", _faulted()),
    # no sample at all: by the CPU time and the spans alone
    ("caller", _canned(n=0, outside_ns=1400 * MS)),
    ("unknown", _canned(n=0)),
])
def test_where_beyond_the_first_case_of_each_word(word, record):
    assert where_of(record) == word


def test_the_records_fields():
    tel = Telemetry()
    report = {"dispatches": [{"phase": "decode", "batch": 16, "tokens": 1,
                              "t0_ns": 10 * S + 1}]}
    wd = _fed(DECODE, 5 * MS, tel=tel)
    record = wd.judge("serve/loop", None, DECODE, 10 * S, 11 * S,
                      cpu_ns=4 * MS, span_id=None, report=report)
    assert set(record) == {
        "name", "key", "kind", "t0_ns", "t1_ns", "median_ns", "cpu_ns",
        "thread", "tid", "spans", "compiles", "dispatches", "samples",
        "threads", "machine", "late", "where"}
    assert record["dispatches"] == report["dispatches"]
    assert record["dispatches"][0] is not report["dispatches"][0]
    assert (record["samples"], record["threads"], record["machine"]) == \
        ([], [], {})
    assert record["where"] in SLOW_STEP_WHERE
    assert tel.slow_steps() == [record]
    assert Telemetry().slow_steps() == []       # a store a telemetry


def test_a_compile_inside_the_step_is_in_the_record():
    wd = _fed(DECODE, 5 * MS)
    compiled = {"t0_ns": 10 * S, "t1_ns": 10 * S + 900 * MS,
                "name": "jit_serve_decode", "trace_s": 0.1, "lower_s": 0.1,
                "backend_s": 0.7, "cache": "miss", "site": "serve/step_fn",
                "shapes": None, "repeat": True, "span": "serve/step",
                "span_attrs": None, "span_ids": ()}
    telemetry_module._account.records.append(compiled)
    try:
        record = wd.judge("serve/loop", None, DECODE, 10 * S - MS, 11 * S)
    finally:
        telemetry_module._account.records.remove(compiled)
    assert record["where"] == "compile"
    assert record["compiles"] == [{
        "name": "jit_serve_decode", "site": "serve/step_fn",
        "cache": "miss", "t0_ns": 10 * S, "t1_ns": 10 * S + 900 * MS,
        "span": "serve/step"}]


# ----------------------------------------------------------------------
# /proc
# ----------------------------------------------------------------------
PROC = {
    "/proc/stat": "cpu  100 0 50 1000 30 0 5 70 0 0\ncpu0 1 2 3\n",
    "/proc/vmstat": "pgmajfault 12\nallocstall_normal 2\n"
                    "allocstall_movable 3\ncompact_stall 4\nnr_foo 9\n",
    "/proc/pressure/cpu": "some avg10=0.00 avg60=0.00 total=1234\n",
    "/proc/pressure/memory": "some avg10=0.00 total=55\nfull total=1\n",
    "/proc/pressure/io": "some avg10=0.00 total=66\n",
    "/proc/loadavg": "1.50 0.70 0.30 2/345 678\n",
    "/proc/self/task/11/status": "Name:\tpython\n"
                                 "voluntary_ctxt_switches:\t40\n"
                                 "nonvoluntary_ctxt_switches:\t7\n",
}
WHOLE = {"iowait_s": 0.3, "steal_s": 0.7, "pgmajfault": 12, "allocstall": 5,
         "compact_stall": 4, "pressure_cpu_us": 1234,
         "pressure_memory_us": 55, "pressure_io_us": 66, "load1": 1.5,
         "voluntary": 40, "nonvoluntary": 7}
FIELDS_OF = {"/proc/stat": ("iowait_s", "steal_s"),
             "/proc/vmstat": ("pgmajfault", "allocstall", "compact_stall"),
             "/proc/pressure/cpu": ("pressure_cpu_us",),
             "/proc/pressure/memory": ("pressure_memory_us",),
             "/proc/pressure/io": ("pressure_io_us",),
             "/proc/loadavg": ("load1",),
             "/proc/self/task/11/status": ("voluntary", "nonvoluntary")}


@pytest.mark.parametrize("unreadable", [None, *PROC])
def test_an_unreadable_file_leaves_its_fields_out(monkeypatch, unreadable):
    monkeypatch.setattr(telemetry_module, "_TICK_S", 0.01)
    monkeypatch.setattr(telemetry_module, "_read", lambda path: None
                        if path == unreadable else PROC.get(path))
    expected = {k: v for k, v in WHOLE.items()
                if k not in FIELDS_OF.get(unreadable, ())}
    assert read_machine(11) == pytest.approx(expected)
    assert set(read_machine(11)) == set(expected)       # never a 0 instead


def test_this_processs_own_threads_can_be_read():
    tasks = read_tasks()
    me = tasks[threading.get_native_id()]
    assert me[1] == "R" and me[2] >= 0 and isinstance(me[0], str)
    assert set(read_tasks(only=[threading.get_native_id(), 2 ** 30])) == {
        threading.get_native_id()}
    assert read_tasks(budget_ns=-1, first=[threading.get_native_id()]).get(
        None, "partial") == "partial"
    assert isinstance(read_machine(threading.get_native_id()), dict)


# ----------------------------------------------------------------------
# what goes out: the warning line, and with telemetry enabled the event
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def checker():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "scripts", "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("checker", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_a_record_says_why_on_standard_error_with_telemetry_off(monkeypatch):
    lines = []
    monkeypatch.setattr(telemetry_module.logger, "warning", lines.append)
    wd = _fed(("engine", "train"), 490 * MS)
    wd.judge("engine/train_batch", 31, ("engine", "train"), 60 * S,
             60 * S + 2300 * MS, cpu_ns=12 * MS, period=True)
    (line,) = lines
    for said in ("slow step: engine/train_batch 31 [train]", "took 2.300s",
                 "median of its kind 0.4900s", "0.012s on the CPU",
                 "no program span", "t0_ns 60000000000"):
        assert said in line
    assert line.split(": ")[2].split(";")[0] in SLOW_STEP_WHERE


def test_a_two_second_step_among_steps_of_0_41_emits_the_stall_event(
        tmp_path, checker):
    """``train-mellum2-12b-ep4-s8192`` runs with telemetry enabled and
    ``stall_watchdog`` off, as here: the old verdict asked for max(10 x
    0.41 s, 1 s) = 4.1 s and missed the 1.9-3.4 s steps that cell met."""
    tel = Telemetry().configure(TelemetryConfig({
        "enabled": True, "output_path": str(tmp_path), "job_name": "slow",
        "stall_watchdog": False, "incidents": {"enabled": True}}), rank=0)
    wd = _fed(("engine", "train"), 410 * MS, n=12, tel=tel)
    assert not wd.hangs
    record = wd.judge("engine/train_batch", 12, ("engine", "train"),
                      50 * S, 52 * S, cpu_ns=6 * MS, period=True)
    bundles = tel.incidents.bundle_dir
    tel.close()
    path = os.path.join(str(tmp_path), "slow", "events.jsonl")
    with open(path) as f:
        events = [json.loads(line) for line in f]
    (stall,) = [e for e in events if e["kind"] == "stall"]
    assert stall["name"] == "engine/train_batch" and stall["step"] == 12
    assert stall["gap_s"] == 2.0 and stall["median_step_s"] == 0.41
    assert stall["threshold_s"] == pytest.approx(1.23)
    assert stall["where"] == record["where"] and stall["cpu_s"] == 0.006
    assert checker.validate_file(path) == []
    assert [n for n in os.listdir(bundles) if n.endswith("-stall")]
    # the old verdict on the same beats: four seconds, so nothing
    old = StepStallWatchdog(Telemetry(), stall_factor=10.0,
                            min_stall_secs=1.0)
    for i in range(12):
        old.beat(i, now=i * 0.41)
    assert not old.check(now=11 * 0.41 + 2.0)
    assert old.check(now=11 * 0.41 + 4.2)


def test_the_checker_refuses_a_where_it_does_not_know(checker):
    event = {"ts": 1.0, "kind": "stall", "name": "serve/loop", "step": 3,
             "gap_s": 1.0, "median_step_s": 0.005, "threshold_s": 0.255,
             "where": "descheduled", "cpu_s": 0.002, "span": "serve/step"}
    assert checker.validate_event(event) == []
    assert checker.validate_event(dict(event, where="gremlins")) == [
        "stall: unknown where 'gremlins'"]


def test_an_engine_sets_what_the_hang_verdict_goes_by():
    tel = Telemetry()
    wd = tel.watchdog
    assert not wd.hangs and wd._thread is None
    assert wd.configure(hangs=True, stall_factor=2.0,
                        min_stall_secs=0.5) is wd
    wd.beat(0, now=1.0)
    wd.beat(1, now=2.0)
    wd.beat(2, now=3.0)
    assert wd.check(now=6.0)
    wd.configure(hangs=False)
    assert not wd.hangs and wd.median_step_secs() is None
