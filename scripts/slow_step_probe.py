#!/usr/bin/env python3
"""Does a stalled step say why?  A short serving loop and a short trainer
loop on the chat cell's configuration (``chipbench/configs/olmo2-1b.json``;
the trainer holds the first ``--train-layers`` of its 16 layers, so that
its state fits one chip beside nothing else), each with stalls brought on
from OUTSIDE the program, and the slow-step records the program left
(``get_telemetry().slow_steps()``, docs/telemetry.md "The slow-step
record") beside the ``where`` each was built to have:

both loops
  * ``other_thread``: a second Python thread holds the interpreter's lock
    for the stall (one C call, ``sum(range(n))``, which never lets go);
  * ``descheduled``: a child process stops this one with ``SIGSTOP`` and
    sends ``SIGCONT`` a stall later (the sampler oversleeps with the rest
    of the process: its ``late_ns``);
the serving loop
  * a ``time.sleep`` of a stall BETWEEN two ``serve/loop`` spans: pacing,
    which must leave NO record;
  * ``host_python``: a stall of Python arithmetic inside a wrapper of
    ``engine._sample`` on the stepping thread (innermost span
    ``serve/decode/sample``);
the trainer loop
  * ``caller``: a ``time.sleep`` of a stall between two steps, in the
    caller's code.

It also times the judge: one outermost span through ``Telemetry.span`` and
through ``Telemetry.step_span``, in nanoseconds.

    python3 scripts/slow_step_probe.py --loop serve     # one process a loop:
    python3 scripts/slow_step_probe.py --loop train     # the chip is one's
    JAX_PLATFORMS=cpu python3 scripts/slow_step_probe.py --toy   # rehearsal

One line a record, one JSON line at the end (``ok`` true when every record
is there with its ``where``, the stepping thread's stack in its samples
and a native thread that is not Python's among its ``threads``)."""

import argparse
import gc
import json
import os
import signal
import subprocess
import sys
import threading
import time
import timeit

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STOPPER = """
import os, signal, sys, time
pid, wait, stall = int(sys.argv[1]), float(sys.argv[2]), float(sys.argv[3])
time.sleep(wait)
os.kill(pid, signal.SIGSTOP)
try:
    time.sleep(stall)
finally:
    os.kill(pid, signal.SIGCONT)
"""


class Hog(threading.Thread):
    """A Python thread that, when told, holds the interpreter's lock for
    ``seconds``: ``sum(range(n))`` is one C call."""

    def __init__(self):
        super().__init__(name="hog", daemon=True)
        self.go, self.seconds = threading.Event(), 1.0
        t0 = time.perf_counter()
        sum(range(5_000_000))
        self.per_second = 5_000_000 / (time.perf_counter() - t0)
        self.start()

    def run(self):
        while True:
            self.go.wait()
            self.go.clear()
            sum(range(int(self.per_second * self.seconds)))

    def hold(self, seconds):
        self.seconds = seconds
        self.go.set()


def stop_me(seconds):
    """A child that stops this process in 0.2 s for ``seconds``."""
    return subprocess.Popen([sys.executable, "-c", STOPPER, str(os.getpid()),
                             "0.2", str(seconds)])


def busy(seconds):
    """Python arithmetic on the calling thread."""
    end, x = time.perf_counter() + seconds, 0
    while time.perf_counter() < end:
        x += 1
    return x


def judge_cost_ns(tel_class):
    tel = tel_class()
    report = {"dispatches": [{"phase": "decode", "batch": 32, "tokens": 1,
                              "t0_ns": 2 ** 62}]}

    def plain():
        with tel.span("serve/loop"):
            pass

    def judged():
        with tel.step_span("serve/loop", report=report, owner=report):
            pass

    ns = {f.__name__: min(timeit.repeat(f, number=20000, repeat=5)) / 20000
          * 1e9 for f in (plain, judged, plain, judged)}
    return {"span_ns": round(ns["plain"], 1),
            "step_span_ns": round(ns["judged"], 1),
            "judge_ns": round(ns["judged"] - ns["plain"], 1)}


def describe(loop, expected, record, stall_s):
    """One line a record, and whether it is what it was built to be."""
    if record is None:
        print(f"{loop} {expected}: NO RECORD", flush=True)
        return False
    me = record["thread"]
    stacks = [s["stacks"].get(me) for s in record["samples"]]
    native = [t for t in record["threads"] if t["python"] is None]
    wall = (record["t1_ns"] - record["t0_ns"]) / 1e9
    late = max((n["late_ns"] for n in record["late"]), default=0) / 1e9
    last = record["samples"][-1] if record["samples"] else {}
    ok = record["where"] == expected
    whole = ok and any(stacks) and bool(native)
    print(f"{loop} built {expected}: where={record['where']} "
          f"{'OK' if ok else 'WRONG'} kind={record['kind']} "
          f"wall={wall:.3f}s median={record['median_ns'] / 1e9:.4f}s "
          f"cpu={record['cpu_ns'] / 1e9:.3f}s stall={stall_s:.2f}s "
          f"span={last.get('span')} samples={len(record['samples'])} "
          f"stack={'yes' if any(stacks) else 'no'} late={late:.3f}s "
          f"outside={record.get('outside_ns', 0) / 1e9:.3f}s "
          f"threads={[(t['comm'], t['python'], round(t['cpu_s'], 2)) for t in record['threads'][:4]]} "
          f"native={len(native)} machine={record['machine']} "
          f"top={(stacks[-1] or ('',))[0] if stacks else None}", flush=True)
    return whole


def records_between(tel, marks):
    """The record of each injection: the one slow step between its mark
    and the next."""
    out = {}
    names = list(marks)
    for name, nxt in zip(names, names[1:] + [None]):
        found = tel.slow_steps(marks[name], marks[nxt] if nxt else None)
        out[name] = found
    return out


# ----------------------------------------------------------------------
def serve_loop(args, cells, sut):
    import numpy as np
    import deepspeed_tpu
    cell = cells.load_cell("serve-olmo2-1b-chat")
    cfg = dict(cell.config)
    engine_kwargs = dict(cfg["serve"]["engine"])
    if args.toy:
        cfg.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=4, intermediate_size=128,
                   vocab_size=512)
        engine_kwargs.update(page_size=8, max_seq=1024, num_pages=300,
                             serving={"attention_backend": "jnp"})
        cell.config = cfg
    import jax
    model = sut.build_model(cell)
    dtype = "float32" if args.toy else cfg["serve"]["dtype"]
    params = sut.seeded_weights(model, args.seed, sut.DTYPES[dtype],
                                jax.devices()[:1])
    engine = deepspeed_tpu.init_inference(
        model=model, params=params, dtype=dtype).create_serving_engine(
        max_batch=4 if args.toy else 32, **engine_kwargs)
    tel = engine.telemetry
    rng = np.random.default_rng(args.seed)
    state = {"n": 0, "sample_stall": 0.0}

    def refill():
        while engine.n_active + len(engine.queue) < 4:
            state["n"] += 1
            engine.add_request(
                f"probe-{state['n']}",
                rng.integers(0, cfg["vocab_size"], 96, dtype=np.int32),
                max_new_tokens=400)

    sample = engine._sample

    def slow_sample(req, row):
        if state["sample_stall"]:
            stall, state["sample_stall"] = state["sample_stall"], 0.0
            busy(stall)
        return sample(req, row)

    engine._sample = slow_sample

    def steps(n):
        for _ in range(n):
            refill()
            engine.step()

    hog = Hog()
    stall = args.stall
    steps(args.warm_steps)
    marks = {}
    marks["other_thread"] = time.perf_counter_ns()
    hog.hold(stall)
    steps(args.between)
    marks["descheduled"] = time.perf_counter_ns()
    child = stop_me(stall)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < stall + 0.6:
        steps(1)
    child.wait()
    steps(args.between)
    marks["pacing"] = time.perf_counter_ns()
    time.sleep(stall)               # between two loops: no step's time
    steps(args.between)
    marks["host_python"] = time.perf_counter_ns()
    state["sample_stall"] = stall
    steps(args.between)
    found = records_between(tel, marks)
    ok = True
    for name in ("other_thread", "descheduled", "host_python"):
        ok &= len(found[name]) == 1
        ok &= describe("serve", name, (found[name] or [None])[0], stall)
    print(f"serve pacing: {len(found['pacing'])} records (0 expected)",
          flush=True)
    ok &= not found["pacing"]
    median = sorted(r["t1_ns"] - r["t0_ns"]
                    for r in engine.step_reports())
    return {"loop": "serve", "ok": bool(ok),
            "records": {k: len(v) for k, v in found.items()},
            "where": {k: [r["where"] for r in v] for k, v in found.items()},
            "loop_median_ms": median[len(median) // 2] / 1e6,
            "sampler_wakes": tel.watchdog.wakes}


def train_loop(args, cells, sut):
    import numpy as np
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.parallel import groups
    cell = cells.load_cell("serve-olmo2-1b-chat")
    cfg = dict(cell.config, num_hidden_layers=args.train_layers)
    seq, micro = args.train_seq, 2
    if args.toy:
        cfg.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=4, intermediate_size=128,
                   vocab_size=512)
        seq = 64
    cell.config = cfg
    model = sut.build_model(cell, remat=True, remat_policy="dots_saveable")
    params = sut.seeded_weights(model, args.seed, jax.numpy.float32,
                                jax.devices()[:1])
    groups.reset_mesh()
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config={
            "optimizer": {"type": "AdamW", "params": {
                "lr": 1e-4, "weight_decay": 0.0,
                "moment_dtype": "bfloat16"}},
            "bf16": {"enabled": not args.toy},
            "zero_optimization": {"stage": 3},
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": 1})
    del params
    gc.collect()
    tel = engine.telemetry
    rng = np.random.default_rng(args.seed)
    took = []

    def steps(n):
        for _ in range(n):
            batch = {"input_ids": rng.integers(
                0, cfg["vocab_size"], (micro, seq), dtype=np.int32)}
            t0 = time.perf_counter()
            float(jax.block_until_ready(engine.train_batch(batch=batch)))
            took.append(time.perf_counter() - t0)

    hog = Hog()
    steps(args.warm_steps)
    median = sorted(took[2:])[len(took[2:]) // 2]
    # a step has to pass three medians AND the median by a quarter second
    stall = max(args.stall, 4.0 * median)
    between = max(10, args.between // 10)
    # a trainer's step runs from one train_batch to the next: each mark is
    # followed by a step of its own, so the stall's period starts after it
    marks = {}
    marks["other_thread"] = time.perf_counter_ns()
    steps(1)
    hog.hold(stall)
    steps(between)
    marks["descheduled"] = time.perf_counter_ns()
    steps(1)
    child = stop_me(stall)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < stall + 0.6:
        steps(1)
    child.wait()
    steps(between)
    marks["caller"] = time.perf_counter_ns()
    steps(1)
    time.sleep(stall)               # the caller's own code, between two steps
    steps(between)
    found = records_between(tel, marks)
    ok = True
    for name in ("other_thread", "descheduled", "caller"):
        ok &= len(found[name]) == 1
        ok &= describe("train", name, (found[name] or [None])[0], stall)
    return {"loop": "train", "ok": bool(ok),
            "records": {k: len(v) for k, v in found.items()},
            "where": {k: [r["where"] for r in v] for k, v in found.items()},
            "step_median_ms": median * 1e3, "stall_s": stall,
            "sampler_wakes": tel.watchdog.wakes}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--loop", choices=("serve", "train", "both"),
                        default="both")
    parser.add_argument("--toy", action="store_true",
                        help="a tiny model, for a rehearsal on the CPU")
    parser.add_argument("--seed", type=int, default=2954001001)
    parser.add_argument("--stall", type=float, default=1.0)
    parser.add_argument("--warm-steps", type=int, default=None)
    parser.add_argument("--between", type=int, default=200)
    parser.add_argument("--train-layers", type=int, default=8)
    parser.add_argument("--train-seq", type=int, default=1024)
    args = parser.parse_args()
    from chipbench import cells, sut
    from deepspeed_tpu.monitor.telemetry import Telemetry
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    out = {"device": jax.devices()[0].device_kind,
           "judge": judge_cost_ns(Telemetry), "loops": []}
    print(json.dumps({"judge": out["judge"]}), flush=True)
    for name, loop in (("serve", serve_loop), ("train", train_loop)):
        if args.loop in (name, "both"):
            if args.warm_steps is None:
                args.warm_steps = 300 if name == "serve" else 14
            out["loops"].append(loop(args, cells, sut))
            args.warm_steps = None
            gc.collect()
    out["ok"] = all(loop["ok"] for loop in out["loops"])
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    sys.exit(main())
