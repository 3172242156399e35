#!/usr/bin/env bash
# Chain every offline quality gate in one command:
#
#   scripts/run_gates.sh [TELEMETRY_DIR] [INCIDENTS_DIR] [TUNE_DIR]
#
#   1. check_telemetry_schema.py <events.jsonl...>   frozen event vocab
#   2. check_telemetry_schema.py --incidents         incident bundles
#   3. check_telemetry_schema.py --tune              tune journals/overlay
#   4. comm-quant smoke                              int8 codec roundtrip
#   5. ds_trace_export.py --check                    Perfetto trace export
#   6. overlap smoke                                 ZeRO-3 comm overlap
#   7. fleet xproc smoke                             kill -9 a worker proc
#   8. chaos smoke                                   seeded wire faults
#   9. tiered smoke                                  memory block -> store
#
# TELEMETRY_DIR (optional) is searched recursively for events*.jsonl
# streams; INCIDENTS_DIR (optional) holds incident bundles; TUNE_DIR
# (optional, default autotuning_results/ when present) holds the
# autotuner's trial journals, tune/* event stream, and overlay.json.
# Gates whose input is absent are SKIPPED, not failed — the script is
# safe to run on a fresh checkout and in CI alike.  Exit 0 iff every
# gate that ran passed.

set -u
REPO="$(cd "$(dirname "$0")/.." && pwd)"
PY="${PYTHON:-python}"
TELEMETRY_DIR="${1:-}"
INCIDENTS_DIR="${2:-}"
TUNE_DIR="${3:-}"
fail=0

run_gate() {
    local name="$1"; shift
    echo "== gate: $name =="
    if "$@"; then
        echo "-- $name: PASS"
    else
        echo "-- $name: FAIL"
        fail=1
    fi
}

# 1. event-stream schema (every events*.jsonl under TELEMETRY_DIR)
if [ -n "$TELEMETRY_DIR" ] && [ -d "$TELEMETRY_DIR" ]; then
    mapfile -t streams < <(find "$TELEMETRY_DIR" -name 'events*.jsonl' \
                                -type f | sort)
    if [ "${#streams[@]}" -gt 0 ]; then
        run_gate "event schema" \
            "$PY" "$REPO/scripts/check_telemetry_schema.py" "${streams[@]}"
    else
        echo "== gate: event schema == SKIP (no events*.jsonl under" \
             "$TELEMETRY_DIR)"
    fi
else
    echo "== gate: event schema == SKIP (no telemetry dir given)"
fi

# 2. incident bundles
if [ -n "$INCIDENTS_DIR" ] && [ -d "$INCIDENTS_DIR" ]; then
    run_gate "incident bundles" \
        "$PY" "$REPO/scripts/check_telemetry_schema.py" --incidents \
        "$INCIDENTS_DIR"
else
    echo "== gate: incident bundles == SKIP (no incidents dir given)"
fi

# 3. autotuner artifacts: trial journals, tune/* stream, overlay
# provenance (defaults to the control plane's results_dir when present)
if [ -z "$TUNE_DIR" ] && [ -d "$REPO/autotuning_results" ]; then
    TUNE_DIR="$REPO/autotuning_results"
fi
if [ -n "$TUNE_DIR" ] && [ -e "$TUNE_DIR" ]; then
    run_gate "tune artifacts" \
        "$PY" "$REPO/scripts/check_telemetry_schema.py" --tune "$TUNE_DIR"
else
    echo "== gate: tune artifacts == SKIP (no tune dir given)"
fi

# 4. quantized-collective smoke: the comm.quantization config block must
# parse, activate the int8 codec, shrink the wire, and produce a
# schema-valid annotated census event + frozen quant gauge
run_gate "comm quant smoke" env JAX_PLATFORMS=cpu REPO="$REPO" "$PY" - <<'EOF'
import importlib.util, json, os, sys, tempfile
repo = os.environ["REPO"]
sys.path.insert(0, repo)
import numpy as np
import jax.numpy as jnp
from deepspeed_tpu.comm.quantize import CommQuantizer, quant_bytes_saved
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.monitor.telemetry import Telemetry
from deepspeed_tpu.runtime.config import TelemetryConfig

cfg = DeepSpeedConfig({"train_batch_size": 4,
                       "comm": {"quantization": {"enabled": True,
                                                 "block_size": 64}}})
q = CommQuantizer.from_config(cfg.comm_quantization)
assert q.active(), "quantization config did not activate the codec"
g = jnp.asarray(np.random.default_rng(0).standard_normal(4096),
                dtype=jnp.float32)
out, saved = q.qdq_tree({"w": g}, "all_reduce")
assert saved == quant_bytes_saved(4096, "float32", 64) > 0
err = float(jnp.linalg.norm(out["w"] - g) / jnp.linalg.norm(g))
assert err < 0.05, f"codec error {err}"
tmp = tempfile.mkdtemp()
tel = Telemetry().configure(TelemetryConfig(
    {"enabled": True, "output_path": tmp, "job_name": "quant_smoke"}),
    rank=0)
tel.collective("all_reduce", g.size * 4 - saved, "fsdp", dtype="float32",
               world=4, wire_dtype="int8", bytes_saved=int(saved))
tel.close()
spec = importlib.util.spec_from_file_location(
    "checker", os.path.join(repo, "scripts",
                            "check_telemetry_schema.py"))
checker = importlib.util.module_from_spec(spec)
spec.loader.exec_module(checker)
events = [json.loads(l) for l in
          open(os.path.join(tmp, "quant_smoke", "events.jsonl"))]
problems = [p for ev in events for p in checker.validate_event(ev)]
assert not problems, problems[:3]
annotated = [ev for ev in events if ev.get("bytes_saved")]
assert annotated, "no bytes_saved-annotated census event emitted"
gauges = [ev for ev in events if ev.get("kind") == "gauge" and
          str(ev.get("name", "")).startswith("comm/")]
assert all(ev["name"] in checker.QUANT_GAUGES for ev in gauges)
print(f"quant smoke: saved {int(saved)} bytes, rel err {err:.4f}, "
      f"{len(events)} schema-valid events")
EOF

# 5. trace export: every telemetry stream found under TELEMETRY_DIR must
# convert to a valid Chrome trace-event file (attribution flow arrows
# included) — the exporter is the debugging path of last resort, so a
# stream it chokes on is a gate failure, not a rendering nit
if [ -n "$TELEMETRY_DIR" ] && [ -d "$TELEMETRY_DIR" ]; then
    mapfile -t trace_dirs < <(find "$TELEMETRY_DIR" -name 'events*.jsonl' \
                                   -type f -exec dirname {} \; |
                              sort -u)
    if [ "${#trace_dirs[@]}" -gt 0 ]; then
        trace_tmp="$(mktemp -d)"
        trap 'rm -rf "$trace_tmp"' EXIT
        i=0
        for d in "${trace_dirs[@]}"; do
            run_gate "trace export ($d)" \
                "$PY" "$REPO/scripts/ds_trace_export.py" "$d" \
                --check -o "$trace_tmp/trace.$i.json"
            i=$((i + 1))
        done
    else
        echo "== gate: trace export == SKIP (no events*.jsonl under" \
             "$TELEMETRY_DIR)"
    fi
else
    echo "== gate: trace export == SKIP (no telemetry dir given)"
fi

# 6. overlap smoke: a ZeRO-3 config with zero_optimization.overlap on
# must run the double-buffered step on the simulated 8-device mesh with
# a bit-identical forward vs the serial oracle (the gather pipeline may
# reorder communication, never math), the trajectory inside ulp
# tolerance, and the frozen comm/overlap/* + step/attr/exposed_comm_frac
# gauges riding a schema-valid stream
run_gate "overlap smoke" env JAX_PLATFORMS=cpu REPO="$REPO" "$PY" - <<'EOF'
import importlib.util, json, os, sys, tempfile
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
repo = os.environ["REPO"]
sys.path.insert(0, repo)
import numpy as np
import jax
import jax.numpy as jnp
import deepspeed_tpu
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.runtime.zero.stage_plan import layer_scan

HIDDEN, LAYERS = 16, 4

class Stacked:
    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {"layers": {"w": jax.random.normal(
                               k1, (LAYERS, HIDDEN, HIDDEN)) * 0.1,
                           "b": jnp.zeros((LAYERS, HIDDEN))},
                "out": jax.random.normal(k2, (HIDDEN, HIDDEN)) * 0.1}

    def tp_rules(self):
        from jax.sharding import PartitionSpec as P
        return [(r"\['w'\]$", P("fsdp")), (r"\['b'\]$", P("fsdp"))]

    def apply(self, params, x):
        def body(h, layer):
            return jnp.tanh(h @ layer["w"] + layer["b"]), None
        h, _ = layer_scan(body, x, params["layers"])
        return h @ params["out"]

    def loss(self, params, batch, rng=None):
        x, y = jnp.asarray(batch["x"]), jnp.asarray(batch["y"])
        return jnp.mean(jnp.square(self.apply(params, x) - y))

def batch(i):
    rng = np.random.default_rng(i)
    x = rng.normal(size=(32, HIDDEN)).astype(np.float32)
    return {"x": x, "y": np.roll(x, 1, axis=-1) * 0.5}

def run(zero, tmp=None):
    groups.reset_mesh()
    model = Stacked()
    params = model.init(jax.random.key(0))
    cfg = {"train_micro_batch_size_per_gpu": 4,
           "optimizer": {"type": "Adam",
                         "params": {"lr": 1e-2, "weight_decay": 0.0}},
           "zero_optimization": dict({"stage": 3,
                                      "param_persistence_threshold": 0},
                                     **zero),
           "mesh": {"dp": 2, "fsdp": 4}}
    if tmp:
        cfg["telemetry"] = {"enabled": True, "output_path": tmp,
                            "job_name": "overlap_smoke",
                            "attribution": {"enabled": True}}
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=cfg)
    losses = [float(engine.train_batch(batch=batch(i))) for i in range(3)]
    if tmp:
        engine.flush_telemetry()
    return losses

serial = run({})
tmp = tempfile.mkdtemp()
over = run({"overlap": {"enabled": True, "gather_prefetch_depth": 1,
                        "rs_bucket_bytes": 2048}}, tmp=tmp)
assert serial[0] == over[0], \
    f"forward not bit-identical: {serial[0]} vs {over[0]}"
np.testing.assert_allclose(serial, over, rtol=5e-6, atol=1e-7)
stream = os.path.join(tmp, "overlap_smoke", "events.jsonl")
events = [json.loads(l) for l in open(stream)]
names = {ev.get("name") for ev in events if ev.get("kind") == "gauge"}
assert "step/attr/exposed_comm_frac" in names, sorted(names)
spec = importlib.util.spec_from_file_location(
    "checker", os.path.join(repo, "scripts",
                            "check_telemetry_schema.py"))
checker = importlib.util.module_from_spec(spec)
spec.loader.exec_module(checker)
missing = set(checker.OVERLAP_GAUGES) - names
assert not missing, f"missing overlap gauges: {sorted(missing)}"
problems = [p for ev in events for p in checker.validate_event(ev)]
assert not problems, problems[:3]
print(f"overlap smoke: 3 overlapped steps vs serial — step-0 loss "
      f"bit-identical ({serial[0]:.6f}), trajectory within ulp "
      f"tolerance, {len(checker.OVERLAP_GAUGES)} overlap gauges + "
      f"exposed_comm_frac on a {len(events)}-event schema-valid stream")
EOF

# 7. cross-process fleet smoke: a 2-worker subprocess fleet must serve
# the same tokens as the in-process fleet bit-for-bit, then survive a
# real kill -9 of one worker mid-decode with zero lost requests — every
# id reaches exactly one typed terminal, survivors stay bit-identical,
# and the death is booked as a schema-valid fleet/worker_lost event plus
# a worker_lost incident bundle the checker accepts
run_gate "fleet xproc smoke" env JAX_PLATFORMS=cpu REPO="$REPO" "$PY" - <<'EOF'
import importlib.util, json, os, signal, sys, tempfile
repo = os.environ["REPO"]
sys.path.insert(0, repo)
from deepspeed_tpu.inference.fleet import FleetRouter
from deepspeed_tpu.inference.fleet_worker import tiny_engine_factory
from deepspeed_tpu.monitor.telemetry import Telemetry
from deepspeed_tpu.runtime.config import TelemetryConfig

SPEC = {"factory":
        "deepspeed_tpu.inference.fleet_worker:tiny_engine_factory",
        "kwargs": {}}
XPROC = {"mode": "subprocess", "heartbeat_interval_s": 0.2,
         "heartbeat_deadline_s": 10.0}
PROMPTS = {f"q{i}": [1 + i, 2 + i, 3 + i, 4 + i] for i in range(6)}

def run(factory, fleet, kill_rid=None, telemetry=None):
    router = FleetRouter(factory, fleet=fleet, telemetry=telemetry)
    try:
        for rid, p in sorted(PROMPTS.items()):
            router.submit(rid, p, max_new_tokens=6, temperature=0.7,
                          seed=11)
        killed = False
        for step in range(300):
            if kill_rid and step == 3 and not killed:
                os.kill(router.replicas[kill_rid].handle.proc.pid,
                        signal.SIGKILL)
                killed = True
            router.step()
            if not router._unresolved():
                break
        assert not router._unresolved(), "fleet did not converge"
        return (dict(router.finished), router.pop_terminated(),
                router.leak_report(), dict(router.stats))
    finally:
        router.close()

base = {"replicas": 2, "health_interval": 4}
ref, term, leaks, _ = run(tiny_engine_factory, dict(base))
assert not term and leaks == {}, (term, leaks)

out, term, leaks, _ = run(SPEC, dict(base, transport=dict(XPROC)))
assert not term and leaks == {}, (term, leaks)
assert out == ref, "subprocess fleet not bit-identical to in-process"

tmp = tempfile.mkdtemp()
tel = Telemetry().configure(TelemetryConfig(
    {"enabled": True, "output_path": tmp, "job_name": "xproc_gate",
     "incidents": {"enabled": True, "cooldown_s": 0.0}}), rank=0)
try:
    out, term, leaks, stats = run(SPEC, dict(base, transport=dict(XPROC)),
                                  kill_rid="r0", telemetry=tel)
finally:
    tel.close()
assert leaks == {}, leaks
assert stats["workers_lost"] == 1, stats
assert set(out) | set(term) == set(PROMPTS), (set(out), set(term))
assert not (set(out) & set(term)), "a request reached two terminals"
for rid, toks in out.items():
    assert toks == ref[rid], f"{rid} diverged after kill -9"

spec = importlib.util.spec_from_file_location(
    "checker", os.path.join(repo, "scripts",
                            "check_telemetry_schema.py"))
checker = importlib.util.module_from_spec(spec)
spec.loader.exec_module(checker)
stream = os.path.join(tmp, "xproc_gate", "events.jsonl")
assert checker.validate_file(stream) == [], "event stream schema-invalid"
events = [json.loads(l) for l in open(stream) if l.strip()]
assert any(e.get("kind") == "fleet" and
           e.get("name") == "fleet/worker_lost" for e in events)
assert any(e.get("kind") == "incident" and
           e.get("trigger") == "worker_lost" for e in events)
bundles = os.path.join(tmp, "xproc_gate", "incidents")
problems, n_bundles = checker.validate_incidents_path(bundles)
assert problems == [], problems[:3]
assert n_bundles >= 1, "no incident bundle written"
print(f"fleet xproc smoke: {len(ref)} requests bit-identical across the "
      f"process boundary; kill -9 mid-decode -> {len(out)} finished + "
      f"{len(term)} re-terminated, zero lost, workers_lost="
      f"{stats['workers_lost']}, respawns={stats['respawns']}, "
      f"schema-valid worker_lost event + incident bundle")
EOF

# 8. chaos smoke: deterministic wire-fault campaign over the 2-worker
# subprocess fleet — lost add_request ack (channel retry + ikey dedup),
# slow worker (circuit breaker opens, probes, closes; no respawn), and a
# torn commit_import ack (gray migrate recovers exactly-once). Each
# scenario asserts zero lost requests, one terminal per request, empty
# leak report, bit-identical survivors vs an in-process reference, and
# checker-valid telemetry.
run_gate "chaos smoke" env JAX_PLATFORMS=cpu "$PY" \
    "$REPO/scripts/ds_chaos.py" --scenarios ack_loss,slow_worker,torn_commit

# 9. tiered-store smoke: a memory config block must build a TieredStore
# whose quantized NVMe entries carry their scale sidecars, whose sealed
# directory fscks COMMITTED (and flags a torn payload file as partial),
# and whose frozen tier/* gauges ride a schema-valid stream
run_gate "tiered smoke" env JAX_PLATFORMS=cpu REPO="$REPO" "$PY" - <<'EOF'
import importlib.util, json, os, sys, tempfile
repo = os.environ["REPO"]
sys.path.insert(0, repo)
import numpy as np
from deepspeed_tpu.monitor import telemetry as telmod
from deepspeed_tpu.runtime import resilience
from deepspeed_tpu.runtime.config import DeepSpeedConfig, TelemetryConfig
from deepspeed_tpu.runtime.tiered_store import TieredStore

tmp = tempfile.mkdtemp(prefix="tiered_gate_")
cfg = DeepSpeedConfig({
    "train_batch_size": 1,
    "memory": {"placement_policy": "nvme", "nvme_dir": tmp,
               "quantize_tiers": True, "quant_block": 64},
})
tel = telmod.get_telemetry().configure(TelemetryConfig(
    {"enabled": True, "output_path": tmp, "job_name": "tier_gate"}),
    rank=0)
store = TieredStore.from_config(cfg.memory_config, name="gate")
rng = np.random.default_rng(0)
W = {f"L{i}": rng.standard_normal(256).astype(np.float32)
     for i in range(4)}
for k, v in W.items():
    store.put(k, v)
store.commit()
status, manifest = store.validate()
assert status == resilience.COMMITTED, status
listed = [f["path"] for f in manifest["files"]]
assert any(p.endswith(".scales.bin") for p in listed), listed
for k, v in W.items():
    got = store.fetch(k)
    bound = float(np.max(np.abs(v))) / 127.0
    assert float(np.max(np.abs(got - v))) <= bound
store.publish_gauges()
tel.close()
# torn payload file -> the fsck verdict flips to partial
victim = os.path.join(store.nvme_path,
                      next(p for p in listed if p.endswith(".q.bin")))
with open(victim, "r+b") as f:
    f.truncate(8)
assert store.validate()[0] == resilience.PARTIAL
spec = importlib.util.spec_from_file_location(
    "checker", os.path.join(repo, "scripts",
                            "check_telemetry_schema.py"))
checker = importlib.util.module_from_spec(spec)
spec.loader.exec_module(checker)
stream = os.path.join(tmp, "tier_gate", "events.jsonl")
assert checker.validate_file(stream) == [], "event stream schema-invalid"
events = [json.loads(l) for l in open(stream) if l.strip()]
names = {e["name"] for e in events if e.get("kind") == "gauge"
         and str(e.get("name", "")).startswith("tier/")}
assert "tier/nvme_bytes" in names and "tier/quant_bytes_saved" in names
print(f"tiered smoke: memory config -> {len(W)} int8 NVMe entries with "
      f"manifest-listed scale sidecars, fsck COMMITTED -> torn file "
      f"flagged partial, {len(names)} tier/* gauges schema-valid")
EOF

if [ "$fail" -ne 0 ]; then
    echo "GATES: FAIL"
    exit 1
fi
echo "GATES: OK"
