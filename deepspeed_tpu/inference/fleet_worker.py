"""Fleet worker: one ``ServingEngine`` per OS process.

``python -m deepspeed_tpu.inference.fleet_worker --fd N`` is the child
half of the cross-process fleet: the router (``inference/fleet.py``,
``transport.mode = "subprocess"``) creates a ``socketpair``, passes one
end's fd to this entry point, and drives the engine through the framed
RPC protocol in ``inference/transport.py``.  The worker is a real fault
domain — ``kill -9`` takes exactly one replica's state, and the router
recovers from its own request table.

Protocol (router → worker ``op`` frames, one ``resp``/``err`` frame
back each, strictly ordered):

* ``init`` — first frame.  Carries the replica identity (``rid``,
  ``epoch``), the ENGINE FACTORY SPEC, the heartbeat interval, and an
  optional telemetry config.  The factory spec is a dotted path
  ``"module:function"`` plus JSON kwargs — a deterministic recipe, not
  a pickled object, so a respawned worker rebuilds the exact same
  engine (same model init key ⇒ bit-identical outputs, the property
  every fleet acceptance test leans on).
* engine ops — ``add_request`` / ``step`` / ``pop_terminated`` /
  ``pop_prefilled`` / ``release_handoff`` / ``resident_prefix`` /
  ``export_payload`` / ``import_request`` / ``commit_import`` (the
  migration transaction's explicit ack) / ``cancel_import`` / ``drain``
  / ``leak_report`` / ``health`` / ``generate`` / ``ping`` /
  ``shutdown``.  Typed engine rejections (``RequestRejected``) cross
  the wire as typed ``err`` frames; any other engine exception becomes
  a generic ``err`` the router maps to its replica-kill path.

Every response piggybacks a ``load`` stamp (queue depth, active slots,
free pages, prefix hit rate, shed count) so the router's spill-order
and autoscale decisions read cached state instead of paying an RPC per
replica per dispatch.

Liveness: a daemon thread emits ``kind: "hb"`` frames every
``hb_interval_s`` with a monotonically increasing ``seq`` and the
worker's epoch; the router declares the replica dead after a missed-
heartbeat deadline.  Worker telemetry rides the rank-stamped shard sink
(``telemetry.distributed``): each worker writes ``events.rank{N}.jsonl``
in the shared shard dir, so one merged stream keeps per-replica
attribution.

Exactly-once under gray failures: every request frame carries a call id
(``cid``) echoed on the response; a duplicated delivery of the same cid
resends the cached response without re-executing.  Mutating ops
(``add_request`` / ``import_request`` / ``commit_import``) additionally
carry an idempotency key (``ikey``, ``epoch:req_id`` at the router) —
a RETRY under a fresh cid replays the cached outcome (response flagged
``dup: true``) instead of double-admitting or double-committing.  And
because a dropped response must not silently lose completed work, the
lossy result ops (``step`` / ``pop_terminated`` / ``pop_prefilled``)
are cumulative: results stay buffered until the router acks them on its
next call (``ack`` list), so a timed-out response is redelivered whole.
"""

import argparse
import importlib
import socket
import sys
import threading
import time
from collections import OrderedDict

from deepspeed_tpu.inference.transport import (TransportError,
                                               WIRE_VERSION,
                                               pack_value, payload_to_wire,
                                               payload_from_wire,
                                               recv_frame, send_frame,
                                               unpack_value)
from deepspeed_tpu.utils.compile_cache import enable_compile_cache
from deepspeed_tpu.utils.logging import logger


def resolve_factory(spec):
    """``{"factory": "module:function", "kwargs": {...}}`` (or the bare
    ``"module:function"`` string) → a ``factory(rid, epoch)`` callable.
    The dotted path is the whole point: a deterministic, re-importable
    recipe the router can respawn a dead worker from."""
    if isinstance(spec, str):
        spec = {"factory": spec}
    path = spec["factory"]
    kwargs = dict(spec.get("kwargs") or {})
    mod_name, _, fn_name = path.partition(":")
    if not fn_name:
        raise ValueError(f"factory spec {path!r} is not 'module:function'")
    fn = getattr(importlib.import_module(mod_name), fn_name)
    return lambda rid, epoch: fn(rid, epoch, **kwargs)


def tiny_engine_factory(replica_id, epoch, **overrides):
    """The deterministic tiny-transformer engine used by the xproc
    tests, gate 9, and the ``cpu_fleet_xproc`` bench: same geometry as
    ``tests/unit/test_fleet.py``'s in-process factory, init key 0, so an
    in-process fleet over this factory is the bit-identity oracle for a
    subprocess fleet over the same spec."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.serving import ServingEngine
    from deepspeed_tpu.models.transformer import (CausalTransformerLM,
                                                  TransformerConfig)
    cfg = TransformerConfig.tiny(hidden_size=64, n_heads=4, n_kv_heads=2)
    model = CausalTransformerLM(cfg)
    params = model.init(jax.random.key(0))
    kwargs = dict(max_batch=4, page_size=8, max_seq=128,
                  dtype=jnp.float32, replica_epoch=epoch,
                  serving={"prefix_cache": {"enabled": True}})
    kwargs.update(overrides)
    return ServingEngine(model, params, **kwargs)


def _result_to_wire(res):
    """``RequestResult`` → plain dict (fields are already primitives)."""
    return {"req_id": pack_value(res.req_id), "status": res.status,
            "reason": res.reason, "tokens": [int(t) for t in res.tokens],
            "n_generated": int(res.n_generated), "detail": res.detail}


class FleetWorker:
    """Hosts one engine behind the socket; see the module docstring."""

    # dedup cache bounds: cids are dense (a dup arrives right behind the
    # original), ikeys live as long as a retry storm plausibly can
    MAX_CID_CACHE = 32
    MAX_IKEY_CACHE = 4096

    def __init__(self, sock):
        self.sock = sock
        self.stream = sock.makefile("rb")
        self.wlock = threading.Lock()   # main loop vs heartbeat thread
        self.engine = None
        self.rid = None
        self.epoch = None
        self._hb_stop = threading.Event()
        self._resp_by_cid = OrderedDict()   # cid → sent response frame
        self._done_ikeys = OrderedDict()    # (ikey, op) → response core
        self.dup_calls = 0                  # replays served from caches
        # cumulative result buffers, pruned by the router's acks — a
        # dropped response cannot silently lose finished work
        self._done_buf = {}                 # rid → generated tokens
        self._term_buf = {}                 # rid → wire RequestResult
        self._hand_buf = {}                 # rid → wire PrefillHandoff

    # -- liveness --------------------------------------------------------
    def _heartbeat_loop(self, interval_s):
        seq = 0
        while not self._hb_stop.wait(interval_s):
            try:
                send_frame(self.sock,
                           {"kind": "hb", "seq": seq, "rid": self.rid,
                            "epoch": self.epoch,
                            "ts": round(time.monotonic(), 6)},
                           lock=self.wlock)
            except TransportError:
                return          # router is gone; main loop exits too
            seq += 1

    # -- op handlers -----------------------------------------------------
    def _load(self):
        eng = self.engine
        cache = eng.prefix_cache
        return {"queue": len(eng.queue), "active": int(eng.n_active),
                "free_pages": int(eng.alloc.free_page_count),
                "num_pages": int(eng.alloc.num_pages),
                "hit_rate": (cache.snapshot()["hit_rate"]
                             if cache is not None else None),
                "shed": int(eng.stats["shed"])}

    def _op_init(self, frame):
        from deepspeed_tpu.monitor.telemetry import get_telemetry
        self.rid = frame["rid"]
        self.epoch = frame["epoch"]
        tcfg = frame.get("telemetry")
        if tcfg:
            from deepspeed_tpu.runtime.config import TelemetryConfig
            get_telemetry().configure(TelemetryConfig(dict(tcfg)),
                                      rank=int(frame.get("rank", 0)))
        factory = resolve_factory(frame["spec"])
        self.engine = factory(self.rid, self.epoch)
        hb = float(frame.get("hb_interval_s", 1.0))
        if hb > 0:
            threading.Thread(target=self._heartbeat_loop, args=(hb,),
                             daemon=True, name="fleet-hb").start()
        return {"v": list(WIRE_VERSION),
                "page_size": int(self.engine.page_size),
                "kv_page_bytes": int(self.engine.kv_page_bytes)}

    def _op_add_request(self, frame):
        self.engine.add_request(unpack_value(frame["req_id"]),
                                frame["prompt"], **frame["kwargs"])
        return {}

    @staticmethod
    def _ack(frame, buf):
        """Prune a cumulative result buffer by the router's ack list —
        ids the router confirms it has consumed from a prior response."""
        for rid in frame.get("ack") or []:
            buf.pop(rid, None)

    def _op_step(self, frame):
        self._ack(frame, self._done_buf)
        for rid, toks in self.engine.step().items():
            self._done_buf[rid] = [int(t) for t in toks]
        return {"done": [[pack_value(rid), list(toks)]
                         for rid, toks in self._done_buf.items()]}

    def _op_pop_terminated(self, frame):
        self._ack(frame, self._term_buf)
        for rid, res in self.engine.pop_terminated().items():
            self._term_buf[rid] = _result_to_wire(res)
        return {"results": [[pack_value(rid), dict(res)]
                            for rid, res in self._term_buf.items()]}

    def _op_pop_prefilled(self, frame):
        self._ack(frame, self._hand_buf)
        for rid, h in self.engine.pop_prefilled().items():
            self._hand_buf[rid] = h.to_wire()
        return {"handoffs": [[pack_value(rid), dict(h)]
                             for rid, h in self._hand_buf.items()]}

    def _op_release_handoff(self, frame):
        return {"ok": self.engine.release_handoff(
            unpack_value(frame["req_id"]))}

    def _op_resident_prefix(self, frame):
        cache = self.engine.prefix_cache
        pages = (cache.resident_prefix(frame["prompt"])
                 if cache is not None else [])
        return {"pages": [int(p) for p in pages]}

    def _op_export_payload(self, frame):
        """Export + encode in one hop: the int8 wire codec runs HERE, on
        the source worker, so what crosses the process boundary is the
        quantized payload — the codec's byte saving is real wire bytes."""
        from deepspeed_tpu.comm.quantize import QuantizedPayload
        pages = [int(p) for p in frame["pages"]]
        if not pages:
            return {"payload": None, "quant": False}
        payload = self.engine.comm_quant.encode_payload(
            self.engine.export_pages(pages))
        return {"payload": payload_to_wire(payload),
                "quant": isinstance(payload, QuantizedPayload)}

    def _op_import_request(self, frame):
        from deepspeed_tpu.inference.serving import PrefillHandoff
        handoff = PrefillHandoff.from_wire(frame["handoff"])
        payload = payload_from_wire(frame.get("payload"))
        ok = self.engine.import_request(
            handoff, payload=payload,
            shared_pages=[int(p) for p in frame.get("shared_pages") or []],
            deadline_s=frame.get("deadline_s"))
        return {"ok": bool(ok)}

    def _op_commit_import(self, frame):
        self.engine.commit_import(unpack_value(frame["req_id"]))
        return {"ok": True}     # the explicit commit ack

    def _op_cancel_import(self, frame):
        return {"ok": self.engine.cancel_import(
            unpack_value(frame["req_id"]))}

    def _op_drain(self, frame):
        res = self.engine.drain()
        return {"finished": [[pack_value(rid), [int(t) for t in toks]]
                             for rid, toks in res["finished"].items()],
                "shed": [pack_value(r) for r in res["shed"]],
                "steps": int(res["steps"]), "health": res["health"]}

    def _op_leak_report(self, frame):
        return {"leaks": self.engine.leak_report()}

    def _op_health(self, frame):
        return {"health": self.engine.health()}

    def _op_generate(self, frame):
        out = self.engine.generate(frame["prompts"],
                                   max_new_tokens=int(
                                       frame.get("max_new_tokens", 8)))
        return {"out": [[int(t) for t in toks] for toks in out]}

    def _op_ping(self, frame):
        return {}

    # -- main loop -------------------------------------------------------
    def serve(self):
        while True:
            try:
                frame = unpack_value(recv_frame(self.stream))
            except TransportError:
                return          # router closed the socket (or died)
            op = frame.get("op")
            cid = frame.get("cid")
            if cid is not None and cid in self._resp_by_cid:
                # duplicated delivery of the same request frame: resend
                # the cached response verbatim, execute nothing — the
                # router discards the extra copy by cid
                self.dup_calls += 1
                try:
                    send_frame(self.sock, self._resp_by_cid[cid],
                               lock=self.wlock)
                except TransportError:
                    return
                continue
            if op == "shutdown":
                self._hb_stop.set()
                send_frame(self.sock, {"kind": "resp", "cid": cid},
                           lock=self.wlock)
                return
            handler = getattr(self, f"_op_{op}", None)
            ikey = frame.get("ikey")
            try:
                if handler is None:
                    raise ValueError(f"unknown op {op!r}")
                if ikey is not None and (ikey, op) in self._done_ikeys:
                    # retried mutation whose first execution succeeded
                    # but whose ack was lost: replay the outcome, do
                    # not double-admit / double-commit
                    self.dup_calls += 1
                    resp = dict(self._done_ikeys[(ikey, op)])
                    resp["dup"] = True
                else:
                    resp = handler(frame)
                    if ikey is not None:
                        self._done_ikeys[(ikey, op)] = dict(resp)
                        while len(self._done_ikeys) > self.MAX_IKEY_CACHE:
                            self._done_ikeys.popitem(last=False)
                resp["kind"] = "resp"
                if self.engine is not None:
                    resp["load"] = self._load()
            except Exception as e:
                resp = self._err_frame(op, e)
            resp["cid"] = cid
            if cid is not None:
                self._resp_by_cid[cid] = resp
                while len(self._resp_by_cid) > self.MAX_CID_CACHE:
                    self._resp_by_cid.popitem(last=False)
            try:
                send_frame(self.sock, resp, lock=self.wlock)
            except TransportError:
                return

    @staticmethod
    def _err_frame(op, e):
        from deepspeed_tpu.inference.robustness import RequestRejected
        from deepspeed_tpu.inference.transport import WireVersionError
        if isinstance(e, RequestRejected):
            return {"kind": "err", "etype": "RequestRejected",
                    "req_id": pack_value(e.req_id), "reason": e.reason,
                    "detail": e.detail}
        if isinstance(e, WireVersionError):
            return {"kind": "err", "etype": "WireVersionError",
                    "got": pack_value(e.got), "what": e.what}
        logger.warning(f"fleet worker op {op!r} raised: {e}")
        return {"kind": "err", "etype": type(e).__name__,
                "detail": str(e)}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--fd", type=int, required=True,
                        help="inherited socketpair fd from the router")
    args = parser.parse_args(argv)
    enable_compile_cache()
    sock = socket.socket(fileno=args.fd)
    FleetWorker(sock).serve()
    return 0


if __name__ == "__main__":
    sys.exit(main())
