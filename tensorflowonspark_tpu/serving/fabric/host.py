"""Fabric host: one engine executor running N replica worker threads.

Parity note: the reference runs one TF node per Spark executor and
multiplexes work over the manager wire (TFSparkNode.py:480-482, the
DataFeed transport); a fabric host generalizes that to one *serving*
process per host whose replica count changes at runtime under the
autoscaler's plan.  No reference equivalent for the serving side
itself (Inference.scala:27-79 stops at offline batch inference).

Shape mirrors ``serving/replicas._make_replica_task``: a module-level
task factory (cloudpickle-able under the spawn start method), manager
queues for transport, a keyed manager-KV heartbeat for liveness, and
an in-band message loop.  The difference is one level of fan-out: the
host's dispatcher loop routes envelopes onto per-worker thread inboxes,
and each :class:`_Worker` owns its own ``_Predictor`` and (when the
spec mounts decode) its own ``DecodeEngine`` — so a host with 3
replicas holds 3 independent KV caches, which is what makes
session-affinity routing (``router.py``) worth doing.

Wire (all host->driver messages lead with the HOST index — workers are
a host-local detail; the driver's dispatch table is keyed by host):

- driver->host (``fabric_in_<h>``): ``("batch", bid, blob)``,
  ``("gen", sid, rid, blob)`` (``rid`` = worker hint from affinity
  routing, ``None`` = host picks least-busy), ``("reload"[, step])``,
  ``("scale", gen, n)`` (generation-fenced; stale directives dropped),
  ``("stats",)``, ``("stop",)``.
- host->driver (``fabric_out``): ``("up", h, pid, version, workers)``,
  ``("down", h)``, ``("done", h, bid, blob, meta)``,
  ``("batch_error", h, bid, tb)``, ``("gen_batch", h, events)`` (one
  hand-over of a worker's decode engine — an iteration's or an
  admission's events, in its order: ``("token", sid, i, tok)``,
  ``("done", sid, tokens, meta)``, ``("error", sid, err)``; a session's
  tokens precede its ``done``), ``("reloaded", h, version)``,
  ``("scaled", h, gen, n)``, ``("stats", h, st)``,
  ``("init_error", h, err)``.

Scale-down retires the HIGHEST worker ids first (LIFO): a retiring
worker stops admitting, drains its inbox in order, waits out its live
decode sessions, then stops its engine — scale-down never drops an
in-flight request.
"""

from __future__ import annotations

import logging
import os
import queue as _queue
import threading
import time

import cloudpickle

from tensorflowonspark_tpu import manager as tfmanager
from tensorflowonspark_tpu.actors import liveness
from tensorflowonspark_tpu.serving.replicas import (
    _maybe_reload,
    _resolve_predictor,
)
from tensorflowonspark_tpu.utils import telemetry

logger = logging.getLogger(__name__)

OUT_QUEUE = "fabric_out"
HEARTBEAT_PREFIX = "fabric_heartbeat:"
ENDPOINT_KEY = "fabric:ep:"     # + host index -> {"pid", "workers", ...}
LOAD_KEY = "fabric:load"        # router-published per-host load rollup
PLAN_KEY = "fabric:plan"        # autoscaler-published replica plan

RETIRE_GRACE_S = 30.0


def _in_queue(h):
    return f"fabric_in_{h}"


class _Worker:
    """One replica: a thread owning a predictor + optional decode engine.

    ``load()`` is the host's local routing signal: queued envelopes plus
    the one being handled plus live decode sessions.  The driver keeps
    its own per-host load in the dispatch table; this only breaks ties
    *within* a host.
    """

    def __init__(self, host, rid, payload, outq):
        self.host = host
        self.rid = rid
        self.payload = payload
        self.outq = outq
        self.inbox = _queue.Queue()
        self.accepting = True
        self.ready = threading.Event()
        self.error = None
        self.pred = None
        self.engine = None
        self._pending = 0
        self._sessions = 0
        self._lock = threading.Lock()
        self.thread = threading.Thread(
            target=self._run, name=f"fabric-worker-{host}-{rid}", daemon=True)

    def start(self):
        self.thread.start()
        return self

    def push(self, msg):
        with self._lock:
            self._pending += 1
        self.inbox.put(msg)

    def retire(self):
        """Stop admitting; the queued ``retire`` marker is handled after
        everything already in the inbox (in-band, ordered drain)."""
        self.accepting = False
        self.inbox.put(("retire",))

    def load(self):
        with self._lock:
            return self._pending + self._sessions

    def version(self):
        pred = self.pred
        return pred.version if pred is not None else None

    def stats(self):
        pred, engine = self.pred, self.engine
        st = pred.stats() if pred is not None else {}
        if engine is not None:
            st["decode"] = engine.stats()
        st["load"] = self.load()
        st["accepting"] = self.accepting
        return st

    def _emit(self, events):
        ended = sum(1 for event in events if event[0] in ("done", "error"))
        if ended:
            with self._lock:
                self._sessions = max(0, self._sessions - ended)
        self.outq.put(("gen_batch", self.host, events))

    def _run(self):
        try:
            pred = _resolve_predictor(self.payload)
            engine = None
            if self.payload.get("decode") is not None:
                from tensorflowonspark_tpu.serving.decode.scheduler import (
                    DecodeEngine,
                )

                engine = DecodeEngine(
                    pred.params, self.payload["decode"], self._emit,
                    replica=self.rid).start()
        except BaseException as e:  # noqa: BLE001 - report, stay down
            self.error = e
            self.accepting = False
            self.ready.set()
            return
        self.pred = pred
        self.engine = engine
        self.ready.set()
        try:
            while True:
                msg = self.inbox.get()
                kind = msg[0]
                if kind == "retire":
                    break
                try:
                    if kind == "batch":
                        _, bid, blob = msg
                        inputs, n_valid = cloudpickle.loads(blob)
                        with telemetry.span(
                                telemetry.SERVE_BATCH,
                                replica=f"{self.host}/{self.rid}",
                                n=n_valid):
                            outputs, device_ms = pred(inputs)
                        meta = {"device_ms": device_ms,
                                "version": pred.version,
                                "replica": self.rid,
                                "host": self.host}
                        self.outq.put(("done", self.host, bid,
                                       cloudpickle.dumps(outputs), meta))
                    elif kind == "gen":
                        _, sid, blob = msg
                        if engine is None:
                            self.outq.put(("gen_batch", self.host, [(
                                "error", sid,
                                "spec has no decode engine")]))
                        else:
                            req = cloudpickle.loads(blob)
                            with self._lock:
                                self._sessions += 1
                            engine.submit(sid, req["prompt"],
                                          max_tokens=req.get("max_tokens"),
                                          eos_id=req.get("eos_id"),
                                          sampling=req.get("sampling"),
                                          trace=req.get("trace"))
                    elif kind == "reload":
                        pin = msg[1]
                        if self.payload.get("ckpt_dir") \
                                and _maybe_reload(pred,
                                                  self.payload["ckpt_dir"],
                                                  step=pin):
                            if engine is not None:
                                engine.set_params(pred.params)
                        self.outq.put(("reloaded", self.host, pred.version))
                except BaseException as e:  # noqa: BLE001 - one bad
                    # envelope must not take the worker down
                    if kind == "batch":
                        import traceback

                        self.outq.put(("batch_error", self.host, msg[1],
                                       f"{e!r}\n{traceback.format_exc()}"))
                    elif kind == "gen":
                        with self._lock:
                            self._sessions = max(0, self._sessions - 1)
                        self.outq.put(("gen_batch", self.host,
                                       [("error", msg[1], repr(e))]))
                    else:
                        logger.exception("worker %d/%d failed a %s",
                                         self.host, self.rid, kind)
                finally:
                    with self._lock:
                        self._pending = max(0, self._pending - 1)
        finally:
            # retiring: wait out live decode sessions, then stop clean
            if engine is not None:
                deadline = time.monotonic() + RETIRE_GRACE_S
                while time.monotonic() < deadline:
                    with self._lock:
                        if self._sessions <= 0:
                            break
                    time.sleep(0.05)
                engine.stop()


class _Host:
    """Worker-thread supervisor inside one fabric host process."""

    def __init__(self, h, payload, outq):
        self.h = h
        self.payload = payload
        self.outq = outq
        self.gen = 0                 # last applied scale generation
        self._workers = []
        self._next_rid = 0
        self._lock = threading.Lock()

    def _active(self):
        return [w for w in self._workers
                if w.accepting and w.error is None]

    def scale_to(self, n, wait_first=False, timeout=120.0):
        """Grow/shrink to ``n`` accepting workers.  Growth is async
        (new workers admit once their predictor resolves); shrink
        retires the highest worker ids first (LIFO)."""
        n = max(1, int(n))
        with self._lock:
            active = self._active()
            while len(active) < n:
                w = _Worker(self.h, self._next_rid, self.payload, self.outq)
                self._next_rid += 1
                self._workers.append(w)
                w.start()
                active.append(w)
                if wait_first and len(active) == 1:
                    w.ready.wait(timeout)
                    if w.error is not None:
                        raise w.error
            excess = max(0, len(active) - n)
            for w in sorted(active, key=lambda x: -x.rid)[:excess]:
                w.retire()

    def route(self, msg):
        kind = msg[0]
        with self._lock:
            # a not-yet-ready worker is routable: its inbox queues until
            # the predictor resolves (admission gates live driver-side)
            cands = self._active()
        if not cands:
            mid, err = msg[1], "host has no live workers"
            self.outq.put(("batch_error", self.h, mid, err)
                          if kind == "batch" else
                          ("gen_batch", self.h, [("error", mid, err)]))
            return
        if kind == "gen":
            _, sid, rid, blob = msg
            w = next((x for x in cands if x.rid == rid), None)
            if w is None:
                w = min(cands, key=lambda x: (x.load(), x.rid))
            w.push(("gen", sid, blob))
        else:
            _, bid, blob = msg
            w = min(cands, key=lambda x: (x.load(), x.rid))
            w.push(("batch", bid, blob))

    def broadcast(self, msg):
        with self._lock:
            for w in self._active():
                w.push(msg)

    def reap(self):
        """Drop retired/broken workers whose threads have exited."""
        with self._lock:
            self._workers = [w for w in self._workers
                             if w.thread.is_alive() or
                             (w.accepting and w.error is None)]

    def n_workers(self):
        with self._lock:
            return len(self._active())

    def version(self):
        with self._lock:
            versions = [w.version() for w in self._active()]
        versions = [v for v in versions if v is not None]
        return max(versions, default=0)

    def load(self):
        with self._lock:
            return sum(w.load() for w in self._active())

    def stats(self):
        with self._lock:
            workers = list(self._workers)
        return {
            "pid": os.getpid(),
            "n_workers": self.n_workers(),
            "workers": {w.rid: w.stats() for w in workers
                        if w.error is None},
        }

    def endpoint_record(self):
        return {"pid": os.getpid(), "workers": self.n_workers(),
                "load": self.load(), "version": self.version(),
                "ts": time.time()}

    def stop(self):
        with self._lock:
            workers = list(self._workers)
        for w in workers:
            w.retire()
        for w in workers:
            w.thread.join(timeout=5)


def _make_host_task(payload_blob, mgr_addr, mgr_authkey):
    """The engine task every fabric host runs.  A real module-level
    factory (spawn start method): the closure is cloudpickled into the
    executor and resolves this module by import there."""

    def _host_task(it):
        items = list(it)
        h = int(os.environ.get(
            "TFOS_PARTITION_INDEX", items[0] if items else 0))
        mgr = tfmanager.connect(mgr_addr, mgr_authkey)
        inq = mgr.get_queue(_in_queue(h))
        outq = mgr.get_queue(OUT_QUEUE)
        telemetry.configure(node_id=f"fabric-host-{h}", role="serving")
        try:
            payload = cloudpickle.loads(payload_blob)
            fabric_cfg = payload.get("fabric") or {}
            host = _Host(h, payload, outq)
            host.scale_to(int(fabric_cfg.get("replicas_per_host", 1)),
                          wait_first=True)
        except BaseException as e:  # noqa: BLE001 - report, then fail task
            outq.put(("init_error", h, repr(e)))
            raise
        stop_beat = liveness.start_heartbeat(mgr, HEARTBEAT_PREFIX + str(h))
        outq.put(("up", h, os.getpid(), host.version(), host.n_workers()))
        last_ep = 0.0
        try:
            while True:
                now = time.monotonic()
                if now - last_ep >= 1.0:
                    last_ep = now
                    try:
                        mgr.set(ENDPOINT_KEY + str(h),
                                host.endpoint_record())
                    except Exception:  # noqa: BLE001 - manager going away
                        pass
                try:
                    msg = inq.get(timeout=0.25)
                except _queue.Empty:
                    host.reap()
                    continue
                kind = msg[0]
                if kind == "stop":
                    break
                if kind == "scale":
                    _, gen, n = msg
                    if gen <= host.gen:
                        continue  # stale generation: epoch-fenced
                    host.gen = gen
                    try:
                        host.scale_to(int(n))
                    except Exception:  # noqa: BLE001 - keep serving
                        logger.exception("scale to %s failed", n)
                    outq.put(("scaled", h, gen, host.n_workers()))
                elif kind == "reload":
                    # bare ("reload",) = latest-wins; ("reload", step) =
                    # pinned (watermark convergence after a respawn)
                    pin = msg[1] if len(msg) > 1 else None
                    host.broadcast(("reload", pin))
                elif kind == "stats":
                    outq.put(("stats", h, host.stats()))
                elif kind in ("batch", "gen"):
                    host.route(msg)
        finally:
            stop_beat.set()
            host.stop()
            outq.put(("down", h))
            telemetry.flush()

    return _host_task
