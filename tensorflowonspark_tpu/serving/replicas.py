"""Replica pool: N model replicas as supervised engine executors.

No reference equivalent (the reference stops at offline batch inference,
Inference.scala:27-79); the *machinery* is reused from this repo's
runtime instead of reinvented:

- replicas run as ``engine.foreach_partition(spread=True,
  retryable=True)`` tasks, so a SIGKILLed replica is respawned by the
  engine's supervision (engine.py `_respawn_executor`) and its task blob
  re-dispatched byte-identically;
- request/response transport is the executor IPC manager
  (manager.TFManager named queues — the DataFeed transport of
  reference TFSparkNode.py:480-482, batched);
- liveness is the keyed manager-KV heartbeat (``actors.liveness``) plus
  direct executor-process checks, the same two signals engine/node and
  actor supervision use.

Dispatch is least-loaded among live replicas (round-robin when idle —
ties broken by index), via the shared ``actors.dispatch.InFlightTable``
(one table, keys namespaced ``("batch", id)`` / ``("gen", sid)``).
In-flight batches of a dead replica are re-dispatched to survivors;
`batcher.Batch` resolves once, so a duplicate answer from a half-dead
replica is a no-op.

Checkpoint hot-reload: when the spec names a ``ckpt_dir``, a watcher
thread polls ``utils/checkpoint.latest`` every
``TFOS_SERVE_RELOAD_SECS`` and broadcasts an in-band ``reload`` message
to every replica.  In-band means ordered behind already-queued batches:
in-flight requests finish on the old params, later ones see the new —
no drop, no lock.

Canary routing (docs/deployment.md): ``set_canary`` pins a subset of
replicas at a candidate version (in-band ``("reload", step)`` — pinned
reloads may go DOWN-version, unlike the latest-wins watcher) and splits
dispatch deterministically by request id: ~pct% of traffic lands on the
canary arm, the rest on the baseline, least-loaded within the arm.  Arm
outcomes accumulate into ``tfos_deploy_*`` metrics and ``canary_stats``
for the promotion controller's burn-window verdict; ``promote_canary``
reloads the baseline at the candidate and advances the watermark,
``rollback_canary`` re-pins the canary arm at the blessed watermark.
While a watermark is set the latest-wins watcher stands down (the
controller owns version transitions) and a respawned replica that cold-
booted at the wrong version is steered back to its arm's pin.
"""

from __future__ import annotations

import logging
import os
import queue as _queue
import threading
import time

import cloudpickle
import numpy as np

from tensorflowonspark_tpu import manager as tfmanager
from tensorflowonspark_tpu.actors import liveness
from tensorflowonspark_tpu.actors.dispatch import InFlightTable
from tensorflowonspark_tpu.utils import faults, metrics_registry, telemetry

logger = logging.getLogger(__name__)

REPLICAS_ENV = "TFOS_SERVE_REPLICAS"
RELOAD_SECS_ENV = "TFOS_SERVE_RELOAD_SECS"
RETRIES_ENV = "TFOS_SERVE_RETRIES"

HEARTBEAT_PREFIX = "serve_heartbeat:"
OUT_QUEUE = "serve_out"


def num_replicas_default():
    return int(os.environ.get(REPLICAS_ENV, "2"))


def reload_secs_default():
    return float(os.environ.get(RELOAD_SECS_ENV, "2"))


def max_retries_default():
    return int(os.environ.get(RETRIES_ENV, "8"))


def _in_queue(idx):
    return f"serve_in_{idx}"


class ModelSpec:
    """What a replica serves.  Two resolution paths:

    - ``export_dir``: a ``utils/checkpoint.export_model`` directory; the
      predict callable is resolved from the export metadata's
      ``predict`` ("module:qualname") entry, overridable via ``predict``
      here (the ``signature_def_key`` analogue, pipeline.py parity).
    - ``predict`` as a direct callable (+ optional ``params``): shipped
      to replicas by value via cloudpickle — the test/probe path; such
      replicas never import jax when ``jit=False``.

    ``ckpt_dir`` additionally arms checkpoint hot-reload: replicas start
    from the newest checkpoint in it (falling back to export params) and
    the pool's watcher broadcasts reloads as new steps appear.

    ``jit``: True forces AOT compilation (error if the predict is not
    jax-pure), False forces eager, None ("auto") tries AOT and falls
    back to eager.

    ``decode``: a ``serving.decode.scheduler.DecodeSpec`` mounts the
    continuous-batching autoregressive decode engine on every replica
    (docs/serving.md "Autoregressive decode"); the pool then accepts
    ``dispatch_session`` alongside batch ``dispatch``.  A decode-only
    spec needs no ``predict`` — params still resolve from
    ``export_dir``/``params``/``ckpt_dir``.
    """

    def __init__(self, export_dir=None, ckpt_dir=None, predict=None,
                 params=None, jit=None, decode=None):
        if export_dir is None and predict is None and decode is None:
            raise ValueError(
                "ModelSpec needs an export_dir, a predict "
                "callable/'module:qualname' string, or a decode spec")
        self.export_dir = export_dir
        self.ckpt_dir = ckpt_dir
        self.predict = predict
        self.params = params
        self.jit = jit
        self.decode = decode

    def to_payload(self):
        return {
            "export_dir": self.export_dir,
            "ckpt_dir": self.ckpt_dir,
            "predict": self.predict,
            "params": self.params,
            "jit": self.jit,
            "decode": self.decode,
        }


class _Predictor:
    """Replica-side model: params + per-signature compiled executables.

    The compile-count contract (the acceptance criterion's hook): one
    entry is added to ``compiles`` exactly when a new (shape, dtype)
    signature is first seen — via ``jax.jit(fn).lower(...).compile()``
    (AOT, one executable per bucket by construction) or, for non-jittable
    predicts, eager first-call instantiation.  Buckets repeat, signatures
    don't grow past ``log2(max_batch)+1`` per input layout.
    """

    def __init__(self, fn, params, version, jit_mode):
        self._fn = fn
        self.params = params
        self.version = version
        self._jit = jit_mode
        self._compiled = {}
        self.compiles = {}           # sig str -> compile count
        self.mesh_shape = None       # set by an elastic resize
        self.batches = 0
        self.rows = 0
        self.device_ms = 0.0

    def _sig(self, inputs):
        # keyed by (mesh shape, shapes/dtypes): after an elastic reshard
        # the same bucket must re-lower — reusing an executable against a
        # stale sharding would be a silent wrong-placement
        return (self.mesh_shape,) + tuple(
            (k, tuple(v.shape), str(v.dtype))
            for k, v in sorted(inputs.items()))

    def _lower(self, inputs):
        if self._jit is False:
            return None
        try:
            import jax

            return jax.jit(self._fn).lower(self.params, inputs).compile()
        except Exception as e:  # noqa: BLE001 - non-jax-pure predict
            if self._jit is True:
                raise
            # jit=None only: the caller left the choice to us, and an
            # eager predict is slower by orders of magnitude — say so
            # where an operator looks (jit=True makes this fatal)
            logger.warning("predict not AOT-compilable (%s); serving "
                           "EAGERLY", e)
            return None

    def __call__(self, inputs):
        if self._fn is None:
            raise RuntimeError(
                "this spec serves decode sessions only (no predict "
                "signature); use generate, not predict")
        sig = self._sig(inputs)
        if sig not in self._compiled:
            self._compiled[sig] = self._lower(inputs)
            key = str(sig)
            self.compiles[key] = self.compiles.get(key, 0) + 1
        exe = self._compiled[sig]
        t0 = time.perf_counter()
        if exe is None:
            out = self._fn(self.params, inputs)
        else:
            try:
                out = exe(self.params, inputs)
            except Exception:  # noqa: BLE001 - params changed layout
                # hot-reload swapped params whose avals no longer match
                # the executable (dtype/shape drift): re-lower once
                self._compiled[sig] = exe = self._lower(inputs)
                key = str(sig)
                self.compiles[key] = self.compiles.get(key, 0) + 1
                out = (exe(self.params, inputs) if exe is not None
                       else self._fn(self.params, inputs))
        out = {k: np.asarray(v) for k, v in out.items()}
        dur = (time.perf_counter() - t0) * 1e3
        self.batches += 1
        self.rows += next(iter(inputs.values())).shape[0]
        self.device_ms += dur
        return out, dur

    def stats(self):
        return {
            "version": self.version,
            "compiles": dict(self.compiles),
            "batches": self.batches,
            "rows": self.rows,
            "device_ms": round(self.device_ms, 3),
        }


def _import_qualname(spec):
    """Resolve a "module:qualname" predict spec (pipeline._load_predictor
    convention)."""
    import importlib

    mod_name, _, fn_name = spec.partition(":")
    fn = importlib.import_module(mod_name)
    for part in fn_name.split("."):
        fn = getattr(fn, part)
    return fn


def _resolve_predictor(payload):
    """Build the replica's :class:`_Predictor` from a ModelSpec payload."""
    fn = payload.get("predict")
    params = payload.get("params")
    version = 0
    if payload.get("export_dir"):
        from tensorflowonspark_tpu.utils import checkpoint as ckpt

        params, meta = ckpt.load_exported(payload["export_dir"])
        if not callable(fn):
            spec = (fn if isinstance(fn, str) else None) or meta.get("predict")
            if not spec and payload.get("decode") is None:
                raise ValueError(
                    f"export {payload['export_dir']} has no 'predict' "
                    "metadata and the spec names no callable")
            fn = _import_qualname(spec) if spec else None
    elif isinstance(fn, str):
        fn = _import_qualname(fn)
    pred = _Predictor(fn, params, version, payload.get("jit"))
    if payload.get("ckpt_dir"):
        _maybe_reload(pred, payload["ckpt_dir"])
    if pred.params is None:
        raise ValueError("no params: provide export_dir, params, or a "
                         "ckpt_dir containing a checkpoint")
    return pred


def _maybe_reload(pred, ckpt_dir, step=None):
    """Swap in new params; returns True when they changed.

    ``step=None``: the newest checkpoint, if newer than ``pred.version``
    (the latest-wins watcher path).  ``step=N``: that step EXACTLY —
    pinned reloads serve the canary candidate and the rollback target,
    and may go down-version by design."""
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    if step is not None:
        step = int(step)
        if step == pred.version:
            return False
        pred.params = ckpt.restore_step(ckpt_dir, step)
        pred.version = step
        logger.info("replica pinned params at step %d", step)
        return True
    step, _path = ckpt.latest(ckpt_dir)
    if step is None or step == pred.version:
        return False
    tree, step = ckpt.restore_any(ckpt_dir)
    if tree is None or step == pred.version:
        return False
    pred.params = tree
    pred.version = step
    logger.info("replica reloaded params at step %d", step)
    return True


def canary_arm(route_id, pct):
    """True when ``route_id`` hashes into the canary arm.  Deterministic
    (same id, same arm — across processes and retries) with 1% split
    granularity; zlib.crc32 so the split needs no seeding."""
    import zlib

    return (zlib.crc32(str(route_id).encode()) % 100) < float(pct)


def _profile_capture(outq, idx, seq, seconds, out_dir):
    """The replica's answer to a ``("profile", ...)`` directive, on a
    thread of its own so the message loop keeps admitting sessions: a
    ``utils.profiler`` capture of ``seconds`` into ``out_dir``, then the
    ack.  The same capture trainers take for ``POST /profilez``."""
    from tensorflowonspark_tpu.utils import profiler

    ok = profiler.start_trace(out_dir)
    if ok:
        time.sleep(seconds)
        ok = profiler.stop_trace()
    outq.put(("profiled", idx, seq, bool(ok), out_dir))


def _make_replica_task(payload_blob, mgr_addr, mgr_authkey):
    """The engine task every replica runs.  A real module-level factory
    (not a heredoc/driver lambda): the closure is cloudpickled into the
    executor and must resolve this module by import there."""

    def _replica_task(it):
        items = list(it)
        idx = int(os.environ.get(
            "TFOS_PARTITION_INDEX", items[0] if items else 0))
        mgr = tfmanager.connect(mgr_addr, mgr_authkey)
        inq = mgr.get_queue(_in_queue(idx))
        outq = mgr.get_queue(OUT_QUEUE)
        telemetry.configure(node_id=f"replica-{idx}", role="serving")
        _elastic = None
        el_state = {"gen": 0, "covered": None, "resizes": 0, "boot": "cold"}
        try:
            payload = cloudpickle.loads(payload_blob)
            elastic_cfg = payload.get("elastic")
            if elastic_cfg:
                # elastic boot gate (serving/elastic.py): announce this
                # incarnation, then wait for the supervisor's directive —
                # "cold" (load from the spec) or "adopt" (live params
                # resharded from the survivors' mirror, never a
                # checkpoint reload)
                from tensorflowonspark_tpu.serving import elastic as _elastic

                outq.put(("hello", idx, os.getpid()))
                boot = _elastic.await_boot(inq)
                if boot[0] == "stop":
                    outq.put(("down", idx))
                    return
                if boot[0] == "adopt":
                    pred = _elastic.adopt_predictor(payload, boot[1], boot[2])
                    el_state["boot"] = "adopted"
                else:
                    pred = _resolve_predictor(payload)
            else:
                pred = _resolve_predictor(payload)
            engine = None
            if payload.get("decode") is not None:
                from tensorflowonspark_tpu.serving.decode.scheduler import (
                    DecodeEngine,
                )

                def _gen_emit(events):
                    # one hand-over of the engine, one message
                    outq.put(("gen_batch", idx, events))

                engine = DecodeEngine(
                    pred.params, payload["decode"], _gen_emit,
                    replica=idx).start()
        except BaseException as e:  # noqa: BLE001 - report, then fail task
            outq.put(("init_error", idx, repr(e)))
            raise
        # keyed manager-KV heartbeat (actors.liveness): the pool reads
        # its age to tell a wedged replica from a slow one
        stop_beat = liveness.start_heartbeat(
            mgr, HEARTBEAT_PREFIX + str(idx))
        outq.put(("up", idx, os.getpid(), pred.version))
        if elastic_cfg and el_state["boot"] == "cold":
            # seed the supervisor's params mirror so the NEXT incarnation
            # can adopt instead of cold-loading
            outq.put(("params_sync", idx, pred.version,
                      _elastic.params_blob(pred.params)))
        try:
            while True:
                try:
                    msg = inq.get(timeout=1.0)
                except _queue.Empty:
                    continue
                kind = msg[0]
                if kind == "stop":
                    break
                if kind == "reload":
                    # bare ("reload",) = latest-wins; ("reload", step) =
                    # pinned (canary candidate / rollback target)
                    pin = msg[1] if len(msg) > 1 else None
                    try:
                        if payload.get("ckpt_dir") \
                                and _maybe_reload(pred, payload["ckpt_dir"],
                                                  step=pin):
                            if engine is not None:
                                engine.set_params(pred.params)
                            if elastic_cfg:
                                outq.put(("params_sync", idx, pred.version,
                                          _elastic.params_blob(pred.params)))
                        outq.put(("reloaded", idx, pred.version))
                    except Exception as e:  # noqa: BLE001 - keep serving
                        logger.exception("reload failed")
                        outq.put(("reload_error", idx, repr(e)))
                elif kind == "resize":
                    _, gen, covered, logical = msg
                    if gen <= el_state["gen"]:
                        continue  # stale generation: epoch-fenced
                    try:
                        ms = _elastic.apply_resize(pred, covered, logical)
                        el_state.update(gen=gen, covered=covered,
                                        resizes=el_state["resizes"] + 1)
                        if engine is not None:
                            engine.set_params(pred.params)
                        outq.put(("resized", idx, gen, covered, ms))
                    except Exception as e:  # noqa: BLE001 - keep serving
                        # on the previous layout; the supervisor retries
                        logger.exception("resize to covered=%s failed",
                                         covered)
                        outq.put(("resize_error", idx, gen, repr(e)))
                elif kind == "stats":
                    st = pred.stats()
                    if engine is not None:
                        st["decode"] = engine.stats()
                    if elastic_cfg:
                        st["elastic"] = dict(el_state)
                    outq.put(("stats", idx, st))
                elif kind == "profile":
                    threading.Thread(
                        target=_profile_capture, name="tfos-profile",
                        args=(outq, idx) + tuple(msg[1:]),
                        daemon=True).start()
                elif kind == "gen":
                    _, sid, blob = msg
                    if engine is None:
                        outq.put(("gen_batch", idx, [
                            ("error", sid, "spec has no decode engine")]))
                        continue
                    try:
                        req = cloudpickle.loads(blob)
                        engine.submit(sid, req["prompt"],
                                      max_tokens=req.get("max_tokens"),
                                      eos_id=req.get("eos_id"),
                                      sampling=req.get("sampling"),
                                      trace=req.get("trace"))
                    except BaseException as e:  # noqa: BLE001 - one bad
                        # session must not take the replica down
                        outq.put(("gen_batch", idx,
                                  [("error", sid, repr(e))]))
                elif kind == "batch":
                    _, batch_id, blob = msg
                    try:
                        inputs, n_valid = cloudpickle.loads(blob)
                        with telemetry.span(telemetry.SERVE_BATCH,
                                            replica=idx, n=n_valid):
                            outputs, device_ms = pred(inputs)
                        meta = {"device_ms": device_ms,
                                "version": pred.version,
                                "replica": idx}
                        outq.put(("done", idx, batch_id,
                                  cloudpickle.dumps(outputs), meta))
                    except BaseException as e:  # noqa: BLE001 - one bad
                        # batch must not take the replica down
                        import traceback

                        outq.put(("batch_error", idx, batch_id,
                                  f"{e!r}\n{traceback.format_exc()}"))
        finally:
            stop_beat.set()
            if engine is not None:
                engine.stop()
            outq.put(("down", idx))
            telemetry.flush()

    return _replica_task


class ReplicaPool:
    """Owns the replicas' engine job, the IPC manager, dispatch, failover
    and hot-reload.  ``dispatch(batch)`` is the MicroBatcher sink."""

    def __init__(self, spec, num_replicas=None, engine=None, env=None,
                 max_retries=None, request_timeout=None):
        self.spec = spec
        self.num_replicas = int(num_replicas or num_replicas_default())
        self._engine = engine
        self._owns_engine = engine is None
        self._env = dict(env) if env else None
        self._max_retries = (max_retries_default() if max_retries is None
                             else int(max_retries))
        self._request_timeout = request_timeout
        self._mgr = None
        self._inqs = {}
        self._lock = threading.Lock()
        # membership, loads and the in-flight batch/session entries all
        # live in the shared dispatch table (actors.dispatch); keys are
        # namespaced ("batch", id) / ("gen", sid)
        self._table = InFlightTable(self.num_replicas)
        self._versions = {}          # idx -> last acked params version
        # staged-rollout state (all under self._lock): the open canary
        # split, the blessed watermark, and bounded per-arm outcome
        # accumulators for the controller's burn-window verdict
        self._canary = None          # {"replicas", "version", "pct"}
        self._watermark = None       # blessed step the pool is pinned to
        self._reload_watermark = None  # newest latest-wins broadcast step
        self._arm_stats = None       # arm -> {"n", "errors", "ms": [...]}
        self._stats_replies = {}
        self._stats_event = threading.Event()
        self._profile_acks = {}      # seq -> (ok, out_dir), by _collect
        self._profile_event = threading.Event()
        self._registered = threading.Event()
        self._job_error = None
        self._stop = threading.Event()
        self._threads = []
        self.respawns_observed = 0

    # -- lifecycle -----------------------------------------------------------
    def start(self, timeout=180.0):
        if self._owns_engine:
            from tensorflowonspark_tpu.engine import LocalEngine

            self._engine = LocalEngine(self.num_replicas, env=self._env)
        authkey = os.urandom(16)
        self._mgr = tfmanager.start(
            authkey,
            [OUT_QUEUE] + [_in_queue(i) for i in range(self.num_replicas)])
        self._inqs = {i: self._mgr.get_queue(_in_queue(i))
                      for i in range(self.num_replicas)}
        self._outq = self._mgr.get_queue(OUT_QUEUE)
        task = _make_replica_task(
            cloudpickle.dumps(self._payload()),
            tuple(self._mgr.address), authkey)

        def _launch():
            try:
                ds = self._engine.parallelize(
                    list(range(self.num_replicas)), self.num_replicas)
                ds.foreach_partition(task, spread=True, retryable=True,
                                     max_retries=self._max_retries)
            except BaseException as e:  # noqa: BLE001 - surfaced by monitor
                self._job_error = e
                logger.error("serving replica job failed: %s", e)

        for name, target in (("tfos-serve-launch", _launch),
                             ("tfos-serve-collect", self._collect),
                             ("tfos-serve-monitor", self._monitor)):
            t = threading.Thread(target=target, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        if self.spec.ckpt_dir:
            t = threading.Thread(target=self._watch_reload,
                                 name="tfos-serve-reload", daemon=True)
            t.start()
            self._threads.append(t)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._job_error is not None:
                raise RuntimeError(
                    f"replica pool failed to start: {self._job_error}")
            if len(self._table.live()) >= self.num_replicas:
                return self
            self._registered.wait(0.2)
            self._registered.clear()
        raise TimeoutError(
            f"replicas not up within {timeout}s "
            f"({len(self._table.live())}/{self.num_replicas})")

    def stop(self):
        if self._stop.is_set():
            return
        self._stop.set()
        err = RuntimeError("replica pool stopped")
        for key, entry in self._table.drain():
            if key[0] == "batch":
                entry["batch"].fail(err)
            else:
                entry["session"]._fail(err)
        for inq in self._inqs.values():
            try:
                inq.put(("stop",))
            except Exception:  # noqa: BLE001 - manager may be gone
                pass
        for t in self._threads:
            if t.name == "tfos-serve-launch":
                t.join(timeout=15)
        if self._owns_engine and self._engine is not None:
            self._engine.stop()
        if self._mgr is not None:
            try:
                self._mgr.shutdown()
            except Exception:  # noqa: BLE001
                pass

    def _payload(self):
        """Replica task payload hook (the elastic pool subclass rides it
        to ship its logical-capacity config alongside the ModelSpec)."""
        return self.spec.to_payload()

    # -- dispatch ------------------------------------------------------------
    def dispatch(self, batch):
        """Send one batcher Batch to the least-loaded live replica.
        Called from the batcher thread; must not block on the device."""
        faults.check("serve.dispatch", what="batch", id=batch.id)
        if self._job_error is not None and not self._table.live():
            raise RuntimeError(
                f"no replicas left (job failed: {self._job_error})")
        blob = cloudpickle.dumps((batch.inputs, batch.n_valid))
        idx = self._route(("batch", batch.id),
                          {"batch": batch, "blob": blob}, batch.id)
        self._inqs[idx].put(("batch", batch.id, blob))

    def dispatch_session(self, session):
        """Send one decode :class:`~.decode.scheduler.PendingSession` to
        the least-loaded live replica.  Same failover contract as batch
        dispatch: a dead replica's sessions re-dispatch to survivors
        (full re-prefill there), and the session's index-keyed ledger
        plus resolve-once ``_set`` make the replay zero-drop/zero-dup.
        """
        faults.check("serve.dispatch", what="gen", id=session.id)
        if self.spec.decode is None:
            raise RuntimeError("spec has no decode engine; pass "
                               "ModelSpec(..., decode=DecodeSpec(...))")
        if self._job_error is not None and not self._table.live():
            raise RuntimeError(
                f"no replicas left (job failed: {self._job_error})")
        blob = cloudpickle.dumps({
            "prompt": session.prompt,
            "max_tokens": session.max_tokens,
            "eos_id": session.eos_id,
            # the resolved sampling dict (seed included) rides the blob,
            # so a failover re-dispatch replays the identical stream
            "sampling": getattr(session, "sampling", None),
            # traceparent header: replica-side admit/retire telemetry
            # joins the originating request's trace tree
            "trace": getattr(session, "trace", None),
        })
        idx = self._route(("gen", session.id),
                          {"session": session, "blob": blob}, session.id)
        self._inqs[idx].put(("gen", session.id, blob))

    def cancel_session(self, sid):
        """Forget a session (client gave up): its slot keeps generating
        replica-side, but late answers find no entry and are dropped."""
        return self._table.pop(("gen", sid)) is not None

    def outstanding_sessions(self):
        return sum(1 for k in self._table.keys() if k[0] == "gen")

    def _route(self, key, entry, route_id):
        """Owner pick: least-loaded overall, or — with a canary open —
        least-loaded within the arm ``route_id`` hashes into.  An arm
        with no live member degrades to any live replica (a routing
        split must never drop a request)."""
        with self._lock:
            canary = self._canary
        if canary is None:
            return self._table.add(key, entry)
        arm = "canary" if canary_arm(route_id, canary["pct"]) else "baseline"
        live = self._table.live()
        if arm == "canary":
            cands = [i for i in live if i in canary["replicas"]]
        else:
            cands = [i for i in live if i not in canary["replicas"]]
        entry["arm"] = arm
        if not cands:
            return self._table.add(key, entry)
        loads = self._table.loads()
        owner = min(cands, key=lambda i: (loads.get(i, 0), i))
        return self._table.add(key, entry, owner=owner)

    def _account(self, entry, ok):
        """Per-arm outcome accounting for a resolved entry dispatched
        under a canary split (no-op otherwise): feeds the
        ``tfos_deploy_*`` metrics and the bounded in-memory stats the
        promotion controller reads via :meth:`canary_stats`."""
        arm = entry.get("arm")
        if arm is None:
            return
        ms = (time.monotonic() - entry["t"]) * 1e3
        metrics_registry.inc("tfos_deploy_requests_total", arm=arm,
                             status="ok" if ok else "error")
        metrics_registry.observe("tfos_deploy_request_ms", ms, arm=arm)
        with self._lock:
            if self._arm_stats is None:
                return
            st = self._arm_stats.get(arm)
            if st is None:
                return
            st["n"] += 1
            if not ok:
                st["errors"] += 1
            st["ms"].append(ms)
            del st["ms"][:-512]  # bounded: enough for burn-window p95

    # -- canary / staged rollout ----------------------------------------------
    def set_watermark(self, step):
        """Pin the blessed version.  While set, the latest-wins reload
        watcher stands down (the promotion controller owns version
        transitions) and freshly-up replicas are steered to their arm's
        pin (:meth:`_enforce_version`).  ``None`` releases the pin."""
        with self._lock:
            self._watermark = None if step is None else int(step)

    def watermark(self):
        with self._lock:
            return self._watermark

    def reload_watermark(self):
        """Newest step the latest-wins reload watcher has broadcast
        (None before the first broadcast).  The fabric router and the
        elastic pool's mirror refresh key respawn convergence on it
        when no promotion watermark pins the pool: a respawn must adopt
        the version the survivors actually serve, not whatever
        checkpoint happens to be newest at its boot instant."""
        with self._lock:
            return self._reload_watermark

    def set_canary(self, replicas, version, pct):
        """Open a canary: pin ``replicas`` at candidate ``version`` (in-
        band pinned reload) and route ~``pct``% of traffic to them.
        The arm must leave at least one baseline replica."""
        arm = tuple(sorted(int(i) for i in replicas))
        live = self._table.live()
        if not arm or not set(arm) <= set(live):
            raise ValueError(f"canary replicas {arm} not all live ({live})")
        if len(arm) >= len(live):
            raise ValueError("canary arm must leave a baseline replica")
        version = int(version)
        with self._lock:
            self._canary = {"replicas": arm, "version": version,
                            "pct": float(pct)}
            self._arm_stats = {
                "canary": {"n": 0, "errors": 0, "ms": []},
                "baseline": {"n": 0, "errors": 0, "ms": []},
            }
        for idx in arm:
            self._inqs[idx].put(("reload", version))
        metrics_registry.set_gauge("tfos_deploy_canary_step", version)
        telemetry.event(telemetry.DEPLOY_CANARY, version=version,
                        replicas=list(arm), pct=float(pct))
        logger.info("canary open: replicas %s at step %d (%s%% traffic)",
                    arm, version, pct)
        return arm

    def promote_canary(self):
        """Candidate wins: reload the baseline at the candidate version,
        advance the watermark, clear the split.  Returns the promoted
        step."""
        with self._lock:
            canary = self._canary
        if canary is None:
            raise RuntimeError("promote_canary: no canary open")
        version = canary["version"]
        for idx in self._table.live():
            if idx not in canary["replicas"]:
                self._inqs[idx].put(("reload", version))
        with self._lock:
            self._watermark = version
            self._canary = None
        logger.info("canary promoted: pool pinned at step %d", version)
        return version

    def rollback_canary(self, step=None):
        """Candidate loses: re-pin the canary arm at the blessed
        watermark (or an explicit ``step``), clear the split.  Returns
        the step rolled back to."""
        with self._lock:
            canary = self._canary
            target = self._watermark if step is None else int(step)
        if canary is None:
            raise RuntimeError("rollback_canary: no canary open")
        if target is None:
            raise RuntimeError("rollback_canary: no watermark to re-pin")
        for idx in canary["replicas"]:
            self._inqs[idx].put(("reload", target))
        with self._lock:
            self._watermark = target
            self._canary = None
        logger.info("canary rolled back: arm %s re-pinned at step %d",
                    canary["replicas"], target)
        return target

    def pin_version(self, step):
        """Pin the WHOLE pool at blessed ``step``: targeted reloads on
        every live replica + the watermark.  The bootstrap promotion
        path (first blessed checkpoint, no baseline to canary against)
        and the recovery path (driver restart re-pins from the newest
        blessed manifest) both land here."""
        step = int(step)
        for idx in self._table.live():
            self._inqs[idx].put(("reload", step))
        self.set_watermark(step)
        return step

    def canary(self):
        """The open split ({"replicas", "version", "pct"}) or None."""
        with self._lock:
            return dict(self._canary) if self._canary else None

    def canary_stats(self):
        """Per-arm outcome snapshot since the split opened:
        ``{arm: {"n", "errors", "p50_ms", "p95_ms"}}`` — the burn-window
        evidence the promotion controller judges."""
        with self._lock:
            stats = self._arm_stats
            out = {}
            if stats is None:
                return out
            for arm, st in stats.items():
                ms = sorted(st["ms"])
                out[arm] = {
                    "n": st["n"],
                    "errors": st["errors"],
                    "p50_ms": ms[len(ms) // 2] if ms else None,
                    "p95_ms": ms[int(len(ms) * 0.95)] if ms else None,
                }
            return out

    def canary_snapshot(self):
        """The split's per-arm outcomes as a registry-shaped snapshot
        (``{metric: {"type", "series": [...]}}``) — the exact input
        ``obs/slo.evaluate`` consumes, so the promotion controller
        judges the burn window with the same SLO math as the live
        metrics plane.  The bounded ms samples are bucketed onto the
        default histogram bounds; empty without an open split."""
        bounds = list(metrics_registry.DEFAULT_BUCKETS_MS)
        counters, hists = [], []
        with self._lock:
            stats = self._arm_stats
            if stats is None:
                return {}
            for arm, st in sorted(stats.items()):
                counters.append({"labels": {"arm": arm, "status": "ok"},
                                 "value": float(st["n"] - st["errors"])})
                counters.append({"labels": {"arm": arm, "status": "error"},
                                 "value": float(st["errors"])})
                counts = [0] * (len(bounds) + 1)
                for v in st["ms"]:
                    for i, b in enumerate(bounds):
                        if v <= b:
                            counts[i] += 1
                            break
                    else:
                        counts[-1] += 1
                hists.append({"labels": {"arm": arm}, "bounds": bounds,
                              "counts": counts, "sum": float(sum(st["ms"])),
                              "count": len(st["ms"])})
        return {"tfos_deploy_requests_total": {"type": "counter",
                                               "series": counters},
                "tfos_deploy_request_ms": {"type": "histogram",
                                           "series": hists}}

    def _enforce_version(self, idx, version):
        """Respawn-mid-rollout convergence: a replica that just came up
        cold-booted at the NEWEST checkpoint, which mid-canary may be
        the unblessed candidate.  Steer it to its arm's pinned version
        with a targeted in-band reload."""
        with self._lock:
            canary, wm = self._canary, self._watermark
        if canary is not None and idx in canary["replicas"]:
            want = canary["version"]
        else:
            want = wm
        if want is None or version == want:
            return
        try:
            self._inqs[idx].put(("reload", want))
        except Exception:  # noqa: BLE001 - manager tearing down
            pass

    # -- background threads ----------------------------------------------------
    def _collect(self):
        """Drain serve_out: replica registrations, answers, acks."""
        while not self._stop.is_set():
            try:
                msg = self._outq.get(timeout=0.25)
            except _queue.Empty:
                continue
            except Exception:  # noqa: BLE001 - manager shut down
                return
            if self._handle_extra(msg):
                continue
            kind = msg[0]
            if kind == "up":
                _, idx, pid, version = msg
                respawned = self._table.up(idx, pid)
                if respawned:
                    self.respawns_observed += 1
                with self._lock:
                    self._versions[idx] = version
                self._registered.set()
                telemetry.event("serve/replica_up", replica=idx, pid=pid,
                                version=version)
                self._enforce_version(idx, version)
                if respawned:
                    # A respawn can beat the monitor's death-detection
                    # poll, so this is the authoritative failover trigger:
                    # batches the dead incarnation had popped are gone;
                    # ones still queued in the inherited inbox will at
                    # worst be answered twice (Batch resolves once, the
                    # duplicate is dropped).  Re-dispatch everything the
                    # old incarnation owned.
                    self._record_lost(idx, "respawned")
                    self._redispatch({idx})
            elif kind == "down":
                self._table.down(msg[1])
            elif kind == "done":
                _, idx, batch_id, payload, meta = msg
                entry = self._table.pop(("batch", batch_id))
                if entry is None:
                    continue  # duplicate answer after a re-dispatch
                try:
                    outputs = cloudpickle.loads(payload)
                    entry["batch"].complete(outputs, meta)
                    self._account(entry, ok=True)
                except Exception as e:  # noqa: BLE001
                    entry["batch"].fail(e)
                    self._account(entry, ok=False)
            elif kind == "batch_error":
                _, idx, batch_id, tb = msg
                entry = self._table.pop(("batch", batch_id))
                if entry is not None:
                    entry["batch"].fail(RuntimeError(
                        f"replica {idx} failed the batch:\n{tb}"))
                    self._account(entry, ok=False)
            elif kind == "gen_batch":
                # one hand-over of a replica's decode engine: an
                # iteration's (or an admission's) events, in its order
                _, idx, events = msg
                for event in events:
                    self._gen_event(idx, *event)
            elif kind == "reloaded":
                with self._lock:
                    self._versions[msg[1]] = msg[2]
                telemetry.event("serve/replica_reloaded", replica=msg[1],
                                version=msg[2])
            elif kind == "stats":
                self._stats_replies[msg[1]] = msg[2]
                self._stats_event.set()
            elif kind == "profiled":
                self._profile_acks[msg[2]] = (msg[3], msg[4])
                self._profile_event.set()
            elif kind in ("init_error", "reload_error"):
                logger.warning("replica %s reported %s: %s",
                               msg[1], kind, msg[2])

    def _gen_event(self, idx, kind, sid, *rest):
        """One decode-session event of replica ``idx``'s ``gen_batch``."""
        if kind == "token":
            # touch: a streamed token proves the stream is alive
            entry = self._table.touch(("gen", sid))
            if entry is not None:
                entry["session"]._token(*rest)
            return
        entry = self._table.pop(("gen", sid))
        if entry is None:
            return  # duplicate answer after a re-dispatch
        if kind == "done":
            entry["session"]._set(*rest)
        else:
            entry["session"]._fail(RuntimeError(
                f"replica {idx} failed the decode session: {rest[0]}"))
        self._account(entry, ok=kind == "done")

    def _handle_extra(self, msg):
        """Subclass hook, called before the base message chain: consume
        pool-specific out-queue traffic (the elastic pool's boot/mirror/
        resize-ack messages).  True when the message was handled."""
        return False

    def _tick(self):
        """Subclass hook, called once per monitor pass (the elastic pool
        rides it to reconcile membership against its assignments)."""

    def _monitor(self):
        """Failure detection: executor-process death (fast path) and
        stale manager-KV heartbeats (wedged-replica path).  Either way
        the replica's in-flight batches are re-dispatched to survivors
        (Batch resolves once, so duplicated answers are no-ops)."""
        while not self._stop.wait(0.2):
            try:
                self._tick()
            except Exception:  # noqa: BLE001 - next pass retries
                logger.exception("pool tick failed")
            now = time.monotonic()
            dead = liveness.scan(self._table.live(), self._proc_alive,
                                 self._beat_age, tfmanager.stale_after())
            for idx, why in dead:
                self._table.lost(idx)
                logger.warning("replica %d lost (%s); re-dispatching its "
                               "in-flight batches", idx, why)
                self._record_lost(idx, why)
            if dead:
                self._redispatch({idx for idx, _ in dead})
            # request timeout: fail requests stuck past the deadline so
            # clients see an error instead of their full wait.  A decode
            # session's ``t`` refreshes on every streamed token
            # (collect), so only a genuinely stalled stream times out —
            # not a long, healthy generation.
            for key, entry in self._table.stale(self._request_timeout, now):
                if key[0] == "batch":
                    entry["batch"].fail(TimeoutError(
                        "batch not answered within "
                        f"{self._request_timeout}s"))
                else:
                    entry["session"]._fail(TimeoutError(
                        "decode session streamed no token within "
                        f"{self._request_timeout}s"))
                self._account(entry, ok=False)

    def _redispatch(self, dead_idxs):
        """Re-send a dead replica's in-flight work to survivors.  Decode
        sessions re-prefill fully on their new replica; greedy decode is
        deterministic, so the survivor re-streams identical (index,
        token) pairs — the session ledger keeps first arrivals and _set
        resolves once.  With no survivor the entries stay assigned: the
        engine-respawned replica drains the inbox it inherited."""
        moved = {"batch": 0, "gen": 0}
        for key in self._table.owned_by(dead_idxs):
            idx = self._table.reassign(key)
            entry = self._table.get(key)
            if idx is None or entry is None:
                continue
            self._inqs[idx].put((key[0], key[1], entry["blob"]))
            moved[key[0]] += 1
        if moved["batch"] or moved["gen"]:
            telemetry.event("serve/redispatch", batches=moved["batch"],
                            sessions=moved["gen"], to=self._table.live())

    def _record_lost(self, idx, why):
        """Record one replica death: the telemetry event plus a
        black-box flight dump of the dispatch table (docs/telemetry.md
        "Flight recorder").  Called from whichever supervision path
        notices first — the monitor's death scan or the respawned
        incarnation's registration."""
        telemetry.event("serve/replica_lost", replica=idx, reason=why)
        try:  # never let a flight dump block failover
            from tensorflowonspark_tpu.obs import flight as _flight

            _flight.snapshot("serve/replica_lost",
                             node=f"replica-{idx}", reason=why,
                             inflight=self._inflight_summary())
        except Exception:  # noqa: BLE001
            logger.debug("flight snapshot failed", exc_info=True)

    def _inflight_summary(self, limit=32):
        """Small-scalar view of the dispatch table for flight dumps —
        ids, owners and trace headers only, never prompts or blobs
        (redaction contract, docs/telemetry.md "Flight recorder")."""
        out = []
        for key in list(self._table.keys())[:limit]:
            entry = self._table.get(key)
            if entry is None:
                continue
            item = {"kind": key[0], "id": key[1]}
            sess = entry.get("session") if isinstance(entry, dict) else None
            if sess is not None and getattr(sess, "trace", None):
                item["trace"] = sess.trace
            out.append(item)
        return out

    def _proc_alive(self, idx):
        procs = getattr(self._engine, "_procs", None)
        if procs is None or idx >= len(procs):
            return True  # foreign engine: no process visibility
        try:
            return procs[idx].is_alive()
        except Exception:  # noqa: BLE001
            return True

    def _beat_age(self, idx):
        return liveness.beat_age(self._mgr, HEARTBEAT_PREFIX + str(idx))

    def _watch_reload(self):
        """Poll utils/checkpoint.latest; broadcast in-band reloads."""
        from tensorflowonspark_tpu.utils import checkpoint as ckpt

        with self._lock:
            last = max(self._versions.values(), default=0)
        interval = reload_secs_default()
        while not self._stop.wait(interval):
            with self._lock:
                managed = (self._watermark is not None
                           or self._canary is not None)
            if managed:
                # a promotion controller owns version transitions:
                # latest-wins broadcasts would race the pinned arms
                continue
            try:
                step, _path = ckpt.latest(self.spec.ckpt_dir)
            except Exception:  # noqa: BLE001 - transient fs error
                continue
            if step is None or step == last:
                continue
            last = step
            with self._lock:
                self._reload_watermark = step
            metrics_registry.inc("tfos_serve_reloads_total")
            telemetry.event(telemetry.SERVE_RELOAD, step=step)
            logger.info("hot-reload: broadcasting checkpoint step %d", step)
            for idx in self._table.live():
                try:
                    self._inqs[idx].put(("reload",))
                except Exception:  # noqa: BLE001
                    pass

    # -- introspection ---------------------------------------------------------
    def live_replicas(self):
        return self._table.live()

    def replica_pids(self):
        return self._table.pids()

    def versions(self):
        with self._lock:
            return dict(self._versions)

    def profile(self, seconds, out_dir, replica=0, timeout=60.0):
        """Have one replica take a profiler capture of ``seconds`` into
        ``out_dir`` (``utils.profiler``: Python tracer off, the
        program's ``tfos/*`` spans in it) while it keeps serving.
        Returns ``out_dir`` once the replica acked; False where capture
        is unavailable in its build; TimeoutError if it never acks."""
        seq = time.monotonic_ns()
        self._inqs[replica].put(
            ("profile", seq, float(seconds), str(out_dir)))
        deadline = time.monotonic() + float(seconds) + timeout
        while seq not in self._profile_acks:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"replica {replica} did not ack the profile directive")
            self._profile_event.wait(0.1)
            self._profile_event.clear()
        ok, where = self._profile_acks.pop(seq)
        return where if ok else False

    def stats(self, timeout=10.0):
        """Broadcast a stats request; gather per-replica predictor stats
        (compile counts per signature, batches, rows, version)."""
        targets = self._table.live()
        self._stats_replies = {}
        self._stats_event.clear()
        for idx in targets:
            self._inqs[idx].put(("stats",))
        deadline = time.monotonic() + timeout
        while (set(self._stats_replies) < set(targets)
               and time.monotonic() < deadline):
            self._stats_event.wait(0.1)
            self._stats_event.clear()
        return dict(self._stats_replies)
