"""FabricRouter: driver-side cross-host dispatch for the serving fabric.

Parity note: the reference's TFCluster.py drives N hosts from one
driver over the manager wire for *training*; this is the serving-side
analogue (no reference equivalent for serving itself —
Inference.scala:27-79 stops at offline batch inference).  PARITY.md
§2.2 tracks the mapping.

The router implements the same pool protocol as
``serving.replicas.ReplicaPool`` (``start``/``stop``/``dispatch``/
``dispatch_session``/``cancel_session``/``stats``/...), so
``serving.server.Server`` mounts it unchanged — but its members are
fabric HOSTS (one engine executor each, N worker replicas inside, see
``fabric/host.py``) instead of single local replicas:

- **Cross-host addressing** — envelopes ride per-host manager queues;
  membership, per-host load and every in-flight batch/session live in
  the shared ``actors.dispatch.InFlightTable`` keyed by host index.
  A SIGKILLed host's in-flight entries re-dispatch to survivors;
  ``batcher.Batch``/``PendingSession`` resolve once, so duplicate
  answers from a half-dead host are no-ops (zero drop, zero dup).
- **Session affinity** — ``dispatch_session`` routes a session
  carrying a ``route_id`` to the ``(host, worker)`` whose
  ``PagedKVCache`` still holds its prefix blocks: a live binding wins
  (outcome ``"hit"``), an unknown route goes through the consistent-
  hash ring (``"miss"``), and a dead or saturated target falls back
  least-loaded (``"fallback"``).  The outcome rides the session's
  result meta so load generators can measure ``affinity_hit_rate``.
- **Autoscaling actuation** — the router publishes per-host
  ``{workers, depth}`` to the manager KV (``fabric:load``) and applies
  the ``ServeAutoscaler``'s plan (``fabric:plan``) as generation-fenced
  in-band ``("scale", gen, n)`` directives; acks update the worker map
  the ring is built from.
- **Version convergence** — a respawned host cold-boots at the newest
  checkpoint; ``_enforce_version`` steers it back to the promotion
  watermark when one is set, else to the hot-reload watermark the
  latest-wins watcher last broadcast (the pinned-version contract the
  elastic pool's mirror refresh shares, serving/elastic.py).

Chaos sites: ``serve.fabric_dispatch`` fires before an envelope is
routed, ``serve.fabric_route`` inside the affinity pick (utils/faults).
"""

from __future__ import annotations

import logging
import os
import queue as _queue
import threading
import time
import weakref

import cloudpickle

from tensorflowonspark_tpu import manager as tfmanager
from tensorflowonspark_tpu.actors import liveness
from tensorflowonspark_tpu.actors.dispatch import InFlightTable
from tensorflowonspark_tpu.serving.fabric import host as _host
from tensorflowonspark_tpu.serving.fabric.affinity import AffinityMap, Ring
from tensorflowonspark_tpu.serving.replicas import (
    max_retries_default,
    reload_secs_default,
)
from tensorflowonspark_tpu.utils import faults, metrics_registry, telemetry

logger = logging.getLogger(__name__)

HOSTS_ENV = "TFOS_FABRIC_HOSTS"

# Live routers, for the /statusz "pods" section (obs/http.py) — same
# weak-registry pattern as serving/elastic._POOLS / actors.actor_table.
_ROUTERS = weakref.WeakSet()


def num_hosts_default():
    return int(os.environ.get(HOSTS_ENV, "2"))


def fabric_table():
    """Per-host rows for every live router (the /statusz ``pods``
    section and the ``tfos-top --pods`` pane)."""
    rows = []
    for n, router in enumerate(list(_ROUTERS)):
        try:
            desc = router.describe()
        except Exception:  # noqa: BLE001 - router tearing down
            logger.debug("fabric_table: describe failed", exc_info=True)
            continue
        for hrow in desc.get("hosts", ()):
            rows.append(dict(hrow, router=n))
    return rows


class FabricRouter:
    """Owns the fabric hosts' engine job, the IPC manager, affinity
    routing, failover and the autoscaler loop.  Pool-protocol
    compatible: ``Server(..., fabric=True)`` mounts it as ``pool``."""

    def __init__(self, spec, num_hosts=None, replicas_per_host=1,
                 engine=None, env=None, max_retries=None,
                 request_timeout=None, autoscale=False,
                 affinity_max_load=None):
        self.spec = spec
        self.num_hosts = int(num_hosts or num_hosts_default())
        self.replicas_per_host = max(1, int(replicas_per_host))
        self._engine = engine
        self._owns_engine = engine is None
        self._env = dict(env) if env else None
        self._max_retries = (max_retries_default() if max_retries is None
                             else int(max_retries))
        self._request_timeout = request_timeout
        # autoscale: False | True | {kernel kwargs for ServeAutoscaler}
        self._autoscale = autoscale
        self._asys = None
        self._mgr = None
        self._inqs = {}
        self._lock = threading.Lock()
        self._table = InFlightTable(self.num_hosts)
        self._workers = {}           # host -> acked worker count
        self._versions = {}          # host -> last acked params version
        self._watermark = None       # promotion pin (set_watermark)
        self._reload_watermark = None  # newest latest-wins broadcast
        self._affinity = AffinityMap()
        self._ring = None
        self._ring_sig = None
        self._rr = 0
        decode = getattr(spec, "decode", None)
        self._sat_load = int(affinity_max_load
                             or (decode.slots if decode is not None else 8))
        self._aff = {"hit": 0, "miss": 0, "fallback": 0}
        self._aff_host = {}          # host -> outcome counts
        self._gen = 0                # scale-directive generation fence
        self._plan_applied = 0
        self._last_pub = 0.0
        self.scale_ups = 0
        self.scale_downs = 0
        self.redispatched = 0
        self._stats_replies = {}
        self._stats_event = threading.Event()
        self._registered = threading.Event()
        self._job_error = None
        self._stop = threading.Event()
        self._threads = []
        self.respawns_observed = 0

    # -- lifecycle -----------------------------------------------------------
    def start(self, timeout=180.0):
        if self._owns_engine:
            from tensorflowonspark_tpu.engine import LocalEngine

            self._engine = LocalEngine(self.num_hosts, env=self._env)
        authkey = os.urandom(16)
        self._mgr = tfmanager.start(
            authkey,
            [_host.OUT_QUEUE]
            + [_host._in_queue(h) for h in range(self.num_hosts)])
        self._inqs = {h: self._mgr.get_queue(_host._in_queue(h))
                      for h in range(self.num_hosts)}
        self._outq = self._mgr.get_queue(_host.OUT_QUEUE)
        payload = dict(self.spec.to_payload(),
                       fabric={"replicas_per_host": self.replicas_per_host})
        task = _host._make_host_task(
            cloudpickle.dumps(payload), tuple(self._mgr.address), authkey)

        def _launch():
            try:
                ds = self._engine.parallelize(
                    list(range(self.num_hosts)), self.num_hosts)
                ds.foreach_partition(task, spread=True, retryable=True,
                                     max_retries=self._max_retries)
            except BaseException as e:  # noqa: BLE001 - surfaced by monitor
                self._job_error = e
                logger.error("fabric host job failed: %s", e)

        for name, target in (("tfos-fabric-launch", _launch),
                             ("tfos-fabric-collect", self._collect),
                             ("tfos-fabric-monitor", self._monitor)):
            t = threading.Thread(target=target, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        if self.spec.ckpt_dir:
            t = threading.Thread(target=self._watch_reload,
                                 name="tfos-fabric-reload", daemon=True)
            t.start()
            self._threads.append(t)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._job_error is not None:
                raise RuntimeError(
                    f"fabric failed to start: {self._job_error}")
            if len(self._table.live()) >= self.num_hosts:
                break
            self._registered.wait(0.2)
            self._registered.clear()
        else:
            raise TimeoutError(
                f"fabric hosts not up within {timeout}s "
                f"({len(self._table.live())}/{self.num_hosts})")
        if self._autoscale:
            self._start_autoscaler(authkey)
        _ROUTERS.add(self)
        return self

    def _start_autoscaler(self, authkey):
        """Spawn the supervised ServeAutoscaler actor against this
        router's manager KV (its own ActorSystem, its own process —
        SIGKILL-safe: a respawned incarnation reseeds its plan sequence
        from the KV)."""
        from tensorflowonspark_tpu.actors.policy import SupervisionPolicy
        from tensorflowonspark_tpu.actors.runtime import ActorSystem
        from tensorflowonspark_tpu.serving.fabric.autoscale import (
            ServeAutoscaler,
        )

        opts = dict(self._autoscale) if isinstance(self._autoscale, dict) \
            else {}
        tick = float(opts.pop("tick_secs", 0.5))
        actor = ServeAutoscaler(mgr_addr=tuple(self._mgr.address),
                                mgr_authkey=authkey, **opts)
        self._asys = ActorSystem(1, env=self._env)
        self._asys.spawn(actor, "serve-autoscaler",
                         policy=SupervisionPolicy(tick_secs=tick))

    def stop(self):
        if self._stop.is_set():
            return
        self._stop.set()
        _ROUTERS.discard(self)
        if self._asys is not None:
            try:
                self._asys.stop()
            except Exception:  # noqa: BLE001
                pass
        err = RuntimeError("fabric router stopped")
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
            if t.name == "tfos-fabric-launch":
                t.join(timeout=15)
        if self._owns_engine and self._engine is not None:
            self._engine.stop()
        if self._mgr is not None:
            try:
                self._mgr.shutdown()
            except Exception:  # noqa: BLE001
                pass

    # -- dispatch ------------------------------------------------------------
    def dispatch(self, batch):
        """Send one batcher Batch to the least-loaded live host (predict
        batches coalesce unrelated requests, so session affinity does
        not apply — the host picks its least-busy worker)."""
        faults.check("serve.fabric_dispatch", what="batch", id=batch.id)
        if self._job_error is not None and not self._table.live():
            raise RuntimeError(
                f"no fabric hosts left (job failed: {self._job_error})")
        blob = cloudpickle.dumps((batch.inputs, batch.n_valid))
        h = self._table.add(("batch", batch.id),
                            {"batch": batch, "blob": blob})
        metrics_registry.inc("tfos_fabric_dispatches_total", kind="batch")
        self._inqs[h].put(("batch", batch.id, blob))

    def dispatch_session(self, session):
        """Route one decode session: affinity binding -> consistent-hash
        ring -> least-loaded fallback.  Same failover contract as the
        local pool — a dead host's sessions re-dispatch to survivors
        (full re-prefill there) and resolve exactly once."""
        faults.check("serve.fabric_dispatch", what="gen", id=session.id)
        if self.spec.decode is None:
            raise RuntimeError("spec has no decode engine; pass "
                               "ModelSpec(..., decode=DecodeSpec(...))")
        if self._job_error is not None and not self._table.live():
            raise RuntimeError(
                f"no fabric hosts left (job failed: {self._job_error})")
        blob = cloudpickle.dumps({
            "prompt": session.prompt,
            "max_tokens": session.max_tokens,
            "eos_id": session.eos_id,
            "sampling": getattr(session, "sampling", None),
            "trace": getattr(session, "trace", None),
        })
        route_id = getattr(session, "route_id", None)
        h, rid, outcome = self._route_session(route_id)
        entry = {"session": session, "blob": blob, "rid": rid,
                 "route_id": None if route_id is None else str(route_id),
                 "affinity": outcome}
        owner = self._table.add(("gen", session.id), entry, owner=h)
        metrics_registry.inc("tfos_fabric_dispatches_total", kind="gen")
        if outcome is not None:
            metrics_registry.inc("tfos_fabric_affinity_total",
                                 outcome=outcome)
            with self._lock:
                self._aff[outcome] += 1
                per = self._aff_host.setdefault(
                    owner, {"hit": 0, "miss": 0, "fallback": 0})
                per[outcome] += 1
        self._inqs[owner].put(("gen", session.id, rid, blob))

    def cancel_session(self, sid):
        return self._table.pop(("gen", sid)) is not None

    def outstanding_sessions(self):
        return sum(1 for k in self._table.keys() if k[0] == "gen")

    def _live_workers(self):
        """{live host: acked worker count} (>=1: a host that never
        acked a scale still runs its boot complement)."""
        live = self._table.live()
        with self._lock:
            return {h: max(1, int(self._workers.get(h, 1))) for h in live}

    def _ring_for(self, workers):
        """The consistent-hash ring over live (host, worker) endpoints,
        rebuilt only when membership or worker counts change."""
        sig = tuple(sorted(workers.items()))
        if sig != self._ring_sig:
            self._ring = Ring([(h, r) for h, n in sorted(workers.items())
                               for r in range(n)])
            self._ring_sig = sig
        return self._ring

    def _saturated(self, h, workers, loads):
        return loads.get(h, 0) >= workers.get(h, 1) * self._sat_load

    def _route_session(self, route_id):
        """(host, worker hint, outcome).  ``(None, None, None)`` lets
        the dispatch table pick least-loaded (no route id, or nothing
        live to route against)."""
        faults.check("serve.fabric_route", route=route_id)
        workers = self._live_workers()
        if route_id is None or not workers:
            return None, None, None
        key = str(route_id)
        loads = self._table.loads()
        bound = self._affinity.get(key)
        if bound is not None:
            bh, br = bound
            if (bh in workers and br < workers[bh]
                    and not self._saturated(bh, workers, loads)):
                return bh, br, "hit"
            outcome = "fallback"     # target dead, retired or saturated
        else:
            outcome = "miss"         # first sighting: place via the ring
        h, r = self._ring_for(workers).lookup(key)
        if self._saturated(h, workers, loads):
            cands = [x for x in workers
                     if not self._saturated(x, workers, loads)] or \
                list(workers)
            h = min(cands, key=lambda x: (loads.get(x, 0), x))
            self._rr += 1
            r = self._rr % workers[h]
            outcome = "fallback"
        self._affinity.bind(key, (h, r))
        return h, r, outcome

    # -- version pinning ------------------------------------------------------
    def set_watermark(self, step):
        """Pin the fabric at a blessed version: the latest-wins reload
        watcher stands down and respawned hosts are steered to it."""
        with self._lock:
            self._watermark = None if step is None else int(step)

    def watermark(self):
        with self._lock:
            return self._watermark

    def reload_watermark(self):
        with self._lock:
            return self._reload_watermark

    def _enforce_version(self, h, version):
        """A respawned host cold-boots at the NEWEST checkpoint; steer
        it to the pinned version — the promotion watermark when set,
        else the hot-reload watermark the watcher last broadcast."""
        with self._lock:
            want = (self._watermark if self._watermark is not None
                    else self._reload_watermark)
        if want is None or version == want:
            return
        try:
            self._inqs[h].put(("reload", want))
        except Exception:  # noqa: BLE001 - manager tearing down
            pass

    def _watch_reload(self):
        """Poll utils/checkpoint.latest; broadcast in-band reloads and
        record the step as the reload watermark respawns converge to."""
        from tensorflowonspark_tpu.utils import checkpoint as ckpt

        with self._lock:
            last = max(self._versions.values(), default=0)
        interval = reload_secs_default()
        while not self._stop.wait(interval):
            with self._lock:
                managed = self._watermark is not None
            if managed:
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
            for h in self._table.live():
                try:
                    self._inqs[h].put(("reload",))
                except Exception:  # noqa: BLE001
                    pass

    # -- background threads ----------------------------------------------------
    def _collect(self):
        """Drain fabric_out: host registrations, answers, acks."""
        while not self._stop.is_set():
            try:
                msg = self._outq.get(timeout=0.25)
            except _queue.Empty:
                continue
            except Exception:  # noqa: BLE001 - manager shut down
                return
            kind = msg[0]
            if kind == "up":
                _, h, pid, version, n_workers = msg
                respawned = self._table.up(h, pid)
                if respawned:
                    self.respawns_observed += 1
                with self._lock:
                    self._versions[h] = version
                    self._workers[h] = int(n_workers)
                self._registered.set()
                telemetry.event("serve/fabric_host_up", host=h, pid=pid,
                                version=version, workers=n_workers)
                self._enforce_version(h, version)
                if respawned:
                    # authoritative failover trigger (a respawn can beat
                    # the monitor's death scan) — same contract as
                    # ReplicaPool._collect
                    telemetry.event("serve/fabric_host_lost", host=h,
                                    reason="respawned")
                    self._redispatch({h})
            elif kind == "down":
                self._table.down(msg[1])
            elif kind == "done":
                _, h, batch_id, payload, meta = msg
                entry = self._table.pop(("batch", batch_id))
                if entry is None:
                    continue  # duplicate answer after a re-dispatch
                try:
                    outputs = cloudpickle.loads(payload)
                    entry["batch"].complete(outputs, meta)
                except Exception as e:  # noqa: BLE001
                    entry["batch"].fail(e)
            elif kind == "batch_error":
                _, h, batch_id, tb = msg
                entry = self._table.pop(("batch", batch_id))
                if entry is not None:
                    entry["batch"].fail(RuntimeError(
                        f"fabric host {h} failed the batch:\n{tb}"))
            elif kind == "gen_batch":
                # one hand-over of a worker's decode engine: an
                # iteration's (or an admission's) events, in its order
                _, h, events = msg
                for event in events:
                    self._gen_event(h, *event)
            elif kind == "reloaded":
                with self._lock:
                    self._versions[msg[1]] = msg[2]
            elif kind == "scaled":
                _, h, gen, n_workers = msg
                with self._lock:
                    self._workers[h] = int(n_workers)
            elif kind == "stats":
                self._stats_replies[msg[1]] = msg[2]
                self._stats_event.set()
            elif kind == "init_error":
                logger.warning("fabric host %s reported init_error: %s",
                               msg[1], msg[2])

    def _gen_event(self, h, kind, sid, *rest):
        """One decode-session event of host ``h``'s ``gen_batch`` — same
        contract as ``ReplicaPool._gen_event``."""
        if kind == "token":
            entry = self._table.touch(("gen", sid))
            if entry is not None:
                entry["session"]._token(*rest)
            return
        entry = self._table.pop(("gen", sid))
        if entry is None:
            return  # duplicate answer after a re-dispatch
        if kind == "done":
            tokens, meta = rest
            meta = dict(meta or {})
            meta["host"] = h
            if entry.get("affinity") is not None:
                meta["affinity"] = entry["affinity"]
            entry["session"]._set(tokens, meta)
        else:
            entry["session"]._fail(RuntimeError(
                f"fabric host {h} failed the decode session: {rest[0]}"))

    def _monitor(self):
        """Death/stale detection + plan actuation + load publishing."""
        while not self._stop.wait(0.2):
            now = time.monotonic()
            dead = liveness.scan(self._table.live(), self._proc_alive,
                                 self._beat_age, tfmanager.stale_after())
            for h, why in dead:
                self._table.lost(h)
                logger.warning("fabric host %d lost (%s); re-dispatching "
                               "its in-flight envelopes", h, why)
                telemetry.event("serve/fabric_host_lost", host=h,
                                reason=why)
            if dead:
                self._redispatch({h for h, _ in dead})
            for key, entry in self._table.stale(self._request_timeout, now):
                if key[0] == "batch":
                    entry["batch"].fail(TimeoutError(
                        "batch not answered within "
                        f"{self._request_timeout}s"))
                else:
                    entry["session"]._fail(TimeoutError(
                        "decode session streamed no token within "
                        f"{self._request_timeout}s"))
            try:
                self._apply_plan()
            except Exception:  # noqa: BLE001 - next pass retries
                logger.debug("plan application failed", exc_info=True)
            self._publish_load(now)

    def _apply_plan(self):
        """Actuate the autoscaler's newest plan (``fabric:plan``) as
        generation-fenced in-band scale directives."""
        if self._mgr is None:
            return
        try:
            plan = self._mgr.get(_host.PLAN_KEY)
        except Exception:  # noqa: BLE001 - manager tearing down
            return
        if not isinstance(plan, dict):
            return
        seq = int(plan.get("seq", 0))
        if seq <= self._plan_applied:
            return
        self._plan_applied = seq
        live = set(self._table.live())
        for hs, n in (plan.get("hosts") or {}).items():
            h, n = int(hs), int(n)
            if h not in live:
                continue
            with self._lock:
                cur = self._workers.get(h)
            if cur is None or n == cur:
                continue
            direction = "up" if n > cur else "down"
            if direction == "up":
                self.scale_ups += 1
            else:
                self.scale_downs += 1
            self._gen += 1
            metrics_registry.inc("tfos_fabric_scale_events_total",
                                 direction=direction)
            telemetry.event("serve/fabric_scale", host=h,
                            direction=direction, workers=n, seq=seq)
            logger.info("fabric scale %s: host %d %d -> %d workers",
                        direction, h, cur, n)
            try:
                self._inqs[h].put(("scale", self._gen, n))
            except Exception:  # noqa: BLE001
                pass

    def _publish_load(self, now):
        """Per-host {workers, depth} rollup to the manager KV — the
        autoscaler's input signal — plus the fabric gauges."""
        if now - self._last_pub < 0.5:
            return
        self._last_pub = now
        workers = self._live_workers()
        loads = self._table.loads()
        doc = {"ts": time.time(),
               "hosts": {str(h): {"workers": w,
                                  "depth": int(loads.get(h, 0))}
                         for h, w in workers.items()}}
        try:
            self._mgr.set(_host.LOAD_KEY, doc)
        except Exception:  # noqa: BLE001 - manager tearing down
            pass
        metrics_registry.set_gauge("tfos_fabric_hosts", len(workers))
        metrics_registry.set_gauge("tfos_fabric_replicas",
                                   sum(workers.values()))
        metrics_registry.set_gauge("tfos_fabric_queue_depth",
                                   len(self._table))

    def _redispatch(self, dead_hosts):
        """Re-send a dead host's in-flight envelopes to survivors.
        Re-dispatched sessions re-prefill on worker 0 of the survivor
        and the route is rebound there, so the session's NEXT request
        follows its blocks (deterministic decode keeps the replayed
        stream token-identical; the session ledger + resolve-once
        ``_set`` make it zero-drop/zero-dup)."""
        moved = {"batch": 0, "gen": 0}
        for key in self._table.owned_by(dead_hosts):
            h = self._table.reassign(key)
            entry = self._table.get(key)
            if h is None or entry is None:
                continue
            if key[0] == "batch":
                self._inqs[h].put(("batch", key[1], entry["blob"]))
            else:
                entry["rid"] = 0
                if entry.get("route_id") is not None:
                    self._affinity.bind(entry["route_id"], (h, 0))
                self._inqs[h].put(("gen", key[1], entry["rid"],
                                   entry["blob"]))
            metrics_registry.inc("tfos_fabric_redispatches_total",
                                 kind=key[0])
            moved[key[0]] += 1
            self.redispatched += 1
        if moved["batch"] or moved["gen"]:
            telemetry.event("serve/fabric_redispatch",
                            batches=moved["batch"], sessions=moved["gen"],
                            to=self._table.live())

    def _proc_alive(self, h):
        procs = getattr(self._engine, "_procs", None)
        if procs is None or h >= len(procs):
            return True  # foreign engine: no process visibility
        try:
            return procs[h].is_alive()
        except Exception:  # noqa: BLE001
            return True

    def _beat_age(self, h):
        return liveness.beat_age(self._mgr, _host.HEARTBEAT_PREFIX + str(h))

    # -- introspection ---------------------------------------------------------
    def live_replicas(self):
        return self._table.live()

    def replica_pids(self):
        return self._table.pids()

    def host_pids(self):
        return self._table.pids()

    def versions(self):
        with self._lock:
            return dict(self._versions)

    def affinity_binding(self, route_id):
        """The (host, worker) a route is bound to, or None."""
        return self._affinity.get(str(route_id))

    def affinity_counts(self):
        with self._lock:
            return dict(self._aff)

    def stats(self, timeout=10.0):
        """Broadcast a stats request; gather per-host rollups (worker
        predictor/decode stats keyed by worker id)."""
        targets = self._table.live()
        self._stats_replies = {}
        self._stats_event.clear()
        for h in targets:
            self._inqs[h].put(("stats",))
        deadline = time.monotonic() + timeout
        while (set(self._stats_replies) < set(targets)
               and time.monotonic() < deadline):
            self._stats_event.wait(0.1)
            self._stats_event.clear()
        return dict(self._stats_replies)

    def describe(self):
        """Summary + per-host rows (the /statusz pods section)."""
        live = set(self._table.live())
        loads = self._table.loads()
        pids = self._table.pids()
        with self._lock:
            workers = dict(self._workers)
            versions = dict(self._versions)
            aff = {h: dict(v) for h, v in self._aff_host.items()}
            aff_total = dict(self._aff)
        hosts = []
        for h in range(self.num_hosts):
            a = aff.get(h, {})
            total = sum(a.values())
            hosts.append({
                "host": h,
                "alive": h in live,
                "pid": pids.get(h),
                "replicas": int(workers.get(h, 0)) if h in live else 0,
                "queue_depth": int(loads.get(h, 0)),
                "version": versions.get(h),
                "affinity_hit_rate": (round(a.get("hit", 0) / total, 4)
                                      if total else None),
            })
        return {
            "fabric": True,
            "num_hosts": self.num_hosts,
            "live_hosts": len(live),
            "replicas": sum(int(workers.get(h, 0)) for h in live),
            "autoscale": bool(self._autoscale),
            "scale_ups": self.scale_ups,
            "scale_downs": self.scale_downs,
            "redispatched": self.redispatched,
            "respawns": self.respawns_observed,
            "affinity": aff_total,
            "hosts": hosts,
        }
