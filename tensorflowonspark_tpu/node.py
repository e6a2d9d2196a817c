"""Executor-side node runtime (parity: reference TFSparkNode.py).

One framework node per engine executor.  The node task:

1. claims TPU chips for this process (tpu_info, parity: _get_gpus),
2. derives its job/task from the cluster template,
3. starts the per-executor IPC manager (manager.py),
4. registers with the driver's rendezvous server and awaits the full
   cluster (rendezvous.py),
5. exports the JAX-distributed bootstrap env (coordinator address +
   process id — the TF_CONFIG equivalent, TFSparkNode.py:366-374),
6. runs the user ``main_fun(args, ctx)`` — foreground for direct-read
   workers, background process for InputMode.SPARK workers so the executor
   slot frees up for feeder tasks, control-queue wait loop for
   ps/evaluator (TFSparkNode.py:411-443).

The feeder/inference/shutdown closures at the bottom reattach to the
node's manager through the executor-id file (util.py:77-94 pattern) and
move data in **chunks** (lists of records), not per-record.
"""

from __future__ import annotations

import itertools
import json
import logging
import multiprocessing
import os
import signal
import socket
import sys
import tempfile
import threading
import time
import traceback

from tensorflowonspark_tpu import manager as tfmanager
from tensorflowonspark_tpu import marker, rendezvous, tpu_info
from tensorflowonspark_tpu.utils import (
    faults,
    get_ip_address,
    metrics_registry,
    read_executor_id,
    reap_child,
    telemetry,
    track_child_pid,
    write_executor_id,
)

logger = logging.getLogger(__name__)

# Records per queue chunk on the feed path; one IPC hop per chunk.
FEED_CHUNK_RECORDS = int(os.environ.get("TFOS_FEED_CHUNK", "1024"))


def _feed_chunk_records():
    """Chunk size resolved where the feeder RUNS, not where it was pickled.

    The feeder closures are cloudpickled by value, which snapshots module
    globals from the driver — so :data:`FEED_CHUNK_RECORDS` as seen by an
    executor would silently be the *driver's* import-time value.  Reading
    the env at call time lets per-executor overrides (``LocalEngine(env=
    {"TFOS_FEED_CHUNK": ...})``) actually pace the feed."""
    try:
        return int(os.environ.get("TFOS_FEED_CHUNK", "")) or FEED_CHUNK_RECORDS
    except ValueError:
        return FEED_CHUNK_RECORDS

COMPUTE_JOBS = ("chief", "master", "worker")


class _NodeState:
    """Per-executor-process globals (parity: TFSparkNode class attrs)."""

    mgr = None
    cluster_id = None
    epoch = 0  # cluster incarnation this node belongs to
    ring = None  # shm feed ring (creator side), kept alive for the cluster
    tb_proc = None  # TensorBoard child of the dashboard node


def _teardown_node_state():
    """Dismantle this executor's node incarnation — background trainer,
    IPC manager, shm ring, TensorBoard — so a retried node task or a new
    cluster epoch can boot clean on the same executor.  Best-effort
    throughout: the incarnation being torn down may already be half dead."""
    mgr = _NodeState.mgr
    if mgr is not None:
        try:
            bg = mgr.get("bg_pid")
            if bg:
                reap_child(int(str(bg)), timeout=0.2, term_first=False)
        except Exception:  # noqa: BLE001
            pass
        try:
            mgr.shutdown()
        except Exception:  # noqa: BLE001
            pass
    if _NodeState.ring is not None:
        try:
            _NodeState.ring.close()
        except Exception:  # noqa: BLE001
            pass
    if _NodeState.tb_proc is not None:
        try:
            _NodeState.tb_proc.kill()
        except Exception:  # noqa: BLE001
            pass
    _NodeState.mgr = None
    _NodeState.cluster_id = None
    _NodeState.ring = None
    _NodeState.tb_proc = None


def _get_cluster_spec(cluster_info):
    """{job: [node_meta sorted by task_index]} (TFSparkNode.py:43-56)."""
    spec = {}
    for meta in sorted(cluster_info, key=lambda m: m["executor_id"]):
        spec.setdefault(meta["job_name"], []).append(meta)
    for job, nodes in spec.items():
        seen = {}
        for n in nodes:
            if n["task_index"] in seen:
                raise RuntimeError(
                    f"duplicate task_index {n['task_index']} in job {job}: "
                    f"{n} vs {seen[n['task_index']]}"
                )
            seen[n["task_index"]] = n
    return spec


def _distributed_env(cluster_info):
    """Bootstrap info for jax.distributed (the TF_CONFIG replacement).

    Compute processes (chief/master/worker) get contiguous process ids
    with the chief first; the coordinator is process 0's reserved
    host:port.  ps/evaluator nodes are *not* part of the SPMD job.
    """
    compute = [m for m in cluster_info if m["job_name"] in COMPUTE_JOBS]
    compute.sort(key=lambda m: (m["job_name"] not in ("chief", "master"), m["executor_id"]))
    ids = {m["executor_id"]: i for i, m in enumerate(compute)}
    coordinator = f"{compute[0]['host']}:{compute[0]['port']}" if compute else None
    return {
        "coordinator_address": coordinator,
        "num_processes": len(compute),
        "process_ids": ids,
    }


class TFNodeContext:
    """Node metadata handed to user code (parity: TFSparkNode.py:59-99)."""

    def __init__(
        self,
        executor_id,
        job_name,
        task_index,
        cluster_spec,
        default_fs,
        working_dir,
        mgr,
        cluster_info=None,
        epoch=0,
    ):
        self.executor_id = executor_id
        self.job_name = job_name
        self.task_index = task_index
        self.cluster_spec = cluster_spec
        self.default_fs = default_fs
        self.working_dir = working_dir
        self.mgr = mgr
        self.cluster_info = cluster_info or []
        self.epoch = epoch  # cluster incarnation (bumped by recovery)

    @property
    def num_workers(self):
        return sum(len(v) for k, v in self.cluster_spec.items() if k in COMPUTE_JOBS)

    def absolute_path(self, path):
        from tensorflowonspark_tpu import feed

        return feed.hdfs_path(self, path)

    def get_data_feed(
        self, train_mode=True, qname_in="input", qname_out="output",
        input_mapping=None, metrics=None,
    ):
        from tensorflowonspark_tpu.feed import DataFeed

        return DataFeed(
            self.mgr, train_mode, qname_in, qname_out, input_mapping, metrics
        )

    def restore_latest(self, ckpt_dir, target_shardings=None):
        """(tree, start_step) from the newest checkpoint in ``ckpt_dir``
        regardless of who wrote it (npz or orbax layouts; (None, 0) when
        empty) — the auto-resume half of ``cluster.run(restarts=N)``:
        training mains call this at startup, so a relaunched incarnation
        continues from where the dead one last saved.

        ``target_shardings`` (pytree of ``Sharding`` or callable
        ``tree -> shardings``) re-places the restored leaves under this
        incarnation's mesh — required after an elastic resize, where the
        checkpoint was written under a different topology
        (``utils/checkpoint.restore_any``, docs/elastic.md)."""
        from tensorflowonspark_tpu.utils import checkpoint as _ckpt

        tree, step = _ckpt.restore_any(ckpt_dir,
                                       target_shardings=target_shardings)
        telemetry.event("node/resume", step=step, epoch=self.epoch,
                        found=tree is not None,
                        resharded=target_shardings is not None)
        if tree is not None:
            logger.info("node %s:%s resuming from step %d (epoch %d)",
                        self.job_name, self.task_index, step, self.epoch)
        return tree, step

    def elastic_runtime(self, mesh_axes, devices=None, global_batch=0,
                        accum_axis="data"):
        """An :class:`elastic.ElasticRuntime` for this node: the logical
        mesh shape ``mesh_axes`` resolved over this incarnation's
        devices (default: all devices visible after
        ``jax_initialize``).  A relaunched node on a shrunken cluster
        gets a smaller physical mesh for the SAME logical shape, with
        gradient accumulation making up the difference
        (docs/elastic.md)."""
        from tensorflowonspark_tpu import elastic

        return elastic.from_context(
            self,
            elastic.TrainSpec(mesh_axes=dict(mesh_axes),
                              global_batch=int(global_batch),
                              accum_axis=accum_axis),
            devices=devices)

    def distributed_env(self):
        env = _distributed_env(self.cluster_info)
        return {
            "coordinator_address": env["coordinator_address"],
            "num_processes": env["num_processes"],
            "process_id": env["process_ids"].get(self.executor_id),
        }

    def jax_initialize(self):
        """Join the multi-controller JAX job (TF_CONFIG/MWMS replacement).

        No-op for ps/evaluator roles (they own no chips).  Single-process
        jobs skip jax.distributed but still run the slice health check —
        the silent libtpu-fallback (training on host CPU) is most common
        exactly there.
        """
        env = self.distributed_env()
        if env["process_id"] is None:  # ps/evaluator: no accelerator claim
            return env
        if env["num_processes"] > 1:
            self._export_tpu_process_group()
            import jax

            plat = (os.environ.get("JAX_PLATFORMS")
                    or str(getattr(jax.config, "jax_platforms", None) or ""))
            if plat.split(",")[0].strip() == "cpu":
                # multi-process SPMD on the CPU backend needs the gloo
                # cross-process collectives; without them every sharded
                # computation fails with "Multiprocess computations
                # aren't implemented on the CPU backend".  Must be set
                # before the backend initializes.
                jax.config.update(
                    "jax_cpu_collectives_implementation", "gloo")
            jax.distributed.initialize(
                coordinator_address=env["coordinator_address"],
                num_processes=env["num_processes"],
                process_id=env["process_id"],
            )
            self._jax_distributed = True
        # slice health at bring-up (SURVEY.md §5): a process that joined
        # the job but sees a wedged chip or a short device count should
        # say so here, where the error queue still reaches the driver,
        # not via a hang in the first collective
        from tensorflowonspark_tpu import tpu_info

        health = tpu_info.slice_health(
            expected_processes=env["num_processes"])
        env["slice_health"] = health
        if not health["healthy"]:
            logger.error("slice health check failed: %s", health["errors"])
            # TFOS_SLICE_HEALTH modes:
            #   lenient (default) — definite findings (wrong device
            #     counts, CPU fallback, smoke failure) are fatal; a probe
            #     that merely TIMED OUT with nothing else found is
            #     warn-only, because the first contact with a large
            #     slice can exceed any fixed window (widen via
            #     TFOS_SLICE_HEALTH_TIMEOUT).
            #   strict — everything fatal, including probe timeouts:
            #     fail-fast for deployments that prefer a bring-up error
            #     over a possible hang in the first collective.
            #   warn — log only, never fatal.
            mode = os.environ.get(
                "TFOS_SLICE_HEALTH", "lenient").strip().lower()
            if mode not in ("strict", "lenient", "warn"):
                logger.warning(
                    "unknown TFOS_SLICE_HEALTH=%r; treating as 'lenient' "
                    "(valid: strict|lenient|warn)", mode)
                mode = "lenient"
            only_timeout = health.get("bare_timeout", False)
            # raising here routes through the node wrapper's exception
            # path onto the error queue, which the feeder/driver observe
            if mode != "warn" and not (only_timeout and mode == "lenient"):
                raise RuntimeError(
                    f"unhealthy accelerator slice: {health['errors']}")
        else:
            logger.info(
                "slice healthy: %d local / %d global devices (%s)",
                health["local_devices"], health["global_devices"],
                health["platform"])
        return env

    def _export_tpu_process_group(self):
        """Processes of this job that share a host and each claimed
        chips (``cluster.run(num_chips=N)``) must be described to the
        TPU runtime as ONE job before it starts: each claim alone is a
        complete one-process job, and ``jax.distributed`` cannot join
        those afterwards.  The peers' order is the order of their chip
        blocks (``_same_host_index``)."""
        if not os.environ.get("TPU_VISIBLE_CHIPS"):
            return  # natural visibility: one process owns the host
        me = next(m for m in self.cluster_info
                  if m["executor_id"] == self.executor_id)
        compute = [m for m in self.cluster_info
                   if m["job_name"] in COMPUTE_JOBS]
        peers = sorted(m["executor_id"] for m in compute
                       if m["host"] == me["host"])
        if len(peers) < 2:
            return
        if len(peers) != len(compute):
            raise RuntimeError(
                f"{len(peers)} of this job's {len(compute)} processes "
                f"share host {me['host']} and each claimed chips: one "
                "TPU job across several hosts needs one process per host "
                "(leave num_chips unset)")
        tpu_info.export_process_group(
            peers.index(self.executor_id), len(peers))

    def sync_exit_barrier(self):
        """Cross-process barrier run by the node wrapper after user code
        returns: every process drains its async dispatch queue and waits
        for its peers before tearing down its collective endpoints.

        Without this, a worker that finishes feeding first exits while a
        peer's final all-reduce is still in flight and resets the
        connection mid-collective (the TPU-native analogue of the
        reference's grace_secs-before-export contract, TFCluster.py:125).
        """
        if not getattr(self, "_jax_distributed", False):
            return
        try:
            from jax.experimental import multihost_utils

            # blocks until every process reaches it, and its collective is
            # ordered after all previously dispatched collectives on every
            # participant
            multihost_utils.sync_global_devices("tfos_node_exit")
        except Exception as e:  # noqa: BLE001 - best-effort on teardown
            logger.warning("exit barrier failed: %s", e)

    def export_env(self):
        """Export bootstrap env vars for subprocesses (TF_CONFIG parity)."""
        env = self.distributed_env()
        os.environ["TFOS_COORDINATOR"] = env["coordinator_address"] or ""
        os.environ["TFOS_NUM_PROCESSES"] = str(env["num_processes"])
        os.environ["TFOS_PROCESS_ID"] = str(
            env["process_id"] if env["process_id"] is not None else -1
        )
        os.environ["TFOS_CLUSTER_SPEC"] = json.dumps(
            {k: [f"{m['host']}:{m['port']}" for m in v] for k, v in self.cluster_spec.items()}
        )


def _job_for_executor(cluster_template, executor_id):
    for job, ids in cluster_template.items():
        if executor_id in ids:
            return job, sorted(ids).index(executor_id)
    raise RuntimeError(f"executor {executor_id} not in template {cluster_template}")


def run(fn, tf_args, cluster_meta, tensorboard=False, log_dir=None,
        queues=None, background=False, num_chips=0):
    """Build the node-startup closure (parity: TFSparkNode.run :149-445)."""
    queues = queues or ["input", "output", "error", "control"]

    def _mapfn(iterator):
        boot_t0 = time.perf_counter()
        executor_id = None
        for item in iterator:  # one element per spread partition
            executor_id = item
        assert executor_id is not None, "empty node partition"

        # (1) claim TPU chips before any jax/XLA initialization —
        # scheduler (Spark-3 resources API) first, host scan second
        # (decision table: tpu_info.claim_chips, ref TFSparkNode.py:170-229)
        tpu_info.claim_chips(num_chips, _same_host_index(executor_id))

        # (2) role from template
        job_name, task_index = _job_for_executor(
            cluster_meta["cluster_template"], executor_id
        )

        # Pin telemetry identity + node-local spool for this process AND
        # its fork children (trainer), via the env channel.  In-process
        # engines (sparkstub) may run this in the driver itself — never
        # relabel the driver's recorder there.  The spool must live
        # OUTSIDE the engine scratch cwd: engine.stop() rmtree's the
        # scratch root, and flight dumps (*.json) are not part of the
        # *.jsonl drain — a dump written moments before a crash has to
        # survive engine teardown.  Non-dot dir name on purpose:
        # postmortem's recursive glob skips dotdirs.
        if os.environ.get(telemetry.ROLE_ENV) != "driver":
            base = os.environ.get(telemetry.DIR_ENV) or os.path.join(
                tempfile.gettempdir(), ".tfos_telemetry")
            cid = cluster_meta["id"] & 0xffffffff
            telemetry.configure(
                node_id=f"{job_name}-{task_index}",
                role=job_name,
                spool=os.path.join(
                    os.path.abspath(base),
                    f"spool-{cid:x}-{job_name}-{task_index}"),
            )

        faults.check("node.boot", executor=executor_id, job=job_name)

        # (3) idempotency/retry guard (TFSparkNode.py:249-255), epoch-aware:
        # a live manager from the SAME cluster AND epoch means a duplicate
        # placement — raise so the engine/Spark retries this task elsewhere.
        # A node from a PREVIOUS epoch (cluster recovery relaunched us on a
        # surviving executor) is stale: tear it down and boot fresh.
        epoch = int(cluster_meta.get("epoch", 0))
        if (_NodeState.mgr is not None
                and _NodeState.cluster_id == cluster_meta["id"]):
            try:
                state = str(_NodeState.mgr.get("state"))
            except Exception:  # noqa: BLE001 - manager server already dead
                state = None
            if (_NodeState.epoch == epoch
                    and state in ("running", "terminating")):
                raise RuntimeError(
                    f"executor already hosts a node of cluster "
                    f"{cluster_meta['id']}"
                )
            logger.info(
                "tearing down stale node incarnation (epoch %d state %s) "
                "before booting epoch %d", _NodeState.epoch, state, epoch)
            _teardown_node_state()

        authkey = bytes.fromhex(cluster_meta["authkey"])
        mode = "remote" if job_name in ("ps", "evaluator") else "local"
        mgr = tfmanager.start(authkey, queues, mode)
        _NodeState.mgr = mgr
        _NodeState.cluster_id = cluster_meta["id"]
        _NodeState.epoch = epoch
        write_executor_id(executor_id)

        # Everything up to execution is boot: a failure here (rendezvous
        # rejection, injected fault, dead ring) must release this
        # executor's node identity — manager, ring, children — so an
        # engine-level retry of the SAME task can boot clean instead of
        # tripping the duplicate-placement guard forever.
        try:
            # Fast same-host feed transport: a shared-memory ring for the
            # 'input' stream (native/shmqueue.cpp).  The manager keeps
            # control/error/output and the state machine; the ring carries
            # the bulk record chunks with no per-chunk manager RPC.
            if os.environ.get("TFOS_SHM_FEED", "1") != "0":
                try:
                    from tensorflowonspark_tpu.recordio import shm as shmq

                    if shmq.available():
                        # epoch in the name: a recovered cluster's fresh ring
                        # must never collide with a dead incarnation's shm
                        # segment that a wedged orphan still maps
                        ring_name = (
                            f"/tfos-{cluster_meta['id'] & 0xffffffff:x}"
                            f"{'' if not epoch else f'-e{epoch}'}"
                            f"-{executor_id}")
                        cap = int(os.environ.get("TFOS_SHM_FEED_BYTES", str(256 << 20)))
                        _NodeState.ring = shmq.ShmQueue(ring_name, cap, create=True)
                        mgr.set("shm_input", ring_name)
                except Exception as e:  # noqa: BLE001 - optional acceleration
                    logger.warning("shm feed unavailable: %s", e)

            # (4) rendezvous: reserve a port for the coordinator service (the
            # free-port trick, TFSparkNode.py:337-342), then register.
            client = rendezvous.Client(cluster_meta["server_addr"])
            host = get_ip_address()
            tmp_sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            tmp_sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            port_env = os.environ.get("TFOS_NODE_PORT")
            tmp_sock.bind(("", int(port_env) if port_env else 0))
            port = tmp_sock.getsockname()[1]
            maddr = list(mgr.address)
            if mode == "remote" and maddr[0] in ("", "0.0.0.0"):
                maddr[0] = host  # advertise a dialable address to the driver
            node_meta = {
                "executor_id": executor_id,
                "host": host,
                "job_name": job_name,
                "task_index": task_index,
                "port": port,
                "addr": maddr,
                "authkey": cluster_meta["authkey"],
            }

            # dashboard node: spawn TensorBoard before registering so its
            # port travels with the reservation (TFSparkNode.py:282-319)
            if (
                tensorboard
                and task_index == 0
                and job_name in ("chief", "master", "worker")
                and ("chief" not in cluster_meta["cluster_template"]
                     and "master" not in cluster_meta["cluster_template"]
                     or job_name in ("chief", "master"))
            ):
                from tensorflowonspark_tpu.utils import profiler as _profiler

                tb_dir = log_dir or os.path.join(
                    cluster_meta["working_dir"], "tensorboard",
                    f"cluster-{cluster_meta['id'] & 0xffffffff:x}",
                )
                _NodeState.tb_proc, tb_port = _profiler.launch_tensorboard(tb_dir)
                if tb_port:
                    node_meta["tb_port"] = tb_port
                    # pid in the manager KV so the shutdown closure (which
                    # may run in a different python worker) can kill the
                    # child
                    mgr.set("tb_pid", _NodeState.tb_proc.pid)
                    telemetry.event("node/tb_spawn", port=tb_port,
                                    pid=_NodeState.tb_proc.pid)

            client.register(node_meta, epoch=epoch)
            cluster_info = client.await_reservations(
                timeout=cluster_meta.get("reservation_timeout", 600)
            )
            client.close()
            logger.info("node %d: cluster complete (%d nodes)", executor_id, len(cluster_info))

            # (5) context + bootstrap env
            cluster_spec = _get_cluster_spec(cluster_info)
            ctx = TFNodeContext(
                executor_id,
                job_name,
                task_index,
                cluster_spec,
                cluster_meta["default_fs"],
                cluster_meta["working_dir"],
                mgr,
                cluster_info,
                epoch=epoch,
            )
            ctx.export_env()

            # release the reserved port as late as possible
            tmp_sock.close()

            # Boot complete: chips claimed, manager up, rendezvous done.
            # The spool dir is advertised in the manager KV so the driver
            # drain (cluster.shutdown -> drain_telemetry) can find every
            # node file.
            telemetry.register_with(mgr)
            telemetry.record_span(
                "node/boot", time.perf_counter() - boot_t0,
                executor=executor_id, nodes=len(cluster_info))
        except BaseException:
            telemetry.flush()
            _teardown_node_state()
            raise

        def wrapper_fn(args, context):
            if isinstance(args, list):
                sys.argv = args
            # liveness beacon for the feeder: a trainer that stops beating
            # is DEAD, one that beats while busy is merely SLOW
            hb = tfmanager.start_heartbeat(mgr)
            # live metrics plane: snapshot this process's registry into
            # the manager KV every TFOS_OBS_INTERVAL (None when disabled)
            from tensorflowonspark_tpu.obs import publish as obs_publish

            obs_id = f"{context.job_name}-{context.task_index}"
            pub = obs_publish.start_publisher(mgr, obs_id,
                                              role=context.job_name)
            from tensorflowonspark_tpu.obs.health import HealthHalt

            try:
                with telemetry.span("node/main", job=context.job_name,
                                    task=context.task_index):
                    faults.check("node.main", job=context.job_name,
                                 task=context.task_index)
                    fn(args, context)
                # all processes leave together (see sync_exit_barrier
                # docstring)
                context.sync_exit_barrier()
            except HealthHalt as e:
                # a health reaction (TFOS_HEALTH_ACTION=halt) already
                # checkpointed at the last finite step; stop this node
                # cleanly — no exit barrier (peers halting on the same
                # anomaly stop on their own; waiting on a diverged run
                # would burn exactly the chip hours halt exists to save)
                logger.warning("node %s:%d health halt: %s",
                               context.job_name, context.task_index, e)
                telemetry.event("health/halt", job=context.job_name,
                                task=context.task_index, reason=str(e))
                try:
                    mgr.set("state", "terminating")  # feeders drain
                except Exception:  # noqa: BLE001 - manager tearing down
                    pass
            finally:
                hb.set()
                if pub is not None:
                    pub.set()
                    # the thread's final publish races process exit; land
                    # the tail counts synchronously
                    obs_publish.publish_once(mgr, obs_id,
                                             role=context.job_name)
                telemetry.flush()

        def wrapper_fn_background(args, context):
            # fork child: the pid-keyed recorder opens its own sink file;
            # advertise it for the shutdown drain
            telemetry.register_with(mgr)
            errq = mgr.get_queue("error")
            try:
                wrapper_fn(args, context)
            except Exception:  # noqa: BLE001 - forwarded via error queue
                errq.put(traceback.format_exc())

        # (6) execute (TFSparkNode.py:411-443)
        if job_name in ("ps", "evaluator") or background:
            logger.info(
                "starting %s:%d on executor %d in background process",
                job_name, task_index, executor_id,
            )
            fork = multiprocessing.get_context("fork")
            p = fork.Process(target=wrapper_fn_background, args=(tf_args, ctx))
            p.daemon = job_name in ("ps", "evaluator")
            p.start()
            # Reapability contract: the shutdown closure (manager KV) and the
            # engine's teardown (pid file) must both be able to find this
            # child — a crashed run must never leave an orphaned trainer
            # wedging interpreter exit on the resource-tracker pipe.
            mgr.set("bg_pid", p.pid)
            track_child_pid(p.pid)
            if job_name in ("ps", "evaluator"):
                _control_wait_loop(mgr, job_name)
        else:
            logger.info(
                "starting %s:%d on executor %d in foreground",
                job_name, task_index, executor_id,
            )
            wrapper_fn(tf_args, ctx)
            logger.info("finished %s:%d on executor %d", job_name, task_index, executor_id)

    return _mapfn


def _same_host_index(executor_id):
    """Worker index among same-host peers for chip partitioning."""
    try:
        return int(os.environ.get("TFOS_EXECUTOR_INDEX", executor_id))
    except (TypeError, ValueError):
        return executor_id


def _control_wait_loop(mgr, job_name):
    """Block a ps/evaluator slot until the driver sends None
    (TFSparkNode.py:420-438)."""
    queue = mgr.get_queue("control")
    equeue = mgr.get_queue("error")
    while True:
        while queue.empty() and equeue.empty():
            time.sleep(1)
        if not equeue.empty():
            e_str = equeue.get()
            equeue.task_done()
            raise RuntimeError(f"exception in {job_name}:\n{e_str}")
        msg = queue.get(block=True)
        queue.task_done()
        logger.info("%s got control msg: %s", job_name, msg)
        if msg is None:
            logger.info("terminating %s", job_name)
            mgr.set("state", "stopped")
            return


def _get_manager(cluster_info, host, executor_id):
    """Reattach to this executor's manager (TFSparkNode.py:119-146)."""
    for meta in cluster_info:
        if meta["executor_id"] == executor_id:
            addr = tuple(meta["addr"])
            authkey = bytes.fromhex(meta["authkey"])
            return tfmanager.connect(addr, authkey)
    raise RuntimeError(
        f"no node of this cluster on executor {executor_id} (host {host}); "
        f"cluster_info={[(m['host'], m['executor_id']) for m in cluster_info]}"
    )


def _open_feed_ring(mgr, qname, producer_nonblock=False):
    """Producer-side handle on the shared transport handshake (feed.py)."""
    from tensorflowonspark_tpu.feed import open_feed_ring

    return open_feed_ring(mgr, qname, producer=True,
                          producer_nonblock=producer_nonblock)


def _raise_if_consumer_lost(mgr, equeue):
    """Fail the feeder fast when the consumer errored or died.

    The error queue is PEEKED — get, then put back — so an engine/Spark
    retry of this feeder task still observes a persistent worker failure
    (a consuming read would make the retry hang on an empty queue until
    feed_timeout).  Heartbeat age (manager.py) distinguishes DEAD from
    SLOW: a busy trainer keeps beating, a killed one goes stale; no beat
    ever recorded means 'unknown', never 'dead'."""
    if not equeue.empty():
        e_str = equeue.get()
        equeue.task_done()
        equeue.put(e_str)
        raise RuntimeError(f"exception in worker:\n{e_str}")
    age = tfmanager.heartbeat_age(mgr)
    if age is not None and age > tfmanager.stale_after():
        raise RuntimeError(
            f"consumer appears dead: no heartbeat for {age:.0f}s "
            f"(stale after {tfmanager.stale_after():.0f}s, "
            f"TFOS_HEARTBEAT_STALE)")


class _Terminating(Exception):
    """The consumer asked for termination while the feeder waited for
    room in the ring."""


def _await_ring_consumption(mgr, ring, pos, feed_timeout):
    """Wait until the consumer has taken everything up to ``pos``, the
    ring's position behind the feeder's last byte (what its last commit
    returned) — in the ring's own back-off, not in a poll, and whatever a
    later producer has written behind it.  The error queue and the
    consumer's heartbeat are checked once a second."""
    equeue = mgr.get_queue("error")
    deadline = time.monotonic() + feed_timeout
    while True:
        try:
            return ring.wait_consumed(pos, timeout_ms=1000)
        except TimeoutError:
            _raise_if_consumer_lost(mgr, equeue)
            if time.monotonic() > deadline:
                raise TimeoutError(
                    "timed out waiting for consumption of partition"
                ) from None


def _await_consumption(mgr, waiter, feed_timeout, poll=1.0):
    """Wait for the consumer to drain what we queued, polling the error
    queue and the consumer heartbeat (parity: TFSparkNode.py:484-497).
    ``waiter()`` returns True while data is still outstanding."""
    equeue = mgr.get_queue("error")
    timeout = feed_timeout
    while waiter():
        _raise_if_consumer_lost(mgr, equeue)
        time.sleep(poll)
        timeout -= poll
        if timeout <= 0:
            raise TimeoutError("timed out waiting for consumption of partition")


class _ChunkEncoder:
    """Per-partition chunk encoder: all-numeric row chunks go columnar
    (marker.ColumnChunk via marshal.rows_to_columns — ~10x cheaper to
    serialize, ~2x smaller on the wire than pickled row lists); chunks
    with string/object/ragged columns stay as plain row lists.

    n-D ndarray fields (images: [H, W, C] uint8) are flattened to width
    H*W*C columns — reshape VIEWS, no copy — with the original trailing
    shape carried in ``ColumnChunk.shapes`` so the consumer can slice
    dense ``[n, H, W, C]`` batches with zero per-record python work
    (``DataFeed.next_batch_columns``).

    The first row fixes the spec; the first row that breaks it (shape
    drift, an object column) turns the encoder ``off`` for good and that
    chunk, like every later one, travels as the row list it was."""

    def __init__(self):
        self.off = os.environ.get("TFOS_COLUMNAR_FEED", "1") == "0"
        self.spec = None
        self.shapes = None

    def _flatten(self, row):
        import numpy as np

        out = []
        for i, v in enumerate(row):
            if self.shapes[i] is not None:
                if not (isinstance(v, np.ndarray)
                        and v.shape == self.shapes[i]):
                    raise TypeError(
                        f"field {i} shape drift: expected {self.shapes[i]}, "
                        f"got {getattr(v, 'shape', type(v).__name__)}")
                v = v.reshape(-1)
            out.append(v)
        return tuple(out)

    def _fix_spec(self, row):
        import numpy as np

        from tensorflowonspark_tpu.recordio import marshal

        if not isinstance(row, (tuple, list)):
            raise TypeError("non-tuple row")
        shapes = tuple(
            v.shape if isinstance(v, np.ndarray) and v.ndim > 1 else None
            for v in row)
        self.shapes = shapes if any(s is not None for s in shapes) else None
        if self.shapes is not None:
            row = self._flatten(row)
        spec = marshal.infer_spec(row)
        if any(c == "O" for c, _ in spec):
            raise TypeError("object column")
        self.spec = spec

    def _give_up(self, e):
        self.off = True
        logger.info("feed: row-chunk path (columnar not applicable: %s)", e)

    def record_bytes(self, row):
        """Bytes a record takes in a columnar frame, from the partition's
        first ``row`` (which fixes the spec); None on the row path."""
        import numpy as np

        from tensorflowonspark_tpu.recordio import marshal

        if not self.off and self.spec is None:
            try:
                self._fix_spec(row)
            except Exception as e:  # noqa: BLE001 - heterogeneous data
                self._give_up(e)
        if self.off:
            return None
        return sum(np.dtype(d).itemsize * int(np.prod(shape[1:]))
                   for d, shape in marshal.column_descrs(self.spec, 1))

    def __call__(self, chunk, alloc=None):
        """The chunk as a ColumnChunk, or as it came on the row path.
        ``alloc(spec, shapes, descrs)`` brings the column arrays to fill
        (the feeder: views of a frame it reserved in the ring) instead of
        fresh ones; it is not called for a chunk that stays rows, and
        what it raises passes through."""
        from tensorflowonspark_tpu.recordio import marshal

        if self.off:
            return chunk
        try:
            if self.spec is None:
                self._fix_spec(chunk[0])
            rows = (chunk if self.shapes is None
                    else [self._flatten(r) for r in chunk])
        except Exception as e:  # noqa: BLE001 - heterogeneous data: row path
            self._give_up(e)
            return chunk
        out = None if alloc is None else alloc(
            self.spec, self.shapes,
            marshal.column_descrs(self.spec, len(rows)))
        try:
            columns = marshal.rows_to_columns(rows, self.spec, out=out)
        except Exception as e:  # noqa: BLE001 - a row broke the spec
            self._give_up(e)
            return chunk
        return marker.ColumnChunk(self.spec, columns, shapes=self.shapes)


def _frame_records(record_bytes, capacity, limit):
    """Records per frame the feeder encodes in place: the largest power
    of two whose frame takes at most a quarter of the ring — so that the
    consumer copies one frame out while the feeder fills the next, with
    two more in between — capped at ``limit`` (the chunk size).  0 when
    not even one record fits: such chunks are copied in and may wrap."""
    room = capacity // 4 - 4096  # the frame's header and padding
    if record_bytes > room:
        return 0
    n = 1
    while 2 * n * record_bytes <= room:
        n *= 2
    return min(n, limit)


def _partition_index():
    """This feed task's partition id: Spark TaskContext under real
    pyspark, else the engine-exported TFOS_PARTITION_INDEX; -1 when
    neither is known (feed-consumption accounting is then disabled)."""
    try:
        from pyspark import TaskContext

        tc = TaskContext.get()
        if tc is not None:
            return int(tc.partitionId())
    except Exception:  # noqa: BLE001 - no spark on this path
        pass
    try:
        return int(os.environ.get("TFOS_PARTITION_INDEX", "-1"))
    except (TypeError, ValueError):
        return -1


def train(cluster_info, cluster_meta, feed_timeout=600, qname="input",
          skip=None):
    """Feeder closure: push partition records as chunks over the shm ring
    (fast path) or the manager queue (parity: TFSparkNode.train :448-515).

    ``skip`` is a set of partition indices already fully consumed in a
    previous cluster incarnation (rendezvous feed ledger): a relaunched
    feed job drains those partitions without re-feeding, so auto-resumed
    training never sees the same record twice."""
    skip = frozenset(skip or ())

    def _train(iterator):
        pidx = _partition_index()
        if pidx >= 0 and pidx in skip:
            count = sum(1 for _ in iterator)
            logger.info("feeder: partition %d already consumed before "
                        "recovery, skipping %d records", pidx, count)
            telemetry.event("feed/partition_skipped", part=pidx,
                            records=count)
            return
        mgr = _get_manager(cluster_info, get_ip_address(), read_executor_id())
        telemetry.register_with(mgr)
        state = str(mgr.get("state"))
        if state in ("terminating", "stopped"):
            logger.info("feeder: state=%s, skipping/draining partition", state)
            count = sum(1 for _ in iterator)
            logger.info("feeder: discarded %d records", count)
            return
        ring = _open_feed_ring(mgr, qname)
        queue = None if ring is not None else mgr.get_queue(qname)
        equeue = mgr.get_queue("error")
        encode = _ChunkEncoder()
        chunk_records = _feed_chunk_records()
        # records per frame encoded straight into the ring, derived from
        # the first record's size and the ring's; 0: chunks are copied in
        frame_records = 0
        iterator = iter(iterator)
        first = next(iterator, None)
        if first is not None:
            iterator = itertools.chain((first,), iterator)
            nbytes = None if ring is None else encode.record_bytes(first)
            if nbytes:
                frame_records = _frame_records(
                    nbytes, ring.capacity, chunk_records)

        def frame_size():
            """Records of the next frame to encode in place; 0 where the
            chunk is encoded apart and copied in (no ring, no spec, or a
            row has broken it)."""
            return 0 if encode.off else frame_records

        def in_slices(op):
            """``op(timeout_ms)`` waits for room in the ring: run it in
            one-second slices and check between them, so a feeder never
            deadlocks against a consumer that stopped draining, and
            fails fast when the consumer errored or its heartbeat went
            stale."""
            while True:
                try:
                    return op(1000)
                except TimeoutError:
                    if str(mgr.get("state")) == "terminating":
                        raise _Terminating from None
                    _raise_if_consumer_lost(mgr, equeue)

        # one tfos/feeder/chunk span per chunk (never per record): where
        # THIS side of the ring spends its time — inside the partition
        # iterator, waiting for room in the ring, or writing.  Another
        # process than the trainer's, so it reaches the spool only.
        timed = telemetry.active()
        t_src = time.perf_counter()
        last_pos = None  # the ring's position behind our last byte

        def room_waited():
            return ring.room_wait_s if ring is not None else 0.0

        def put(chunk):
            """False once the consumer requested termination mid-feed."""
            nonlocal t_src, last_pos
            faults.check("feed.put", part=pidx)
            t0, w0 = time.perf_counter(), room_waited()
            frame = []

            def alloc(spec, shapes, descrs):
                frame.append(in_slices(lambda ms: ring.reserve_columns(
                    spec, shapes, descrs, timeout_ms=ms)))
                return frame[0]

            try:
                out = encode(chunk, alloc if frame_size() else None)
                t1, w1 = time.perf_counter(), room_waited()
                inplace = bool(frame) and isinstance(out, marker.ColumnChunk)
                if inplace:
                    last_pos = ring.commit()
                elif ring is not None:
                    # after a row that broke the spec mid-frame nothing
                    # of the frame is published: the chunk goes as rows
                    ring.drop()
                    last_pos = in_slices(
                        lambda ms: ring.put(out, timeout_ms=ms))
                else:
                    queue.put(out, block=True)
            except _Terminating:
                return False
            if inplace:
                metrics_registry.inc("tfos_feed_frames_inplace_total")
            else:
                metrics_registry.inc("tfos_feed_frames_copied_total")
            if timed:
                now, w2 = time.perf_counter(), room_waited()
                if inplace:  # one pass: the encoding IS the write
                    times = {"write_ms": now - t0 - (w2 - w0)}
                else:
                    times = {"encode_ms": t1 - t0 - (w1 - w0),
                             "write_ms": now - t1 - (w2 - w1)}
                telemetry.record_span(
                    telemetry.FEEDER_CHUNK, now - t_src, part=pidx,
                    records=len(chunk), inplace=int(inplace),
                    source_ms=round((t0 - t_src) * 1e3, 3),
                    room_wait_ms=round((w2 - w0) * 1e3, 3),
                    **{k: round(v * 1e3, 3) for k, v in times.items()})
                t_src = now
            return True

        total = 0
        terminated = False
        chunk = []
        for item in iterator:
            chunk.append(item)
            if len(chunk) >= (frame_size() or chunk_records):
                if not put(chunk):
                    terminated = True
                    break
                total += len(chunk)
                chunk = []
        if chunk and not terminated:
            if put(chunk):
                total += len(chunk)
            else:
                terminated = True
        # a feeder that passed the entry state check before terminate()
        # set the flag may have queued its whole (small) partition without
        # any put ever blocking — re-check here so it never waits on a
        # consumer that already stopped draining
        if not terminated and str(mgr.get("state")) == "terminating":
            terminated = True
        if terminated:
            discarded = sum(1 for _ in iterator)
            logger.info("feeder: termination mid-feed, discarded %d records",
                        discarded + len(chunk))
        logger.info("feeder: queued %d records (%s path)", total,
                    "shm" if ring is not None else "manager")
        telemetry.event("feed/partition_queued", part=pidx, records=total,
                        path="shm" if ring is not None else "manager",
                        terminated=terminated)

        # the hand-over: this task holds the ring until the consumer has
        # taken its last byte; the next partition's feeder waits behind it
        with telemetry.span(telemetry.FEEDER_HANDOFF, part=pidx):
            if ring is not None:
                if not terminated and last_pos is not None:
                    # terminate()'s drain loop keeps reading while we hold
                    # the producer flock, so the consumer always gets there
                    _await_ring_consumption(mgr, ring, last_pos,
                                            feed_timeout)
                ring.close()
            else:
                joining = threading.Thread(target=queue.join, daemon=True)
                joining.start()
                _await_consumption(mgr, joining.is_alive, feed_timeout)

        # fully consumed, not cut short: record it in the driver's feed
        # ledger so a post-recovery relaunch of this feed job skips it.
        # Best-effort — standalone tests feed against a placeholder
        # server_addr with no rendezvous listening.
        if not terminated and pidx >= 0:
            try:
                client = rendezvous.Client(cluster_meta["server_addr"])
                client.partition_done(qname, pidx)
                client.close()
            except Exception as e:  # noqa: BLE001 - accounting only
                logger.warning(
                    "feeder: could not record partition %d consumed: %s",
                    pidx, e)

        if str(mgr.get("state")) == "terminating":
            logger.info("feeder: consumer requested termination")
            client = rendezvous.Client(cluster_meta["server_addr"])
            client.request_stop()

    return _train


def inference(cluster_info, cluster_meta, feed_timeout=600, qname="input"):
    """Inference closure: feed a partition, collect exactly as many results
    (parity: TFSparkNode.inference :518-579)."""

    def _inference(iterator):
        mgr = _get_manager(cluster_info, get_ip_address(), read_executor_id())
        telemetry.register_with(mgr)
        ring = _open_feed_ring(mgr, qname)
        queue = None if ring is not None else mgr.get_queue(qname)
        encode = _ChunkEncoder()

        def put(item):
            if isinstance(item, list):
                item = encode(item)
            if ring is not None:
                ring.put(item)
            else:
                queue.put(item, block=True)

        count = 0
        chunk = []
        chunk_records = _feed_chunk_records()
        for item in iterator:
            chunk.append(item)
            if len(chunk) >= chunk_records:
                put(chunk)
                count += len(chunk)
                chunk = []
        if chunk:
            put(chunk)
            count += len(chunk)
        put(marker.EndPartition())

        # await consumption with error polling
        if ring is not None:
            _await_consumption(
                mgr, lambda: ring.qsize_bytes() > 0, feed_timeout, poll=0.1
            )
            ring.close()
        else:
            joining = threading.Thread(target=queue.join, daemon=True)
            joining.start()
            _await_consumption(mgr, joining.is_alive, feed_timeout, poll=0.2)
        if count == 0:
            return []  # empty partition: nothing to collect

        # collect exactly `count` results (results arrive as chunks)
        results = []
        out_q = mgr.get_queue("output")
        while len(results) < count:
            got = out_q.get(block=True)
            out_q.task_done()
            if isinstance(got, list):
                results.extend(got)
            else:
                results.append(got)
        logger.info("inference: partition yielded %d results", len(results))
        return results

    return _inference


def shutdown(cluster_info, queues, cluster_id, grace_secs=0):
    """Worker-shutdown closure (parity: TFSparkNode.shutdown :582-636)."""

    def _shutdown(iterator):
        list(iterator)
        executor_id = read_executor_id()
        mgr = _get_manager(cluster_info, get_ip_address(), executor_id)
        logger.info("shutdown: signalling end-of-feed on executor %s", executor_id)
        tb_pid = mgr.get("tb_pid")  # kill TB child (TFSparkNode.py:599-605)
        if tb_pid:
            try:
                os.kill(int(str(tb_pid)), signal.SIGKILL)
            except (OSError, ValueError):
                pass
            try:  # reap when this worker happens to be the spawning parent
                os.waitpid(int(str(tb_pid)), 0)
            except (ChildProcessError, OSError, ValueError):
                pass
            mgr.set("tb_pid", None)
        ring = _open_feed_ring(mgr, "input")
        for qname in queues:
            if qname in ("error", "control"):
                continue  # end-of-feed applies to data queues only
            try:
                if qname == "input" and ring is not None:
                    ring.put(None)
                else:
                    mgr.get_queue(qname).put(None, block=True)
            except Exception as e:  # noqa: BLE001
                logger.warning("shutdown: queue %s: %s", qname, e)
        if ring is not None:
            ring.close()
        if grace_secs:
            time.sleep(grace_secs)
        # PEEK the error queue — get and put back — so an engine/Spark task
        # retry still observes the failure (TFSparkNode.py:624-630).
        equeue = mgr.get_queue("error")
        err = None
        if not equeue.empty():
            err = equeue.get()
            equeue.put(err)
        # Reap the background trainer: it received end-of-feed above and
        # must exit on its own; a worker still alive past the bound is
        # stuck (e.g. crashed feed left it blocked on the ring) and gets
        # killed so no orphan survives the cluster.  The healthy-path
        # budget is deliberately long (feed_timeout scale): the trainer
        # may still be consuming queued batches, compiling, or writing a
        # final checkpoint, and killing working user code loses data — an
        # already-errored worker is reaped fast instead.
        bg_pid = mgr.get("bg_pid")
        if bg_pid:
            budget = (5.0 if err is not None else max(
                grace_secs, float(os.environ.get("TFOS_REAP_TIMEOUT", "600"))
            ))
            exited = reap_child(int(str(bg_pid)), timeout=budget)
            if not exited:
                logger.warning("shutdown: background worker %s did not exit "
                               "cleanly and was killed", bg_pid)
            mgr.set("bg_pid", None)
        if err is not None:
            raise RuntimeError(f"exception in worker:\n{err}")
        mgr.set("state", "stopped")

    return _shutdown


def drain_telemetry(cluster_info):
    """Executor-side telemetry drain closure: flush this process, then
    read every spool dir the node's processes advertised in the manager
    KV (telemetry.register_with) and return the raw JSONL so the driver
    can write one run directory.  Best-effort throughout — a drain
    failure must never turn a clean shutdown into an error."""

    def _drain(iterator):
        list(iterator)
        telemetry.flush()
        out = []
        try:
            executor_id = read_executor_id()
            mgr = _get_manager(cluster_info, get_ip_address(), executor_id)
            spools = mgr.telemetry_spools()
        except Exception as e:  # noqa: BLE001 - drain is best-effort
            logger.warning("telemetry drain: no manager/spools: %s", e)
            return out
        for spool in spools:
            for name, text in telemetry.read_spool(spool):
                out.append((executor_id, name, text))
        return out

    return _drain
