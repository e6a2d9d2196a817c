"""Contract test double for the pyspark API surface this framework uses.

NOT a Spark reimplementation: a faithful stand-in backed by the package's
own ``LocalEngine`` (separate executor *processes*, one task slot each —
the same fixture philosophy as the reference's 2-worker local Standalone
cluster, reference test/run_tests.sh:15-22).  Tests insert this package's
parent dir on ``sys.path`` so ``import pyspark`` resolves here **only
when real pyspark is absent**; with real pyspark installed (CI), the same
tests run against genuine Spark.

Faithfulness notes (semantics mirrored from pyspark, not invented):
- ``RDD`` is lazy for ``mapPartitions``, eager for actions.
- ``rdd.barrier().mapPartitions(fn)`` schedules all tasks concurrently,
  one per free slot (Spark barrier execution) — realized here as the
  LocalEngine's ``spread`` dispatch.
- ``SparkContext`` is a process singleton; ``getOrCreate`` returns it.
- Executor processes import the driver's modules fresh (spawn), exactly
  like Spark python workers.
"""

from __future__ import annotations

import os
import sys
import threading

__version__ = "3.5.0-stub"

_STUB_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SparkConf:
    def __init__(self):
        self._conf = {}

    def set(self, key, value):
        self._conf[key] = str(value)
        return self

    def setMaster(self, master):
        return self.set("spark.master", master)

    def setAppName(self, name):
        return self.set("spark.app.name", name)

    def get(self, key, defaultValue=None):
        return self._conf.get(key, defaultValue)

    def getAll(self):
        return list(self._conf.items())


class _JavaConfShim:
    """Mimics sc._jsc.hadoopConfiguration().get(...)."""

    def hadoopConfiguration(self):
        return self

    def get(self, key, default=None):
        if key == "fs.defaultFS":
            return "file:///"
        return default


class SparkContext:
    _active = None
    _lock = threading.Lock()

    def __init__(self, master=None, appName=None, conf=None):
        from tensorflowonspark_tpu.engine import LocalEngine

        with SparkContext._lock:
            if SparkContext._active is not None:
                raise ValueError(
                    "Cannot run multiple SparkContexts at once"
                )
            SparkContext._active = self
        self._conf = conf or SparkConf()
        if master:
            self._conf.setMaster(master)
        if appName:
            self._conf.setAppName(appName)
        n = int(self._conf.get("spark.executor.instances", "2"))
        # Executor env: make this stub importable in children, and pin
        # them to the CPU jax platform (the stub is a test double; it
        # never owns a chip).
        self._engine = LocalEngine(
            n,
            env={
                "PYTHONPATH": _STUB_DIR,
                "TFOS_STUB_POOL_SIZE": str(n),
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            },
        )
        self._jsc = _JavaConfShim()
        self.defaultParallelism = n

    @classmethod
    def getOrCreate(cls, conf=None):
        with cls._lock:
            if cls._active is not None:
                return cls._active
        return cls(conf=conf)

    def getConf(self):
        return self._conf

    def parallelize(self, seq, numSlices=None):
        return RDD(self._engine.parallelize(seq, numSlices), self)

    def union(self, rdds):
        first, rest = rdds[0], rdds[1:]
        return first.union(*rest)

    def cancelAllJobs(self):
        self._engine.cancel_all_jobs()

    def stop(self):
        with SparkContext._lock:
            if SparkContext._active is self:
                SparkContext._active = None
        self._engine.stop()


class RDD:
    """Wraps a LocalDataset behind the pyspark RDD surface."""

    def __init__(self, dataset, sc, barrier=False):
        self._ds = dataset
        self.context = sc
        self._barrier = barrier

    def getNumPartitions(self):
        return self._ds.num_partitions

    def mapPartitions(self, f):
        return RDD(self._ds.map_partitions(f), self.context, self._barrier)

    def map(self, f):
        def _mapper(it, _f=f):
            return [_f(x) for x in it]

        return RDD(self._ds.map_partitions(_mapper), self.context, self._barrier)

    def foreachPartition(self, f):
        self._ds.foreach_partition(f, spread=self._barrier)

    def collect(self):
        return self._ds.collect(spread=self._barrier)

    def count(self):
        return len(self.collect())

    def union(self, *others):
        return RDD(
            self._ds.union(*[o._ds for o in others]), self.context, self._barrier
        )

    def repartition(self, num_partitions):
        return RDD(self._ds.repartition(num_partitions), self.context,
                   self._barrier)

    def barrier(self):
        return RDDBarrier(self)


class RDDBarrier:
    """Parity: pyspark RDDBarrier — mapPartitions under barrier scheduling
    (all tasks concurrent, one per slot)."""

    def __init__(self, rdd):
        self._rdd = rdd

    def mapPartitions(self, f):
        return RDD(self._rdd._ds.map_partitions(f), self._rdd.context, barrier=True)


class TaskContext:
    _ctx = None

    @classmethod
    def get(cls):
        return cls._ctx

    def partitionId(self):
        return int(os.environ.get("TFOS_EXECUTOR_INDEX", "0"))

    @staticmethod
    def resources():
        return {}


class _TaskInfo:
    def __init__(self, address):
        self.address = address


class BarrierTaskContext(TaskContext):
    """Executor-side barrier context; addresses are the executor pool."""

    @classmethod
    def get(cls):
        return cls()

    def getTaskInfos(self):
        n = int(os.environ.get("TFOS_STUB_POOL_SIZE", "1"))
        return [_TaskInfo(f"127.0.0.1:{i}") for i in range(n)]

    def barrier(self):
        pass


def _ensure_stub_warning():
    if "PYTEST_CURRENT_TEST" not in os.environ and not os.environ.get(
        "TFOS_ALLOW_SPARK_STUB"
    ):
        sys.stderr.write(
            "warning: using the tensorflowonspark_tpu pyspark test stub, "
            "not real Spark\n"
        )


_ensure_stub_warning()
