"""Input-pipeline subsystem (data/): composable graph semantics, the
ColumnChunk wire contract with DataFeed, the disaggregated data service
(exactly-once unit ledger + fault resume), and the telemetry stall
decomposition through scripts/trace_merge.py.

Parity intent: these are the redesigned counterparts of the reference's
DataFeed/TFNode tests (test_TFNode.py) plus the guarantees the reference
never had — deterministic global shuffle, exactly-once epoch accounting,
and a killed data worker resuming at its shard cursor (SURVEY.md §2,
PARITY.md §2.1).
"""

import json
import os
import secrets
import subprocess
import sys

import numpy as np
import pytest

from tensorflowonspark_tpu import data, marker, recordio
from tensorflowonspark_tpu import manager as tfmanager
from tensorflowonspark_tpu.data import service as dsvc
from tensorflowonspark_tpu.feed import DataFeed
from tensorflowonspark_tpu.utils import faults

pytestmark = pytest.mark.data


def _arrays(n, width=4):
    """Identifiable records: y[i] == i is the record identity."""
    x = (np.arange(n * width, dtype=np.float32).reshape(n, width)) / 7.0
    y = np.arange(n, dtype=np.int64)
    return {"x": x, "y": y}


def _ids(blocks):
    out = []
    for b in blocks:
        out.extend(int(v) for v in np.asarray(b["y"]).ravel())
    return out


# -- graph semantics ---------------------------------------------------------


def test_sources_and_batch_drop_remainder():
    pipe = data.from_arrays(_arrays(50), block_size=8)
    sizes = [data.block_len(b) for b in pipe.blocks()]
    assert sizes == [8] * 6 + [2]
    assert _ids(pipe.blocks()) == list(range(50))

    kept = list(pipe.batch(16).blocks())
    assert [data.block_len(b) for b in kept] == [16, 16, 16, 2]
    assert _ids(kept) == list(range(50))
    # and the content re-chunks losslessly, not just the ids
    np.testing.assert_allclose(
        np.concatenate([b["x"] for b in kept]), _arrays(50)["x"])

    dropped = list(pipe.batch(16, drop_remainder=True).blocks())
    assert [data.block_len(b) for b in dropped] == [16, 16, 16]
    assert _ids(dropped) == list(range(48))


def test_from_dataset_collects_engine_rows():
    from tensorflowonspark_tpu.engine import LocalEngine

    engine = LocalEngine(2, env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    try:
        rows = [([float(i), float(i)], i) for i in range(20)]
        pipe = data.from_dataset(engine.parallelize(rows, 2), block_size=6)
        got = list(pipe.blocks())
    finally:
        engine.stop()
    assert sum(data.block_len(b) for b in got) == 20


def test_shuffle_exactly_once_and_deterministic():
    pipe = data.from_arrays(_arrays(101), block_size=9).shuffle(37, seed=5)
    run1 = _ids(pipe.blocks())
    # every record exactly once per epoch
    assert sorted(run1) == list(range(101))
    assert run1 != list(range(101))  # it actually shuffled
    # two same-seed runs: identical batch order (determinism contract)
    assert _ids(pipe.blocks()) == run1
    # a different seed is a different order over the same records
    other = _ids(data.from_arrays(_arrays(101), block_size=9)
                 .shuffle(37, seed=6).blocks())
    assert sorted(other) == list(range(101)) and other != run1


def test_shard_partitions_shuffled_stream_exactly_once():
    """The global-shuffle correctness contract (ISSUE satellite): with a
    fixed seed, shard(i, n) consumers each see a deterministic stream and
    the union over one epoch is every record exactly once."""
    base = data.from_arrays(_arrays(97), block_size=8).shuffle(97, seed=3)
    shards = [list(_ids(base.shard(i, 3).blocks())) for i in range(3)]
    # deterministic per consumer
    assert [list(_ids(base.shard(i, 3).blocks())) for i in range(3)] == shards
    # disjoint, exactly-once union
    all_ids = [v for s in shards for v in s]
    assert sorted(all_ids) == list(range(97))
    assert len(set(all_ids)) == len(all_ids)
    # the split is by GLOBAL record index over the (shuffled) stream, so
    # shard sizes are balanced to within one record
    assert sorted(len(s) for s in shards) == [32, 32, 33]


def _write_examples(path, rows):
    with recordio.TFRecordWriter(str(path)) as w:
        for feats in rows:
            w.write(recordio.encode_example(feats))


def _shard_dir(tmp_path, n_shards=4, per_shard=12):
    d = tmp_path / "tfr"
    d.mkdir()
    k = 0
    for s in range(n_shards):
        _write_examples(
            d / f"part-r-{s:05d}",
            [{"x": ("float", [float(k + i), 0.5]),
              "y": ("int64", [k + i])} for i in range(per_shard)])
        k += per_shard
    return d, n_shards * per_shard


def test_tfrecords_interleave_parallel_map(tmp_path):
    d, n = _shard_dir(tmp_path)
    pipe = (data.from_tfrecords(str(d), block_size=5)
            .interleave(cycle_length=2)
            .parallel_map(lambda b: {"x": b["x"] * 2.0, "y": b["y"]},
                          num_workers=2))
    got = list(pipe.blocks())
    assert sorted(_ids(got)) == list(range(n))
    allx = np.concatenate([b["x"] for b in got])
    ally = np.concatenate([np.asarray(b["y"]).ravel() for b in got])
    np.testing.assert_allclose(allx[:, 0], ally * 2.0)  # fn really ran
    # interleave actually alternates shards: the first two blocks come
    # from different source files (ids 0.. and 12..)
    first_two = {int(np.asarray(b["y"]).ravel()[0]) // 12 for b in got[:2]}
    assert len(first_two) == 2

    # unordered mode: same multiset, order free
    unord = (data.from_tfrecords(str(d), block_size=5)
             .interleave(2)
             .parallel_map(lambda b: b, num_workers=2, ordered=False))
    assert sorted(_ids(unord.blocks())) == list(range(n))


def test_interleave_requires_multishard_source():
    with pytest.raises(ValueError, match="multi-shard"):
        data.from_arrays(_arrays(10), block_size=4).interleave(2)


def test_cache_spill_repeat_prefetch(tmp_path):
    pipe = data.from_arrays(_arrays(60), block_size=7)
    # memory budget far below the data size forces the spill file path
    cached = pipe.cache(spill_dir=str(tmp_path), memory_bytes=128)
    first = _ids(cached.blocks())
    assert first == list(range(60))
    assert any(f.startswith("tfos-data-cache") for f in os.listdir(tmp_path))
    # second pass replays from the cache, byte-identical ids
    assert _ids(cached.blocks()) == first
    assert _ids(cached.repeat(3).blocks()) == first * 3
    assert _ids(cached.prefetch(2).blocks()) == first
    cached.purge()
    assert not any(f.startswith("tfos-data-cache")
                   for f in os.listdir(tmp_path))


def test_chunks_and_skip_blocks_resume():
    pipe = data.from_arrays(_arrays(40), block_size=6)
    chunks = list(pipe.chunks())
    assert all(isinstance(c, marker.ColumnChunk) for c in chunks)
    # deterministic resume: skipping k blocks lands exactly on the suffix
    resumed = list(pipe.chunks(skip_blocks=3))
    assert len(resumed) == len(chunks) - 3
    for a, b in zip(resumed, chunks[3:]):
        np.testing.assert_array_equal(a.columns[1], b.columns[1])
    # skipping past the end is an empty stream, not an error
    assert list(pipe.chunks(skip_blocks=99)) == []


# -- the ColumnChunk wire contract with DataFeed -----------------------------


@pytest.fixture
def mgr():
    m = tfmanager.start(secrets.token_bytes(8), ["input", "output", "error"])
    yield m
    m.shutdown()


def test_pipeline_chunks_feed_datafeed_columnar(mgr):
    """Pipeline leaves speak the same ColumnChunk wire format as the
    feeder path: n-D fields round-trip dense through next_batch_columns
    with their original shapes."""
    n = 48
    images = np.arange(n * 4 * 6 * 3, dtype=np.uint8).reshape(n, 4, 6, 3)
    labels = np.arange(n, dtype=np.int64)
    pipe = data.from_arrays({"image": images, "label": labels},
                            block_size=16)
    q = mgr.get_queue("input")
    for c in pipe.chunks():
        assert isinstance(c, marker.ColumnChunk)
        q.put(c)
    q.put(None)

    feed = DataFeed(mgr, train_mode=True,
                    input_mapping={"image": "image", "label": "label"})
    b = feed.next_batch_columns(16)
    assert b["image"].shape == (16, 4, 6, 3)
    assert b["image"].dtype == np.uint8
    np.testing.assert_array_equal(b["image"], images[:16])
    got = [int(v) for v in b["label"]]
    while not feed.should_stop():
        got.extend(int(v) for v in feed.next_batch_columns(16)["label"])
    assert got == list(range(n))


# -- the data service --------------------------------------------------------


def _trainer_meta(m, executor_id, authkey):
    return {"executor_id": executor_id, "host": "localhost",
            "job_name": "worker", "addr": list(m.address),
            "authkey": authkey.hex()}


def _drain_queue(q):
    out = []
    while not q.empty():
        out.append(q.get())
        q.task_done()
    return out


def test_data_service_resumes_at_unit_ledger(monkeypatch):
    """Kill-resume exactly-once proof, transport-level: a data worker
    faulted at the start of unit 1 leaves unit 0 in the PDONE ledger; a
    fresh worker resumes at the cursor and the trainer receives every
    block exactly once, in order."""
    from tensorflowonspark_tpu import rendezvous

    faults._reset_for_tests()
    monkeypatch.setenv(faults.PLAN_ENV, "data.serve:exc@2")
    authkey = secrets.token_bytes(8)
    m = tfmanager.start(authkey, ["input", "output", "error"])
    server = rendezvous.Server(1)
    addr = server.start()
    try:
        cluster_info = [_trainer_meta(m, 0, authkey)]
        cluster_meta = {"server_addr": addr}
        pipe = data.from_arrays(_arrays(100), block_size=10)  # 10 blocks

        svc = dsvc.DataService(pipe, cluster_info, cluster_meta,
                               num_workers=1, worker_index=0, unit_blocks=4)
        with pytest.raises(faults.FaultInjected):
            svc.run()
        # unit 0 (blocks 0-3) was pushed AND recorded before the fault
        assert server.fed_partitions(dsvc.ledger_feed("input", 0)) == [0]

        svc2 = dsvc.DataService(pipe, cluster_info, cluster_meta,
                                num_workers=1, worker_index=0, unit_blocks=4)
        summary = svc2.run()
        assert summary == {0: 60}  # blocks 4-9 only: no re-push of unit 0
        # final partial unit (blocks 8-9) recorded at exhaust
        assert server.fed_partitions(dsvc.ledger_feed("input", 0)) == [0, 1, 2]

        chunks = _drain_queue(m.get_queue("input"))
        assert len(chunks) == 10  # exactly once, no EOF (cluster owns EOF)
        got = [int(v) for c in chunks for v in c.columns[1]]
        assert got == list(range(100))
    finally:
        monkeypatch.delenv(faults.PLAN_ENV)
        faults._reset_for_tests()
        server.stop()
        m.shutdown()


def test_data_service_shards_per_trainer_and_per_worker():
    """rank % num_workers == worker_index assignment + shard(rank, T)
    streams: each trainer sees its strided split exactly once."""
    keys = [secrets.token_bytes(8) for _ in range(2)]
    mgrs = [tfmanager.start(k, ["input", "output", "error"]) for k in keys]
    try:
        cluster_info = [_trainer_meta(m, i, k)
                        for i, (m, k) in enumerate(zip(mgrs, keys))]
        pipe = data.from_arrays(_arrays(40), block_size=5)
        for widx in range(2):  # two workers, one trainer each
            svc = dsvc.DataService(pipe, cluster_info, cluster_meta={},
                                   num_workers=2, worker_index=widx,
                                   unit_blocks=2)
            summary = svc.run()
            assert summary == {widx: 20}
        for rank, m in enumerate(mgrs):
            chunks = _drain_queue(m.get_queue("input"))
            got = [int(v) for c in chunks for v in c.columns[1]]
            assert got == list(range(rank, 40, 2))
    finally:
        for m in mgrs:
            m.shutdown()


def test_data_service_skips_terminating_trainer():
    authkey = secrets.token_bytes(8)
    m = tfmanager.start(authkey, ["input", "output", "error"])
    try:
        m.set("state", "terminating")
        svc = dsvc.DataService(
            data.from_arrays(_arrays(10), block_size=5),
            [_trainer_meta(m, 0, authkey)], cluster_meta={},
            num_workers=1, worker_index=0)
        assert svc.run() == {0: 0}
        assert m.get_queue("input").empty()
    finally:
        m.shutdown()


# -- telemetry: per-stage spans through trace_merge --------------------------


def test_data_stage_spans_and_trace_merge(tmp_path, monkeypatch, mgr):
    from tensorflowonspark_tpu.utils import telemetry

    tdir = tmp_path / "telemetry"
    monkeypatch.setenv(telemetry.DIR_ENV, str(tdir))
    monkeypatch.setenv(telemetry.NODE_ENV, "test-0")
    # earlier in-process cluster tests leak a stale spool/role via
    # telemetry.configure(); a leaked SPOOL_ENV would silently redirect
    # this test's sink away from DIR_ENV
    monkeypatch.delenv(telemetry.SPOOL_ENV, raising=False)
    monkeypatch.delenv(telemetry.ROLE_ENV, raising=False)
    try:
        assert telemetry.enabled()
        pipe = (data.from_arrays(_arrays(64), block_size=8)
                .map(lambda b: b).batch(16).prefetch(2))
        q = mgr.get_queue("input")
        for c in pipe.chunks():
            q.put(c)
        q.put(None)
        feed = DataFeed(mgr, train_mode=True,
                        input_mapping={"x": "x", "y": "y"})
        while not feed.should_stop():
            feed.next_batch_columns(16)
        telemetry.flush()
    finally:
        telemetry.flush()

    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "scripts", "trace_merge.py"),
         str(tdir)],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=""), timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # the per-stage stall table (ISSUE satellite: `-- data --` section)
    assert "-- data (data/stage spans) --" in proc.stdout
    # the feed's consumer is the last row: its tfos/feed/to_columns span
    for stage in ("arrays", "map", "batch", "prefetch", "to_columns"):
        assert stage in proc.stdout, proc.stdout
    trace = json.loads((tdir / "trace.json").read_text())
    spans = [e for e in trace["traceEvents"]
             if e.get("name") == "data/stage"]
    stages = {e["args"]["stage"] for e in spans}
    assert {"arrays", "map", "batch", "prefetch"} <= stages
    fed = [e for e in trace["traceEvents"]
           if e.get("name") == "tfos/feed/to_columns"]
    assert fed and all(e["args"]["wait_ms"] >= 0 for e in fed)
    # prefetch accounts its block time as WAIT (it only stalls, never
    # computes), so downstream stall attribution stays truthful
    pre = [e for e in spans if e["args"]["stage"] == "prefetch"]
    assert pre and all(e["args"]["wait_ms"] >= 0 for e in pre)
    # every span carries the per-block record count for rec/s math (the
    # end-of-feed consumer pull is a legitimate 0-record span)
    assert all("records" in e["args"] for e in spans)
    assert sum(e["args"]["records"] for e in spans) > 0


# -- slow lane: mnist end-to-end through the data service --------------------

BATCH = 25  # == per-trainer shard block size: the aligned consumer path
SOURCE_BLOCK = 50  # shard(rank, 2) halves each block -> 25-record blocks
N_RECORDS = 800  # 16 source blocks -> 16 thin blocks/trainer -> 2 units of 8


def mnist_ds_main(args, ctx):
    """Trainer consuming the data service via next_batch_columns, with
    checkpoint auto-resume (the data-service twin of mnist_ft_main)."""
    import jax
    import optax

    from tensorflowonspark_tpu.models import mnist
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    ckpt_dir = os.path.join(args["model_dir"], f"worker-{ctx.task_index}")
    params = mnist.init_params(jax.random.PRNGKey(0))
    opt = optax.sgd(0.05, momentum=0.9)
    opt_state = opt.init(params)
    saved, start = ctx.restore_latest(ckpt_dir)
    if saved is not None:
        params = saved
    step_fn = jax.jit(mnist.make_train_step(opt))

    feed = ctx.get_data_feed(
        train_mode=True, input_mapping={"image": "image", "label": "label"})
    step = start
    while not feed.should_stop():
        b = feed.next_batch_columns(BATCH)
        if len(b["label"]) < BATCH:
            continue
        images = np.asarray(b["image"], dtype=np.float32)
        labels = np.asarray(b["label"], dtype=np.int32)
        params, opt_state, loss, acc = step_fn(
            params, opt_state, images, labels)
        step += 1
        ckpt.save_checkpoint(ckpt_dir, params, step)


def _synthetic_columns(n):
    rng = np.random.default_rng(0)
    images = rng.random((n, 28, 28, 1), dtype=np.float32)
    q = np.stack(
        [
            images[:, :14, :14, 0].mean((1, 2)),
            images[:, :14, 14:, 0].mean((1, 2)),
            images[:, 14:, :14, 0].mean((1, 2)),
            images[:, 14:, 14:, 0].mean((1, 2)),
        ],
        axis=-1,
    )
    labels = (np.argmax(q, axis=-1) * 2 + (q.sum(-1) > 2.0)).astype(np.int32)
    return images, labels


@pytest.mark.slow
@pytest.mark.faults
def test_mnist_data_service_survives_worker_kill(tmp_path, monkeypatch):
    """The e2e acceptance (ISSUE satellite): mnist trained through
    cluster.run(..., data_workers=1) with the data worker SIGKILLed
    mid-serve.  The engine respawns the executor, the driver recovers the
    cluster, the relaunched worker resumes at its unit ledger
    (data/serve_resume), and the run exits cleanly with checkpoints.

    Kill placement: each trainer's stream is 16 blocks = 2 ledger units,
    and every unit START (plus the exhaust probe) is one data.serve
    check.  Reaching check 5 requires at least two units recorded (a
    unit's start needs its predecessor completed), so after the ledger
    resume the relaunched worker performs at most 4 checks — ``kill@5``
    fires exactly once under any ring-backpressure interleaving."""
    from tensorflowonspark_tpu import cluster as TFCluster
    from tensorflowonspark_tpu.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine
    from tensorflowonspark_tpu.utils import telemetry

    telemetry_dir = tmp_path / "telemetry"
    monkeypatch.setenv(telemetry.DIR_ENV, str(telemetry_dir))
    for k in (telemetry.SPOOL_ENV, telemetry.ROLE_ENV, telemetry.NODE_ENV):
        monkeypatch.delenv(k, raising=False)  # stale leaks misroute sinks
    # this e2e asserts the STATIC service's recovery semantics (unit
    # ledger + shard-cursor resume); the dynamic default has its own
    # kill e2e in test_data_splits.py
    monkeypatch.setenv("TFOS_DATA_DISPATCH", "static")
    monkeypatch.chdir(tmp_path)
    engine = LocalEngine(2, env={
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "TFOS_DATA_DISPATCH": "static",
        faults.PLAN_ENV: "data.serve:kill@5",
    })
    try:
        cluster = TFCluster.run(
            engine, mnist_ds_main, {"model_dir": str(tmp_path / "model")},
            num_executors=2, input_mode=InputMode.SPARK, restarts=1,
            data_workers=1,
        )
        images, labels = _synthetic_columns(N_RECORDS)
        pipe = data.from_arrays({"image": images, "label": labels},
                                block_size=SOURCE_BLOCK)
        cluster.train(pipe, num_epochs=1, feed_timeout=240)
        assert cluster._restarts_used == 1, (
            f"expected one recovery, got {cluster._restarts_used}")
        cluster.shutdown(grace_secs=2)
    finally:
        engine.stop()
        for k in (telemetry.NODE_ENV, telemetry.ROLE_ENV,
                  telemetry.SPOOL_ENV):
            os.environ.pop(k, None)

    # both trainers made it past the kill: checkpoints exist
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    steps = [ckpt.latest_step(str(tmp_path / "model" / f"worker-{i}"))
             for i in range(2)]
    assert all(s and s > 0 for s in steps), f"missing checkpoints: {steps}"

    # the kill, the respawn, and the ledger resume are all on the
    # telemetry timeline, and trace_merge accepts the whole run
    import glob

    raw = ""
    for path in glob.glob(str(telemetry_dir / "**" / "*"), recursive=True):
        if os.path.isfile(path):
            with open(path, errors="replace") as f:
                raw += f.read()
    for ev in ("fault/injected", "engine/executor_respawn",
               "cluster/recover_begin", "data/serve_resume"):
        assert ev in raw, f"telemetry event {ev} missing from drained run"
    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "scripts", "trace_merge.py"),
         str(telemetry_dir)],
        capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=""), timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "-- data (data/stage spans) --" in proc.stdout
