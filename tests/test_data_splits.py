"""Dynamic-split data service (data/splits.py + DynamicDataService),
shared epoch cache (data/cache.py), and the stall autoscaler
(data/autoscale.py).

The guarantees under test are the ISSUE-19 acceptance criteria:

- FCFS split dispatch with discovered eof and pure-arithmetic epochs;
- exactly-once per split id on the PDONE/PQUERY ledger: exact cover,
  permutation-invariant across concurrent claimants, preserved under a
  provider requeue of a dead claimant's splits;
- consumer-side dedup of a re-served split's already-consumed prefix;
- the epoch cache decodes once (memory + spill) and is shared by
  signature;
- the autoscaler's hysteresis decision kernel.

The full-cluster SIGKILL e2e (worker killed mid-split, engine respawn,
record multiset vs the single-process oracle) is the slow lane's
``test_dynamic_service_survives_worker_kill``.
"""

import collections
import os
import secrets
import threading
import time

import numpy as np
import pytest

from tensorflowonspark_tpu import data, rendezvous
from tensorflowonspark_tpu import manager as tfmanager
from tensorflowonspark_tpu.actors import liveness
from tensorflowonspark_tpu.data import autoscale as ascale
from tensorflowonspark_tpu.data import cache as dcache
from tensorflowonspark_tpu.data import service as dsvc
from tensorflowonspark_tpu.data import splits as S
from tensorflowonspark_tpu.feed import DataFeed
from tensorflowonspark_tpu.utils import faults

pytestmark = pytest.mark.data


def _arrays(n, width=4):
    x = (np.arange(n * width, dtype=np.float32).reshape(n, width)) / 7.0
    y = np.arange(n, dtype=np.int64)
    return {"x": x, "y": y}


def _trainer_meta(m, executor_id, authkey):
    return {"executor_id": executor_id, "host": "localhost",
            "job_name": "worker", "addr": list(m.address),
            "authkey": authkey.hex()}


def _drain_ids(q):
    ids = []
    while not q.empty():
        c = q.get()
        q.task_done()
        if c is not None:
            ids.extend(int(v) for v in c.columns[1])
    return ids


# -- sid packing -------------------------------------------------------------


def test_sid_part_roundtrip():
    for sid in [(0, 0), (0, 7), (3, 0), (12, 2**31 + 5)]:
        assert S.part_to_sid(S.sid_to_part(sid)) == sid
    # distinct sids -> distinct ledger parts (the exactly-once key)
    parts = {S.sid_to_part((e, k)) for e in range(4) for k in range(100)}
    assert len(parts) == 400


# -- provider protocol -------------------------------------------------------


class _Board:
    """Board over a bare test manager (no ActorSystem needed)."""

    def __init__(self, qname="input"):
        self.authkey = secrets.token_bytes(8)
        self.mgr = tfmanager.start(self.authkey, [])
        self.board = S.SplitBoard(self.mgr, qname)

    def close(self):
        self.mgr.shutdown()


class _Ctx:
    """Minimal ActorContext stand-in for driving SplitProvider inline."""

    def __init__(self, mgr):
        self.mgr = mgr
        self._kv = {}

    def kv_get(self, key):
        return self._kv.get(key)

    def kv_set(self, key, value):
        self._kv[key] = value


def _provider(ctx, server_addr, num_epochs=1, window=4, stale_secs=None):
    p = S.SplitProvider("input", server_addr=server_addr,
                        num_epochs=num_epochs, window=window,
                        stale_secs=stale_secs)
    p.on_start(ctx)
    return p


def test_provider_posts_fcfs_discovers_eof_and_completes():
    """One simulated worker: FCFS order, eof clamp at the discovered
    split count, epoch advance by id arithmetic, ledger-driven
    completion."""
    tb = _Board()
    server = rendezvous.Server(1)
    addr = server.start()
    try:
        ctx = _Ctx(tb.mgr)
        p = _provider(ctx, addr, num_epochs=2, window=4)
        client = rendezvous.Client(addr)
        got = []
        for _ in range(200):
            p.on_tick(ctx)
            if tb.board.complete():
                break
            sid = tb.board.claim_next()
            if sid is None:
                continue
            tb.board.set_claim(sid, 0)
            if sid[1] >= 3:            # the dataset "has 3 splits"
                tb.board.set_eof(sid[1])
            else:
                got.append(sid)
            client.partition_done(S.split_feed("input"), S.sid_to_part(sid))
        assert tb.board.complete(), "provider never declared completion"
        assert tb.board.eof() == 3
        assert got[:3] == [(0, 0), (0, 1), (0, 2)]  # FCFS posting order
        assert sorted(got) == [(e, k) for e in range(2) for k in range(3)]
        client.close()
    finally:
        server.stop()
        tb.close()


def test_provider_requeues_dead_claimants_splits_to_pin_queue():
    """A claimed-but-never-recorded split whose claimant stopped
    heartbeating goes back on the queue — pinned requeues target the
    originally chosen trainer's pin queue."""
    tb = _Board()
    server = rendezvous.Server(1)
    addr = server.start()
    try:
        # stale window must clear the manager's per-RPC latency (a KV
        # set can cost ~0.2s here), else a live beat still looks stale
        ctx = _Ctx(tb.mgr)
        p = _provider(ctx, addr, num_epochs=1, window=2, stale_secs=1.0)
        p.on_tick(ctx)
        sid = tb.board.claim_next()
        assert sid == (0, 0)
        tb.board.set_claim(sid, 7)       # worker 7 claims...
        tb.board.set_pin(sid, 1)         # ...pins it to trainer 1...
        time.sleep(1.2)                  # ...and dies (no heartbeat ever)
        p.on_tick(ctx)
        # requeued to trainer 1's pin queue, not the shared queue
        assert tb.board.claim_next(ranks=[1]) == sid
        assert tb.board.claim_of(sid) is None
        # a live claimant is NOT swept, however old the claim
        sid2 = tb.board.claim_next()
        tb.board.set_claim(sid2, 3)
        stop = liveness.start_heartbeat(tb.mgr, tb.board.beat_key(3),
                                        interval=0.1)
        time.sleep(1.2)
        p.on_tick(ctx)
        claim = tb.board.claim_of(sid2)
        assert claim is not None and claim[0] == 3
        stop.set()
    finally:
        server.stop()
        tb.close()


def test_provider_resume_skips_ledger_done_splits():
    """Cross-recovery half of exactly-once: a fresh provider (new board,
    durable ledger) never re-posts what the ledger already has."""
    tb = _Board()
    server = rendezvous.Server(1)
    addr = server.start()
    try:
        client = rendezvous.Client(addr)
        # previous incarnation served (0,0) and (0,2); eof was 3
        for k in (0, 2):
            client.partition_done(S.split_feed("input"),
                                  S.sid_to_part((0, k)))
        tb.board.set_eof(3)
        ctx = _Ctx(tb.mgr)
        p = _provider(ctx, addr, num_epochs=1, window=8)
        served = []
        for _ in range(100):
            p.on_tick(ctx)
            if tb.board.complete():
                break
            sid = tb.board.claim_next()
            if sid is None:
                continue
            tb.board.set_claim(sid, 0)
            served.append(sid)
            client.partition_done(S.split_feed("input"), S.sid_to_part(sid))
        assert tb.board.complete()
        assert served == [(0, 1)]   # only the missing split re-posted
        client.close()
    finally:
        server.stop()
        tb.close()


# -- dynamic service: exact cover across concurrent workers ------------------


N_RECORDS = 120
BLOCK = 6          # 20 blocks
SPLIT_BLOCKS = 4   # -> 5 splits per epoch


def _run_dynamic_workers(n_workers, n_trainers, num_epochs=1,
                         use_cache=False):
    """Board + provider + ``n_workers`` DynamicDataService threads over
    ``n_trainers`` bare trainer managers; returns per-trainer id lists."""
    keys = [secrets.token_bytes(8) for _ in range(n_trainers)]
    mgrs = [tfmanager.start(k, ["input", "output", "error"]) for k in keys]
    tb = _Board()
    server = rendezvous.Server(1)
    addr = server.start()
    try:
        tb.board.set_plan(range(n_workers))
        ctx = _Ctx(tb.mgr)
        p = _provider(ctx, addr, num_epochs=num_epochs, window=8)
        cluster_info = [_trainer_meta(m, i, k)
                        for i, (m, k) in enumerate(zip(mgrs, keys))]
        meta = {
            "server_addr": addr,
            dsvc.SPLIT_BOARD_META: {"address": tuple(tb.mgr.address),
                                    "authkey": tb.authkey},
        }
        pipe = data.from_arrays(_arrays(N_RECORDS), block_size=BLOCK)

        stop_ticking = threading.Event()

        def _tick():
            while not stop_ticking.is_set() and not tb.board.complete():
                p.on_tick(ctx)
                time.sleep(0.02)

        ticker = threading.Thread(target=_tick, daemon=True)
        ticker.start()
        workers = [
            dsvc.DynamicDataService(
                pipe, cluster_info, meta, worker_index=w,
                split_blocks=SPLIT_BLOCKS, feed_timeout=60,
                use_cache=use_cache)
            for w in range(n_workers)
        ]
        for w in workers:
            # nothing drains the trainer queues until the workers are
            # done, so the cap must exceed one whole run's chunk count
            w.queue_cap = 4 * (N_RECORDS // BLOCK) * num_epochs
        threads = [threading.Thread(target=w.run, daemon=True)
                   for w in workers]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=90)
            assert not t.is_alive(), "dynamic worker wedged"
        stop_ticking.set()
        ticker.join(timeout=5)
        assert tb.board.complete()
        return [_drain_ids(m.get_queue("input")) for m in mgrs]
    finally:
        server.stop()
        tb.close()
        for m in mgrs:
            m.shutdown()


def test_dynamic_exact_cover_single_worker():
    per_trainer = _run_dynamic_workers(1, 2)
    allids = [v for ids in per_trainer for v in ids]
    assert sorted(allids) == list(range(N_RECORDS))
    # both trainers actually receive splits (round-robin tie-break)
    assert all(ids for ids in per_trainer), [len(i) for i in per_trainer]


def test_dynamic_exact_cover_two_workers_and_epochs():
    """Permutation-invariance gate: whatever FCFS interleaving two
    concurrent claimants land on, the union of delivered records is an
    exact cover — each id exactly ``num_epochs`` times."""
    per_trainer = _run_dynamic_workers(2, 2, num_epochs=2)
    counts = collections.Counter(
        v for ids in per_trainer for v in ids)
    assert counts == {i: 2 for i in range(N_RECORDS)}


def test_dynamic_exact_cover_through_shared_cache():
    """Same exactness when blocks replay from the shared epoch cache."""
    dcache.clear()
    try:
        per_trainer = _run_dynamic_workers(1, 1, num_epochs=2,
                                           use_cache=True)
        counts = collections.Counter(per_trainer[0])
        assert counts == {i: 2 for i in range(N_RECORDS)}
    finally:
        dcache.clear()


# -- consumer dedup of re-served prefixes ------------------------------------


def test_datafeed_drops_reserved_split_prefix():
    """A re-served split (worker died after pushing, before recording)
    arrives tagged with the same (sid, seq) pairs; the feed keeps one
    copy of each chunk and never double-delivers a record."""
    from tensorflowonspark_tpu import marker

    authkey = secrets.token_bytes(8)
    m = tfmanager.start(authkey, ["input", "output", "error"])
    try:
        pipe = data.from_arrays(_arrays(24), block_size=6)
        chunks = list(pipe.chunks())
        q = m.get_queue("input")
        sid = (0, 0)
        for seq, c in enumerate(chunks[:2]):
            c.meta = ("split", sid, seq, seq + 1)
            q.put(c)
        # worker died; split re-served WHOLE to the same trainer
        for seq, c in enumerate(chunks[:4]):
            c2 = marker.ColumnChunk(c.spec, c.columns, shapes=c.shapes,
                                    meta=("split", sid, seq, seq + 1))
            q.put(c2)
        q.put(None)
        feed = DataFeed(m, train_mode=True,
                        input_mapping={"x": "x", "y": "y"})
        got = []
        while not feed.should_stop():
            got.extend(int(v) for v in feed.next_batch_columns(6)["y"])
        assert got == list(range(24)), got
    finally:
        m.shutdown()


# -- kill-mid-split at the transport level -----------------------------------


def test_dynamic_service_fault_mid_split_requeues_and_stays_exact(
        monkeypatch):
    """A worker faulted mid-split (after pushing a chunk, before the
    record) leaves the split claimed-but-undone; the provider sweeps it
    back (pinned), a fresh worker re-serves it whole, and the feed-level
    dedup keeps delivery exact."""
    faults._reset_for_tests()
    monkeypatch.setenv(faults.PLAN_ENV, "data.split_serve:exc@6")
    authkey = secrets.token_bytes(8)
    m = tfmanager.start(authkey, ["input", "output", "error"])
    tb = _Board()
    server = rendezvous.Server(1)
    addr = server.start()
    try:
        tb.board.set_plan([0])
        ctx = _Ctx(tb.mgr)
        p = _provider(ctx, addr, num_epochs=1, window=8, stale_secs=0.8)
        cluster_info = [_trainer_meta(m, 0, authkey)]
        meta = {
            "server_addr": addr,
            dsvc.SPLIT_BOARD_META: {"address": tuple(tb.mgr.address),
                                    "authkey": tb.authkey},
        }
        pipe = data.from_arrays(_arrays(N_RECORDS), block_size=BLOCK)
        for _ in range(30):
            p.on_tick(ctx)
            if tb.board.queue_depth():
                break
        svc = dsvc.DynamicDataService(
            pipe, cluster_info, meta, worker_index=0,
            split_blocks=SPLIT_BLOCKS, feed_timeout=60, use_cache=False)
        with pytest.raises(faults.FaultInjected):
            svc.run()
        monkeypatch.delenv(faults.PLAN_ENV)
        faults._reset_for_tests()
        # let the claim of the faulted worker go stale, then sweep it
        time.sleep(1.0)
        p.on_tick(ctx)
        svc2 = dsvc.DynamicDataService(
            pipe, cluster_info, meta, worker_index=0,
            split_blocks=SPLIT_BLOCKS, feed_timeout=60, use_cache=False)
        done = threading.Event()

        def _tick():
            while not done.is_set() and not tb.board.complete():
                p.on_tick(ctx)
                time.sleep(0.02)

        t = threading.Thread(target=_tick, daemon=True)
        t.start()
        svc2.run()
        done.set()
        t.join(timeout=5)
        assert tb.board.complete()
        # the QUEUE holds duplicates of the re-served prefix by design;
        # the consumer-side feed is what must stay exact
        feed = DataFeed(m, train_mode=True,
                        input_mapping={"x": "x", "y": "y"})
        m.get_queue("input").put(None)
        got = []
        while not feed.should_stop():
            got.extend(int(v) for v in feed.next_batch_columns(6)["y"])
        assert sorted(got) == list(range(N_RECORDS))
        assert len(got) == N_RECORDS  # zero duplicates delivered
    finally:
        monkeypatch.delenv(faults.PLAN_ENV, raising=False)
        faults._reset_for_tests()
        server.stop()
        tb.close()
        m.shutdown()


# -- shared epoch cache ------------------------------------------------------


def test_epoch_cache_incremental_fill_and_random_access():
    pipe = data.from_arrays(_arrays(60), block_size=7)  # 9 blocks
    c = dcache.EpochCache(pipe, memory_bytes=1 << 30)
    try:
        # random-ish access fills incrementally, never recomputes
        b5 = c.block(5)
        assert [int(v) for v in b5["y"]] == list(range(35, 42))
        assert c.num_blocks is None          # end not discovered yet
        assert c.block(0) is not None
        assert c.block(9) is None            # past EOF
        assert c.num_blocks == 9
        ids = [int(v) for b in c.blocks_range(2, 3) for v in b["y"]]
        assert ids == list(range(14, 35))
    finally:
        c.close()


def test_epoch_cache_spills_past_memory_budget(tmp_path):
    pipe = data.from_arrays(_arrays(80), block_size=8)  # 10 blocks
    c = dcache.EpochCache(pipe, memory_bytes=1,  # force immediate spill
                          spill_dir=str(tmp_path))
    try:
        ids = [int(v) for b in c.blocks_range() for v in b["y"]]
        assert ids == list(range(80))
        assert c._spill_path and os.path.exists(c._spill_path)
        # replay out of the spill, including seeks into the middle
        again = [int(v) for b in c.blocks_range(4, 2) for v in b["y"]]
        assert again == list(range(32, 48))
    finally:
        c.close()
    assert not os.path.exists(c._spill_path or "")


def test_shared_cache_registry_keys_by_signature():
    dcache.clear()
    try:
        arrays = _arrays(40)
        p1 = data.from_arrays(arrays, block_size=5)
        p2 = data.from_arrays(arrays, block_size=5)   # same content
        p3 = data.from_arrays(arrays, block_size=8)   # different graph
        c1 = dcache.shared(p1)
        assert dcache.shared(p2) is c1                # hit by signature
        assert dcache.shared(p3) is not c1
        assert p1.signature() == p2.signature()
        assert p1.signature() != p3.signature()
    finally:
        dcache.clear()


# -- pipeline: blocks_range / signature / chunksize --------------------------


def test_blocks_range_slices_match_oracle():
    import itertools

    pipe = data.from_arrays(_arrays(50), block_size=6).map(lambda b: b)
    oracle = list(pipe.blocks())
    for skip, num in [(0, None), (0, 3), (4, 2), (7, 100), (9, 1)]:
        got = list(pipe.blocks_range(skip, num))
        want = list(itertools.islice(oracle, skip,
                                     None if num is None else skip + num))
        assert [list(map(int, b["y"])) for b in got] == \
            [list(map(int, b["y"])) for b in want], (skip, num)


def test_signature_stable_across_stages():
    base = _arrays(30)
    p = data.from_arrays(base, block_size=5)
    assert p.signature() == data.from_arrays(base, block_size=5).signature()
    assert p.signature() != p.shuffle(7, seed=1).signature()
    assert (p.shuffle(7, seed=1).signature()
            != p.shuffle(7, seed=2).signature())
    assert p.batch(10).signature() != p.batch(10, True).signature()


def test_parallel_map_chunksize_env(monkeypatch):
    from tensorflowonspark_tpu.data import pipeline as dpipe

    monkeypatch.setenv(dpipe.CHUNKSIZE_ENV, "3")
    pipe = data.from_arrays(_arrays(48), block_size=4).parallel_map(
        lambda b: {"x": b["x"], "y": b["y"] + 1000}, num_workers=2)
    ids = [int(v) for b in pipe.blocks() for v in b["y"]]
    assert ids == [i + 1000 for i in range(48)]


# -- autoscaler --------------------------------------------------------------


def test_autoscaler_hysteresis_and_clamps():
    stall = {"v": 0.5}
    ups, downs = [], []
    a = ascale.StallAutoscaler(
        lambda: stall["v"], ups.append, downs.append,
        min_workers=1, max_workers=3, high=0.25, low=0.05, cooldown=10.0)
    t = 100.0
    assert a.step(t) == "up" and ups == [1]
    assert a.step(t + 1) is None          # cooldown
    t += 20
    assert a.step(t) == "up" and ups == [1, 2]
    t += 20
    assert a.step(t) is None              # max_workers clamp
    assert a.workers == 3
    stall["v"] = 0.01
    t += 20
    assert a.step(t) == "down" and downs == [2]   # LIFO retirement
    t += 20
    assert a.step(t) == "down" and downs == [2, 1]
    t += 20
    assert a.step(t) is None              # min_workers clamp
    assert a.workers == 1
    stall["v"] = 0.15                     # inside the deadband
    t += 20
    assert a.step(t) is None
    a2 = ascale.StallAutoscaler(lambda: None, ups.append, downs.append,
                                min_workers=1, max_workers=2)
    assert a2.step(1000.0) is None        # no signal -> no action


def test_obs_stall_reader_computes_windowed_ratio():
    snaps = {"t0": {"role": "worker", "metrics": {
        "tfos_feed_wait_seconds_total": {"series": [{"value": 0.0}]}}}}
    read = ascale.obs_stall_reader(lambda: snaps)
    assert read() is None                 # first call only baselines
    snaps["t0"]["metrics"]["tfos_feed_wait_seconds_total"][
        "series"][0]["value"] = 0.05
    time.sleep(0.1)
    ratio = read()
    assert ratio is not None and 0.0 < ratio <= 1.0
    # data-worker and driver snapshots never count as trainer stall
    snaps["d0"] = {"role": "data", "metrics": {
        "tfos_feed_wait_seconds_total": {"series": [{"value": 999.0}]}}}
    time.sleep(0.05)
    assert read() < 10.0


# -- full-cluster SIGKILL e2e (slow lane) ------------------------------------


E2E_N = 200
E2E_BLOCK = 10


def dynamic_consume_main(args, ctx):
    """Trainer that records every delivered id (exactness oracle)."""
    feed = ctx.get_data_feed(train_mode=True,
                             input_mapping={"x": "x", "y": "y"})
    ids = []
    while not feed.should_stop():
        b = feed.next_batch_columns(16)
        ids.extend(int(v) for v in b["y"])
    out = os.path.join(args["out_dir"], f"ids-{ctx.task_index}.txt")
    with open(out, "w") as f:
        f.write("\n".join(str(i) for i in ids))


@pytest.mark.slow
@pytest.mark.faults
def test_dynamic_service_survives_worker_kill(tmp_path, monkeypatch):
    """The dynamic-dispatch e2e acceptance (ISSUE 19): the data worker is
    SIGKILLed mid-split (data.split_serve:kill@3 — after pushing part of
    a split, before recording it), the engine respawns it, the provider
    requeues the orphaned split pinned to its original trainer, and the
    union of delivered ids is STILL exactly one copy per record — zero
    loss, zero duplicates."""
    from tensorflowonspark_tpu import cluster as TFCluster
    from tensorflowonspark_tpu.cluster import InputMode
    from tensorflowonspark_tpu.engine import LocalEngine

    monkeypatch.chdir(tmp_path)
    out_dir = tmp_path / "ids"
    out_dir.mkdir()
    engine = LocalEngine(3, env={
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "TFOS_DATA_SPLIT_BLOCKS": "4",
        faults.PLAN_ENV: "data.split_serve:kill@3",
        faults.EXECUTOR_ENV: "2",  # only the data-worker slot
    })
    try:
        cluster = TFCluster.run(
            engine, dynamic_consume_main, {"out_dir": str(out_dir)},
            num_executors=2, input_mode=InputMode.SPARK, restarts=1,
            data_workers=1)
        pipe = data.from_arrays(_arrays(E2E_N), block_size=E2E_BLOCK)
        cluster.train(pipe, num_epochs=1, feed_timeout=240)
        cluster.shutdown(grace_secs=2)
    finally:
        engine.stop()

    ids = []
    for i in range(2):
        with open(out_dir / f"ids-{i}.txt") as f:
            ids.extend(int(v) for v in f.read().split())
    counts = collections.Counter(ids)
    assert counts == {i: 1 for i in range(E2E_N)}, (
        f"exactness violated: missing="
    f"{[k for k in range(E2E_N) if counts.get(k, 0) < 1][:10]} "
        f"dup={[k for k, v in counts.items() if v > 1][:10]}")


# -- /statusz data section -------------------------------------------------


def test_statusz_data_summary_rolls_up_across_processes():
    """The /statusz "data" section sums split/cache counters across the
    provider, workers and trainers, sums per-process cache gauges, and
    takes the largest reporter for the singleton gauges (queue depth,
    worker count).  Static-shard runs — records but no split/cache
    activity — get no section at all."""
    from tensorflowonspark_tpu.obs import http as obs_http

    def snap(**kv):
        return {name: {"series": [{"value": float(v)}]}
                for name, v in kv.items()}

    provider = snap(tfos_data_splits_posted_total=10,
                    tfos_data_splits_requeued_total=1,
                    tfos_data_split_queue_depth=3)
    w1 = snap(tfos_data_splits_claimed_total=4,
              tfos_data_splits_served_total=4,
              tfos_data_records_total=400,
              tfos_data_cache_bytes=100,
              tfos_data_cache_blocks=2)
    w2 = snap(tfos_data_splits_claimed_total=5,
              tfos_data_splits_served_total=5,
              tfos_data_records_total=500,
              tfos_data_cache_bytes=50,
              tfos_data_cache_blocks=1)
    scaler = snap(tfos_data_workers=2)
    got = obs_http.data_summary([provider, w1, w2, scaler, None])
    assert got == {
        "splits_posted": 10.0, "splits_claimed": 9.0,
        "splits_served": 9.0, "splits_requeued": 1.0,
        "records": 900.0, "cache_bytes": 150.0, "cache_blocks": 3.0,
        "split_queue_depth": 3.0, "workers": 2.0,
    }
    # records alone (static service) doesn't rate a section
    assert obs_http.data_summary(
        [snap(tfos_data_records_total=5)]) is None
    assert obs_http.data_summary([None, {}]) is None
