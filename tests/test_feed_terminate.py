"""DataFeed.terminate drain protocol on the shm ring.

The drain must be ended by the producer flock (no feeder mid-partition),
never by a timeout guess: a producer that pauses longer than any consumer
poll interval must not strand its queued data (reference guessed with an
empty+timeout heuristic, TFNode.py:307-329)."""

import os
import threading
import time

import pytest
from feed_helpers import FakeMgr, patch_feeder, start_feeder

from tensorflowonspark_tpu.recordio import shm

pytestmark = pytest.mark.skipif(not shm.available(), reason="no native lib")


def test_producer_active_tracks_flock():
    name = f"/tfosq-term-{os.getpid()}-a"
    ring = shm.ShmQueue(name, capacity=1 << 14, create=True)
    try:
        assert not shm.producer_active(name)
        prod = shm.ShmQueue(name, create=False, producer=True)
        assert shm.producer_active(name)
        prod.close()
        assert not shm.producer_active(name)
    finally:
        ring.close()


def test_terminate_waits_for_slow_producer():
    """A producer stalled >5s mid-partition (longer than the old drain
    heuristic) still gets fully drained before terminate() returns."""
    from tensorflowonspark_tpu.feed import DataFeed

    name = f"/tfosq-term-{os.getpid()}-b"
    ring = shm.ShmQueue(name, capacity=1 << 14, create=True)
    mgr = FakeMgr({"shm_input": name})
    drained = []

    def producer():
        q = shm.ShmQueue(name, create=False, producer=True)
        q.put(["r1"])
        time.sleep(6.0)  # longer than any drain-poll interval
        q.put(["r2"])
        q.close()

    t = threading.Thread(target=producer)
    t.start()
    time.sleep(0.2)  # let the producer take the flock
    try:
        feed = DataFeed(mgr)
        orig_get = feed._ring.get

        def spy_get(timeout_ms=-1, available=None):
            v = orig_get(timeout_ms, available)
            drained.append(v)
            return v

        feed._ring.get = spy_get
        feed.terminate()
        assert mgr.kv["state"] == "terminating"
        assert ["r1"] in drained and ["r2"] in drained
        assert feed._ring.qsize_bytes() == 0
    finally:
        t.join(10)
        ring.close()


def test_terminate_while_prefetch_thread_blocked():
    """terminate() from the main thread while a prefetch thread is blocked
    inside next_batch must not race the single-consumer ring: the blocked
    get turns into end-of-feed and the drain proceeds under the shared
    lock (the infeed.synchronized early-stop path)."""
    from tensorflowonspark_tpu.feed import DataFeed
    from tensorflowonspark_tpu.infeed import batch_iterator

    name = f"/tfosq-term-{os.getpid()}-d"
    ring = shm.ShmQueue(name, capacity=1 << 16, create=True)
    mgr = FakeMgr({"shm_input": name})
    prod = shm.ShmQueue(name, create=False, producer=True)
    try:
        for i in range(3):
            prod.put([(float(i),)] * 8)

        feed = DataFeed(mgr)
        got = []
        done = threading.Event()

        def consume():
            # 8-record batches: consumes the 3 chunks then BLOCKS on the
            # empty ring (no end-of-feed None was sent)
            for b in batch_iterator(feed, 8):
                got.append(b)
            done.set()

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        deadline = time.time() + 10
        while len(got) < 3 and time.time() < deadline:
            time.sleep(0.05)
        assert len(got) == 3, got
        # consumer is now blocked inside _get_chunk; terminate
        # concurrently while the producer is still mid-partition
        term_done = threading.Event()

        def do_term():
            feed.terminate()
            term_done.set()

        tt = threading.Thread(target=do_term, daemon=True)
        tt.start()
        time.sleep(0.4)  # flag set; consumer has left its pending get
        prod.put([(99.0,)] * 8)  # data the drain must absorb, not consume
        prod.close()  # release the flock so the drain can finish
        assert term_done.wait(10), "terminate did not finish draining"
        assert done.wait(5), "prefetch thread did not exit after terminate"
        assert len(got) == 3  # post-terminate data was drained, not consumed
        assert feed.should_stop()
        assert ring.qsize_bytes() == 0
    finally:
        prod.close()
        ring.close()


def test_feeder_put_bails_on_termination(monkeypatch):
    """A feeder blocked on a full ring notices state='terminating' and
    returns instead of deadlocking against a consumer that stopped
    draining (node.train put loop)."""
    from tensorflowonspark_tpu import node

    name = f"/tfosq-term-{os.getpid()}-c"
    ring = shm.ShmQueue(name, capacity=1 << 12, create=True)
    mgr = FakeMgr({"shm_input": name, "state": "running"})
    calls = patch_feeder(monkeypatch, mgr, chunk_records=4)

    feeder = node.train({}, {"server_addr": ("127.0.0.1", 0)}, feed_timeout=30)
    records = [b"x" * 256] * 200  # far more than the 4KiB ring holds

    done = threading.Event()

    def run():
        feeder(iter(records))
        done.set()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    time.sleep(1.0)  # feeder is now blocked on the full ring
    assert not done.is_set()
    mgr.kv["state"] = "terminating"
    assert done.wait(15), "feeder did not bail after termination"
    assert calls["stops"], "feeder skipped the STOP handshake"
    ring.close()


# -- the hand-over: the feeder waits for its own last byte -------------------

def _rows(n):
    return [([float(i), float(2 * i)], i) for i in range(n)]


def test_handover_returns_once_tail_passes_its_last_byte():
    """Not "ring empty": a later producer's bytes behind the feeder's
    last one do not hold its hand-over up."""
    from tensorflowonspark_tpu import node

    name = f"/tfosq-term-{os.getpid()}-e"
    ring = shm.ShmQueue(name, capacity=1 << 14, create=True)
    mgr = FakeMgr({"shm_input": name, "state": "running"})
    prod = shm.ShmQueue(name, create=False, producer=True)
    later = shm.ShmQueue(name, create=False)  # no flock: writes at once
    try:
        pos = prod.put(["mine"])
        later.put(["later", "producer"])
        done = threading.Event()

        def wait():
            node._await_ring_consumption(mgr, prod, pos, 30)
            done.set()

        threading.Thread(target=wait, daemon=True).start()
        assert not done.wait(0.3), "returned before its bytes were taken"
        assert ring.get(timeout_ms=1000) == ["mine"]
        assert done.wait(2), "still waiting although tail passed its byte"
        assert ring.qsize_bytes() > 0  # the later message is untouched
        assert ring.get(timeout_ms=1000) == ["later", "producer"]
    finally:
        prod.close()
        later.close()
        ring.close()


def test_partition_done_only_after_the_last_byte_was_taken(monkeypatch):
    """The feed ledger's exactly-once contract: the partition is
    reported consumed after the consumer took its last frame, never
    while frames are still in the ring."""
    name = f"/tfosq-term-{os.getpid()}-f"
    ring = shm.ShmQueue(name, capacity=1 << 16, create=True)
    mgr = FakeMgr({"shm_input": name, "state": "running"})
    calls = patch_feeder(monkeypatch, mgr, chunk_records=8, partition=7)
    try:
        t, box = start_feeder(_rows(20))
        deadline = time.time() + 10
        while ring.qsize_bytes() == 0 and time.time() < deadline:
            time.sleep(0.01)
        time.sleep(0.5)  # all three frames queued; nobody consumes
        assert not box["done"].is_set() and calls["done"] == []
        got = []
        for _ in range(2):
            got.append(ring.get(timeout_ms=1000))
        time.sleep(0.3)  # one frame left: still not done
        assert not box["done"].is_set() and calls["done"] == []
        got.append(ring.get(timeout_ms=1000))
        assert box["done"].wait(5) and box["error"] is None
        assert calls["done"] == [("input", 7)]
        assert [len(c) for c in got] == [8, 8, 4]
        assert sum((c.columns[1].tolist() for c in got), []) == list(range(20))
    finally:
        ring.close()


def test_terminate_during_handover_drains_and_feeder_returns(monkeypatch):
    """terminate() while the feeder waits in its hand-over: the drain
    takes what is queued, the feeder's wait ends, and it asks the driver
    to stop."""
    from tensorflowonspark_tpu.feed import DataFeed

    name = f"/tfosq-term-{os.getpid()}-g"
    ring = shm.ShmQueue(name, capacity=1 << 16, create=True)
    mgr = FakeMgr({"shm_input": name, "state": "running"})
    calls = patch_feeder(monkeypatch, mgr, chunk_records=8, partition=2)
    try:
        feed = DataFeed(mgr, input_mapping={"x": "x", "y": "y"})
        t, box = start_feeder(_rows(40))
        first = feed.next_batch_columns(8)  # mid-partition
        assert first["y"].tolist() == list(range(8))
        time.sleep(0.3)
        assert not box["done"].is_set()  # waiting for the other frames
        feed.terminate()
        assert box["done"].wait(10), "feeder still waiting after the drain"
        assert box["error"] is None
        assert calls["stops"] == 1
        assert ring.qsize_bytes() == 0
        assert not shm.producer_active(name)
    finally:
        ring.close()


def test_dead_consumer_fails_the_handover(monkeypatch):
    """A consumer whose heartbeat went stale fails the waiting feeder
    within the heartbeat's limit (+ the one-second check), not after
    feed_timeout."""
    from tensorflowonspark_tpu import manager as tfmanager

    name = f"/tfosq-term-{os.getpid()}-h"
    ring = shm.ShmQueue(name, capacity=1 << 16, create=True)
    mgr = FakeMgr({"shm_input": name, "state": "running"})
    calls = patch_feeder(monkeypatch, mgr, chunk_records=8, partition=1)
    monkeypatch.setenv("TFOS_HEARTBEAT_STALE", "1")
    monkeypatch.delenv("TFOS_ACTOR_HEARTBEAT_STALE", raising=False)
    mgr.kv[tfmanager.HEARTBEAT_KEY] = time.time()  # alive, then silent
    try:
        t0 = time.time()
        t, box = start_feeder(_rows(12), feed_timeout=600)
        assert box["done"].wait(10), "the feeder outlived a dead consumer"
        assert time.time() - t0 < 6
        assert isinstance(box["error"], RuntimeError)
        assert "consumer appears dead" in str(box["error"])
        assert calls["done"] == []  # nothing was reported consumed
    finally:
        ring.close()
