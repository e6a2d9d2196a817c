"""DataFeed.terminate drain protocol on the shm ring.

The drain must be ended by the producer flock (no feeder mid-partition),
never by a timeout guess: a producer that pauses longer than any consumer
poll interval must not strand its queued data (reference guessed with an
empty+timeout heuristic, TFNode.py:307-329)."""

import os
import threading
import time

import pytest

from tensorflowonspark_tpu.recordio import shm

pytestmark = pytest.mark.skipif(not shm.available(), reason="no native lib")


class FakeMgr:
    """KV + queue stub speaking the manager protocol DataFeed/node use."""

    def __init__(self, kv=None):
        self.kv = dict(kv or {})

    def get(self, key):
        return self.kv.get(key)

    def set(self, key, value):
        self.kv[key] = value

    def get_queue(self, name):
        if name == "error":  # _await_consumption polls this
            class _Empty:
                @staticmethod
                def empty():
                    return True

            return _Empty()
        raise AssertionError("ring path must not touch manager data queues")


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

    stops = []

    class FakeClient:
        def __init__(self, addr):
            pass

        def request_stop(self):
            stops.append(True)

    monkeypatch.setattr(node, "FEED_CHUNK_RECORDS", 4)
    monkeypatch.setattr(node, "_get_manager", lambda *a, **kw: mgr)
    monkeypatch.setattr(node, "read_executor_id", lambda *a, **kw: 0)
    monkeypatch.setattr(node, "get_ip_address", lambda: "127.0.0.1")
    monkeypatch.setattr(node.rendezvous, "Client", FakeClient)

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
    assert stops, "feeder skipped the STOP handshake"
    ring.close()
