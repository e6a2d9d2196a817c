"""Stand-ins for driving the feeder closure (``node.train``) against a
real shm ring with no cluster around it."""

import threading


class FakeMgr:
    """KV + queue stub speaking the manager protocol DataFeed/node use."""

    def __init__(self, kv=None):
        self.kv = dict(kv or {})

    def get(self, key):
        return self.kv.get(key)

    def set(self, key, value):
        self.kv[key] = value

    def get_queue(self, name):
        if name == "error":  # the feeder's waits poll this
            class _Empty:
                @staticmethod
                def empty():
                    return True

            return _Empty()
        raise AssertionError("ring path must not touch manager data queues")


def patch_feeder(monkeypatch, mgr, chunk_records=None, partition=None):
    """Point ``node.train`` at ``mgr`` and at a rendezvous client that
    only records: returns ``{"done": [(qname, part)...], "stops": n}``."""
    from tensorflowonspark_tpu import node

    calls = {"done": [], "stops": 0}

    class FakeClient:
        def __init__(self, addr):
            pass

        def partition_done(self, qname, part):
            calls["done"].append((qname, part))

        def request_stop(self):
            calls["stops"] += 1

        def close(self):
            pass

    monkeypatch.setattr(node, "_get_manager", lambda *a, **kw: mgr)
    monkeypatch.setattr(node, "read_executor_id", lambda *a, **kw: 0)
    monkeypatch.setattr(node, "get_ip_address", lambda: "127.0.0.1")
    monkeypatch.setattr(node.rendezvous, "Client", FakeClient)
    if chunk_records is not None:
        monkeypatch.setenv("TFOS_FEED_CHUNK", str(chunk_records))
    if partition is not None:
        monkeypatch.setenv("TFOS_PARTITION_INDEX", str(partition))
    return calls


def start_feeder(records, feed_timeout=30):
    """Run the feeder over ``records`` on a thread: ``(thread, box)``;
    ``box["error"]`` holds what it raised, ``box["done"]`` is set at its
    end either way."""
    from tensorflowonspark_tpu import node

    feeder = node.train({}, {"server_addr": ("127.0.0.1", 0)},
                        feed_timeout=feed_timeout)
    box = {"done": threading.Event(), "error": None}

    def run():
        try:
            feeder(iter(records))
        except BaseException as e:  # noqa: BLE001 - handed to the test
            box["error"] = e
        finally:
            box["done"].set()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, box
