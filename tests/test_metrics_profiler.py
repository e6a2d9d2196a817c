"""utils.metrics counters + profiler trace capture + DataFeed wiring."""

import os
import time
import types

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.utils import metrics as M
from tensorflowonspark_tpu.utils import profiler


def test_train_metrics_rates_and_mfu():
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    m = M.TrainMetrics(flops_per_item=1e6, device=v5e)
    m.step()  # arm
    for _ in range(3):
        time.sleep(0.01)
        m.infeed_wait(0.002)
        m.step(items=10)
    rep = m.report()
    assert rep["steps"] == 4 and rep["items"] == 30
    assert rep["step_time_avg_s"] > 0
    assert 0 < rep["infeed_stall_frac"] < 1
    # mfu = items*flops / time / peak — sane positive number
    assert rep["mfu"] > 0


def test_transformer_flops_estimator():
    from tensorflowonspark_tpu.models import transformer

    cfg = transformer.Config(vocab_size=100, dim=64, n_layers=2, n_heads=4,
                             max_seq=128)
    per_tok = M.transformer_flops_per_token(cfg)
    assert per_tok > 6 * 100 * 64 * 2  # at least the embedding term


def test_profiler_trace_writes_events(tmp_path):
    log_dir = str(tmp_path / "trace")
    with profiler.trace(log_dir):
        jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
    found = []
    for root, _dirs, files in os.walk(log_dir):
        found.extend(os.path.join(root, f) for f in files)
    assert found, "profiler trace produced no files"


def test_datafeed_accounts_infeed_wait():
    from tensorflowonspark_tpu.feed import DataFeed

    class FakeQueue:
        def __init__(self, items):
            self.items = list(items)

        def get(self, block=True, timeout=None):
            time.sleep(0.005)
            return self.items.pop(0)

        def task_done(self):
            pass

    class FakeMgr:
        def __init__(self, items):
            self.q = FakeQueue(items)

        def get(self, key):
            return None  # no shm ring

        def get_queue(self, name):
            return self.q

    m = M.TrainMetrics()
    feed = DataFeed(FakeMgr([[1, 2, 3], None]), metrics=m)
    batch = feed.next_batch(3)
    assert batch == [1, 2, 3]
    assert m.report()["infeed_wait_s"] > 0
