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


def test_get_chunk_splits_its_one_timing_where_the_wait_ends():
    """``ring_wait + ring_read == infeed_wait``: one clock, read once per
    boundary, feeds ``TrainMetrics`` and both spans; the wait ends where
    the transport has a chunk, the read is the copy-out and decode."""
    import threading

    import numpy as np

    from tensorflowonspark_tpu import marker
    from tensorflowonspark_tpu.feed import DataFeed
    from tensorflowonspark_tpu.recordio import shm
    from tensorflowonspark_tpu.utils import telemetry

    if not shm.available():
        import pytest

        pytest.skip("native shm ring unavailable")
    name = f"/tfosq-split-{os.getpid()}"
    ring = shm.ShmQueue(name, capacity=1 << 22, create=True)

    class Mgr:
        def get(self, key):
            return name if key == "shm_input" else None

    chunk = marker.ColumnChunk(
        (("u1", 4096),), (np.ones((256, 4096), np.uint8),))

    def producer():
        q = shm.ShmQueue(name, create=False, producer=True)
        time.sleep(0.15)  # the ring is EMPTY for this long
        q.put(chunk)
        q.close()

    seen = []
    real_span = telemetry.span

    def spy(span_name, **attrs):
        seen.append(span_name)
        return real_span(span_name, **attrs)

    m = M.TrainMetrics(health=False)
    feed = DataFeed(Mgr(), metrics=m, input_mapping={"c0": "x"})
    t = threading.Thread(target=producer)
    t.start()
    telemetry.span = spy
    try:
        got = feed._get_chunk()
    finally:
        telemetry.span = real_span
        t.join()
        ring.close()
    assert len(got) == 256
    assert seen == [telemetry.FEED_RING_WAIT, telemetry.FEED_RING_READ]
    assert m.ring_wait_time >= 0.14               # the empty ring
    read = m.infeed_time - m.ring_wait_time       # copy-out + decode
    assert 0 < read < 0.1
    assert m.report()["ring_wait_s"] == m.ring_wait_time
    assert m.report()["infeed_wait_s"] == m.infeed_time


def test_start_trace_is_the_one_capture_call(monkeypatch, tmp_path):
    """Python tracer off, host tracer on, and the capture opens with the
    wall clock in a ``tfos/clock`` annotation."""
    from tensorflowonspark_tpu.utils import telemetry

    calls, spans = [], []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: calls.append((d, profiler_options)))
    real_span = telemetry.span
    monkeypatch.setattr(
        telemetry, "span",
        lambda n, **a: spans.append((n, a)) or real_span(n, **a))
    t0 = time.time_ns()
    assert profiler.start_trace(str(tmp_path)) is True
    (where, opts), = calls
    assert where == str(tmp_path)
    assert opts.python_tracer_level == 0 and opts.host_tracer_level == 2
    (name, args), = spans
    assert name == telemetry.CLOCK and t0 <= args["time_ns"] <= time.time_ns()
    # nothing else in the package starts a capture or names the
    # profiler's annotation class
    import subprocess

    pkg = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tensorflowonspark_tpu")
    for pattern, only in ((r"jax\.profiler\.start_trace", "profiler.py"),
                          (r"TraceAnnotation", "telemetry.py")):
        out = subprocess.run(["grep", "-rlE", pattern, pkg, "--include=*.py"],
                             capture_output=True, text=True).stdout.split()
        assert [os.path.basename(f) for f in out] == [only], (pattern, out)


def test_scopes_and_kernel_names_reach_the_lowered_program():
    """The stable names a device trace is reduced by: ``jax.named_scope``
    paths in the models, ``name=`` on the Pallas calls, and the decode
    engine's programs and K/V insert named after what they are."""
    from tensorflowonspark_tpu import ops
    from tensorflowonspark_tpu.models import resnet, transformer
    from tensorflowonspark_tpu.serving.decode import kvcache

    def lowered(fn, *shapes):
        return jax.jit(fn).lower(*shapes).as_text(debug_info=True)

    cfg = transformer.Config(vocab_size=64, dim=32, n_layers=1, n_heads=2,
                             max_seq=16, dtype="float32",
                             attn_impl="reference")
    params = jax.eval_shape(lambda k: transformer.init(k, cfg),
                            jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    text = lowered(lambda p, t: transformer.loss_fn(p, t, cfg), params, tok)
    for scope in ("embed", "block/attn", "block/mlp", "lm_head", "loss"):
        assert scope in text, scope
    text = lowered(lambda p, t: transformer.prefill(p, t, cfg), params, tok)
    for scope in ("attn", "mlp", "write_kv"):
        assert f"{scope}/" in text, scope
    pool = jax.ShapeDtypeStruct((5, 1, 2, 4, 16), jnp.float32)
    text = lowered(
        lambda p, t, pk, pv: transformer.decode_step_paged(
            p, t, cfg, pk, pv, jnp.zeros((2, 4), jnp.int32),
            jnp.zeros((2,), jnp.int32)),
        params, jax.ShapeDtypeStruct((2, 1), jnp.int32), pool, pool)
    for scope in ("gather_kv", "write_kv", "attn", "mlp"):
        assert f"{scope}/" in text, scope

    rparams, rstate = jax.eval_shape(
        lambda k: resnet.init(k, depth=20, num_classes=10,
                              small_inputs=True), jax.random.PRNGKey(0))
    img = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    text = lowered(lambda p, s, x: resnet.apply(
        p, s, x, depth=20, small_inputs=True)[0], rparams, rstate, img)
    for scope in ("stem", "stage1", "stage3", "head"):
        assert f"{scope}/" in text, scope

    q = jax.ShapeDtypeStruct((1, 128, 2, 64), jnp.float32)
    text = lowered(lambda q: jax.grad(lambda q: ops.flash_attention(
        q, q, q, causal=True, bwd_impl="pallas").sum())(q), q)
    for kernel in ("tfos_flash_fwd", "tfos_flash_bwd_dq",
                   "tfos_flash_bwd_dkv"):
        assert kernel in text, kernel
    x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    assert "tfos_rmsnorm" in lowered(
        lambda x: ops.fused_rmsnorm(x, jnp.ones((128,))), x)
    # one pool whose rows are [heads, T, head_dim] (token axis 1), blocks
    # of 4: a prefill's [B, layers, heads, T, head_dim], row 0 of it
    insert = kvcache._kv_insert((1,), 4)
    text = insert.lower((pool,), jnp.zeros((2,), jnp.int32),
                        (jax.ShapeDtypeStruct((3, 1, 2, 8, 16),
                                              jnp.float32),),
                        jnp.int32(0)).as_text(debug_info=True)
    assert "tfos_kv_insert" in text and "kv_insert" in text
