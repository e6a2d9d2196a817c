"""utils.telemetry + scripts/trace_merge: span recording, spawn/fork
safety, driver-side drain, and the Chrome-trace merge.

Parity framing: the reference's observability is log lines only
(reference ``__init__.py:1-5``, SURVEY.md §5); these tests pin the
structured replacement — one schema everywhere, no files when disabled,
every node's records collected into one run directory at shutdown.
"""

import importlib.util
import json
import multiprocessing as mp
import os
import subprocess
import sys
import time

import pytest

from tensorflowonspark_tpu import cluster as TFCluster
from tensorflowonspark_tpu.cluster import InputMode
from tensorflowonspark_tpu.engine import LocalEngine, TaskError
from tensorflowonspark_tpu.utils import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_MERGE = os.path.join(REPO, "scripts", "trace_merge.py")

_ENV_KEYS = (telemetry.DIR_ENV, telemetry.SPOOL_ENV, telemetry.NODE_ENV,
             telemetry.ROLE_ENV, telemetry.BUFFER_ENV, telemetry.FLUSH_ENV,
             telemetry.TRACE_ENV, telemetry.RING_ENV)


def _load_trace_merge():
    spec = importlib.util.spec_from_file_location("trace_merge", TRACE_MERGE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _telemetry_env():
    """Isolate every test from ambient telemetry env AND restore it:
    cluster.run/configure write identity into os.environ by design."""
    saved = {k: os.environ.get(k) for k in _ENV_KEYS}
    for k in _ENV_KEYS:
        os.environ.pop(k, None)
    yield
    telemetry.flush()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _records(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def _all_records(root):
    out = []
    for dirpath, _dirs, files in os.walk(root):
        for name in sorted(files):
            if name.endswith(".jsonl"):
                out.extend(_records(os.path.join(dirpath, name)))
    return out


# --- recorder core ----------------------------------------------------------

@pytest.fixture
def no_profiler_sink(monkeypatch):
    """The process of these tests has jax imported, so the profiler sink
    is on by default; a test of "both sinks off" turns it off."""
    monkeypatch.setattr(telemetry, "_annotation", lambda: None)


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what one
    span call hands the profiler; ``recording`` is whether a capture
    records (``is_enabled``)."""

    seen = []
    recording = True

    @classmethod
    def is_enabled(cls):
        return cls.recording

    def __init__(self, name, **kwargs):
        self.name, self.kwargs, self.state = name, dict(kwargs), "new"
        _FakeAnnotation.seen.append(self)

    def __enter__(self):
        self.state = "open"
        return self

    def __exit__(self, *exc):
        self.state = "closed"
        return False

    def set_metadata(self, **kwargs):
        self.kwargs.update(kwargs)


@pytest.fixture
def fake_profiler(monkeypatch):
    import types

    _FakeAnnotation.seen = []
    monkeypatch.setattr(_FakeAnnotation, "recording", True)
    fake_jax = types.SimpleNamespace(profiler=types.SimpleNamespace(
        TraceAnnotation=_FakeAnnotation))
    monkeypatch.setitem(sys.modules, "jax", fake_jax)
    monkeypatch.setattr(telemetry, "_ANNOTATION", None)
    return _FakeAnnotation.seen


def test_no_annotation_is_made_outside_a_capture(fake_profiler, tmp_path):
    """jax imported, no capture recording: the profiler sink is off (the
    runtime would record nothing), so the span costs no annotation; the
    spool still gets every record."""
    _FakeAnnotation.recording = False
    assert not telemetry.capturing() and not telemetry.active()
    assert telemetry.span("tfos/decode/iterate", active=2) is telemetry._NULL
    assert telemetry.trace_span("tfos/decode/admit") is telemetry._NULL
    telemetry.record_span("tfos/feeder/chunk", 0.1)
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    telemetry.configure(node_id="t-0", role="test")
    with telemetry.span("tfos/decode/emit", n=1):
        pass
    telemetry.record_span("tfos/feed/ring_wait", 0.2)
    telemetry.flush()
    assert fake_profiler == []
    assert [r["name"] for r in _records(telemetry.sink_path())] == [
        "tfos/decode/emit", "tfos/feed/ring_wait"]
    _FakeAnnotation.recording = True
    assert telemetry.capturing() and telemetry.active()
    with telemetry.span("tfos/decode/emit"):
        pass
    assert [a.name for a in fake_profiler] == ["tfos/decode/emit"]


def test_only_names_a_capture_reader_keeps_reach_the_capture(
        fake_profiler, tmp_path):
    """While a capture records, ``tfos/*`` and ``bench/*`` names become
    annotations; every other name (``decode/retire``, ``train/step``,
    ``rendezvous/*``) goes to the spool alone."""
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    telemetry.configure(node_id="t-0", role="test")
    with telemetry.span("train/step", step=1):
        pass
    with telemetry.trace_span("rendezvous/register"):
        pass
    telemetry.record_span("decode/retire", 0.5, sid="a")
    with telemetry.span("bench/dispatch_step"):
        pass
    telemetry.record_span("tfos/feeder/chunk", 0.25, records=7)
    with telemetry.trace_span("tfos/decode/admit"):
        pass
    telemetry.flush()
    assert [a.name for a in fake_profiler] == [
        "bench/dispatch_step", "tfos/feeder/chunk", "tfos/decode/admit"]
    assert [r["name"] for r in _records(telemetry.sink_path())] == [
        "train/step", "rendezvous/register", "decode/retire",
        "bench/dispatch_step", "tfos/feeder/chunk", "tfos/decode/admit"]
    # with the spool off, a name the capture does not keep costs nothing
    os.environ.pop(telemetry.DIR_ENV)
    assert telemetry.span("decode/retire") is telemetry._NULL


def test_span_reaches_the_profiler_with_its_scalar_args(fake_profiler):
    """One call, the profiler as a sink: spool off, jax in sys.modules."""
    assert not telemetry.enabled() and telemetry.active()
    with telemetry.span("tfos/feed/ring_read", records=3, note="x",
                        blob=[1, 2]) as sp:
        assert fake_profiler[-1].state == "open"
        sp.add(bytes=4096, more={"not": "scalar"})
    ann = fake_profiler[-1]
    assert ann.name == "tfos/feed/ring_read" and ann.state == "closed"
    # scalars travel as the event's stats; lists and dicts do not
    assert ann.kwargs == {"records": 3, "note": "x", "bytes": 4096}
    telemetry.record_span("tfos/feeder/chunk", 0.25, records=7)
    assert fake_profiler[-1].kwargs == {"dur_ms": 250.0, "records": 7}
    assert fake_profiler[-1].state == "closed"


def test_span_writes_both_sinks_from_one_call(fake_profiler, tmp_path):
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    telemetry.configure(node_id="t-0", role="test")
    with telemetry.span("tfos/feed/h2d", n=1):
        pass
    telemetry.flush()
    assert [a.name for a in fake_profiler] == ["tfos/feed/h2d"]
    recs = _records(telemetry.sink_path())
    assert [(r["name"], r["attrs"]) for r in recs] \
        == [("tfos/feed/h2d", {"n": 1})]


def test_telemetry_never_imports_jax():
    """In a process without jax the span call imports nothing: the
    profiler sink is found in sys.modules or not at all."""
    code = (
        "import sys\n"
        "from tensorflowonspark_tpu.utils import telemetry\n"
        "assert 'jax' not in sys.modules\n"
        "s = telemetry.span('tfos/x', a=1)\n"
        "assert s is telemetry._NULL and not telemetry.active()\n"
        "with s: pass\n"
        "telemetry.record_span('tfos/y', 0.1)\n"
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
        "print('ok')\n")
    env = {k: v for k, v in os.environ.items() if k not in _ENV_KEYS}
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr


def test_both_sinks_off_reads_no_clock(no_profiler_sink, monkeypatch):
    def boom(*_a):
        raise AssertionError("a clock was read with both sinks off")

    monkeypatch.setattr(telemetry.time, "time", boom)
    monkeypatch.setattr(telemetry.time, "perf_counter", boom)
    monkeypatch.setattr(telemetry.time, "time_ns", boom, raising=False)
    assert not telemetry.active()
    with telemetry.span("tfos/feed/next", n=1) as sp:
        sp.add(k=2)
    telemetry.record_span("tfos/feeder/chunk", 0.1)
    assert telemetry.span("x") is telemetry._NULL


def test_trace_merge_names_follow_telemetry():
    tm = _load_trace_merge()
    assert tm.FEED_FETCH_SPANS == (telemetry.FEED_RING_WAIT,
                                   telemetry.FEED_RING_READ)
    assert tm.FEED_TO_COLUMNS == telemetry.FEED_TO_COLUMNS
    assert tm.CLOCK_SPAN == telemetry.CLOCK
    assert tm.FEEDER_CHUNK == telemetry.FEEDER_CHUNK
    assert tm.FEEDER_HANDOFF == telemetry.FEEDER_HANDOFF
    names = [v for k, v in vars(telemetry).items()
             if k.isupper() and isinstance(v, str) and v.startswith("tfos/")]
    assert len(names) == len(set(names)) >= 20
    assert all(len(n.split("/")) == 3 for n in names if n != telemetry.CLOCK)


def test_disabled_is_noop(tmp_path, no_profiler_sink):
    assert not telemetry.enabled()
    assert telemetry.sink_path() is None
    assert telemetry.span("x") is telemetry._NULL
    with telemetry.span("x", a=1) as sp:
        sp.add(b=2)
    telemetry.event("y")
    telemetry.record_span("z", 0.1)
    telemetry.flush()
    assert list(tmp_path.iterdir()) == []  # nothing anywhere


def test_span_schema_nesting_and_monotonic_clocks(tmp_path):
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    telemetry.configure(node_id="t-0", role="test")
    assert telemetry.enabled()
    with telemetry.span("outer", phase="a"):
        time.sleep(0.02)
        with telemetry.span("inner") as sp:
            time.sleep(0.01)
            sp.add(marker=1)
    telemetry.event("tick", n=3)
    telemetry.flush()

    path = telemetry.sink_path()
    assert os.path.basename(path) == f"t-0-{os.getpid()}.jsonl"
    recs = _records(path)
    assert [set(r) for r in recs] == [set(telemetry.SCHEMA_KEYS)] * 3
    by_name = {r["name"]: r for r in recs}
    inner, outer, tick = by_name["inner"], by_name["outer"], by_name["tick"]
    assert outer["kind"] == "span" and tick["kind"] == "event"
    assert tick["dur_ms"] is None
    assert inner["attrs"] == {"marker": 1}
    # monotonic-clock durations, wall-clock anchors: the inner span
    # starts after and ends before the outer one
    assert outer["dur_ms"] >= inner["dur_ms"] >= 9.0
    assert outer["ts"] <= inner["ts"] <= tick["ts"]
    assert inner["ts"] + inner["dur_ms"] / 1e3 <= \
        outer["ts"] + outer["dur_ms"] / 1e3 + 0.01
    assert all(r["node_id"] == "t-0" and r["role"] == "test" for r in recs)


def test_record_span_backdates_start(tmp_path):
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    telemetry.configure(node_id="t-0", role="test")
    before = time.time()
    telemetry.record_span("train/step", 1.5, items=8)
    telemetry.flush()
    (rec,) = _records(telemetry.sink_path())
    assert rec["dur_ms"] == pytest.approx(1500.0)
    # self-timed spans anchor at START so the trace lays them out right
    assert rec["ts"] == pytest.approx(before - 1.5, abs=0.25)


def test_span_error_annotates_and_propagates(tmp_path):
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    telemetry.configure(node_id="t-0", role="test")
    with pytest.raises(ValueError, match="boom"):
        with telemetry.span("will/fail"):
            raise ValueError("boom")
    telemetry.flush()
    (rec,) = _records(telemetry.sink_path())
    assert "boom" in rec["attrs"]["error"]


def test_ring_buffer_counts_drops(tmp_path):
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    os.environ[telemetry.BUFFER_ENV] = "4"
    os.environ[telemetry.FLUSH_ENV] = "1000"  # no threshold flush
    telemetry.configure(node_id="t-0", role="test")
    for i in range(10):
        telemetry.event("e", i=i)
    telemetry.flush()
    recs = _records(telemetry.sink_path())
    dropped = [r for r in recs if r["name"] == "telemetry/dropped"]
    assert dropped and dropped[0]["attrs"]["count"] >= 1
    assert len([r for r in recs if r["name"] == "e"]) <= 4


def _spawn_child_emit():
    # relies on the exit-time Finalize/atexit flush: NO explicit flush
    from tensorflowonspark_tpu.utils import telemetry as t

    with t.span("spawn/child", pid=os.getpid()):
        pass


def test_spawn_child_roundtrip(tmp_path):
    """A spawned child inherits the env channel, writes its own
    <node>-<pid>.jsonl, and its exit hook flushes without help."""
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    telemetry.configure(node_id="parent", role="test")
    ctx = mp.get_context("spawn")
    p = ctx.Process(target=_spawn_child_emit)
    p.start()
    p.join(60)
    assert p.exitcode == 0
    child = [r for r in _all_records(tmp_path) if r["name"] == "spawn/child"]
    assert len(child) == 1
    assert child[0]["attrs"]["pid"] == p.pid
    assert child[0]["node_id"] == "parent"  # identity inherited via env
    files = sorted(f.name for f in tmp_path.iterdir())
    assert f"parent-{p.pid}.jsonl" in files


# --- causal tracing ---------------------------------------------------------

def test_trace_context_mint_child_header_roundtrip():
    ctx = telemetry.TraceContext()
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    assert ctx.parent_id is None
    kid = ctx.child()
    assert kid.trace_id == ctx.trace_id
    assert kid.parent_id == ctx.span_id and kid.span_id != ctx.span_id
    hdr = kid.to_header()
    assert hdr == f"00-{kid.trace_id}-{kid.span_id}-01"
    back = telemetry.TraceContext.from_header(hdr)
    assert back.trace_id == kid.trace_id and back.span_id == kid.span_id
    # malformed headers parse to None, never raise
    for bad in ("", "garbage", "00-zz-xx-01", None, "00-abc-def-01"):
        assert telemetry.TraceContext.from_header(bad) is None


def test_trace_span_links_parents_and_rides_attrs(tmp_path):
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    telemetry.configure(node_id="t-0", role="test")
    with telemetry.trace_span("serve/request") as root:
        rctx = root.ctx
        with telemetry.span("engine/task"):
            telemetry.event("tick")
    telemetry.flush()
    recs = {r["name"]: r for r in _records(telemetry.sink_path())}
    outer, inner, tick = (recs["serve/request"], recs["engine/task"],
                          recs["tick"])
    assert outer["attrs"]["trace_id"] == rctx.trace_id
    assert outer["attrs"]["parent_id"] is None
    assert inner["attrs"]["trace_id"] == rctx.trace_id
    assert inner["attrs"]["parent_id"] == outer["attrs"]["span_id"]
    # events carry the enclosing span as parent
    assert tick["attrs"]["parent_id"] == inner["attrs"]["span_id"]
    # outside any trace, current() is empty again
    assert telemetry.current() is None


def test_trace_span_exception_path_pops_context(tmp_path):
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    telemetry.configure(node_id="t-0", role="test")
    with pytest.raises(RuntimeError, match="kaboom"):
        with telemetry.trace_span("serve/request"):
            raise RuntimeError("kaboom")
    # the thread-local stack MUST unwind on the error path, or every
    # later span in this thread would silently join the failed trace
    assert telemetry.current() is None
    telemetry.flush()
    (rec,) = _records(telemetry.sink_path())
    assert "kaboom" in rec["attrs"]["error"]
    assert rec["attrs"]["trace_id"]


def _spawn_traced_child():
    from tensorflowonspark_tpu.utils import telemetry as t

    # the child sees the parent's exported context via TFOS_TRACE_PARENT
    with t.span("spawn/traced_child"):
        pass


def test_trace_inherited_across_spawn(tmp_path):
    """trace_root exports TFOS_TRACE_PARENT; a spawned child's spans
    join the same trace with a valid parent link."""
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    telemetry.configure(node_id="parent", role="test")
    ctx = telemetry.trace_root("cluster/run")
    assert os.environ[telemetry.TRACE_ENV] == ctx.to_header()
    p = mp.get_context("spawn").Process(target=_spawn_traced_child)
    p.start()
    p.join(60)
    assert p.exitcode == 0
    telemetry.flush()
    recs = _all_records(tmp_path)
    child = next(r for r in recs if r["name"] == "spawn/traced_child")
    anchor = next(r for r in recs if r["name"] == "cluster/run")
    assert child["attrs"]["trace_id"] == ctx.trace_id
    assert child["attrs"]["parent_id"] == ctx.span_id
    assert anchor["attrs"]["span_id"] == ctx.span_id


def test_trace_disabled_is_noop(tmp_path, no_profiler_sink):
    assert not telemetry.enabled()
    assert telemetry.trace_root("cluster/run") is None
    assert telemetry.trace_span("serve/request") is telemetry._NULL
    assert telemetry.current() is None
    with telemetry.activate("00-" + "a" * 32 + "-" + "b" * 16 + "-01"):
        assert telemetry.current() is None
    assert list(tmp_path.iterdir()) == []


# --- cluster drain ----------------------------------------------------------

def _telemetry_node_fn(args, ctx):
    from tensorflowonspark_tpu.utils import telemetry as t

    with t.span("user/work", task=ctx.task_index):
        time.sleep(0.01)


def _fail_after_feed_fn(args, ctx):
    feed = ctx.get_data_feed(train_mode=True)
    while not feed.should_stop():
        feed.next_batch(100)
    raise RuntimeError("deliberate failure after feeding")


def _run_dirs(root):
    return sorted(d for d in os.listdir(root) if d.startswith("run-"))


def test_drain_on_clean_shutdown(tmp_path):
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    engine = LocalEngine(2)
    try:
        cluster = TFCluster.run(
            engine, _telemetry_node_fn, [], num_executors=2,
            input_mode=InputMode.TENSORFLOW,
        )
        cluster.shutdown()
    finally:
        engine.stop()
    (run,) = _run_dirs(tmp_path)
    drained = _all_records(tmp_path / run)
    names = {r["name"] for r in drained}
    # node lifecycle + user spans all collected into the one run dir
    assert {"node/boot", "node/main", "user/work",
            "rendezvous/register"} <= names
    assert {r["node_id"] for r in drained if r["name"] == "user/work"} == \
        {"worker-0", "worker-1"}
    # the driver's own spans land in the root (cluster/start before the
    # run id exists; the drain span itself covers the collection)
    driver = [r for r in _all_records(tmp_path)
              if r["role"] == "driver"]
    dnames = {r["name"] for r in driver}
    assert {"cluster/start", "cluster/shutdown",
            "cluster/telemetry_drain"} <= dnames


def test_drain_on_error_shutdown(tmp_path):
    """A failing node program must still get its telemetry drained —
    the error path is exactly when the timeline matters most."""
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    engine = LocalEngine(2)
    try:
        cluster = TFCluster.run(
            engine, _fail_after_feed_fn, [], num_executors=2,
            input_mode=InputMode.SPARK,
        )
        ds = engine.parallelize(range(100), 2)
        cluster.train(ds)
        with pytest.raises((TaskError, SystemExit)) as ei:
            cluster.shutdown(grace_secs=3)
    finally:
        engine.stop()
    (run,) = _run_dirs(tmp_path)
    names = {r["name"] for r in _all_records(tmp_path / run)}
    assert "node/boot" in names
    driver = {r["name"] for r in _all_records(tmp_path)
              if r["role"] == "driver"}
    assert "cluster/shutdown" in driver
    if isinstance(ei.value, SystemExit):
        # the tf_status error path emits the cluster/error event before
        # cancelling jobs (a TaskError from the stop-job raises earlier)
        assert "cluster/error" in driver


def test_telemetry_disabled_cluster_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)  # catch stray spool dirs too
    engine = LocalEngine(2)
    try:
        cluster = TFCluster.run(
            engine, _telemetry_node_fn, [], num_executors=2,
            input_mode=InputMode.TENSORFLOW,
        )
        cluster.shutdown()
    finally:
        engine.stop()
    assert not list(tmp_path.glob("**/*.jsonl"))
    assert not (tmp_path / ".tfos_telemetry").exists()


def _flight_dump_node_fn(args, ctx):
    from tensorflowonspark_tpu.obs import flight
    from tensorflowonspark_tpu.utils import telemetry as t

    with t.span("user/work", task=ctx.task_index):
        time.sleep(0.01)
    assert flight.snapshot("test/manual", reason="spool survival probe")


def test_flight_dump_survives_engine_stop(tmp_path):
    """Regression (deploy-loop satellite): flight dumps used to spool
    into a dotdir inside the engine scratch cwd, which engine.stop()
    deletes — the black box died with the plane.  They must spool under
    $TFOS_TELEMETRY_DIR and outlive full engine teardown."""
    import glob as _glob

    telemetry_dir = tmp_path / "telemetry"
    os.environ[telemetry.DIR_ENV] = str(telemetry_dir)
    engine = LocalEngine(2)
    try:
        cluster = TFCluster.run(
            engine, _flight_dump_node_fn, [], num_executors=2,
            input_mode=InputMode.TENSORFLOW,
        )
        cluster.shutdown()
    finally:
        engine.stop()
    # engine scratch is gone; the dumps are not
    dumps = _glob.glob(os.path.join(str(telemetry_dir), "spool-*",
                                    "flight-*.json"))
    assert dumps, "flight dump did not survive engine stop"
    doc = json.loads(open(dumps[0], encoding="utf-8").read())
    assert doc["trigger"] == "test/manual"
    # and postmortem's recursive walk can see them (non-dot spool dirs)
    from tensorflowonspark_tpu.obs import postmortem

    found = postmortem.load_dumps(str(telemetry_dir))
    assert found, "postmortem walk missed the surviving dump"


# --- trace merge ------------------------------------------------------------

def _synthesize(tmp_path):
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    for node, role in (("worker-0", "worker"), ("worker-1", "worker")):
        telemetry.configure(node_id=node, role=role)
        for i in range(10):
            telemetry.record_span(
                "train/step", 0.010 + 0.001 * i, items=32,
                flops_per_item=2.0e9, peak_flops=197e12)
            telemetry.record_span(telemetry.FEED_RING_WAIT, 0.002,
                                  eof=False)
        telemetry.event("node/tb_spawn", port=6006)
        telemetry.flush()


def test_trace_merge_golden(tmp_path):
    _synthesize(tmp_path)
    tm = _load_trace_merge()
    pairs, skipped = tm.load_records(str(tmp_path))
    assert skipped == 0 and len(pairs) == 42
    assert [p[0]["ts"] for p in pairs] == \
        sorted(p[0]["ts"] for p in pairs)

    trace = tm.to_chrome_trace(pairs)
    evs = trace["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert phases == {"M", "X", "i"}
    procs = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs == {"worker-0 (worker)", "worker-1 (worker)"}
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 40
    assert all(e["ts"] >= 0 and e["dur"] > 0 for e in xs)

    text, stats = tm.summarize(pairs, skipped)
    assert stats["phases"]["train/step"]["count"] == 20
    for node in ("worker-0", "worker-1"):
        n = stats["nodes"][node]
        assert n["steps"] == 10
        assert n["p50_ms"] == pytest.approx(14.0, abs=0.1)
        assert n["p99_ms"] >= n["p90_ms"] >= n["p50_ms"]
        # 10 x 2ms waits in a ~165ms loop (145ms steps + 20ms waits)
        assert n["infeed_stall_frac"] == pytest.approx(20 / 165, abs=0.02)
        # mfu = items*flops / (time * peak)
        assert n["mfu"] == pytest.approx(
            (10 * 32 * 2.0e9) / (n["step_total_s"] * 197e12), rel=1e-6)
    assert "train/step" in text and "worker-1" in text


def test_trace_merge_feeder_rows(tmp_path):
    """The producer's side of the ring, from the feeder task's spans:
    frames in place and copied, means per frame, the hand-over."""
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    telemetry.configure(node_id="chief-0", role="chief")
    for part in range(2):
        for _ in range(8):
            telemetry.record_span(
                telemetry.FEEDER_CHUNK, 0.020, part=part, records=256,
                inplace=1, source_ms=1.0, room_wait_ms=4.0, write_ms=15.0)
        telemetry.record_span(telemetry.FEEDER_HANDOFF, 0.040, part=part)
    telemetry.record_span(
        telemetry.FEEDER_CHUNK, 0.100, part=2, records=100, inplace=0,
        source_ms=1.0, room_wait_ms=0.0, encode_ms=60.0, write_ms=39.0)
    telemetry.flush()
    tm = _load_trace_merge()
    pairs, skipped = tm.load_records(str(tmp_path))
    text, stats = tm.summarize(pairs, skipped)
    fd = stats["feeder"]["chief-0"]
    assert (fd["frames"], fd["inplace"], fd["copied"]) == (17, 16, 1)
    assert fd["records"] == 16 * 256 + 100
    assert fd["room_wait_ms"] == pytest.approx(64.0 / 17)
    assert fd["write_ms"] == pytest.approx((16 * 15.0 + 39.0) / 17)
    assert fd["encode_ms"] == pytest.approx(60.0 / 17)
    assert fd["handoff_ms"] == pytest.approx(40.0, rel=1e-3)
    assert fd["records_per_s"] == pytest.approx(
        fd["records"] / (16 * 0.020 + 2 * 0.040 + 0.100), rel=1e-3)
    assert "-- feeder (tfos/feeder/chunk, handoff) --" in text


def test_trace_merge_skips_malformed_lines(tmp_path):
    _synthesize(tmp_path)
    bad = tmp_path / "torn-123.jsonl"
    bad.write_text('{"ts": 1.0, "half a record...\nnot json\n')
    tm = _load_trace_merge()
    pairs, skipped = tm.load_records(str(tmp_path))
    assert len(pairs) == 42 and skipped == 2


def test_read_spool_skips_truncated_trailing_record(tmp_path):
    """A writer SIGKILLed mid-write leaves a torn trailing line; the
    drain must keep the valid prefix, drop the torn tail, and never
    raise (it runs on live executors)."""
    os.environ[telemetry.DIR_ENV] = str(tmp_path)
    telemetry.configure(node_id="t-0", role="test")
    telemetry.event("good", n=1)
    telemetry.event("good", n=2)
    telemetry.flush()
    path = telemetry.sink_path()
    with open(path, "r+", encoding="utf-8") as f:
        whole = f.read()
        head, last = whole.rstrip("\n").rsplit("\n", 1)
        f.seek(0)
        f.truncate()
        # valid record, then a record cut mid-JSON with no newline
        f.write(head + "\n" + last[: len(last) // 2])
    # a sibling file that is ALL garbage is dropped entirely
    (tmp_path / "junk-1.jsonl").write_text("\x00\x01 not json")

    out = telemetry.read_spool(str(tmp_path))
    by_name = dict(out)
    assert os.path.basename(path) in by_name
    assert "junk-1.jsonl" not in by_name
    recs = [json.loads(ln) for ln in
            by_name[os.path.basename(path)].splitlines()]
    assert [r["attrs"]["n"] for r in recs if r["name"] == "good"] == [1]
    # sanitized output ends with a newline (merge-safe concatenation)
    assert by_name[os.path.basename(path)].endswith("\n")


def test_read_spool_missing_dir_is_empty(tmp_path):
    assert telemetry.read_spool(str(tmp_path / "nope")) == []


def test_trace_merge_summary_json(tmp_path):
    """--summary-json writes the machine-readable stats next to the
    human summary, numbers identical to summarize()'s dict."""
    _synthesize(tmp_path)
    env = dict(os.environ, PYTHONPATH="")
    out_json = tmp_path / "stats.json"
    proc = subprocess.run(
        [sys.executable, TRACE_MERGE, str(tmp_path),
         "--summary-json", str(out_json)],
        capture_output=True, text=True, env=env, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    stats = json.loads(out_json.read_text())
    assert stats["records"] == 42 and stats["skipped"] == 0
    tm = _load_trace_merge()
    pairs, skipped = tm.load_records(str(tmp_path))
    _text, want = tm.summarize(pairs, skipped)
    for node in ("worker-0", "worker-1"):
        assert stats["nodes"][node]["steps"] == 10
        assert stats["nodes"][node]["p50_ms"] == \
            pytest.approx(want["nodes"][node]["p50_ms"])
        assert stats["nodes"][node]["mfu"] == \
            pytest.approx(want["nodes"][node]["mfu"])
    assert stats["phases"]["train/step"]["count"] == 20


def test_trace_merge_cli(tmp_path):
    _synthesize(tmp_path)
    env = dict(os.environ, PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, TRACE_MERGE, str(tmp_path),
         "--summary-out", str(tmp_path / "summary.txt")],
        capture_output=True, text=True, env=env, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "per-node train steps" in proc.stdout
    trace = json.loads((tmp_path / "trace.json").read_text())
    assert trace["traceEvents"]
    assert "worker-0" in (tmp_path / "summary.txt").read_text()

    empty = tmp_path / "empty"
    empty.mkdir()
    proc = subprocess.run(
        [sys.executable, TRACE_MERGE, str(empty)],
        capture_output=True, text=True, env=env, timeout=60)
    assert proc.returncode == 1
    assert "no telemetry records" in proc.stderr


def test_trace_merge_lands_a_capture_on_the_spools_clock(tmp_path):
    """``--xplane``: a capture's timestamps count from its start; the
    ``tfos/clock`` annotation that opens it carries the wall clock, so
    the SAME span, written to both sinks by one call, lands at the same
    place on the merged timeline from either source."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.utils import profiler

    tel, cap = tmp_path / "tel", tmp_path / "cap"
    os.environ[telemetry.DIR_ENV] = str(tel)
    telemetry.configure(node_id="trainer-0", role="worker")
    assert profiler.start_trace(str(cap))
    try:
        with telemetry.span(telemetry.FEED_RING_READ, records=4):
            jnp.ones(8).block_until_ready()
            time.sleep(0.02)
    finally:
        assert profiler.stop_trace()
    telemetry.flush()
    out = tmp_path / "trace.json"
    proc = subprocess.run(
        [sys.executable, TRACE_MERGE, str(tel), "--xplane", str(cap),
         "--out", str(out)], capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    events = [e for e in json.loads(out.read_text())["traceEvents"]
              if e.get("name") == telemetry.FEED_RING_READ]
    spool = [e for e in events if e["cat"] == "worker"]
    capture = [e for e in events if e["cat"] == "capture"]
    assert len(spool) == 1 and len(capture) == 1
    assert capture[0]["args"] == {"records": 4}
    assert abs(spool[0]["ts"] - capture[0]["ts"]) < 5e3      # microseconds
    assert abs(spool[0]["dur"] - capture[0]["dur"]) < 5e3
    # a capture that no tfos/clock opens cannot be placed: say so
    bare = tmp_path / "bare"
    import jax

    jax.profiler.start_trace(str(bare))
    jax.profiler.stop_trace()
    proc = subprocess.run(
        [sys.executable, TRACE_MERGE, str(tel), "--xplane", str(bare)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 1 and "tfos/clock" in proc.stderr
