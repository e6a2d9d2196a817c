"""Live metrics plane: registry semantics, node publish -> driver KV
sweep, the /metrics + /healthz + /statusz endpoint, tfos-top, and the
catalog/docs lint.

Parity framing: the reference's only runtime surface is driver log
lines (reference ``TFCluster.py:343-344``, SURVEY.md §5); these tests
pin the in-flight replacement — one env gate, no threads when off,
per-process registries that never alias across fork/spawn, and a
driver endpoint that reflects node liveness within one publish
interval.
"""

import io
import json
import multiprocessing as mp
import os
import re
import threading
import time
import types
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from tensorflowonspark_tpu import cluster as TFCluster
from tensorflowonspark_tpu.cluster import InputMode
from tensorflowonspark_tpu.engine import LocalEngine
from tensorflowonspark_tpu.obs import http as obs_http
from tensorflowonspark_tpu.obs import publish as obs_publish
from tensorflowonspark_tpu.obs import slo as obs_slo
from tensorflowonspark_tpu.obs import top as obs_top
from tensorflowonspark_tpu.utils import metrics_registry as reg

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "tensorflowonspark_tpu")

_ENV_KEYS = (reg.PORT_ENV, reg.INTERVAL_ENV, obs_http.HOST_ENV,
             obs_slo.SPEC_ENV)


@pytest.fixture(autouse=True)
def _obs_env():
    """Every test starts gate-off with a clean registry and leaves no
    obs env behind (the gate is ambient by design: children inherit)."""
    saved = {k: os.environ.get(k) for k in _ENV_KEYS}
    for k in _ENV_KEYS:
        os.environ.pop(k, None)
    reg.reset()
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    reg.reset()


def _enable(port="0", interval="0.2"):
    os.environ[reg.PORT_ENV] = port
    os.environ[reg.INTERVAL_ENV] = interval
    reg.reset()


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode("utf-8")


# --- registry core ----------------------------------------------------------

def test_disabled_is_total_noop():
    assert not reg.enabled()
    reg.inc("tfos_engine_jobs_total", status="ok")
    reg.set_gauge("tfos_feed_ring_bytes", 42)
    reg.observe("tfos_train_step_ms", 12.5)
    assert reg.snapshot() is None
    # no publisher thread, no server either
    assert obs_publish.start_publisher(object(), "n-0") is None
    assert obs_http.start_for_cluster(None) is None
    names = {t.name for t in threading.enumerate()}
    assert not any(n.startswith("tfos-obs") for n in names)


def test_counter_gauge_histogram_semantics():
    _enable()
    assert reg.enabled()
    reg.inc("tfos_engine_tasks_total", status="ok")
    reg.inc("tfos_engine_tasks_total", 2, status="ok")
    reg.inc("tfos_engine_tasks_total", status="error")
    reg.set_gauge("tfos_serve_queue_depth", 7)
    reg.set_gauge("tfos_serve_queue_depth", 3)  # last write wins
    for v in (1.0, 8.0, 40.0, 900.0):
        reg.observe("tfos_train_step_ms", v)
    snap = reg.snapshot()

    tasks = {tuple(sorted(s["labels"].items())): s["value"]
             for s in snap["tfos_engine_tasks_total"]["series"]}
    assert tasks[(("status", "ok"),)] == 3.0
    assert tasks[(("status", "error"),)] == 1.0
    (q,) = snap["tfos_serve_queue_depth"]["series"]
    assert q["value"] == 3.0
    (h,) = snap["tfos_train_step_ms"]["series"]
    assert h["count"] == 4 and h["sum"] == pytest.approx(949.0)
    assert sum(h["counts"]) == 4
    assert len(h["counts"]) == len(h["bounds"]) + 1  # +Inf bin

    text = reg.render_text([({"node": "w-0"}, snap)])
    assert "# TYPE tfos_engine_tasks_total counter" in text
    assert "# HELP tfos_train_step_ms" in text
    assert 'tfos_engine_tasks_total{node="w-0",status="ok"} 3' in text
    # histogram buckets are cumulative and end at +Inf = count
    assert 'tfos_train_step_ms_bucket{le="+Inf",node="w-0"} 4' in text
    assert 'tfos_train_step_ms_count{node="w-0"} 4' in text
    m = re.findall(r'le="1000",node="w-0"} (\d+)', text)
    assert m == ["4"]  # 900ms lands at or below the 1000ms bound


def test_quantile_interpolation_and_inf_clamp():
    _enable()
    for v in (1.0, 8.0, 40.0, 900.0, 10**9):  # last -> +Inf bucket
        reg.observe("tfos_train_step_ms", v)
    (h,) = reg.snapshot()["tfos_train_step_ms"]["series"]
    p50 = reg.quantile(h, 0.5)
    assert 25.0 <= p50 <= 50.0  # interpolated inside the 25-50ms bucket
    # the +Inf bucket clamps to the last finite bound, never inf
    assert reg.quantile(h, 0.999) == h["bounds"][-1]
    assert reg.quantile({"count": 0}, 0.5) is None


def test_gate_change_rekeys_registry():
    _enable(port="0")
    reg.inc("tfos_engine_jobs_total")
    assert reg.snapshot()
    os.environ[reg.PORT_ENV] = "9090"  # different gate value
    assert reg.snapshot() == {}  # fresh registry, counts gone
    del os.environ[reg.PORT_ENV]
    assert not reg.enabled()


def _child_probe(q):
    from tensorflowonspark_tpu.utils import metrics_registry as r

    q.put({"enabled": r.enabled(), "snap": r.snapshot(),
           "pid": os.getpid()})
    r.inc("tfos_feed_chunks_total")
    q.put({"snap2": r.snapshot()})


def test_spawn_child_gets_fresh_registry():
    """A spawned child inherits the gate through the env but NOT the
    parent's counts — registries are keyed by pid."""
    _enable()
    reg.inc("tfos_engine_jobs_total", status="ok")
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_child_probe, args=(q,))
    p.start()
    first, second = q.get(timeout=60), q.get(timeout=60)
    p.join(60)
    assert p.exitcode == 0
    assert first["enabled"] and first["pid"] != os.getpid()
    assert first["snap"] == {}  # empty, not the parent's series
    assert set(second["snap2"]) == {"tfos_feed_chunks_total"}
    # and the parent never saw the child's series
    assert "tfos_feed_chunks_total" not in reg.snapshot()


# --- instrumented subsystems (in-process) -----------------------------------

def test_checkpoint_metrics(tmp_path):
    np = pytest.importorskip("numpy")
    from tensorflowonspark_tpu.utils import checkpoint as ckpt

    _enable()
    ckpt.save_checkpoint(str(tmp_path), {"w": np.ones(4)}, step=1)
    _step, path = ckpt.latest(str(tmp_path))
    ckpt.load_checkpoint(path)
    snap = reg.snapshot()
    assert obs_http._metric_total(snap, "tfos_checkpoint_saves_total") == 1
    assert obs_http._metric_total(snap, "tfos_checkpoint_restores_total") == 1
    assert obs_http._metric_hist(snap, "tfos_checkpoint_save_ms")["count"] == 1
    assert obs_http._metric_hist(
        snap, "tfos_checkpoint_restore_ms")["count"] == 1


def test_serving_metrics():
    np = pytest.importorskip("numpy")
    from tensorflowonspark_tpu.serving import replicas as R
    from tensorflowonspark_tpu.serving import server as S

    _enable()
    spec = R.ModelSpec(predict=_double_predict, params=2.0, jit=False)
    with S.Server(spec, num_replicas=1, max_batch=8, max_delay_ms=5) as srv:
        c = srv.client()
        for i in range(4):
            c.predict({"x": np.full((2,), float(i), np.float32)}, timeout=60)
    snap = reg.snapshot()
    assert obs_http._metric_total(snap, "tfos_serve_requests_total") == 4
    assert obs_http._metric_hist(snap, "tfos_serve_request_ms")["count"] == 4
    assert obs_http._metric_total(snap, "tfos_serve_batches_total") >= 1
    # one row per request (the (2,) vector is the feature dim)
    assert obs_http._metric_total(snap, "tfos_serve_batch_rows_total") == 4
    assert obs_http._metric_gauge(snap, "tfos_serve_queue_depth") is not None


def _double_predict(params, inputs):
    return {"y": inputs["x"] * params}


def test_train_metrics_bridge():
    from tensorflowonspark_tpu.utils.metrics import TrainMetrics

    _enable()
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    tm = TrainMetrics(flops_per_item=1e9, device=v5e)
    tm.step()  # arms the timer
    for _ in range(3):
        tm.infeed_wait(0.001)
        tm.step(items=32)
    snap = reg.snapshot()
    assert obs_http._metric_total(snap, "tfos_train_steps_total") == 3
    assert obs_http._metric_hist(snap, "tfos_train_step_ms")["count"] == 3
    assert obs_http._metric_gauge(snap, "tfos_train_items_per_sec") > 0
    # sub-ms fake steps make the absolute MFU meaningless; just wired
    assert obs_http._metric_gauge(snap, "tfos_train_mfu") > 0
    assert obs_http._metric_gauge(
        snap, "tfos_train_infeed_stall_frac") <= 1.0
    summary = obs_http.node_summary(snap)
    assert summary["steps"] == 3 and summary["items_per_sec"] > 0
    assert summary["step_ms_p50"] <= summary["step_ms_p99"]


# --- e2e: cluster run with the endpoint up ----------------------------------

def _obs_trainer_fn(args, ctx):
    from tensorflowonspark_tpu.utils.metrics import TrainMetrics

    tm = TrainMetrics()
    feed = ctx.get_data_feed(train_mode=True, metrics=tm)
    tm.step()
    while not feed.should_stop():
        batch = feed.next_batch(8)
        tm.step(items=len(batch))


def test_cluster_endpoints_e2e():
    """The acceptance scenario: TFOS_OBS_PORT set, a small SPARK-mode
    run, and curl-style scrapes see engine + feed + train series, a
    live /statusz, and a 200 /healthz — then everything tears down."""
    _enable(port="0", interval="0.1")
    engine = LocalEngine(2)
    cluster = None
    try:
        cluster = TFCluster.run(
            engine, _obs_trainer_fn, [], num_executors=2,
            input_mode=InputMode.SPARK)
        assert cluster.obs is not None and cluster.obs.port > 0
        base = cluster.obs.url
        ds = engine.parallelize(range(64), 2)
        cluster.train(ds)

        want = ("tfos_engine_jobs_total", "tfos_feed_chunks_total",
                "tfos_train_steps_total")
        deadline = time.monotonic() + 60
        text = ""
        while time.monotonic() < deadline:
            _, text = _get(base + "/metrics")
            if all(w in text for w in want):
                break
            time.sleep(0.2)
        assert all(w in text for w in want), text[-2000:]
        # engine counters come from the driver process ...
        assert 'node="driver"' in text
        # ... feed/train series from the published worker snapshots
        assert re.search(r'tfos_train_steps_total\{node="worker-\d"\}', text)

        # a serving roundtrip in the driver process shows up on the
        # same scrape (acceptance: engine+feed+train+serving covered)
        np = pytest.importorskip("numpy")
        from tensorflowonspark_tpu.serving import replicas as R
        from tensorflowonspark_tpu.serving import server as S
        spec = R.ModelSpec(predict=_double_predict, params=2.0, jit=False)
        with S.Server(spec, num_replicas=1, max_batch=4,
                      max_delay_ms=5) as srv:
            srv.client().predict({"x": np.ones(2, np.float32)}, timeout=60)
        _, text = _get(base + "/metrics")
        assert ('tfos_serve_requests_total'
                '{node="driver",status="ok"} 1') in text

        code, body = _get(base + "/healthz")
        health = json.loads(body)
        assert code == 200 and health["status"] == "ok"
        assert any(nid.startswith("worker-") for nid in health["nodes"])
        assert all(n["alive"] for n in health["nodes"].values())

        _, body = _get(base + "/statusz")
        status = json.loads(body)
        assert status["cluster"]["num_executors"] == 2
        workers = {nid: e for nid, e in status["nodes"].items()
                   if nid.startswith("worker-")}
        assert len(workers) == 2
        assert all(e["alive"] and e["role"] == "worker"
                   for e in workers.values())
        assert any(e["summary"].get("steps", 0) > 0
                   for e in workers.values())
        # freshness: published within a few publish intervals
        assert all(e["last_seen_age_s"] < 10 for e in workers.values()
                   if e.get("last_seen_age_s") is not None)

        # tfos-top renders the real statusz
        out = io.StringIO()
        assert obs_top.main(["--url", base, "--once"], out=out) == 0
        table = out.getvalue()
        assert "NODE" in table and "worker-0" in table and "yes" in table

        cluster.shutdown()
        assert cluster.obs is None  # server stopped with the cluster
        names = {t.name for t in threading.enumerate()}
        assert not any(n.startswith("tfos-obs") for n in names)
    finally:
        if cluster is not None and cluster.obs is not None:
            cluster.obs.stop()
        engine.stop()


def test_cluster_without_gate_has_no_obs():
    engine = LocalEngine(1)
    try:
        cluster = TFCluster.run(
            engine, _noop_fn, [], num_executors=1,
            input_mode=InputMode.TENSORFLOW)
        assert cluster.obs is None
        names = {t.name for t in threading.enumerate()}
        assert not any(n.startswith("tfos-obs") for n in names)
        cluster.shutdown()
    finally:
        engine.stop()


def _noop_fn(args, ctx):
    pass


# --- tfos-top against a canned statusz --------------------------------------

_CANNED = {
    "cluster": {"id": "abcd1234", "epoch": 0, "num_executors": 2,
                "restarts": 2, "restarts_used": 1},
    "feeds": {"default": 4},
    "nodes": {
        "worker-0": {"role": "worker", "alive": True,
                     "last_seen_age_s": 0.4,
                     "summary": {"steps": 120, "step_ms_p50": 12.5,
                                 "items_per_sec": 25562.0, "mfu": 0.41,
                                 "stall_frac": 0.02, "queue_depth": 3,
                                 "serve_p50_ms": 4.0, "serve_p99_ms": 21.0}},
        "worker-1": {"role": "worker", "alive": False,
                     "heartbeat_age_s": 99.0, "summary": {}},
    },
    # an obs/slo.py report, rendered only under --slo
    "slo": [
        {"name": "decode_ttft", "kind": "latency",
         "metric": "tfos_decode_ttft_ms", "target_pct": 99.0,
         "threshold_ms": 500.0, "current": 128.5, "burn": 0.4,
         "breaching": False, "samples": 900},
        {"name": "serve_availability", "kind": "availability",
         "metric": "tfos_serve_requests_total", "target_pct": 99.0,
         "current": 0.985, "burn": 1.5, "breaching": True,
         "samples": 4000},
        {"name": "quiet", "kind": "latency", "metric": "m",
         "target_pct": 99.0, "threshold_ms": 10.0, "current": None,
         "burn": None, "breaching": False, "samples": 0},
    ],
    # a serving/fabric fabric_table() rollup, rendered only under --pods
    "pods": [
        {"router": 0, "host": 0, "alive": True, "pid": 4242,
         "replicas": 3, "queue_depth": 2, "version": 7,
         "affinity_hit_rate": 0.75},
        {"router": 0, "host": 1, "alive": False, "pid": 4243,
         "replicas": 0, "queue_depth": 0, "version": 7,
         "affinity_hit_rate": 0.0},
    ],
}


class _StatuszStub(BaseHTTPRequestHandler):
    def log_message(self, *a):
        pass

    def do_GET(self):  # noqa: N802 - http.server API
        body = json.dumps(_CANNED).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


def test_tfos_top_once_renders_table():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _StatuszStub)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        out = io.StringIO()
        assert obs_top.main(["--url", url, "--once"], out=out) == 0
        text = out.getvalue()
        assert "cluster abcd1234" in text and "restarts=1/2" in text
        assert "feed ledger: default:4" in text
        lines = text.splitlines()
        (w0,) = [ln for ln in lines if ln.startswith("worker-0")]
        assert "yes" in w0 and "25.6k" in w0     # items/s compacted
        assert "41.0" in w0 and "4/21" in w0     # mfu%, p50/p99
        (w1,) = [ln for ln in lines if ln.startswith("worker-1")]
        assert "DOWN" in w1
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_tfos_top_errors_without_target():
    assert obs_top.main(["--once"], out=io.StringIO()) == 2  # no url, no env
    # unreachable target with --once: exit 2, not a hang
    assert obs_top.main(["--url", "http://127.0.0.1:1", "--once"],
                        out=io.StringIO()) == 2


def test_tfos_top_slo_pane():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _StatuszStub)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        out = io.StringIO()
        assert obs_top.main(["--url", url, "--once", "--slo"],
                            out=out) == 0
        text = out.getvalue()
        assert "slo burn (obs/slo.py):" in text
        lines = text.splitlines()
        (ttft,) = [ln for ln in lines if "decode_ttft" in ln]
        assert "<500ms" in ttft and "128.5ms" in ttft and "ok" in ttft
        (avail,) = [ln for ln in lines if "serve_availability" in ln]
        assert "BREACH" in avail and "98.5" in avail and "1.5" in avail
        (quiet,) = [ln for ln in lines if ln.startswith("quiet")]
        assert "no-data" in quiet
        # without --slo the pane stays hidden
        out2 = io.StringIO()
        assert obs_top.main(["--url", url, "--once"], out=out2) == 0
        assert "slo burn" not in out2.getvalue()
    finally:
        httpd.shutdown()
        httpd.server_close()
    assert "(no objectives reported)" in obs_top.render_slo({})


def test_tfos_top_pods_pane():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _StatuszStub)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        out = io.StringIO()
        assert obs_top.main(["--url", url, "--once", "--pods"],
                            out=out) == 0
        text = out.getvalue()
        assert "pods (serving/fabric/):" in text
        lines = text.splitlines()
        (h0,) = [ln for ln in lines if ln.startswith("0/0")]
        assert "yes" in h0 and "4242" in h0 and "75.0" in h0
        (h1,) = [ln for ln in lines if ln.startswith("0/1")]
        assert "DOWN" in h1
        # without --pods the pane stays hidden
        out2 = io.StringIO()
        assert obs_top.main(["--url", url, "--once"], out=out2) == 0
        assert "pods (serving/fabric/)" not in out2.getvalue()
    finally:
        httpd.shutdown()
        httpd.server_close()
    assert "(no fabric routers)" in obs_top.render_pods({})


# --- slo engine (obs/slo.py) -------------------------------------------------

def test_slo_spec_parse_errors_disable_engine():
    bad = (
        "nope",                      # no fields at all
        "n:weird:m@9",               # unknown kind
        "n:latency:m@9",             # latency without threshold
        "n:latency:m<x@9",           # non-numeric threshold
        "n:availability:m@0",        # target must be in (0, 100)
        "n:availability:m@100",
        "n:availability:m",          # missing @good_pct
        ":latency:m<1@9",            # empty name
    )
    for spec in bad:
        with pytest.raises(ValueError):
            obs_slo.parse_spec(spec)
    assert obs_slo.parse_spec("") == []
    # an invalid env/ctor spec disables the engine instead of raising
    assert obs_slo.Engine("garbage").objectives == []
    # the default spec parses and round-trips through repr
    objs = obs_slo.parse_spec(obs_slo.DEFAULT_SPEC)
    assert [o.name for o in objs] == ["decode_ttft", "serve_availability"]
    again = obs_slo.parse_spec(";".join(repr(o) for o in objs))
    assert [repr(o) for o in again] == [repr(o) for o in objs]


def test_slo_engine_burn_math_and_edge_trigger():
    _enable()
    eng = obs_slo.Engine(
        "av:availability:tfos_serve_requests_total@99;"
        "lat:latency:tfos_decode_ttft_ms<500@99")
    rep = eng.step([])
    assert [r["burn"] for r in rep["objectives"]] == [None, None]
    assert not any(r["breaching"] for r in rep["objectives"])

    snap = {
        "tfos_serve_requests_total": {"series": [
            {"labels": {"status": "ok"}, "value": 90.0},
            {"labels": {"status": "error"}, "value": 10.0},
        ]},
        "tfos_decode_ttft_ms": {"series": [
            {"labels": {}, "bounds": [100.0, 500.0],
             "counts": [8.0, 1.0, 1.0], "sum": 1000.0, "count": 10},
        ]},
    }
    rep = eng.step([snap, snap])
    by = {r["name"]: r for r in rep["objectives"]}
    av, lat = by["av"], by["lat"]
    # availability: 10% bad against a 1% error budget -> burn 10x
    assert av["samples"] == 200 and av["current"] == pytest.approx(0.9)
    assert av["burn"] == pytest.approx(10.0) and av["breaching"]
    # latency: 10% of samples in the +Inf bucket (> 500ms) @ p99 target
    assert lat["samples"] == 20
    assert lat["burn"] == pytest.approx(10.0) and lat["breaching"]
    assert lat["current"] == pytest.approx(500.0)  # clamps to last bound
    # breach counter is edge-triggered: a second breaching step no-ops
    eng.step([snap, snap])
    series = reg.snapshot()["tfos_slo_breaches_total"]["series"]
    counts = {s["labels"]["objective"]: s["value"] for s in series}
    assert counts == {"av": 1.0, "lat": 1.0}


def test_slo_endpoint_and_statusz_section():
    _enable()
    reg.inc("tfos_serve_requests_total", 99, status="ok")
    reg.inc("tfos_serve_requests_total", 1, status="shed")
    for _ in range(10):
        reg.observe("tfos_decode_ttft_ms", 5.0)
    srv = obs_http.ObsServer(cluster=None, port=0, interval=999).start()
    try:
        status, text = _get(srv.url + "/slo")
        assert status == 200
        doc = json.loads(text)
        assert set(doc) == {"ts", "objectives"}
        by = {r["name"]: r for r in doc["objectives"]}
        av = by["serve_availability"]
        assert av["burn"] == pytest.approx(1.0) and not av["breaching"]
        ttft = by["decode_ttft"]
        assert ttft["burn"] == 0.0 and ttft["samples"] == 10
        # statusz grows an slo section once the poller has stepped
        srv.poll_once()
        status, text = _get(srv.url + "/statusz")
        assert status == 200
        names = {r["name"] for r in json.loads(text)["slo"]}
        assert names == {"decode_ttft", "serve_availability"}
        status, text = _get(srv.url + "/metrics")
        assert "tfos_slo_burn_rate" in text
        assert 'objective="serve_availability"' in text
    finally:
        srv.stop()


# --- catalog / docs lint ----------------------------------------------------

_CALL_RE = re.compile(
    r'(?:inc|set_gauge|observe)\(\s*"(tfos_[a-z0-9_]+)"')


def _source_metric_names():
    """Metric names at actual instrumentation call sites (inc /
    set_gauge / observe), so unrelated ``tfos_*`` string literals
    (env keys, KV keys) don't trip the lint."""
    names = set()
    for dirpath, _dirs, files in os.walk(PKG):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fname), encoding="utf-8") as f:
                names.update(_CALL_RE.findall(f.read()))
    return names


def test_every_metric_in_catalog_and_docs():
    """The CATALOG is the contract: every ``tfos_*`` literal the package
    uses must be declared there, and every declared metric must be
    documented in docs/observability.md (same lint discipline as the
    telemetry span table)."""
    in_code = _source_metric_names()
    in_catalog = set(reg.CATALOG)
    assert in_code <= in_catalog, (
        f"undeclared metric names: {sorted(in_code - in_catalog)}")
    assert in_catalog <= in_code, (
        f"catalog entries never emitted: {sorted(in_catalog - in_code)}")
    with open(os.path.join(REPO, "docs", "observability.md"),
              encoding="utf-8") as f:
        docs = f.read()
    missing = [n for n in sorted(in_catalog) if n not in docs]
    assert not missing, f"metrics undocumented in docs/observability.md: {missing}"
