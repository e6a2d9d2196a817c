"""``benchmark/lib/program_trace``'s arithmetic on two small hand-made
traces (tests/benchmark_tests/data/program_trace_*.json): self times per
span and thread, idle gaps put down to the span that explains them,
device time per kernel and per program, exposed collective time; and the
eight metric readers on top of it.  Every expected number is computed by
hand from the fixtures' nanoseconds."""

import json
import math
import os

import pytest

from benchmark.lib import manifest as M
from benchmark.lib import program_trace as P

HERE = os.path.dirname(os.path.abspath(__file__))
NS = 1e-9


def fixture(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)["events"]


@pytest.fixture(scope="module")
def train():
    return P.reduce(fixture("program_trace_train.json"))


@pytest.fixture(scope="module")
def serve():
    return P.reduce(fixture("program_trace_serve.json"))


def approx(x):
    return pytest.approx(x, rel=1e-9, abs=1e-15)


def test_self_segments_name_the_deepest_open_span():
    segs = P.self_segments([["a", 0, 100, {}], ["b", 10, 30, {}],
                            ["c", 20, 10, {}], ["d", 200, 5, {}]])
    assert segs == [(0, 10, "a"), (10, 20, "b"), (20, 30, "c"),
                    (30, 40, "b"), (40, 100, "a"), (200, 205, "d")]


def test_a_child_that_outlives_its_parent_does_not_move_time_backwards():
    segs = P.self_segments([["a", 0, 50, {}], ["b", 40, 30, {}]])
    assert segs == [(0, 40, "a"), (40, 70, "b")]


def test_span_tables_count_total_self_and_summed_args(train):
    feed = train["threads"]["/host:CPU|python3#6"]
    assert feed["tfos/feed/to_columns"] == {
        "count": 1, "total_s": approx(2500 * NS),
        "self_s": approx(200 * NS), "args": {"records": 256}}
    assert feed["tfos/feed/ring_wait"]["self_s"] == approx(1900 * NS)
    assert feed["tfos/feed/ring_read"]["args"] == {"records": 1024,
                                                   "depth_bytes": 0}
    assert train["spans"]["bench/dispatch_step"]["count"] == 2
    assert train["steps"] == 2
    assert train["extent_s"] == approx(8100 * NS)   # 900 .. 9000


def test_threads_are_known_by_the_spans_they_carry(train, serve):
    assert train["dispatch_thread"] == "/host:CPU|python3#5"
    assert train["feed_thread"] == "/host:CPU|python3#6"
    assert serve["dispatch_thread"] == "/host:CPU|python3#7"
    assert serve["feed_thread"] is None and serve["device"] is None


def test_device_busy_window_and_idle(train):
    dev = train["device"]
    assert dev["busy_s"] == approx(5800 * NS)    # 3000 + 2000 + 800
    assert dev["window_s"] == approx(8000 * NS)  # 1000 .. 9000
    assert dev["idle_s"] == approx(2200 * NS)    # 2000 + 200


def test_idle_gaps_are_put_down_to_what_the_feed_thread_was_doing(train):
    """The 2,000 ns gap lies under ``tfos/feed/next`` (a pure wait) on
    the dispatching thread, so the feed's own thread names it: 1,000 ns
    of empty ring, 400 reading the chunk, 100 assembling, 200 staging;
    100 ns nobody explains stay with the wait.  The 200 ns gap lies under
    ``tfos/feed/sync``."""
    by = {k: round(v / NS) for k, v in train["device"]["idle_by"].items()}
    assert by == {"tfos/feed/ring_wait": 1000, "tfos/feed/ring_read": 400,
                  "tfos/feed/to_columns": 100, "tfos/feed/h2d": 200,
                  "tfos/feed/next": 100, "bench/wait_batch": 100,
                  "bench/dispatch_step": 100, "tfos/feed/sync": 200}
    # a wait alone names nothing: 1,900 of 2,200 ns are explained
    assert train["device"]["idle_named_frac"] == approx(1900 / 2200)


def test_device_time_per_kernel_and_per_program(train):
    k = {n: round(s / NS) for n, s in train["device"]["kernels"].items()}
    assert k == {"fusion": 3600, "tfos_flash_fwd": 1000,
                 "tfos_flash_bwd_dq": 500, "while": 200,
                 "all-reduce": 400, "all-reduce-done": 100}
    assert train["device"]["programs"] == {
        "jit_tfos_step": {"runs": 2, "seconds": approx(6000 * NS)}}


def test_exposed_collective_time(train):
    """In flight 3000..4000 (the loop's all-reduce, then an asynchronous
    one from start to done); ``fusion.3`` hides 400 ns of it; the loop
    that HOLDS the first all-reduce hides nothing."""
    dev = train["device"]
    assert dev["collective_s"] == approx(1000 * NS)
    assert dev["collective_exposed_s"] == approx(600 * NS)


def test_names_are_reduced_to_what_the_program_gave(train):
    assert P.kernel_name("%tfos_flash_bwd_dkv.7 [tpu_custom_call]") \
        == "tfos_flash_bwd_dkv"
    assert P.program_name("jit_tfos_decode_step_paged(85066412295645549)") \
        == "jit_tfos_decode_step_paged"


WANT = {
    "feed_ring_wait_frac": ("train", 1900 / 8100),
    "feed_host_busy_frac": ("train", 800 / 8100),    # read + columns + h2d
    "sync_ms": ("train", 300e-6),
    "collective_ms": ("train", 1000e-6 / 2),
    "collective_exposed_frac": ("train", 0.6),
    "attn_kernel_frac": ("train", 1500 / 5800),
    "iter_host_ms": ("serve", (2000 - 400 - 1000) * 1e-6 / 2),
    "admit_frac": ("serve", 400 / 2500),             # extent 1000 .. 3500
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_new_reader_on_the_fixtures(name, train, serve, monkeypatch):
    which, want = WANT[name]
    reduced = {"train": train, "serve": serve}[which]
    monkeypatch.setattr(P, "load", lambda facts: reduced)
    reader = M.load_module(M.reader_path("per_layer", name))
    got = reader.read({"trace": {"busy_s": 1.0}})
    assert got == approx(want) and math.isfinite(got)


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_new_reader_is_silent_without_a_capture(name):
    """``--trace 0`` runs read every metric too: no capture, no value,
    no child process, no exception."""
    reader = M.load_module(M.reader_path("per_layer", name))
    assert reader.read({"trace": None, "setup_s": 1.0, "window_s": 1.0,
                        "attempted": 1}) is None
    assert reader.read({"trace": None, "nodes": [
        {"trace_dir": None, "process_index": 0}]}) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_new_reader_is_silent_on_a_program_without_the_spans(
        name, monkeypatch):
    """The parent of PR25 writes no ``tfos/*`` span and names no kernel:
    its capture gives the new metrics nothing to read."""
    bare = P.reduce({"/device:TPU:0": {"XLA Ops#2": [
        ["%fusion.1", 1000, 1000, {}], ["%fusion.2", 3000, 1000, {}]]},
        "/host:CPU": {"python3#5": [["bench/dispatch_step", 900, 50, {}]]}})
    monkeypatch.setattr(P, "load", lambda facts: bare)
    reader = M.load_module(M.reader_path("per_layer", name))
    assert reader.read({"trace": {"busy_s": 1.0}}) is None
    assert bare["device"]["idle_named_frac"] == 0.0


def test_find_capture_matches_the_serving_run_by_its_facts(tmp_path,
                                                           monkeypatch):
    work = tmp_path / ".bench_work" / "cell"
    (work / "trace-replica").mkdir(parents=True)
    facts = {"setup_s": 12.5, "window_s": 40.0, "attempted": 50}
    (work / "facts.json").write_text(json.dumps(facts))
    monkeypatch.chdir(tmp_path)
    assert P.find_capture(dict(facts, trace=None)) \
        == str(work / "trace-replica")
    assert P.find_capture(dict(facts, setup_s=13.0)) is None
    assert P.find_capture({"nodes": [{"trace_dir": "/x", "process_index": 1},
                                     {"trace_dir": "/y"}]}) == "/y"
