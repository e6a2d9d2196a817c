"""``benchmark/lib/aligned_trace`` on a hand-made serving capture whose
device stamps run 2.0 ms ahead of the host's: 16 clock probes of 0.15 ms
round trip in four bursts, then 10 decode iterations, each with a launch
of 2.1 ms (dispatch begin to the step's start) and a return of 0.4 ms
(the pick's end to the fetch's end).  The offset comes back from the
probes, the iterations keep causality only once aligned, the two metric
readers read the constructed values, and a gap ``program_trace`` puts
under ``logits_fetch`` moves to ``emit`` / ``build_window``.  Every
expected number is computed from the nanoseconds below."""

import contextlib
import io

import pytest

from benchmark.layer_metrics import step_launch_ms, step_return_ms
from benchmark.lib import aligned_trace as A
from benchmark.lib import program_trace as P

MS = 1_000_000  # ns
AHEAD = 2 * MS  # device stamp = host stamp + AHEAD (+ drift x host time)
DEVICE = "/device:TPU:0"
ENGINE = "python3#7"
# where each probe's program starts after its span began: 0.02-0.12 ms
PROBE_LAG = (20_000, 120_000, 50_000, 90_000)
PROBE_NS, PROBE_RUN_NS = 150_000, 10_000
# one iteration, from its begin t (host clock)
WINDOW, CALL = 300_000, 200_000        # build_window, step_dispatch
LAUNCH = 2_100_000                     # dispatch begin -> step start
STEP, PICK = 10 * MS, 50_000
RETURN = 400_000                       # pick end -> fetch end
SAMPLE, EMIT = 100_000, 400_000
ITERS = 10
T0 = 2000 * MS                         # the first iteration's begin


def capture(bursts=4, drift_ppm=0.0, insert_before=None):
    """``{plane: {line: events}}`` as ``read_raw`` gives it, with an
    ``XLA Ops`` line beside ``XLA Modules`` (the same events) so that
    ``program_trace.reduce`` reads it too.  ``insert_before``: the
    iteration before which an admission's insert, dispatched without a
    wait, still runs when the iteration begins."""
    host, runs = [], []

    def on_device(name, s, d):
        runs.append([name, s + AHEAD + drift_ppm * 1e-6 * s, d, {}])

    seq = 0
    for b in range(bursts):
        h = 10 * MS + b * 500 * MS
        for lag in PROBE_LAG:
            seq += 1
            host.append(["tfos/clock/probe", h, PROBE_NS, {"seq": seq}])
            on_device("jit_tfos_clock_probe", h + lag, PROBE_RUN_NS)
            h += 200_000
    t = T0
    for i in range(ITERS):
        if i == insert_before:
            on_device("jit_tfos_kv_insert", t + 200_000, 400_000)
        d = t + WINDOW
        f = d + LAUNCH + STEP + PICK + RETURN
        host += [["tfos/decode/iterate", t, f + SAMPLE + EMIT - t, {}],
                 ["tfos/decode/build_window", t, WINDOW, {}],
                 ["tfos/decode/step_dispatch", d, CALL, {}],
                 ["tfos/decode/logits_fetch", d + CALL, f - d - CALL, {}],
                 ["tfos/decode/sample", f, SAMPLE, {}],
                 ["tfos/decode/emit", f + SAMPLE, EMIT, {}]]
        on_device("jit_tfos_decode_step_paged", d + LAUNCH, STEP)
        on_device("jit_tfos_pick", d + LAUNCH + STEP, PICK)
        t = f + SAMPLE + EMIT
    return {"/host:CPU": {ENGINE: host},
            DEVICE: {"XLA Modules#0": runs,
                     "XLA Ops#1": [list(r) for r in runs]}}


def test_the_offset_comes_back_within_the_probes_width():
    got = A.align(capture())
    assert got["probes"] == 16 and got["drift_ppm"] == 0
    assert abs(got["theta_ms"] * MS + AHEAD) <= PROBE_NS
    # the bounds: max(-AHEAD - lag) .. min(-AHEAD + 0.15 - lag - 0.01)
    assert got["theta_ms"] == pytest.approx(-2.0)
    assert got["width_ms"] == pytest.approx(
        (PROBE_NS - PROBE_RUN_NS - max(PROBE_LAG) + min(PROBE_LAG)) / MS)


def test_causality_holds_once_aligned_and_not_before():
    got = A.align(capture())
    assert got["violations"] == 0
    # on the device's own stamps the step and the pick both end after the
    # fetch returned, in every iteration
    assert got["violations_raw"] == 2 * ITERS


def test_the_metrics_read_the_constructed_launch_and_return():
    got = A.align(capture())
    assert got["iterations"] == ITERS
    assert got["launch_ms"] == pytest.approx(LAUNCH / MS)
    assert got["dispatch_call_ms"] == pytest.approx(CALL / MS)
    assert got["launch_after_call_ms"] == pytest.approx((LAUNCH - CALL) / MS)
    assert got["return_ms"] == pytest.approx(RETURN / MS)
    assert got["host_between_ms"] == pytest.approx(
        (SAMPLE + EMIT + WINDOW) / MS)
    # the capture: the first probe's begin to the last pick's end, which
    # the device stamped AHEAD late
    last_fetch = T0 + ITERS * (WINDOW + LAUNCH + STEP + PICK + RETURN
                               + SAMPLE + EMIT) - SAMPLE - EMIT
    extent = last_fetch - RETURN + AHEAD - 10 * MS
    assert got["probe_share"] == pytest.approx(16 * PROBE_NS / extent)


def test_an_admissions_insert_is_not_the_iterations_program():
    """The insert's run straddles the next dispatch's begin: no violation
    of that iteration's, and its launch and return are what they were."""
    got = A.align(capture(insert_before=4))
    assert got["violations"] == 0 and got["violations_raw"] == 2 * ITERS
    assert got["launch_ms"] == pytest.approx(LAUNCH / MS)
    assert got["return_ms"] == pytest.approx(RETURN / MS)


def test_a_drifting_clock_is_fitted_when_no_constant_offset_fits():
    """40 ppm over 1.5 s of probes is 60 us, more than the 40 us the
    probes leave a constant offset: the line through them fits."""
    got = A.align(capture(drift_ppm=40.0))
    assert got["drift_ppm"] == pytest.approx(-40.0, rel=0.05)
    assert got["violations"] == 0
    assert got["launch_ms"] == pytest.approx(LAUNCH / MS, abs=0.01)
    assert got["return_ms"] == pytest.approx(RETURN / MS, abs=0.01)


def test_a_gap_under_logits_fetch_moves_to_emit_and_build_window():
    raw = P.reduce(capture())["device"]["idle_by"]
    got = A.align(capture())
    aligned = got["idle_by"]
    # the same attribution on the device's own stamps as program_trace's
    for name in ("tfos/decode/logits_fetch", "tfos/decode/build_window"):
        assert got["idle_by_raw"][name] == pytest.approx(raw[name])
    # raw: each gap between two steps lies under the NEXT iteration's
    # fetch; only the first iteration's window shows, and no emit
    assert "tfos/decode/emit" not in raw
    assert raw["tfos/decode/build_window"] == pytest.approx(WINDOW / 1e9)
    gap = RETURN + SAMPLE + EMIT + WINDOW + LAUNCH  # pick to step
    assert raw["tfos/decode/logits_fetch"] == pytest.approx(
        ((ITERS - 1) * gap + LAUNCH - CALL + AHEAD) / 1e9)
    # aligned: the iteration's own tail and the next one's window
    assert aligned["tfos/decode/emit"] == pytest.approx(
        (ITERS - 1) * EMIT / 1e9)
    assert aligned["tfos/decode/sample"] == pytest.approx(
        (ITERS - 1) * SAMPLE / 1e9)
    assert aligned["tfos/decode/build_window"] == pytest.approx(
        ITERS * WINDOW / 1e9)
    assert aligned["tfos/decode/logits_fetch"] == pytest.approx(
        ((ITERS - 1) * RETURN + ITERS * (LAUNCH - CALL)) / 1e9)


@pytest.mark.parametrize("bursts", [0, 1])
def test_too_few_probes_give_no_alignment(bursts):
    """None on a capture without probes (a program from before them) and
    on one with fewer than ``MIN_PROBES``."""
    assert A.align(capture(bursts=bursts)) is None


def test_no_device_plane_gives_no_alignment():
    events = capture()
    del events[DEVICE]
    assert A.align(events) is None


@pytest.fixture
def readers(monkeypatch):
    """The metric readers over a capture the test hands in place of the
    run's, and the number of times the child would have read one."""
    monkeypatch.setattr(A, "_MEMO", {})
    reads = []

    def use(events):
        monkeypatch.setattr(P, "find_capture", lambda facts: "capture-dir")
        monkeypatch.setattr(A, "_align_in_child",
                            lambda cap: reads.append(cap) or A.align(events))
        return reads
    return use


def test_the_readers_share_one_reading_and_announce_it(readers):
    reads = readers(capture())
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert step_launch_ms.read({}) == pytest.approx(LAUNCH / MS)
        assert step_return_ms.read({}) == pytest.approx(RETURN / MS)
    assert reads == ["capture-dir"]
    line, = out.getvalue().splitlines()
    assert line.startswith("[bench:aligned_trace] theta -2.0000 ms")
    assert "0 after" in line and f"{2 * ITERS} before" in line


def test_the_readers_read_none_on_a_capture_without_probes(readers):
    readers(capture(bursts=0))
    assert step_launch_ms.read({}) is None
    assert step_return_ms.read({}) is None


def test_the_readers_read_none_without_a_capture(monkeypatch):
    """``--trace 0``: no capture is found and no child is started."""
    monkeypatch.setattr(A, "_MEMO", {})

    def never(cap):
        raise AssertionError("a child was started without a capture")
    monkeypatch.setattr(A, "_align_in_child", never)
    facts = {"trace": None, "setup_s": 12.345678, "window_s": 40.000123,
             "attempted": 1}
    assert step_launch_ms.read(facts) is None
    assert step_return_ms.read(facts) is None


def test_read_raw_keeps_the_probes_of_a_real_capture(tmp_path):
    """A capture taken on this machine's CPU: the probe spans are kept
    (no device plane, so no alignment)."""
    import jax

    from benchmark.lib import trace as T
    from tensorflowonspark_tpu.utils import profiler

    profiler.probe_program()
    jax.profiler.start_trace(str(tmp_path))
    try:
        profiler.clock_probe(rounds=3)
    finally:
        jax.profiler.stop_trace()
    raw = A.read_raw(T.find_xplane(str(tmp_path)))
    probes = [e for plane in raw.values() for evs in plane.values()
              for e in evs if e[0] == A.PROBE_SPAN]
    assert len(probes) == 3 and A.align(raw) is None
