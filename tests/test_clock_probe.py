"""The clock probe (``utils/profiler.clock_probe``) and the decode
engine's use of it: the engine probes only while a profiler capture
records (``telemetry.capturing``, monkeypatched here: no capture runs),
at most every ``scheduler.PROBE_EVERY_S``, always between two iterations
and never inside one, and the served tokens are the same with the probe
on and off."""

import json
import threading
import time

import pytest

from tensorflowonspark_tpu.serving import decode as D
from tensorflowonspark_tpu.serving.decode import scheduler
from tensorflowonspark_tpu.utils import profiler, telemetry

pytestmark = pytest.mark.decode

_ENV_KEYS = (telemetry.DIR_ENV, telemetry.SPOOL_ENV, telemetry.NODE_ENV,
             telemetry.ROLE_ENV)


@pytest.fixture(autouse=True)
def _no_spool(monkeypatch):
    for k in _ENV_KEYS:
        monkeypatch.delenv(k, raising=False)


def _cfg():
    from tensorflowonspark_tpu.models import transformer as T
    return T.Config(vocab_size=61, dim=32, n_layers=2, n_heads=2,
                    max_seq=32, dtype="float32", attn_impl="reference")


def _params(cfg):
    import jax

    from tensorflowonspark_tpu.models import transformer as T
    return T.init(jax.random.PRNGKey(0), cfg)


# the real ones, whatever a test has patched since
_ITERATE = D.DecodeEngine._iterate
_PROBE = profiler.clock_probe
_PROGRAM = profiler.probe_program

# 2 slots, 6 greedy sessions: three rounds of admissions and ~70 iterations
_JOBS = [(i, [2 + i, 3, 5, 7, 11][: 2 + i % 4], 20 + i) for i in range(6)]


def _serve(monkeypatch, capturing):
    """Run ``_JOBS`` through one engine with ``telemetry.capturing``
    answering ``capturing``; ``(tokens by sid, log, probe runs)``: the
    log holds ``("begin"|"end", t)`` around every iteration and
    ``("probe", t)`` per probe call, in order."""
    log, runs = [], []
    lock = threading.Lock()
    monkeypatch.setattr(telemetry, "capturing", lambda: capturing)

    def iterate(self, cache, dcache):
        log.append(("begin", time.perf_counter()))
        _ITERATE(self, cache, dcache)
        log.append(("end", time.perf_counter()))

    def probe(rounds=4):
        log.append(("probe", time.perf_counter()))
        _PROBE(rounds)

    def program():
        run, x = _PROGRAM()

        def counted(arg):
            runs.append(1)
            return run(arg)
        return counted, x

    monkeypatch.setattr(D.DecodeEngine, "_iterate", iterate)
    monkeypatch.setattr(profiler, "clock_probe", probe)
    monkeypatch.setattr(profiler, "probe_program", program)
    cfg = _cfg()
    done = {}

    def emit(events):
        with lock:
            for kind, sid, *rest in events:
                assert kind != "error", rest
                if kind == "done":
                    done[sid] = rest[0]

    eng = D.DecodeEngine(_params(cfg), D.DecodeSpec(cfg, slots=2), emit)
    eng.start(timeout=300)
    try:
        for sid, prompt, max_tokens in _JOBS:
            eng.submit(sid, prompt, max_tokens=max_tokens)
        deadline = time.time() + 300
        while len(done) < len(_JOBS) and time.time() < deadline:
            time.sleep(0.01)
    finally:
        eng.stop()
    assert len(done) == len(_JOBS)
    return done, log, runs


def test_the_engine_runs_no_probe_without_a_capture(monkeypatch):
    done, log, runs = _serve(monkeypatch, capturing=False)
    assert sum(kind == "end" for kind, _t in log) >= 20
    assert [kind for kind, _t in log if kind == "probe"] == [] and runs == []


def test_the_engine_probes_between_iterations_at_most_every_interval(
        monkeypatch):
    monkeypatch.setattr(scheduler, "PROBE_EVERY_S", 0.05)
    _done, log, runs = _serve(monkeypatch, capturing=True)
    probes = [t for kind, t in log if kind == "probe"]
    assert probes and len(runs) == 4 * len(probes)
    assert all(b - a >= 0.05 for a, b in zip(probes, probes[1:]))
    # every probe right after an iteration ended, none inside one
    inside = False
    for i, (kind, _t) in enumerate(log):
        if kind == "probe":
            assert not inside and log[i - 1][0] == "end"
        inside = {"begin": True, "end": False}.get(kind, inside)


def test_the_probe_leaves_the_served_tokens_as_they_were(monkeypatch):
    monkeypatch.setattr(scheduler, "PROBE_EVERY_S", 0.0)
    off, _log, no_runs = _serve(monkeypatch, capturing=False)
    on, log, runs = _serve(monkeypatch, capturing=True)
    assert no_runs == [] and len(runs) >= 4 * 20
    assert on == off
    assert sum(kind == "probe" for kind, _t in log) == sum(
        kind == "end" for kind, _t in log)


def test_clock_probe_writes_rounds_spans_of_rising_seq(tmp_path,
                                                       monkeypatch):
    monkeypatch.setenv(telemetry.DIR_ENV, str(tmp_path))
    monkeypatch.setenv(telemetry.NODE_ENV, "probe-0")
    profiler.clock_probe(rounds=3)
    profiler.clock_probe(rounds=2)
    telemetry.flush()
    with open(telemetry.sink_path()) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    seqs = [r["attrs"]["seq"] for r in recs
            if r["name"] == telemetry.CLOCK_PROBE]
    assert len(seqs) == 5
    assert seqs == list(range(seqs[0], seqs[0] + 5))


def test_the_probe_program_is_built_once_under_its_name():
    run, x = profiler.probe_program()
    assert profiler.probe_program() == (run, x)
    assert run.as_text().startswith("HloModule jit_tfos_clock_probe")
    assert x.shape == (1,) and str(x.dtype) == "int32"
    assert int(run(x)[0]) == 1
