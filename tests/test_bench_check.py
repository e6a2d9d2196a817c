"""scripts/bench_check.py: the perf-regression gate over BENCH lines.

Pins the two on-disk bench-file shapes (bare line, driver wrapper with
the line inside ``tail``), the skipping of lines without a
measurement, direction-aware tolerance (throughput up = good, serve p99
up = bad), and the exit codes.
"""

import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_CHECK = os.path.join(REPO, "scripts", "bench_check.py")


def _load():
    spec = importlib.util.spec_from_file_location("bench_check", BENCH_CHECK)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _line(img_s=None, p99=None, tok_s=None, fabric=None, value=0.4):
    extra = {}
    if img_s is not None:
        extra["images_per_sec_per_chip"] = img_s
    if p99 is not None:
        extra["serve"] = {"p99_ms": p99, "req_per_sec": 900.0}
    if tok_s is not None:
        extra["transformer"] = {"tokens_per_sec_per_chip": tok_s}
    if fabric is not None:
        extra["serve_fabric"] = dict(
            {"req_per_sec": 40.0, "p99_ms": 250.0, "dropped": 0,
             "affinity_hit_rate": 0.5, "scale_ups": 2}, **fabric)
    return {"metric": "resnet_train_mfu", "value": value, "unit": "frac",
            "extra": extra}


def _write(tmp_path, name, doc):
    (tmp_path / name).write_text(json.dumps(doc))


def _run(tmp_path, *args):
    env = dict(os.environ, PYTHONPATH="")
    env.pop("TFOS_BENCH_TOL", None)
    proc = subprocess.run(
        [sys.executable, BENCH_CHECK, "--dir", str(tmp_path), *args],
        capture_output=True, text=True, env=env, timeout=60)
    return proc.returncode, proc.stdout + proc.stderr


def test_ok_within_tolerance(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _line(img_s=2500, p99=20, tok_s=70e3))
    _write(tmp_path, "BENCH_r02.json", _line(img_s=2450, p99=21, tok_s=72e3))
    rc, out = _run(tmp_path)
    assert rc == 0, out
    assert "bench_check: OK" in out
    assert "newest=BENCH_r02.json prior=BENCH_r01.json" in out


def test_throughput_regression_fails(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _line(img_s=2500))
    _write(tmp_path, "BENCH_r02.json", _line(img_s=2000))  # -20%
    rc, out = _run(tmp_path)
    assert rc == 1
    assert "REGRESSION resnet.img_s -20.0%" in out


def test_serve_p99_direction_is_lower_better(tmp_path):
    # latency DOWN 20% is an improvement, not a regression ...
    _write(tmp_path, "BENCH_r01.json", _line(img_s=2500, p99=25))
    _write(tmp_path, "BENCH_r02.json", _line(img_s=2500, p99=20))
    rc, out = _run(tmp_path)
    assert rc == 0, out
    # ... latency UP 50% is one
    _write(tmp_path, "BENCH_r03.json", _line(img_s=2500, p99=30))
    rc, out = _run(tmp_path)
    assert rc == 1
    assert "serve.p99_ms" in out


def test_tolerance_flag(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _line(img_s=2500))
    _write(tmp_path, "BENCH_r02.json", _line(img_s=2200))  # -12%
    assert _run(tmp_path)[0] == 1
    assert _run(tmp_path, "--tolerance", "0.15")[0] == 0


def test_wrapper_and_unmeasured_shapes(tmp_path):
    """Driver-wrapper files parse via ``tail``; lines without a
    measurement (value null, no lanes) are skipped when picking rounds."""
    good = _line(img_s=2500)
    _write(tmp_path, "BENCH_r01.json",
           {"n": 1, "cmd": "python bench.py", "rc": 0,
            "tail": "noise\n" + json.dumps(good) + "\n"})
    _write(tmp_path, "BENCH_r02.json", _line(img_s=2490))
    _write(tmp_path, "BENCH_r03.json",  # rc=124 wedge: no line at all
           {"n": 3, "cmd": "python bench.py", "rc": 124, "tail": "killed"})
    _write(tmp_path, "BENCH_r04.json",  # parses, but measured nothing
           {"metric": "resnet_train_mfu", "value": None,
            "extra": {"error": "no_measurement"}})
    rc, out = _run(tmp_path)
    assert rc == 0, out
    assert "newest=BENCH_r02.json prior=BENCH_r01.json" in out


def test_fewer_than_two_usable_is_skip(tmp_path):
    rc, out = _run(tmp_path)
    assert rc == 0 and "SKIP (0 usable" in out
    _write(tmp_path, "BENCH_r01.json", _line(img_s=2500))
    rc, out = _run(tmp_path)
    assert rc == 0 and "SKIP (1 usable" in out


def test_disjoint_lanes_is_skip(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _line(img_s=2500, value=None))
    _write(tmp_path, "BENCH_r02.json", _line(tok_s=70e3, value=None))
    rc, out = _run(tmp_path)
    assert rc == 0 and "SKIP (no lane present in both" in out


def test_run_stamp_keys_are_ignored_by_lanes(tmp_path, monkeypatch):
    """bench.py stamps ``run_id``/``telemetry_dir`` into its line so a
    BENCH file can be joined to its trace directory; bench_check must
    treat those as non-lane metadata (ISSUE 12 satellite)."""
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    monkeypatch.setenv("TFOS_TELEMETRY_DIR", str(tmp_path / "tel"))
    stamp = bench.run_stamp()
    assert re.fullmatch(r"\d{8}T\d{6}-[0-9a-f]{6}", stamp["run_id"])
    assert stamp["telemetry_dir"] == str(tmp_path / "tel")
    assert bench.run_stamp()["run_id"] == stamp["run_id"]  # stable per run

    bc = _load()
    plain = _line(img_s=2500, p99=20)
    stamped = dict(_line(img_s=2500, p99=20), **stamp)
    assert bc.lanes_of(stamped) == bc.lanes_of(plain)

    _write(tmp_path, "old.json", plain)
    _write(tmp_path, "new.json", stamped)
    rc, out = _run(tmp_path, "--baseline", str(tmp_path / "old.json"),
                   "--latest", str(tmp_path / "new.json"))
    assert rc == 0, out


def test_fabric_dropped_ceiling_is_pinned_at_zero(tmp_path):
    """The fabric lane's zero-drop contract: any client-visible error
    fails the gate even when the PRIOR round was just as bad (absolute
    ceiling, not a trend)."""
    _write(tmp_path, "BENCH_r01.json", _line(img_s=2500,
                                             fabric={"dropped": 3}))
    _write(tmp_path, "BENCH_r02.json", _line(img_s=2500,
                                             fabric={"dropped": 3}))
    rc, out = _run(tmp_path)
    assert rc == 1
    assert "fabric.dropped" in out and "above ceiling" in out
    # dropped back at 0: the trend lanes take over and pass
    _write(tmp_path, "BENCH_r03.json", _line(img_s=2500, fabric={}))
    _write(tmp_path, "BENCH_r04.json", _line(img_s=2500, fabric={}))
    rc, out = _run(tmp_path)
    assert rc == 0, out


def test_fabric_scale_ups_floor_and_p99_trend(tmp_path):
    """scale_ups < 1 means the autoscaler never actuated — an absolute
    floor on the newest line; it is NOT compared round-over-round (how
    many steps the load shape needed is not a trend).  fabric.p99_ms
    is a plain lower-is-better trend lane."""
    _write(tmp_path, "BENCH_r01.json", _line(img_s=2500,
                                             fabric={"scale_ups": 4}))
    _write(tmp_path, "BENCH_r02.json", _line(img_s=2500,
                                             fabric={"scale_ups": 0}))
    rc, out = _run(tmp_path)
    assert rc == 1
    assert "fabric.scale_ups" in out and "below floor" in out
    # fewer scale_ups than last round but >= 1: not a regression
    _write(tmp_path, "BENCH_r03.json", _line(img_s=2500,
                                             fabric={"scale_ups": 1}))
    rc, out = _run(tmp_path)
    assert rc == 0, out
    assert "fabric.scale_ups" not in out
    # p99 blowing up past tolerance IS one
    _write(tmp_path, "BENCH_r04.json", _line(img_s=2500,
                                             fabric={"p99_ms": 400.0}))
    rc, out = _run(tmp_path)
    assert rc == 1
    assert "fabric.p99_ms" in out


def test_real_repo_bench_files_are_comparable():
    """The checked-in bench line must stay parseable (the driver-wrapper
    shape is covered by test_wrapper_and_unmeasured_shapes)."""
    bc = _load()
    usable = {os.path.basename(p) for p, _ in bc.discover(REPO)}
    assert "BENCH_session_r4.json" in usable
    lanes, _ = bc.load_bench(os.path.join(REPO, "BENCH_session_r4.json"))
    assert lanes["resnet.img_s"] > 0 and lanes["fed.img_s"] > 0
