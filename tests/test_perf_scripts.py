"""Dry-run the perf scripts off-chip.

Their TPU-only branches (promote, config merge, refusal) run nowhere in
the sandbox unless a test drives them.  These tests run the REAL scripts
as subprocesses — tiny shapes via TFOS_SWEEP_TINY, a faked TPU device
identity via tests/fake_tpu_driver.py where the branch under test
demands one — so chip time is spent measuring, not debugging.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRIVER = os.path.join(REPO, "tests", "fake_tpu_driver.py")


def _env(cfg_path, **extra):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("TFOS_")}
    env.update(
        JAX_PLATFORMS="cpu",
        TFOS_BENCH_CONFIG=str(cfg_path),
        TFOS_SWEEP_TINY="1",
        # explicit acknowledgement that promoting tiny results is the
        # POINT of these dry runs; without it the sweeps refuse (the
        # guard a leftover TFOS_SWEEP_TINY on a live claim relies on)
        TFOS_SWEEP_TINY_PROMOTE_OK="1",
    )
    env.update(extra)
    return env


def _run(args, env, timeout=600):
    proc = subprocess.run(
        [sys.executable] + args, cwd=REPO, env=env,
        capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout


def test_resnet_promote_writes_config_on_faked_tpu(tmp_path):
    cfg = tmp_path / "bench_config.json"
    out = _run(
        [DRIVER, "sweep_resnet", "faketpu",
         "--steps", "2", "--image", "32", "--promote"],
        _env(cfg, TFOS_SWEEP="b512_s2d_bnf"))
    assert "promoted" in out, out
    written = json.loads(cfg.read_text())
    assert written["winner"] == "b512_s2d_bnf"
    assert written["batch"] == 4 and written["image"] == 32
    assert written["stem_s2d"] is True
    assert "FakeTpuDevice" in written["device"]


def test_transformer_promote_merges_resnet_section(tmp_path):
    cfg = tmp_path / "bench_config.json"
    # pre-existing resnet winner must survive the transformer promote
    cfg.write_text(json.dumps(
        {"batch": 512, "stem_s2d": True, "remat": False,
         "winner": "b512_s2d_bnf", "image": 224}))
    out = _run(
        [DRIVER, "sweep_transformer", "faketpu",
         "--steps", "2", "--promote"],
        _env(cfg, TFOS_SWEEP="b16_q512_kv512"))
    assert "promoted" in out, out
    written = json.loads(cfg.read_text())
    assert written["winner"] == "b512_s2d_bnf"  # resnet section kept
    assert written["transformer"]["winner"] == "b16_q512_kv512"
    assert written["transformer"]["bwd"] == "xla"


def test_transformer_promote_records_seq(tmp_path):
    """The r5 long-seq configs carry a per-config seq; the promote path
    must record it so bench._transformer_bench sizes cfg.max_seq from
    the promoted winner (tiny mode rewrites long-seq to 2x the tiny
    base seq — the key's presence and round-trip is what's under test)."""
    cfg = tmp_path / "bench_config.json"
    out = _run(
        [DRIVER, "sweep_transformer", "faketpu",
         "--steps", "2", "--promote"],
        _env(cfg, TFOS_SWEEP="b16_s4096_remat_pbwd_bce"))
    assert "promoted" in out, out
    written = json.loads(cfg.read_text())["transformer"]
    assert written["winner"] == "b16_s4096_remat_pbwd_bce"
    assert written["seq"] == 512  # tiny base 256 x 2 for long-seq picks
    assert written["ce"] == "block"


def test_promote_refused_on_real_cpu(tmp_path):
    """Without the faked device the promote guard must refuse: a CPU run
    may never pin the TPU bench to toy shapes."""
    cfg = tmp_path / "bench_config.json"
    out = _run(
        [DRIVER, "sweep_resnet", "cpu",
         "--steps", "2", "--image", "32", "--promote"],
        _env(cfg, TFOS_SWEEP="b512_s2d_bnf"))
    assert "promote skipped" in out, out
    assert not cfg.exists()


def test_tiny_promote_refused_without_acknowledgement(tmp_path):
    """A leftover TFOS_SWEEP_TINY=1 during a live chip claim must not
    pin bench_config.json to batch-4 toy shapes: promote requires the
    explicit TFOS_SWEEP_TINY_PROMOTE_OK acknowledgement."""
    cfg = tmp_path / "bench_config.json"
    env = _env(cfg, TFOS_SWEEP="b512_s2d_bnf")
    env.pop("TFOS_SWEEP_TINY_PROMOTE_OK")
    out = _run(
        [DRIVER, "sweep_resnet", "faketpu",
         "--steps", "2", "--image", "32", "--promote"], env)
    assert "promote skipped" in out, out
    assert not cfg.exists()


def test_bench_reads_env_config_path(tmp_path, monkeypatch):
    """bench.py must pick up TFOS_BENCH_CONFIG so dry runs and tests
    never collide with the repo-root promoted config."""
    cfg = tmp_path / "bench_config.json"
    cfg.write_text(json.dumps({"batch": 123, "transformer": {"batch": 7}}))
    monkeypatch.setenv("TFOS_BENCH_CONFIG", str(cfg))
    sys.path.insert(0, REPO)
    try:
        import bench

        got = bench._promoted_config()
    finally:
        sys.path.remove(REPO)
    assert got["batch"] == 123 and got["transformer"]["batch"] == 7


def test_stress_fed_both_modes(tmp_path):
    """The fed consumer stress bench (scripts/stress_fed.py) must run
    both wire modes end-to-end: real feeder process -> shm ring ->
    DataFeed, correct shapes, non-zero throughput."""
    env = _env(tmp_path / "unused.json")
    out = _run([os.path.join(REPO, "scripts", "stress_fed.py"),
                "--batch", "32", "--image", "32", "--steps", "6"],
               env, timeout=300)
    lines = [json.loads(x) for x in out.strip().splitlines()
             if x.startswith("{")]
    by_mode = {r["mode"]: r for r in lines if "mode" in r}
    assert set(by_mode) == {"rows", "columnar"}, out
    for r in by_mode.values():
        assert r["records_per_sec"] > 0 and r["batches"] > 0, out
