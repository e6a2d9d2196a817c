"""chip_smoke.py off the chip: the rehearsal runs, a failure is never an
exit 0, and the pieces the chip run leans on resolve as documented."""

import json
import os
import subprocess
import sys
import types

import pytest

from tensorflowonspark_tpu import tpu_info
from tensorflowonspark_tpu.utils import compile_cache
from tensorflowonspark_tpu.utils import metrics as M

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(tmp, *argv, **env):
    full = {k: v for k, v in os.environ.items()
            if not k.startswith("TFOS_")}
    full.update(JAX_PLATFORMS="cpu", **env)
    proc = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp), *argv], cwd=str(tmp),
        env=full, capture_output=True, text=True, timeout=300)
    lines = proc.stdout.strip().splitlines()
    return proc, lines, json.loads(lines[-1])


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    return _smoke(tmp_path_factory.mktemp("smoke"), "--rehearse",
                  "--phases", "train_fed")


def test_rehearsal_train_fed_passes_and_says_what_it_is(rehearsal):
    proc, lines, last = rehearsal
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["phases"] == ["train_fed"]


def test_rehearsal_can_never_say_tpu(rehearsal):
    _proc, lines, _last = rehearsal
    assert not any('"platform": "tpu"' in ln or "'platform': 'tpu'" in ln
                   for ln in lines), lines


def test_rehearsal_feeds_every_record_exactly_once_through_the_ring(
        rehearsal):
    _proc, lines, _last = rehearsal
    fed = [ln for ln in lines if "ids exactly once" in ln]
    assert fed and "records=32" in fed[0], lines
    assert any("ring /tfos-" in ln for ln in lines), lines


def test_parent_never_imports_jax(rehearsal):
    """Checked where it matters, in the parent process itself: it looks
    at its own ``sys.modules`` after every phase has run and fails the
    run if jax is there."""
    _proc, lines, _last = rehearsal
    assert "[chip_smoke:parent] jax imported by the parent: False" in lines


def test_hidden_chip_is_a_failure_not_a_cpu_run(tmp_path):
    proc, _lines, last = _smoke(tmp_path, "--phases", "kernels")
    assert proc.returncode != 0
    assert last["ok"] is False
    assert "no accelerator" in proc.stderr


def test_failed_phase_is_never_exit_zero(tmp_path):
    """A trainer that dies (injected at node.main) fails the run."""
    proc, _lines, last = _smoke(tmp_path, "--rehearse", "--phases",
                                "train_fed", TFOS_FAULT_PLAN="node.main:exc")
    assert proc.returncode != 0
    assert last["ok"] is False and "train_fed" in last["error"]


# -- compile cache -----------------------------------------------------------

def test_compile_cache_dir_given_is_used_untouched():
    env = {compile_cache.DIR_ENV: "/some/where/else"}
    assert compile_cache.export_env(env) == "/some/where/else"
    assert env == {compile_cache.DIR_ENV: "/some/where/else"}


def test_compile_cache_dir_unset_is_one_fixed_path_in_the_checkout():
    first, second = {}, {}
    assert compile_cache.export_env(first) == compile_cache.export_env(second)
    assert first[compile_cache.DIR_ENV] == os.path.join(REPO, ".jax_cache")


# -- the one peak table ------------------------------------------------------

def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("device,want", [
    (_device("tpu", "TPU v5 lite"), 197e12),
    (_device("cpu", "cpu"), None),
])
def test_peak_flops_known_devices(device, want):
    assert M.peak_flops(device) == want


def test_peak_flops_unknown_accelerator_is_an_error(monkeypatch):
    monkeypatch.setenv("TFOS_PEAK_FLOPS", "1e15")  # no override any more
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        M.peak_flops(_device("tpu", "TPU v9 imaginary"))
    with pytest.raises(ValueError):
        M.peak_flops(_device("gpu", "NVIDIA H100"))


# -- chips of one host as one job --------------------------------------------

@pytest.fixture
def tpu_env(monkeypatch):
    for k in list(os.environ):
        if k.startswith(("TPU_", "CLOUD_TPU")):
            monkeypatch.delenv(k)
    # monkeypatch restores whatever the exports below set
    for k in ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
              "TPU_PROCESS_BOUNDS", "TPU_PROCESS_ADDRESSES",
              "TPU_PROCESS_PORT", "CLOUD_TPU_TASK_ID",
              "ALLOW_MULTIPLE_LIBTPU_LOAD"):
        monkeypatch.setenv(k, "")
    return os.environ


@pytest.mark.parametrize("chips,bounds", [
    ([0], "1,1,1"), ([2, 3], "1,2,1"), ([0, 1, 2, 3], "2,2,1")])
def test_claim_alone_is_a_one_process_job(tpu_env, chips, bounds):
    tpu_info._export_visible(chips)
    assert tpu_env["TPU_VISIBLE_CHIPS"] == ",".join(map(str, chips))
    assert tpu_env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds
    assert tpu_env["TPU_PROCESS_BOUNDS"] == "1,1,1"


def test_four_claims_on_a_2x2_host_form_one_job(tpu_env):
    tpu_info._export_visible([2])
    tpu_info.export_process_group(2, 4)
    assert tpu_env["TPU_PROCESS_BOUNDS"] == "2,2,1"
    assert tpu_env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert tpu_env["CLOUD_TPU_TASK_ID"] == "2"
    assert tpu_env["TPU_PROCESS_ADDRESSES"].split(",")[2] == \
        "localhost:" + tpu_env["TPU_PROCESS_PORT"]
    assert len(tpu_env["TPU_PROCESS_ADDRESSES"].split(",")) == 4


def test_a_split_the_runtime_does_not_know_is_a_clear_error(tpu_env):
    tpu_info._export_visible([0])
    with pytest.raises(RuntimeError, match="cannot form one TPU job"):
        tpu_info.export_process_group(0, 3)
    tpu_info._export_visible([1])
    with pytest.raises(RuntimeError, match="chip order"):
        tpu_info.export_process_group(0, 4)
    with pytest.raises(RuntimeError, match="no TPU layout known"):
        tpu_info._export_visible([0, 1, 2])
