"""The ``train_fed`` runner off the chip: ``--rehearse`` passes at tiny size
and can never say ``tpu``; without ``--rehearse`` a machine with no chip is
a failure with no result line; a cell, a configuration, a traffic mix and
per-layer metrics added as FILES are found without editing any file that
is there.  One module-scoped subprocess for the runner (as
tests/test_chip_smoke.py does)."""

import json
import os
import shutil
import subprocess
import sys

import pytest
from bench_helpers import KEYS, REPO, bench, last_line, never_says_tpu


@pytest.fixture(scope="module")
def train_run():
    return bench(REPO, "--workload", "resnet50-fed", "--seed",
                  str(2**31 + 5), "--seconds", "2", "--trace", "0",
                  "--rehearse")


def test_train_fed_rehearsal_passes(train_run):
    proc, lines = train_run
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = last_line(lines)
    assert set(last) == KEYS and last["rehearsal"] is True
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["metrics"]["setup_s"]["value"] > 0
    # no peak for a CPU: the device metric is left out, never estimated
    assert "train_mfu" not in last["metrics"]


def test_train_fed_rehearsal_checks_ids_and_the_reference(train_run):
    _proc, lines = train_run
    assert any("lost/duplicated 0" in ln for ln in lines), lines
    ref = [ln for ln in lines if "reference:" in ln]
    assert ref and "'ok': True" in ref[0], ref


def test_train_fed_rehearsal_can_never_say_tpu(train_run):
    never_says_tpu(train_run[1])


def test_without_a_chip_the_run_fails_and_prints_no_result():
    proc, lines = bench(REPO, "--workload", "resnet50-fed", "--seed", "1",
                         "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert not lines or not lines[-1].startswith("{")
    # the program's own chip claim refuses first; the runner's check of
    # the platform is the second line of defence
    out = proc.stdout + proc.stderr
    assert "unable to allocate" in out or "no accelerator" in out


def test_unknown_workload_is_an_error():
    proc, _lines = bench(REPO, "--workload", "no-such-cell", "--seed", "1",
                          "--seconds", "1", "--trace", "0", "--rehearse")
    assert proc.returncode != 0


def test_bare_benchmark_directory_fails(tmp_path):
    """Only BENCHMARK.json and the files under paths: no program, no run."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "resnet50-fed",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    out = proc.stdout.strip().splitlines()
    assert not out or not out[-1].startswith("{")


def test_a_cell_added_as_files_is_found_without_editing_any(tmp_path):
    """A later PR adds a configuration, a traffic mix, a per-layer metric
    and a cell by adding files and BENCHMARK.json entries."""
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    cfg = json.loads(
        (tmp_path / "benchmark/configs/resnet50.json").read_text())
    cfg["name"] = "resnet50-wide-batch"
    cfg["rehearse"]["batch_per_chip"] = 4
    (tmp_path / "benchmark/configs/resnet50-wide-batch.json").write_text(
        json.dumps(cfg))
    mix = json.loads(
        (tmp_path / "benchmark/traffic/fed-1x1.json").read_text())
    mix["records_per_partition"] = 256
    mix["provision_records_per_s"] = 400
    (tmp_path / "benchmark/traffic/fed-small-parts.json").write_text(
        json.dumps(mix))
    (tmp_path / "benchmark/layer_metrics/window_steps.py").write_text(
        'def read(facts):\n    return facts.get("window_steps")\n')
    (tmp_path / "benchmark/layer_metrics/never_there.py").write_text(
        'def read(facts):\n    return None\n')
    man["configs"].append({
        "name": "resnet50-wide-batch", "source": "arXiv:1512.03385 again",
        "file": "benchmark/configs/resnet50-wide-batch.json", "reduced": [],
        "why": "added by a test"})
    man["workloads"].append({
        "name": "added-cell", "config": "resnet50-wide-batch",
        "traffic": "fed-small-parts", "chips": 1, "why": "added by a test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m and "resnet50-fed" in m["workloads"]:
            m["workloads"].append("added-cell")
    for name in ("window_steps", "never_there"):
        man["per_layer"].append({
            "name": name, "unit": "steps", "better": "higher",
            "source": "program_counter", "layer": "step",
            "moves": "train_mfu", "workloads": ["added-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    proc, lines = bench(str(tmp_path), "--workload", "added-cell", "--seed",
                         "3", "--seconds", "1", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = last_line(lines)
    assert last["metrics"]["window_steps"]["value"] > 0
    assert last["metrics"]["window_steps"]["unit"] == "steps"
    # a reader that finds nothing to read: its metric is left out
    assert "never_there" not in last["metrics"]
    assert last["correct"] is True
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"
