"""The four-executor cell off the chip: ``resnet50-fed-4x1`` rehearsed on
four CPU processes trains ONE job (every record id once, the four
processes agree on losses and step count, the float32 reference agrees on
the gathered global batch).  (Its traced run reads the mesh layer's span,
``tfos/feed/sync``; that reader is checked on the hand-made trace and on
the chip: a second four-process run here would only add load.)"""

import pytest
from bench_own_root import own_root  # noqa: F401 - a fixture
from bench_helpers import KEYS, bench, last_line, never_says_tpu

from benchmark.lib import manifest as M

CELL = "resnet50-fed-4x1"


@pytest.fixture(scope="module")
def four_run(own_root):
    return bench(own_root, "--workload", CELL, "--seed", str(2**31 + 41),
                  "--seconds", "2", "--trace", "0", "--rehearse",
                  timeout=600)


def test_the_cell_is_the_manifests_one_four_chip_cell():
    man = M.load()
    cell = M.cell(man, CELL)
    assert cell["workload"]["chips"] == 4
    assert [w["name"] for w in man["workloads"] if w["chips"] == 4] == [CELL]
    assert cell["mix"]["executors"] == 4
    assert cell["mix"]["chips_per_process"] == 1
    one = M.cell(man, "resnet50-fed")["mix"]
    differs = {k for k in one if one[k] != cell["mix"][k]}
    assert differs == {"what", "executors", "provision_records_per_s",
                       "provision_why"}
    assert cell["mix"]["provision_records_per_s"] \
        == 4 * one["provision_records_per_s"]


def test_four_processes_train_one_job(four_run):
    proc, lines = four_run
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = last_line(lines)
    assert set(last) == KEYS
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == 4
    assert any("lost/duplicated 0" in ln for ln in lines)
    refs = [ln for ln in lines if "reference:" in ln]
    assert len(refs) == 4 and all("'ok': True" in ln for ln in refs)
    assert not any("disagree" in ln for ln in lines)
    never_says_tpu(lines)
