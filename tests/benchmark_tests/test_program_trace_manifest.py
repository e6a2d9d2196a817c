"""The manifest contract with PR25's entries: the new cell and the eight
new per-layer metrics are there, each with a reader file and the layer,
source and cells ISSUE 25 names; and what PR23 declared is unchanged but
for names appended to ``workloads`` lists."""

import json
import os
import subprocess

import pytest

from benchmark.lib import manifest as M

MAN = M.load()
NEW = {
    "feed_ring_wait_frac": ("program_span", "feed", "train_mfu",
                            ["resnet50-fed", "pythia-1.4b-train",
                             "resnet50-fed-4x1"]),
    "feed_host_busy_frac": ("program_span", "feed", "train_mfu",
                            ["resnet50-fed", "pythia-1.4b-train",
                             "resnet50-fed-4x1"]),
    "sync_ms": ("program_span", "mesh", "train_mfu", ["resnet50-fed-4x1"]),
    "collective_ms": ("device_trace", "mesh", "train_mfu",
                      ["resnet50-fed-4x1"]),
    "collective_exposed_frac": ("device_trace", "mesh", "train_mfu",
                                ["resnet50-fed-4x1"]),
    "attn_kernel_frac": ("device_trace", "kernels", "train_mfu",
                         ["pythia-1.4b-train"]),
    "iter_host_ms": ("program_span", "serving scheduler", "serve_tok_per_s",
                     ["pythia-1.4b-serve-c8"]),
    "admit_frac": ("program_span", "serving scheduler", "serve_tok_per_s",
                   ["pythia-1.4b-serve-c8"]),
}


def test_the_contract_holds_with_the_new_entries():
    assert M.problems(MAN) == []


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_is_declared_as_the_issue_names_it(name):
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    source, layer, moves, cells = NEW[name]
    assert (entry["source"], entry["layer"], entry["moves"],
            entry["workloads"]) == (source, layer, moves, cells)
    assert entry["better"] == "lower"
    assert os.path.exists(M.reader_path("per_layer", name))


def test_new_entries_come_last_and_old_ones_only_gain_cells():
    """Against the parent commit's manifest where git has one (the
    driver's checkout is no repository: then the order alone is checked)."""
    names = [m["name"] for m in MAN["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    assert MAN["workloads"][-1]["name"] == "resnet50-fed-4x1"
    try:
        old = json.loads(subprocess.run(
            ["git", "show", "057c9e0:BENCHMARK.json"], cwd=M.ROOT,
            capture_output=True, text=True, check=True, timeout=30).stdout)
    except (subprocess.SubprocessError, OSError, ValueError):
        return
    for key in ("command", "paths", "run_seconds", "configs"):
        assert MAN[key] == old[key]
    for group in ("workloads", "end_to_end", "per_layer"):
        assert len(MAN[group]) >= len(old[group])
        for was, now in zip(old[group], MAN[group]):
            grown = dict(now)
            if "workloads" in was:
                assert now["workloads"][:len(was["workloads"])] \
                    == was["workloads"]
                grown["workloads"] = was["workloads"]
            assert grown == was
