"""A ``--rehearse --trace 1`` run of a training cell prints every
``program_span`` metric the manifest lists for it as a finite number: the
program's ``tfos/feed/*`` spans reach the capture, ``lib/program_trace``
finds the run's raw trace and reads it in a child process, and the
readers reduce it.  (A CPU capture has host spans and no device plane, so
the ``device_trace`` metrics stay silent here.)"""

import math

import pytest
from bench_own_root import own_root  # noqa: F401 - a fixture
from bench_helpers import bench, last_line

from benchmark.lib import manifest as M

CELL = "resnet50-fed"


@pytest.fixture(scope="module")
def traced_run(own_root):
    return bench(own_root, "--workload", CELL, "--seed", str(2**31 + 25),
                  "--seconds", "3", "--trace", "1", "--rehearse")


def test_every_program_span_metric_of_the_cell_is_finite(traced_run):
    proc, lines = traced_run
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    metrics = last_line(lines)["metrics"]
    want = [m["name"] for m in M.metrics_of(M.load(), CELL, "per_layer")
            if m["source"] == "program_span"]
    assert set(want) == {"feed_ring_wait_frac", "feed_host_busy_frac"}
    for name in want:
        assert math.isfinite(metrics[name]["value"]), (name, metrics)
        assert 0.0 <= metrics[name]["value"] <= 1.0


def test_the_reduction_is_announced_on_an_earlier_line(traced_run):
    _proc, lines = traced_run
    said = [ln for ln in lines if ln.startswith("[bench:program_trace]")]
    assert len(said) == 1, said          # read once, shared by the readers
    # which feed spans fall into a rehearsal's short slice varies
    assert "tfos/feed/" in said[0]


def test_an_untraced_run_reads_no_capture(own_root):
    proc, lines = bench(own_root, "--workload", CELL, "--seed", "3",
                         "--seconds", "2", "--trace", "0", "--rehearse")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert not any(ln.startswith("[bench:program_trace]") for ln in lines)
    other = [ln for ln in lines if "per_layer readers" in ln]
    assert other and "feed_ring_wait_frac" not in other[0]
