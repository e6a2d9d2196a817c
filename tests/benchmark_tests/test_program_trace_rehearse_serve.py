"""A ``--rehearse --trace 1`` run of the serving cell prints every
``program_span`` metric the manifest lists for it as a finite number:
the engine thread's ``tfos/decode/*`` spans reach the replica's capture,
and ``lib/program_trace`` finds it beside the run's facts file."""

import math

import pytest
from bench_own_root import own_root  # noqa: F401 - a fixture
from bench_helpers import bench, last_line

from benchmark.lib import manifest as M

CELL = "pythia-1.4b-serve-c8"


@pytest.fixture(scope="module")
def traced_run(own_root):
    return bench(own_root, "--workload", CELL, "--seed", "25", "--seconds", "3",
                  "--trace", "1", "--rehearse")


def test_every_program_span_metric_of_the_cell_is_finite(traced_run):
    proc, lines = traced_run
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    metrics = last_line(lines)["metrics"]
    want = [m["name"] for m in M.metrics_of(M.load(), CELL, "per_layer")
            if m["source"] == "program_span"]
    assert set(want) == {"iter_host_ms", "admit_frac"}
    for name in want:
        assert math.isfinite(metrics[name]["value"]), (name, metrics)
    assert metrics["iter_host_ms"]["value"] > 0
    assert 0.0 <= metrics["admit_frac"]["value"] < 1.0


def test_the_engines_phases_are_in_the_capture(traced_run):
    _proc, lines = traced_run
    said = [ln for ln in lines if ln.startswith("[bench:program_trace]")]
    assert len(said) == 1, said
    for name in ("tfos/decode/iterate", "tfos/decode/step_dispatch",
                 "tfos/decode/logits_fetch", "tfos/decode/admit",
                 "tfos/decode/prefill", "tfos/decode/kv_insert"):
        assert name in said[0], (name, said[0])
