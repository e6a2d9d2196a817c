"""The ``serve_decode`` runner off the chip: ``--rehearse`` passes at tiny
size (server, HTTP clients, warm-up waves, replica hook, verify child) and
can never say ``tpu``.  One module-scoped subprocess for the runner."""

import pytest
from bench_helpers import KEYS, REPO, bench, last_line, never_says_tpu  # noqa: F401


@pytest.fixture(scope="module")
def serve_run():
    return bench(REPO, "--workload", "pythia-1.4b-serve-c8", "--seed", "7",
                  "--seconds", "3", "--trace", "1", "--rehearse")


def test_serve_decode_rehearsal_passes(serve_run):
    proc, lines = serve_run
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = last_line(lines)
    assert set(last) == KEYS
    assert last["correct"] is True and last["failed"] == 0
    # --trace 1: the per-layer metrics, and only those
    assert {"boot_s", "batch_occupancy", "iter_ms"} <= set(last["metrics"])
    assert "serve_tok_per_s" not in last["metrics"]
    assert 0 < last["metrics"]["batch_occupancy"]["value"] <= 1


def test_serve_decode_rehearsal_verifies_against_the_reference(serve_run):
    _proc, lines = serve_run
    verify = [ln for ln in lines if "verify child" in ln]
    assert verify and "'wrong': 0" in verify[0], verify


def test_serve_decode_rehearsal_can_never_say_tpu(serve_run):
    never_says_tpu(serve_run[1])
