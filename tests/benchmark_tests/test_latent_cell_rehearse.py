"""The ``serve_model`` runner and the cell ``sarvam-105b-serve-c16-ctx2k``
off the chip: ``--rehearse`` passes at the configuration's tiny
``rehearse`` sizes (export child, server, HTTP clients, warm-up waves
under the prefill bound, replica hook, replay + reference in the verify
child) and can never say ``tpu``.  One module-scoped subprocess."""

import json

import pytest
from bench_helpers import KEYS, REPO, bench, last_line, never_says_tpu  # noqa: F401

CELL = "sarvam-105b-serve-c16-ctx2k"


@pytest.fixture(scope="module")
def run():
    # 6 s, not 3: on a loaded CPU a wave of the warm-up can split on every
    # attempt, its program then compiles inside the window, and a window
    # that one compile fills sees no request sent and reads ``correct`` false
    return bench(REPO, "--workload", CELL, "--seed", "2147483659",
                 "--seconds", "6", "--trace", "1", "--rehearse", timeout=900)


def test_rehearsal_passes_and_reports_the_cells_metrics(run):
    proc, lines = run
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = last_line(lines)
    assert set(last) == KEYS
    assert last["correct"] is True and last["failed"] == 0
    # --trace 1: the per-layer metrics, and only those.  The two that read
    # the device's plane have nothing to read on the CPU and stay out.
    got = set(last["metrics"])
    assert {"boot_s", "batch_occupancy", "iter_ms", "iter_host_ms",
            "admit_frac", "expert_tokens_mean"} <= got
    assert "serve_tok_per_s" not in got
    assert not {"latent_step_ms", "latent_step_roofline_frac"} & got
    assert last["metrics"]["expert_tokens_mean"]["value"] >= 1


def test_rehearsal_compares_logits_of_the_replayed_programs(run):
    _proc, lines = run
    verify = [ln for ln in lines if "verify child" in ln]
    assert verify, lines[-5:]
    got = json.loads(verify[0].split("verify child: ", 1)[1])
    assert got["ok"] is True and got["refused_by"] == []
    # every served token is the argmax of the replayed programs' logits
    assert got["positions"] > 0 and got["own_exact_share"] == 1.0
    assert got["own_widest_gap"] <= got["limits"]["replay_tie_logit"]
    # float32 at this size: the engine's programs sit on the reference,
    # and one precision lower goes through the same rule and is refused
    assert got["rel_err_p99"] <= got["limits"]["rel_err_p99_max"]
    assert "rel_err_median_max" in got["lower_precision_refused_by"]
    assert got["lower_precision_reads"]["rel_err_median"] \
        > 10 * got["limits"]["rel_err_median_max"]
    # the replay mirrors the row bucket of the prefill the engine ran
    assert all(rows >= 1 for rows in got["prefill_rows"])


def test_rehearsal_warms_every_prefill_under_the_bound(run):
    _proc, lines = run
    warm = [ln for ln in lines if "warm-up" in ln and "prefill_programs" in ln]
    assert warm, lines[-5:]
    programs = json.loads(
        warm[0].split("'prefill_programs': ", 1)[1].split(", 'resent")[0])
    # rehearse: prompts 8..96 -> buckets 8..128, bound 256 tokens, 16 slots
    assert [8, 16] in programs and [128, 2] in programs
    assert all(t * rows <= 256 for t, rows in programs)


def test_rehearsal_can_never_say_tpu(run):
    never_says_tpu(run[1])
