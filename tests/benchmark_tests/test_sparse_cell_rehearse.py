"""The cell ``glm-5.3-flash-serve-c16-ctx8k`` off the chip: ``--rehearse
--trace 1`` passes at the configuration's tiny ``rehearse`` sizes (export
child, server, 16 HTTP clients, one-row warm-up waves, replica hook and
capture, replay + reference in the verify child: the runner
``serve_model`` as it is), reads the selection's share from the capture,
and can never say ``tpu``.  One module-scoped subprocess with a time limit
of its own: a runner that waits on a dead blocker ends here, not in the
suite's."""

import json

import pytest
from bench_helpers import KEYS, REPO, bench, last_line, never_says_tpu  # noqa: F401

CELL = "glm-5.3-flash-serve-c16-ctx8k"


@pytest.fixture(scope="module")
def run():
    return bench(REPO, "--workload", CELL, "--seed", "2147483901",
                 "--seconds", "8", "--trace", "1", "--rehearse",
                 timeout=600)


def test_rehearsal_passes_and_reads_the_selections_share(run):
    proc, lines = run
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = last_line(lines)
    assert set(last) == KEYS
    assert last["correct"] is True and last["failed"] == 0
    got = last["metrics"]
    assert {"boot_s", "batch_occupancy", "iter_ms", "iter_host_ms",
            "admit_frac", "expert_tokens_mean", "dsa_selected_frac"} \
        <= set(got)
    # prompts of 20-64 tokens against 16 positions read: well under all
    assert 0 < got["dsa_selected_frac"]["value"] < 0.8
    # no device plane on the CPU: the step's device time is not there
    assert not {"sparse_step_roofline_frac", "latent_step_ms",
                "prefill_ms_per_ktok"} & set(got)


def test_rehearsal_compares_logits_of_the_replayed_programs(run):
    _proc, lines = run
    verify = [ln for ln in lines if "verify child" in ln]
    assert verify, lines[-5:]
    got = json.loads(verify[0].split("verify child: ", 1)[1])
    assert got["ok"] is True and got["refused_by"] == []
    assert got["positions"] > 0 and got["own_exact_share"] == 1.0
    assert "rel_err_median_max" in got["lower_precision_refused_by"]


def test_rehearsal_warms_one_row_a_prefill(run):
    _proc, lines = run
    warm = [ln for ln in lines if "warm-up" in ln and "prefill_programs" in ln]
    assert warm, lines[-5:]
    programs = json.loads(
        warm[0].split("'prefill_programs': ", 1)[1].split(", 'resent")[0])
    assert sorted(programs) == [[32, 1], [64, 1]]
    window = [ln for ln in lines if "; cache {" in ln]
    assert "'index_row_bytes': 64" in window[0]


def test_rehearsal_can_never_say_tpu(run):
    never_says_tpu(run[1])
