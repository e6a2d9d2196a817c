"""The cell ``kimi-linear-48b-serve-c64-out512`` off the chip:
``--rehearse`` passes at the configuration's tiny ``rehearse`` sizes
(export child, server, 16 HTTP clients, warm-up waves under the prefill
bound, replica hook, replay + reference in the verify child: the runner
``serve_model`` as it is) and can never say ``tpu``.  One module-scoped
subprocess with a time limit of its own."""

import json

import pytest
from bench_helpers import KEYS, REPO, bench, last_line, never_says_tpu  # noqa: F401

CELL = "kimi-linear-48b-serve-c64-out512"


@pytest.fixture(scope="module")
def run():
    # 12 s: on a loaded CPU a wave of the warm-up can split on every
    # attempt, its program then compiles inside the window, and a window
    # that compiles fill sees no request sent.  The limit is the test's
    # own: a runner that waits on a dead blocker ends here, not in the
    # suite's
    return bench(REPO, "--workload", CELL, "--seed", "2147483693",
                 "--seconds", "12", "--trace", "1", "--rehearse",
                 timeout=840)


def test_rehearsal_passes_and_reports_the_cells_metrics(run):
    proc, lines = run
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = last_line(lines)
    assert set(last) == KEYS
    assert last["correct"] is True and last["failed"] == 0
    # --trace 1: the per-layer metrics, and only those.  The three that read
    # the device's plane have nothing to read on the CPU and stay out.
    got = set(last["metrics"])
    assert {"boot_s", "batch_occupancy", "iter_ms", "iter_host_ms",
            "admit_frac", "expert_tokens_mean"} <= got
    assert "serve_tok_per_s" not in got
    assert not {"latent_step_ms", "hybrid_step_roofline_frac",
                "prefill_ms_per_ktok"} & got


def test_rehearsal_compares_logits_of_the_replayed_programs(run):
    _proc, lines = run
    verify = [ln for ln in lines if "verify child" in ln]
    assert verify, lines[-5:]
    got = json.loads(verify[0].split("verify child: ", 1)[1])
    assert got["ok"] is True and got["refused_by"] == []
    # every served token is the argmax of the replayed programs' logits
    assert got["positions"] > 0 and got["own_exact_share"] == 1.0
    # float32 at this size: the engine's programs sit on the reference,
    # and one precision lower (weights, rows and state) goes through the
    # same rule and is refused
    assert got["rel_err_p99"] <= got["limits"]["rel_err_p99_max"]
    assert "rel_err_median_max" in got["lower_precision_refused_by"]
    assert got["lower_precision_reads"]["rel_err_median"] \
        > 10 * got["limits"]["rel_err_median_max"]


def test_rehearsal_counts_the_state_beside_the_rows(run):
    _proc, lines = run
    window = [ln for ln in lines if "; cache {" in ln]
    assert window, lines[-5:]
    cache = window[0].split("; cache ", 1)[1]
    # 2 latent layers x 40 x 4 B a token; 6 KDA layers x (4 x 16 x 16 +
    # 3 x 192) x 4 B a session, 16 slots in a rehearsal
    assert "'row_bytes': 320" in cache
    assert "'state_row_bytes': 38400" in cache
    assert "'state_bytes': 614400" in cache
    assert "'state_session_steps': " in cache
    assert "prefix hits 0" in "".join(lines)


def test_rehearsal_warms_every_prefill_under_the_bound(run):
    _proc, lines = run
    warm = [ln for ln in lines if "warm-up" in ln and "prefill_programs" in ln]
    assert warm, lines[-5:]
    programs = json.loads(
        warm[0].split("'prefill_programs': ", 1)[1].split(", 'resent")[0])
    # rehearse: prompts 8..32 -> buckets 8, 16, 32, bound 32 tokens: the
    # same ladder of buckets x rows as the cell's own, at a fourth of its
    # width
    assert sorted(programs) == [[8, 1], [8, 2], [8, 4], [16, 1], [16, 2],
                                [32, 1]]


def test_rehearsal_can_never_say_tpu(run):
    never_says_tpu(run[1])
