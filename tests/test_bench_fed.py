"""bench.py's fed lane against its own device-resident comparator (CPU
regression gate; on a chip the same fields set the framework against
the host→device link)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_fed_lane_vs_device_resident_regression():
    """Feeder process -> shm ring -> DataFeed -> per-dispatch train must
    reach ~the device-resident comparator's throughput when the link is
    free (measured 0.98 on this image; gate at 0.75 for CI noise), and
    the transfer-ceiling ratio must be recorded."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("TFOS_", "JAX_", "XLA_"))}
    env.update(
        JAX_PLATFORMS="cpu",
        TFOS_BENCH_TRANSFORMER="0", TFOS_BENCH_TFRECORD_READ="0",
        TFOS_BENCH_SEGMENTATION="0", TFOS_BENCH_BATCH_INFERENCE="0",
        TFOS_BENCH_SERVE="0", TFOS_BENCH_ELASTIC_SERVE="0",
        TFOS_BENCH_DEPLOY="0", TFOS_BENCH_DECODE="0",
        TFOS_BENCH_SERVE_FABRIC="0",
        TFOS_BENCH_DATA="0", TFOS_BENCH_ELASTIC="0",
        TFOS_BENCH_ACTORS="0",
        TFOS_BENCH_FED_AB="0",  # one lane is enough for the gate
        # keep the lane's own stall diagnostics reachable BEFORE the
        # subprocess timeout kills the child opaquely
        TFOS_BENCH_FED_DEADLINE="120",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON line in stdout: {proc.stdout!r}"
    line = json.loads(lines[-1])
    # a CPU run has no peak to be a share of: counts, never a utilization
    assert line["value"] is None and line["extra"]["platform"] == "cpu"
    fed = line["extra"]["fed"]
    assert "error" not in fed, fed
    assert not fed.get("deadline_hit"), fed
    assert fed["vs_device_resident"] >= 0.75, fed
    assert fed["vs_transfer_ceiling"] is not None, fed
    assert fed["infeed_stall_frac"] < 0.5, fed
