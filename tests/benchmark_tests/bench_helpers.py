"""Shared by the rehearsal tests: run benchmark/run.py as a subprocess."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device", "rehearsal"}


def bench(root, *argv, timeout=420):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("TFOS_", "BENCH_"))}
    # the program comes from the repo even when the benchmark is a copy
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, BENCH_RUN="ignored")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *argv],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    return proc, proc.stdout.strip().splitlines()


def last_line(lines):
    return json.loads(lines[-1])


def never_says_tpu(lines):
    assert lines and "REHEARSAL" in lines[0]
    assert not any('"platform": "tpu"' in ln or "'platform': 'tpu'" in ln
                   for ln in lines)
    assert last_line(lines)["device"]["platform"] == "cpu"
