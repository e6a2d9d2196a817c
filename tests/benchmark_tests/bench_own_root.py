"""A fixture for rehearsal tests that must not share a work directory:
runs of one cell share ``<root>/.bench_work/<cell>/``, and several test
files rehearse the same cells at the same time (pytest-xdist)."""

import os
import shutil

import pytest
from bench_helpers import REPO


@pytest.fixture(scope="module")
def own_root(tmp_path_factory):
    """The benchmark's files in a directory of the test module's own; the
    program still comes from the repo (``bench_helpers.bench``)."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(root)
