"""Tests of the benchmark itself (``benchmark/``): its files are found by
path, not installed, so its directory goes on ``sys.path`` the way
``python benchmark/run.py`` puts it there."""

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCHMARK_DIR = os.path.join(REPO_ROOT, "benchmark")

if BENCHMARK_DIR not in sys.path:
    sys.path.insert(0, BENCHMARK_DIR)
