"""The harness's own CPU checks: ``JAX_PLATFORMS=cpu python3 -m pytest benchmark/checks``.
Not part of the repo's tier-1 tests."""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
