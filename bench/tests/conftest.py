"""Tests of the benchmark's own code, on the CPU:

    python -m pytest bench/tests

They import the planner (``src``) only to check that the benchmark's
copies and reference agree with it, and to drive the harness at a tiny
size with faults planted under it.
"""
import os
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# compiled programs of the tests stay out of the checkout's cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(tempfile.gettempdir(), "bench_tests_cache"))
