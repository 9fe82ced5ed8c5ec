"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.

They live with the benchmark and are not part of the repository's tier-1
run.  Everything here runs on the CPU at rehearsal sizes; no number they
produce is a measurement.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)
